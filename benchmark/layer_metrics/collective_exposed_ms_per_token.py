"""Time in collective ops during which no compute op runs on that device,
inside decode steps, per decoded token (one ``inference.step`` span is one
token), on the device where it is largest."""

from benchmark.harness import reduce_trace

LAYER = "collectives"
UNIT = "ms/token"
MOVES = "decode_ms_per_token"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    return reduce_trace.collective_exposed_ms_per_step(run.trace)
