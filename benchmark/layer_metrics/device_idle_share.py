"""1 - the union of device-op intervals over the traced window, per device,
the most idle device reported (``harness/reduce_trace.py``)."""

from benchmark.harness import reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "decode_ms_per_token"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return reduce_trace.idle_share(run.trace)
