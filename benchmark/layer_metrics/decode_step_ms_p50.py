"""Device time from the first to the last op of one decode dispatch: the
ops that started inside the benchmark's span around the call into the step
(``inference.step`` / ``serve.step``). The median step; steps that held an
admission prefill are the slow tail."""

from benchmark.harness import reduce_trace
from benchmark.harness.runtime import median

LAYER = "device step"
UNIT = "ms"
MOVES = "decode_ms_per_token"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    return median([s["device_s"] * 1e3
                   for s in reduce_trace.steps(run.trace)])
