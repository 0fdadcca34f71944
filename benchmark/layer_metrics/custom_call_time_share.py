"""Pallas custom-call time (Q40 matmuls and attention) over device busy
time, on the busiest device. Drops if a shape falls to the
dequantize-then-dot route."""

from benchmark.harness import reduce_trace

LAYER = "kernels"
UNIT = "%"
MOVES = "decode_ms_per_token"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    per_dev = reduce_trace.time_by_class(run.trace)
    best = None
    for dev in per_dev.values():
        total = sum(dev["by_class"].values())
        if total > 0 and (best is None or total > best[0]):
            custom = sum(dev["by_class"].get(c, 0.0)
                         for c in ("q40", "attention", "custom"))
            best = (total, 100.0 * custom / total)
    return best[1] if best else None
