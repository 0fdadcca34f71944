"""``mimo_sliding_device_time_share`` for the eleven expert sub-blocks: from
the op after a layer's ``wo`` to the next layer's first dense call (FFN
norm, router, choice bias, top-k, slot building, the held experts' kernels,
combine, residual)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "mimo_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "moe")
