"""``motif_sliding_device_time_share`` for the ten expert sub-blocks: from
the op after a layer's ``wo`` to the next layer's first attention leaf (the
streams' mixes there, FFN norm, router, top-k, slot building, the held
experts' kernels with PolyNorm between them, the shared expert, combine)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "motif_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "moe")
