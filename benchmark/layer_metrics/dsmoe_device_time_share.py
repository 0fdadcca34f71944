"""How much of the device's busy time the expert sub-block of a
latent-attention expert model takes: FFN norm, router (sigmoid, groups,
top-k), slot building, the routed experts held here, the shared expert,
combine and residual, found by position between a layer's ``wo`` and the
next layer's first attention leaf (``harness/latent.block_seconds``), over
the union of op intervals, on the first device, over the whole traced
window (decode steps and admission chunks). None where the trace holds no
forward of such a model."""

from benchmark.harness import cells

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return cells.load_reader("layer_metrics", "mla_device_time_share").read(
        run, block="moe")
