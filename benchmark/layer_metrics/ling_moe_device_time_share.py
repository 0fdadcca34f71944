"""``ling_kda_device_time_share`` for the FFNs (the norm, the router and its
grouped top-k, slot building, the routed experts' kernels, the shared expert,
the combine and the residual; the leading layer's dense SwiGLU is among
them)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_kda = load_reader("layer_metrics", "ling_kda_device_time_share")


def read(run):
    return _kda.part_share(run, "moe")
