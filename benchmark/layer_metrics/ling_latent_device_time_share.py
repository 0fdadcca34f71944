"""``ling_kda_device_time_share`` for the latent mixers (the pre-norm,
``wq`` and ``wkv_a``, the RoPE and the absorbed products, the page writes,
the paged kernel or a chunk's attention walk, the head-wise gate, ``wo``)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_kda = load_reader("layer_metrics", "ling_kda_device_time_share")


def read(run):
    return _kda.part_share(run, "full")
