"""``nemo_ssd_device_time_share`` for the attention layers (the pre-norm,
``wqkv``, the page writes, the paged kernel or a chunk's attention, ``wo``,
the residual add)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ssd = load_reader("layer_metrics", "nemo_ssd_device_time_share")


def read(run):
    return _ssd.part_share(run, "full")
