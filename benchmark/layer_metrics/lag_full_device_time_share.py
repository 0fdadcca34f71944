"""``lag_sliding_device_time_share`` for the four full layers' mixers
(``wqkv``, the partial YaRN RoPE, the page writes, the paged kernel or a
chunk's walk over its live prefix, the per-head gate, ``wo``)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "lag_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "full")
