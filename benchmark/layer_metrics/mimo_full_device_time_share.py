"""``mimo_sliding_device_time_share`` for the three full layers' mixers
(``wqkv``, the kind's RoPE, the page writes, the paged kernel, the output's
scale, ``wo``)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "mimo_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "full")
