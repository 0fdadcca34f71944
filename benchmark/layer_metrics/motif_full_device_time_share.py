"""``motif_sliding_device_time_share`` for the three full layers' attention
(the low-rank projections, RoPE, the page writes, the paged latent kernel,
the differential fold, the gate, ``wo``)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "motif_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "full")
