"""The full layers' paged latent decode kernel's share of its roofline at 80
absorbed query heads over ONE plane (``mla_paged_attn_decode``): the larger
of the PUBLISHED bytes of plane a decode step must read ONCE (2,304 B a
cached position a row reads, position + 1 of them, in each of the three
full layers' pools) over 819 GB/s and of its operations (80 heads x (576 +
512) x 2 a position) over the MXU's peak at the six bf16 passes a float32
product at HIGHEST takes, from the depths the program counted
(``shared_kv_positions`` a step, across the TRACED seconds), over the device
time of the kernel's calls in the median decode step of the traced window.
None for a program or a trace without the kernel or the counter."""

from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "motif_ring_attn_roofline")


def read(run):
    return _ring.attn_share(run, "full", "shared_kv_positions", "full")
