"""The full layers' paged decode attention kernel's share of the HBM roofline
at 4 KV heads of K 192 / V 128 and groups of 16 query heads: the PUBLISHED
bytes of K and V a decode step must read ONCE (5,120 B a cached position a
row reads, position + 1 of them, in each of the three full layers' pools:
``harness/mimo.full_step_bytes`` over the program's ``shared_kv_positions``
counter a step, across the TRACED seconds) over the device time of the
``hm_attn_paged_decode`` calls in the median decode step of the traced
window, over 819 GB/s. The pool holds K in 256 lanes (6,144 B a position):
five sixths is the most this share can read. None for a program or a trace
without the kernel or the counter."""

from benchmark.harness import mimo
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "lag_ring_attn_roofline")


def read(run):
    positions = _ring.a_step(run, "shared_kv_positions")
    if not positions:
        return None
    return _ring.share(run, mimo.full_step_bytes(
        mimo.sizes_of(run.cell.config), positions),
        _ring.step_seconds(run, "paged"))
