"""The attention layers' paged decode kernel's share of the HBM roofline at
2 KV heads of 128 and groups of 16 query heads: the bytes of K and V a decode
step must read ONCE (2,048 B a cached position a row reads, position + 1 of
them, in each attention layer's pool: ``harness/nemotron.full_step_bytes``
over the program's ``shared_kv_positions`` counter a step, across the TRACED
seconds, so at the trace's mean depth) over the device time of the
``hm_attn_paged_decode`` calls in the median decode step of the traced
window, over 819 GB/s. None for a program or a trace without the kernel or
the counter."""

from benchmark.harness import nemotron
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_state = load_reader("layer_metrics", "nemo_ssd_state_roofline")


def read(run):
    positions = _state.a_step(run, "shared_kv_positions")
    if not positions:
        return None
    return _state.share(run, nemotron.full_step_bytes(
        nemotron.sizes_of(run.cell.config), positions),
        _state.step_seconds(run, "paged"))
