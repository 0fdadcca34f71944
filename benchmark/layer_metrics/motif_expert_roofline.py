"""The routed-expert decode kernel's share of the HBM roofline where a chip
holds 48 of a layer's 384 experts of width 1,280 and PolyNorm runs between
the kernel's two calls: the packed Q40 bytes of the DISTINCT held experts a
decode step routed to (the program's ``moe_active`` counter over its steps,
across the TRACED seconds, times one expert's three leaves:
``harness/motif.expert_bytes``, 8,847,360 B, each read once) over the device
time of the ``moe_q40_slots`` calls in the median decode step of the traced
window, over 819 GB/s. None for a program that counts no experts, or a
trace without the ring kernel's decode steps."""

from benchmark.harness import motif
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_lag = load_reader("layer_metrics", "lag_ring_attn_roofline")
_ring = load_reader("layer_metrics", "motif_ring_attn_roofline")


def read(run):
    active = _lag.a_step(run, "moe_active")
    if not active:
        return None
    return _lag.share(run, active * motif.expert_bytes(
        motif.sizes_of(run.cell.config)), _ring.step_seconds(run, "slots"))
