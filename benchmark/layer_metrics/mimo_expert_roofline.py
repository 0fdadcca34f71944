"""The routed-expert decode kernel's share of the HBM roofline where a chip
holds 32 of a layer's 256 experts of width 2,048 under a mixer-kinds spec:
the packed Q40 bytes of the DISTINCT held experts a decode step routed to
(the program's ``moe_active`` counter over its steps, across the TRACED
seconds, times one expert's three leaves: ``harness/mimo.expert_bytes``,
14,155,776 B, each read once) over the device time of the ``moe_q40_slots``
calls in the median decode step of the traced window, over 819 GB/s. It
cannot pass 100 % unless the kernel skips an expert it was routed to. None
for a program that counts no experts, or a trace without the ring kernel's
decode steps."""

from benchmark.harness import mimo
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "lag_ring_attn_roofline")


def read(run):
    active = _ring.a_step(run, "moe_active")
    if not active:
        return None
    return _ring.share(run, active * mimo.expert_bytes(
        mimo.sizes_of(run.cell.config)), _ring.step_seconds(run, "slots"))
