"""The routed-expert decode kernel's share of the HBM roofline where a chip
holds ONE routing group (64) of a layer's 512 experts of width 768: the
packed Q40 bytes of the DISTINCT held experts a decode step routed to (the
program's ``moe_active`` counter over its steps, across the TRACED seconds,
times one expert's three tensors: ``harness/ling.expert_bytes``, each read
once by the slot kernel's two calls a layer) over the device time of the
``moe_q40_slots`` calls in the median decode step of the traced window, over
819 GB/s. It cannot pass 100 % unless the kernel skips an expert it was
routed to. None for a program that counts no experts, or a trace without the
state kernel's decode steps."""

from benchmark.harness import ling
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_state = load_reader("layer_metrics", "ling_kda_state_roofline")


def read(run):
    active = _state.a_step(run, "moe_active")
    if not active:
        return None
    return _state.share(run, active * ling.expert_bytes(
        ling.sizes_of(run.cell.config)), _state.step_seconds(run, "slots"))
