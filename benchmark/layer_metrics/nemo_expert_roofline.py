"""The routed-expert decode kernel's share of the HBM roofline where a chip
holds 64 of a layer's 128 non-gated experts of width 1,856: the packed Q40
bytes of the DISTINCT held experts a decode step routed to (the program's
``moe_active`` counter over its steps, across the TRACED seconds, times one
expert's two leaves AS READ, at the padded width of 2,048 and 88 blocks a
row: ``harness/nemotron.expert_bytes``, each read once by the slot kernel's
two calls a layer) over the device time of the ``moe_q40_slots`` calls in the
median decode step of the traced window, over 819 GB/s. It cannot pass 100 %
unless the kernel skips an expert it was routed to. None for a program that
counts no experts, or a trace without the state kernel's decode steps."""

from benchmark.harness import nemotron
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_state = load_reader("layer_metrics", "nemo_ssd_state_roofline")


def read(run):
    active = _state.a_step(run, "moe_active")
    if not active:
        return None
    return _state.share(run, active * nemotron.expert_bytes(
        nemotron.sizes_of(run.cell.config)),
        _state.step_seconds(run, "slots"))
