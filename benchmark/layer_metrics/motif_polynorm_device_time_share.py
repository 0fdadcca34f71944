"""``motif_sliding_device_time_share`` for PolyNorm: the ops of a decode
step under the program's ``ffn.polynorm`` scope (the three powers, their
means over the FFN's own width, the clamped bias, in the dense FFNs, the
shared experts and between the expert kernel's two calls), told BY IDENTITY
from the step's compiled text (``harness/motif.scoped_instructions``). None
without the text."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "motif_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "polynorm")
