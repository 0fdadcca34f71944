"""The dense Q40 leaves' share of the HBM roofline in this configuration's
decode step: the packed bytes of every leaf a step reads whole whatever it
routes (a Mamba-2 layer's ``in_zx`` and ``out_proj``, an attention layer's
``wqkv`` and ``wo``, an expert layer's shared expert, the classifier over
half the vocabulary: ``harness/nemotron.dense_q40_bytes``, at the published
widths: the zero blocks a leaf of 84 or 116 blocks a row is padded with are
read too and not counted, so the share reads up to a twentieth low) over the
device time of the Q40 calls that are not expert kernels in the median decode
step of the traced window, over 819 GB/s. None where the trace holds no
decode step of this model."""

from benchmark.harness import nemotron
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_state = load_reader("layer_metrics", "nemo_ssd_state_roofline")


def read(run):
    return _state.share(run, nemotron.dense_q40_bytes(
        nemotron.sizes_of(run.cell.config)),
        _state.step_seconds(run, "dense"))
