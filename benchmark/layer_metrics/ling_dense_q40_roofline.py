"""The dense Q40 leaves' share of the HBM roofline in this configuration's
decode step: the packed bytes of every leaf a step reads whole whatever it
routes (a KDA layer's ``in_qkvag`` and ``wo``, a latent layer's ``wq``,
``wkv_a`` and ``wo``, the leading layer's dense FFN, an expert layer's shared
expert, the classifier over an eighth of the vocabulary:
``harness/ling.dense_q40_bytes``) over the device time of the Q40 calls that
are not expert kernels in the median decode step of the traced window, over
819 GB/s. None where the trace holds no decode step of this model."""

from benchmark.harness import ling
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_state = load_reader("layer_metrics", "ling_kda_state_roofline")


def read(run):
    return _state.share(run, ling.dense_q40_bytes(
        ling.sizes_of(run.cell.config)), _state.step_seconds(run, "dense"))
