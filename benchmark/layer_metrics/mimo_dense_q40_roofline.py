"""The dense Q40 leaves' share of the HBM roofline in this configuration's
decode step: the packed bytes of every leaf a step reads whole whatever it
routes (each layer's ``wqkv`` and ``wo`` at its kind's KV head count, layer
0's dense FFN of 16,384, the classifier over an eighth of the vocabulary:
``harness/mimo.dense_q40_bytes``, 0.79 GB) over the device time of the Q40
calls that are not expert kernels in the median decode step of the traced
window, over 819 GB/s. None where the trace holds no decode step of this
model."""

from benchmark.harness import mimo
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "lag_ring_attn_roofline")


def read(run):
    return _ring.share(run, mimo.dense_q40_bytes(
        mimo.sizes_of(run.cell.config)), _ring.step_seconds(run, "dense"))
