"""The dense Q40 leaves' share of the HBM roofline in a mixer-kinds expert
model's decode step: the packed bytes of every leaf a step reads whole
whatever it routes (each layer's ``wqkv`` and ``wo`` at its kind's head
count, layer 0's dense FFN, the expert layers' shared expert, the
classifier: ``harness/laguna.dense_q40_bytes``, 0.49 GB) over the device time
of the Q40 calls that are not expert kernels in the median decode step of
the traced window, over 819 GB/s. None where the trace holds no decode step
of this model."""

from benchmark.harness import laguna
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "lag_ring_attn_roofline")


def read(run):
    return _ring.share(run, laguna.dense_q40_bytes(
        laguna.sizes_of(run.cell.config)), _ring.step_seconds(run, "dense"))
