"""The dense Q40 leaves' share of the HBM roofline in this configuration's
decode step: the packed bytes of every leaf a step reads whole whatever it
routes (each layer's ``wq_a``, ``wq_b``, ``wkv_a`` with the gate's ``wg``
behind it and ``wo``; the two leading layers' dense FFN of 12,288; the
expert layers' shared expert; the classifier over an eighth of the
vocabulary: ``harness/motif.dense_q40_bytes``) over the device time of the
Q40 calls that are not expert kernels in the median decode step of the
traced window, over 819 GB/s. None where the trace holds no decode step of
this model."""

from benchmark.harness import motif
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_lag = load_reader("layer_metrics", "lag_ring_attn_roofline")
_ring = load_reader("layer_metrics", "motif_ring_attn_roofline")


def read(run):
    return _lag.share(run, motif.dense_q40_bytes(
        motif.sizes_of(run.cell.config)), _ring.step_seconds(run, "dense"))
