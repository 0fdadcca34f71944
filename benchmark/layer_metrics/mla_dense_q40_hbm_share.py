"""The dense Q40 kernels' share of the HBM roofline in a latent-attention
expert model's decode step: the packed bytes of the leaves every step reads
whole (``wq_a`` / ``wq_b`` / ``wkv_a`` / ``wo`` of every layer, the leading
layers' dense FFN, the shared experts, the classifier:
``harness/latent.dense_q40_bytes``) over the device time of the Q40 calls
that are NOT expert kernels in the median decode step, over 819 GB/s. They
are the kernels the dense cells run, at this model's shapes (block counts
224, 48, 512, 576, 64), so a change tuned for another model's shows here.
None where the trace holds no latent decode kernel."""

from benchmark.harness import latent, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    secs = [s["dense"] for s in latent.step_kernel_seconds(run.trace)
            if s["dense"] > 0]
    if not secs:
        return None
    nbytes = latent.dense_q40_bytes(latent.sizes_of(run.cell.config))
    return 100.0 * nbytes / median(secs) / peaks.peak(run.device["kind"],
                                                      "hbm_bytes_per_s")
