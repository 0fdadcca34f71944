"""The dense Q40 kernels' share of the HBM roofline in an expert model's
decode step: the packed bytes of ``wq`` / ``wk`` / ``wv`` / ``wo`` of every
layer and the classifier (``harness/olmoe.dense_q40_bytes``) over the device
time of the Q40 calls that are NOT expert kernels in the median decode step,
over 819 GB/s. They are the kernels the dense cells run, at this model's
shapes (block count 64), so a change tuned for another model's shows here.
None where the trace holds no expert kernel."""

from benchmark.harness import olmoe, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    secs = [dense for _, dense in olmoe.decode_step_kernel_seconds(run.trace)
            if dense > 0]
    if not secs:
        return None
    nbytes = olmoe.dense_q40_bytes(olmoe.sizes_of(run.cell.config))
    peak = peaks.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * nbytes / median(secs) / peak
