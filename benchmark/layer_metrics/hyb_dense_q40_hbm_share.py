"""The dense Q40 kernels' share of the HBM roofline in a hybrid model's decode
step: the packed bytes of every matmul leaf a step reads whole (each layer's
mixer and FFN leaves and the classifier: ``harness/hybrid.dense_q40_bytes``)
over the device time of the Q40 calls in the median decode step, over 819
GB/s. They are the kernels the dense cells run, at this model's shapes (block
counts 80, 160 and 320), so a change tuned for another model's shows here.
None where the trace holds no Mamba decode kernel."""

from benchmark.harness import hybrid, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def _step(run, what: str):
    """(sizes, median seconds of ``what`` in a traced decode step) or None."""
    if run.trace is None:
        return None
    secs = [s[what] for s in hybrid.step_kernel_seconds(run.trace)
            if s[what] > 0]
    return (hybrid.sizes_of(run.cell.config), median(secs)) if secs else None


def read(run):
    got = _step(run, "dense")
    if got is None:
        return None
    sizes, secs = got
    return (100.0 * hybrid.dense_q40_bytes(sizes) / secs
            / peaks.peak(run.device["kind"], "hbm_bytes_per_s"))
