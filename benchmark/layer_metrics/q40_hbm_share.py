"""The Q40 matmul kernels' share of the HBM roofline in a decode step: the
packed weight bytes one step must read on one chip (from shapes,
``harness/costs.py``) over the summed device time of the Q40 matmul Pallas
calls in the median step, over the chip's published 819 GB/s. Bandwidth
bounds it: a decode step's matmuls do 2 operations a weight."""

from benchmark.harness import costs, model, peaks, reduce_trace
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "decode_ms_per_token"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    secs = [s for s in reduce_trace.class_seconds_per_step(run.trace, "q40")
            if s > 0]
    if not secs:
        return None
    nbytes = costs.decode_step_bytes(model.sizes_of(run.cell.config),
                                     chips=run.cell.chips)
    peak = peaks.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * nbytes / median(secs) / peak
