"""The Mamba decode kernel's share of the HBM roofline: the bytes of state one
decode step must move (every slot's (16, 5120) float32 state of every Mamba
layer read once and written once: ``harness/hybrid.ssm_step_bytes``) over the
device time of the ``mamba_decode_step`` calls in the median decode step of
the traced window, over the chip's published 819 GB/s. Bandwidth bounds it:
about ten operations an element on 8 bytes moved. It counts every slot's row,
as the kernel moves every row, taking part or not. None for a program or a
trace without the kernel."""

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
    got = _step(run, "ssm")
    if got is None:
        return None
    sizes, secs = got
    nbytes = hybrid.ssm_step_bytes(
        sizes, int(run.cell.config["entries"]["serve"]["slots"]))
    return 100.0 * nbytes / secs / peaks.peak(run.device["kind"],
                                              "hbm_bytes_per_s")
