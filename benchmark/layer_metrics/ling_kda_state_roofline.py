"""The delta-rule decode kernel's share of the HBM roofline: the bytes the
``kda_decode_step`` calls of a decode step move (a row's whole state (32,
128, 128) float32 read once and written once, its (128, 128) block of
key-channel columns, its v and b (k . q) rows and its output of that shape:
``harness/ling.state_call_bytes`` at the 32 rows of the slots, which the
kernel walks whoever rides, in every KDA layer) over the device time of those
calls in the median decode step of the traced window, over 819 GB/s.
Bandwidth bounds it (seven vector operations an element, the contraction
over sublanes). It cannot pass 100 % unless the kernel skips a row. None for
a program or a trace without the kernel."""

from benchmark.harness import ling, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def step_seconds(run, what: str):
    """Median seconds of ``what`` in a traced decode step, or None."""
    if run.trace is None:
        return None
    secs = [s[what] for s in ling.step_kernel_seconds(run.trace)
            if s[what] > 0]
    return median(secs) if secs else None


def a_step(run, counter: str):
    """The program's ``counter`` a decode step, across the traced seconds
    (``drivers/serve_nemotron.Served.window`` hands on ``trace_<counter>``)."""
    if "trace_steps" not in run.counters_after:
        return None
    steps = run.delta("trace_steps")
    return run.delta("trace_" + counter) / steps if steps else None


def share(run, nbytes, secs):
    if not nbytes or not secs:
        return None
    return 100.0 * nbytes / secs / peaks.peak(run.device["kind"],
                                              "hbm_bytes_per_s")


def read(run):
    rows = int(run.cell.config["entries"]["serve"]["slots"])
    return share(run, ling.state_step_bytes(
        ling.sizes_of(run.cell.config), rows), step_seconds(run, "state"))
