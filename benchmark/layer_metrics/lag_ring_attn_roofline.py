"""The sliding layers' decode attention kernel's share of the HBM roofline:
the bytes of window ring a decode step must read ONCE (8,192 B of K and V a
ring slot a row reads, min(position + 1, 512) of them, in each of the twelve
sliding layers: ``harness/laguna.ring_step_bytes`` over the program's
``window_kv_positions`` counter a step, across the TRACED seconds: the
driver reads the counters where the profiler starts and stops) over the
device time of the ``hm_attn_rows_decode`` calls in the median decode step
of the traced window, over 819 GB/s. Bandwidth bounds it (two operations a byte at
a group of eight heads). It cannot pass 100 % unless the kernel skips a
slot. None for a program or a trace without the kernel or the counter."""

from benchmark.harness import laguna, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def step_seconds(run, what: str):
    """Median seconds of ``what`` in a traced decode step, or None."""
    if run.trace is None:
        return None
    secs = [s[what] for s in laguna.step_kernel_seconds(run.trace)
            if s[what] > 0]
    return median(secs) if secs else None


def a_step(run, counter: str):
    """The program's ``counter`` a decode step, across the traced seconds
    (``drivers/serve_laguna.Served.window`` hands on ``trace_<counter>``)."""
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
    positions = a_step(run, "window_kv_positions")
    if not positions:
        return None
    return share(run, laguna.ring_step_bytes(
        laguna.sizes_of(run.cell.config), positions),
        step_seconds(run, "ring"))
