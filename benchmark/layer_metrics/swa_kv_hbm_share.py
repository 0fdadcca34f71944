"""The window layers' decode attention kernel's share of the HBM roofline: the
bytes of window ring a decode step must read ONCE (10,240 B of K and V a ring
slot a row reads, min(position + 1, 512) of them, in each of the eight
window layers: ``harness/hybrid.window_step_bytes`` over the program's
``window_kv_positions`` counter a step, across the window) over the device
time of the ``hm_attn_rows_decode`` calls in the median decode step of
the traced window, over 819 GB/s. It cannot pass 100 % unless the kernel
skips a slot. None for a program or a trace without the kernel or the
counter."""

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


def _positions_a_step(run, counter: str):
    """The program's ``counter`` a decode step, across the window."""
    steps = run.delta("steps") if "steps" in run.counters_before else 0
    if not steps or counter not in run.counters_before:
        return None
    return run.delta(counter) / steps


def read(run):
    got = _step(run, "window")
    positions = _positions_a_step(run, "window_kv_positions")
    if got is None or not positions:
        return None
    sizes, secs = got
    return (100.0 * hybrid.window_step_bytes(sizes, positions) / secs
            / peaks.peak(run.device["kind"], "hbm_bytes_per_s"))
