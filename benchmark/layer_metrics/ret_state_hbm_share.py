"""The retention decode kernel's share of the HBM roofline: the bytes of
state one decode step must move (every slot's state of every layer and KV
head read once and written once, float32, D = 8256 rows a head whatever the
program stores: ``harness/retention.state_step_bytes``) over the device time
of the ``retention_decode_step`` calls in the median decode step of the
traced window, over the chip's published 819 GB/s. Bandwidth bounds it: 2 +
2 m operations an element (m = 5 query heads a state) on 8 bytes moved. It
counts every slot's row, as the kernel moves every row, taking part or not.
None for a program or a trace without the kernel."""

from benchmark.harness import peaks, retention
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    secs = retention.decode_step_kernel_seconds(run.trace)
    if not secs:
        return None
    config = run.cell.config
    nbytes = retention.state_step_bytes(
        retention.sizes_of(config), int(config["entries"]["serve"]["slots"]))
    peak = peaks.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * nbytes / median(secs) / peak
