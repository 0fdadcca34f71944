"""The residual path's share of the HBM roofline where the path is several
streams: the bytes a decode step's path must move ONCE whatever implements
it (a sub-layer reads X and writes X', writes its input and reads its
output, reads ``phi``: ``harness/hyper.hc_step_bytes`` at the window's rows
a step) over the device time of the path's ops in the median decode step of
the traced window (``harness/hyper.hc_step_ops``), over the chip's published
819 GB/s. The count does not depend on the implementation, so the share
cannot pass 100 % unless an op of the path is missed. None for a program
without the streams' counter or a trace without such a step."""

from benchmark.harness import hyper, peaks
from benchmark.harness.runtime import median

LAYER = "residual path"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.counters_after.get("hc_streams"):
        return None
    rows = hyper.step_rows(run)
    steps = hyper.hc_step_ops(run.trace, hyper.path_names(run))
    secs = [s["seconds"] for s in steps if s["seconds"] > 0]
    if not rows or not secs:
        return None
    nbytes = hyper.hc_step_bytes(hyper.sizes_of(run.cell.config), round(rows))
    return 100.0 * nbytes / median(secs) / peaks.peak(run.device["kind"],
                                                      "hbm_bytes_per_s")
