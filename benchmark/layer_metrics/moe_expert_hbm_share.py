"""The routed-expert decode kernel's share of the HBM roofline: the packed
Q40 bytes of the DISTINCT experts a decode step routed to (the program's
``moe_active`` counter over its steps, across the window, times one expert's
bytes: ``harness/olmoe.expert_bytes``) over the device time of the
``moe_q40_slots`` calls in the median decode step of the traced window, over
the chip's published 819 GB/s. Bandwidth bounds it (2 operations a weight
and row). It cannot pass 100 % unless the kernel skips an expert it was
routed to. None for a program that counts no routed experts."""

from benchmark.harness import olmoe, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or "moe_active" not in run.counters_after:
        return None
    steps = run.delta("steps")
    secs = [slots for slots, _ in olmoe.decode_step_kernel_seconds(run.trace)]
    if not steps or not secs:
        return None
    nbytes = (run.delta("moe_active") / steps
              * olmoe.expert_bytes(olmoe.sizes_of(run.cell.config)))
    peak = peaks.peak(run.device["kind"], "hbm_bytes_per_s")
    return 100.0 * nbytes / median(secs) / peak
