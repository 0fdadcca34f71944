"""The routed-expert decode kernel's share of the HBM roofline where the
chip holds a SHARE of the experts: the packed Q40 bytes of the DISTINCT held
experts a decode step routed to (the program's ``moe_active`` counter, which
counts held experts only, over its steps, across the window, times one
expert's bytes: ``harness/latent.expert_bytes``, 27.5 MB) over the device
time of the ``moe_q40_slots`` calls in the median decode step of the traced
window, over the chip's published 819 GB/s. It cannot pass 100 % unless the
kernel skips an expert it was routed to. None for a program that counts no
pairs landed here, or a trace without the latent decode kernel."""

from benchmark.harness import latent, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or "moe_local_pairs" not in run.counters_after:
        return None
    steps = run.delta("steps")
    secs = [s["slots"] for s in latent.step_kernel_seconds(run.trace)
            if s["slots"] > 0]
    if not steps or not secs:
        return None
    nbytes = (run.delta("moe_active") / steps
              * latent.expert_bytes(latent.sizes_of(run.cell.config)))
    return 100.0 * nbytes / median(secs) / peaks.peak(run.device["kind"],
                                                      "hbm_bytes_per_s")
