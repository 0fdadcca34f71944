"""The latent decode kernel's share of the chip's peak operations: the
absorbed attention's operations a decode step must do (2 x 128 heads x
(576 + 512) a cached position the step's rows read, a layer:
``harness/latent.latent_step_flops`` over the program's ``latent_positions``
counter a step, across the window) over the device time of the
``mla_paged_attn_decode`` calls in the median decode step of the traced
window, over the chip's published 197e12 a second. The kernel computes in
float32 at HIGHEST (several bf16 passes an operation), so the share reads
low by that factor; it cannot pass 100 %. None for a program or a trace
without the kernel."""

from benchmark.harness import latent, peaks
from benchmark.harness.runtime import median

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def positions_a_step(run):
    if "latent_positions" not in run.counters_after:
        return None
    steps = run.delta("steps")
    return run.delta("latent_positions") / steps if steps else None


def kernel_seconds(run):
    if run.trace is None:
        return None
    secs = [s["latent"] for s in latent.step_kernel_seconds(run.trace)]
    return median(secs) if secs else None


def read(run):
    positions, secs = positions_a_step(run), kernel_seconds(run)
    if not positions or not secs:
        return None
    flops = latent.latent_step_flops(latent.sizes_of(run.cell.config),
                                     positions)
    return 100.0 * flops / secs / peaks.peak(run.device["kind"],
                                             "bf16_flops_per_s")
