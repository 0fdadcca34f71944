"""The latent decode kernel's share of the HBM roofline: the bytes of
latent cache a decode step must read ONCE (576 float32 values = 2,304 B a
cached position the step's rows read, a layer:
``harness/latent.latent_step_bytes`` over the program's ``latent_positions``
counter a step, across the window) over the device time of the
``mla_paged_attn_decode`` calls in the median decode step of the traced
window, over the chip's published 819 GB/s. It cannot pass 100 % unless the
kernel skips a position. At this cell's contexts the kernel is bound by its
operations (``mla_attn_flops_share``), so this reads low. None for a program
or a trace without the kernel."""

from benchmark.harness import cells, latent, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    flops = cells.load_reader("layer_metrics", "mla_attn_flops_share")
    positions, secs = flops.positions_a_step(run), flops.kernel_seconds(run)
    if not positions or not secs:
        return None
    nbytes = latent.latent_step_bytes(latent.sizes_of(run.cell.config),
                                      positions)
    return 100.0 * nbytes / secs / peaks.peak(run.device["kind"],
                                              "hbm_bytes_per_s")
