"""The sliding layers' ring decode kernel's share of its roofline where a
ring holds 128 LATENT rows that 80 absorbed query heads read
(``mla_ring_attn_decode``, the one kernel this configuration brought): the
larger of the PUBLISHED bytes of ring a decode step must read ONCE (2,304 B
a slot a row reads, min(position + 1, 128) of them, in each of the nine
sliding layers) over 819 GB/s and of its operations (80 heads x (576 + 512)
x 2 a slot) over the MXU's peak at the six bf16 passes a float32 product at
HIGHEST takes (``harness/motif.attn_step_cost`` / ``roofline_seconds`` over
the program's ``window_kv_positions`` counter a step, across the TRACED
seconds), over the device time of the kernel's calls in the median decode
step of the traced window. At 75 operations a byte the operations bound.
None for a program or a trace without the kernel or the counter."""

from statistics import median

from benchmark.harness import motif
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "lag_ring_attn_roofline")


def step_seconds(run, what: str):
    """Median seconds of ``what`` in a traced decode step of this model
    (``motif.step_kernel_seconds``), or None."""
    if run.trace is None:
        return None
    secs = [s[what] for s in motif.step_kernel_seconds(run.trace)
            if s[what] > 0]
    return median(secs) if secs else None


def attn_share(run, kind: str, counter: str, what: str):
    positions = _ring.a_step(run, counter)
    secs = step_seconds(run, what)
    if not positions or not secs:
        return None
    nbytes, flops = motif.attn_step_cost(
        motif.sizes_of(run.cell.config), kind, positions)
    return 100.0 * motif.roofline_seconds(run.device["kind"], nbytes,
                                          flops) / secs


def read(run):
    return attn_share(run, "sliding", "window_kv_positions", "ring")
