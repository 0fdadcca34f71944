"""The latent layers' paged decode kernel's share of the HBM roofline: the
bytes of latent rows a decode step must read ONCE (2,560 B a cached position
a row reads, 576 values in 640 lanes, position + 1 of them, in each latent
layer's pool: ``harness/ling.latent_step_bytes`` over the program's
``shared_kv_positions`` counter a step, across the TRACED seconds, so at the
trace's mean depth) over the device time of the ``mla_paged_attn_decode``
calls in the median decode step of the traced window, over 819 GB/s. At 32
heads over one 640-lane row the kernel is bound by its products, not by the
bytes (``mla_latent_hbm_share`` says so of DeepSeek-V3's): a low share is
its nature. None for a program or a trace without the kernel or the
counter."""

from benchmark.harness import ling
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_state = load_reader("layer_metrics", "ling_kda_state_roofline")


def read(run):
    positions = _state.a_step(run, "shared_kv_positions")
    if not positions:
        return None
    return _state.share(run, ling.latent_step_bytes(
        ling.sizes_of(run.cell.config), positions),
        _state.step_seconds(run, "latent"))
