"""The sliding layers' decode attention kernel's share of the HBM roofline
where a ring holds K heads of 192 beside V heads of 128 over 8 KV heads and
a head has a sink: the PUBLISHED bytes of window ring a decode step must read
ONCE (10,240 B of K and V a ring slot a row reads, min(position + 1, 128) of
them, in each of the nine sliding layers: ``harness/mimo.ring_step_bytes``
over the program's ``window_kv_positions`` counter a step, across the TRACED
seconds: the driver reads the counters where the profiler starts and stops)
over the device time of the ``hm_attn_rows_decode`` calls in the median
decode step of the traced window, over 819 GB/s. The ring holds K in 256
lanes, so the kernel copies 12,288 B a slot: five sixths is the most this
share can read. None for a program or a trace without the kernel or the
counter."""

from benchmark.harness import mimo
from benchmark.harness.cells import load_reader

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ring = load_reader("layer_metrics", "lag_ring_attn_roofline")


def read(run):
    positions = _ring.a_step(run, "window_kv_positions")
    if not positions:
        return None
    return _ring.share(run, mimo.ring_step_bytes(
        mimo.sizes_of(run.cell.config), positions),
        _ring.step_seconds(run, "ring"))
