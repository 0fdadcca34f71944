"""How much of the device's busy time the routed-expert sub-block takes:
the expert kernels (``moe_q40_*``) and the XLA ops around them (FFN norm,
router, top-k, slot building, gathers, SiLU, combine), found by position
between a layer's last dense Q40 call and the next one
(``harness/olmoe.moe_block_seconds`` says why not by scope), over the union
of op intervals, on the first device, over the whole traced window (decode
steps and admission chunks). None where the trace holds no expert kernel."""

from benchmark.harness import olmoe, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    moe = olmoe.moe_block_seconds(run.trace.devices[device])
    if busy <= 0 or moe <= 0:
        return None
    return 100.0 * moe / busy
