"""How much of the device's busy time goes to the twelve sliding layers'
mixers (``wqkv``, the kind's RoPE, the ring writes, the ring kernel or a
chunk's masked attention, the per-head gate, ``wo``), found by position among
a program run's dense Q40 calls (``harness/laguna.block_seconds`` says how),
over the union of op intervals, on the first device, over the whole traced
window: decode steps and admission chunks alike. With its two siblings it
says which part of a layer sets the step; what is left is layer 0's dense
FFN, the classifier and the embedding. None where the trace holds no forward
of this model."""

from benchmark.harness import laguna, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def part_share(run, part: str):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    secs = laguna.block_seconds(
        run.trace, laguna.sizes_of(run.cell.config))[part]
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy


def read(run):
    return part_share(run, "sliding")
