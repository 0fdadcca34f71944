"""How much of the device's busy time goes to the nine sliding layers'
mixers (``wqkv``, the kind's RoPE, the ring writes, the ring kernel with its
sink, the output's scale, ``wo``), found by position among a program run's
dense Q40 calls (``harness/mimo.block_seconds`` says how: two a layer here,
there is no shared expert), over the union of op intervals, on the first
device, over the whole traced window. With its two siblings it says which
part of a layer sets the step; what is left is layer 0's dense FFN, the
classifier and the embedding. None where the trace holds no forward of this
model."""

from benchmark.harness import mimo, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def part_share(run, part: str):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    secs = mimo.block_seconds(run.trace, mimo.sizes_of(run.cell.config))[part]
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy


def read(run):
    return part_share(run, "sliding")
