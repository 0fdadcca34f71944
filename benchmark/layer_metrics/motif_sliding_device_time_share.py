"""How much of the device's busy time goes to the nine sliding layers'
attention (the low-rank projections, RoPE, the ring's write, the ring
kernel, the differential fold, the gate, ``wo``), found by position among a
program run's dense Q40 calls (``harness/motif.block_seconds`` says how: six
a layer, as ``harness/latent.py`` has them), over the union of op intervals,
on the first device, over the whole traced window. With its siblings it
says which part of a layer sets the step; what is left is the dense layers'
FFN, the streams' mixes around the blocks, the classifier and the embedding.
None where the trace holds no forward of this model."""

from benchmark.harness import motif, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def part_share(run, part: str):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    secs = motif.block_seconds(
        run.trace, motif.sizes_of(run.cell.config),
        getattr(run, "scoped_ops", None))[part]
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy


def read(run):
    return part_share(run, "sliding")
