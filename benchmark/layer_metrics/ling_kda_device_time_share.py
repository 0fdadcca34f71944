"""How much of the device's busy time goes to the KDA mixers (the pre-norm,
``in_qkvag`` and the write-strength rows, the three convolutions, the q / k
norms and the gates, the state kernel or a chunk's matrix products and
triangular solve, the output's norm and gate, ``wo``: the ``kda.*`` scopes),
found by position among a program run's dense Q40 calls
(``harness/ling.block_seconds`` says how), over the union of op intervals, on
the first device, over the whole traced window. With its two siblings it
says which kind of layer sets the step; what is left is the classifier and
the embedding. None where the trace holds no forward of this model."""

from benchmark.harness import ling, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def part_share(run, part: str):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    secs = ling.block_seconds(run.trace,
                              ling.sizes_of(run.cell.config))[part]
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy


def read(run):
    return part_share(run, "kda")
