"""How much of the device's busy time goes to the full layer's and the cross-decoder's
mixers (layers 17 to 31: wqkv or wq, the page writes, the paged kernel, the
combine, wo; a GMU's in_proj, gate and out_proj),
found by position among a program run's dense Q40 calls
(``harness/hybrid.mixer_seconds`` says how, and why not by scope), over the
union of op intervals, on the first device, over the whole traced window.
With its two siblings it says which kind of layer sets the step; what is
left is the FFNs, the classifier and the embedding. None where the trace
holds no forward of this model."""

from benchmark.harness import hybrid, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    part = hybrid.mixer_seconds(
        run.trace, hybrid.sizes_of(run.cell.config))["xdec"]
    if busy <= 0 or part <= 0:
        return None
    return 100.0 * part / busy
