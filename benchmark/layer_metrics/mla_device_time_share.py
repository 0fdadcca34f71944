"""How much of the device's busy time the latent-attention sub-block takes:
from a layer's first attention leaf (``wq_a``) to its ``wo``, both included
(the low-rank q, the latent row, RoPE, the absorbed products, the latent
decode kernel or a chunk's XLA attention, the output projection), found by
position among a program's dense Q40 calls (``harness/latent.block_seconds``
says how), over the union of op intervals, on the first device, over the
whole traced window (decode steps and admission chunks). None where the
trace holds no forward of such a model."""

from benchmark.harness import latent, reduce_trace

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run, block: str = "mla"):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    secs = latent.block_seconds(run.trace, device)[block]
    if busy <= 0 or secs <= 0:
        return None
    return 100.0 * secs / busy
