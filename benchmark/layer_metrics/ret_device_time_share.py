"""How much of the device's busy time the retention sub-block takes: the two
kernels (``retention_decode_step``, ``retention_prefill_chunk``) and the XLA
ops around them (per-head q/k-norm, RoPE, the gate, phi, the division by the
normaliser), found by position between a layer's ``wqkv`` call and its
``wo`` call (``harness/retention.retention_block_seconds`` says why not by
scope), over the union of op intervals, on the first device, over the whole
traced window (decode steps and admission chunks). Does the new mechanism do
most of the work. None where the trace holds no retention kernel."""

from benchmark.harness import reduce_trace, retention

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    device = sorted(run.trace.devices)[0]
    busy = reduce_trace.busy(run.trace)["busy_s"].get(device, 0.0)
    block = retention.retention_block_seconds(run.trace.devices[device])
    if busy <= 0 or block <= 0:
        return None
    return 100.0 * block / busy
