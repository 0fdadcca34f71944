"""The retention chunk kernel's share of the chip's published matmul peak:
the operations its calls must do (``harness/retention.chunk_kernel_flops``:
reading the earlier state for each query head, advancing it, and the chunk's
own positions in the attention form, D = 8256) over the device time of the
``retention_prefill_chunk`` calls in the traced window, over 197e12. The peak
is a bfloat16 one and the kernel multiplies in float32 at HIGHEST precision,
which the MXU does as six bfloat16 passes: a kernel that kept the MXU full
would read about 100 / 6 = 17 %, so this reads low by construction and is
compared with itself across PRs. None where the trace holds no such call."""

from benchmark.harness import peaks, retention

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    ops = run.trace.devices[sorted(run.trace.devices)[0]]
    calls = retention.kernel_calls(ops, retention.CHUNK_KERNEL)
    if not calls or sum(calls) <= 0:
        return None
    config = run.cell.config
    flops = retention.chunk_kernel_flops(
        retention.sizes_of(config),
        int(config["entries"]["serve"]["prefill_chunk"]))
    peak = peaks.peak(run.device["kind"], "bf16_flops_per_s")
    return 100.0 * flops * len(calls) / sum(calls) / peak
