"""Device time of the prompt's prefill inside a generation's time to its
first sampled token: the ``jit_inference_prefill_chunk`` runs between the
generation's ``inference.encode`` and the end of its first
``inference.fetch`` (``harness/first_token.py``), on the busiest device.
The median over the capture's generations (the 1.5 s capture holds one or
two). With ``decode_step_ms_p50`` and ``ttft_host_ms`` it adds up to the
interval. None for a program without ``inference.encode``."""

from benchmark.harness import first_token

LAYER = "generation loop"
UNIT = "ms"
MOVES = "ttft_ms_p50"
SOURCE = "device_trace"


def read(run):
    return first_token.median_of(run, "prefill_device_ms")
