"""Device idle time under ``Engine.infer``'s two phases together,
``inference.dispatch`` (building the token and position arguments and the
call of the step program) and ``inference.fetch`` (the wait for the step
and the transfer of its logits), per decode step: what launching a step
and getting its logits back cost the device (``harness/phases.py``).

ONE number for the two phases, because a capture cannot split them: the
device's clock reads 0.7 to 2 ms early against the host's (PERF.md section
3), which moves the idle gap's end from the launch to the tail of the
previous ``fetch``; read apart, dispatch showed 0.03 ms where it costs
about 1. Their sum does not depend on the offset. None for a program
without the phases."""

from benchmark.harness import phases

LAYER = "generation loop"
UNIT = "ms/token"
MOVES = "decode_ms_per_token"
SOURCE = "program_span"


def read(run):
    return phases.inference_idle_ms_per_token(run, "step")
