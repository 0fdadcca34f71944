"""Device idle time between decode steps under neither
``inference.dispatch`` nor ``inference.fetch``: the sampler, ``emit`` and
the generation loop's own bookkeeping, per decode step; idle time under
prefill is left out (``harness/phases.py``). With
``idle_step_ms_per_token`` it adds up to the window's idle time outside
prefill. The phases it reads lie wholly inside an idle gap, so the clocks'
offset (PERF.md section 3) does not move it. None for a program without
the phases."""

from benchmark.harness import phases

LAYER = "generation loop"
UNIT = "ms/token"
MOVES = "decode_ms_per_token"
SOURCE = "program_span"


def read(run):
    return phases.inference_idle_ms_per_token(run, "loop")
