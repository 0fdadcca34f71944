"""Device idle time after an iteration's logits are on the host, per
dispatch: under ``serve.sample``, ``serve.census``, ``serve.journal`` and
whatever of the iteration no phase covers; the scheduler's sleep when
nothing is active (``serve.idle``) is left out (``harness/phases.py``).
With the prepare and fetch metrics it adds up to the window's idle time
outside ``serve.idle``. None for a program without the phases."""

from benchmark.harness import phases

LAYER = "scheduler"
UNIT = "ms/step"
MOVES = "gap_ms_p50"
SOURCE = "program_span"


def read(run):
    return phases.serve_idle_ms_per_step(run, "finish")
