"""Device idle time before an iteration's step can start, per dispatch:
under the scheduler's phases ``serve.intake``, ``serve.admit`` and its
children, ``serve.grow_pages``, ``serve.stage`` and ``serve.dispatch``
(``harness/phases.py``). The device's clock reads 0.7 to 2 ms early against
the host's (PERF.md section 3), so the step's start is read inside
``serve.dispatch`` where it truly lies after it: this metric reads low by
at most one ``serve.dispatch`` (0.4 to 0.9 ms), which the fetch metric
gains; the phases before the dispatch lie wholly inside the idle gap and
read true. None for a program without the phases."""

from benchmark.harness import phases

LAYER = "scheduler"
UNIT = "ms/step"
MOVES = "gap_ms_p50"
SOURCE = "program_span"


def read(run):
    return phases.serve_idle_ms_per_step(run, "prepare")
