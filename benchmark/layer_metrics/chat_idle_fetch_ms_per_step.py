"""Device idle time under the scheduler's ``serve.fetch`` phase (the wait
for the step and the transfer of the pool's logits to the host), per
dispatch (``harness/phases.py``). It holds both ends of an idle gap: the
launch of the step after ``serve.dispatch`` has returned and the tail of
the transfer after the step has ended; the clocks' offset (PERF.md section
3) moves time between the two ends, not out of this metric, save for the
part of one ``serve.dispatch`` that the prepare metric loses to it. None
for a program without the phase."""

from benchmark.harness import phases

LAYER = "scheduler"
UNIT = "ms/step"
MOVES = "gap_ms_p50"
SOURCE = "program_span"


def read(run):
    return phases.serve_idle_ms_per_step(run, "fetch")
