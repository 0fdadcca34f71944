"""The pace of a decode step as the host sees it: the median interval from
one landing (``serve.land``: a step's results are on the host) to the next,
over the landings with no admission prefill chunk inside
(``harness/landings.py``). Against ``..decode_step_ms_p50`` (the step
program's device time) the excess is time the device did not spend in the
step program. None for a program without the phase."""

from benchmark.harness import landings

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gap_ms_p50"
SOURCE = "program_span"


def read(run):
    return landings.plain_ms_p50(run.trace)
