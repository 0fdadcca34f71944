"""The share of the window that decode rows stood still for admissions: the
prefill chunks of the WHOLE window (the change of ``ContinuousStats``'
``prefill_chunks``) x the capture's stall a chunk
(``..admit_stall_ms_per_chunk``) over the window (``harness/landings.py``).
Unlike ``..admission_device_share`` it does not depend on which chunks the
capture happened to hold, only on what one costs (where the capture holds
no whole landing pair, on the window's own subtraction:
``landings.stall``). None for a program without the phase."""

from benchmark.harness import landings

LAYER = "scheduler"
UNIT = "%"
MOVES = "gap_ms_p50"
SOURCE = "program_span"


def read(run):
    return landings.window_share(run)
