"""What admission costs the decode rows on the device: the device time of
the program runs named ``jit_serve_admit_*`` (gather, prefill chunk,
scatter: they run one after the other while every decode row waits) over
the device's busy time (``harness/phases.py``). None for a program that
names no program."""

from benchmark.harness import phases

LAYER = "scheduler"
UNIT = "%"
MOVES = "gap_ms_p50"
SOURCE = "device_trace"


def read(run):
    return phases.program_share(run.trace, phases.ADMISSION_PROGRAMS)
