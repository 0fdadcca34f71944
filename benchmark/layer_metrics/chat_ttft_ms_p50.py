"""Median time to the first sampled token in the chat cell. Recorded and not
judged: it sits between the unobstructed mode and the obstructed one (see
``chat_ttft_ms_p25.py``)."""

from benchmark.harness.runtime import median

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gap_ms_p50"
SOURCE = "host_clock"


def read(run):
    return median(run.ttft_ms())
