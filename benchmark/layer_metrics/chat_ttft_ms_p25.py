"""25th percentile of the time from when a request was DUE to its first
sampled token: the first token of a request that met no queue and no other
admission, which is the admission path itself (wait for the step's end,
gather, prefill, scatter, one decode step). The MEDIAN cannot be judged in a
chat cell: about half the requests of a window are unobstructed, so the
median sits between two modes and flips with the seed (190 to 262 ms over
six seeds where this quartile read 170 to 176; my chip run, PR 22).
Recorded and not judged: over two sets of six runs this quartile still
spread by 5.3 to 5.5 % (164 to 184 ms: the mode is as wide as one decode
step, which an arrival waits out), more than half of the widest bound a
metric may have."""

from benchmark.harness.runtime import percentile


LAYER = "scheduler"
UNIT = "ms"
MOVES = "gap_ms_p50"
SOURCE = "host_clock"


def read(run):
    return percentile(run.ttft_ms(), 25)
