"""What an admission prefill chunk costs the rows that wait: over the
landings with chunks inside (``serve.land.chunk``: the step stood behind
them on the device queue) and the landing before each (which the enqueue of
the admission can hold up, by what the next interval then lacks), the
interval back to the landing before less the median plain interval, not
under 0, summed and divided by the chunks (``harness/landings.py``): a chunk
with its admission's share of gather and scatter. Where the capture holds
landings and no such pair (its edge cut the burst), the same cost from the
WHOLE window's counts: the time requests stood in the server less the
window's steps at the capture's plain pace, over its chunks
(``landings.window_stall_ms_per_chunk``; a note on stderr says so). None for
a program without the phase."""

from benchmark.harness import landings

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gap_ms_p50"
SOURCE = "program_span"


def read(run):
    return landings.stall(run)
