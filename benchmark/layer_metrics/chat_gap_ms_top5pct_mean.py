"""Mean of the slowest 5 % of the gaps between streamed tokens, client
side, over all requests of the window: the stutter a chat user sees one gap
in twenty. Not the 95th percentile: the gaps are bimodal (a plain step is
43 ms, a step that also held an admission prefill 150 ms or more, my chip
run, PR 22) and below the knee about one gap in twenty holds a prefill, so
the percentile sits on the edge between the modes and flips with the seed
(43.9 ms at 1.2 requests/s, 146 at 1.4); the mean of the tail moves
smoothly. Not judged: each slow gap counts once per row that was active, so
even this spread by 4 to 5 % over six seeds at 48 admissions a window."""

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gap_ms_p50"
SOURCE = "host_clock"


def read(run):
    gaps = sorted(run.gaps_ms())
    if len(gaps) < 20:
        return None
    tail = gaps[len(gaps) - len(gaps) // 20:]
    return sum(tail) / len(tail)
