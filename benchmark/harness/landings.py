"""What an admission costs the decode rows, from the scheduler's own landing
spans on the host's lines of a capture.

Since PR 35 ``ContinuousEngine._land`` runs inside the host phase
``serve.land``, which opens at the instant a step's results are on the host,
and opens inside it one empty ``serve.land.chunk`` per admission prefill
chunk that stood before that step on the device queue (the scheduler knows
the queue's order; a capture's device lines do not say which step waited).
With the device never idle, the interval from one landing to the next is the
device time of whatever was queued between the two steps. Everything here is
read on the host's clock alone, so no boundary moves with the offset between
the clocks (PERF.md section 3), and a landing is paired with its chunks by
containment, not by guessing at the capture's edges.

Why the landing before counts: the host enqueues an admission's programs
BEFORE it fetches the step in flight, and the runtime holds it in an enqueue
once enough programs are in flight. The landing before a burst then comes
long after its step ended (0.04 to 0.7 s in the first captures, PR 35) and
the interval of the landing with the chunks inside is short by as much: 129
to 159 ms a chunk on ``deepseekv3.gen-sat32`` from that interval alone,
where the chunk programs ran 161.8 ms. The two intervals add up to the truth
whatever the lateness (161.7 to 161.9 in the same captures).

A program without the phase (a parent commit) has no ``serve.land`` span:
every reader here then returns None and the metric is left out of the line.

  landings        the ``serve.land`` spans in order, each with the interval
                  back to the landing before it and the chunks inside it
  plain_ms_p50    median interval of landings with no chunk inside: the pace
                  of a step as the host sees it
  stall_ms_per_chunk  what the landings with chunks inside, AND the landing
                  before each, took beyond that median (not under 0), over
                  the chunks; None where the capture holds no such pair (not
                  0: a capture that missed every admission says nothing of
                  their cost)
  window_stall_ms_per_chunk  the same cost from the WHOLE window's counts,
                  for a capture that holds landings and no whole pair (a
                  burst that the capture's edge cuts: 1 capture in 7 of
                  ``brumby14b.gen-sat16``): the time requests stood in the
                  server, less the window's steps at the capture's plain
                  pace, over the window's chunks
  stall           the capture's reading where it has one, else the window's:
                  a line with landings in it always carries the metric
  window_share    the window's chunks (``prefill_chunks``, counted over the
                  WHOLE window) x the stall a chunk, over the window
"""

from __future__ import annotations

import dataclasses

from . import reduce_trace as rt
from .runtime import median, note

LAND = "serve.land"
CHUNK = "serve.land.chunk"
IDLE = "serve.idle"


@dataclasses.dataclass(frozen=True)
class Landing:
    start: float               # ns, the landing's instant
    interval_ms: float | None  # back to the landing before; None where the
    #                            capture's edge cuts it or a sleep lies between
    chunks: int                # prefill chunks the step stood behind


def landings(trace: rt.Trace | None) -> list:
    """The capture's landings in order. A landing that the window's edge
    cuts is left out (its chunks may be cut off with it), and so is the
    interval of the one after the capture's first (nothing to count back
    to) and of one with a ``serve.idle`` span since the landing before:
    the pool was empty and the interval holds a sleep."""
    if trace is None or not trace.spans:
        return []
    lo, hi = (trace.window if trace.window is not None
              else (float("-inf"), float("inf")))
    lands = [s for s in trace.spans if s.name == LAND]
    chunks = [s for s in trace.spans if s.name == CHUNK]
    idles = sorted(s.start for s in trace.spans if s.name == IDLE)
    out = []
    prev = None
    for land in lands:
        whole = lo <= land.start and land.end <= hi
        if whole:
            interval = None
            if prev is not None and not any(
                    prev.start < t < land.start for t in idles):
                interval = (land.start - prev.start) / 1e6
            inside = sum(c.label == land.label and land.start <= c.start
                         and c.end <= land.end for c in chunks)
            out.append(Landing(land.start, interval, inside))
        prev = land
    return out


def _plain(lands: list) -> float | None:
    return median([x.interval_ms for x in lands
                   if x.interval_ms is not None and not x.chunks])


def plain_ms_p50(trace) -> float | None:
    return _plain(landings(trace))


def stall_ms_per_chunk(trace) -> float | None:
    lands = landings(trace)
    plain = _plain(lands)
    if plain is None:
        return None
    stall, chunks = 0.0, 0
    for before, land in zip(lands, lands[1:]):
        # a pair counts where both intervals are known: how late the
        # landing before came cannot be told otherwise
        if not land.chunks or None in (land.interval_ms,
                                       before.interval_ms):
            continue
        stall += max(0.0, land.interval_ms - plain)
        if not before.chunks:        # else it is the pair before's own
            stall += max(0.0, before.interval_ms - plain)
        chunks += land.chunks
    return stall / chunks if chunks else None


def busy_ms(run) -> float:
    """Milliseconds of the window in which some request stood in the server
    (the union of sent-to-done over the client's records, cut to the
    window): a closed loop's whole window, an open loop's less its sleeps."""
    hi = run.window_s
    spans = sorted((max(0.0, r["sent"]), hi if r["done"] is None
                    else min(hi, r["done"]))
                   for r in run.records if r.get("sent") is not None)
    total, end = 0.0, 0.0
    for a, b in spans:
        if b > max(a, end):
            total += b - max(a, end)
            end = b
    return total * 1e3


def window_stall_ms_per_chunk(run) -> float | None:
    """What a chunk cost over the whole window, by subtraction: the time the
    engine had work, less the window's steps at the capture's plain pace
    (on the host's clock, so a plain step's idle time is in it), over the
    window's chunks. It takes the capture's median for every step of the
    window, so it is the second choice; None for a program without the
    phase (no plain pace) and for a window with no chunk."""
    plain = plain_ms_p50(run.trace)
    after = run.counters_after
    if plain is None or "steps" not in after or "prefill_chunks" not in after:
        return None
    chunks = run.delta("prefill_chunks")
    if chunks <= 0:
        return None
    return max(0.0, busy_ms(run) - run.delta("steps") * plain) / chunks


def stall(run) -> float | None:
    got = stall_ms_per_chunk(run.trace)
    if got is None:
        got = window_stall_ms_per_chunk(run)
        if got is not None:
            note("the capture holds no landing pair behind an admission: "
                 f"a chunk's stall from the window's counts, {got:.2f} ms")
    return got


def window_share(run) -> float | None:
    """Percent of the window that decode rows stood still for admissions."""
    stall_ms = stall(run)
    if stall_ms is None or "prefill_chunks" not in run.counters_after \
            or run.window_s <= 0:
        return None
    return 100.0 * run.delta("prefill_chunks") * stall_ms / (
        run.window_s * 1e3)
