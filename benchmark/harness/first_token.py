"""A generation's time to its first sampled token, from the PROGRAM's own
spans and the device's program runs: what ``ttft_ms_p50`` is made of.

``generate`` opens ``inference.encode`` around the tokenizer when it is
called (PR 50) and ``Engine.infer`` closes its first ``inference.fetch`` when
the first sampled token is on the host. Between the two lie the tokenizer,
the launch of the prompt's chunk (``inference.prefill_chunk``), the echo of
the forced tokens while the chunk runs (``inference.echo``), the launch of
the first decode step (``inference.dispatch``) and the wait for it. The
device runs the chunk program(s), ``jit_inference_prefill_chunk``, then one
decode step, ``jit_inference_step``.

Both ends of the interval are HOST events. What is taken out of it are
DURATIONS on the device's clock: whole program runs, given to the interval
by their midpoint as ``reduce_trace.steps`` gives a run to a span. The step
enqueued ahead starts as the first one ends, so its midpoint lies past the
fetch's end and it is left out, whole; clipping busy time to the interval
would instead read the clocks' offset (PERF.md section 3: about 1 ms) at
that end. So the offset moves neither number.

  generations     (start, end) of each generation whose first fetch the
                  capture holds: an ``inference.encode``'s start to the end
                  of the first ``inference.fetch`` before the next encode
  first_tokens    per generation {"interval_ms", "prefill_device_ms",
                  "busy_ms", "host_ms"} on the busiest device

A program without ``inference.encode`` (a parent commit) has no generation
here: every reader then returns None and the metric is left out of the line.
"""

from __future__ import annotations

from . import reduce_trace as rt
from .runtime import median

ENCODE = "inference.encode"
FETCH = "inference.fetch"
CHUNK_PROGRAM = "jit_inference_prefill_chunk"


def generations(trace: rt.Trace) -> list[tuple[float, float]]:
    """[(start_ns, end_ns)]: per ``inference.encode`` that starts inside
    the traced window, to the end of the first ``inference.fetch`` that
    starts after it and before the next encode. A generation whose first
    fetch the capture does not hold whole (the capture cut it) is left
    out."""
    lo, hi = trace.window or (float("-inf"), float("inf"))
    encodes = [s for s in trace.spans if s.name == ENCODE and s.start >= lo]
    fetches = [s for s in trace.spans if s.name == FETCH]
    out = []
    for i, enc in enumerate(encodes):
        nxt = encodes[i + 1].start if i + 1 < len(encodes) else float("inf")
        first = next((f for f in fetches
                      if enc.end <= f.start < nxt), None)
        if first is not None and first.end <= hi:
            out.append((enc.start, first.end))
    return out


def _runs_inside(mods: list, start: float, end: float) -> list:
    return [m for m in mods if start <= (m.start + m.end) / 2 <= end]


def first_tokens(trace) -> list[dict]:
    """Per generation of ``generations``, on the device whose program runs
    inside the interval take longest: the interval, the device time of the
    prefill chunk's runs, of all runs, and the rest (the host's)."""
    if trace is None or not trace.modules:
        return []
    out = []
    for start, end in generations(trace):
        per_dev = {d: _runs_inside(m, start, end)
                   for d, m in trace.modules.items()}
        dev = max(sorted(per_dev), key=lambda d: sum(
            m.end - m.start for m in per_dev[d]))
        runs = per_dev[dev]
        busy = sum(m.end - m.start for m in runs)
        chunk = sum(m.end - m.start for m in runs if m.name == CHUNK_PROGRAM)
        out.append({"interval_ms": (end - start) / 1e6,
                    "prefill_device_ms": chunk / 1e6,
                    "busy_ms": busy / 1e6,
                    "host_ms": (end - start - busy) / 1e6})
    return out


def median_of(run, key: str) -> float | None:
    """The median of ``key`` over the capture's generations; None where it
    holds none."""
    return median([g[key] for g in first_tokens(run.trace)])
