"""The device's idle time by what the host was doing, from the PROGRAM's own
host phases, and its busy time by program name.

The benchmark's spans wrap public calls (``inference.step``, ``serve.step``);
since PR 24 the program opens phases of its own beneath them on the
profiler's clock (``obs/spans.host_phase``: ``inference.dispatch``,
``serve.fetch``, ...; PERF.md section 3 has the list) and names the programs
it runs (``jit_serve_admit_gather``, ...). ``reduce_trace.load`` already keeps
those spans and ``idle_gaps`` already gives each gap to the innermost one;
this file sums the same attribution by GROUPS of names, so that the groups
of one cell partition its idle time, and divides by the steps of the window.

A program without the phases (a parent commit) has no ``*.dispatch`` span:
every reader here then returns None and the metric is left out of the line.

  idle_by_span    idle seconds of the most idle device by the innermost
                  host span over each part of each gap ("(none)" where no
                  span is): ``reduce_trace.idle_gaps`` without its cut to
                  ten names
  idle_split      the same summed by group; names in no group are ``rest``
  idle_ms_per     one group's idle time over the count of a span's name
  program_share   device time of the program runs whose name starts with a
                  prefix, over busy time
"""

from __future__ import annotations

import sys

from . import reduce_trace as rt

NONE = "(none)"

# ``inference``: one decode step is a dispatch and a fetch (Engine.infer),
# read as ONE group: the device's clock reads 0.7 to 2 ms early against the
# host's (PERF.md section 3), so the end of an idle gap, which the launch
# in ``inference.dispatch`` makes, is read under the previous step's
# ``inference.fetch``; the sum of the two does not depend on the offset.
# The sampler, ``emit`` and the loop lie between steps, wholly inside a
# gap. Prefill is another phase of a generation, counted by no per-token
# metric.
INFERENCE_GROUPS = {
    "step": ("inference.dispatch", "inference.fetch"),
    "prefill": ("inference.prefill", "inference.prefill_chunk"),
}
INFERENCE_REST = "loop"
INFERENCE_STEP = "inference.dispatch"

# ``serve``: what comes before the device can start an iteration's step,
# the wait for its logits, and what comes after. ``serve.idle`` is the
# scheduler's sleep when nothing is active: idle for want of work. The
# phases between two steps lie wholly inside the idle gap; only its two
# ends feel the clocks' offset: the step's start is read up to one
# ``serve.dispatch`` (0.4 to 0.9 ms) early, which ``prepare`` loses and
# ``fetch`` gains at the gap's other end.
SERVE_GROUPS = {
    "prepare": ("serve.intake", "serve.admit", "serve.admit.gather",
                "serve.admit.prefill_chunk", "serve.admit.scatter",
                "serve.grow_pages", "serve.stage", "serve.dispatch"),
    "fetch": ("serve.fetch",),
    "idle": ("serve.idle",),
}
SERVE_REST = "finish"
SERVE_STEP = "serve.dispatch"

ADMISSION_PROGRAMS = "jit_serve_admit_"
PROGRAM_NAMES = ("jit_serve_", "jit_inference_")


def idle_by_span(trace: rt.Trace) -> dict:
    """{span name: idle seconds}: ``reduce_trace.idle_gaps`` without its
    cut to ten names. Kept on the trace: three readers of a cell ask for
    it."""
    cached = vars(trace).get("_idle_by_span")
    if cached is None:
        cached = (dict(rt.idle_gaps(trace, n=sys.maxsize))
                  if any(trace.devices.values()) else {})
        vars(trace)["_idle_by_span"] = cached
    return cached


def idle_split(trace: rt.Trace, groups: dict, rest: str) -> dict:
    """{group: idle seconds}, ``rest`` included: a partition of the idle
    time of ``idle_by_span``. A name in no group counts under ``rest``."""
    owner = {name: g for g, names in groups.items() for name in names}
    out = {g: 0.0 for g in (*groups, rest)}
    for name, sec in idle_by_span(trace).items():
        out[owner.get(name, rest)] += sec
    return out


def count(trace: rt.Trace, name: str) -> int:
    return sum(s.name == name for s in trace.spans)


def idle_ms_per(trace, groups: dict, rest: str, group: str,
                per: str) -> float | None:
    """Idle milliseconds of ``group`` over the number of ``per`` spans in
    the window; None where the trace has none (no trace, or a program
    without the phases)."""
    if trace is None or not trace.devices:
        return None
    n = count(trace, per)
    if not n:
        return None
    return 1e3 * idle_split(trace, groups, rest)[group] / n


def inference_idle_ms_per_token(run, group: str) -> float | None:
    return idle_ms_per(run.trace, INFERENCE_GROUPS, INFERENCE_REST, group,
                       INFERENCE_STEP)


def serve_idle_ms_per_step(run, group: str) -> float | None:
    return idle_ms_per(run.trace, SERVE_GROUPS, SERVE_REST, group,
                       SERVE_STEP)


def busy_by_program(trace: rt.Trace, device: str | None = None) -> dict:
    """{program name: seconds}: the summed durations of a device's program
    runs ("XLA Modules" line), by name."""
    if not trace.modules:
        return {}
    device = device or sorted(trace.modules)[0]
    out: dict = {}
    for m in trace.modules[device]:
        out[m.name] = out.get(m.name, 0.0) + (m.end - m.start) / 1e9
    return out


def program_share(trace, prefix: str) -> float | None:
    """Device time of the program runs whose name starts with ``prefix``
    over the device's busy time, in %. None where no program run carries
    one of the program's own names (a program that names none: every run
    is ``jit__unknown`` there and admission cannot be told from decode)."""
    if trace is None or not trace.devices or not trace.modules:
        return None
    device = sorted(trace.modules)[0]
    by_name = busy_by_program(trace, device)
    if not any(n.startswith(PROGRAM_NAMES) for n in by_name):
        return None
    busy = rt.busy(trace)["busy_s"].get(device, 0.0)
    if busy <= 0:
        return None
    named = sum(s for n, s in by_name.items() if n.startswith(prefix))
    return 100.0 * named / busy
