"""From a profiler trace to numbers: the one reduction every PR's per-layer
metrics go through.

``load(path)`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into a ``Trace``: per device the operations
of its "XLA Ops" line, and the host's benchmark spans (``TraceAnnotation``s
written by the drivers, names in ``SPAN_PREFIXES``). Everything below works
on that plain structure, so tests can also build one by hand.

What the planes are (looked at by hand on a v5e trace, PERF.md section 3): a
device is a plane named ``/device:TPU:<n>``; its line "XLA Ops" holds one
event per executed HLO operation, named by the instruction's whole text
(``%_q40_matvec_nb_stacked.26 = f32[1,28672]{...} custom-call(...),
custom_call_target="tpu_custom_call"``), with start and duration in ns; its
line "XLA Modules" holds one event per program run (``jit__unknown(...)``).
Host threads are lines of the plane ``/host:CPU``; a ``TraceAnnotation`` is
an event on its thread's line. Host and device clocks agree to a few tenths
of a millisecond, no better: an op can seem to start before the host call
that launched it, so ops are given to a host span through the MODULE run
whose midpoint lies in the span, never by their own start. Operations NEST
on a device line (a ``while`` holds its body's operations), so busy time is
a UNION of intervals and a name's time is its SELF time (its span minus its
children).

  busy / idle     union of a device's op intervals over the traced window
  kernel time     self time summed by class: ``classify`` puts an op into
                  "q40" (the Q40 matmul Pallas calls), "attention" (the
                  attention Pallas calls), "collective" or "xla"
  exposure        collective time during which no other op runs on that
                  device
  gaps            each idle gap goes to the benchmark span that covers most
                  of it, and is summed by that span's name
  steps           per ``*.step`` host span, the module runs whose midpoint
                  lies inside it: device time from the first one's start to
                  the last one's end, and the ops between
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("inference.", "serve.", "client.", "bench.")

# how the trace names things (PERF.md section 3 records the look by hand):
# an op's event name is its HLO text. A Pallas call is a ``custom-call``
# whose instruction name is the kernel's jitted function
# (``_q40_matvec_nb_stacked``, ``decode_attention``).
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = ")
_KIND = re.compile(r" (?P<kind>[a-z][a-z0-9\-]*)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")


def short_name(text: str) -> str:
    """``%fusion.31 = f32[...] fusion(...)`` -> ``fusion.31``."""
    m = _HLO.match(text)
    return m.group("name") if m else text[:80]


def op_kind(text: str) -> str:
    """The HLO opcode of an op's text (``custom-call``, ``fusion``, ...):
    the first lower-case word that opens a parenthesis after the ``=``."""
    eq = text.find(" = ")
    m = _KIND.search(text, eq + 2) if eq >= 0 else None
    return m.group("kind") if m else ""


@dataclasses.dataclass(frozen=True)
class Op:
    name: str      # the instruction's name, e.g. "_q40_matvec_nb_stacked.26"
    label: str     # its HLO opcode ("custom-call", "fusion", "while", ...);
    #                for a host span, the thread's line
    start: float   # ns
    end: float


@dataclasses.dataclass
class Trace:
    devices: dict          # device name -> [Op, ...] sorted by start
    spans: list            # host benchmark spans: [Op, ...] sorted by start
    window: tuple | None = None   # (start_ns, end_ns) of the traced window
    modules: dict = dataclasses.field(default_factory=dict)
    # ^ device name -> program runs [Op, ...] sorted by start

    def bounds(self) -> tuple[float, float]:
        if self.window is not None:
            return self.window
        ops = [o for v in self.devices.values() for o in v]
        return (min(o.start for o in ops), max(o.end for o in ops))


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, window_span: str | None = "bench.window") -> Trace:
    """Read an ``.xplane.pb``. With ``window_span``, the traced window is
    that host span's interval (the drivers wrap the measured part in it);
    ops outside it are dropped."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    modules: dict = {}
    spans: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = []
                    for ev in line.events:
                        text = ev.name
                        start = float(ev.start_ns)
                        ops.append(Op(short_name(text), op_kind(text), start,
                                      start + float(ev.duration_ns)))
                    ops.sort(key=lambda o: (o.start, -o.end))
                    devices[plane.name] = ops
                elif line.name == MODULES_LINE:
                    mods = [Op(ev.name.split("(")[0], "module",
                               float(ev.start_ns),
                               float(ev.start_ns + ev.duration_ns))
                            for ev in line.events]
                    mods.sort(key=lambda o: o.start)
                    modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append(Op(ev.name, line.name,
                                        float(ev.start_ns),
                                        float(ev.start_ns + ev.duration_ns)))
    spans.sort(key=lambda o: (o.start, -o.end))
    trace = Trace(devices, spans, modules=modules)
    if window_span is not None:
        win = [s for s in spans if s.name == window_span]
        if win:
            lo, hi = win[0].start, win[-1].end
            trace.window = (lo, hi)

            def inside(ops):
                return [o for o in ops if o.start >= lo and o.end <= hi]

            trace.devices = {d: inside(ops) for d, ops in devices.items()}
            trace.modules = {d: inside(m) for d, m in modules.items()}
            trace.spans = [s for s in spans if s.end > lo and s.start < hi
                           and s.name != window_span]
    return trace


# ------------------------------------------------------------------ intervals

def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(ops: list) -> list[float]:
    """Per op (sorted by start, longer first), its duration minus the time
    its DIRECT children cover."""
    selfs = [o.end - o.start for o in ops]
    stack: list[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            selfs[stack[-1]] -= o.end - o.start
        stack.append(i)
    return selfs


def classify(op: Op) -> str:
    """"collective", "q40" (a Q40 matmul Pallas call), "attention" (an
    attention Pallas call), "custom" (any other custom call), "control"
    (a ``while``/``call``/``conditional``: a parent, no work of its own) or
    "xla" (everything the compiler made: fusions, copies, slices)."""
    kind = op.label
    if kind.removesuffix("-start").removesuffix("-done") in _COLLECTIVES:
        return "collective"
    if kind == "custom-call":
        low = op.name.lower()
        if "q40" in low:
            return "q40"
        if "attention" in low or "attn" in low:
            return "attention"
        return "custom"
    if kind in ("while", "call", "conditional"):
        return "control"
    return "xla"


# -------------------------------------------------------------------- metrics

def busy(trace: Trace) -> dict:
    """Per device: seconds busy (union of op intervals) and the window."""
    lo, hi = trace.bounds()
    out = {}
    for dev, ops in trace.devices.items():
        out[dev] = total(union((max(o.start, lo), min(o.end, hi))
                               for o in ops)) / 1e9
    return {"window_s": (hi - lo) / 1e9, "busy_s": out}


def idle_share(trace: Trace) -> float:
    """1 - busy over the window, of the device that idles most, in %."""
    b = busy(trace)
    if not b["busy_s"] or b["window_s"] <= 0:
        raise ValueError("trace has no device ops")
    return 100.0 * (1.0 - min(b["busy_s"].values()) / b["window_s"])


def time_by_class(trace: Trace) -> dict:
    """Per device: self seconds by class, and by op name within a class."""
    out = {}
    for dev, ops in trace.devices.items():
        by_class: dict = {}
        by_name: dict = {}
        for o, s in zip(ops, self_times(ops)):
            c = classify(o)
            by_class[c] = by_class.get(c, 0.0) + s / 1e9
            key = (c, re.sub(r"[.\d]+$", "", o.name))
            by_name[key] = by_name.get(key, 0.0) + s / 1e9
        out[dev] = {"by_class": by_class, "by_name": by_name}
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds], ...] of the busiest device: self time by op name
    (numeric suffixes stripped), largest first."""
    per_dev = time_by_class(trace)
    if not per_dev:
        return []
    dev = max(per_dev, key=lambda d: sum(per_dev[d]["by_class"].values()))
    rows = sorted(per_dev[dev]["by_name"].items(), key=lambda kv: -kv[1])
    return [[f"{c}:{name}", sec] for (c, name), sec in rows[:n]]


def collective_exposed_s(ops: list) -> float:
    """Seconds in which a collective op ran and no other op did, among
    ``ops`` of one device."""
    selfs = self_times(ops)
    # a parent (while, call) is no work of its own: leaves only
    leaves = [o for o, s in zip(ops, selfs)
              if s >= 0.999 * (o.end - o.start)]
    coll = union((o.start, o.end) for o in leaves
                 if classify(o) == "collective")
    comp = union((o.start, o.end) for o in leaves
                 if classify(o) != "collective")
    return total(subtract(coll, comp)) / 1e9


def collective_exposed_ms_per_step(trace: Trace,
                                   suffix: str = ".step") -> float | None:
    """Mean exposed collective time of a step (``steps``), in ms, on the
    device where it is largest."""
    worst = None
    for dev in trace.devices:
        per = [collective_exposed_s(st["ops"])
               for st in steps(trace, suffix, dev)]
        if per:
            ms = 1e3 * sum(per) / len(per)
            worst = ms if worst is None else max(worst, ms)
    return worst


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[span name, seconds], ...]: the idle time of the most idle device
    by what the host was doing: the part of each gap that a benchmark span
    covers goes to that span (the innermost first), the rest to "(none)";
    summed by name, largest first."""
    lo, hi = trace.bounds()
    b = busy(trace)["busy_s"]
    if not b:
        return []
    dev = min(b, key=b.get)
    gaps = subtract([(lo, hi)], union((o.start, o.end)
                                      for o in trace.devices[dev]))
    spans = trace.spans                       # sorted by start
    starts = [s.start for s in spans]
    longest = max((s.end - s.start for s in spans), default=0.0)
    by_name: dict = {}
    for g_lo, g_hi in gaps:
        i = bisect.bisect_left(starts, g_lo - longest)
        j = bisect.bisect_left(starts, g_hi)
        over = sorted((s for s in spans[i:j] if s.end > g_lo),
                      key=lambda s: s.end - s.start)
        left = [(g_lo, g_hi)]
        for s in over:
            rest = subtract(left, [(s.start, s.end)])
            covered = total(left) - total(rest)
            if covered > 0:
                by_name[s.name] = by_name.get(s.name, 0.0) + covered / 1e9
            left = rest
        if left:
            by_name["(none)"] = by_name.get("(none)", 0.0) + total(left) / 1e9
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[name, sec] for name, sec in rows[:n]]


def steps(trace: Trace, suffix: str = ".step",
          device: str | None = None) -> list[dict]:
    """Per host span whose name ends in ``suffix``: the step's dispatch on
    ``device`` (default: the first), which is the LONGEST program run whose
    midpoint lies inside the span (the loop's small programs beside it,
    slices and converts of a microsecond, are other dispatches), as
    ``{"span", "device_s", "ops"}``: ``device_s`` from that run's first op
    to its last, ``ops`` the ops inside it. Spans in which nothing ran on
    the device are left out."""
    if not trace.devices:
        return []
    device = device or sorted(trace.devices)[0]
    ops = trace.devices[device]
    mods = trace.modules.get(device, [])
    starts = [o.start for o in ops]
    mids = [(m.start + m.end) / 2 for m in mods]
    out = []
    for s in trace.spans:
        if not s.name.endswith(suffix):
            continue
        i = bisect.bisect_left(mids, s.start)
        j = bisect.bisect_right(mids, s.end)
        if j <= i:
            continue
        run = max(mods[i:j], key=lambda m: m.end - m.start)
        inside = ops[bisect.bisect_left(starts, run.start):
                     bisect.bisect_right(starts, run.end)]
        out.append({"span": s.name, "device_s": (run.end - run.start) / 1e9,
                    "ops": inside})
    return out


def class_seconds_per_step(trace: Trace, cls: str,
                           suffix: str = ".step") -> list[float]:
    """For each step (``steps``), the self seconds of class ``cls``."""
    out = []
    for st in steps(trace, suffix):
        ops = st["ops"]
        out.append(sum(s for o, s in zip(ops, self_times(ops))
                       if classify(o) == cls) / 1e9)
    return out
