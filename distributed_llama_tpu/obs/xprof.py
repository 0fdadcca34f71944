"""xprof capture loader: device events → per-phase / per-collective time.

Input is either of:

* a **jax.profiler capture directory** — the ``POST /profile`` /
  ``DLLAMA_PROFILE_DIR`` output. The newest ``*.xplane.pb`` is walked with
  the same plane/line selection as utils/it_split.py, and each device-op
  event is bucketed by the canonical scope names of obs/spans.py when they
  survive into the op/fusion names (jax.named_scope threads them through
  parallel/tp.py). Events carrying no recognizable scope land in
  ``unattributed`` — coverage is REPORTED, never assumed. Op events carry
  no byte counts, so ``bytes`` is None on this path (the drift gate then
  checks counts and time only).
* a **synthetic capture fixture** (``*.json``, schema ``dllama-trace/1``)
  — the checked-in CPU-testable stand-in (tests/fixtures/traces/,
  generated by tools/make_trace_fixture.py). Fixtures aggregate events
  (total ns + occurrence count + total bytes) and carry the (model, tp,
  scheme, buffer) config header, so the whole attribute→reconcile
  pipeline runs in tier-1 without a TPU.

Output is one ``Attribution``: per-phase ms, per-collective-kind
count/bytes/time, total device ms, and the token count to divide by.
Collective events inside a phase scope count in BOTH tables (the phase
owns the wall time; the kind table feeds the drift reconciler).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

from .spans import COLLECTIVE_SCOPE_KINDS, PHASE_SCOPES

FIXTURE_SCHEMA = "dllama-trace/1"

# xplane event name → budget collective kind (comm_stats vocabulary).
# Ordered: reduce-scatter must match before the bare all-reduce pattern.
_KIND_PATTERNS = (
    ("reduce_scatter", re.compile(r"reduce[_-]scatter|psum[_-]scatter")),
    ("all_gather", re.compile(r"all[_-]gather")),
    ("psum", re.compile(r"all[_-]reduce|\bpsum\b|^psum")),
    ("all_to_all", re.compile(r"all[_-]to[_-]all")),
    # the overlap scheme's ring hop; named 'ppermute' to speak the
    # comm_stats budget vocabulary (a collective-permute in an xplane IS
    # the budget's ppermute term)
    ("ppermute", re.compile(r"collective[_-]permute|ppermute")),
    ("send_recv", re.compile(r"^send|^recv|\bsend\b|\brecv\b")),
)
_PHASE_RE = {p: re.compile(rf"(^|[/_.-]){p}([/_.-]|$)") for p in PHASE_SCOPES}


@dataclasses.dataclass
class CollectiveMeasure:
    """Totals over the capture for one collective kind; ``bytes`` is None
    when the source carries no byte counts (real op traces).
    ``overlap_ms`` is the share of this kind's wall time covered by
    concurrent compute (the latency-hiding the overlap scheme claims) —
    carried by fixtures (``overlap_ns`` per event) and by capture formats
    with per-event timestamps; None when the source cannot know (the
    duration-only xplane wrapper), in which case the drift gate's overlap
    check reports SKIP rather than inventing a verdict."""

    kind: str
    count: int = 0
    ms: float = 0.0
    bytes: int | None = None
    overlap_ms: float | None = None

    def add(self, n: int, ms: float, nbytes: int | None,
            overlap_ms: float | None = None) -> None:
        self.count += n
        self.ms += ms
        if nbytes is not None:
            self.bytes = (self.bytes or 0) + nbytes
        if overlap_ms is not None:
            self.overlap_ms = (self.overlap_ms or 0.0) + overlap_ms

    @property
    def overlap_fraction(self) -> float | None:
        """Fraction of this kind's time hidden behind compute, or None
        when the capture carries no overlap information."""
        if self.overlap_ms is None:
            return None
        return self.overlap_ms / self.ms if self.ms else 0.0


@dataclasses.dataclass
class Attribution:
    """A capture reduced to the drift observatory's join keys.

    ``counts_exact`` says whether collective occurrence counts are a
    faithful launch census (fixtures: yes; real captures: the trace window
    may include warmup/compile steps, so the reconciler holds counts to a
    ratio there instead of exact equality)."""

    tokens: int
    total_ms: float = 0.0
    phase_ms: dict = dataclasses.field(default_factory=dict)
    collectives: dict = dataclasses.field(default_factory=dict)
    config: dict = dataclasses.field(default_factory=dict)
    source: str = ""
    counts_exact: bool = True

    @property
    def attributed_ms(self) -> float:
        return sum(self.phase_ms.values())

    @property
    def coverage(self) -> float:
        """Fraction of device op time attributed to a named phase."""
        return self.attributed_ms / self.total_ms if self.total_ms else 0.0

    def phase_ms_per_token(self) -> dict:
        n = max(self.tokens, 1)
        out = {p: round(ms / n, 6) for p, ms in sorted(self.phase_ms.items())}
        out["unattributed"] = round(
            max(self.total_ms - self.attributed_ms, 0.0) / n, 6)
        return out

    def _bucket(self, name: str, scope: str, ms: float, count: int,
                nbytes: int | None, collective: str | None,
                overlap_ms: float | None = None) -> None:
        self.total_ms += ms
        parts = scope.split("/") if scope else []
        phase = next((p for p in reversed(parts) if p in PHASE_SCOPES), None)
        if phase is None:  # no explicit scope: match the event name itself
            for p, rx in _PHASE_RE.items():
                if rx.search(name.lower()):
                    phase = p
                    break
        kind = collective
        if kind is None:
            kind = next((COLLECTIVE_SCOPE_KINDS[p] for p in reversed(parts)
                         if p in COLLECTIVE_SCOPE_KINDS), None)
        if kind is None:
            kind = classify_collective(name)
        if kind is not None and phase is None:
            phase = "collective"  # labeled wire time outside any phase scope
        if phase is not None:
            self.phase_ms[phase] = self.phase_ms.get(phase, 0.0) + ms
        if kind is not None:
            self.collectives.setdefault(
                kind, CollectiveMeasure(kind)).add(count, ms, nbytes,
                                                  overlap_ms)


def classify_collective(name: str) -> str | None:
    """Budget-vocabulary collective kind for an op/event name, or None."""
    low = name.lower()
    for kind, rx in _KIND_PATTERNS:
        if rx.search(low):
            return kind
    return None


# -- loaders ---------------------------------------------------------------


def load_capture(path: str, tokens: int = 0) -> Attribution:
    """Load a capture directory / xplane file / fixture JSON.

    ``tokens`` overrides (fixtures) or supplies (xplane captures, which do
    not know the token count) the per-token divisor.
    """
    if os.path.isdir(path):
        if glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                     recursive=True):
            return parse_xplane_capture(path, tokens)
        fixture = os.path.join(path, "capture.json")
        if os.path.exists(fixture):
            return parse_fixture(fixture, tokens)
        raise FileNotFoundError(
            f"{path!r}: no *.xplane.pb and no capture.json — not a "
            f"profiler capture directory")
    if path.endswith(".ndjson"):
        raise ValueError(
            f"{path!r}: NDJSON (e.g. a saved /debug/timeline stream) is "
            f"not a capture — expected a {FIXTURE_SCHEMA!r} fixture .json "
            f"or a jax.profiler capture directory")
    if path.endswith(".json"):
        return parse_fixture(path, tokens)
    return parse_xplane_capture(path, tokens)


def parse_fixture(path: str, tokens: int = 0) -> Attribution:
    """Load a ``dllama-trace/1`` synthetic capture fixture."""
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) or doc.get("schema") != FIXTURE_SCHEMA:
        raise ValueError(f"{path!r}: expected schema {FIXTURE_SCHEMA!r}, "
                         f"got {doc.get('schema')!r}")
    n_tokens = int(tokens or doc.get("tokens", 0))
    if n_tokens <= 0:
        raise ValueError(f"{path!r}: fixture carries no positive token "
                         f"count and none was supplied")
    att = Attribution(tokens=n_tokens, config=dict(doc.get("config", {})),
                      source=path)
    for i, ev in enumerate(doc.get("events", ())):
        if not isinstance(ev, dict) or "dur_ns" not in ev:
            raise ValueError(f"{path!r}: events[{i}] is not an event "
                             f"object with 'dur_ns'")
        overlap = ev.get("overlap_ns")
        att._bucket(str(ev.get("name", "")), str(ev.get("scope", "")),
                    float(ev["dur_ns"]) / 1e6,
                    int(ev.get("occurrences", 1)),
                    ev.get("bytes"), ev.get("collective"),
                    None if overlap is None else float(overlap) / 1e6)
    return att


def parse_xplane_capture(path: str, tokens: int) -> Attribution:
    """Walk a real jax.profiler capture's device-op events.

    Reuses utils/it_split.py's plane/line selection and op-name filters
    (one parse discipline for both consumers) but keeps per-EVENT
    occurrence counts, which the DeviceSplit aggregation drops and the
    collective census needs. Totals are averaged over devices like
    it_split.summarize: the drift join is per-chip/token, matching
    comm_stats accounting.
    """
    from jax.profiler import ProfileData

    from ..utils.it_split import (_HLO_RE, _OP_RE, _SKIP_RE, _is_op_line,
                                  find_xplane)

    if tokens <= 0:
        raise ValueError("xplane captures carry no token count — pass "
                         "tokens explicitly (tracecheck --tokens)")
    per_name: dict[str, list] = {}
    n_dev = 0
    for plane in ProfileData.from_file(find_xplane(path)).planes:
        lines = list(plane.lines)
        has_xla_ops = any(ln.name == "XLA Ops" for ln in lines)
        plane_has_ops = False
        for line in lines:
            if not _is_op_line(plane.name, line.name, has_xla_ops):
                continue
            for ev in line.events:
                name = ev.name
                hlo = _HLO_RE.match(name)
                if hlo:
                    name = hlo.group(1)
                if _SKIP_RE.search(name) or not _OP_RE.match(name):
                    continue
                plane_has_ops = True
                rec = per_name.setdefault(name, [0.0, 0])
                rec[0] += float(ev.duration_ns)
                rec[1] += 1
        n_dev += plane_has_ops
    if not per_name:
        raise ValueError(f"no op events found in {path!r} (empty trace?)")
    n_dev = max(n_dev, 1)
    att = Attribution(tokens=tokens, source=path, counts_exact=False)
    for name, (ns, count) in per_name.items():
        # async collectives trace as start/done PAIRS: the -done event
        # carries wait time (counts toward the kind's ms) but is not a
        # second launch — only the start/base event counts
        is_done = "-done" in name or name.startswith("done")
        att._bucket(name, "", ns / 1e6 / n_dev,
                    0 if is_done else max(round(count / n_dev), 1),
                    None, None)
    return att
