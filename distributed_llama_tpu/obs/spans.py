"""Hierarchical span tracer: request → prefill/decode → layer → phase.

The reference's timing vocabulary is two buckets per token (I/T,
utils.cpp:104-106). This tracer carries the full hierarchy instead, on two
rails that share ONE naming scheme:

* **host spans** — ``SpanTracer.span("step", cat="decode")`` context
  managers around scheduler work (runtime/continuous.py), kept in a
  bounded ring buffer and exported as Chrome-trace/Perfetto JSON
  (``GET /debug/timeline``) or NDJSON;
* **device scopes** — ``jax.named_scope`` annotations threaded through the
  tp forward (parallel/tp.py) using the canonical names below, so a
  jax.profiler capture carries per-phase and per-collective labels that
  a reader of captures can bucket without guessing;
* **host phases** — ``host_phase("serve.fetch")`` puts what the host is
  doing on the PROFILER's clock, beside the device trace, so a capture's
  idle gaps split by phase. The ring's ``perf_counter`` shares nothing
  with the device trace; its decode spans have a twin here;
* **the start-up account** — ``startup_phase("pack")`` is a host phase
  named ``startup.pack`` whose wall seconds are also summed in ONE
  process-wide dict, beside every program JAX makes (by name, with the
  seconds of its tracing, lowering and compile or cache read), read with
  ``startup_account()``.

The scope names are the contract between the forward (which emits them)
and whatever reads a capture by them (benchmark/harness/reduce_trace.py
is the one reader of captures; ROADMAP D11 lists the names it takes).
Change them here or nowhere.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import re
import sys
import threading
import time
from collections import deque

# -- canonical device-scope names (parallel/tp.py emits these) -------------

SCOPE_EMBED = "embed"      # token embedding lookup
SCOPE_ATTN = "attn"        # qkv + rope + attention core + wo (+ its combine)
SCOPE_FFN = "ffn"          # ffn rmsnorm + swiglu + w2 (+ its combine)
SCOPE_LOGITS = "logits"    # final norm + wcls + logits gather
SCOPE_LAYER = "layer"      # the scanned layer body (parent of attn/ffn)
PHASE_SCOPES = (SCOPE_EMBED, SCOPE_ATTN, SCOPE_FFN, SCOPE_LOGITS)
# inside SCOPE_FFN of an expert spec (ops/pallas_moe.moe_ffn opens them)
SCOPE_MOE_ROUTER = "moe.router"    # router matmul, softmax, top-k
SCOPE_MOE_EXPERTS = "moe.experts"  # slot building, expert kernels, combine
# a spec with several residual streams (ops/hyper.py opens them, beside
# SCOPE_ATTN / SCOPE_FFN: a sub-layer's coefficients, and its two mixes)
SCOPE_HC_COEF = "hc.coef"   # flat norm, projection, sigmoids, Sinkhorn
SCOPE_HC_MIX = "hc.mix"     # the sub-layer's input and the streams' update
# a mixer-kinds spec's (models/laguna.py opens them inside SCOPE_ATTN): the
# per-head output gate, each kind's own RoPE, the output's ``value_scale``
# (an op of its own after the decode kernels' fold and the chunk's) and the
# softmax's sink column where XLA computes it (``models/llama.
# attention_core``; inside the decode kernels it is the walk's first carry
# and no op)
SCOPE_ATTN_GATE = "attn.gate"
SCOPE_ATTN_SCALE = "attn.scale"
SCOPE_ATTN_SINK = "attn.sink"
# a latent spec's with noise heads, rings and PolyNorm (models/latent.py
# opens the first two inside SCOPE_ATTN beside SCOPE_ATTN_GATE, its
# elementwise gate; ops/linear.polynorm the third inside SCOPE_FFN): the
# noise heads' subtraction with its per-token lambda and the one W_UV
# product after it, a sliding layer's write of its ring, the activation
SCOPE_ATTN_DIFF = "attn.diff"
SCOPE_RING_WRITE = "ring.write"
SCOPE_POLYNORM = "ffn.polynorm"
# an ssd spec's Mamba-2 layers (models/nemotron.py opens them inside
# SCOPE_ATTN; an expert layer's moe.router / moe.experts lie in SCOPE_FFN):
# the projections in and out, the convolution and its activation, the
# state's update and read (the decode kernel ``ops/mamba2.DECODE_KERNEL`` or
# a chunk's matrix products), the gate and the grouped norm
SCOPE_SSD_PROJ = "ssd.proj"
SCOPE_SSD_CONV = "ssd.conv"
SCOPE_SSD_SCAN = "ssd.scan"
SCOPE_SSD_GATE_NORM = "ssd.gate_norm"
# inside SCOPE_ATTN of a kda spec's Kimi-Delta-Attention layer
# (models/kda.py): the projection in, the three convolutions and their
# activation, the q / k norms with the decay and the write strength, the
# state's update and read (the decode kernel ``ops/kda.DECODE_KERNEL`` or a
# chunk's matrix products and its triangular solve), the output's norm and
# gate
SCOPE_KDA_PROJ = "kda.proj"
SCOPE_KDA_CONV = "kda.conv"
SCOPE_KDA_GATE = "kda.gate"
SCOPE_KDA_SCAN = "kda.scan"
SCOPE_KDA_OUT_NORM = "kda.out_norm"


def scope_rope(kind: str) -> str:
    return f"rope.{kind}"

# collective scopes: one per _ici_* helper, named after the helper so a
# trace event inside e.g. `ici_all_gather` is attributable to the exact
# budget term in comm_stats.tp_collective_budget. The mapping to budget
# KINDS mirrors the budget's own accounting: a psum_scatter is charged as
# the reduce_scatter half of the fused Q80 combine.
ICI_SCOPE_PREFIX = "ici_"
SCOPE_ICI_GATHER = "ici_all_gather"
SCOPE_ICI_PSUM = "ici_psum"
SCOPE_ICI_SCATTER = "ici_psum_scatter"
SCOPE_ICI_PPERMUTE = "ici_ppermute"
COLLECTIVE_SCOPE_KINDS = {
    SCOPE_ICI_GATHER: "all_gather",
    SCOPE_ICI_PSUM: "psum",
    SCOPE_ICI_SCATTER: "reduce_scatter",
    SCOPE_ICI_PPERMUTE: "ppermute",
}

# -- host phases on the profiler's clock -----------------------------------


_annotation = None  # jax.profiler.TraceAnnotation, resolved on first use


def host_phase(name: str, **args):
    """A host span in the profiler's own trace (a context manager), on the
    clock the device trace uses. The ONE place the program names the
    profiler's annotation: with no capture running it costs about what a
    ``contextlib.nullcontext()`` does, so it has no switch. Names are a
    contract with whoever reads a capture (PERF.md section 3 lists them);
    none ends in ``.step``, which the benchmark's own spans use. ``args``
    become the event's arguments (a request's trace id).

    Two of the names carry the scheduler's admission account into a
    capture: ``serve.land`` opens at the instant a step's results are on
    the host (parent of ``serve.census`` and ``serve.sample``), and holds
    one empty ``serve.land.chunk`` per admission prefill chunk that stood
    before that step on the device queue."""
    global _annotation
    if _annotation is None:  # obs/ imports without JAX
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name, **args)


def named_program(name: str, fn):
    """``fn`` under a ``__name__`` of its own, for ``jax.jit`` to read: a
    capture's "XLA Modules" line then shows each run as ``jit_<name>``,
    where a ``functools.partial`` or a lambda reads ``jit__unknown`` and
    two uses of one function read alike. A wrapper, because ``fn`` may be
    shared (one forward is both the decode step and the prefill chunk)."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return program


# -- the start-up account ---------------------------------------------------
#
# What a process spends before it serves, in two halves. PHASES: the wall
# seconds of each ``startup_phase`` (load, pack, place, cache, engine), a
# phase's own time only, so nested phases add up to the wall time they
# cover. PROGRAMS: every program JAX makes, by the name ``named_program``
# gave it, from ONE pair of ``jax.monitoring`` listeners. What JAX 0.9.0
# sends (checked against the installed ``jax/_src``: ``pjit.py``,
# ``interpreters/pxla.py``, ``compiler.py``): the three durations below
# carry ``fun_name=`` (the bare name for tracing, ``jit(<name>)`` for the
# other two); a fetch from the persistent cache fires the plain event
# ``cache_hits`` (no name) on the compiling thread INSIDE that program's
# ``backend_compile_duration``, whose duration then is the read, not a
# compile. Tracing, lowering and the compile of one program follow each
# other on one thread, so what is pending on a thread when a compile ends
# is that program's. A program traced INSIDE another's tracing (a jitted
# helper) fires a tracing event of its own and no compile: it is dropped,
# its seconds being part of the outer program's.

JAX_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JAX_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
JAX_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JAX_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
PROGRAM_HOWS = ("compiled", "cache")
STARTUP_PHASES = ("load", "pack", "place", "cache", "engine")  # as printed
_JIT_NAME = re.compile(r"^\w+\((.*)\)$")    # ``jit(serve_decode_step)``


class _Account:
    def __init__(self):
        self.lock = threading.Lock()
        self.local = threading.local()  # phase stack, pending program
        self.phases: dict = {}          # name -> own seconds, first-opened
        self.bytes_placed = 0
        self.programs: dict = {}        # name -> how -> seconds and count
        self.listening = False
        self.closed = False             # the summary is out: log each make
        self.sinks: list = []           # fn(program, how, seconds)


_account = _Account()


def _listen() -> None:
    """Register the ONE pair of listeners, at the account's first use
    (JAX has no call to take a listener off, so never twice)."""
    with _account.lock:
        if _account.listening:
            return
        _account.listening = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _pending() -> dict:
    p = getattr(_account.local, "pending", None)
    if p is None:
        p = _account.local.pending = {"trace": {}, "lower": {}, "hit": False}
    return p


def _on_event(event: str, **_kw) -> None:
    if event == JAX_CACHE_HIT_EVENT:
        _pending()["hit"] = True


def _on_duration(event: str, duration: float, fun_name: str = "",
                 **_kw) -> None:
    if event not in (JAX_TRACE_EVENT, JAX_LOWER_EVENT, JAX_COMPILE_EVENT):
        return
    m = _JIT_NAME.match(fun_name)
    name = m.group(1) if m else fun_name
    p = _pending()
    if event == JAX_TRACE_EVENT:
        p["trace"][name] = p["trace"].get(name, 0.0) + duration
        return
    if event == JAX_LOWER_EVENT:
        p["lower"][name] = p["lower"].get(name, 0.0) + duration
        return
    how = "cache" if p["hit"] else "compiled"
    trace_s, lower_s = p["trace"].get(name, 0.0), p["lower"].get(name, 0.0)
    p["trace"].clear()
    p["lower"].clear()
    p["hit"] = False
    with _account.lock:
        row = _account.programs.setdefault(name, {}).setdefault(
            how, {"makes": 0, "trace_s": 0.0, "lower_s": 0.0,
                  "backend_s": 0.0})
        row["makes"] += 1
        row["trace_s"] += trace_s
        row["lower_s"] += lower_s
        row["backend_s"] += duration
        closed, sinks = _account.closed, list(_account.sinks)
    seconds = trace_s + lower_s + duration
    for sink in sinks:
        sink(name, how, seconds)
    if closed:
        from .log import log_event

        log_event("program.made",
                  f"program made: {name} {seconds:.2f} s ({how})",
                  file=sys.stderr, program=name, seconds=round(seconds, 4),
                  how=how)


@contextlib.contextmanager
def startup_phase(name: str):
    """``host_phase("startup." + name)``, with its wall seconds added to
    the process's start-up account under ``name``: a phase's OWN seconds
    (less the phases opened inside it), so the account's phases add up to
    the wall time they cover. It synchronises nothing: a phase measures
    its call as the call is (a placement that only enqueues reads short).
    Also usable as a decorator (``Engine.__init__``)."""
    _listen()
    stack = getattr(_account.local, "stack", None)
    if stack is None:
        stack = _account.local.stack = []
    with _account.lock:
        _account.phases.setdefault(name, 0.0)
    frame = [0.0]                # seconds of the phases opened inside
    stack.append(frame)
    t0 = time.perf_counter()
    try:
        with host_phase("startup." + name):
            yield
    finally:
        whole = time.perf_counter() - t0
        stack.pop()
        if stack:
            stack[-1][0] += whole
        with _account.lock:
            _account.phases[name] += whole - frame[0]


def startup_placed(tree) -> None:
    """``startup.place`` put ``tree``'s arrays on the devices: their bytes
    go to the account."""
    import jax

    nbytes = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))
    with _account.lock:
        _account.bytes_placed += nbytes


def startup_account() -> dict:
    """The account as a plain dict (``json.dumps`` takes it): ``phases``
    {name: seconds} in the order first opened, ``bytes_placed``, and
    ``programs`` {name: {how: {makes, trace_s, lower_s, backend_s}}} with
    ``how`` one of ``PROGRAM_HOWS``. A copy: the account keeps counting."""
    with _account.lock:
        return _snapshot()


def _snapshot() -> dict:      # under the account's lock
    return {"phases": dict(_account.phases),
            "bytes_placed": _account.bytes_placed,
            "programs": {n: {h: dict(r) for h, r in hows.items()}
                         for n, hows in _account.programs.items()}}


def program_seconds(row: dict) -> float:
    """Tracing, lowering and compile or cache read of one account row."""
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


def startup_line(account: dict | None = None) -> str:
    """The operator's one line: ``startup: load 0.0 pack 6.1 ... | programs
    7 made, 4.9 s (cache 7, compiled 0): serve_decode_step 1.9, ...``;
    every program by name, the slowest first, marked ``(compiled)`` where
    it was not read from the persistent cache; ``place`` with the bytes
    it put on the devices."""
    acc = account or startup_account()
    order = {n: i for i, n in enumerate(STARTUP_PHASES)}
    phases = " ".join(
        f"{n} {s:.1f}" + (f" ({acc['bytes_placed'] / 2**30:.2f} GiB)"
                          if n == "place" else "")
        for n, s in sorted(acc["phases"].items(),
                           key=lambda kv: order.get(kv[0], len(order))))
    count = {h: sum(hows[h]["makes"] for hows in acc["programs"].values()
                    if h in hows) for h in PROGRAM_HOWS}
    by_name = sorted(
        ((sum(program_seconds(r) for r in hows.values()), n,
          " (compiled)" if "compiled" in hows else "")
         for n, hows in acc["programs"].items()), reverse=True)
    names = ", ".join(f"{n} {s:.1f}{how}" for s, n, how in by_name)
    return (f"startup: {phases or 'no phase'} | programs "
            f"{sum(count.values())} made, "
            f"{sum(s for s, _, _ in by_name):.1f} s (cache {count['cache']}, "
            f"compiled {count['compiled']}){': ' + names if names else ''}")


def log_startup() -> dict:
    """Print ``startup_line`` on stderr (under ``--log-json`` the same
    fields as one ``startup.summary`` record) and close start-up: every
    program made from here on is logged as it is made (``program.made``).
    Returns the account it printed."""
    from .log import log_event

    acc = startup_account()
    with _account.lock:
        _account.closed = True
    log_event("startup.summary", startup_line(acc), file=sys.stderr, **acc)
    return acc


def on_program_made(sink) -> dict:
    """Call ``sink(program, how, seconds)`` for every program made from
    now on (the ``/metrics`` registry's feed; it runs inside JAX's
    listener and must not raise); ``off_program_made`` takes it away.
    Returns the account as it stood when the sink went on, so a copy and
    the feed after it count every program once."""
    _listen()
    with _account.lock:
        _account.sinks.append(sink)
        return _snapshot()


def off_program_made(sink) -> None:
    with _account.lock:
        if sink in _account.sinks:
            _account.sinks.remove(sink)


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed host span. Times are ``time.perf_counter`` seconds;
    ``depth`` is the nesting level at record time (0 = top level on its
    thread) so exports can rebuild the hierarchy without parent ids."""

    name: str
    cat: str
    t_start: float
    dur_s: float
    tid: int
    depth: int
    meta: dict


class SpanTracer:
    """Thread-safe bounded span recorder.

    ``span()`` is a context manager: it stamps perf_counter on entry and
    records the completed span on exit (exceptions included — a failed
    step still shows up in the timeline, with ``error`` in its meta).
    Each thread keeps its own nesting stack; the buffer is a deque so a
    long-lived server holds the most recent ``capacity`` spans only —
    and overflow is COUNTED, not silent (ISSUE 15 satellite): every
    span the ring evicted bumps ``dropped`` (mirrored into
    ``dllama_spans_dropped_total`` via ``on_drop``) and every export
    carries the count, so a truncated timeline reads as truncated
    instead of quietly misleading.
    """

    def __init__(self, capacity: int = 4096, on_drop=None):
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.epoch = time.perf_counter()
        self.dropped = 0       # spans evicted by the ring bound
        self.on_drop = on_drop  # e.g. the dllama_spans_dropped_total .inc

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "phase", **meta):
        stack = self._stack()
        depth = len(stack)
        stack.append(name)
        t0 = time.perf_counter()
        try:
            yield
        except BaseException as e:
            meta = dict(meta, error=f"{type(e).__name__}: {e}")
            raise
        finally:
            stack.pop()
            self.add(name, cat, t0, time.perf_counter() - t0,
                     depth=depth, **meta)

    def add(self, name: str, cat: str, t_start: float, dur_s: float,
            depth: int = 0, **meta) -> None:
        """Record an already-timed span (e.g. a request's admit→finish
        window derived from its lifecycle timestamps at retirement)."""
        sp = Span(name, cat, t_start, max(dur_s, 0.0),
                  threading.get_ident(), depth, meta)
        overflowed = False
        with self._lock:
            if (self._spans.maxlen is not None
                    and len(self._spans) == self._spans.maxlen):
                # the append below evicts the oldest span: the ring
                # overflow the exports must report
                self.dropped += 1
                overflowed = True
            self._spans.append(sp)
        if overflowed and self.on_drop is not None:
            self.on_drop()

    def snapshot(self, trace_id: str | None = None) -> list:
        """Recorded spans, oldest first; ``trace_id`` filters to one
        trace's spans (the ``/debug/timeline?trace=<id>`` view)."""
        with self._lock:
            spans = list(self._spans)
        if trace_id is not None:
            spans = [s for s in spans
                     if s.meta.get("trace_id") == trace_id]
        return spans

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    # -- exports -----------------------------------------------------------

    def export_chrome(self, trace_id: str | None = None) -> dict:
        """Chrome-trace (Perfetto-loadable) JSON object: complete ('X')
        events, ts/dur in microseconds relative to the tracer epoch. The
        top-level ``dropped`` field counts ring-evicted spans — a viewer
        (or CI) can tell a short timeline from a truncated one."""
        doc = spans_to_chrome(self.snapshot(trace_id), self.epoch)
        doc["dropped"] = self.dropped
        return doc

    def export_ndjson(self, trace_id: str | None = None) -> str:
        """One JSON object per span per line — the log-shipper export
        (and tools/tracejoin.py's input). A final ``_meta`` record
        reports ring overflow whenever any span was dropped."""
        out = []
        for s in self.snapshot(trace_id):
            rec = {"span": s.name, "cat": s.cat,
                   "t_start_s": round(s.t_start - self.epoch, 6),
                   "dur_ms": round(s.dur_s * 1e3, 3),
                   "tid": s.tid, "depth": s.depth}
            rec.update(s.meta)
            out.append(json.dumps(rec))
        if self.dropped:
            out.append(json.dumps({"span": "_meta", "cat": "meta",
                                   "dropped": self.dropped}))
        return "\n".join(out) + ("\n" if out else "")


def spans_to_chrome(spans: list, epoch: float = 0.0) -> dict:
    """Spans → Chrome trace-event JSON (the ``traceEvents`` array form,
    which both chrome://tracing and Perfetto load)."""
    events = []
    for s in spans:
        events.append({
            "name": s.name, "cat": s.cat, "ph": "X",
            # clamp: re-anchored spans (monotonic→perf_counter) can land a
            # hair before the tracer epoch on platforms where the two
            # clocks differ; the viewer needs non-negative timestamps
            "ts": max(round((s.t_start - epoch) * 1e6, 3), 0.0),
            "dur": round(s.dur_s * 1e6, 3),
            "pid": os.getpid(), "tid": s.tid,
            "args": dict(s.meta, depth=s.depth),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def validate_chrome_trace(obj) -> None:
    """Schema-check a Chrome trace object (the CI-artifact gate): raises
    ValueError naming the first offending event rather than letting a
    malformed artifact be archived and discovered dead in a viewer."""
    if not isinstance(obj, dict) or not isinstance(
            obj.get("traceEvents"), list):
        raise ValueError("chrome trace must be an object with a "
                         "'traceEvents' array")
    for i, ev in enumerate(obj["traceEvents"]):
        if not isinstance(ev, dict):
            raise ValueError(f"traceEvents[{i}]: not an object")
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            raise ValueError(f"traceEvents[{i}]: missing/empty 'name'")
        ph = ev.get("ph")
        if ph not in ("X", "B", "E", "i", "M", "C"):
            raise ValueError(f"traceEvents[{i}]: bad phase {ph!r}")
        if not isinstance(ev.get("ts"), (int, float)) or ev["ts"] < 0:
            raise ValueError(f"traceEvents[{i}]: bad 'ts' {ev.get('ts')!r}")
        if ph == "X" and (not isinstance(ev.get("dur"), (int, float))
                          or ev["dur"] < 0):
            raise ValueError(f"traceEvents[{i}]: 'X' event needs a "
                             f"non-negative 'dur'")
        if "args" in ev and not isinstance(ev["args"], dict):
            raise ValueError(f"traceEvents[{i}]: 'args' must be an object")
