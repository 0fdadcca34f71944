"""What every driver needs around the system under test: the device check,
the compile cache, a count of programs made, the profiler window, the
record a run hands to the metric readers, and small statistics."""

from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import tempfile
import time

from .cells import ROOT

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_SPAN = "bench.window"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def note(msg: str) -> None:
    """Narration goes to stderr: stdout's last line is the result."""
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


_T0 = time.monotonic()


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a FIXED path: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_cache/`` at the root of
    the checkout (the same rule as ``utils/compile_cache.py``, so the
    program and the benchmark agree). The path is part of the cache's key:
    a directory that moves never hits."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_devices(chips: int, rehearse: bool) -> dict:
    """The device triple as JAX reports it. Off a TPU, or with fewer chips
    than the cell needs, this raises: a measurement path that finds no chip
    fails and does not fall back. ``rehearse`` (tests only) lets a CPU run
    through; its result is marked not correct."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s); jax sees "
                            f"{len(devs)} x {dev['kind']!r}")
    if dev["platform"] != "tpu" and not rehearse:
        raise NoAccelerator(
            f"jax sees {len(devs)} x {dev['kind']!r} on platform "
            f"{dev['platform']!r}, not a TPU; a number from this backend "
            f"would not be a device metric")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend reports
    none, as the CPU's does)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts programs made in this process: every backend compile, and
    every fetch of one from the persistent cache (JAX reports both under
    one event). Inside the measured window the count must stay 0."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


class Tracer:
    """The profiler over the first ``seconds`` of the window, in a traced
    run. The Python tracer is OFF: it records every Python call, and on the
    chip it doubled the host's time per token and put seconds of pause into
    each generation's tokenizer call (PERF.md, findings of PR 22), which
    would be read as device idle time. Host spans are ``TraceAnnotation``s
    only. The trace lives under ``TMPDIR`` and is removed after it is read.
    """

    def __init__(self, seconds: float, keep: str | None = None):
        self.seconds = float(seconds)
        self.keep = keep
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.active = False
        self._window = None
        self._t0 = 0.0

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = span(WINDOW_SPAN)
        self._window.__enter__()
        self._t0 = time.monotonic()
        self.active = True

    def due(self) -> bool:
        return self.active and time.monotonic() - self._t0 >= self.seconds

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self.active = False
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def finish(self):
        """Stop if still on, read the trace, remove its files."""
        from . import reduce_trace

        self.stop()
        try:
            trace = reduce_trace.load(reduce_trace.find_xplane(self.dir))
            if self.keep:
                shutil.copytree(self.dir, self.keep, dirs_exist_ok=True)
            return trace
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    """A host span on the profiler's clock (a no-op outside a trace)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def wrap_span(obj, attr: str, name: str) -> None:
    """Wrap the PUBLIC call ``obj.attr`` in a host span, on this instance
    only. Spans come from the benchmark's files; spans inside the program
    are a later ``tracing`` issue."""
    import functools

    fn = getattr(obj, attr)

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with span(name):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    cell: object                   # cells.Cell
    seed: int
    window_s: float                # the measured window, seconds
    setup_s: float                 # process start to the first request
    records: list                  # per request: see harness/client.py
    device: dict
    counters_before: dict = dataclasses.field(default_factory=dict)
    counters_after: dict = dataclasses.field(default_factory=dict)
    trace: object = None           # reduce_trace.Trace in a traced run
    checks: list = dataclasses.field(default_factory=list)
    # ^ correctness: [{"what", "ok", "detail"}]

    def delta(self, key: str) -> float:
        return self.counters_after[key] - self.counters_before[key]

    def in_window(self) -> list:
        """Requests due inside the window that completed correctly."""
        return [r for r in self.records
                if r["ok"] and 0 <= r["due"] < self.window_s]

    def gaps_ms(self) -> list:
        """Gaps between consecutive SAMPLED tokens of a request, over the
        requests of the window."""
        out = []
        for r in self.in_window():
            st = r["stamps"]
            out.extend((b - a) * 1e3 for a, b in zip(st, st[1:]))
        return out

    def ttft_ms(self) -> list:
        """First sampled token minus when the request was DUE."""
        return [(r["stamps"][0] - r["due"]) * 1e3 for r in self.in_window()
                if r["stamps"]]


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile; None of an empty list (the reader
    then returns nothing and the metric is left out of the line)."""
    if not values:
        return None
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def median(values) -> float | None:
    return percentile(values, 50)
