"""jax.profiler capture hooks: on-demand server traces + one-shot env runs.

Two entry styles over one guarded capture:

* ``POST /profile`` (runtime/server.py) calls ``start_capture(dir, secs)``:
  the trace starts immediately and a daemon timer stops it after ``secs`` —
  the server keeps serving while the device trace accumulates, which is the
  whole point (profile UNDER load, not a synthetic run).
* ``DLLAMA_PROFILE_DIR`` covers one-shot CLI runs with no flag plumbing:
  frontend/cli.py treats it as a default for ``--profile``.

Only one capture can be active per process (jax.profiler is a process-wide
singleton); a second request gets a clean RuntimeError, which the server
surfaces as HTTP 409.
"""

from __future__ import annotations

import os
import threading
import time

_lock = threading.Lock()
_active_dir: str | None = None


def env_profile_dir() -> str | None:
    """DLLAMA_PROFILE_DIR, or None when unset/empty."""
    return os.environ.get("DLLAMA_PROFILE_DIR") or None


def capture_active() -> str | None:
    """The directory of the in-flight capture, or None."""
    with _lock:
        return _active_dir


def capture_options():
    """The profiler options every capture of this program starts with:
    the Python tracer OFF, host annotations (``obs/spans.host_phase``) on.
    JAX's default records every Python call: on the chip that ran the
    per-token loop at 24.9 instead of 12.0 ms/token and a capture then
    read 59 % of the device idle (PERF.md section 6). The benchmark's
    captures use the same levels, so an operator's capture shows the
    same phases at the same cost."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def start_capture(trace_dir: str, seconds: float) -> None:
    """Start a jax.profiler trace into ``trace_dir`` and schedule its stop
    ``seconds`` from now on a daemon thread. Raises RuntimeError if a
    capture is already running, ValueError on a non-positive or non-finite
    duration (json.loads accepts NaN/Infinity; either would kill the stop
    timer's sleep and wedge the capture open forever)."""
    import math

    if not seconds or not math.isfinite(seconds) or seconds <= 0:
        raise ValueError(f"profile duration must be positive and finite, "
                         f"got {seconds}")
    import jax

    global _active_dir
    with _lock:
        if _active_dir is not None:
            raise RuntimeError(f"a profile capture into {_active_dir} is "
                               f"already running")
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=capture_options())
        _active_dir = trace_dir

    def _stop():
        global _active_dir
        time.sleep(seconds)
        with _lock:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass  # a torn-down backend must not crash the timer thread
            _active_dir = None

    threading.Thread(target=_stop, daemon=True,
                     name="dllama-profile-stop").start()


def wait_capture(timeout: float = 30.0) -> bool:
    """Block until no capture is active (True) or ``timeout`` expires
    (False). Test/shutdown convenience."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if capture_active() is None:
            return True
        time.sleep(0.02)
    return capture_active() is None
