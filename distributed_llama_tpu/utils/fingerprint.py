"""Session fingerprint + run-config stamp, shared by bench rows and logs.

``env_fingerprint`` is the bench drift defense (ISSUE 3): the round-5
records drifted ±5-8% between sessions, so every bench row pins the
jax/runtime versions, the chip kind, and the clock source. ``run_stamp``
adds the active kernel-policy knobs (tp scheme, Q40 body policy) and is
stamped onto every ``--log-json``
NDJSON record (obs/log.py), so traces and log streams are JOINABLE with
bench rows: same fingerprint → same session basis, different → visibly
not comparable.
"""

from __future__ import annotations

import os
import sys
import time

# env_fingerprint cache, keyed by whether jax was importable at compute
# time: an early log event (weight streaming runs log BEFORE jax is
# imported) must not freeze a jax-less fingerprint for the whole process
_FP_CACHE: dict = {}


def env_fingerprint() -> dict:
    """jax/jaxlib versions, backend + device kind, and the clock source.

    Querying devices initializes jax's backend; when jax was never
    imported by this process (a log-only tool), the device fields are
    skipped rather than dragging a backend up from a log call.
    """
    out: dict = {}
    clock = time.get_clock_info("perf_counter")
    out["clock"] = clock.implementation
    out["clock_resolution_s"] = clock.resolution
    if "jax" not in sys.modules:
        return out
    import jax
    import jaxlib

    out["jax"] = jax.__version__
    out["jaxlib"] = jaxlib.__version__
    try:
        d = jax.devices()[0]
        out["backend"] = d.platform
        out["device_kind"] = getattr(d, "device_kind", "")
        out["n_devices"] = len(jax.devices())
    except Exception:  # noqa: BLE001 - a dead backend must not kill a log line
        pass
    return out


# the Q40 layout label the process last announced
# (ops/linear.announce_q40_layout); "unresolved" until one was
_Q40_BODY = "unresolved"


def stamp_q40_body(label: str) -> None:
    """Record the resolved Q40 layout label for every later run stamp."""
    global _Q40_BODY

    _Q40_BODY = label


def run_stamp() -> dict:
    """The joinability header: tp scheme + Q40 body policy + fingerprint.

    The knob fields are read FRESH per call (cheap env lookups): a
    ``--model-from-root`` run logs fetch-progress events before cli.py
    has exported ``--tp-scheme`` into the env, and a frozen early stamp
    would mislabel every later decode record. Only the fingerprint is
    cached, keyed by jax's import state for the same reason. Never
    raises — a malformed env var degrades the stamp, not the log line
    carrying it.
    """
    stamp: dict = {}
    try:
        from ..parallel.comm_stats import tp_scheme

        stamp["tp_scheme"] = tp_scheme()
    except Exception:  # noqa: BLE001
        stamp["tp_scheme"] = os.environ.get("DLLAMA_TP_SCHEME", "?")
    stamp["q40_body"] = _Q40_BODY
    key = "jax" in sys.modules
    if key not in _FP_CACHE:
        try:
            _FP_CACHE[key] = env_fingerprint()
        except Exception:  # noqa: BLE001
            _FP_CACHE[key] = {}
    stamp["env_fingerprint"] = _FP_CACHE[key]
    return stamp


def reset_stamp_cache() -> None:
    """Test hook: recompute the fingerprint after env changes."""
    _FP_CACHE.clear()
