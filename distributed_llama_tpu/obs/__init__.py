"""Serving telemetry: metrics registry, request tracing, structured logs,
profiler hooks.

Stdlib-only observability for the serving stack (the reference's only
instrument is one end-of-run benchmark line, tokenizer.cpp:381):

* ``obs.metrics`` — thread-safe Counter/Gauge/Histogram + Registry with
  Prometheus text exposition (``GET /metrics``);
* ``obs.trace`` — per-request lifecycle instruments (queue wait, TTFT,
  per-token decode latency) and engine step/occupancy accounting;
* ``obs.log`` — optional NDJSON event log (``DLLAMA_LOG_JSON=1``) behind
  the existing 🌐/⏩/🔶 print sites;
* ``obs.profiler`` — guarded jax.profiler captures (``POST /profile``,
  ``DLLAMA_PROFILE_DIR``);
* ``obs.spans`` — hierarchical span tracer (request → prefill/decode →
  layer → phase) + the canonical jax.named_scope names the tp forward
  emits; Chrome-trace/Perfetto + NDJSON exports (``GET /debug/timeline``);
  ``host_phase`` puts the host's phases on the profiler's clock and
  ``named_program`` names the programs a capture shows;
* ``obs.slo`` — declarative SLO policies (priority classes with TTFT +
  per-token budgets) and the per-request verdict tracker behind
  ``dllama_slo_requests_total{class,verdict}`` / goodput accounting and
  the /health "slo" block (tools/loadcheck.py's gate);
* ``obs.tracectx`` — the W3C-traceparent-style distributed trace
  context (one id producer; minted at request ingress, carried through
  journal records, the disagg handoff, and the page channel so a
  recovered/handed-off request continues the SAME trace —
  ``tools/tracejoin.py`` stitches two pools' exports on it);
* ``obs.flightrec`` — the crash-forensics flight recorder: always-on
  event ring dumped as a postmortem bundle (spans + metrics + journal
  tail + config fingerprint) on watchdog trips, SIGTERM drains, and
  crash-loop respawns, validated by ``tools/tracecheck.py``;
* ``obs.fleet`` — the fleet signal plane: per-replica /health+/metrics
  rows + count-summed rollups with scrape-age staleness accounting
  (``tools/fleetcheck.py``; the signal surface the multi-replica router
  consumes);
* ``obs.watch`` — the watchtower (ISSUE 20): per-replica signal ring of
  integer snapshot deltas, seven pure detectors with pinned thresholds
  + hysteresis, incidents with evidence rows + trace ids, auto-dumped
  flight-recorder forensics (``GET /debug/incidents``,
  ``dllama_incidents_total{kind}``; ``tools/watchcheck.py`` holds the
  detection matrix in CI).

Collection is opt-in: hot paths hold a None handle when disabled and make
zero registry calls (tests/test_obs.py pins this).
"""

from .log import json_mode, log_event
from .metrics import (Counter, Gauge, Histogram, Registry, summarize_values)
from .slo import SLOClass, SLOPolicy, SLOTracker
from .spans import SpanTracer, spans_to_chrome, validate_chrome_trace
from .trace import EngineMetrics

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "EngineMetrics",
           "SLOClass", "SLOPolicy", "SLOTracker",
           "SpanTracer", "spans_to_chrome", "validate_chrome_trace",
           "json_mode", "log_event", "summarize_values"]
