"""HTTP inference server on the continuous-batching engine.

A minimal stdlib (http.server) API surface over runtime/continuous.py — the
serving layer the reference lacks entirely (its only interface is the argv
one-shot, main.cpp:38-63). Requests from concurrent clients stream through
the slot pool: admission happens mid-flight between device steps, so a short
request never waits for a long one to finish.

Endpoints:
  POST /generate  {"prompt": str, "steps"?: int, "temperature"?: float,
                   "topp"?: float, "seed"?: int, "stream"?: bool,
                   "class"?: str  (SLO priority class, --slo policy)}
               -> {"text": str, "tokens": [int], "steps": int}
               or, with "stream": true, chunked newline-delimited JSON:
               one {"token": int, "piece": str} line per token as it
               decodes, then a final {"done": true, "text": ..., "steps": N}
  GET  /health -> {"active": int, "queued": int, "slots": int,
                   "steps": int, "generated_tokens": int, "uptime_s",
                   "occupancy", (--spec-k on) a "speculative" block with
                   proposed/accepted/accept_rate, and (metrics on)
                   "ttft_s"/"token_latency_s"/"queue_wait_s" p50/p95/p99
                   summaries}
  GET  /metrics -> Prometheus text exposition of the obs registry (request
               lifecycle histograms, engine step/occupancy, counters, and
               the per-scheme collective schedule series)
  GET  /debug/timeline -> Chrome-trace/Perfetto JSON of the engine's recent
               spans (request → prefill/decode windows, obs/spans.py);
               ``?format=ndjson`` emits one span object per line instead
  GET  /debug/incidents -> the watchtower plane (obs/watch.py, ISSUE 20):
               detector states + incident log with evidence rows + the
               signal-ring tail; ``?kind=`` filters, ``?n=`` bounds the
               tails, ``?format=ndjson`` streams one incident per line;
               /health carries the compact "watch" heartbeat block and an
               incident dumps a reason="incident" flight-recorder bundle
  POST /profile  {"seconds"?: float, "dir"?: str} -> starts a jax.profiler
               capture into dir for N seconds WHILE SERVING (409 if one is
               already running) — profile under real load
  POST /prefill  (--disagg-role prefill only, ISSUE 14) the decode pool's
               internal handoff RPC: {"tokens": [ids], "steps": N, ...}
               -> the request's journal-record state + page-channel
               coordinates (or {"final": true} when the stream ended
               inside the prefill cut); /health gains a "disagg" block
               (role, peer, page channel, handoff queue depth) on both
               roles

Threading model: http.server's ThreadingHTTPServer handles each connection
on its own thread; handlers only encode, submit (thread-safe), and wait on
the request's done event. ONE scheduler thread owns the device loop
(ContinuousEngine.step_once), sleeping briefly when idle — the JAX step and
all slot state stay single-threaded.

Crash safety (ISSUE 9): with a write-ahead journal (``journal=``,
runtime/journal.py) the server recovers journaled in-flight requests at
construction, a step watchdog (``watchdog_s``, runtime/supervisor.py)
detects hung dispatches and degrades health, SIGTERM triggers a graceful
drain — stop admission (503), finish in-flight work within ``drain_s``,
journal the remainder, exit 0 — and the health state machine
(starting/serving/degraded/draining/stopped) is surfaced in ``/health``
and the ``dllama_health_state`` gauge.
"""

from __future__ import annotations

import json
import select
import signal
import socket
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

from ..io.tokenizer import Tokenizer
from ..models.spec import TransformerSpec
from ..obs import tracectx
from ..obs.log import log_event
from ..obs.spans import host_phase
from .continuous import ContinuousEngine, Request
from .supervisor import HealthMonitor, StepWatchdog

_IDLE_SLEEP_S = 0.002

# /health schema version, emitted as the payload's "schema" key so a
# fleet rollup can see version skew across replicas (absent on pre-
# schema replicas — obs/fleet treats that as 0). Keep equal to
# analysis/wiremodel.HEALTH_SCHEMA_VERSION (the registry cannot import
# the runtime; tests/test_wirecheck_repo.py pins the two equal) and
# bump BOTH when the payload gains or renames a key.
HEALTH_SCHEMA = 3


class OversizedRequest(ValueError):
    """A request the model literally cannot serve (prompt or steps beyond
    seq_len) — its own 400 + ``admission_rejected{reason="oversized"}``
    series, distinct from malformed-payload bad_request."""


def _peer_open(conn: socket.socket) -> bool:
    """False once the peer has closed its end of ``conn`` (an EOF or a
    reset is waiting to be read); never blocks, consumes nothing. A client
    that half-closes after its request and still reads is taken for gone,
    as by most HTTP servers."""
    try:
        ready = select.poll()
        ready.register(conn, select.POLLIN)
        return not ready.poll(0) or conn.recv(1, socket.MSG_PEEK) != b""
    except (OSError, ValueError):
        return False


class _BurstHTTPServer(ThreadingHTTPServer):
    """``ThreadingHTTPServer`` with a listen backlog that holds a burst.
    The stdlib's is 5: when a closed loop's 32 clients connect in the same
    instant, connections past it are reset (``ConnectionResetError`` at
    the client, a failed request: 1 of 8 runs of a 32-client cell on the
    chip, PR 31; 2 of 25 five-second runs, PR 29)."""

    request_queue_size = 256


class InferenceServer:
    """Owns the engine, the HTTP listener, and the scheduler thread."""

    def __init__(self, spec: TransformerSpec, params: dict[str, Any],
                 tokenizer: Tokenizer, host: str, port: int, slots: int,
                 steps: int, temperature: float, topp: float, seed: int,
                 cache_dtype=None, mesh=None, prefill_chunk: int = 0,
                 block_steps: int = 1, quiet: bool = False,
                 fast_prefill: bool = False, metrics: bool = True,
                 registry=None, page_size: int = 0, kv_pages: int = 0,
                 spec_k: int = 0, spec_ngram: int = 3,
                 dispatch_tokens: int = 0, slo=None,
                 chaos=None, journal=None, watchdog_s: float = 0.0,
                 drain_s: float = 10.0, kv_quant: str = "f32",
                 kv_host_pages: int = 0, kv_disk_dir: str | None = None,
                 kv_disk_bytes: int = 0, disagg_role: str | None = None,
                 disagg_peer: str | None = None,
                 page_channel_port: int = 0, handoff_min_pages: int = 2,
                 flightrec_dir: str | None = None,
                 watch_interval_s: float = 0.0, q40_layout=None):
        self.spec = spec
        self.tokenizer = tokenizer
        self.default_steps = steps
        self.quiet = quiet
        self.drain_s = drain_s
        # prefill/decode disaggregation (ISSUE 14): "prefill" serves
        # POST /prefill + the page channel; "decode" fronts clients and
        # forwards long prompts to ``disagg_peer`` (host:port of the
        # prefill server), ingesting the returned journal record + the
        # shipped pages. None = plain single-pool serving.
        if disagg_role not in (None, "prefill", "decode"):
            raise ValueError(f"disagg_role {disagg_role!r}: expected "
                             f"prefill|decode|None")
        if disagg_role is not None and page_size <= 0:
            raise ValueError("disaggregation ships KV PAGES: pass "
                             "page_size > 0 (--kv-page-size)")
        if disagg_role == "decode" and not disagg_peer:
            raise ValueError("--disagg-role decode needs --disagg-peer "
                             "HOST:PORT (the prefill server)")
        self.disagg_role = disagg_role
        self.disagg_peer = disagg_peer
        self.handoff_min_pages = max(1, handoff_min_pages)
        self._page_channel = None
        self._disagg_obs = None
        self._handoff_seq = 0
        # SLO policy (obs/slo.SLOPolicy) — verdicts per priority class in
        # /health + /metrics; ``chaos`` (runtime/chaos.ChaosMonkey) arms
        # deterministic fault injection for operator drills (--chaos)
        self.slo_policy = slo
        # metrics default ON for the server (it IS the observability
        # surface); --no-metrics turns collection off, and /metrics then
        # 404s. Each server gets its OWN registry unless one is injected —
        # two servers in one process must not sum their counters.
        if metrics:
            from ..obs.metrics import Registry

            self.registry = registry if registry is not None else Registry()
        else:
            self.registry = None
        self._t_start = time.monotonic()
        # crash-safety surface (ISSUE 9): the health state machine is
        # always on (a journal-less server still reports starting/serving/
        # draining/stopped); the watchdog and journal are opt-in knobs
        self.health = HealthMonitor(self.registry)
        self.journal = journal
        # crash-forensics flight recorder (ISSUE 15): the ring is ALWAYS
        # recording (cheap); bundle files land in flightrec_dir when the
        # watchdog fires or the SIGTERM drain runs (None = ring only)
        from ..obs.flightrec import FlightRecorder

        self.flightrec_dir = flightrec_dir
        self.flightrec = FlightRecorder(
            registry=self.registry,
            journal_path=journal.path if journal is not None else None,
            config=(dict(journal.config)
                    if journal is not None and journal.config else {}))
        self._watchdog = (StepWatchdog(watchdog_s, on_hang=self._on_hang)
                          if watchdog_s > 0 else None)
        self._drain_hist = (self.registry.histogram(
            "dllama_drain_seconds",
            "Graceful-drain duration: SIGTERM to in-flight work finished "
            "or journaled") if self.registry is not None else None)
        self.engine = ContinuousEngine(spec, params, slots, temperature,
                                       topp, seed, cache_dtype=cache_dtype,
                                       mesh=mesh,
                                       prefill_chunk=prefill_chunk,
                                       block_steps=block_steps,
                                       fast_prefill=fast_prefill,
                                       metrics=self.registry,
                                       page_size=page_size,
                                       kv_pages=kv_pages, spec_k=spec_k,
                                       spec_ngram=spec_ngram,
                                       dispatch_tokens=dispatch_tokens,
                                       slo=slo,
                                       chaos=chaos, journal=journal,
                                       watchdog=self._watchdog,
                                       kv_quant=kv_quant,
                                       kv_host_pages=kv_host_pages,
                                       kv_disk_dir=kv_disk_dir,
                                       kv_disk_bytes=kv_disk_bytes,
                                       remote_pages=(
                                           disagg_role == "decode"),
                                       slo_priority=(
                                           disagg_role == "prefill"
                                           and slo is not None),
                                       q40_layout=q40_layout)
        if disagg_role == "prefill":
            from .disagg import make_priority_hold
            from .page_channel import PageChannelServer

            # bind the channel on the same interface as the HTTP listener:
            # a 0.0.0.0 serve host means remote decode pools connect, and
            # the page channel must be reachable from exactly as far
            self._page_channel = PageChannelServer(
                host=host if host else "0.0.0.0",
                port=page_channel_port)
            if slo is not None:
                # SLO-aware admission: interactive prefills jump the
                # queue AND preempt batch prefills at page-aligned
                # chunk boundaries
                self.engine.prefill_hold = make_priority_hold(
                    self.engine, slo)
        if disagg_role is not None and self.registry is not None:
            from .disagg import DisaggMetrics

            self._disagg_obs = DisaggMetrics(self.registry)
        # the engine's span tracer feeds the flight recorder's bundle
        # (None when metrics are off — the ring of notes still records);
        # the census ring + ledger book (always on) ride along so a
        # crash bundle shows WHAT the scheduler was dispatching and
        # WHOSE requests were mid-flight (ISSUE 16)
        self.flightrec.bind(spans=self.engine._spans,
                            census=self.engine.sched_census,
                            ledgers=self.engine.ledger_book)
        self.flightrec.note("server.start", role=disagg_role or "single",
                            slots=slots, page_size=page_size)
        # incident-detection plane (ISSUE 20): always constructed — the
        # detectors run on every watch_tick() whether the periodic
        # supervisor loop is on (watch_interval_s > 0) or a test/sim
        # drives ticks by hand. A firing detector dumps a flight-
        # recorder bundle with reason="incident" + the detector kind.
        from ..obs.watch import Watchtower

        self.watch_interval_s = watch_interval_s
        self._watch = Watchtower(registry=self.registry,
                                 spans=self.engine._spans,
                                 on_incident=self._on_incident)
        self._watch_stop = threading.Event()
        # replay the previous life's unfinished requests BEFORE the
        # listener opens: recovered work re-queues first, so a restarted
        # server continues exactly where the crash cut it off
        self.recovered = (self.engine.recover(quiet=quiet)
                          if journal is not None else 0)
        if self.recovered:
            self.flightrec.note("server.recovered", n=self.recovered)
        self._shutdown = threading.Event()
        self._stopped = threading.Event()  # stop() ran to completion
        # live streaming-handler threads (the _stream loop): stop() joins
        # these AFTER waking their requests — a blocked q.get/done.wait
        # must not outlive the server (the thread-leak satellite)
        self._streams: set = set()
        self._streams_lock = threading.Lock()
        server = self

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 is required for Transfer-Encoding: chunked — on a
            # /1.0 status line RFC-compliant clients (curl) do not de-chunk
            # and would see raw chunk framing; the non-streaming path is
            # fine either way (it always sends Content-Length)
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet the per-request noise
                if not server.quiet:
                    log_event("http.request",
                              f"🌐 {self.address_string()} {fmt % args}",
                              client=self.address_string(),
                              line=fmt % args)

            def _json(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path.split("?")[0] == "/debug/timeline":
                    return self._timeline()
                if self.path.split("?")[0] == "/debug/sched":
                    return self._sched()
                if self.path.split("?")[0] == "/debug/incidents":
                    return self._incidents()
                if self.path == "/metrics":
                    if server.registry is None:
                        return self._json(404, {"error": "metrics disabled "
                                                "(--no-metrics)"})
                    body = server.registry.expose().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4; "
                                     "charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path != "/health":
                    return self._json(404, {"error": "unknown path"})
                self._json(200, server._health_payload())

            def _timeline(self):
                """GET /debug/timeline: the engine's recent span timeline
                (request → prefill/decode windows, obs/spans.py).
                Default: Chrome-trace JSON — save it and load it straight
                into Perfetto / chrome://tracing; ?format=ndjson streams
                one span object per line for log shippers;
                ?trace=<trace_id> filters to ONE distributed trace's
                spans (the cross-pool join view, ISSUE 15)."""
                from urllib.parse import parse_qs, urlparse

                spans = server.engine._spans
                if spans is None:
                    return self._json(404, {"error": "timeline disabled "
                                            "(--no-metrics)"})
                q = parse_qs(urlparse(self.path).query)
                trace_id = (q.get("trace") or [None])[0]
                if (q.get("format") or [None])[0] == "ndjson":
                    body = spans.export_ndjson(trace_id).encode()
                    ctype = "application/x-ndjson"
                else:
                    body = json.dumps(spans.export_chrome(trace_id)).encode()
                    ctype = "application/json"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _sched(self):
                """GET /debug/sched: the per-dispatch scheduler census
                ring + the cost-ledger state (ISSUE 16). Default: one
                JSON document (census totals + ring tail, open-ledger
                snapshots, closed tail, grand/per-class cost columns);
                ?format=ndjson streams one census record per line for
                log shippers; ?n=<k> bounds both tails (default 64)."""
                from urllib.parse import parse_qs, urlparse

                eng = server.engine
                q = parse_qs(urlparse(self.path).query)
                try:
                    n = int((q.get("n") or ["64"])[0])
                except ValueError:
                    return self._json(400, {"error": "n must be an "
                                            "integer"})
                census, book = eng.sched_census, eng.ledger_book
                if (q.get("format") or [None])[0] == "ndjson":
                    body = "".join(
                        json.dumps(r, sort_keys=True) + "\n"
                        for r in census.tail(n)).encode()
                    ctype = "application/x-ndjson"
                else:
                    doc = census.to_json(tail=n)
                    doc["open_ledgers"] = book.open_snapshots()
                    doc["closed_tail"] = book.closed_tail(n)
                    doc["cost_totals"] = book.grand_totals()
                    doc["cost_by_class"] = book.class_rollup()
                    body = json.dumps(doc).encode()
                    ctype = "application/json"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _incidents(self):
                """GET /debug/incidents: the watchtower's incident log
                + detector states + the signal-ring tail (ISSUE 20).
                Default: one JSON document (Watchtower.to_json);
                ``?format=ndjson`` streams one incident per line for
                log shippers; ``?n=<k>`` bounds the incident tail and
                the ring tail (default 64); ``?kind=<detector>``
                filters the ndjson stream to one detector kind."""
                from urllib.parse import parse_qs, urlparse

                q = parse_qs(urlparse(self.path).query)
                try:
                    n = int((q.get("n") or ["64"])[0])
                except ValueError:
                    return self._json(400, {"error": "n must be an "
                                            "integer"})
                kind = (q.get("kind") or [None])[0]
                watch = server._watch
                if (q.get("format") or [None])[0] == "ndjson":
                    body = "".join(
                        json.dumps(inc.to_json(), sort_keys=True) + "\n"
                        for inc in watch.incidents(n, kind)).encode()
                    ctype = "application/x-ndjson"
                else:
                    doc = watch.to_json(tail=n)
                    doc["incident_log"] = [
                        inc.to_json() for inc in watch.incidents(n, kind)]
                    body = json.dumps(doc).encode()
                    ctype = "application/json"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                if self.path == "/profile":
                    return self._profile()
                if self.path == "/prefill":
                    return self._prefill_handoff()
                if self.path != "/generate":
                    return self._json(404, {"error": "unknown path"})
                if server.health.state in ("draining", "stopped"):
                    # drain contract: admission stops FIRST; clients get a
                    # clean retryable refusal, never a dropped request
                    server.count_reject("draining")
                    return self._json(503, {"error": "server is draining; "
                                            "retry after restart"})
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(payload, dict):
                        raise ValueError("body must be a JSON object")
                    stream = bool(payload.get("stream", False))
                    req = server.make_request(payload)
                except OversizedRequest as e:
                    server.count_reject("oversized")
                    return self._json(400, {"error": str(e)})
                except (ValueError, KeyError, TypeError) as e:
                    server.count_reject("bad_request")
                    return self._json(400, {"error": str(e)})
                if server.disagg_role == "decode":
                    req, submit = server.remote_prefill(req)
                else:
                    submit = lambda r=req: server.engine.submit(r)  # noqa: E731
                # asked where the request leaves the queue: a client that
                # hung up while it waited gets no admission prefill
                req.alive = lambda c=self.connection: _peer_open(c)
                if stream:
                    return self._stream(req, submit)
                if submit is not None:
                    submit()
                req.done.wait()
                if req.error is not None:
                    return self._json(500, {"error": req.error})
                text = server.decode(req)
                self._json(200, {"text": text, "tokens": req.out,
                                 "steps": len(req.out)})

            def _prefill_handoff(self):
                """POST /prefill (prefill role, ISSUE 14): the decode
                pool's internal RPC. Body: {"tokens": [ids], "steps":
                N, "temperature"?, "topp"?, "seed"?, "class"?}. Runs
                prompt prefill + samples the FIRST token, publishes the
                full prompt pages on the page channel, and returns the
                request's journal-record state for the decode pool to
                re-admit — or {"final": true, ...} when the stream ended
                inside the prefill cut."""
                from .disagg import (encode_handoff_pages, entry_for_stub,
                                     prefill_stub, stub_needs_handoff)
                from .journal import entry_to_wire

                if server.disagg_role != "prefill":
                    return self._json(404, {"error": "not a prefill pool"})
                if server.health.state in ("draining", "stopped"):
                    server.count_reject("draining")
                    return self._json(503, {"error": "draining"})
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    tokens = [int(t) for t in payload["tokens"]]
                    steps = int(payload["steps"])
                    if not tokens or steps < 1 \
                            or len(tokens) > server.spec.seq_len:
                        raise ValueError(
                            f"bad handoff prompt/steps ({len(tokens)} "
                            f"tokens, {steps} steps)")
                    temp = payload.get("temperature")
                    topp = payload.get("topp")
                    seed = payload.get("seed")
                    slo_class = payload.get("class")
                except (ValueError, KeyError, TypeError) as e:
                    server.count_reject("bad_request")
                    return self._json(400, {"error": str(e)})
                # trace propagation (ISSUE 15): continue the decode
                # pool's trace from the RPC's traceparent — the recv
                # half of the clock-skew anchor pair tracejoin aligns
                # on. The drop-traceparent mutation severs it HERE.
                trace_hdr = payload.get("trace")
                chaos = server.engine._chaos
                if trace_hdr is not None and chaos is not None \
                        and chaos.trace_drop():
                    trace_hdr = None
                recv_parent = None
                if trace_hdr:
                    try:
                        recv_parent = tracectx.parse_header(str(trace_hdr))
                    except ValueError:
                        recv_parent = None
                recv = (recv_parent.child() if recv_parent is not None
                        else tracectx.mint())
                t_recv0 = time.perf_counter()
                stub, _ = prefill_stub(
                    tokens, steps,
                    temperature=None if temp is None else float(temp),
                    topp=None if topp is None else float(topp),
                    seed=None if seed is None else int(seed),
                    slo_class=slo_class)
                stub.trace = recv.child()
                server.engine.submit(stub)
                stub.done.wait()

                def recv_span(pages: int) -> None:
                    if server.engine._spans is not None:
                        from .disagg import HANDOFF_CAT, SPAN_HANDOFF_RECV

                        server.engine._spans.add(
                            SPAN_HANDOFF_RECV, HANDOFF_CAT, t_recv0,
                            time.perf_counter() - t_recv0, pages=pages,
                            **tracectx.span_fields(recv))

                if stub.error is not None:
                    return self._json(500, {"error": stub.error})
                if not stub_needs_handoff(stub):
                    if server._disagg_obs is not None:
                        # wirecheck: allow[W002] metric verdict label, not a wire key
                        server._disagg_obs.handoffs["local"].inc()
                    recv_span(0)
                    return self._json(200, {"final": True,
                                            "out": stub.out})
                try:
                    entry = entry_for_stub(server.engine, stub)
                except ValueError as e:  # sampled stream, no journal
                    return self._json(500, {"error": str(e)})
                payloads = server.engine.export_prefix_sync(tokens)
                records = encode_handoff_pages(payloads)
                hid = f"h{stub.index}"
                server._page_channel.publish(hid, records,
                                             trace=entry.trace)
                recv_span(len(records))
                if server._disagg_obs is not None:
                    from .pagewire import record_payload_bytes

                    obs = server._disagg_obs
                    # wirecheck: allow[W002] metric verdict label, not a wire key
                    obs.handoffs["shipped"].inc()
                    if records:
                        # PAYLOAD bytes (the DCN budget's unit — frame
                        # overhead excluded), the same accounting as
                        # DisaggPair: the series stays reconcilable
                        # against dcn_handoff_budget
                        obs.pages_shipped.inc(len(records))
                        obs.bytes_shipped.inc(sum(
                            record_payload_bytes(r) for r in records))
                    obs.queue_depth.set(server._page_channel.queue_depth)
                self._json(200, {
                    "record": entry_to_wire(entry),
                    "hid": hid, "n_pages": len(records),
                    "channel_port": server._page_channel.port})

            def _profile(self):
                """POST /profile: capture a jax.profiler trace for N
                seconds while the server keeps serving. One capture per
                process (jax.profiler is a singleton) -> 409 on overlap."""
                import tempfile

                from ..obs import profiler

                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(payload, dict):
                        raise ValueError("body must be a JSON object")
                    seconds = float(payload.get("seconds", 5.0))
                    trace_dir = payload.get("dir") \
                        or profiler.env_profile_dir() \
                        or tempfile.mkdtemp(prefix="dllama-profile-")
                    profiler.start_capture(trace_dir, seconds)
                except RuntimeError as e:  # capture already in flight
                    return self._json(409, {"error": str(e)})
                except OSError as e:
                    # unwritable/uncreatable trace dir (bad
                    # DLLAMA_PROFILE_DIR): a server-side env problem, and
                    # the capture never started — the next request may
                    # name a good dir
                    return self._json(500, {"error": f"trace dir: {e}"})
                except (ValueError, KeyError, TypeError) as e:
                    return self._json(400, {"error": str(e)})
                self._json(200, {"dir": trace_dir, "seconds": seconds})

            def _stream(self, req, submit=None):
                """Chunked newline-delimited JSON, one line per token.

                The scheduler thread only enqueues (on_token must never
                block the decode loop on a slow client socket); THIS
                handler thread drains the queue and does the blocking
                writes. ``submit`` hands the request to the engine AFTER
                the hook is registered (the disagg decode path passes an
                ingest closure; None with ``done`` already set means the
                request completed remotely — replay its tokens).
                """
                import queue

                q: queue.Queue = queue.Queue()
                req.on_token = q.put
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()

                def chunk(obj):
                    body = (json.dumps(obj) + "\n").encode()
                    self.wfile.write(f"{len(body):x}\r\n".encode() + body
                                     + b"\r\n")
                    self.wfile.flush()

                if submit is None and req.done.is_set():
                    # completed inside the peer's prefill cut: replay the
                    # finished stream as one burst
                    try:
                        prev = req.tokens[0]
                        for tok in req.out:
                            piece = server.tokenizer.decode_piece(prev,
                                                                  tok)
                            prev = tok
                            chunk({"token": tok,
                                   "piece": piece.decode(
                                       "utf-8", errors="replace")})
                        if req.error is not None:
                            chunk({"done": True, "error": req.error})
                        else:
                            chunk({"done": True,
                                   "text": server.decode(req),
                                   "steps": len(req.out)})
                        self.wfile.write(b"0\r\n\r\n")
                        self.wfile.flush()
                    except OSError:
                        pass
                    return

                # register with the server so stop() can join this thread
                # once the request is woken — without the registry a
                # handler blocked in q.get outlives the server silently
                with server._streams_lock:
                    server._streams.add(threading.current_thread())
                if submit is not None:
                    submit()
                else:
                    server.engine.submit(req)
                prev = req.tokens[0]
                sent = 0
                try:
                    while True:
                        try:
                            tok = q.get(timeout=0.1)
                        except queue.Empty:
                            if req.done.is_set() and sent == len(req.out):
                                break
                            continue
                        piece = server.tokenizer.decode_piece(prev, tok)
                        prev = tok
                        sent += 1
                        chunk({"token": tok,
                               "piece": piece.decode("utf-8",
                                                     errors="replace")})
                    if req.error is not None:
                        chunk({"done": True, "error": req.error})
                    else:
                        chunk({"done": True, "text": server.decode(req),
                               "steps": len(req.out)})
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                except OSError:
                    # client went away mid-stream: cancel in the ENGINE —
                    # a queued request completes now, an in-flight one is
                    # swept before the next dispatch, freeing its slot and
                    # KV pages immediately instead of decoding the rest of
                    # the budget (or another whole fused chain) for nobody
                    server.engine.cancel(req)
                finally:
                    with server._streams_lock:
                        server._streams.discard(
                            threading.current_thread())

        self.httpd = _BurstHTTPServer((host, port), Handler)
        self._threads: list[threading.Thread] = []

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def count_reject(self, reason: str) -> None:
        """Feed the admission_rejected{reason} series (no-op dark)."""
        if self.engine._obs is not None:
            self.engine._obs.reject(reason)

    def make_request(self, payload: dict) -> Request:
        if not isinstance(payload, dict):
            raise ValueError("body must be a JSON object")
        prompt = payload.get("prompt", "")
        if not isinstance(prompt, str):
            raise ValueError("prompt must be a string")
        steps = int(payload.get("steps", self.default_steps))
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if steps > self.spec.seq_len:
            raise OversizedRequest(
                f"steps must be in 1..{self.spec.seq_len}, got {steps}")
        temp = payload.get("temperature")
        topp = payload.get("topp")
        seed = payload.get("seed")
        slo_class = payload.get("class")
        if slo_class is not None:
            if self.slo_policy is None:
                raise ValueError(
                    "request names an SLO class but the server has no "
                    "--slo policy")
            self.slo_policy.resolve(str(slo_class))  # unknown -> 400
            slo_class = str(slo_class)
        tokens = self.tokenizer.encode(prompt, bos=True, eos=False)
        if len(tokens) > self.spec.seq_len:
            # the model literally cannot hold this prompt; truncating
            # silently would return an answer to a question never asked
            raise OversizedRequest(
                f"prompt encodes to {len(tokens)} positions, over the "
                f"model's seq_len {self.spec.seq_len}")
        return Request(tokens=tokens, steps=steps,
                       temperature=None if temp is None else float(temp),
                       topp=None if topp is None else float(topp),
                       seed=None if seed is None else int(seed),
                       slo_class=slo_class,
                       # trace minted at INGRESS (ISSUE 15): the id every
                       # span, journal record, and handoff hop of this
                       # request's life carries from here on
                       trace=tracectx.mint())

    def decode(self, req: Request) -> str:
        from .continuous import decode_stream

        return decode_stream(self.tokenizer, req.tokens[0], req.out)

    def remote_prefill(self, req: Request):
        """Decode-role routing (ISSUE 14): prompts spanning >=
        ``handoff_min_pages`` full pages forward to the prefill peer
        (POST /prefill), whose reply is either the finished stream (it
        ended inside the prefill cut) or a journal record + page-channel
        coordinates; shipped pages are fetched, CRC-verified, and handed
        to the scheduler with the re-admission request. Shorter prompts
        — and ANY peer failure — run locally: disaggregation degrades to
        single-pool serving, never to a dropped request.

        Returns ``(request, submit_fn)``: the request to track (the
        original, or the peer-built re-admission) and a thunk that hands
        it to the engine — None when it is already complete. Callers
        register streaming hooks BEFORE invoking the thunk."""
        import urllib.request

        from .disagg import HANDOFF_CAT, SPAN_HANDOFF_SEND, decode_request
        from .journal import entry_from_wire
        from .page_channel import PageChannelClient

        local = (req, lambda: self.engine.submit(req))
        n_full = (len(req.tokens) - 1) // max(self.engine.page_size, 1)
        if n_full < self.handoff_min_pages:
            if self._disagg_obs is not None:
                # wirecheck: allow[W002] metric verdict label, not a wire key
                self._disagg_obs.handoffs["local"].inc()
            return local
        t0 = time.monotonic()
        # the RPC span (ISSUE 15): the send half of the clock-skew
        # anchor pair — its traceparent rides the POST body, so the
        # prefill pool's spans become this span's descendants
        rpc = (req.trace.child() if req.trace is not None
               else tracectx.mint())
        t_send0 = time.perf_counter()

        def send_span(pages: int) -> None:
            if self.engine._spans is not None:
                self.engine._spans.add(
                    SPAN_HANDOFF_SEND, HANDOFF_CAT, t_send0,
                    time.perf_counter() - t_send0, pages=pages,
                    **tracectx.span_fields(rpc))

        dreq = None
        resp = None
        try:
            body = json.dumps({
                "tokens": req.tokens, "steps": req.steps,
                "temperature": req.temperature, "topp": req.topp,
                "seed": req.seed, "class": req.slo_class,
                "trace": rpc.to_header()}).encode()
            rq = urllib.request.Request(
                f"http://{self.disagg_peer}/prefill", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(rq, timeout=120) as r:
                resp = json.loads(r.read())
            if resp.get("final"):
                req.out.extend(int(t) for t in resp["out"])
                req.done.set()
                send_span(0)
                return req, None
            entry = entry_from_wire(resp["record"])
            dreq = decode_request(entry, req.steps)
            if self.engine._journal is not None:
                # the durability point: the admit record lands BEFORE
                # any page moves, so a crash mid-transfer recovers the
                # request from this journal (the kill_mid_handoff
                # contract, honored on the HTTP path too)
                self.engine.prejournal(dreq)
            host = self.disagg_peer.rsplit(":", 1)[0]
            client = PageChannelClient(
                f"{host}:{resp['channel_port']}")
            planes = client.fetch(resp["hid"], int(resp["n_pages"]))
            prompt = list(req.tokens)
            if self._disagg_obs is not None:
                obs = self._disagg_obs
                # wirecheck: allow[W002] metric verdict label, not a wire key
                obs.handoffs["shipped"].inc()
                obs.handoff_latency.observe(time.monotonic() - t0)
            send_span(int(resp["n_pages"]))
            log_event("disagg.handoff_shipped", None, trace=rpc,
                      peer=self.disagg_peer, pages=int(resp["n_pages"]))
            return dreq, (lambda: self.engine.ingest_remote(
                prompt, planes, dreq))
        except (OSError, ValueError, KeyError, TypeError) as e:
            log_event("disagg.handoff_failed",
                      f"🔶 handoff to {self.disagg_peer} failed "
                      f"({type(e).__name__}: {e}); serving locally",
                      file=sys.stderr, trace=rpc,
                      error=f"{type(e).__name__}: {e}")
            if dreq is not None:
                # the fallback serves the ORIGINAL request — retire the
                # prejournaled life, or the next recovery would replay
                # it on top of the fallback's stream
                self.engine.abandon_prejournaled(dreq)
            if resp is not None and resp.get("hid"):
                # best-effort: tell the prefill pool to drop the
                # published pages (nothing will fetch them now)
                try:
                    host = self.disagg_peer.rsplit(":", 1)[0]
                    PageChannelClient(
                        f"{host}:{resp['channel_port']}",
                        connect_window=2.0).ack(resp["hid"])
                except (OSError, ValueError, KeyError):
                    pass  # the channel's retention cap bounds the leak
            if self._disagg_obs is not None:
                # wirecheck: allow[W002] metric verdict label, not a wire key
                self._disagg_obs.handoffs["failed"].inc()
            return local

    def _health_payload(self) -> dict:
        """Assemble the GET /health JSON (the fleet plane's primary
        scrape surface — the registered producer of wiremodel's
        "health" format). Shared by the HTTP handler and the watch
        plane's self-scrape (watch_tick), so the detectors see exactly
        the payload a remote scraper would."""
        eng = self.engine
        with eng._lock:
            queued = len(eng._queue)
        active = sum(not s.free for s in eng._pool)
        payload = {
            "schema": HEALTH_SCHEMA,
            "state": self.health.state,
            "active": active,
            "queued": queued,
            "queue_depth": queued,
            "slots": eng.slots,
            "steps": eng.stats.steps,
            "generated_tokens": eng.stats.tokens,
            "uptime_s": round(time.monotonic() - self._t_start, 3),
            "occupancy": round(active / eng.slots, 4),
            # admission-pressure counters (ISSUE 8): page-starved
            # slot pauses and dry-pool head-of-queue requeues
            "pauses": eng.stats.pauses,
            "requeues": eng.stats.requeues,
        }
        if eng.allocator is not None:
            # paged-KV capacity surface (ISSUE 11): pool shape,
            # occupancy, the KV quantization in play, and the
            # pool planes' GLOBAL logical bytes (whole pool
            # across tp shards; per-device is /tp) — the
            # /metrics dllama_kv_quant_info / page-pool gauges'
            # JSON twin
            a = eng.allocator
            payload["paged_kv"] = {
                "page_size": a.page_size,
                "pages": a.n_pages,
                "pages_free": a.n_free,
                "kv_quant": eng.kv_quant,
                "pool_bytes": sum(int(x.nbytes)
                                  for x in eng.cache),
                "prefix_hit_rate": round(a.hit_rate, 4),
                # raw hit/miss COUNTS (ISSUE 15): the fleet
                # plane recomputes aggregate hit rates from
                # summed counts, never from averaged ratios
                "prefix_hits": a.prefix_hits,
                "prefix_misses": a.prefix_misses,
                "prefill_tokens_saved": a.tokens_saved,
                "evictions": a.evictions,
            }
            if a.tiered:
                # KV-tier hierarchy surface (ISSUE 12): per-tier
                # page population + promotion/demotion flow +
                # the prefill tokens the spilled tiers rescued —
                # the dllama_kv_tier_pages/... series' JSON twin
                counts = a.tier_page_counts()
                payload["kv_tiers"] = {
                    "pages": counts,
                    "host_capacity": (a.host.n_pages
                                      if a.host else 0),
                    "disk_live_bytes": (a.disk.live_bytes
                                        if a.disk else 0),
                    "disk_budget_bytes": (a.disk.budget_bytes
                                          if a.disk else 0),
                    "demotions": dict(a.demotions),
                    "promotions": dict(a.promotions),
                    "prefill_tokens_saved_by_tier":
                        dict(a.tokens_saved_by_tier),
                    "crc_drops": a.crc_drops,
                }
        if self.disagg_role is not None:
            # disaggregated-topology surface (ISSUE 14): this
            # pool's role, its peer, and the handoff backlog —
            # the dllama_handoff_*/dllama_dcn_* series' JSON twin
            payload["disagg"] = {
                "role": self.disagg_role,
                "peer": self.disagg_peer,
                "page_channel_port": (
                    self._page_channel.port
                    if self._page_channel is not None else None),
                "handoff_queue_depth": (
                    self._page_channel.queue_depth
                    if self._page_channel is not None else 0),
            }
            if eng.allocator is not None:
                payload["disagg"]["pages_adopted"] = \
                    eng.allocator.remote_adopted
        if self.journal is not None:
            # recovery bookkeeping: requests replayed from the
            # journal at startup + append volume since
            payload["journal"] = {
                "path": self.journal.path,
                "fsync": self.journal.fsync,
                "recovered": self.recovered,
                "records": self.journal.records_total,
            }
        if self._watchdog is not None:
            payload["watchdog"] = {
                "timeout_s": self._watchdog.timeout_s,
                "trips": self._watchdog.trips,
            }
        if eng.slo_tracker is not None:
            # per-class attempted/met/violated/failed + attainment
            # + goodput (obs/slo.SLOTracker.snapshot)
            payload["slo"] = eng.slo_tracker.snapshot()
        if eng._obs is not None:
            payload["admission_rejected"] = \
                eng._obs.rejected_total()
        # cost-accounting surface (ISSUE 16): census dispatch
        # totals + ledger book counts and per-class cost columns
        # — GET /debug/sched's summary twin, the block the fleet
        # plane (obs/fleet.signals_from_health) sums across
        # replicas
        book = eng.ledger_book
        payload["sched"] = {
            "census": eng.sched_census.totals(),
            "ledgers": {"opened": book.opened_n,
                        "closed": book.closed_n,
                        "open": book.n_open},
            "cost_totals": book.grand_totals(),
            "cost_by_class": book.class_rollup(),
        }
        if eng.spec_k:
            # speculative decoding health (ISSUE 7): proposal
            # volume + accept rate of the n-gram self-drafter
            payload["speculative"] = {
                "k": eng.spec_k,
                "proposed": eng.stats.spec_proposed,
                "accepted": eng.stats.spec_accepted,
                "accept_rate": round(eng.stats.spec_accept_rate, 4),
            }
        # incident-detection heartbeat (ISSUE 20): detection-plane
        # tick count + per-kind incident totals and hysteresis states
        # (evidence stays on /debug/incidents — health is a heartbeat,
        # not a forensics dump)
        payload["watch"] = self._watch.snapshot()
        if self.registry is not None:
            for key, name in (
                    ("ttft_s", "dllama_request_ttft_seconds"),
                    ("token_latency_s",
                     "dllama_request_decode_token_seconds"),
                    ("queue_wait_s",
                     "dllama_request_queue_wait_seconds")):
                h = self.registry.get(name)
                s = h.summary()
                payload[key] = {k: round(v, 6) if k != "count"
                                else v for k, v in s.items()}
        return payload

    def watch_tick(self) -> list:
        """One detection-plane scrape of THIS process: assemble the
        /health payload, fold it (plus the parsed /metrics exposition)
        into a fleet row, and feed the watchtower — exactly what a
        remote scraper's tick would see. Returns the NEW incidents
        (transitions into firing). Called by the ``_watch_loop``
        supervisor thread when ``watch_interval_s > 0``; tests and sim
        drivers call it directly on their own clock."""
        from ..obs.fleet import parse_metrics, signals_from_health
        from ..obs.watch import sample_from_signals

        row = signals_from_health("self", self._health_payload())
        samples = (parse_metrics(self.registry.expose())
                   if self.registry is not None else None)
        return self._watch.observe("self", sample_from_signals(row,
                                                               samples))

    def _watch_loop(self):
        """Supervisor thread (threadmodel ENTRYPOINTS): periodic
        watch_tick every ``watch_interval_s`` seconds until stop() sets
        the event. Detector exceptions are logged, never fatal — a
        broken detector must not take the watch plane down."""
        while not self._watch_stop.wait(self.watch_interval_s):
            try:
                self.watch_tick()
            except Exception as e:  # noqa: BLE001 - keep the loop alive
                log_event("watch.error",
                          f"🔶 watch tick failed: {e!r}",
                          file=sys.stderr,
                          error=f"{type(e).__name__}: {e}")

    def _on_incident(self, inc) -> None:
        """Watchtower firing hook (obs/watch.Incident): auto-forensics.
        Note the incident into the flight-recorder ring and dump a
        bundle with reason="incident" + the detector kind — the
        postmortem snapshot taken AT detection time, not at the
        operator's later convenience."""
        from ..obs.flightrec import REASON_INCIDENT

        log_event("watch.incident",
                  f"🔶 incident #{inc.seq} {inc.kind} on {inc.replica} "
                  f"tick {inc.tick}: {inc.note}",
                  file=sys.stderr, kind=inc.kind, replica=inc.replica,
                  tick=inc.tick, note=inc.note)
        self._flightrec_dump(REASON_INCIDENT, incident_kind=inc.kind)

    def _flightrec_dump(self, reason: str,
                        incident_kind: str | None = None) -> None:
        """One postmortem bundle (obs/flightrec): note the trigger into
        the ring, then write a bundle file when a directory is
        configured. Never raises — this runs on fault paths."""
        self.flightrec.note(reason, state=self.health.state,
                            outstanding=self._outstanding(),
                            **({"incident_kind": incident_kind}
                               if incident_kind else {}))
        if not self.flightrec_dir:
            return
        try:
            path = self.flightrec.dump(self.flightrec_dir, reason,
                                       incident_kind=incident_kind)
            log_event("flightrec.dump",
                      f"🔶 flight recorder: {reason} bundle -> {path}",
                      file=sys.stderr, path=path, reason=reason)
        except OSError as e:
            log_event("flightrec.failed",
                      f"🔶 flight recorder dump failed: {e}",
                      file=sys.stderr, error=f"{type(e).__name__}: {e}")

    def _on_hang(self, elapsed_s: float):
        """Watchdog trip (monitor thread): a dispatch overran its deadline.
        Detection only — mark the server degraded (and drop a flight-
        recorder bundle: the hung state IS the postmortem moment); the
        scheduler flips it back to serving once dispatches complete on
        time again."""
        try:
            self.health.to("degraded")
        except ValueError:
            pass  # already draining/stopped: the drain verdict wins
        self._flightrec_dump("watchdog")

    def _scheduler(self):
        while not self._shutdown.is_set():
            try:
                active = self.engine.step_many(self.engine.block_steps,
                                               quiet=self.quiet)
            except Exception as e:
                # a dead scheduler must not leave clients blocked forever:
                # fail everything queued/in flight (handlers answer 500) and
                # keep the loop alive — a persistent device fault just fails
                # each subsequent request the same way
                import traceback

                traceback.print_exc()
                log_event("scheduler.error",
                          f"🌐 scheduler step failed: {e!r}; failing "
                          f"pending requests",
                          error=f"{type(e).__name__}: {e}")
                self.engine.fail_all(f"{type(e).__name__}: {e}")
                time.sleep(0.1)
                continue
            if (self.health.state == "degraded"
                    and self._watchdog is not None
                    and not self._watchdog.overdue):
                # the hang resolved: dispatches are landing again (never
                # flip back while an armed dispatch is still overrunning)
                try:
                    self.health.to("serving")
                except ValueError:
                    pass  # drain/stop raced us: their state wins
            if active == 0:
                # idle for want of work, not for want of host speed: a
                # capture must tell the two apart
                with host_phase("serve.idle"):
                    time.sleep(_IDLE_SLEEP_S)

    def _outstanding(self) -> int:
        with self.engine._lock:
            queued = len(self.engine._queue)
        return queued + sum(not s.free for s in self.engine._pool)

    def _scheduler_stopped(self, timeout: float) -> bool:
        """Join the scheduler thread (started first); True once it is no
        longer running. suspend()/fail_all() walk the slot pool, so the
        shutdown paths must never run them concurrently with a live
        scheduler step — when this times out (a wedged dispatch, the
        watchdog's scenario) the caller SKIPS them: journaled work stays
        live for the next process, which is the safe outcome."""
        for t in self._threads[:1]:
            t.join(timeout=timeout)
            if t.is_alive():
                log_event("server.scheduler_wedged",
                          f"🔶 scheduler did not stop within {timeout:.0f}s "
                          f"(wedged dispatch?) — leaving in-flight work "
                          f"journaled instead of racing a live step",
                          file=sys.stderr, timeout_s=timeout)
                return False
        return True

    def start(self):
        """Start the scheduler + HTTP threads and return (non-blocking).
        With ``watch_interval_s > 0`` the watch-plane supervisor thread
        rides along (incident detection over the process's own signal
        plane, ISSUE 20)."""
        for target in (self._scheduler, self.httpd.serve_forever,
                       self._watch_loop):
            if target == self._watch_loop and self.watch_interval_s <= 0:
                continue  # detectors still run on manual watch_tick()
            t = threading.Thread(target=target, daemon=True)
            t.start()
            self._threads.append(t)
        # where start-up went, by phase and by program made (stderr; one
        # ``startup.summary`` record under --log-json), the same on
        # /metrics, and from here on every program made is logged by name
        from ..obs.spans import log_startup

        log_startup()
        if self.engine._obs is not None:
            self.engine._obs.bind_startup()
        self.health.to("serving")

    def serve_forever(self):
        """Blocking entry (cmd_serve): serve until SIGTERM or Ctrl-C, then
        drain gracefully — stop admission, finish in-flight work within the
        drain budget, journal whatever remains — and return (exit 0)."""
        self.start()
        stop_requested = threading.Event()
        prev_handler = None
        try:
            prev_handler = signal.signal(
                signal.SIGTERM, lambda signum, frame: stop_requested.set())
        except ValueError:
            pass  # not the main thread (tests): rely on stop()/drain()
        try:
            while not stop_requested.is_set():
                time.sleep(0.2)
        except KeyboardInterrupt:
            pass
        finally:
            if prev_handler is not None:
                signal.signal(signal.SIGTERM, prev_handler)
            self.drain()

    def drain(self, budget_s: float | None = None) -> int:
        """Graceful shutdown: stop admission (handlers 503), let the
        scheduler finish in-flight work for up to ``budget_s`` seconds,
        then journal whatever is still outstanding (suspend) — or fail it
        loudly when there is no journal — and stop. Returns the number of
        requests left journaled for the next process."""
        budget = self.drain_s if budget_s is None else budget_s
        t0 = time.monotonic()
        try:
            self.health.to("draining")
        except ValueError:
            return 0  # already stopped
        # the SIGTERM postmortem bundle: state AS THE DRAIN BEGINS —
        # in-flight work, queue depth, journal tail, recent spans
        self._flightrec_dump("sigterm_drain")
        log_event("server.drain",
                  f"🌐 draining: admission stopped, "
                  f"{self._outstanding()} requests in flight, "
                  f"budget {budget:.1f}s",
                  outstanding=self._outstanding(), budget_s=budget)
        deadline = t0 + budget
        while self._outstanding() and time.monotonic() < deadline:
            time.sleep(0.01)
        # scheduler off BEFORE suspending: a step racing a retire-less
        # suspend could double-process a request's slot
        self._shutdown.set()
        sched_ok = self._scheduler_stopped(30)
        remainder = self._outstanding()
        if remainder and sched_ok:
            if self.journal is not None:
                self.engine.suspend()
            else:
                self.engine.fail_all("server draining: request dropped "
                                     "(no --journal to recover from)")
        drain_s = time.monotonic() - t0
        if self._drain_hist is not None:
            self._drain_hist.observe(drain_s)
        journaled = remainder if self.journal is not None else 0
        if not remainder:
            msg = (f"🌐 drained in {drain_s:.2f}s: all in-flight work "
                   f"completed")
        elif self.journal is not None:
            msg = (f"🌐 drained in {drain_s:.2f}s: {remainder} requests "
                   f"journaled for recovery")
        else:
            msg = (f"🔶 drained in {drain_s:.2f}s: {remainder} requests "
                   f"DROPPED (no --journal to carry them over)")
        log_event("server.drained", msg, seconds=round(drain_s, 3),
                  journaled=journaled, dropped=remainder - journaled)
        self.stop()
        return remainder

    def stop(self):
        """Tear down every thread the server owns. Idempotent; safe from
        any thread. Requests still outstanding are failed (use drain() for
        the graceful path) so no handler stays blocked on done.wait or the
        stream queue — then the streaming handler threads are JOINED, not
        abandoned."""
        if self._stopped.is_set():
            return
        self._stopped.set()
        self._shutdown.set()
        if self.engine._obs is not None:
            self.engine._obs.unbind_startup()
        # park the watch loop FIRST: a watch tick mid-teardown would
        # scrape a half-closed engine (the event also bounds the
        # _watch_loop thread's lifetime — threadmodel's joined_by)
        self._watch_stop.set()
        self.httpd.shutdown()
        sched_ok = self._scheduler_stopped(30)
        for t in self._threads[1:]:
            t.join(timeout=5)
        if self._outstanding() and sched_ok:
            # stop() without drain(): wake every waiter NOW — handlers
            # answer 500/stream-error and their threads exit. With a
            # journal the interrupted work is suspended (recoverable),
            # without one it is failed loudly. Skipped when the
            # scheduler would not stop (_scheduler_stopped): walking the
            # pool under a live step risks double-frees — the journal
            # carries the work instead.
            if self.journal is not None:
                self.engine.suspend()
            else:
                self.engine.fail_all("server stopped")
        # join streaming handlers until the registry drains. A single
        # snapshot has a TOCTOU hole: a handler that registers AFTER the
        # snapshot (its request raced the shutdown) would never be
        # joined. Re-snapshot under the lock each pass — joins happen
        # OUTSIDE the lock so a handler's deregister (finally block)
        # can't deadlock against us.
        deadline = time.monotonic() + 5.0
        while True:
            with self._streams_lock:
                pending = [t for t in self._streams if t.is_alive()]
            if not pending:
                break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            for t in pending:
                t.join(timeout=max(0.05, remaining))
        self.httpd.server_close()
        if self._page_channel is not None:
            self._page_channel.close()
        self.engine.close()  # KV-tier uploader thread (no-op untiered)
        if self._watchdog is not None:
            self._watchdog.close()
        st = self.engine.stats
        log_event("server.summary",
                  f"🌐 served {st.tokens} tokens in {st.steps} steps "
                  f"({st.avg_active:.2f} rows a step); {st.steps_ahead} "
                  f"steps launched ahead on device-resident tokens, "
                  f"{st.rows_dropped_ahead} rows of them dropped; "
                  f"{st.admission_clause}"
                  + (f"; window rings {st.window_bytes / 2**20:.0f} MiB "
                     f"resident, {st.shared_kv_pages} pages of the full "
                     f"layers' K / V in use, {st.window_kv_positions} ring "
                     f"slots and {st.shared_kv_positions} cached positions "
                     f"read a sliding and a full layer, {st.moe_pairs} "
                     f"routed pairs, per-head gate smallest "
                     f"{st.gate_min:.3g} mean {st.gate_mean:.3g}"
                     if st.gate_steps else
                     f"; state {st.state_bytes / 2**20:.0f} MiB and window "
                     f"rings {st.window_bytes / 2**20:.0f} MiB resident, "
                     f"{st.shared_kv_pages} pages of the shared K / V in "
                     f"use, the cross-decoder at {st.xdec_positions} of "
                     f"{st.prompt_positions} prompt positions, smallest "
                     f"state decay {st.ssm_min_decay:.3g}"
                     if st.window_bytes else
                     f"; state {st.state_bytes / 2**20:.0f} MiB resident, "
                     f"smallest normaliser {st.min_normaliser:.3g}"
                     if st.state_bytes else
                     f"; {st.hc_streams} residual streams mixed around "
                     f"{st.hc_sublayers_a_step} sub-layers a step, "
                     f"{st.latent_positions} cached positions read over "
                     f"{st.latent_pages} pages in use at the end, "
                     f"{st.moe_pairs} routed pairs"
                     if st.hc_streams else
                     f"; {st.paged_kv_positions} cached positions read a "
                     f"layer of the KV page pool"
                     if st.paged_kv_positions else "")
                  + (f"; chunks walked {st.chunk_walk_share:.1%} of the "
                     f"plane" if st.chunk_plane_positions else "")
                  + (f"; {st.moe_diag_slots} of {st.moe_slots} expert "
                     f"slots took the block-diagonal body "
                     f"({st.moe_single_row_slots} held one row)"
                     if st.moe_slots else "")
                  + (f"; {st.dense_diag_steps} of {st.steps} steps ran "
                     f"their dense Q40 leaves through the stacked "
                     f"block-diagonal body"
                     if st.dense_diag_steps else ""),
                  file=sys.stderr, tokens=st.tokens, steps=st.steps,
                  sum_active=st.sum_active, steps_ahead=st.steps_ahead,
                  rows_dropped_ahead=st.rows_dropped_ahead,
                  land_s=st.land_s, lands_behind_admit=st.lands_behind_admit,
                  land_behind_admit_s=st.land_behind_admit_s,
                  prefill_chunks=st.prefill_chunks,
                  admit_prefills=st.admit_prefills,
                  admits_back_to_back_max=st.admits_back_to_back_max,
                  admit_pages_moved=st.admit_pages_moved,
                  admit_pages_table=st.admit_pages_table,
                  admit_gathers=st.admit_gathers,
                  fetch_wait_s=st.fetch_wait_s,
                  fetch_wait_behind_admit_s=st.fetch_wait_behind_admit_s,
                  admit_share=st.admit_share,
                  admit_stall_ms_per_chunk=st.admit_stall_ms_per_chunk,
                  plain_step_ms=st.plain_step_ms,
                  host_ms_per_step=st.host_ms_per_step,
                  hc_streams=st.hc_streams,
                  hc_sublayers_a_step=st.hc_sublayers_a_step,
                  latent_positions=st.latent_positions,
                  latent_pages=st.latent_pages,
                  paged_kv_positions=st.paged_kv_positions,
                  chunk_walked_positions=st.chunk_walked_positions,
                  chunk_plane_positions=st.chunk_plane_positions,
                  moe_pairs=st.moe_pairs,
                  moe_local_pairs=st.moe_local_pairs,
                  moe_active=st.moe_active,
                  moe_slots=st.moe_slots,
                  moe_single_row_slots=st.moe_single_row_slots,
                  moe_diag_slots=st.moe_diag_slots,
                  dense_diag_steps=st.dense_diag_steps,
                  state_bytes=st.state_bytes,
                  window_bytes=st.window_bytes,
                  shared_kv_pages=st.shared_kv_pages,
                  shared_kv_positions=st.shared_kv_positions,
                  window_kv_positions=st.window_kv_positions,
                  gate_min=st.gate_min if st.gate_steps else None,
                  gate_mean=st.gate_mean if st.gate_steps else None,
                  prompt_positions=st.prompt_positions,
                  xdec_positions=st.xdec_positions,
                  ssm_min_decay=(st.ssm_min_decay
                                 if st.window_bytes and not st.gate_steps
                                 else None),
                  min_normaliser=(st.min_normaliser
                                  if st.state_bytes and not st.window_bytes
                                  else None))
        if self.journal is not None:
            self.journal.close()
        try:
            self.health.to("stopped")
        except ValueError:
            pass
