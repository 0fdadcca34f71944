"""Telemetry subsystem: metric math, registry thread-safety, Prometheus
exposition, engine lifecycle tracing, and the off-unless-enabled contract
(a disabled engine makes ZERO registry calls on the hot path)."""

import json
import threading
import urllib.request

import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                               Registry, summarize_values)

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=16)


class _IdTokenizer:
    def encode(self, text, bos=True, eos=False):
        return [1] + [3 + b for b in text.encode()]

    def decode_piece(self, prev, tok):
        return b"<%d>" % tok


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


# ---------------------------------------------------------------- metrics


def test_histogram_bucket_and_percentile_math():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 7.0, 100.0):
        h.observe(v)
    counts, s, total = h.snapshot()
    assert counts == [1, 2, 1, 1, 1]  # per-bucket, +Inf last
    assert total == 6 and s == pytest.approx(113.5)
    # p50: rank 3 of 6 -> second bucket (1, 2]: 1 + (3-1)/2 * 1 = 2.0
    assert h.percentile(0.50) == pytest.approx(2.0)
    # p100 lands in +Inf: clamps to the last finite bound
    assert h.percentile(1.0) == pytest.approx(8.0)
    # empty histogram: all zeros
    assert Histogram("e", buckets=(1.0,)).percentile(0.9) == 0.0
    summ = h.summary()
    assert summ["count"] == 6
    assert summ["mean"] == pytest.approx(113.5 / 6)


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(1.0, 1.0))


def test_summarize_values_matches_percentile_semantics():
    s = summarize_values(range(1, 101))  # 1..100
    assert s["count"] == 100
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(50.5)
    assert s["p99"] == pytest.approx(99.01)
    assert summarize_values([])["p95"] == 0.0
    # unit_scale rescales on the way in (ms list -> seconds)
    assert summarize_values([1000.0], unit_scale=1e-3)["p50"] == 1.0


def test_registry_get_or_create_and_mismatch():
    reg = Registry()
    c1 = reg.counter("c", "help")
    assert reg.counter("c") is c1
    with pytest.raises(ValueError):
        reg.gauge("c")
    h = reg.histogram("h", buckets=(1.0, 2.0))
    assert reg.histogram("h", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(1.0, 3.0))


def test_registry_thread_safety_exact_counts():
    reg = Registry()
    c = reg.counter("dllama_test_total")
    g = reg.gauge("dllama_test_gauge")
    h = reg.histogram("dllama_test_seconds", buckets=(0.5, 1.5))
    N, T = 2000, 8

    def writer():
        for i in range(N):
            c.inc()
            g.inc()
            h.observe(i % 2)

    threads = [threading.Thread(target=writer) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    assert g.value == N * T
    counts, s, total = h.snapshot()
    assert total == N * T
    assert counts == [N * T // 2, N * T // 2, 0]
    assert s == pytest.approx(N * T // 2)


def test_counter_rejects_negative():
    with pytest.raises(ValueError):
        Counter("c").inc(-1)


def test_exposition_format_golden():
    reg = Registry()
    reg.counter("dllama_generated_tokens_total", "Tokens emitted").inc(7)
    g = reg.gauge("dllama_active_slots", "Active now")
    g.set(2.5)
    h = reg.histogram("dllama_ttft_seconds", "TTFT",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(3.0)
    assert reg.expose() == (
        "# HELP dllama_generated_tokens_total Tokens emitted\n"
        "# TYPE dllama_generated_tokens_total counter\n"
        "dllama_generated_tokens_total 7\n"
        "# HELP dllama_active_slots Active now\n"
        "# TYPE dllama_active_slots gauge\n"
        "dllama_active_slots 2.5\n"
        "# HELP dllama_ttft_seconds TTFT\n"
        "# TYPE dllama_ttft_seconds histogram\n"
        'dllama_ttft_seconds_bucket{le="0.1"} 1\n'
        'dllama_ttft_seconds_bucket{le="1"} 2\n'
        'dllama_ttft_seconds_bucket{le="+Inf"} 3\n'
        "dllama_ttft_seconds_sum 3.55\n"
        "dllama_ttft_seconds_count 3\n")


# ------------------------------------------------------------ event log


def test_log_event_json_and_text_modes(capsys, monkeypatch):
    from distributed_llama_tpu.obs.log import log_event

    monkeypatch.delenv("DLLAMA_LOG_JSON", raising=False)
    log_event("x", "human line", field=1)
    assert capsys.readouterr().out == "human line\n"

    monkeypatch.setenv("DLLAMA_LOG_JSON", "1")
    log_event("decode.token", "human line", pos=3, gen_ms=1.5)
    rec = json.loads(capsys.readouterr().out)
    assert rec["event"] == "decode.token"
    assert rec["pos"] == 3 and rec["gen_ms"] == 1.5
    assert "ts" in rec

    # text=None: JSON-only event, silent in human mode
    monkeypatch.delenv("DLLAMA_LOG_JSON", raising=False)
    log_event("run.summary", None, tokens=5)
    assert capsys.readouterr().out == ""


# -------------------------------------------------- engine lifecycle


def _patch_instrument_calls(monkeypatch):
    """Wrap every registry-instrument mutator with a call counter."""
    calls = []

    def wrap(cls, name):
        orig = getattr(cls, name)

        def spy(self, *a, **kw):
            calls.append((cls.__name__, name))
            return orig(self, *a, **kw)

        monkeypatch.setattr(cls, name, spy)

    wrap(Counter, "inc")
    wrap(Gauge, "set")
    wrap(Gauge, "inc")
    wrap(Gauge, "dec")
    wrap(Histogram, "observe")
    return calls


def test_engine_zero_registry_calls_when_disabled(params, monkeypatch):
    """The acceptance gate: metrics collection is OFF the hot path unless
    enabled — an engine built without a registry must not touch any
    instrument during submit/step/retire."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    calls = _patch_instrument_calls(monkeypatch)
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5)
    outs, _ = eng.run([[1, 5, 9], [1, 7]], steps=8)
    assert all(outs)
    assert calls == []


def test_engine_lifecycle_metrics_populated(params):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg)
    outs, stats = eng.run([[1, 5, 9], [1, 7], [1, 2]], steps=8)
    assert reg.get("dllama_requests_total").value == 3
    assert reg.get("dllama_request_ttft_seconds").count == 3
    assert reg.get("dllama_request_queue_wait_seconds").count == 3
    assert reg.get("dllama_request_decode_token_seconds").count == 3
    assert reg.get("dllama_generated_tokens_total").value == stats.tokens
    assert reg.get("dllama_engine_steps_total").value == stats.steps
    assert reg.get("dllama_engine_step_duration_seconds").count > 0
    occ = reg.get("dllama_engine_batch_occupancy")
    assert occ.count > 0
    # queue drained at the end
    assert reg.get("dllama_engine_queued_requests").value == 0


def test_paged_engine_exports_page_and_prefix_series(params):
    """ISSUE 6 satellite: a paged engine moves dllama_kv_pages_free and
    dllama_prefix_hits_total, and both land in the Prometheus exposition
    with their HELP/TYPE headers."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    sys_p = [1] + list(range(20, 28))  # 2 full pages at page_size=4
    reqs = [sys_p + [40 + i] for i in range(4)]
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg, page_size=4,
                           prefill_chunk=4)
    eng.run(reqs, steps=12)
    a = eng.allocator
    assert reg.get("dllama_prefix_hits_total").value == a.prefix_hits > 0
    assert reg.get("dllama_prefill_tokens_saved_total").value \
        == a.tokens_saved > 0
    # after the drain: every page is free or idle in the radix tree
    assert reg.get("dllama_kv_pages_free").value == a.n_free
    text = reg.expose()
    for family, kind in (("dllama_kv_pages_free", "gauge"),
                         ("dllama_prefix_hits_total", "counter"),
                         ("dllama_prefill_tokens_saved_total", "counter")):
        assert f"# TYPE {family} {kind}" in text
        assert f"# HELP {family} " in text


def test_contiguous_engine_page_series_stay_zero(params):
    """The paged instruments exist on every engine (layout-invariant
    scrape surface) but a contiguous engine never moves them."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg)
    eng.run([[1, 5, 9], [1, 7]], steps=8)
    assert eng.allocator is None
    assert reg.get("dllama_kv_pages_free").value == 0
    assert reg.get("dllama_prefix_hits_total").value == 0
    assert "dllama_kv_pages_free 0" in reg.expose()


def test_spec_engine_exports_proposed_and_accepted_series(params):
    """ISSUE 7 satellite: a speculative engine moves
    dllama_spec_proposed_total / dllama_spec_accepted_total, pinned equal
    to the engine's own stats counters, and both land in the exposition
    with HELP/TYPE headers."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg, page_size=4,
                           spec_k=4)
    _, st = eng.run([[1, 5, 9], [1, 22], [1, 7, 33]], steps=10)
    assert reg.get("dllama_spec_proposed_total").value \
        == st.spec_proposed > 0
    assert reg.get("dllama_spec_accepted_total").value == st.spec_accepted
    assert st.spec_accepted <= st.spec_proposed
    text = reg.expose()
    for family in ("dllama_spec_proposed_total",
                   "dllama_spec_accepted_total"):
        assert f"# TYPE {family} counter" in text
        assert f"# HELP {family} " in text


def test_plain_engine_spec_series_stay_zero(params):
    """Spec instruments exist on every engine but never move when
    spec_k == 0 — dashboards survive the knob."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg, page_size=4)
    _, st = eng.run([[1, 5, 9]], steps=8)
    assert eng.spec_k == 0 and st.spec_proposed == 0
    assert reg.get("dllama_spec_proposed_total").value == 0
    assert "dllama_spec_proposed_total 0" in reg.expose()


def test_admission_pressure_series_exposed_at_zero(params):
    """ISSUE 8 satellite: dllama_queue_depth, dllama_slot_pauses_total,
    and the full dllama_admission_rejected_total{reason} matrix are
    registered at engine creation — a fresh scrape shows them all at
    zero, one HELP/TYPE header per family."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                     seed=5, metrics=reg)
    text = reg.expose()
    assert "# TYPE dllama_queue_depth gauge" in text
    assert "dllama_queue_depth 0" in text
    assert "# TYPE dllama_slot_pauses_total counter" in text
    assert "dllama_slot_pauses_total 0" in text
    assert text.count("# TYPE dllama_admission_rejected_total counter") == 1
    for reason in ("pool_dry", "deadlock", "oversized", "bad_request"):
        assert (f'dllama_admission_rejected_total{{reason="{reason}"}} 0'
                in text)


def test_queue_depth_tracks_legacy_gauge(params):
    """dllama_queue_depth (the ISSUE-8 canonical name) and the legacy
    dllama_engine_queued_requests are written together and can never
    diverge."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=1, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg)
    eng.submit(Request(tokens=[1, 5], steps=4))
    eng.submit(Request(tokens=[1, 7], steps=4))
    assert reg.get("dllama_queue_depth").value == 2
    assert reg.get("dllama_engine_queued_requests").value == 2
    while eng.step_once():
        pass
    assert reg.get("dllama_queue_depth").value == 0
    assert reg.get("dllama_engine_queued_requests").value == 0


def test_pool_dry_requeue_moves_reject_counter_and_pauses(params):
    """Transient page starvation (chaos denial) exercises the dry-pool
    admission path: the head-of-queue requeue counts under
    admission_rejected{reason="pool_dry"}, pinned to stats.requeues."""
    from distributed_llama_tpu.runtime.chaos import ChaosMonkey
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg, page_size=4,
                           chaos=ChaosMonkey(deny_pages=2))
    outs, st = eng.run([[1, 5, 9]], steps=8)
    assert outs[0]  # the request completed once the denials ran out
    assert st.requeues >= 1
    assert reg.get('dllama_admission_rejected_total'
                   '{reason="pool_dry"}').value == st.requeues


def test_page_starved_slot_pause_counts(params):
    """A slot pausing for pages (pool oversubscribed, other slots still
    runnable) moves dllama_slot_pauses_total in step with stats.pauses."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    # 3 slots over a 5-page pool at page_size 4: the third request's
    # growth finds the pool dry while the other two keep decoding, so it
    # pauses (not a deadlock — len(paused) < active) until a retirement
    # frees pages
    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=3, temperature=0.0,
                           topp=0.9, seed=5, metrics=reg, page_size=4,
                           kv_pages=5, prefix_share=False)
    reqs = [[1, 5, 9], [1, 7, 11], [1, 6, 13]]
    outs, st = eng.run(reqs, steps=12)
    assert all(outs)
    assert st.pauses > 0
    assert reg.get("dllama_slot_pauses_total").value == st.pauses


def test_server_health_reports_spec_accept_rate(params):
    """ISSUE 7 satellite: /health carries the speculative block (k,
    proposed, accepted, accept_rate) when --spec-k is on."""
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=2, steps=8, temperature=0.0, topp=0.9,
                          seed=5, quiet=True, page_size=4, spec_k=4)
    srv.start()
    try:
        _post(srv.port, "/generate", {"prompt": "xyx", "steps": 6})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/health", timeout=30) as r:
            h = json.loads(r.read())
        sp = h["speculative"]
        assert sp["k"] == 4
        assert sp["accepted"] <= sp["proposed"]
        assert 0.0 <= sp["accept_rate"] <= 1.0
        assert sp["accept_rate"] == round(
            sp["accepted"] / max(sp["proposed"], 1), 4)
    finally:
        srv.stop()


def test_engine_compile_event_counter():
    """Programs made while the registry is bound to the start-up account
    (obs/spans; ``InferenceServer.start`` binds it) count as compile
    events: a new fused-chain shape makes one, reusing it makes none."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    # a width of its own: no other test has made this engine's programs
    spec = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                           n_kv_heads=2, vocab_size=152, seq_len=16)
    reg = Registry()
    eng = ContinuousEngine(spec, synth_params(spec, q40=False, seed=4,
                                              scale=0.3),
                           slots=2, temperature=0.0, topp=0.9, seed=5,
                           block_steps=3, metrics=reg)
    assert reg.get("dllama_engine_compile_events_total").value == 0
    eng._obs.bind_startup()
    try:
        eng.run([[1, 5]], steps=6)
        first = reg.get("dllama_engine_compile_events_total").value
        assert first >= 1
        eng.run([[1, 7]], steps=6)  # same chain shape: no program made
        assert reg.get("dllama_engine_compile_events_total").value == first
    finally:
        eng._obs.unbind_startup()


# ---------------------------------------------------- server round-trip


@pytest.fixture()
def server(params):
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=2, steps=8, temperature=0.0, topp=0.9,
                          seed=5, quiet=True)
    srv.start()
    yield srv
    srv.stop()


def _post(port, path, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_server_metrics_round_trip(server):
    """/metrics after a /generate: valid Prometheus text whose values are
    consistent with the completed request (the acceptance criterion)."""
    r = _post(server.port, "/generate", {"prompt": "ab", "steps": 8})
    n_tokens = len(r["tokens"])
    assert n_tokens > 0

    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()

    metrics = {}
    for line in text.splitlines():
        assert line, "blank line in exposition"
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE "))
            continue
        name_part, value = line.rsplit(" ", 1)
        metrics[name_part] = float(value)
    assert metrics["dllama_request_ttft_seconds_count"] == 1
    assert metrics["dllama_request_queue_wait_seconds_count"] == 1
    assert metrics["dllama_generated_tokens_total"] == n_tokens
    assert metrics["dllama_engine_step_duration_seconds_count"] >= 1
    assert metrics["dllama_requests_total"] == 1
    # cumulative bucket invariant: +Inf bucket == count
    assert metrics['dllama_request_ttft_seconds_bucket{le="+Inf"}'] \
        == metrics["dllama_request_ttft_seconds_count"]


def test_server_health_enriched(server):
    _post(server.port, "/generate", {"prompt": "x", "steps": 4})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/health", timeout=30) as r:
        h = json.loads(r.read())
    assert h["slots"] == 2
    assert h["uptime_s"] > 0
    assert 0.0 <= h["occupancy"] <= 1.0
    for key in ("ttft_s", "token_latency_s", "queue_wait_s"):
        assert h[key]["count"] >= 1
        assert h[key]["p50"] <= h[key]["p95"] <= h[key]["p99"]


def test_server_no_metrics_disables_endpoint(params):
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=1, steps=4, temperature=0.0, topp=0.9,
                          seed=5, quiet=True, metrics=False)
    srv.start()
    try:
        assert srv.engine._obs is None
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # /health still serves its engine-level fields
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/health", timeout=30) as r:
            h = json.loads(r.read())
        assert "ttft_s" not in h and h["slots"] == 1
    finally:
        srv.stop()


def test_server_profile_endpoint(server, tmp_path):
    from distributed_llama_tpu.obs import profiler

    d = str(tmp_path / "trace")
    out = _post(server.port, "/profile", {"seconds": 0.2, "dir": d})
    assert out == {"dir": d, "seconds": 0.2}
    # a second capture while one is running -> 409
    try:
        _post(server.port, "/profile", {"seconds": 0.2, "dir": d})
        overlapped = False
    except urllib.error.HTTPError as e:
        assert e.code == 409
        overlapped = True
    assert profiler.wait_capture(30)
    assert overlapped or profiler.capture_active() is None
    # bad duration -> 400
    try:
        _post(server.port, "/profile", {"seconds": -1})
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


# ------------------------------------------- flash-degrade warning


def test_explicit_flash_degrade_warns_once(monkeypatch, capsys):
    """DLLAMA_PREFILL_ATTN=flash degrading to the blockwise walk must say
    so loudly, once (the fail-loud policy for explicit modes)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models import llama

    monkeypatch.setenv("DLLAMA_PREFILL_ATTN", "flash")
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "xla")  # kernel unavailable
    monkeypatch.setattr(llama, "_flash_degrade_warned", False)
    t_len = 16
    q = jnp.zeros((t_len, SPEC.n_heads, SPEC.head_size))
    k = jnp.zeros((SPEC.seq_len, SPEC.n_kv_heads, SPEC.head_size))
    v = jnp.zeros_like(k)
    llama.attention(SPEC, q, k, v, jnp.int32(0), t_len)
    err = capsys.readouterr().err
    assert "DLLAMA_PREFILL_ATTN=flash" in err
    assert "blockwise" in err
    llama.attention(SPEC, q, k, v, jnp.int32(0), t_len)
    assert "DLLAMA_PREFILL_ATTN" not in capsys.readouterr().err  # once


# --------------------------------------- labeled series + collective gauges


def test_labeled_counter_exposition_and_family_grouping():
    reg = Registry()
    a = reg.labeled_counter("dllama_ici_collectives_total",
                            {"kind": "psum", "scheme": "fused"}, "Launches")
    b = reg.labeled_counter("dllama_ici_collectives_total",
                            {"kind": "all_gather", "scheme": "fused"})
    a.inc(3)
    b.inc(1)
    # same (name, labels) -> the same series; different labels -> distinct
    assert reg.labeled_counter("dllama_ici_collectives_total",
                               {"kind": "psum", "scheme": "fused"}) is a
    assert a is not b
    text = reg.expose()
    assert text.count("# TYPE dllama_ici_collectives_total counter") == 1
    assert ('dllama_ici_collectives_total{kind="psum",scheme="fused"} 3'
            in text)
    assert ('dllama_ici_collectives_total{kind="all_gather",scheme="fused"}'
            ' 1' in text)
    assert reg.get(
        'dllama_ici_collectives_total{kind="psum",scheme="fused"}') is a


def test_interleaved_registration_still_groups_families():
    """bind_collectives registers (launches, bytes) PAIRWISE per kind;
    the exposition must still emit each family as ONE contiguous group
    under a single header (the Prometheus grouping rule — interleaved
    families parse as duplicate untyped ones)."""
    reg = Registry()
    reg.labeled_counter("dllama_ici_collectives_total",
                        {"kind": "psum"}, "launches").inc(2)
    reg.labeled_counter("dllama_ici_bytes_total",
                        {"kind": "psum"}, "bytes").inc(10)
    reg.labeled_counter("dllama_ici_collectives_total",
                        {"kind": "all_gather"}).inc(1)
    reg.labeled_counter("dllama_ici_bytes_total",
                        {"kind": "all_gather"}).inc(5)
    lines = reg.expose().splitlines()
    series_families = [ln.split("{")[0] for ln in lines
                       if not ln.startswith("#")]
    assert series_families == ["dllama_ici_collectives_total"] * 2 + \
        ["dllama_ici_bytes_total"] * 2
    assert lines[0].startswith("# HELP dllama_ici_collectives_total")


def test_labeled_series_kind_mismatch_raises():
    reg = Registry()
    reg.labeled_counter("m", {"k": "v"})
    with pytest.raises(ValueError):
        reg.labeled_gauge("m", {"k": "v"})
    # kind is a FAMILY property: a differently-labeled (or unlabeled)
    # series cannot smuggle a second kind under the same name — it would
    # expose under the wrong TYPE header
    with pytest.raises(ValueError):
        reg.labeled_gauge("m", {"k": "other"})
    reg.counter("plain")
    with pytest.raises(ValueError):
        reg.labeled_gauge("plain", {"k": "v"})


def test_label_order_does_not_split_series():
    """The label SET is the series identity: two call sites passing the
    same labels in different key order must land on one series (and one
    exposition line — duplicates fail a Prometheus scrape)."""
    reg = Registry()
    a = reg.labeled_counter("m", {"kind": "psum", "scheme": "fused"})
    b = reg.labeled_counter("m", {"scheme": "fused", "kind": "psum"})
    assert a is b
    a.inc(2)
    assert reg.expose().count('m{kind="psum",scheme="fused"}') == 1


def test_engine_metrics_collective_gauges_track_steps():
    """bind_collectives turns the analytic schedule into labeled series:
    N launches and rows*bytes per device step, per kind."""
    from distributed_llama_tpu.models.synth import llama2_7b_spec
    from distributed_llama_tpu.obs.trace import EngineMetrics
    from distributed_llama_tpu.parallel.comm_stats import tp_collective_budget

    reg = Registry()
    em = EngineMetrics(reg)
    budget = tp_collective_budget(llama2_7b_spec(), 8, "fused")
    em.bind_collectives(budget, "fused", rows=4)
    em.record_step(0.01, active=2, steps=3)
    counts = budget.kind_counts()
    by_kind = budget.bytes_by_kind()
    for kind in counts:
        launches = reg.get(f'dllama_ici_collectives_total'
                           f'{{kind="{kind}",scheme="fused"}}')
        moved = reg.get(f'dllama_ici_bytes_total'
                        f'{{kind="{kind}",scheme="fused"}}')
        assert launches.value == counts[kind] * 3
        assert moved.value == by_kind[kind] * 4 * 3


def test_sharded_engine_exports_collective_gauges(params):
    """A tp>1 engine with metrics on exposes the budget series on its
    registry — the /metrics surface the drift gate checks against."""
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.comm_stats import (
        tp_collective_budget, tp_scheme)
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, mesh=make_mesh(tp=2),
                           metrics=reg)
    eng.run([[1, 5, 9]], steps=6)
    scheme = tp_scheme()
    budget = tp_collective_budget(SPEC, 2, scheme)
    assert budget.entries, "tp=2 must have a collective budget"
    steps = reg.get("dllama_engine_steps_total").value
    for kind, count, moved in budget.entries:
        launches = reg.get(f'dllama_ici_collectives_total'
                           f'{{kind="{kind}",scheme="{scheme}"}}')
        assert launches is not None, f"missing series for {kind}"
        assert launches.value == count * steps
        moved_c = reg.get(f'dllama_ici_bytes_total'
                          f'{{kind="{kind}",scheme="{scheme}"}}')
        # bytes scale by the slot count: each batched collective moves
        # B rows whether or not every slot is occupied
        assert moved_c.value == moved * eng.slots * steps
    assert 'dllama_ici_collectives_total{kind=' in reg.expose()


def test_unsharded_engine_has_no_collective_series(params):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    reg = Registry()
    ContinuousEngine(SPEC, params, slots=1, temperature=0.0, topp=0.9,
                     seed=5, metrics=reg)
    assert "dllama_ici_collectives_total" not in reg.expose()


# --------------------------------------------------- NDJSON run stamp


def test_log_json_records_carry_run_stamp(capsys, monkeypatch):
    """Every NDJSON record carries tp_scheme + the Q40 body policy + the
    bench env_fingerprint, so log streams join against BENCH_* rows."""
    from distributed_llama_tpu.obs.log import log_event
    from distributed_llama_tpu.utils import fingerprint

    from distributed_llama_tpu.ops.linear import (Q40Layout,
                                                  announce_q40_layout)

    monkeypatch.setenv("DLLAMA_LOG_JSON", "1")
    monkeypatch.setenv("DLLAMA_TP_SCHEME", "ref")
    # the stamp carries the label the process last announced, not a knob
    monkeypatch.setattr(fingerprint, "_Q40_BODY", "unresolved")
    announce_q40_layout(Q40Layout("i4-nb", "test"))
    capsys.readouterr()
    fingerprint.reset_stamp_cache()
    try:
        log_event("decode.token", None, pos=1)
        rec = json.loads(capsys.readouterr().out)
        assert rec["tp_scheme"] == "ref"
        assert rec["q40_body"] == "i4-nb"
        assert "clock" in rec["env_fingerprint"]
        # jax is imported under the test env: the fingerprint pins the
        # session basis the same way bench rows do
        assert rec["env_fingerprint"]["backend"] == "cpu"
        assert rec["pos"] == 1
    finally:
        fingerprint.reset_stamp_cache()  # drop the env-specific stamp


def test_log_stamp_knobs_read_fresh_per_record(capsys, monkeypatch):
    """A --model-from-root run logs BEFORE cli.py exports --tp-scheme:
    the knob fields must track the env per record, never freeze at the
    first event's values."""
    from distributed_llama_tpu.obs.log import log_event

    monkeypatch.setenv("DLLAMA_LOG_JSON", "1")
    monkeypatch.delenv("DLLAMA_TP_SCHEME", raising=False)
    log_event("weights.fetch_progress", None)  # early event, default env
    first = json.loads(capsys.readouterr().out)
    assert first["tp_scheme"] == "fused"
    monkeypatch.setenv("DLLAMA_TP_SCHEME", "ref")  # cli.py applies the flag
    log_event("decode.token", None)
    assert json.loads(capsys.readouterr().out)["tp_scheme"] == "ref"


def test_log_stamp_survives_bad_scheme_env(capsys, monkeypatch):
    """A malformed DLLAMA_TP_SCHEME must degrade the stamp, not take the
    log line (or its caller) down."""
    from distributed_llama_tpu.obs.log import log_event
    from distributed_llama_tpu.utils import fingerprint

    monkeypatch.setenv("DLLAMA_LOG_JSON", "1")
    monkeypatch.setenv("DLLAMA_TP_SCHEME", "bogus")
    fingerprint.reset_stamp_cache()
    try:
        log_event("x", None, n=1)
        rec = json.loads(capsys.readouterr().out)
        assert rec["tp_scheme"] == "bogus"  # reported verbatim, not raised
        assert rec["n"] == 1
    finally:
        fingerprint.reset_stamp_cache()


def test_bench_fingerprint_is_the_shared_one():
    """bench.py and the log stamp must report the SAME fingerprint dict —
    joinability means one producer, not two drifting copies."""
    import bench

    from distributed_llama_tpu.utils.fingerprint import env_fingerprint

    assert bench._env_fingerprint() == env_fingerprint()


# ------------------------------------------- profiler error paths


def test_profiler_unwritable_dir_fails_clean(tmp_path):
    """An uncreatable trace dir raises BEFORE the capture starts: the
    singleton stays free and a later capture into a good dir works."""
    from distributed_llama_tpu.obs import profiler

    blocker = tmp_path / "file"
    blocker.write_text("not a dir")
    bad = str(blocker / "sub")  # a path THROUGH a file: mkdir must fail
    with pytest.raises(OSError):
        profiler.start_capture(bad, 1.0)
    assert profiler.capture_active() is None
    good = str(tmp_path / "ok")
    profiler.start_capture(good, 0.2)
    assert profiler.capture_active() == good
    assert profiler.wait_capture(30)


def test_server_profile_409_and_500_paths(server, tmp_path):
    """Deterministic overlap: start a capture directly, then POST — the
    server must answer 409 while it runs and 500 for an unwritable
    DLLAMA_PROFILE_DIR-style target, then recover."""
    from distributed_llama_tpu.obs import profiler

    d = str(tmp_path / "held")
    profiler.start_capture(d, 0.5)
    try:
        _post(server.port, "/profile", {"seconds": 0.1,
                                        "dir": str(tmp_path / "x")})
        assert False, "expected 409"
    except urllib.error.HTTPError as e:
        assert e.code == 409
    assert profiler.wait_capture(30)

    blocker = tmp_path / "plainfile"
    blocker.write_text("x")
    try:
        _post(server.port, "/profile", {"seconds": 0.1,
                                        "dir": str(blocker / "sub")})
        assert False, "expected 500"
    except urllib.error.HTTPError as e:
        assert e.code == 500
        assert "trace dir" in json.loads(e.read())["error"]
    # the failed request must not wedge the singleton
    assert profiler.capture_active() is None
    out = _post(server.port, "/profile",
                {"seconds": 0.2, "dir": str(tmp_path / "after")})
    assert out["seconds"] == 0.2
    assert profiler.wait_capture(30)


# ------------------------------------------------- disaggregation (ISSUE 14)


def test_disagg_series_preregistered_at_zero():
    """ISSUE 14 satellite: DisaggMetrics pre-registers the whole handoff
    matrix at zero — a fresh prefill/decode pool scrapes the full
    surface before any request moves."""
    from distributed_llama_tpu.runtime.disagg import DisaggMetrics

    reg = Registry()
    DisaggMetrics(reg)
    text = reg.expose()
    for verdict in ("shipped", "local", "failed"):
        assert (f'dllama_handoff_requests_total{{verdict="{verdict}"}} 0'
                in text)
    assert "dllama_dcn_pages_shipped_total 0" in text
    assert "dllama_dcn_bytes_total 0" in text
    assert "dllama_handoff_queue_depth 0" in text
    assert "dllama_handoff_seconds_count 0" in text
    for family, kind in (
            ("dllama_handoff_requests_total", "counter"),
            ("dllama_dcn_pages_shipped_total", "counter"),
            ("dllama_dcn_bytes_total", "counter"),
            ("dllama_handoff_queue_depth", "gauge"),
            ("dllama_handoff_seconds", "histogram")):
        assert f"# TYPE {family} {kind}" in text
        assert f"# HELP {family} " in text


def test_disagg_handoff_moves_series_and_health_block(params):
    """A real two-pool handoff moves the dllama_dcn_* series (pages AND
    payload bytes pinned to the DCN budget's numbers), and /health on a
    disaggregated server carries the "disagg" block."""
    import json
    import urllib.request

    from distributed_llama_tpu.parallel.comm_stats import \
        dcn_handoff_budget
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine
    from distributed_llama_tpu.runtime.disagg import DisaggPair
    from distributed_llama_tpu.runtime.server import InferenceServer

    reg = Registry()
    make = lambda remote=False: ContinuousEngine(  # noqa: E731
        SPEC, params, slots=2, temperature=0.0, topp=0.9, seed=11,
        prefill_chunk=4, page_size=4, kv_pages=16, remote_pages=remote)
    pair = DisaggPair(make(), make(remote=True), registry=reg)
    prompt = [1, 9, 17, 25, 31, 7, 3, 44, 11]
    pair.run([prompt], steps=14)
    text = reg.expose()
    budget = dcn_handoff_budget(SPEC, 1, len(prompt) - 1, 4)
    assert f"dllama_dcn_pages_shipped_total {budget['pages']}" in text
    assert f"dllama_dcn_bytes_total {budget['bytes']}" in text
    assert 'dllama_handoff_requests_total{verdict="shipped"} 1' in text
    pair.close()

    server = InferenceServer(SPEC, params, _IdTokenizer(),
                             host="127.0.0.1", port=0, slots=2, steps=8,
                             temperature=0.0, topp=0.9, seed=3,
                             page_size=4, kv_pages=16,
                             disagg_role="prefill", quiet=True)
    server.start()
    try:
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/health", timeout=10).read())
        assert health["disagg"]["role"] == "prefill"
        assert health["disagg"]["handoff_queue_depth"] == 0
        assert health["disagg"]["page_channel_port"] > 0
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics",
            timeout=10).read().decode()
        assert "dllama_dcn_pages_shipped_total 0" in metrics
    finally:
        server.stop()
