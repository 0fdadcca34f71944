"""Chaos drills + fault-injection hooks (runtime/chaos.py, ISSUE 8):
deterministic injection, post-drill invariant audits, the engine cancel()
path, and the server-level disconnect regression that counts
kv_pages_free before/after."""

import json
import urllib.error
import urllib.request

import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs.metrics import Registry
from distributed_llama_tpu.runtime.chaos import (ChaosMonkey, check_invariants,
                                                 run_drills, scrape_problems)
from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                      Request)
from distributed_llama_tpu.runtime.paging import PagedAllocator

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=32)


class _IdTokenizer:
    def encode(self, text, bos=True, eos=False):
        return [1] + [3 + b for b in text.encode()]

    def decode_piece(self, prev, tok):
        return b"<%d>" % tok


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


@pytest.fixture()
def make_engine(params):
    made = []

    def factory(chaos=None, **overrides):
        kw = dict(slots=4, temperature=0.0, topp=0.9, seed=7,
                  metrics=Registry(), prefill_chunk=4, page_size=4,
                  kv_pages=20)
        kw.update(overrides)
        made.append(ContinuousEngine(SPEC, params, chaos=chaos, **kw))
        return made[-1]

    yield factory
    for eng in made:    # a tiered one owns a PageUploader thread
        eng.close()


# -------------------------------------------------------------- audit


def test_audit_clean_and_each_violation_kind():
    alloc = PagedAllocator(n_pages=6, page_size=4)
    assert alloc.audit([]) == []
    a, b = alloc.alloc_page(), alloc.alloc_page()
    assert alloc.audit([[a], [b]]) == []
    # leak: allocated page that no slot or tree node maps
    leak = alloc.audit([[a]])
    assert any("leaked" in p and str(b) in p for p in leak)
    # use-after-free in waiting: slot maps a page the pool freed
    alloc.release_pages([b])
    uaf = alloc.audit([[a], [b]])
    assert any(f"page {b}" in p and "refcount" in p for p in uaf)
    # refcount mismatch: double-mapped page with a single ref
    bad = alloc.audit([[a], [a]])
    assert any("refcount 1 != 2" in p for p in bad)
    # scrap page must never be mapped
    scrap = alloc.audit([[0]])
    assert any("scrap" in p for p in scrap)


def test_audit_accounts_tree_references():
    alloc = PagedAllocator(n_pages=6, page_size=2)
    pages = [alloc.alloc_page(), alloc.alloc_page()]
    tokens = [9, 8, 7, 6]  # two full pages
    alloc.insert_prefix(tokens, pages)
    # slot + tree each hold a ref
    assert alloc.audit([pages]) == []
    alloc.release_pages(pages)  # tree keeps them alive
    assert alloc.audit([]) == []
    assert alloc.pool.refcount(pages[0]) == 1


def test_scrape_problems_flags_broken_exposition():
    class _Bad:
        def expose(self):
            raise RuntimeError("boom")

    assert scrape_problems(None) == []
    assert scrape_problems(Registry()) == []
    assert any("boom" in p for p in scrape_problems(_Bad()))


# -------------------------------------------------------- ChaosMonkey


def test_chaos_monkey_parse_and_determinism():
    m = ChaosMonkey.parse(
        "step_delay_every=3,step_delay_ms=1,deny_pages=2,leak_on_cancel=1")
    assert m.step_delay_every == 3
    assert m.step_delay_s == pytest.approx(0.001)
    assert m.deny_pages == 2 and m.leak_on_cancel
    assert ChaosMonkey.parse("leak_on_cancel=0").leak_on_cancel is False
    with pytest.raises(ValueError):
        ChaosMonkey.parse("nope=1")
    with pytest.raises(ValueError):
        ChaosMonkey.parse("step_delay_every")
    # denial is a counter, not a coin: exactly N denials then clean
    m = ChaosMonkey(deny_pages=2)
    assert [m.deny_page() for _ in range(4)] == [True, True, False, False]
    # delay fires on every Nth dispatch exactly
    m = ChaosMonkey(step_delay_every=2, step_delay_s=0.0001)
    for _ in range(5):
        m.on_dispatch()
    assert m.injected_delays == 2


# ------------------------------------------------------------- cancel


def test_cancel_queued_request_completes_immediately(params):
    eng = ContinuousEngine(SPEC, params, slots=1, temperature=0.0,
                           topp=0.9, seed=5, metrics=Registry())
    first = Request(tokens=[1, 5, 9], steps=SPEC.seq_len)
    queued = Request(tokens=[1, 7], steps=SPEC.seq_len)
    eng.submit(first)
    eng.step_once()  # first occupies the only slot
    eng.submit(queued)
    eng.cancel(queued)  # still queued: completes NOW, no scheduler needed
    assert queued.done.is_set() and queued.cancelled
    reg = eng._obs.registry
    assert reg.get("dllama_requests_cancelled_total").value == 1
    assert reg.get("dllama_queue_depth").value == 0
    first.cancelled = True  # drain the slot for a clean engine
    while eng.step_once():
        pass


def test_cancel_in_flight_frees_pages_at_next_sweep(params):
    """The satellite-1 engine half: cancel() on a decoding request frees
    its slot AND pages at the next scheduler touch (the pre-dispatch
    sweep), not after another full chain of decoding."""
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=Registry(),
                           page_size=4, block_steps=8)
    free0 = eng.allocator.n_free
    req = Request(tokens=[1, 5, 9, 11, 13], steps=SPEC.seq_len)
    eng.submit(req)
    eng.step_many(2)  # two steps: still mid-prompt-echo, pages held
    held = next(len(s.pages) for s in eng._pool if not s.free)
    assert held > 0
    tokens_at_cancel = len(req.out)
    eng.cancel(req)
    eng.step_many(eng.block_steps)  # sweep runs before the next dispatch
    assert req.done.is_set()
    # the sweep retired it BEFORE dispatching another chain: no further
    # tokens were decoded for the vanished consumer
    assert len(req.out) == tokens_at_cancel
    assert eng.allocator.n_free == free0  # cancelled publishes nothing
    assert eng.audit_pages() == []
    reg = eng._obs.registry
    assert reg.get("dllama_kv_pages_free").value == free0


# -------------------------------------------------------------- drills


def test_all_drills_pass_on_healthy_engine(make_engine):
    from distributed_llama_tpu.runtime.chaos import DRILLS

    assert [name for name, _ in DRILLS] == [
        "pool_exhaustion", "transient_starvation", "oversized_prompt",
        "disconnect", "latency_spike", "profiler_under_load",
        "tier_spill_storm", "journal_wal", "kill_mid_handoff",
        "kill_mid_decode", "hung_dispatch", "weight_stream_disconnect"]
    # kill_mid_decode spawns a jax subprocess and kill_mid_handoff
    # drives full two-pool engines — each has its own slow-marked test
    # (here + tests/test_disagg.py); everything else runs here
    which = {name for name, _ in DRILLS} - {"kill_mid_decode",
                                            "kill_mid_handoff"}
    results = run_drills(make_engine, which=which)
    assert len(results) == len(which)
    assert all(r.passed for r in results), [
        (r.name, r.violations) for r in results if not r.passed]
    # the drills actually exercised their faults
    by_name = {r.name: r for r in results}
    assert by_name["pool_exhaustion"].details["pauses"] > 0
    assert by_name["transient_starvation"].details["denied_allocs"] == 6
    assert by_name["latency_spike"].details["injected_delays"] > 0
    assert by_name["disconnect"].details["pages_at_risk"] > 0
    assert by_name["hung_dispatch"].details["trips"] > 0
    assert by_name["weight_stream_disconnect"].details["drops"] > 0
    storm = by_name["tier_spill_storm"].details
    assert storm["prefill_saved_spilled"] > 0
    assert sum(storm["demotions"].values()) > 0
    assert sum(storm["promotions"].values()) > 0


def test_kill_mid_decode_drill_recovers_bitwise(make_engine):
    """The crash-safety acceptance drill (ISSUE 9): SIGKILL a journaling
    subprocess mid-decode; the recovered continuation must be bitwise the
    uninterrupted reference for greedy AND seeded-sampled requests, with
    a clean page audit."""
    results = run_drills(make_engine, which={"kill_mid_decode"})
    assert len(results) == 1
    r = results[0]
    assert r.passed, r.violations
    assert r.details["recovered"] == 2
    assert r.details["replayed_tokens"] >= 4


def test_corrupt_journal_turns_kill_drill_red(make_engine):
    """The recovery gate's mutation arm: a byte smashed MID-journal before
    recovery must raise JournalCorruption and fail the drill — proving
    tools/ci.sh's exit-1 assertion can actually fire."""
    results = run_drills(make_engine, which={"kill_mid_decode"},
                         inject={"corrupt-journal"})
    assert len(results) == 1 and not results[0].passed
    assert any("JournalCorruption" in v for v in results[0].violations)


def test_seeded_leak_turns_disconnect_drill_red(make_engine):
    """The gate's mutation arm: leak_on_cancel must be CAUGHT by the
    disconnect drill's audit (kv_pages_free round-trip + page audit)."""

    def leaky(chaos=None, **overrides):
        if chaos is None:
            chaos = ChaosMonkey(leak_on_cancel=True)
        else:
            chaos.leak_on_cancel = True
        return make_engine(chaos=chaos, **overrides)

    results = run_drills(leaky, which={"disconnect"})
    assert len(results) == 1 and not results[0].passed
    text = " ".join(results[0].violations)
    assert "leaked" in text and "round-trip" in text


def test_check_invariants_passes_fresh_and_flags_leak(make_engine):
    eng = make_engine()
    assert check_invariants(eng) == []
    # hand-build a leak: allocate a page no slot list will ever explain
    eng.allocator.alloc_page()
    assert any("leaked" in p for p in check_invariants(eng))


# ------------------------------------------- server-level regression


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return r.read()


def _metric_value(port, name):
    for line in _get(port, "/metrics").decode().splitlines():
        if line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"{name} not in /metrics")


def test_server_stream_disconnect_frees_kv_pages(params):
    """Satellite 1, drill-backed: a client vanishing mid-stream must free
    the slot AND its KV pages immediately (engine.cancel + pre-dispatch
    sweep), counted via dllama_kv_pages_free before/after."""
    import http.client
    import time

    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=2, steps=SPEC.seq_len, temperature=0.0,
                          topp=0.9, seed=5, quiet=True, page_size=4,
                          block_steps=4)
    srv.start()
    try:
        free_before = _metric_value(srv.port, "dllama_kv_pages_free")
        assert free_before == srv.engine.allocator.n_pages
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=30)
        conn.request("POST", "/generate",
                     body=json.dumps({"prompt": "hello there",
                                      "steps": SPEC.seq_len,
                                      "stream": True}))
        resp = conn.getresponse()
        resp.read(1)  # the request is decoding in a slot, pages held
        conn.close()  # vanish mid-stream

        deadline = time.time() + 30
        while time.time() < deadline:
            h = json.loads(_get(srv.port, "/health"))
            if h["active"] == 0 and h["queued"] == 0:
                break
            time.sleep(0.05)
        assert h["active"] == 0 and h["queued"] == 0, h
        # every page came back: a cancelled request publishes nothing to
        # the radix tree, so free must round-trip exactly
        deadline = time.time() + 10
        while time.time() < deadline:
            if _metric_value(srv.port, "dllama_kv_pages_free") \
                    == free_before:
                break
            time.sleep(0.05)
        assert _metric_value(srv.port, "dllama_kv_pages_free") \
            == free_before
        assert srv.engine.audit_pages() == []
    finally:
        srv.stop()


def test_server_oversized_prompt_rejected_and_counted(params):
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=2, steps=8, temperature=0.0, topp=0.9,
                          seed=5, quiet=True)
    srv.start()
    try:
        body = json.dumps({"prompt": "x" * (2 * SPEC.seq_len),
                           "steps": 8}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
        assert "seq_len" in json.loads(ei.value.read())["error"]
        text = _get(srv.port, "/metrics").decode()
        assert ('dllama_admission_rejected_total{reason="oversized"} 1'
                in text)
        h = json.loads(_get(srv.port, "/health"))
        assert h["admission_rejected"]["oversized"] == 1
    finally:
        srv.stop()
