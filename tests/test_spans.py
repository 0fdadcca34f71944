"""Span tracer + named-scope threading: the tentpole's emission side.

Covers obs/spans.py (nesting, thread safety, Chrome/NDJSON exports, the
schema validator), the engine's span wiring (/debug/timeline round trip,
dark-engine silence), and the contract that parallel/tp.py's traced
forward actually CARRIES the canonical phase/collective scope names a
reader of captures buckets by."""

import json
import threading
import time
import urllib.request

import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs.spans import (COLLECTIVE_SCOPE_KINDS,
                                             PHASE_SCOPES, SpanTracer,
                                             validate_chrome_trace)

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


# ------------------------------------------------------------- tracer


def test_span_nesting_depth_and_meta():
    tr = SpanTracer()
    with tr.span("request", cat="request", index=0):
        with tr.span("step", cat="decode", active=2):
            time.sleep(0.001)
    spans = tr.snapshot()
    # inner completes first; depths rebuild the hierarchy
    assert [(s.name, s.depth) for s in spans] == [("step", 1),
                                                  ("request", 0)]
    assert spans[0].meta == {"active": 2}
    assert spans[0].dur_s > 0
    assert spans[1].dur_s >= spans[0].dur_s


def test_span_records_on_exception():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("step", cat="decode"):
            raise RuntimeError("boom")
    (s,) = tr.snapshot()
    assert s.meta["error"].startswith("RuntimeError")
    # the stack unwound: a new span starts back at depth 0
    with tr.span("next"):
        pass
    assert tr.snapshot()[-1].depth == 0


def test_span_ring_buffer_bounds_memory():
    tr = SpanTracer(capacity=8)
    for i in range(50):
        tr.add(f"s{i}", "phase", float(i), 0.001)
    spans = tr.snapshot()
    assert len(spans) == 8
    assert spans[0].name == "s42" and spans[-1].name == "s49"


def test_span_tracer_thread_safety():
    tr = SpanTracer(capacity=10000)

    def worker(k):
        for _ in range(100):
            with tr.span(f"w{k}"):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.snapshot()
    assert len(spans) == 800
    assert all(s.depth == 0 for s in spans)  # per-thread stacks don't mix


def test_chrome_export_is_valid_and_ordered():
    tr = SpanTracer()
    with tr.span("step", cat="decode", active=1):
        pass
    doc = tr.export_chrome()
    validate_chrome_trace(doc)  # the schema gate used on CI artifacts
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["cat"] == "decode"
    assert ev["args"]["active"] == 1 and ev["args"]["depth"] == 0


def test_ndjson_export_one_object_per_line():
    tr = SpanTracer()
    with tr.span("prefill", cat="prefill", tokens=7):
        pass
    lines = tr.export_ndjson().strip().splitlines()
    recs = [json.loads(ln) for ln in lines]
    assert recs[0]["span"] == "prefill" and recs[0]["tokens"] == 7
    assert tr.export_ndjson().endswith("\n")
    assert SpanTracer().export_ndjson() == ""


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "Z",
                                                "ts": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                                "ts": -1, "dur": 1}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                                "ts": 0}]})  # no dur
    validate_chrome_trace({"traceEvents": []})  # empty is fine


# ------------------------------------------- named scopes in the forward


def _name_stacks(jaxpr, out=None):
    """Every eqn's name-stack string, recursing into sub-jaxprs (scan
    bodies, shard_map callees)."""
    import jax

    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        st = getattr(eqn.source_info, "name_stack", None)
        if st is not None:
            out.append(str(st))
        for v in eqn.params.values():
            leaves = v if isinstance(v, (list, tuple)) else [v]
            for leaf in leaves:
                if isinstance(leaf, jax.extend.core.ClosedJaxpr):
                    _name_stacks(leaf.jaxpr, out)
                elif hasattr(leaf, "eqns"):  # raw Jaxpr
                    _name_stacks(leaf, out)
    return out


@pytest.mark.parametrize("scheme", ["ref", "fused", "overlap"])
def test_tp_forward_carries_phase_and_collective_scopes(scheme):
    """The traced tp forward must label every phase and every collective
    at source — the attribution contract of obs/spans.py."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import init_cache
    from distributed_llama_tpu.parallel import make_mesh, shard_params
    from distributed_llama_tpu.parallel.tp import make_sharded_forward

    mesh = make_mesh(tp=2)
    params = shard_params(synth_params(SPEC, q40=False, seed=0), mesh,
                          scheme=scheme)
    cache = init_cache(SPEC)
    fwd = make_sharded_forward(SPEC, mesh, scheme=scheme)
    jaxpr = jax.make_jaxpr(lambda p, c, t, s: fwd(p, c, t, s))(
        params, cache, jnp.zeros((1,), jnp.int32), jnp.int32(0))
    stacks = _name_stacks(jaxpr.jaxpr)
    if not stacks:
        pytest.skip("this jax exposes no eqn name stacks")
    blob = "\n".join(stacks)
    for scope in PHASE_SCOPES:
        assert scope in blob, f"phase scope {scope!r} missing from trace"
    expected_coll = {"ref": ["ici_all_gather"],
                     "fused": ["ici_all_gather", "ici_psum"],
                     "overlap": ["ici_all_gather", "ici_ppermute"]}[scheme]
    for scope in expected_coll:
        assert scope in blob, f"collective scope {scope!r} missing"


# --------------------------------------------- engine + /debug/timeline


def test_engine_records_spans_when_enabled(params):
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                           topp=0.9, seed=5, metrics=Registry())
    eng.run([[1, 5, 9], [1, 7]], steps=8)
    names = {s.name for s in eng._spans.snapshot()}
    assert "step" in names or "chain" in names
    assert "request" in names
    reqs = [s for s in eng._spans.snapshot() if s.name == "request"]
    assert len(reqs) == 2
    assert all(s.meta["tokens"] > 0 for s in reqs)


def test_engine_chain_spans_and_prefill(params):
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = ContinuousEngine(SPEC, params, slots=1, temperature=0.0,
                           topp=0.9, seed=5, block_steps=3,
                           prefill_chunk=2, metrics=Registry())
    eng.run([[1, 5, 9, 2, 8]], steps=10)
    names = [s.name for s in eng._spans.snapshot()]
    assert "chain" in names
    assert "prefill" in names


def test_engine_dark_records_no_spans(params):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = ContinuousEngine(SPEC, params, slots=1, temperature=0.0,
                           topp=0.9, seed=5)
    eng.run([[1, 5]], steps=4)
    assert eng._spans is None


def test_server_debug_timeline_endpoint(params):
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=2, steps=6, temperature=0.0, topp=0.9,
                          seed=5, quiet=True)
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=json.dumps({"prompt": "ab", "steps": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert json.loads(r.read())["steps"] > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/timeline",
                timeout=30) as r:
            assert r.headers["Content-Type"] == "application/json"
            doc = json.loads(r.read())
        validate_chrome_trace(doc)
        names = {ev["name"] for ev in doc["traceEvents"]}
        assert "request" in names and ("step" in names or "chain" in names)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/timeline?format=ndjson",
                timeout=30) as r:
            assert r.headers["Content-Type"] == "application/x-ndjson"
            lines = r.read().decode().strip().splitlines()
        assert any(json.loads(ln)["span"] == "request" for ln in lines)
    finally:
        srv.stop()


def test_server_timeline_404_when_disabled(params):
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=1, steps=4, temperature=0.0, topp=0.9,
                          seed=5, quiet=True, metrics=False)
    srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/timeline", timeout=30)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_collective_scope_kinds_are_budget_kinds():
    """The scope→kind map must speak the budget's vocabulary — a rename
    on either side silently unjoins measurement from model."""
    from distributed_llama_tpu.models.synth import llama2_7b_spec
    from distributed_llama_tpu.parallel.comm_stats import (
        SCHEMES, tp_collective_budget)

    budget_kinds = set()
    for scheme in SCHEMES:
        budget_kinds |= set(
            tp_collective_budget(llama2_7b_spec(), 8, scheme).kind_counts())
    assert budget_kinds <= set(COLLECTIVE_SCOPE_KINDS.values())
