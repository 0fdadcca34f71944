"""Host phases on the profiler's clock (obs/spans.host_phase) and the names
of the programs a capture shows (obs/spans.named_program).

A capture on the CPU backend with the Python tracer off (obs/profiler.
capture_options) around a few steps of a toy ``Engine`` / ``generate`` and
of a toy paged ``ContinuousEngine`` with one admission, read back with
``jax.profiler.ProfileData``: the names of PERF.md section 3 are there,
children lie inside parents, and no program span takes a name the
benchmark's drivers own. Times are not looked at: nothing measured here is
a device metric.
"""

import glob
import os
import re
import time

import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs import profiler
from distributed_llama_tpu.obs.spans import host_phase, named_program

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=32)

# what the benchmark's drivers wrap public calls in: not the program's
DRIVER_SPANS = {"inference.step", "inference.prefill", "inference.sample",
                "serve.step", "bench.window"}
INFERENCE_PHASES = {"inference.dispatch", "inference.fetch",
                    "inference.prefill_chunk", "inference.emit"}
# the phases one paged step_once iteration with an admission prefill runs
SERVE_PHASES = {"serve.intake", "serve.admit", "serve.admit.gather",
                "serve.admit.prefill_chunk", "serve.admit.scatter",
                "serve.grow_pages", "serve.stage", "serve.dispatch",
                "serve.fetch", "serve.decode", "serve.land",
                "serve.land.chunk", "serve.sample", "serve.census",
                "serve.journal"}
DECODE_CHILDREN = {"serve.stage", "serve.dispatch", "serve.fetch"}


class _IdTokenizer:
    def encode(self, text, bos=True, eos=False):
        return [1] + [3 + b for b in text.encode()]

    def decode_piece(self, prev, tok):
        return b"<%d>" % tok


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


def _capture(tmp_path, fn):
    """Run ``fn`` inside a capture with the program's own options; return
    the host spans ``[(name, line, start_ns, end_ns, args)]`` whose names
    the benchmark's reducer keeps."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=profiler.capture_options())
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/host:") and ev.name.startswith(
                        ("inference.", "serve.")):
                    spans.append((ev.name, line.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  {k: v for k, v in ev.stats}))
    return spans


def _parents(spans):
    """Per span, the name of the innermost span of its thread that
    encloses it (None at the top)."""
    out = []
    for i, (name, line, lo, hi, _a) in enumerate(spans):
        best = None
        for j, (n2, l2, lo2, hi2, _b) in enumerate(spans):
            if j == i or l2 != line or not (lo2 <= lo and hi <= hi2):
                continue
            if (lo2, hi2) == (lo, hi) and j > i:
                continue
            if best is None or hi2 - lo2 < best[1]:
                best = (n2, hi2 - lo2)
        out.append((name, best[0] if best else None))
    return out


# ------------------------------------------------------------- the primitive


def test_host_phase_is_a_profiler_annotation_and_free_when_dark():
    import jax

    ph = host_phase("serve.fetch", trace_id="abc")
    assert isinstance(ph, jax.profiler.TraceAnnotation)
    t0 = time.perf_counter()
    for _ in range(2000):
        with host_phase("serve.fetch"):
            pass
    # no capture runs: a span is well under the 0.1 ms the clocks agree to
    assert (time.perf_counter() - t0) / 2000 < 50e-6


def test_host_phase_is_the_only_annotation_call_site():
    """The program names the profiler's annotation in one place."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "distributed_llama_tpu")
    hits = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as fh:
            for n, text in enumerate(fh, 1):
                if re.search(r"import\b.*\bTraceAnnotation\b", text):
                    hits.append(f"{os.path.relpath(path, root)}:{n}")
    assert len(hits) == 1 and hits[0].startswith(
        os.path.join("obs", "spans.py")), hits
    # and no other file mentions it at all
    assert not [p for p in glob.glob(os.path.join(root, "**", "*.py"),
                                     recursive=True)
                if not p.endswith(os.path.join("obs", "spans.py"))
                and "TraceAnnotation" in open(p, encoding="utf-8").read()]


def test_named_program_names_the_lowered_module():
    import functools

    import jax
    import jax.numpy as jnp

    def scale(k, x):
        return x * k

    part = functools.partial(scale, 2.0)
    assert "jit__unknown" in jax.jit(part).lower(jnp.ones(3)).as_text()
    named = jax.jit(named_program("inference_step", part))
    assert "@jit_inference_step" in named.lower(jnp.ones(3)).as_text()
    assert float(named(jnp.ones(3))[0]) == 2.0
    assert part.__dict__ == {}      # the shared function is left alone


def test_capture_options_turn_the_python_tracer_off():
    opts = profiler.capture_options()
    assert opts.python_tracer_level == 0
    assert opts.host_tracer_level == 2


# --------------------------------------------------------------- inference


@pytest.fixture(scope="module")
def inference_capture(params, tmp_path_factory):
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    eng = Engine(SPEC, params)
    tok = _IdTokenizer()
    prompt = "abcdefghijkl"      # 13 tokens: one padded T=12 prefill chunk

    def run():
        generate(eng, tok, Sampler(SPEC.vocab_size, 0.0, 0.9, seed=3),
                 prompt, 18, emit=lambda piece: None, quiet=True,
                 prefill_chunk=12)

    run()                        # compile outside the capture
    eng.reset()
    return _capture(tmp_path_factory.mktemp("inference"), run)


def test_inference_phases_all_there(inference_capture):
    spans = inference_capture
    got = {s[0] for s in spans}
    assert INFERENCE_PHASES <= got
    assert not got & DRIVER_SPANS
    assert not [n for n in got if n.endswith(".step")]


def test_inference_dispatch_and_fetch_tile_infer(inference_capture):
    """``Engine.infer`` is dispatch then fetch, nothing between: each
    dispatch is followed by a fetch on its thread before the next."""
    spans = inference_capture
    seq = sorted((s for s in spans
                  if s[0] in ("inference.dispatch", "inference.fetch")),
                 key=lambda s: s[2])
    assert len(seq) >= 10 and len(seq) % 2 == 0
    for d, f in zip(seq[::2], seq[1::2]):
        assert (d[0], f[0]) == ("inference.dispatch", "inference.fetch")
        assert d[3] <= f[2]
        # the seam between the two is a few Python bytecodes
        assert f[2] - d[3] < 1e6
    assert all(p is None for n, p in _parents(spans)
               if n.startswith("inference."))


def test_inference_one_pair_a_token_and_no_host_sampler(inference_capture):
    """At temperature 0 the device picks the token (PR 27): ``infer`` is
    still one dispatch and one fetch a token, the step enqueued ahead under
    the dispatch, and the host's sampler phase is not there."""
    spans = inference_capture
    n = {k: sum(s[0] == k for s in spans)
         for k in INFERENCE_PHASES | {"inference.sampler"}}
    # 12 prompt positions prefilled in one chunk, 6 sampled of 18 positions
    assert n["inference.prefill_chunk"] == 1
    assert n["inference.dispatch"] == n["inference.fetch"] == 6
    assert n["inference.sampler"] == 0
    assert n["inference.emit"] == 6


def test_inference_sampler_phase_with_a_temperature(params, tmp_path):
    """With a temperature the host samples as before: one
    ``inference.sampler`` a sampled token, after the prompt only."""
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    eng = Engine(SPEC, params)

    def run():
        generate(eng, _IdTokenizer(),
                 Sampler(SPEC.vocab_size, 0.8, 0.0, seed=3), "abcdefghijkl",
                 18, emit=lambda piece: None, quiet=True, prefill_chunk=12)

    spans = _capture(tmp_path, run)
    n = {k: sum(s[0] == k for s in spans)
         for k in INFERENCE_PHASES | {"inference.sampler"}}
    sampled = n["inference.sampler"]        # 6, or fewer on a sampled BOS
    assert 1 <= sampled <= 6
    assert n["inference.dispatch"] == n["inference.fetch"] == sampled
    assert n["inference.emit"] == sampled


def test_inference_program_names(params):
    """The step and the prefill chunk are two programs with two names."""
    import jax.numpy as jnp

    from distributed_llama_tpu.runtime.generate import Engine

    eng = Engine(SPEC, params)
    step = eng._fwd.lower(eng.params, eng.cache, jnp.zeros((1,), jnp.int32),
                          jnp.int32(0)).as_text()
    chunk = eng._fwd_prefill.lower(
        eng.params, eng.cache, jnp.zeros((12,), jnp.int32),
        jnp.int32(0)).as_text()
    assert "@jit_inference_step" in step
    assert "@jit_inference_prefill_chunk" in chunk


# ------------------------------------------------------------------- serve


def _engine(params, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    return ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                            topp=0.9, seed=5, page_size=4, prefill_chunk=4,
                            **kw)


@pytest.fixture(scope="module")
def serve_capture(params, tmp_path_factory):
    """A DARK paged engine (no registry) with a journal: one admission
    with a prefill of three chunks, then decode steps."""
    from distributed_llama_tpu.obs import tracectx
    from distributed_llama_tpu.runtime.continuous import Request
    from distributed_llama_tpu.runtime.journal import RequestJournal

    warm = _engine(params)
    warm.run([[1, 5, 9, 2, 8, 3, 7, 4, 6, 11]], steps=14)   # compile
    eng = _engine(params, journal=RequestJournal(
        str(tmp_path_factory.mktemp("journal") / "requests.journal")))
    assert eng._spans is None
    ctx = tracectx.mint()
    req = Request(tokens=[1, 5, 9, 2, 8, 3, 7, 4, 6, 12], steps=14,
                  trace=ctx)

    def run():
        eng.submit(req)
        while eng.step_once():
            pass

    spans = _capture(tmp_path_factory.mktemp("serve"), run)
    assert req.done.is_set() and req.error is None
    return spans, ctx, eng


def test_serve_phases_all_there_from_a_dark_engine(serve_capture):
    spans, _, eng = serve_capture
    got = {s[0] for s in spans}
    assert SERVE_PHASES <= got, SERVE_PHASES - got
    assert not got & DRIVER_SPANS
    assert not [n for n in got if n.endswith(".step")]
    assert eng._spans is None          # and the ring recorded nothing


def test_serve_children_lie_inside_their_parents(serve_capture):
    spans, _, _ = serve_capture
    parents = _parents(spans)
    for name, parent in parents:
        if name.startswith("serve.admit."):
            assert parent == "serve.admit", (name, parent)
        elif name in ("serve.dispatch", "serve.fetch"):
            assert parent == "serve.decode", (name, parent)
        elif name == "serve.stage":
            assert parent in (None, "serve.decode")
        elif name in ("serve.land.chunk", "serve.census", "serve.sample"):
            assert parent == "serve.land", (name, parent)
        else:
            assert parent is None, (name, parent)
    inside = {n for n, p in parents if p == "serve.decode"}
    assert inside == DECODE_CHILDREN


def test_serve_admit_carries_the_trace_id(serve_capture):
    spans, ctx, _ = serve_capture
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert len(admits) == 1
    assert admits[0][4].get("trace_id") == ctx.trace_id
    chunks = [s for s in spans if s[0] == "serve.admit.prefill_chunk"]
    assert len(chunks) == 3            # 9 prefix positions in T=4 chunks


def test_serve_program_names(params):
    import jax.numpy as jnp

    eng = _engine(params)
    tbl = jnp.zeros((2, eng._max_pages), jnp.int32)
    row = jnp.zeros((2,), jnp.int32)
    assert "@jit_serve_decode_step" in eng._step.lower(
        eng.params, eng.cache, row, row, tbl).as_text()
    one = jnp.zeros((eng._max_pages,), jnp.int32)
    gathered = eng._gather_pages(eng.cache, one)
    assert "@jit_serve_admit_gather" in eng._gather_pages.lower(
        eng.cache, one).as_text()
    assert "@jit_serve_admit_prefill_chunk" in eng._prefill_fwd.lower(
        eng.params, gathered, jnp.zeros((4,), jnp.int32),
        jnp.int32(0)).as_text()
    assert "@jit_serve_admit_scatter" in eng._scatter_pages.lower(
        eng.cache, gathered, one).as_text()


def test_serve_chain_and_idle_phases(params, tmp_path):
    """The sibling step methods open the same phases (here the fused
    chain), and a lit engine records its ring spans beside them."""
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    def build():
        return ContinuousEngine(SPEC, params, slots=1, temperature=0.0,
                                topp=0.9, seed=5, block_steps=3,
                                prefill_chunk=2, metrics=Registry())

    build().run([[1, 5, 9, 2, 8]], steps=10)      # compile
    eng = build()
    spans = _capture(tmp_path, lambda: eng.run([[1, 5, 9, 2, 8]],
                                               steps=10))
    got = {s[0] for s in spans}
    assert {"serve.decode", "serve.stage", "serve.dispatch", "serve.fetch",
            "serve.sample", "serve.census", "serve.admit",
            "serve.admit.scatter"} <= got
    ring = {s.name for s in eng._spans.snapshot()}
    assert {"chain", "prefill", "request"} <= ring
    assert not [n for n in ring if "." in n]      # the ring keeps its names
    # the contiguous engine's admission ends in the insert program, which
    # ``..admission_device_share`` counts by its prefix
    assert "@jit_serve_admit_insert" in eng._insert.lower(
        eng.cache, eng._scratch_cache(), 0).as_text()


@pytest.mark.parametrize("kw", [dict(dispatch_tokens=4), dict(spec_k=2)],
                         ids=["step_mixed", "step_spec"])
def test_the_sibling_step_methods_open_the_same_phases(params, tmp_path, kw):
    """No cell runs the mixed or the speculative dispatch; a capture of one
    splits by the same names all the same."""
    prompt = [[1, 5, 9, 2, 8, 3, 7]]
    _engine(params, **kw).run(prompt, steps=12)   # compile
    eng = _engine(params, **kw)
    spans = _capture(tmp_path, lambda: eng.run(prompt, steps=12))
    got = {s[0] for s in spans}
    assert {"serve.intake", "serve.admit", "serve.grow_pages",
            "serve.stage", "serve.decode", "serve.dispatch", "serve.fetch",
            "serve.sample", "serve.census"} <= got, got
    assert not [n for n in got if n.endswith(".step")]
    inside = {n for n, p in _parents(spans) if p == "serve.decode"}
    assert inside == DECODE_CHILDREN


def test_server_scheduler_marks_idle(params, tmp_path):
    from distributed_llama_tpu.runtime.server import InferenceServer

    srv = InferenceServer(SPEC, params, _IdTokenizer(), "127.0.0.1", 0,
                          slots=1, steps=4, temperature=0.0, topp=0.9,
                          seed=5, quiet=True, metrics=False)
    srv.start()
    try:
        spans = _capture(tmp_path, lambda: time.sleep(0.05))
    finally:
        srv.stop()
    assert "serve.idle" in {s[0] for s in spans}
