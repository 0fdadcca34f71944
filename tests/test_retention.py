"""A power-retention spec (Brumby-14B's layer at a toy size; Pallas kernels
in interpret mode): the recurrent step, the chunked prefill, ``Engine`` and
``serve`` against ``models/reference_retention`` (the attention form, O(T^2),
float32) on LOGITS with seeded weights; the header's three versions; the
refusals; and a precision guard (phi in bfloat16 must FAIL the tolerance).

A seeded bias-free ``w_gate`` puts the gates near 0.5, so a seeded MODEL
remembers a few tokens. The kernel-level tests therefore take the gate as an
input, drawn in 0.95 to 0.9995 over 1,024 positions: there a state's long
memory is what is compared.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, tensor_byte_ranges,
                                             write_model)
from distributed_llama_tpu.models import reference_retention as ref
from distributed_llama_tpu.models.llama import (forward, forward_retention,
                                                init_cache, init_cache_batch,
                                                params_to_device)
from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import retention
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5      # float32 paths on logits ~N(0, 1); read 6e-6 here
SEQ = 40


def toy_spec(**kw):
    base = dict(dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=2,
                vocab_size=512, seq_len=128,
                weights_float_type=FloatType.Q40, qk_norm=True,
                qk_norm_per_head=True, attn_kind="retention",
                rope_theta=1e6, norm_eps=1e-6)
    base.update(kw)
    return TransformerSpec(**base)


SPEC = toy_spec()


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=11)


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in
            np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)]


@pytest.fixture(scope="module")
def want(tree, tokens):
    return ref.forward(tree, SPEC, tokens)


@pytest.fixture(scope="module")
def step(tree):
    """(params, jitted forward_retention with the normaliser)."""
    params = params_to_device(tree, spec=SPEC)
    fn = jax.jit(lambda p, c, t, pos, nv: forward_retention(
        SPEC, p, c, t, pos, nv, norm_min=True))
    return params, fn


# -- the model against the reference, on logits --------------------------------

def test_recurrent_step_from_position_zero(step, tokens, want):
    params, fn = step
    cache, worst = init_cache(SPEC), 0.0
    for pos in range(12):
        got, cache, low = fn(params, cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos), jnp.int32(1))
        worst = max(worst, float(np.abs(np.asarray(got)[0] - want[pos]).max()))
        assert low.shape == (SPEC.n_layers,) and float(low.min()) > 0
    assert worst < TOL


@pytest.mark.parametrize("chunk", [8, 16, 40, 24])   # 24, 16: do not divide
def test_chunked_prefill_each_position(step, tokens, want, chunk):
    params, fn = step
    cache, worst = init_cache(SPEC), 0.0
    for lo in range(0, SEQ, chunk):
        n = min(chunk, SEQ - lo)
        part = tokens[lo:lo + n] + [0] * (chunk - n)   # padded: not counted
        got, cache, _ = fn(params, cache, jnp.asarray(part), jnp.int32(lo),
                           jnp.int32(n))
        worst = max(worst, float(
            np.abs(np.asarray(got)[:n] - want[lo:lo + n]).max()))
    assert worst < TOL


@pytest.mark.parametrize("n_pre,chunk", [(30, 8), (32, 16), (7, 16)])
def test_prefill_then_decode(step, tokens, want, n_pre, chunk):
    params, fn = step
    cache = init_cache(SPEC)
    for lo in range(0, n_pre, chunk):
        n = min(chunk, n_pre - lo)
        _, cache, _ = fn(params, cache,
                         jnp.asarray(tokens[lo:lo + n] + [0] * (chunk - n)),
                         jnp.int32(lo), jnp.int32(n))
    worst = 0.0
    for pos in range(n_pre, SEQ):
        got, cache, _ = fn(params, cache, jnp.asarray(tokens[pos:pos + 1]),
                           jnp.int32(pos), jnp.int32(1))
        worst = max(worst, float(np.abs(np.asarray(got)[0] - want[pos]).max()))
    assert worst < TOL


def test_forward_routes_a_retention_spec(step, tokens, want):
    params, _ = step
    got, cache = jax.jit(lambda p, c, t: forward(SPEC, p, c, t, jnp.int32(0)))(
        params, init_cache(SPEC), jnp.asarray(tokens))
    assert np.abs(np.asarray(got) - want).max() < TOL
    # nothing in the cache scales with seq_len
    assert cache.s.shape == (2, 2, 9, 16, 16) and cache.z.shape == (2, 2, 9, 16)
    assert init_cache_batch(SPEC, 3).s.shape == (2, 3, 2, 9, 16, 16)


def test_bf16_phi_fails_the_tolerance(step, tokens, want, monkeypatch):
    """The guard: the same comparison with phi (so the state built from it)
    held in bfloat16 is out by orders of magnitude."""
    params, _ = step
    real = retention.phi
    monkeypatch.setattr(retention, "phi", lambda u: real(u).astype(
        jnp.bfloat16).astype(jnp.float32))
    fn = jax.jit(lambda p, c, t, pos, nv: forward_retention(
        SPEC, p, c, t, pos, nv))
    _, cache = fn(params, init_cache(SPEC), jnp.asarray(tokens[:32]),
                  jnp.int32(0), jnp.int32(32))
    got, _ = fn(params, cache, jnp.asarray(tokens[32:33]), jnp.int32(32),
                jnp.int32(1))
    # a chunk's or a step's own positions are exact whatever phi is: what
    # it reads of the state is not
    assert np.abs(np.asarray(got)[0] - want[32]).max() > 20 * TOL


def test_a_stale_state_is_invisible_at_position_zero(step, tokens, want):
    """A sequence's first position finds the state empty whatever it holds:
    a reused engine gives a fresh engine's logits without a reset."""
    params, fn = step
    _, dirty, _ = fn(params, init_cache(SPEC), jnp.asarray(tokens[8:24]),
                     jnp.int32(0), jnp.int32(16))
    got, cache, _ = fn(params, dirty, jnp.asarray(tokens[:16]), jnp.int32(0),
                       jnp.int32(16))
    assert np.abs(np.asarray(got) - want[:16]).max() < TOL
    got, _, _ = fn(params, dirty, jnp.asarray(tokens[:1]), jnp.int32(0),
                   jnp.int32(1))
    assert np.abs(np.asarray(got)[0] - want[0]).max() < TOL


# -- the kernels, gates handed in: a long memory -------------------------------

def _long_case(t_len=1024, d=16, m=2, n_kv=1, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((t_len, n_kv, m, d)).astype(np.float32)
    k = rng.standard_normal((t_len, n_kv, d)).astype(np.float32)
    v = rng.standard_normal((t_len, n_kv, d)).astype(np.float32)
    g = rng.uniform(0.95, 0.9995, (t_len, n_kv)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        y = np.asarray(ref.retention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), jnp.log(jnp.asarray(g)),
                                     d))
    return q, k, v, g, y


def _empty(n_kv, d, layers=1):
    s, z = retention.state_shapes(n_kv, d)
    return (jnp.zeros((layers * n_kv,) + s[1:], jnp.float32),
            jnp.zeros((layers * n_kv,) + z[1:], jnp.float32))


def test_phi_is_the_squared_dot_product():
    rng = np.random.default_rng(1)
    u, w = (jnp.asarray(rng.standard_normal((7, 16)), jnp.float32)
            for _ in range(2))
    got = jnp.sum(retention.phi(u) * retention.phi(w), axis=(-1, -2))
    assert np.allclose(got, np.sum(np.asarray(u) * np.asarray(w), -1) ** 2,
                       rtol=1e-5, atol=1e-5)
    assert retention.phi(u).shape == (7, 9, 16)
    assert retention.state_bytes(8, 128) == 4 * 8 * 65 * 128 * 129


def test_decode_kernel_over_1024_positions():
    """The recurrent form, one position at a time, against the attention
    form: at g in 0.95..0.9995 position 1023 still reads position 0."""
    d, m, n_kv = 16, 2, 1
    q, k, v, g, want_y = _long_case(d=d, m=m, n_kv=n_kv)

    def body(carry, x):
        s, z, t = carry
        qt, kt, vt, gt = x
        y, s, z, low = retention.decode_attention(
            d, m, qt.reshape(1, -1), kt.reshape(1, -1), vt.reshape(1, -1),
            jnp.log(gt)[None], s, z, jnp.int32(0), (t == 0)[None])
        return (s, z, t + 1), (y.reshape(n_kv, m, d), low)

    run = jax.jit(lambda *xs: jax.lax.scan(
        body, (*_empty(n_kv, d), jnp.int32(0)), xs)[1])
    got, low = run(*(jnp.asarray(a) for a in (q, k, v, g)))
    err = np.abs(np.asarray(got) - want_y).max(axis=(1, 2, 3))
    assert err.max() < 2e-4 and err[-64:].max() < 5e-5
    assert np.asarray(low).min() > 0
    # the memory is long: what is over 128 positions back still counts
    # (at a seeded model's g near 0.5 it is gone after 20)
    with jax.default_matmul_precision("highest"):
        cut = np.asarray(ref.retention(*(jnp.asarray(a[-128:]) for a in
                                         (q, k, v)), jnp.log(jnp.asarray(
                                             g[-128:])), d))
    assert np.abs(cut[-1] - want_y[-1]).max() > 1e-3


@pytest.mark.parametrize("chunk", [128, 96])     # 96 does not divide 1024
def test_chunk_kernel_over_1024_positions(chunk):
    d, m, n_kv = 16, 2, 1
    q, k, v, g, want_y = _long_case(d=d, m=m, n_kv=n_kv)
    s, z = _empty(n_kv, d, layers=2)      # layer 1 of 2: layer 0 untouched
    t_len, worst = q.shape[0], 0.0
    run = jax.jit(lambda *a: retention.chunk_attention(d, m, *a))
    for lo in range(0, t_len, chunk):
        n = min(chunk, t_len - lo)

        def part(a, fill=0.0):
            pad = np.full((chunk - n,) + a.shape[1:], fill, np.float32)
            return jnp.asarray(np.concatenate([a[lo:lo + n], pad]).reshape(
                chunk, -1))

        y, s, z = run(part(q), part(k), part(v), jnp.log(part(g, 0.5)), s, z,
                      jnp.int32(1), jnp.asarray(lo == 0), jnp.int32(n))
        got = np.asarray(y).reshape(chunk, n_kv, m, d)[:n]
        worst = max(worst, float(np.abs(got - want_y[lo:lo + n]).max()))
    assert worst < 5e-5
    assert float(jnp.abs(s[:n_kv]).max()) == 0.0
    # ... and the state the chunks leave is the one a step continues from
    y, _, _, _ = retention.decode_attention(
        d, m, jnp.ones((1, n_kv * m * d)), jnp.zeros((1, n_kv * d)),
        jnp.zeros((1, n_kv * d)), jnp.zeros((1, n_kv)), s, z, jnp.int32(1),
        jnp.asarray([False]))
    assert np.isfinite(np.asarray(y)).all()


def test_an_inactive_row_leaves_its_state():
    d, m, n_kv, B = 16, 2, 2, 3
    rng = np.random.default_rng(3)
    s = jnp.asarray(rng.standard_normal((B * n_kv, 9, d, d)), jnp.float32)
    z = jnp.asarray(rng.random((B * n_kv, 9, d)), jnp.float32)
    q, k, v = (jnp.asarray(rng.standard_normal((B, n)), jnp.float32)
               for n in (n_kv * m * d, n_kv * d, n_kv * d))
    _, s2, z2, low = retention.decode_attention(
        d, m, q, k, v, jnp.full((B, n_kv), -0.1), s, z, jnp.int32(0),
        jnp.asarray([False, False, True]), jnp.asarray([True, False, True]))
    s, s2 = np.asarray(s).reshape(B, -1), np.asarray(s2).reshape(B, -1)
    assert (s2[1] == s[1]).all() and (s2[0] != s[0]).any()
    assert np.isfinite(float(low))


# -- Engine and serve ----------------------------------------------------------

def test_engine_prefill_then_infer(tree, tokens, want):
    from distributed_llama_tpu.runtime.generate import Engine

    eng = Engine(SPEC, tree)
    for round_ in range(2):      # the second on the first's stale state
        eng.prefill(tokens[:30], chunk=8)      # 30 = 3 x 8 + 6
        worst = 0.0
        for pos in range(30, SEQ):
            worst = max(worst, float(
                np.abs(eng.infer(tokens[pos], pos) - want[pos]).max()))
        assert worst < TOL, round_
    assert 0 < eng.min_normaliser < float("inf")
    with pytest.raises(ValueError, match="cannot be rewound"):
        eng.infer(tokens[5], 5)


def _greedy(tree, prompt, steps):
    """What single-sequence ``inference`` gives at temperature 0."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng, out, tok = Engine(SPEC, tree), [], prompt[0]
    for pos in range(steps):
        forced = pos + 1 < len(prompt)
        nxt = eng.infer(tok, pos, pick=not forced, last=True)
        tok = prompt[pos + 1] if forced else nxt
        out.append(tok)
    return out


def test_serve_three_staggered_requests(tree, tokens):
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    prompts = [tokens[:9], tokens[5:30], tokens[20:23]]
    budgets = [24, 40, 20]
    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, prefill_chunk=8)
    assert eng._insert.__name__ == "serve_admit_state_insert"
    assert eng.stats.state_bytes == 2 * SPEC.n_layers * retention.state_bytes(
        SPEC.n_kv_heads, SPEC.head_size)
    reqs, n = [eng.submit(Request(tokens=list(prompts[0]),
                                  steps=budgets[0]))], 0
    while True:
        live = eng.step_once()
        n += 1
        if n in (3, 7):    # the third waits for a slot: a stale row, reused
            i = len(reqs)
            reqs.append(eng.submit(Request(tokens=list(prompts[i]),
                                           steps=budgets[i])))
        if not live and n > 8:
            break
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.error is None and r.out == _greedy(tree, p, b)
    st = eng.stats
    assert st.steps_ahead > 0
    assert 0 < st.min_normaliser < float("inf")


def test_a_stale_row_decodes_as_an_empty_one(tree):
    """Nothing resets a retired row: the step program, run from position 0
    on rows that hold other sequences' states, gives bit for bit what it
    gives on an engine that has served nothing."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    def from_zero(dirty):
        eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0,
                               topp=0.9, seed=3, prefill_chunk=8)

        def decode(first, steps):
            out, tok = [], np.asarray(first, np.int32)
            picked = jnp.zeros((2,), jnp.int32)
            for pos in range(steps):
                blk = np.stack([tok, np.full(2, pos, np.int32),
                                np.ones(2, np.int32)], axis=1)
                lg, picked, eng.cache, _ = eng._decode(
                    eng.params, eng.cache, picked, jnp.asarray(blk))
                out.append(np.asarray(lg))
                tok = np.asarray(picked)
            return np.stack(out, 1)

        if dirty:
            decode([5, 9], 12)
        return decode([1, 1], 6)

    assert np.array_equal(from_zero(False), from_zero(True))


def test_an_admission_is_never_parked(tree, tokens):
    """A state cannot be resumed part-way without a snapshot, so a hold
    (which only a refused ``--disagg-role`` installs) parks nothing: the
    prompt is prefilled whole in the iteration that admits it."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, prefill_chunk=8)
    eng.prefill_hold = lambda s: True
    first = eng.submit(Request(tokens=list(tokens[:4]), steps=12))
    long_ = eng.submit(Request(tokens=list(tokens[:33]), steps=40))
    eng.step_once()
    assert not any(s.prefill_pending for s in eng._pool)
    assert eng.stats.prefill_chunks == 5       # 32 = 4 x 8, and 3 padded
    while eng.step_once():
        pass
    assert long_.error is None and long_.out == _greedy(tree, tokens[:33], 40)
    assert first.out == _greedy(tree, tokens[:4], 12)


def test_metrics_expose_the_state(tree, tokens):
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    reg = Registry()
    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, prefill_chunk=8, metrics=reg)
    eng.submit(Request(tokens=list(tokens[:12]), steps=20))
    while eng.step_once():
        pass
    text = reg.expose()
    for name in ("dllama_state_bytes", "dllama_retention_min_normaliser"):
        assert name in text
    assert f"dllama_state_bytes {eng.stats.state_bytes}" in text.replace(
        ".0\n", "\n")


# -- what is refused -----------------------------------------------------------

REFUSED = {
    "tp": (dict(tp=2), "--tp 2"),
    "pages": (dict(page_size=16), "--kv-page-size"),
    "kv_pages": (dict(kv_pages=64), "--kv-pages"),
    "prefix_share": (dict(prefix_share=True), "prefix_share"),
    "spec_k": (dict(spec_k=4), "--spec-k 4"),
    "dispatch_tokens": (dict(dispatch_tokens=64), "--dispatch-tokens 64"),
    "kv_quant": (dict(kv_quant="q8"), "--kv-quant q8"),
    "host_tier": (dict(kv_host_pages=8), "--kv-host-pages"),
    "disk_tier": (dict(kv_disk_dir="/tmp/x"), "--kv-disk-dir"),
    "journal": (dict(journal=True), "--journal"),
    "disagg": (dict(disagg=True), "--disagg-role"),
    "block_steps": (dict(block_steps=4), "--block-steps 4"),
    "kv_cache_dtype": (dict(kv_cache_dtype="bf16"), "--kv-cache-dtype bf16"),
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_each_refused_flag_refuses(flag):
    from distributed_llama_tpu.runtime.continuous import retention_refusals

    kw, names = REFUSED[flag]
    lines = retention_refusals(**kw)
    assert len(lines) == 1 and names in lines[0].split(":")[0]
    assert "state" in lines[0]          # ... and says why
    assert retention_refusals() == []


@pytest.mark.parametrize("kw,match", [
    (dict(page_size=16), "--kv-page-size"),
    (dict(page_size=16, spec_k=4), "--spec-k 4"),
    (dict(page_size=16, dispatch_tokens=32), "--dispatch-tokens"),
    (dict(page_size=16, kv_quant="q8"), "--kv-quant q8"),
    (dict(page_size=16, kv_host_pages=4), "--kv-host-pages"),
    (dict(page_size=16, remote_pages=True), "--disagg-role"),
    (dict(block_steps=4), "--block-steps 4"),
    (dict(cache_dtype=jnp.bfloat16), "--kv-cache-dtype"),
])
def test_the_engine_refuses(tree, kw, match):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    with pytest.raises(ValueError, match=match):
        ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                         seed=3, prefill_chunk=8, **kw)


def test_tp_refuses_a_retention_spec(tree):
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.tp import (param_specs,
                                                   validate_sharding)
    from distributed_llama_tpu.runtime.generate import Engine

    mesh = make_mesh(tp=2)
    for raises in (lambda: validate_sharding(SPEC, mesh),
                   lambda: param_specs(tree),
                   lambda: Engine(SPEC, tree, mesh=mesh)):
        with pytest.raises(ValueError, match="one chip only"):
            raises()


@pytest.mark.parametrize("mode,flags,match", [
    ("inference", ["--tp", "2"], "--tp 2"),
    ("serve", ["--kv-page-size", "16"], "--kv-page-size"),
    ("serve", ["--journal", "J"], "--journal"),
])
def test_the_cli_refuses(tmp_path, capsys, mode, flags, match):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, SPEC, seed=1)
    write_synth_tokenizer(tok, SPEC.vocab_size)
    flags = [f.replace("J", str(tmp_path / "j.wal")) for f in flags]
    rc = cli.main([mode, "--model", model, "--tokenizer", tok,
                   "--weights-float-type", "q40", *flags,
                   *(["--prompt", "hi", "--steps", "4"]
                     if mode == "inference" else ["--port", "0"])])
    err = capsys.readouterr().err
    assert rc == 2 and f"refused: {match}" in err


def test_synth_model_file_runs_through_the_cli(tmp_path, capsys):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    assert write_synth_q40_model(model, SPEC, seed=1) == SPEC.file_size()
    write_synth_tokenizer(tok, SPEC.vocab_size)
    rc = cli.main(["inference", "--model", model, "--tokenizer", tok,
                   "--prompt", "hello there", "--steps", "16", "--tp", "1",
                   "--temperature", "0", "--weights-float-type", "q40",
                   "--prefill-chunk", "4"])
    out = capsys.readouterr().out
    assert not rc and "attnKind: retention" in out
    assert "power retention (degree 2), no KV cache; state: 1 slot x" in out


def test_fused_forward_switch_refuses_a_retention_spec(tree, monkeypatch):
    from distributed_llama_tpu.runtime.generate import Engine

    monkeypatch.setenv("DLLAMA_LAYER_FUSION", "on")
    with pytest.raises(ValueError, match="DLLAMA_LAYER_FUSION"):
        Engine(SPEC, tree)


# -- the header and the file ---------------------------------------------------

V0 = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
          vocab_size=96, seq_len=32)


@pytest.mark.parametrize("version,extra,size", [
    (0, {}, 28),
    (2, dict(qk_norm=True), 52),
    (2, dict(n_experts=4, n_active_experts=2, qk_norm=True), 52),
    (3, dict(qk_norm=True, qk_norm_per_head=True, attn_kind="retention",
             rope_theta=1e6, norm_eps=1e-6), 72),
    (3, dict(rope_theta=5e6), 72),
    (3, dict(norm_eps=1e-6), 72),
    (3, dict(qk_norm=True, qk_norm_per_head=True), 72),
])
def test_header_round_trip(version, extra, size):
    spec = TransformerSpec(**V0, **extra)
    raw = spec.header()
    assert spec.header_version == version and len(raw) == size
    assert spec.header_bytes == size
    assert TransformerSpec.from_header(raw + b"\0" * 80) == spec


def test_old_headers_are_byte_for_byte():
    import struct

    base = tuple(V0.values())
    assert TransformerSpec(**V0).header() == struct.pack("<7i", *base)
    moe = TransformerSpec(**V0, n_experts=4, n_active_experts=2, qk_norm=True)
    assert moe.header() == struct.pack("<13i", -0x444C4D58, 2, 10, *base,
                                       4, 2, 1)
    with pytest.raises(ValueError, match="unknown header extension"):
        TransformerSpec.from_header(struct.pack("<13i", -0x444C4D58, 9, 10,
                                                *base, 0, 0, 0) + b"\0" * 40)


def test_spec_rejects_a_half_described_retention():
    with pytest.raises(ValueError, match="attn_kind"):
        TransformerSpec(**V0, attn_kind="linear")
    with pytest.raises(ValueError, match="qk_norm_per_head"):
        TransformerSpec(**V0, qk_norm_per_head=True)
    with pytest.raises(ValueError, match="dense FFN"):
        TransformerSpec(**V0, attn_kind="retention", n_experts=4,
                        n_active_experts=2)


@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_write_load_round_trip(tmp_path, ftype):
    spec = toy_spec(weights_float_type=ftype)
    dense = synth_params(spec, q40=False, seed=4)
    path = str(tmp_path / "m.bin")
    write_model(path, spec, dense)
    got_spec, got = load_model(path, weights_float_type=ftype)
    assert got_spec == spec
    assert np.array_equal(got["w_gate"], dense["w_gate"])
    assert got["rms_q"].shape == (spec.n_layers, spec.head_size)
    names = [(r.name, r.layer) for r in tensor_byte_ranges(spec)]
    assert names.index(("w_gate", 0)) == names.index(("wo", 0)) + 1
    assert names.index(("w1", 0)) == names.index(("w_gate", 0)) + 1
    last = tensor_byte_ranges(spec)[-1]
    assert last.offset + last.nbytes == spec.file_size()


def test_converter_refuses_another_gate_shape():
    from distributed_llama_tpu.convert import HFCheckpoint

    class Stub(HFCheckpoint):
        def __init__(self, gate):
            import types

            self.torch = types.SimpleNamespace(float32=None)
            self._state = {"model.layers.0.self_attn.gate_proj.weight": gate,
                           "model.layers.0.self_attn.q_norm.weight":
                               _Tensor(np.arange(16.0))}

    class _Tensor:
        def __init__(self, a):
            self.a = np.asarray(a, np.float32)

        def to(self, _):
            return self

        def numpy(self):
            return self.a

    good = Stub(_Tensor(np.ones(SPEC.gate_shape)))
    assert good.tensor_by_name("w_gate", 0, SPEC).shape == SPEC.gate_shape
    with pytest.raises(ValueError, match="n_kv_heads, dim"):
        Stub(_Tensor(np.ones((SPEC.n_heads, SPEC.dim)))).tensor_by_name(
            "w_gate", 0, SPEC)
    # one head's gains, rotate-half order -> interleaved pairs
    gains = good.tensor_by_name("rms_q", 0, SPEC)
    assert list(gains[:4]) == [0.0, 8.0, 1.0, 9.0]


# -- the analysis tools count a retention spec or refuse it --------------------

BRUMBY = dict(dim=5120, hidden_dim=17408, n_layers=10, n_heads=40,
              n_kv_heads=8, vocab_size=151936, seq_len=32768,
              weights_float_type=FloatType.Q40, qk_norm=True,
              qk_norm_per_head=True, attn_kind="retention",
              rope_theta=1e6, norm_eps=1e-6)


def test_memory_model_counts_the_state():
    from distributed_llama_tpu.analysis import memory_model

    spec = TransformerSpec(**BRUMBY)
    slot = memory_model.state_slot_bytes(spec)
    assert slot == 10 * 4 * 8 * 65 * 128 * 129 and 327 < slot / 2**20 < 328
    report = memory_model.device_footprint(spec, 1, "fused", batch=16)
    assert report.kv_cache_bytes == 16 * slot and report.fits
    assert 10.0 < report.total_bytes / 2**30 < 11.0
    for call in (lambda: memory_model.weights_device_bytes(spec, 4),
                 lambda: memory_model.kv_cache_device_bytes(spec, 4),
                 lambda: memory_model.device_footprint(spec, 1, "fused",
                                                       kv_page_size=16)):
        with pytest.raises(ValueError):
            call()
    with pytest.raises(ValueError, match="retention"):
        memory_model.state_slot_bytes(TransformerSpec(**V0))
