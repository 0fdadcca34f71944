"""A hybrid spec (SambaY: Mamba, window and full differential attention, then a
cross-decoder of GMUs and cross-attention over ONE shared KV cache) against
``models/reference_sambay.py`` on LOGITS, at a toy size with the published
pattern (L = 8: Mamba at 0, 2, 4, window layers at 1, 3, the full layer at 5,
a GMU at 6, a cross layer at 7; window 8, so the ring wraps; L = 12 so that
two GMUs and two cross layers read the same memory and K / V).

TOL: float32 against float32 at highest precision differs by op order alone
(the largest reading here is 6e-4 on logits of std 1.6: the sub-norm divides
by the RMS of a difference of two maps); the same forward with bfloat16
products reads 2e-2 and more, which ``test_bfloat16_fails_the_tolerance``
holds.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, tensor_byte_ranges,
                                             write_model)
from distributed_llama_tpu.models import reference_sambay as ref
from distributed_llama_tpu.models import kindscan, sambay
from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                params_to_device)
from distributed_llama_tpu.models.spec import (ExpertLayout, HybridLayers,
                                               LatentAttn, TransformerSpec,
                                               sambay_kinds)
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import mamba
from distributed_llama_tpu.ops.quants import FloatType

TOL = 2e-3
SEQ = 64


def tiny(n_layers=8, wft=FloatType.F32, **kw):
    return TransformerSpec(
        dim=64, hidden_dim=128, n_layers=n_layers, n_heads=4, n_kv_heads=2,
        vocab_size=128, seq_len=SEQ, weights_float_type=wft,
        hybrid=HybridLayers(sambay_kinds(n_layers), window=8, d_inner=128,
                            d_state=16, d_conv=4, dt_rank=4), **kw)


SPEC = tiny()


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in np.random.default_rng(0).integers(3, 128, SEQ)]


@pytest.fixture(scope="module", params=[8, 12])
def model(request, tokens):
    spec = tiny(request.param)
    tree = synth_params(spec, q40=False, seed=3, scale=0.2)
    return spec, tree, ref.forward(tree, spec, tokens)


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=False, seed=3, scale=0.2)


@pytest.fixture(scope="module")
def want(tree, tokens):
    return ref.forward(tree, SPEC, tokens)


# -- the forward against the reference ------------------------------------------

def test_the_pattern_and_its_scans():
    kinds = sambay_kinds(32)
    assert [kinds.count(k) for k in ("mamba", "swa", "full", "gmu",
                                     "xattn")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    hy = HybridLayers(kinds, 512, 5120, 16, 4, 160)
    assert hy.memory_layer == 16 and hy.full_layer == 17
    segs = kindscan.segments([(k,) for k in kinds])
    assert [(f, tuple(s for s, in u), r) for f, u, r in segs] == [
        (0, ("mamba", "swa"), 8), (16, ("mamba",), 1), (17, ("full",), 1),
        (18, ("gmu", "xattn"), 7)]
    # a prefill stops after the full layer: the same scans, cut there
    assert kindscan.segments([(k,) for k in kinds[:18]]) == segs[:3]
    assert sambay_kinds(8) == ("mamba", "swa", "mamba", "swa", "mamba",
                               "full", "gmu", "xattn")


def test_full_forward_matches_the_reference(model, tokens):
    spec, tree, want = model
    got, _ = forward(spec, params_to_device(tree), init_cache(spec),
                     jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[:45]).max() < TOL


def test_chunked_prefill_then_decode(model, tokens):
    """Chunks of 8 with a ragged last one (21 = 2 x 8 + 5) through the
    caches, then decode: the ring has wrapped twice by then."""
    spec, tree, want = model
    params = params_to_device(tree)
    pre = jax.jit(lambda p, c, t, pos, n: sambay.forward_sambay(
        spec, p, c, t, pos, n, xdec=False))
    step = jax.jit(lambda p, c, t, pos: forward(spec, p, c, t, pos))
    cache = init_cache(spec)
    for lo in range(0, 21, 8):
        part = tokens[lo:min(lo + 8, 21)]
        logits, cache = pre(params, cache,
                            jnp.asarray(part + [0] * (8 - len(part))),
                            jnp.int32(lo), jnp.int32(len(part)))
        assert logits.shape == (0, spec.vocab_size)   # no cross-decoder
    worst = 0.0
    for pos in range(21, 40):
        logits, cache = step(params, cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos))
        worst = max(worst, float(np.abs(np.asarray(logits)[0]
                                        - want[pos]).max()))
    assert worst < TOL


def test_a_padded_position_reaches_nothing(tree, tokens):
    """A chunk of 8 of which 5 count leaves state, rings and K / V as the
    5 alone do (another pad token, the same cache)."""
    params = params_to_device(tree)
    pre = jax.jit(lambda t, n: sambay.forward_sambay(
        SPEC, params, init_cache(SPEC), t, jnp.int32(0), n, xdec=False)[1])
    a = pre(jnp.asarray(tokens[:5] + [7, 8, 9]), jnp.int32(5))
    b = pre(jnp.asarray(tokens[:5] + [0, 0, 0]), jnp.int32(5))
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_last_position_only_prompt(tree, tokens, want):
    """``Engine.prefill`` runs no cross-decoder; the prompt's last token
    takes the decode step: the reference's last row."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng = Engine(SPEC, tree)
    for round_ in range(2):      # the second on the first's stale caches
        eng.prefill(tokens[:30], chunk=8)      # 30 = 3 x 8 + 6
        got = eng.infer(tokens[30], 30)
        assert np.abs(got - want[30]).max() < TOL, round_
    assert 0 < eng.ssm_min_decay < 1
    with pytest.raises(ValueError, match="cannot be rewound"):
        eng.infer(tokens[5], 5)


def test_bfloat16_fails_the_tolerance(tree, tokens, want):
    from distributed_llama_tpu.ops.linear import matmul_precision

    with matmul_precision("bf16"):
        got, _ = forward(SPEC, params_to_device(tree), init_cache(SPEC),
                         jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[:45]).max() > 5 * TOL


def test_q40_tree_matches_the_reference(tokens):
    spec = tiny(8, FloatType.Q40)
    tree = synth_params(spec, q40=True, seed=5, scale=0.2)
    want = ref.forward(tree, spec, tokens[:24])
    got, _ = forward(spec, params_to_device(tree), init_cache(spec),
                     jnp.asarray(tokens[:24]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want).max() < TOL
    # tied: the classifier is the embedding in the weights' type
    from distributed_llama_tpu.ops.quants import dequantize_q40

    w = dequantize_q40(tree["wcls"].qs, tree["wcls"].d16)
    assert np.abs(w - tree["tok_embedding"]).max() < 0.1


# -- the kernels against their formulas -------------------------------------------

def _plain_scan(a_log, x, delta, b, c, s0):
    a = -np.exp(a_log)
    s, ys = s0.copy(), []
    for t in range(x.shape[0]):
        s = np.exp(delta[t][None] * a) * s + b[t][:, None] * (
            delta[t] * x[t])[None]
        ys.append((s * c[t][:, None]).sum(0))
    return np.stack(ys), s


@pytest.fixture(scope="module")
def scan_inputs():
    rng = np.random.default_rng(1)
    t_len, di, ds = 24, 256, 16
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return dict(a_log=np.log(np.arange(1, ds + 1, dtype=np.float32))[
        :, None] * np.ones((1, di), np.float32), x=f(t_len, di),
        delta=np.abs(f(t_len, di)) * 0.1, b=f(t_len, ds), c=f(t_len, ds),
        s0=f(ds, di))


def test_mamba_chunk_against_the_scan(scan_inputs):
    i = scan_inputs
    want_y, want_s = _plain_scan(**i)
    ssm = jnp.stack([jnp.zeros_like(i["s0"]), i["s0"]])    # plane 1 of 2
    y, out = mamba.scan_chunk(1, ssm, i["a_log"], i["x"], i["delta"], i["b"],
                              i["c"], jnp.bool_(False), jnp.int32(24))
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4
    assert np.abs(np.asarray(out[1]) - want_s).max() < 1e-4
    assert not np.asarray(out[0]).any()
    # 13 of 24 count: the state is the 13's, and a fresh one starts empty
    y, out = mamba.scan_chunk(1, ssm, i["a_log"], i["x"], i["delta"], i["b"],
                              i["c"], jnp.bool_(True), jnp.int32(13))
    _, s13 = _plain_scan(i["a_log"], i["x"][:13], i["delta"][:13],
                         i["b"][:13], i["c"][:13], np.zeros_like(i["s0"]))
    assert np.abs(np.asarray(out[1]) - s13).max() < 1e-4


def test_mamba_step_against_the_scan(scan_inputs):
    """Three rows step through the same inputs: row 0 from the given state,
    row 1 fresh (its stale state must read as empty), row 2 takes no part."""
    i = scan_inputs
    want_y, want_s = _plain_scan(**i)
    fresh_y, _ = _plain_scan(i["a_log"], i["x"], i["delta"], i["b"], i["c"],
                             np.zeros_like(i["s0"]))
    ssm = jnp.stack([i["s0"]] * 3 * 2)          # layer 1 of 2, three rows
    live = jnp.asarray([True, True, False])
    for t in range(24):
        rows = lambda a: jnp.stack([a[t]] * 3)   # noqa: E731
        y, ssm = mamba.scan_decode(
            1, ssm, i["a_log"], rows(i["x"]), rows(i["delta"]), rows(i["b"]),
            rows(i["c"]), jnp.asarray([False, t == 0, False]), live)
        assert np.abs(np.asarray(y[0]) - want_y[t]).max() < 1e-4
        assert np.abs(np.asarray(y[1]) - fresh_y[t]).max() < 1e-4
    assert np.abs(np.asarray(ssm[3]) - want_s).max() < 1e-4
    assert np.array_equal(np.asarray(ssm[5]), i["s0"])      # masked row
    assert np.array_equal(np.asarray(ssm[:3]), np.stack([i["s0"]] * 3))


@pytest.mark.parametrize("window", [None, 8])
def test_differential_attention_against_two_softmaxes(tree, window):
    """The padded-query grouped attention and the combine, against the
    reference's two softmaxes, lambda and sub-norm."""
    from distributed_llama_tpu.models.llama import attention_core

    rng = np.random.default_rng(2)
    t_len = 20
    q = rng.standard_normal((t_len, SPEC.dim)).astype(np.float32)
    k = rng.standard_normal((t_len, SPEC.kv_dim)).astype(np.float32)
    v = rng.standard_normal((t_len, SPEC.kv_dim)).astype(np.float32)
    lw = ref._layer_of(tree["swa"], 1)
    want = ref.diff_attention(SPEC, lw, 3, jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), window)
    n_q, n_kv, hs = sambay.pair_shape(SPEC)
    pos = np.arange(t_len)
    mask = pos[None] <= pos[:, None]
    if window:
        mask &= pos[:, None] - pos[None] < window
    ao = attention_core(hs, n_q // n_kv,
                        sambay.padded_queries(SPEC, jnp.asarray(q)).reshape(
                            t_len, n_q, hs),
                        jnp.asarray(k).reshape(t_len, n_kv, hs),
                        jnp.asarray(v).reshape(t_len, n_kv, hs),
                        jnp.asarray(mask))
    got = sambay.diff_combine(SPEC, lw, 3, ao)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4


def _hm_inputs(rng, kind, B, S, ps, scale_k=1.0, n_kv=10, kv_mul=4, hs=128,
               nan_past=None):
    """Queries, what the kernel is handed (``rows``: a second layer's planes
    and its index; ``paged``: a pool and a table of ``S // ps`` pages a row)
    and each row's (n_kv, S, hs) K / V as it should see them. ``nan_past``
    (each row's last position): every page of the pool but a row's live
    ones, the rest of its table included, is NaN."""
    f = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    q = f(B, n_kv * kv_mul, hs)
    if kind == "rows":
        k, v = f(2 * B, n_kv, S, hs) * scale_k, f(2 * B, n_kv, S, hs)
        return q, (jnp.asarray(k), jnp.asarray(v), 1), k[B:], v[B:]
    per_row = S // ps
    n_pool = 2 * B * per_row + 1
    pool_k, pool_v = f(n_pool, n_kv, ps, hs) * scale_k, f(n_pool, n_kv, ps, hs)
    table = (rng.permutation(n_pool - 1)[:B * per_row] + 1).reshape(
        B, per_row).astype(np.int32)
    gather = lambda pool: np.swapaxes(  # noqa: E731
        pool[table], 1, 2).reshape(B, n_kv, S, hs)
    k_c, v_c = gather(pool_k), gather(pool_v)
    if nan_past is not None:
        live = np.zeros(n_pool, bool)
        for b, last in enumerate(nan_past):
            live[table[b, :last // ps + 1]] = True
        pool_k[~live] = pool_v[~live] = np.nan
    return q, (jnp.asarray(pool_k), jnp.asarray(pool_v),
               jnp.asarray(table)), k_c, v_c


def _hm_run(kind, q, held, last, kv_mul):
    from distributed_llama_tpu.ops import pallas_head_major_attention as hm

    if kind == "rows":
        return hm.rows_decode_attention(jnp.asarray(q), *held, last,
                                        kv_mul=kv_mul)
    return hm.paged_decode_attention(jnp.asarray(q), held[0], held[1], last,
                                     held[2], kv_mul=kv_mul)


def _attention64(q, k, v, last, kv_mul):
    """float64 grouped attention: q (B, n_q, hs), k / v (B, n_kv, S, hs),
    positions 0 .. last[b]; ``q``, ``k``, ``v`` are taken as given (round
    them first for a low-precision control)."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros(q.shape)
    for b in range(q.shape[0]):
        n = int(last[b]) + 1
        for h in range(q.shape[1]):
            s = k[b, h // kv_mul, :n] @ q[b, h] / np.sqrt(q.shape[-1])
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ v[b, h // kv_mul, :n]
    return out.reshape(q.shape[0], -1)


# kind, S, page, each row's last position. Pages of 16 make a chunk of 128
# (eight a turn); a 512-slot ring walks four chunks of 128
_HM_CASES = {
    "rows": ("rows", 48, None, [0, 17, 47]),
    "paged": ("paged", 48, 8, [0, 17, 47]),
    "paged16-depth-0": ("paged", 320, 16, [0, 77]),
    "paged16-one-under-a-chunk": ("paged", 320, 16, [126, 254]),
    "paged16-exactly-a-chunk": ("paged", 320, 16, [127, 255]),
    "paged16-one-over-a-chunk": ("paged", 320, 16, [128, 256]),
    "paged16-partial-last-page": ("paged", 320, 16, [200, 137]),
    "paged16-full-table": ("paged", 320, 16, [319, 303]),
    "paged16-nan-in-unreferenced-pages": ("paged", 320, 16, [0, 130, 200]),
    "ring512-under-the-edge": ("rows", 512, None, [126, 382]),
    "ring512-at-the-edge": ("rows", 512, None, [127, 383]),
    "ring512-past-the-edge": ("rows", 512, None, [128, 384]),
    "ring512-full": ("rows", 512, None, [511, 510]),
}


@pytest.mark.parametrize("case", list(_HM_CASES))
def test_head_major_decode_kernels_against_the_einsum(case):
    """ops/pallas_head_major_attention (interpret mode) against
    ``attention_core``: rows at their own depths, ten KV heads of 128, four
    query heads a group; the second layer's planes of two. The depths sit
    on, one under and one over a chunk's edge, in a partial page and at the
    table's end; ``nan``: a row owns its live pages only and every other
    page of the pool, the rest of its table included, is NaN: a chunk's
    tail past the last live page must not reach the output."""
    from distributed_llama_tpu.models.llama import attention_core
    from distributed_llama_tpu.ops import pallas_head_major_attention as hm

    kind, S, ps, depths = _HM_CASES[case]
    rng = np.random.default_rng(4)
    B, kv_mul = len(depths), 4
    last = jnp.asarray(depths, jnp.int32)
    q, held, k_c, v_c = _hm_inputs(
        rng, kind, B, S, ps, nan_past=depths if "nan" in case else None)
    got = _hm_run(kind, q, held, last, kv_mul)
    mask = jnp.arange(S)[None, None, :] <= last[:, None, None]
    want = attention_core(128, kv_mul, jnp.asarray(q)[:, None],
                          jnp.swapaxes(jnp.asarray(k_c), 1, 2),
                          jnp.swapaxes(jnp.asarray(v_c), 1, 2),
                          mask).reshape(B, -1)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    assert hm.supports(512, 10, 128) and hm.supports_paged(16, 10, 128)
    assert not hm.supports(512, 10, 64)


def test_head_major_chunks_come_from_the_shapes():
    """A ring is four chunks or more (copies behind folds), a turn's pages
    make the fold's tile of 128 positions."""
    from distributed_llama_tpu.ops import pallas_head_major_attention as hm

    assert hm._chunk(512, 10, 128, 4) == 128
    assert hm._chunk(8704, 10, 128, 4) == 512
    assert hm._chunk(48, 10, 128, 4) == 8 and hm._chunk(8, 10, 128, 4) == 8
    assert [hm._pages_a_turn(ps) for ps in (8, 16, 32, 128, 256)] == [
        16, 8, 4, 1, 1]


# max |kernel - float64| of the PARENT's fold (PR 39's tree, the vector
# unit's multiply-and-reduce, interpret mode) on the same inputs, recorded
# 2026-09-29: the MXU form may be no further than twice that
_PARENT_D64 = {("rows", 1.0): 1.554e-7, ("rows", 30.0): 1.115e-5,
               ("paged", 1.0): 1.147e-7, ("paged", 30.0): 1.758e-5}


@pytest.mark.parametrize("kind,scale_k", list(_PARENT_D64))
def test_head_major_fold_keeps_float32(kind, scale_k):
    """Both kernels against a float64 attention, on standard-normal K and on
    K of thirty times the norm (scores to +-1,000: one winner a row, where a
    bf16 product moves the winner): no further from float64 than twice the
    parent's vector-unit fold, and the same attention with every product's
    operands rounded to bfloat16 is at least 100 times further: a fold that
    is quietly three passes, or one, fails here."""
    rng = np.random.default_rng(40)
    depths, kv_mul = [300, 511], 4
    S, ps = 512, 16
    q, held, k_c, v_c = _hm_inputs(rng, kind, len(depths), S, ps,
                                   scale_k=scale_k)
    got = _hm_run(kind, q, held, jnp.asarray(depths, jnp.int32), kv_mul)
    want = _attention64(q, k_c, v_c, depths, kv_mul)
    d = np.abs(np.asarray(got, np.float64) - want).max()
    bf = lambda a: np.asarray(jax.lax.reduce_precision(  # noqa: E731
        jnp.asarray(a), exponent_bits=8, mantissa_bits=7))
    control = np.abs(_attention64(bf(q), bf(k_c), bf(v_c), depths, kv_mul)
                     - want).max()
    assert d <= 2 * _PARENT_D64[kind, scale_k], (d, _PARENT_D64)
    assert control >= 100 * d, (control, d)


def test_decode_through_the_kernels_matches_the_einsum_route(tree, tokens,
                                                             want,
                                                             monkeypatch):
    """The decode step with the attention kernels on (interpret mode; head
    size 2 x 64 = 128 at this toy width too) against the reference."""
    spec = TransformerSpec(**{**SPEC.__dict__, "dim": 256, "n_heads": 4,
                              "n_kv_heads": 2})
    tree = synth_params(spec, q40=False, seed=3, scale=0.1)
    want = ref.forward(tree, spec, tokens[:20])
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "pallas")
    params = params_to_device(tree)
    step = jax.jit(lambda c, t, pos: forward(spec, params, c, t, pos))
    cache, worst = init_cache(spec), 0.0
    for pos in range(20):
        logits, cache = step(cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos))
        worst = max(worst, float(np.abs(np.asarray(logits)[0]
                                        - want[pos]).max()))
    assert worst < TOL


# -- serve -------------------------------------------------------------------------

def _greedy(spec, tree, prompt, steps):
    """What single-sequence ``inference`` gives at temperature 0."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng, out, tok = Engine(spec, tree), [], prompt[0]
    for pos in range(steps):
        forced = pos + 1 < len(prompt)
        nxt = eng.infer(tok, pos, pick=not forced, last=True)
        tok = prompt[pos + 1] if forced else nxt
        out.append(tok)
    return out


def _engine(tree, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    kw = dict(dict(slots=2, temperature=0.0, topp=0.9, seed=3,
                   prefill_chunk=8, page_size=4, kv_pages=40), **kw)
    return ContinuousEngine(SPEC, tree, **kw)


def test_serve_more_requests_than_slots(tree, tokens, want):
    """Five requests on two slots: a slot is reused over another sequence's
    state, ring and pages. Every stream is ``inference``'s, and every
    served position's logit lies at the reference's maximum."""
    from distributed_llama_tpu.runtime.continuous import Request

    prompts = [tokens[:9], tokens[5:30], tokens[20:22], tokens[10:37],
               tokens[40:52]]
    budgets = [24, 40, 20, 44, 30]
    eng = _engine(tree)
    assert eng._insert.__name__ == "serve_admit_state_insert"
    assert eng._decode.__name__ == "serve_decode_step"
    hy = SPEC.hybrid
    assert eng.stats.state_bytes == 2 * 3 * hy.d_inner * 19 * 4
    assert eng.stats.window_bytes == 2 * 2 * hy.window * 2 * SPEC.kv_dim * 4
    reqs = [eng.submit(Request(tokens=list(p), steps=b))
            for p, b in zip(prompts, budgets)]
    while eng.step_once():
        pass
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.error is None and r.out == _greedy(SPEC, tree, p, b)
        served = r.out[len(p) - 1:]
        logits = ref.forward(tree, SPEC, list(p) + served[:-1])[len(p) - 1:]
        short = logits.max(-1) - logits[np.arange(len(served)), served]
        assert short.max() < TOL
    st = eng.stats
    assert st.steps_ahead > 0 and 0 < st.ssm_min_decay < 1
    # the cross-decoder ran at ONE position a prompt that took chunks (the
    # 2-token prompt crawls: both its tokens are decode steps)
    assert st.prompt_positions == sum(len(p) for p in prompts)
    assert st.xdec_positions == 4 + 2 and st.admit_prefills == 4
    assert st.shared_kv_positions > 0 and st.shared_kv_pages >= 0
    assert st.paged_kv_positions == 0     # a plain KV pool's counter


def test_a_stale_row_decodes_as_an_empty_one(tree):
    """Nothing resets a retired row: the step program, run from position 0
    on rows that hold other sequences' state, ring and pages, gives bit for
    bit what it gives on an engine that has served nothing."""
    def from_zero(dirty):
        eng = _engine(tree)
        table = np.arange(1, 1 + 2 * 16, dtype=np.int32).reshape(2, 16)

        def decode(first, steps):
            out, tok = [], np.asarray(first, np.int32)
            picked = jnp.zeros((2,), jnp.int32)
            for pos in range(steps):
                blk = np.concatenate(
                    [tok[:, None], np.full((2, 1), pos, np.int32), table,
                     np.ones((2, 1), np.int32)], axis=1)
                lg, picked, eng.cache, _ = eng._decode(
                    eng.params, eng.cache, picked, jnp.asarray(blk))
                out.append(np.asarray(lg))
                tok = np.asarray(picked)
            return np.stack(out, 1)

        if dirty:
            decode([5, 9], 12)
        return decode([1, 1], 6)

    assert np.array_equal(from_zero(False), from_zero(True))


def test_a_row_that_takes_no_part_keeps_its_state(tree):
    eng = _engine(tree)
    table = np.arange(1, 33, dtype=np.int32).reshape(2, 16)
    blk = np.concatenate([np.asarray([[5, 0], [9, 0]], np.int32), table,
                          np.asarray([[1], [0]], np.int32)], axis=1)
    before = [np.asarray(a) for a in eng.cache[:2]]
    _, _, cache, _ = eng._decode(eng.params, eng.cache,
                                 jnp.zeros((2,), jnp.int32), jnp.asarray(blk))
    for old, new in zip(before, cache[:2]):
        new = np.asarray(new)
        assert np.array_equal(old[:, 1], new[:, 1])
        assert not np.array_equal(old[:, 0], new[:, 0])


REFUSED = {
    "tp": (dict(tp=2, page_size=16), "--tp 2"),
    "no pages": (dict(), "serve without --kv-page-size"),
    "prefix sharing": (dict(page_size=16, prefix_share=True),
                       "prefix sharing"),
    "spec_k": (dict(page_size=16, spec_k=4), "--spec-k 4"),
    "dispatch_tokens": (dict(page_size=16, dispatch_tokens=32),
                        "--dispatch-tokens 32"),
    "kv_quant": (dict(page_size=16, kv_quant="q8"), "--kv-quant q8"),
    "tiers": (dict(page_size=16, kv_host_pages=4), "--kv-host-pages"),
    "journal": (dict(page_size=16, journal=True), "--journal"),
    "disagg": (dict(page_size=16, disagg=True), "--disagg-role"),
    "block_steps": (dict(page_size=16, block_steps=4), "--block-steps 4"),
    "cache dtype": (dict(page_size=16, kv_cache_dtype="bf16"),
                    "--kv-cache-dtype bf16"),
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_each_refusal_by_name(flag):
    """One list (``cache_refusals``), read by what a sequence caches: a
    hybrid spec's lines name the flag and say why."""
    from distributed_llama_tpu.runtime.continuous import (cache_refusals,
                                                          sequence_caches)

    caches = sequence_caches(SPEC)
    assert caches == {"state", "pages"}
    kw, names = REFUSED[flag]
    lines = cache_refusals(caches, **kw)
    assert len(lines) == 1 and lines[0].startswith(names)
    assert "state" in lines[0] or "one chip only" in lines[0]
    assert cache_refusals(caches, page_size=16) == []
    assert cache_refusals(caches, serve=False) == []     # inference
    dense = TransformerSpec(64, 128, 2, 4, 2, 128, 64)
    assert cache_refusals(sequence_caches(dense), **kw) == []


@pytest.mark.parametrize("kw,match", [
    (dict(page_size=0, kv_pages=0), "serve without --kv-page-size"),
    (dict(prefix_share=True), "prefix sharing"),
    (dict(spec_k=4), "--spec-k 4"),
    (dict(dispatch_tokens=32), "--dispatch-tokens"),
    (dict(kv_quant="q8"), "--kv-quant q8"),
    (dict(kv_host_pages=4), "--kv-host-pages"),
    (dict(remote_pages=True), "--disagg-role"),
    (dict(block_steps=4), "--block-steps 4"),
    (dict(cache_dtype=jnp.bfloat16), "--kv-cache-dtype"),
])
def test_the_engine_refuses(tree, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(tree, **kw)


def test_tp_refuses_a_hybrid_spec(tree):
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
    ("serve", [], "serve without --kv-page-size"),
    ("serve", ["--kv-page-size", "4", "--journal", "J"], "--journal"),
    ("serve", ["--kv-page-size", "4", "--spec-k", "3"], "--spec-k 3"),
])
def test_the_cli_refuses(tmp_path, capsys, mode, flags, match):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    spec = tiny(8, FloatType.Q40, )
    spec = TransformerSpec(**{**spec.__dict__, "vocab_size": 512})
    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, spec, seed=1)
    write_synth_tokenizer(tok, spec.vocab_size)
    flags = [f.replace("J", str(tmp_path / "j.wal")) for f in flags]
    rc = cli.main([mode, "--model", model, "--tokenizer", tok,
                   "--weights-float-type", "q40", *flags,
                   *(["--prompt", "hi", "--steps", "4"]
                     if mode == "inference" else ["--port", "0"])])
    err = capsys.readouterr().err
    assert rc == 2 and f"refused: {match}" in err


def test_synth_model_file_runs_through_the_cli(tmp_path, capsys):
    """``inference`` from a file alone: the header says what the model is."""
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    spec = TransformerSpec(**{**tiny(8, FloatType.Q40).__dict__,
                              "vocab_size": 512})
    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    assert write_synth_q40_model(model, spec, seed=1) == spec.file_size()
    write_synth_tokenizer(tok, spec.vocab_size)
    rc = cli.main(["inference", "--model", model, "--tokenizer", tok,
                   "--prompt", "hello there", "--steps", "16", "--tp", "1",
                   "--temperature", "0", "--weights-float-type", "q40",
                   "--prefill-chunk", "4"])
    out = capsys.readouterr().out
    assert not rc and "3 mamba, 2 window (8), 1 full, 1 gmu, 1 cross" in out


# -- the header and the file ------------------------------------------------------

OLDER = {
    0: dict(),
    2: dict(n_experts=4, n_active_experts=2, qk_norm=True),
    3: dict(qk_norm=True, qk_norm_per_head=True, attn_kind="retention",
            rope_theta=1e6, norm_eps=1e-6),
    4: dict(n_experts=4, n_active_experts=2,
            latent=LatentAttn(16, 32, 8, 8, 8),
            layout=ExpertLayout(1, 96, 1, 2, 2)),
}


@pytest.mark.parametrize("version", sorted(OLDER))
def test_older_headers_read_and_write_byte_for_byte(version):
    """Extension 5 is written only by a spec that needs it: a spec of
    version 0, 2, 3 or 4 writes the bytes it always did (its length is the
    version's), and they read back to it."""
    spec = TransformerSpec(64, 128, 3, 4, 2, 128, 64, **OLDER[version])
    raw = spec.header()
    assert spec.header_version == version and spec.hybrid is None
    assert len(raw) == {0: 28, 2: 52, 3: 72, 4: 192}[version]
    assert TransformerSpec.from_header(raw) == spec
    # ... and read alike from a buffer as long as the longest header
    assert TransformerSpec.from_header(raw + b"\0" * 512) == spec
    assert not spec.planned or version == 4


def test_header_5_and_the_file_round_trip(tmp_path, tree):
    raw = SPEC.header()
    assert SPEC.header_version == 5 and len(raw) == SPEC.header_bytes == 352
    assert TransformerSpec.from_header(raw) == SPEC
    with pytest.raises(ValueError, match="unknown layer kind"):
        TransformerSpec.from_header(raw[:-128] + bytes([9]) * 128)
    path = str(tmp_path / "m.bin")
    write_model(path, SPEC, tree)
    spec2, tree2 = load_model(path)
    assert spec2 == SPEC
    jax.tree_util.tree_map(np.testing.assert_array_equal, tree, tree2)
    ranges = tensor_byte_ranges(SPEC)
    assert ranges[-1].offset + ranges[-1].nbytes == SPEC.file_size()
    assert {r.name for r in ranges} >= {"a_log", "lam", "rms_final_b"}


@pytest.mark.parametrize("bad,match", [
    (dict(kinds=("mamba", "swa", "full", "full", "gmu", "xattn", "gmu",
                 "xattn")), "ONE full-attention"),
    (dict(kinds=("mamba", "swa", "full", "mamba", "gmu", "xattn", "gmu",
                 "xattn")), "only gmu / xattn layers after"),
    (dict(kinds=("swa",) * 7 + ("bogus",)), "one of"),
    (dict(window=0), "positive state-space sizes"),
])
def test_a_bad_list_of_kinds_is_refused(bad, match):
    hy = HybridLayers(**{**SPEC.hybrid.__dict__, **bad})
    with pytest.raises(ValueError, match=match):
        TransformerSpec(**{**SPEC.__dict__, "hybrid": hy})


def test_converter_reads_the_config_and_refuses_the_tensors():
    import types

    from distributed_llama_tpu.convert import HFCheckpoint, hybrid_spec

    cfg = types.SimpleNamespace(
        model_type="phi4flash", hidden_size=2560, intermediate_size=10240,
        num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
        vocab_size=200064, sliding_window=512, mb_per_layer=2,
        layer_norm_eps=1e-5, tie_word_embeddings=True)
    spec = hybrid_spec(cfg, FloatType.Q40, 8704)
    hy = spec.hybrid
    assert (hy.window, hy.d_inner, hy.dt_rank) == (512, 5120, 160)
    assert hy.kinds == sambay_kinds(32) and spec.header_version == 5
    ckpt = HFCheckpoint.__new__(HFCheckpoint)
    ckpt.config = cfg
    assert ckpt.spec(FloatType.Q40, 8704) == spec
    with pytest.raises(ValueError, match="pairing of heads"):
        ckpt.tensor_by_name("wqkv", 1, spec)


def test_memory_model_counts_slot_and_pages():
    from distributed_llama_tpu.analysis import memory_model as mm

    spec = TransformerSpec(
        2560, 10240, 32, 40, 20, 200064, 8704,
        weights_float_type=FloatType.Q40,
        hybrid=HybridLayers(sambay_kinds(32), 512, 5120, 16, 4, 160))
    assert mm.state_slot_bytes(spec) == 9 * 5120 * 19 * 4 + 8 * 512 * 10240
    assert mm.kv_position_bytes(spec, 1) == 10240
    assert mm.kv_page_bytes(spec, 1, 16) == 163840
    values = mm.weight_values_per_device(spec, 1)
    assert abs(values - 3.85e9) < 0.05e9           # the published "3.8B"
    report = mm.device_footprint(spec, 1, "fused", batch=32,
                                 kv_page_size=16, kv_pages=17152)
    assert abs(report.kv_cache_bytes - (17153 * 163840 + 32 * (
        9 * 5120 * 19 * 4 + 8 * 512 * 10240))) < 1
    assert 8.5e9 < report.total_bytes < 9.5e9
    for call in (lambda: mm.weights_device_bytes(spec, 4),
                 lambda: mm.kv_cache_device_bytes(spec, 4),
                 lambda: mm.kv_position_bytes(spec, 2)):
        with pytest.raises(ValueError, match="one chip only"):
            call()
