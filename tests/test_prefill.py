"""Chunked prompt prefill: same cache, same token streams as the
token-at-a-time path (the reference's only prompt handling)."""

import numpy as np
import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.runtime.generate import (Engine, generate,
                                                    generate_fast,
                                                    run_chunked_prefill)
from distributed_llama_tpu.runtime.sampling import Sampler

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=300, seq_len=16)


class _IdTokenizer:
    def encode(self, text, bos=True, eos=False):
        return [1] + [3 + b for b in text.encode()]

    def decode_piece(self, prev, tok):
        return b"?"


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=9, scale=0.3)


def _sampler(seed=77, temp=0.9):
    return Sampler(SPEC.vocab_size, temperature=temp, topp=0.9, seed=seed)


@pytest.mark.parametrize("chunk", [2, 4, 128])
def test_prefill_cache_matches_stepwise(params, chunk):
    """Engine.prefill == the same tokens through T=1 steps: identical live
    cache prefix and identical next-step logits."""
    import jax.numpy as jnp

    tokens = [1, 9, 14, 23, 5, 40, 7]
    eng_a = Engine(SPEC, params)
    for p, t in enumerate(tokens):
        eng_a.infer(t, p)
    la = eng_a.infer(77, len(tokens))

    eng_b = Engine(SPEC, params)
    eng_b.prefill(tokens, 0, chunk=chunk)
    lb = eng_b.infer(77, len(tokens))

    n = len(tokens) + 1
    np.testing.assert_allclose(np.asarray(eng_b.cache.k[:, :n]),
                               np.asarray(eng_a.cache.k[:, :n]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lb, la, rtol=2e-5, atol=2e-5)


def test_prefill_near_seq_len_tail(params):
    """A padded chunk that would cross seq_len must not shift writes back
    over real positions (the dynamic_update_slice clamp hazard): prefill to
    within a chunk of seq_len and compare against stepwise."""
    tokens = list(np.random.default_rng(3).integers(
        3, 200, SPEC.seq_len - 2))  # 14 tokens, chunk 4 -> padded tail would
    tokens[0] = 1                   # reach pos 16 > seq_len without the guard
    eng_a = Engine(SPEC, params)
    for p, t in enumerate(tokens):
        eng_a.infer(t, p)
    eng_b = Engine(SPEC, params)
    eng_b.prefill(tokens, 0, chunk=4)
    np.testing.assert_allclose(
        np.asarray(eng_b.cache.k[:, :len(tokens)]),
        np.asarray(eng_a.cache.k[:, :len(tokens)]), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("temp", [0.0, 0.9])
def test_generate_with_prefill_matches_plain(params, temp):
    tok = _IdTokenizer()
    ref, _ = generate(Engine(SPEC, params), tok, _sampler(temp=temp),
                      "abcde", steps=12, quiet=True)
    got, _ = generate(Engine(SPEC, params), tok, _sampler(temp=temp),
                      "abcde", steps=12, quiet=True, prefill_chunk=4)
    assert got == ref


@pytest.mark.parametrize("temp", [0.0, 0.9])
def test_generate_fast_with_prefill_matches_plain(params, temp):
    tok = _IdTokenizer()
    ref, sref = generate_fast(Engine(SPEC, params), tok, _sampler(temp=temp),
                              "abcde", steps=12, quiet=True)
    got, sgot = generate_fast(Engine(SPEC, params), tok,
                              _sampler(temp=temp), "abcde", steps=12,
                              quiet=True, prefill_chunk=4)
    assert got == ref
    # resumability anchors must agree too (same final pos/token)
    assert (sgot.final_pos, sgot.final_token) == (sref.final_pos,
                                                  sref.final_token)


def test_prefill_early_bos_rng_rewind(params):
    """When the fused chain samples an early BOS, the sampler's RNG must end
    at the same state as the per-step loop — with prefill active, the coin
    accounting must use the CHAIN-generated count, not the echoed total."""
    tok = _IdTokenizer()
    # find a seed whose per-step run stops early on a sampled BOS
    # (multinomial walk over a near-uniform vocab: a small first coin lands
    # on token 1); steps > prompt so prefill engages
    found = None
    for seed in range(300):
        s = Sampler(SPEC.vocab_size, temperature=0.9, topp=1.0, seed=seed)
        eng = Engine(SPEC, params)
        out, st = generate(eng, tok, s, "abc", steps=12, quiet=True)
        if len(out) < 12 - 1 and st.final_token == 1:
            found = (seed, out, s.rng.state)
            break
    assert found is not None, "no early-BOS seed in range — widen the scan"
    seed, ref_out, ref_state = found

    s2 = Sampler(SPEC.vocab_size, temperature=0.9, topp=1.0, seed=seed)
    out2, _ = generate_fast(Engine(SPEC, params), tok, s2, "abc", steps=12,
                            quiet=True, prefill_chunk=2)
    assert out2 == ref_out
    assert s2.rng.state == ref_state


def test_prefill_gates_off_on_midstream_bos(params):
    """A prompt whose encoding contains BOS mid-stream stops the per-token
    loop; prefill must fall back so the truncated output is reproduced."""

    class _MidBos:
        def encode(self, text, bos=True, eos=False):
            return [1, 9, 1, 14, 23]  # BOS at index 2

        def decode_piece(self, prev, tok):
            return b"?"

    tok = _MidBos()
    ref, _ = generate(Engine(SPEC, params), tok, _sampler(), "x", steps=12,
                      quiet=True)
    got, _ = generate(Engine(SPEC, params), tok, _sampler(), "x", steps=12,
                      quiet=True, prefill_chunk=2)
    assert got == ref
    gotf, _ = generate_fast(Engine(SPEC, params), tok, _sampler(), "x",
                            steps=12, quiet=True, prefill_chunk=2)
    assert gotf == ref


@pytest.mark.parametrize("sp,tp", [(1, 2), (2, 1), (2, 2)])
def test_generate_prefill_on_sharded_engine(params, sp, tp):
    """--prefill-chunk on a sharded (sp/tp) engine: same stream as the
    sharded per-token path (the sp cache update handles T>1 windows that
    straddle chunk boundaries — parallel/ring.update_sp_cache)."""
    from distributed_llama_tpu.parallel import make_mesh

    tok = _IdTokenizer()
    mesh = make_mesh(sp=sp, tp=tp)
    ref, _ = generate(Engine(SPEC, params, mesh=mesh), tok, _sampler(),
                      "abcde", steps=12, quiet=True)
    got, _ = generate(Engine(SPEC, params, mesh=mesh), tok, _sampler(),
                      "abcde", steps=12, quiet=True, prefill_chunk=4)
    assert got == ref


def test_prefill_q80_buffer_parity(params):
    """Chunked prefill under the Q80 activation-wire mode: the quantize
    cut points apply identically in T>1 windows, so prefill == stepwise."""
    import dataclasses

    from distributed_llama_tpu.ops.quants import FloatType

    spec_q80 = dataclasses.replace(SPEC, buffer_float_type=FloatType.Q80)
    tok = _IdTokenizer()
    ref, _ = generate(Engine(spec_q80, params), tok, _sampler(), "abcde",
                      steps=12, quiet=True)
    got, _ = generate(Engine(spec_q80, params), tok, _sampler(), "abcde",
                      steps=12, quiet=True, prefill_chunk=4)
    assert got == ref


def test_prefill_gates_off_when_prompt_exceeds_steps(params):
    """Prompt longer than steps: prefill must not engage (the per-token
    path's forced-echo output semantics are load-bearing there)."""
    tok = _IdTokenizer()
    long = "abcdefghij"  # 11 tokens with BOS, steps 6
    ref, _ = generate(Engine(SPEC, params), tok, _sampler(), long, steps=6,
                      quiet=True)
    got, _ = generate(Engine(SPEC, params), tok, _sampler(), long, steps=6,
                      quiet=True, prefill_chunk=4)
    assert got == ref


def test_fast_prefill_bf16_tolerance_and_isolation():
    """--fast-prefill: the bf16 prefill program fills the cache within a
    pinned tolerance of the parity program, touches ONLY T>8 chunks (the
    T=1 tail and decode keep the parity forward), and the same-engine
    decode path object is unchanged (VERDICT r1 #7)."""
    import numpy as np

    import jax.numpy as jnp

    from distributed_llama_tpu.models.synth import synth_params
    from distributed_llama_tpu.runtime.generate import Engine

    params = synth_params(SPEC, q40=False, seed=3, scale=0.3)
    tokens = list(np.random.default_rng(1).integers(2, SPEC.vocab_size,
                                                    12))

    ref = Engine(SPEC, params)
    ref.prefill([int(t) for t in tokens], 0, chunk=12)
    fast = Engine(SPEC, params, fast_prefill=True)
    assert fast._fwd_prefill is not None and fast._fwd_prefill is not fast._fwd
    fast.prefill([int(t) for t in tokens], 0, chunk=12)

    k_ref = np.asarray(ref.cache.k[:, :12])
    k_fast = np.asarray(fast.cache.k[:, :12])
    # pinned bf16 drift bound, relative to activation scale: bf16 mantissa
    # gives ~2^-8 per op; observed ~1.2e-2 relative over 2 layers — pin ~2x
    scale = np.abs(k_ref).max()
    drift = np.abs(k_ref - k_fast).max() / scale
    assert 0 < drift < 2.5e-2
    # decode after prefill still runs the parity program (same jitted fn)
    lg_ref = ref.infer(int(tokens[-1]) % SPEC.vocab_size, 12)
    lg_fast = fast.infer(int(tokens[-1]) % SPEC.vocab_size, 12)
    rel = np.abs(lg_ref - lg_fast).max() / max(np.abs(lg_ref).max(), 1e-9)
    assert rel < 2.5e-2  # only prefilled-cache drift remains


def test_fused_prefill_loop_matches_per_chunk_dispatch():
    """>=2 full windows at chunk>8 run as ONE device program (fori_loop
    over windows, cache donated — Engine._prefill_loop). Cache and
    next-step logits must match the per-chunk host dispatch exactly
    (same per-window program, f32)."""
    import jax.numpy as jnp

    spec = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                           n_kv_heads=2, vocab_size=300, seq_len=64)
    params = synth_params(spec, q40=False, seed=11, scale=0.3)
    tokens = list(np.random.default_rng(3).integers(2, 290, 41))  # 3x12+5

    eng_a = Engine(spec, params)
    eng_a.prefill(tokens, 0, chunk=12)  # fused: 3 full windows + tail 5
    la = eng_a.infer(7, len(tokens))

    eng_b = Engine(spec, params)  # reference: windows dispatched one by one
    for lo in range(0, 36, 12):
        _, eng_b.cache = eng_b._fwd_prefill(
            eng_b.params, eng_b.cache,
            jnp.asarray(tokens[lo:lo + 12], jnp.int32), jnp.int32(lo))
    run_chunked_prefill(
        lambda part, start: setattr(
            eng_b, "cache",
            eng_b._fwd_prefill(eng_b.params, eng_b.cache,
                               jnp.asarray(part, jnp.int32),
                               jnp.int32(start))[1]),
        tokens[36:], 36, 12, spec.seq_len)
    lb = eng_b.infer(7, len(tokens))

    n = len(tokens) + 1
    np.testing.assert_allclose(np.asarray(eng_a.cache.k[:, :n]),
                               np.asarray(eng_b.cache.k[:, :n]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(la, lb, rtol=1e-6, atol=1e-6)


def test_blockwise_prefill_attention_matches_dense(monkeypatch):
    """T>8 prefill attention via the blockwise live-prefix while_loop
    (DLLAMA_PREFILL_ATTN=block, the default) must match the dense
    masked-plane path within online-softmax reassociation noise."""
    spec = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                           n_kv_heads=2, vocab_size=300, seq_len=64)
    params = synth_params(spec, q40=False, seed=21, scale=0.3)
    tokens = list(np.random.default_rng(5).integers(2, 290, 48))

    out = {}
    for mode in ("block", "dense"):
        monkeypatch.setenv("DLLAMA_PREFILL_ATTN", mode)
        eng = Engine(spec, params)
        eng.prefill(tokens, 0, chunk=16)
        out[mode] = (np.asarray(eng.cache.k[:, :49]),
                     eng.infer(7, len(tokens)))
    np.testing.assert_allclose(out["block"][0], out["dense"][0],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out["block"][1], out["dense"][1],
                               rtol=1e-5, atol=1e-5)


def test_prefill_attn_mode_rejects_typos(monkeypatch):
    monkeypatch.setenv("DLLAMA_PREFILL_ATTN", "blockwise")
    from distributed_llama_tpu.models.llama import _prefill_attn_mode

    with pytest.raises(ValueError, match="DLLAMA_PREFILL_ATTN"):
        _prefill_attn_mode()
