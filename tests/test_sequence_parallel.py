"""Sequence parallelism: sp-sharded cache decode parity + ring attention."""

import numpy as np
import pytest

from distributed_llama_tpu.models.spec import TransformerSpec

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=8,
                       n_kv_heads=4, vocab_size=96, seq_len=32)


def _params(seed=11, scale=0.1):
    rng = np.random.default_rng(seed)

    def t(*shape):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    p = {"tok_embedding": t(SPEC.vocab_size, SPEC.dim),
         "rms_final": 1 + t(SPEC.dim), "wcls": t(SPEC.vocab_size, SPEC.dim),
         "rms_att": 1 + t(SPEC.n_layers, SPEC.dim),
         "rms_ffn": 1 + t(SPEC.n_layers, SPEC.dim)}
    for name, shape in SPEC.layer_matmul_shapes():
        p[name] = t(SPEC.n_layers, *shape)
    return p


def _reference_logits(p, tokens):
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import forward, init_cache

    pj = {k: jnp.asarray(v) for k, v in p.items()}
    logits, _ = forward(SPEC, pj, init_cache(SPEC), jnp.asarray(tokens),
                        jnp.int32(0))
    return np.asarray(logits)


@pytest.mark.parametrize("sp,tp", [(2, 1), (4, 1), (2, 2), (4, 2), (2, 4)])
def test_sp_decode_parity(sp, tp):
    """sp x tp sharded forward == single-device forward, across chunked
    prefill that straddles sp chunk boundaries, then continued decode."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import init_cache
    from distributed_llama_tpu.parallel import (make_mesh, make_sharded_forward,
                                                shard_cache, shard_params)

    p = _params()
    # 7 tokens with seq_chunk = 32/sp in {16, 8}: prefill straddles chunks
    tokens = np.array([1, 5, 9, 2, 17, 3, 8], dtype=np.int32)
    want = _reference_logits(p, tokens)

    mesh = make_mesh(sp=sp, tp=tp)
    fwd = make_sharded_forward(SPEC, mesh)
    params = shard_params(p, mesh)
    cache = shard_cache(init_cache(SPEC), mesh)
    got, cache = fwd(params, cache, jnp.asarray(tokens), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)

    # continue decoding one token; compare against the unsharded continuation
    from distributed_llama_tpu.models.llama import forward as fwd1, init_cache as ic1

    pj = {k: jnp.asarray(v) for k, v in p.items()}
    c1 = ic1(SPEC)
    _, c1 = fwd1(SPEC, pj, c1, jnp.asarray(tokens), jnp.int32(0))
    want2, _ = fwd1(SPEC, pj, c1, jnp.asarray([4], dtype=np.int32),
                    jnp.int32(7))
    got2, _ = fwd(params, cache, jnp.asarray([4], dtype=np.int32),
                  jnp.int32(7))
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=0, atol=2e-5)


def test_ring_attention_matches_dense():
    """ring_attention over 4 sp ranks == dense causal attention."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_llama_tpu.models.llama import attention_core
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.ring import ring_attention

    head_size, n_q, n_kv = 8, 4, 2
    kv_mul = n_q // n_kv
    T = 32
    sp = 4
    chunk = T // sp
    rng = np.random.default_rng(0)
    q = rng.standard_normal((T, n_q, head_size)).astype(np.float32)
    k = rng.standard_normal((T, n_kv, head_size)).astype(np.float32)
    v = rng.standard_normal((T, n_kv, head_size)).astype(np.float32)

    # dense reference: full causal attention within the window
    mask = np.tril(np.ones((T, T), bool))
    want = np.asarray(attention_core(head_size, kv_mul, jnp.asarray(q),
                                     jnp.asarray(k), jnp.asarray(v),
                                     jnp.asarray(mask)))

    mesh = make_mesh(sp=sp, tp=1)

    def local(qc, kc, vc):
        start = jax.lax.axis_index("sp") * chunk
        return ring_attention(head_size, kv_mul, qc, kc, vc, start, chunk,
                              axis_size=sp)

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
        out_specs=P("sp"), check_vma=False))
    got = np.asarray(fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_update_sp_cache_straddle():
    """Writes that straddle chunk boundaries land in the right rows."""
    import jax.numpy as jnp

    from distributed_llama_tpu.parallel.ring import update_sp_cache

    chunk, n_kv, hs = 8, 1, 2
    new = jnp.arange(4 * n_kv * hs, dtype=jnp.float32).reshape(4, n_kv, hs)
    # pos=6, T=4: rows 6,7 in chunk 0; rows 0,1 in chunk 1
    c0 = update_sp_cache(jnp.zeros((chunk, n_kv, hs)), new, jnp.int32(6),
                         jnp.int32(0), chunk)
    c1 = update_sp_cache(jnp.zeros((chunk, n_kv, hs)), new, jnp.int32(6),
                         jnp.int32(1), chunk)
    np.testing.assert_array_equal(np.asarray(c0[6]), np.asarray(new[0]))
    np.testing.assert_array_equal(np.asarray(c0[7]), np.asarray(new[1]))
    np.testing.assert_array_equal(np.asarray(c1[0]), np.asarray(new[2]))
    np.testing.assert_array_equal(np.asarray(c1[1]), np.asarray(new[3]))
    assert not np.any(np.asarray(c0[:6]))
    assert not np.any(np.asarray(c1[2:]))


def test_blockwise_chunk_partials_match_dense_partials():
    """The T>8 live-prefix walk inside sp_cache_attention must produce the
    same (m, l, o) flash partials as one dense masked pass over the chunk,
    including rows that see nothing of this chunk (m = -inf) and chunks
    entirely past the live prefix."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_llama_tpu.parallel.ring import (_partial_attention,
                                                     blockwise_chunk_partials)

    rng = np.random.default_rng(17)
    hs, kv_mul, n_kv, t_len, c = 16, 2, 2, 12, 64
    n_q = n_kv * kv_mul
    q = jnp.asarray(rng.standard_normal((t_len, n_q, hs)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((c, n_kv, hs)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((c, n_kv, hs)).astype(np.float32))

    for chunk_start, pos in ((0, 5), (64, 5), (64, 70), (0, 100)):
        q_pos = pos + jnp.arange(t_len)
        key_pos = chunk_start + np.arange(c)
        valid = jnp.asarray(key_pos[None, :] <= np.asarray(q_pos)[:, None])
        want = _partial_attention(hs, kv_mul, q, k, v, valid)
        got = blockwise_chunk_partials(hs, kv_mul, q, k, v,
                                       jnp.int32(chunk_start), q_pos,
                                       block=16)
        for w, g, name in zip(want, got, ("m", "l", "o")):
            w, g = np.asarray(w), np.asarray(g)
            if name == "m":
                # -inf rows must agree exactly; finite rows to fp tolerance
                np.testing.assert_array_equal(np.isfinite(w), np.isfinite(g))
                w, g = np.nan_to_num(w, neginf=0), np.nan_to_num(g, neginf=0)
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{name} at "
                                               f"({chunk_start}, {pos})")
