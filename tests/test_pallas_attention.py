"""Interpret-mode parity tests for the flash-decode attention kernel."""

import numpy as np
import pytest


@pytest.mark.parametrize("kv_mul,pos", [(1, 0), (1, 5), (1, 31), (2, 9),
                                        (4, 17), (8, 9)])
def test_decode_attention_matches_core(kv_mul, pos):
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)
    from distributed_llama_tpu.ops.pallas_attention import decode_attention

    L, S, n_kv, hs = 3, 32, 4, 128
    n_q = n_kv * kv_mul
    layer = 1
    rng = np.random.default_rng(pos * 7 + kv_mul)
    k_all = jnp.asarray(rng.normal(size=(L, S, n_kv, hs)).astype(np.float32))
    v_all = jnp.asarray(rng.normal(size=(L, S, n_kv, hs)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(n_q, hs)).astype(np.float32))

    want = attention_core(hs, kv_mul, q.reshape(1, n_q, hs),
                          k_all[layer], v_all[layer],
                          causal_cache_mask(S, jnp.int32(pos), 1))
    got = decode_attention(q, k_all, v_all, layer, pos, kv_mul=kv_mul,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv_mul,pos", [(1, 0), (1, 17), (2, 9), (8, 9)])
def test_decode_attention_batch_matches_core(kv_mul, pos):
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)
    from distributed_llama_tpu.ops.pallas_attention import \
        decode_attention_batch

    L, B, S, n_kv, hs = 2, 3, 32, 4, 128
    n_q = n_kv * kv_mul
    layer = 1
    rng = np.random.default_rng(pos * 3 + kv_mul)
    # rank-4 batched cache (L*B, S, n_kv, hs), row = layer*B + b
    k4 = jnp.asarray(rng.normal(size=(L * B, S, n_kv, hs)).astype(np.float32))
    v4 = jnp.asarray(rng.normal(size=(L * B, S, n_kv, hs)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, n_q, hs)).astype(np.float32))

    got = decode_attention_batch(q, k4, v4, layer, pos, kv_mul=kv_mul,
                                 interpret=True)
    mask = causal_cache_mask(S, jnp.int32(pos), 1)
    for b in range(B):
        want = attention_core(hs, kv_mul, q[b][None], k4[layer * B + b],
                              v4[layer * B + b], mask)
        np.testing.assert_allclose(np.asarray(got[b][None]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv_mul", [1, 2])
def test_decode_attention_batch_ragged_pos(kv_mul):
    """Per-row position clocks (continuous batching): each row's flash walk
    must honor ITS pos, matching the per-row reference attention."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)
    from distributed_llama_tpu.ops.pallas_attention import \
        decode_attention_batch

    L, B, S, n_kv, hs = 2, 3, 32, 4, 128
    n_q = n_kv * kv_mul
    layer = 1
    pos_vec = jnp.asarray([0, 17, 9], jnp.int32)
    rng = np.random.default_rng(11 + kv_mul)
    k4 = jnp.asarray(rng.normal(size=(L * B, S, n_kv, hs)).astype(np.float32))
    v4 = jnp.asarray(rng.normal(size=(L * B, S, n_kv, hs)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, n_q, hs)).astype(np.float32))

    got = decode_attention_batch(q, k4, v4, layer, pos_vec, kv_mul=kv_mul,
                                 interpret=True)
    for b in range(B):
        mask = causal_cache_mask(S, pos_vec[b], 1)
        want = attention_core(hs, kv_mul, q[b][None], k4[layer * B + b],
                              v4[layer * B + b], mask)
        np.testing.assert_allclose(np.asarray(got[b][None]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


def test_decode_attention_ignores_stale_suffix():
    """Entries beyond pos (stale garbage from earlier generations) must not
    affect the result — the kernel only walks live chunks and masks within
    the last one."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_attention import decode_attention

    L, S, n_kv, hs = 1, 64, 2, 128
    rng = np.random.default_rng(0)
    k_all = rng.normal(size=(L, S, n_kv, hs)).astype(np.float32)
    v_all = rng.normal(size=(L, S, n_kv, hs)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(n_kv, hs)).astype(np.float32))
    pos = 7

    a = decode_attention(q, jnp.asarray(k_all), jnp.asarray(v_all), 0, pos,
                         kv_mul=1, interpret=True)
    k_all[:, pos + 1:] = 1e6  # poison the dead region
    v_all[:, pos + 1:] = -1e6
    b = decode_attention(q, jnp.asarray(k_all), jnp.asarray(v_all), 0, pos,
                         kv_mul=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shard_shapes_have_vmem_headroom():
    """Every bench (model, tp) shard shape must admit a cache chunking
    whose scratch fits the budget, under a raised scoped-VMEM limit with
    real headroom — the 13b-tp4 margin bug (BASELINE.md r4): scratch near
    the 12 MB budget plus compiler temporaries landed 76 KB over the
    default 16 MB limit and silently fell back to the XLA path."""
    from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                    llama2_13b_spec,
                                                    llama2_70b_spec)
    from distributed_llama_tpu.ops import pallas_attention as pa

    # the raised limit must leave a wide margin over the scratch budget,
    # not the 33% the default limit gave
    assert pa._VMEM64_PARAMS.vmem_limit_bytes >= 4 * pa._VMEM_BUDGET

    for spec in (llama2_7b_spec(), llama2_13b_spec(), llama2_70b_spec()):
        for tp in (1, 2, 4, 8):
            if spec.n_kv_heads % tp:
                continue
            n_kv = spec.n_kv_heads // tp
            for itemsize in (2, 4):  # bf16 and f32 caches
                c = pa._chunk(spec.seq_len, n_kv, spec.head_size, itemsize)
                assert c is not None, (spec.n_layers, tp, itemsize)
                assert (pa._scratch_bytes(c, n_kv, spec.head_size,
                                          itemsize)
                        <= pa._VMEM_BUDGET), (spec.n_layers, tp, itemsize)


@pytest.mark.parametrize("kv_mul,pos,t_len", [(1, 0, 16), (1, 16, 16),
                                              (1, 48, 16), (2, 0, 32),
                                              (4, 24, 16), (8, 8, 16)])
def test_prefill_attention_matches_core(kv_mul, pos, t_len):
    """The prefill flash kernel (VERDICT r4 #5) against the dense masked
    path: same causal contract (the chunk's own keys are in the cache),
    every GQA group width, first/mid/deep chunk positions."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)
    from distributed_llama_tpu.ops.pallas_attention import (
        prefill_attention, supports_prefill)

    S, n_kv, hs = 64, 2, 128
    n_q = n_kv * kv_mul
    assert supports_prefill(S, hs, t_len, kv_mul)
    rng = np.random.default_rng(pos * 11 + kv_mul + t_len)
    k = jnp.asarray(rng.normal(size=(S, n_kv, hs)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(S, n_kv, hs)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(t_len, n_q, hs)).astype(np.float32))

    want = attention_core(hs, kv_mul, q, k, v,
                          causal_cache_mask(S, jnp.int32(pos), t_len))
    got = prefill_attention(q, k, v, pos, kv_mul=kv_mul, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want).reshape(t_len, n_q, hs),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_kv,mxu_bf16", [(8, True), (16, True),
                                           (16, False)])
def test_prefill_attention_bf16_cache_and_mode(n_kv, mxu_bf16):
    """bf16 cache dtype + bf16 MXU mode stay within the fast-prefill
    tolerance against the dense path run on the same bf16 cache. A bf16
    cache walks whole 8-head HBM tiles as uint32 head pairs (the chip's
    DMA cannot slice one bf16 head): 16 heads cover a second tile, and
    fewer than 8 are gated off. With f32 MXU passes the widening must be
    exact (flash reassociation only)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)
    from distributed_llama_tpu.ops.pallas_attention import (
        prefill_attention, supports_prefill)

    S, hs, kv_mul, t_len, pos = 64, 128, 2, 16, 24
    assert supports_prefill(S, hs, t_len, kv_mul, n_kv=n_kv, itemsize=2)
    assert not supports_prefill(S, hs, t_len, kv_mul, n_kv=2, itemsize=2)
    n_q = n_kv * kv_mul
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(S, n_kv, hs))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(S, n_kv, hs))).astype(jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(t_len, n_q, hs)).astype(np.float32))

    want = attention_core(hs, kv_mul, q, k.astype(jnp.float32),
                          v.astype(jnp.float32),
                          causal_cache_mask(S, jnp.int32(pos), t_len))
    got = prefill_attention(q, k, v, pos, kv_mul=kv_mul, bf16=mxu_bf16,
                            interpret=True)
    tol = 0.02 if mxu_bf16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want).reshape(t_len, n_q, hs),
        rtol=tol, atol=tol)


def test_prefill_attention_walks_only_live_blocks():
    """Keys beyond the causal bound must not influence the result: poison
    the dead region of the cache with huge values and compare against a
    clean cache."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_attention import prefill_attention

    S, n_kv, hs, t_len, pos = 128, 2, 128, 16, 8
    rng = np.random.default_rng(5)
    k = rng.normal(size=(S, n_kv, hs)).astype(np.float32)
    v = rng.normal(size=(S, n_kv, hs)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(t_len, n_kv, hs)).astype(np.float32))

    clean = prefill_attention(q, jnp.asarray(k), jnp.asarray(v), pos,
                              kv_mul=1, interpret=True)
    live = pos + t_len
    k[live:] = 1e9
    v[live:] = -1e9
    poisoned = prefill_attention(q, jnp.asarray(k), jnp.asarray(v), pos,
                                 kv_mul=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))
