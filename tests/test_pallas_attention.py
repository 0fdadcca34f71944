"""Interpret-mode parity tests for the flash-decode attention kernel."""

import numpy as np
import pytest


# (n_kv, kv_mul): Mistral's heads, a Yi-34B tp-4 rank's, a one-head rank's
# and a 13B tp-4 rank's (whole sublane tiles take the strided read of a
# landed slot, the others its relayout); positions on both sides of a
# 128-position turn and at the end of the plane. A bf16 cache is widened a
# landed slot; the chip lands bf16 heads in whole tiles or in 2s and 4s
# alone (``_chunk`` refuses the rest), so its cases are the first two.
HEADS = [(8, 4), (2, 7), (1, 8), (10, 1)]
S_TURNS = 384
EDGES = (0, 127, 128, 255, 256, S_TURNS - 1)
TURN_CASES = [
    *((4, m, p, 32, "float32") for m, p in [(1, 0), (1, 5), (1, 31), (2, 9),
                                            (4, 17), (8, 9)]),
    *((n, m, p, S_TURNS, dt)
      for dt, heads in (("float32", HEADS), ("bfloat16", HEADS[:2]))
      for n, m in heads for p in EDGES)]


def _caches(rows, S, n_kv, hs, dtype, seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    k, v = (jnp.asarray(rng.normal(size=(rows, S, n_kv, hs)).astype(
        np.float32)).astype(dtype) for _ in range(2))
    return rng, k, v


def _core(q_row, k_row, v_row, pos, kv_mul):
    """models/llama.attention_core of one query row over a cache row read
    as float32 (a bf16 cache widens exactly)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)

    S, _, hs = k_row.shape
    return attention_core(hs, kv_mul, q_row[None], k_row.astype(jnp.float32),
                          v_row.astype(jnp.float32),
                          causal_cache_mask(S, jnp.int32(pos), 1))


@pytest.mark.parametrize("n_kv,kv_mul,pos,S,dtype", TURN_CASES)
def test_decode_attention_matches_core(n_kv, kv_mul, pos, S, dtype):
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_attention import decode_attention

    L, hs = 3, 128
    n_q = n_kv * kv_mul
    layer = 1
    rng, k_all, v_all = _caches(L, S, n_kv, hs, dtype, pos * 7 + kv_mul)
    q = jnp.asarray(rng.normal(size=(n_q, hs)).astype(np.float32))

    want = _core(q, k_all[layer], v_all[layer], pos, kv_mul)
    got = decode_attention(q, k_all, v_all, layer, pos, kv_mul=kv_mul,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_kv,kv_mul,pos,S,dtype", [
    *((4, m, p, 32, "float32") for m, p in [(1, 0), (1, 17), (2, 9), (8, 9)]),
    *((n, m, p, S_TURNS, "float32") for n, m in HEADS for p in (127, 128)),
    (8, 4, 255, S_TURNS, "bfloat16"), (2, 7, 256, S_TURNS, "bfloat16")])
def test_decode_attention_batch_matches_core(n_kv, kv_mul, pos, S, dtype):
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_attention import \
        decode_attention_batch

    L, B, hs = 2, 3, 128
    n_q = n_kv * kv_mul
    layer = 1
    # rank-4 batched cache (L*B, S, n_kv, hs), row = layer*B + b
    rng, k4, v4 = _caches(L * B, S, n_kv, hs, dtype, pos * 3 + kv_mul)
    q = jnp.asarray(rng.normal(size=(B, n_q, hs)).astype(np.float32))

    got = decode_attention_batch(q, k4, v4, layer, pos, kv_mul=kv_mul,
                                 interpret=True)
    for b in range(B):
        want = _core(q[b], k4[layer * B + b], v4[layer * B + b], pos, kv_mul)
        np.testing.assert_allclose(np.asarray(got[b][None]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_kv,kv_mul,pos,S,dtype", [
    (4, 1, [0, 17, 9], 32, "float32"), (4, 2, [0, 17, 9], 32, "float32"),
    *((n, m, [0, 128, S_TURNS - 1], S_TURNS, "float32") for n, m in HEADS),
    *((n, m, [127, 256, 255], S_TURNS, "bfloat16") for n, m in HEADS[:2])])
def test_decode_attention_batch_ragged_pos(n_kv, kv_mul, pos, S, dtype):
    """Per-row position clocks (continuous batching): each row's flash walk
    must honor ITS pos (rows that end in different turns of the walk),
    matching the per-row reference attention."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_attention import \
        decode_attention_batch

    L, B, hs = 2, 3, 128
    n_q = n_kv * kv_mul
    layer = 1
    pos_vec = jnp.asarray(pos, jnp.int32)
    rng, k4, v4 = _caches(L * B, S, n_kv, hs, dtype, 11 + kv_mul)
    q = jnp.asarray(rng.normal(size=(B, n_q, hs)).astype(np.float32))

    got = decode_attention_batch(q, k4, v4, layer, pos_vec, kv_mul=kv_mul,
                                 interpret=True)
    for b in range(B):
        want = _core(q[b], k4[layer * B + b], v4[layer * B + b], pos[b],
                     kv_mul)
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


def _float64_distance(n_kv, kv_mul, scale_k):
    """(max, root mean square) of |kernel - float64 attention| over eight
    one-query rows of a 384-position plane that end around its turns' edges,
    K scaled by ``scale_k``, and the max of the same attention with every
    product's operands rounded to bfloat16 (the control)."""
    import jax
    import jax.numpy as jnp

    from test_pallas_paged_attention import _attention64

    from distributed_llama_tpu.ops.pallas_attention import \
        decode_attention_batch

    pos = [141, 158, 127, 128, 100, 64, S_TURNS - 1, 31]
    B, hs = len(pos), 128
    rng = np.random.default_rng(59)
    k = rng.normal(size=(B, S_TURNS, n_kv, hs)).astype(np.float32) \
        * np.float32(scale_k)
    v = rng.normal(size=(B, S_TURNS, n_kv, hs)).astype(np.float32)
    q = rng.normal(size=(B, n_kv * kv_mul * hs)).astype(np.float32)

    got = np.asarray(decode_attention_batch(
        jnp.asarray(q).reshape(B, -1, hs), jnp.asarray(k), jnp.asarray(v), 0,
        jnp.asarray(pos, jnp.int32), kv_mul=kv_mul,
        interpret=True)).astype(np.float64)
    want = _attention64(q, k, v, pos, kv_mul)
    bf = lambda a: np.asarray(jax.lax.reduce_precision(  # noqa: E731
        jnp.asarray(a), exponent_bits=8, mantissa_bits=7))
    err = np.abs(got - want)
    return (float(err.max()), float(np.sqrt((err ** 2).mean())),
            float(np.abs(_attention64(bf(q), bf(k), bf(v), pos, kv_mul)
                         - want).max()))


# (max, rms) of ``_float64_distance`` in interpret mode on the CPU: of the
# vector-unit fold this kernel had until PR 59 (a chunk of 128 here, ``jnp
# .sum(k * q)`` a query head: commit c44f3d4 run on THIS file's inputs), and
# of the head-major ``_fold`` as PR 59 left it. Interpret mode multiplies in
# float32 whatever the pieces, so what differs HERE is the order of the
# float32 sums alone: a unit or two in the last place of an output either
# way. On the chip, where the piece products are exact and the MXU
# accumulates, the fold read at or under the vector-unit fold at every depth
# and shape timed (PERF.md section 7 has the table).
PARENT_DISTANCE = {(8, 4, 1.0): (3.196e-07, 3.353e-08),
                   (8, 4, 30.0): (1.133e-05, 7.799e-07),
                   (2, 7, 1.0): (3.473e-07, 3.662e-08),
                   (2, 7, 30.0): (7.834e-06, 6.049e-07),
                   (1, 8, 1.0): (2.210e-07, 3.075e-08),
                   (1, 8, 30.0): (1.199e-05, 7.769e-07),
                   (10, 1, 1.0): (3.422e-07, 3.429e-08),
                   (10, 1, 30.0): (1.028e-05, 7.417e-07)}
FOLD_DISTANCE = {(8, 4, 1.0): (3.592e-07, 2.899e-08),
                 (8, 4, 30.0): (1.690e-05, 8.466e-07),
                 (2, 7, 1.0): (3.347e-07, 2.883e-08),
                 (2, 7, 30.0): (1.212e-05, 8.076e-07),
                 (1, 8, 1.0): (1.658e-07, 2.553e-08),
                 (1, 8, 30.0): (1.382e-05, 7.984e-07),
                 (10, 1, 1.0): (4.018e-07, 3.232e-08),
                 (10, 1, 30.0): (1.206e-05, 6.818e-07)}


@pytest.mark.parametrize("scale_k", [1.0, 30.0])
@pytest.mark.parametrize("n_kv,kv_mul", HEADS)
def test_the_fold_keeps_float32(n_kv, kv_mul, scale_k):
    """The fold at every tested head count against a float64 attention, on
    standard-normal K and on K of thirty times the norm (scores to +-1,000:
    one winner a row, where a bf16 product moves the winner), beside the
    vector-unit fold's reading on the SAME inputs (``PARENT_DISTANCE``
    against ``FOLD_DISTANCE``, both stated above): the root mean square
    under it at standard-normal K (0.79 to 0.94 times) and within 1.4 times
    at thirty times the norm, the max of the eight rows within twice (it
    goes either way with the rows drawn). The same attention with every
    product's operands rounded to bfloat16 is at least 100 times further: a
    fold that is quietly three passes, or one, fails here."""
    worst, rms, control = _float64_distance(n_kv, kv_mul, scale_k)
    was_worst, was_rms = PARENT_DISTANCE[n_kv, kv_mul, scale_k]
    assert worst <= 2 * was_worst and rms <= 1.4 * was_rms, (worst, rms)
    assert (worst, rms) == pytest.approx(
        FOLD_DISTANCE[n_kv, kv_mul, scale_k], rel=0.25)
    assert control >= 100 * worst, (control, worst)


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
                if itemsize == 2 and n_kv % 8 and n_kv not in (2, 4):
                    # heads the chip cannot land from a bf16 cache (13B's
                    # 20, 10 and 5, 70B's 1: tests/test_chip_compile.py):
                    # the XLA path, by the gate and not by a refused compile
                    assert c is None and not pa.supports(
                        spec.seq_len, spec.head_size, 1, n_kv, itemsize)
                    continue
                # the turn's rule: the fold's tile, or 256 positions where
                # a side's slot of 256 is within _TURN_BYTES (few heads)
                few = 256 * n_kv * spec.head_size * itemsize <= pa._TURN_BYTES
                assert c == (256 if few else 128), (spec.n_layers, tp,
                                                    itemsize, c)
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
