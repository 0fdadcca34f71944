"""Paged flash-decode Pallas kernel, interpret-mode gates.

A loop turn lands the pages that make 128 positions (8 at 16 a page) and
folds them on the MXU in exact float32 pieces, a KV head a strided read of
the slot, so the cases sit at positions that cross a page and a turn, at
Mistral's (8 KV heads x 4), OLMoE's (16 x 1) and a tp rank's (2 x 4) head
counts.

Contract layers:

* kernel vs the XLA gather path: element-level agreement at the flash
  tolerance (the split-KV accumulation reassociates softmax sums across
  page boundaries — reassociation-only deltas, same contract as the
  prefill flash kernel) on BOTH hot shapes: single-token decode and the
  (B, K) speculative-verify window, scrambled physical pages included;
* BITWISE invariants: physical page placement is invisible (any pool
  permutation + table update reproduces identical bytes), and dead
  writes parked on the scrap page / junk beyond the causal bound never
  reach the output;
* Q8 pages: the in-kernel dequant agrees with the XLA fallback's
  gather-side dequant (identical value map, flash-tolerance reduction);
* float32 kept: the fold is no further from a float64 attention than the
  vector-unit fold it replaced was on the same inputs, and a bfloat16
  attention is far;
* routing: the ONE maybe_paged_flash_decode gate drives the kernel
  through models/llama.paged_decode_attention + spec_verify_attention
  and both tp factories — pinned over tp x scheme x kv-quant with the
  XLA route as reference.
"""

import numpy as np
import pytest


def _pool(L=2, P=13, ps=8, n_kv=2, hs=128, seed=0):
    rng = np.random.default_rng(seed)
    k4 = rng.normal(size=(L * P, ps, n_kv, hs)).astype(np.float32)
    v4 = rng.normal(size=(L * P, ps, n_kv, hs)).astype(np.float32)
    return k4, v4


def _scrambled_table(B, max_pages, P, seed=1):
    """Physical ids deliberately non-contiguous and interleaved across
    rows (never the scrap page 0)."""
    rng = np.random.default_rng(seed)
    ids = 1 + rng.permutation(P - 1)[:B * max_pages]
    return ids.reshape(B, max_pages).astype(np.int32)


def _xla_reference(q, k4, v4, layer, pos, table, ps, P, kv_mul, t_len):
    """The XLA gather path's math, verbatim (paged_decode_attention /
    spec_verify_attention read side)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import attention_core

    B, max_pages = table.shape
    n_kv, hs = k4.shape[2], k4.shape[3]
    s_virt = max_pages * ps
    rows = (layer * P + table).reshape(-1)
    k_c = jnp.take(jnp.asarray(k4), jnp.asarray(rows), axis=0).reshape(
        B, s_virt, n_kv, hs)
    v_c = jnp.take(jnp.asarray(v4), jnp.asarray(rows), axis=0).reshape(
        B, s_virt, n_kv, hs)
    q_pos = jnp.asarray(pos)[:, None] + jnp.arange(t_len)[None, :]
    mask = jnp.arange(s_virt)[None, None, :] <= q_pos[:, :, None]
    return np.asarray(attention_core(
        hs, kv_mul, jnp.asarray(q).reshape(B, t_len, n_kv * kv_mul, hs),
        k_c, v_c, mask)).reshape(B, t_len, -1)


# (n_kv, kv_mul): Mistral's pool, OLMoE's, a 7B pool's heads with one query
# head each, and a tp-4 rank of Mistral's (heads that are not a sublane tile)
HEADS = [(8, 4), (16, 1), (8, 1), (2, 4)]
# pages of 16, ten a row: a turn is 8 pages = 128 positions. A row each at
# depth 0, a page's last position, the next page's first, one under, at and
# one over a turn's edge, and at the last position of the plane (a verify
# window's last queries lie past it: the budget edge)
PS, MAX_PAGES = 16, 10
EDGES = [0, 15, 16, 127, 128, 129, MAX_PAGES * PS - 1]


def _edge_case(n_kv, kv_mul, t_len, seed, pos=EDGES):
    """One call's operands with a row at each of ``pos``: (q, k4, v4, pos,
    table, P), two layers' pools."""
    B = len(pos)
    P = B * MAX_PAGES + 1
    k4, v4 = _pool(2, P, PS, n_kv, 128, seed=seed)
    table = _scrambled_table(B, MAX_PAGES, P, seed=seed)
    q = np.random.default_rng(seed).normal(
        size=(B, t_len, n_kv * kv_mul * 128)).astype(np.float32)
    return q, k4, v4, np.asarray(pos, np.int32), table, P


def _run(q, k4, v4, pos, table, P, kv_mul, t_len, layer=1):
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        paged_decode_attention_kernel

    return np.asarray(paged_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(k4), jnp.asarray(v4), layer, pos,
        jnp.asarray(table), page_size=k4.shape[1], n_pages=P, kv_mul=kv_mul,
        t_len=t_len, interpret=True))


@pytest.mark.parametrize("t_len", [1, 4])
@pytest.mark.parametrize("n_kv,kv_mul", HEADS)
def test_paged_walk_matches_xla_gather_across_pages_and_turns(n_kv, kv_mul,
                                                              t_len):
    """Decode (t 1) and a verify window (t 4) against the XLA gather path,
    a row at each of ``EDGES``: a group's heads and a window's queries are
    rows of one product, each with its own causal bound."""
    q, k4, v4, pos, table, P = _edge_case(n_kv, kv_mul, t_len,
                                          seed=n_kv + t_len)
    got = _run(q, k4, v4, pos, table, P, kv_mul, t_len)
    want = _xla_reference(q, k4, v4, 1, pos, table, PS, P, kv_mul, t_len)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t_len", [1, 4])
@pytest.mark.parametrize("n_kv,kv_mul", HEADS)
def test_a_turn_reads_the_rows_own_live_pages_only(n_kv, kv_mul, t_len):
    """A turn's pages past a row's last live one are neither copied nor
    seen: with the scrap page and every page no live position maps (the
    rest of each row's table included) poisoned with NaN, and the junk past
    each clock inside its last live page (which lands whole and is masked)
    made huge, the output is bit for bit the clean one. The rows come
    shallow after deep, so a slot still holds an earlier row's pages where
    this row's turn has none."""
    pos = [141, 0, 128, 15, 127, 16]
    q, k4, v4, pos, table, P = _edge_case(n_kv, kv_mul, t_len, seed=3,
                                          pos=pos)
    clean = _run(q, k4, v4, pos, table, P, kv_mul, t_len, layer=0)
    k4p, v4p = k4.copy(), v4.copy()
    live = np.zeros(2 * P, bool)
    for b, p in enumerate(pos):
        last = min(int(p) + t_len - 1, MAX_PAGES * PS - 1)
        live[table[b, :last // PS + 1]] = True
        page = table[b, last // PS]
        k4p[page, last % PS + 1:], v4p[page, last % PS + 1:] = 1e9, -1e9
    k4p[~live], v4p[~live] = np.nan, np.nan     # never read
    poisoned = _run(q, k4p, v4p, pos, table, P, kv_mul, t_len, layer=0)
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(clean, poisoned)


@pytest.mark.parametrize("n_kv,kv_mul,t_len", [
    *((n, m, 1) for n, m in HEADS), (8, 4, 4), (2, 4, 4)])
def test_placement_invariance_is_bitwise_across_turns(n_kv, kv_mul, t_len):
    """The paged invariant (below, at pages of 8 in one turn) where a row
    takes two turns and ends inside one: the pool's pages permuted and the
    table remapped, bit for bit the same output."""
    q, k4, v4, pos, table, P = _edge_case(n_kv, kv_mul, t_len, seed=5,
                                          pos=[15, 127, 128, 141])
    base = _run(q, k4, v4, pos, table, P, kv_mul, t_len)
    perm = np.concatenate([[0], 1 + np.random.default_rng(5).permutation(
        P - 1)])
    k5, v5 = (a.reshape(2, P, PS, n_kv, 128) for a in (k4, v4))
    k5p, v5p = np.empty_like(k5), np.empty_like(v5)
    k5p[:, perm], v5p[:, perm] = k5, v5
    moved = _run(q, k5p.reshape(k4.shape), v5p.reshape(v4.shape), pos,
                 perm[table].astype(np.int32), P, kv_mul, t_len)
    np.testing.assert_array_equal(base, moved)


def _attention64(q, k, v, pos, kv_mul):
    """float64 grouped attention of one-query rows: q (B, n_q * hs), k / v
    (B, S, n_kv, hs), positions 0 .. pos[b]; operands taken as given."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    B, _, n_kv, hs = k.shape
    q = q.reshape(B, n_kv, kv_mul, hs)
    out = np.zeros(q.shape)
    for b in range(B):
        n = int(pos[b]) + 1
        s = np.einsum("hmd,shd->hms", q[b], k[b, :n]) / np.sqrt(hs)
        w = np.exp(s - s.max(axis=-1, keepdims=True))
        out[b] = np.einsum("hms,shd->hmd", w / w.sum(axis=-1, keepdims=True),
                           v[b, :n])
    return out.reshape(B, -1)


def _float64_distance(n_kv, kv_mul, scale_k):
    """(max, root mean square) of |kernel - float64 attention| over eight
    one-query rows around the page and turn edges, K scaled by ``scale_k``,
    and the max of the same attention with every product's operands rounded
    to bfloat16 (the control)."""
    import jax
    import jax.numpy as jnp

    pos = [141, 158, 127, 128, 100, 64, 159, 31]
    q, k4, v4, pos, table, P = _edge_case(n_kv, kv_mul, 1, seed=46, pos=pos)
    k4 = k4 * np.float32(scale_k)
    got = _run(q, k4, v4, pos, table, P, kv_mul, 1).astype(np.float64)[:, 0]
    gathered = lambda a: a[P + table].reshape(  # noqa: E731
        len(pos), -1, n_kv, 128)
    k_c, v_c = gathered(k4), gathered(v4)
    want = _attention64(q[:, 0], k_c, v_c, pos, kv_mul)
    bf = lambda a: np.asarray(jax.lax.reduce_precision(  # noqa: E731
        jnp.asarray(a), exponent_bits=8, mantissa_bits=7))
    control = _attention64(bf(q[:, 0]), bf(k_c), bf(v_c), pos, kv_mul)
    err = np.abs(got - want)
    return (float(err.max()), float(np.sqrt((err ** 2).mean())),
            float(np.abs(control - want).max()))


# (max, rms) of ``_float64_distance`` in interpret mode on the CPU: of the
# vector-unit fold this kernel had until PR 47 (a page a turn, ``jnp.sum(k *
# q)`` a query head: commit f17418d run on THIS file's inputs), and of the
# fold as PR 47 left it. Interpret mode multiplies in float32 whatever the
# pieces, so what differs HERE is the order of the float32 sums alone, 128
# positions a turn for 16: one to three units in the last place of an output
# either way. On the chip, where the piece products are exact and the MXU
# accumulates, the fold read UNDER the vector-unit fold on every shape
# timed (4.43e-7 for 6.16e-7 at OLMoE's, 7.08e-7 for 9.75e-7 at Mistral's:
# PERF.md section 7)
PARENT_DISTANCE = {(8, 4, 1.0): (1.971e-07, 2.905e-08),
                   (8, 4, 30.0): (1.172e-05, 6.379e-07),
                   (16, 1, 1.0): (2.415e-07, 2.982e-08),
                   (16, 1, 30.0): (9.020e-06, 6.007e-07)}
FOLD_DISTANCE = {(8, 4, 1.0): (3.840e-07, 3.083e-08),
                 (8, 4, 30.0): (1.226e-05, 7.642e-07),
                 (16, 1, 1.0): (3.535e-07, 3.099e-08),
                 (16, 1, 30.0): (1.614e-05, 7.902e-07)}


@pytest.mark.parametrize("scale_k", [1.0, 30.0])
@pytest.mark.parametrize("n_kv,kv_mul", [(8, 4), (16, 1)])
def test_the_fold_keeps_float32(n_kv, kv_mul, scale_k):
    """The fold at Mistral's and OLMoE's head counts against a float64
    attention, on standard-normal K and on K of thirty times the norm
    (scores to +-1,000: one winner a row, where a bf16 product moves the
    winner), beside the vector-unit fold's reading on the SAME inputs
    (``PARENT_DISTANCE`` against ``FOLD_DISTANCE``, both stated above): the
    root mean square within a quarter of it (1.03 to 1.06 times at
    standard-normal K, 1.2 to 1.3 at thirty times the norm), the max of the
    eight rows within twice (it goes either way with the rows drawn). The
    same attention with every product's operands rounded to bfloat16 is at
    least 100 times further: a fold that is quietly three passes, or one,
    fails here."""
    worst, rms, control = _float64_distance(n_kv, kv_mul, scale_k)
    was_worst, was_rms = PARENT_DISTANCE[n_kv, kv_mul, scale_k]
    assert worst <= 2 * was_worst and rms <= 1.35 * was_rms, (worst, rms)
    assert (worst, rms) == pytest.approx(
        FOLD_DISTANCE[n_kv, kv_mul, scale_k], rel=0.25)
    assert control >= 100 * worst, (control, worst)


@pytest.mark.parametrize("kv_mul,pos", [(1, [0, 5, 31]), (2, [7, 30, 16]),
                                        (4, [3, 3, 12])])
def test_paged_decode_matches_xla_gather(kv_mul, pos):
    """Decode (t=1) over scrambled physical pages: the page-table DMA
    walk reproduces the XLA gather path at the flash tolerance,
    last-partial-page offsets included (pos mid-page)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        paged_decode_attention_kernel

    L, P, ps, n_kv, hs = 2, 13, 8, 2, 128
    B, max_pages = 3, 4
    k4, v4 = _pool(L, P, ps, n_kv, hs, seed=kv_mul)
    table = _scrambled_table(B, max_pages, P)
    rng = np.random.default_rng(11 + kv_mul)
    q = rng.normal(size=(B, 1, n_kv * kv_mul * hs)).astype(np.float32)
    pos = np.asarray(pos, np.int32)

    got = paged_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(k4), jnp.asarray(v4), 1, pos,
        jnp.asarray(table), page_size=ps, n_pages=P, kv_mul=kv_mul,
        t_len=1, interpret=True)
    want = _xla_reference(q, k4, v4, 1, pos, table, ps, P, kv_mul, 1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("kv_mul,t_len", [(1, 3), (2, 4), (1, 8)])
def test_paged_verify_matches_xla_gather_incl_budget_edge(kv_mul, t_len):
    """The (B, K) speculative-verify window: stacked causal masks per
    query, with one row pinned at the BUDGET EDGE — its window extends
    past the virtual plane (the dead writes went to the scrap page;
    reads must still agree with the XLA mask semantics)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        paged_decode_attention_kernel

    L, P, ps, n_kv, hs = 2, 13, 8, 2, 128
    B, max_pages = 3, 4
    s_virt = max_pages * ps
    k4, v4 = _pool(L, P, ps, n_kv, hs, seed=t_len)
    table = _scrambled_table(B, max_pages, P)
    rng = np.random.default_rng(7 + t_len)
    q = rng.normal(size=(B, t_len, n_kv * kv_mul * hs)).astype(np.float32)
    # row 2 at the budget edge: pos + t_len - 1 >= s_virt
    pos = np.asarray([0, 9, s_virt - 2], np.int32)

    got = paged_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(k4), jnp.asarray(v4), 0, pos,
        jnp.asarray(table), page_size=ps, n_pages=P, kv_mul=kv_mul,
        t_len=t_len, interpret=True)
    want = _xla_reference(q, k4, v4, 0, pos, table, ps, P, kv_mul, t_len)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5)


def test_paged_kernel_bitwise_invariant_to_page_placement():
    """THE paged invariant: permuting the pool's physical pages (and
    remapping the table) reproduces bit-identical output — the kernel
    reads pages in logical order through the table, never by address."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        paged_decode_attention_kernel

    L, P, ps, n_kv, hs = 2, 11, 8, 2, 128
    B, max_pages = 2, 4
    k4, v4 = _pool(L, P, ps, n_kv, hs, seed=5)
    table = _scrambled_table(B, max_pages, P, seed=5)
    rng = np.random.default_rng(5)
    q = rng.normal(size=(B, 1, n_kv * hs)).astype(np.float32)
    pos = np.asarray([13, 30], np.int32)

    base = paged_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(k4), jnp.asarray(v4), 1, pos,
        jnp.asarray(table), page_size=ps, n_pages=P, kv_mul=1, t_len=1,
        interpret=True)
    # permute physical pages 1..P-1 (scrap page 0 stays put), remap table
    perm = np.concatenate([[0], 1 + rng.permutation(P - 1)])
    k5 = k4.reshape(L, P, ps, n_kv, hs)
    v5 = v4.reshape(L, P, ps, n_kv, hs)
    k5p, v5p = np.empty_like(k5), np.empty_like(v5)
    k5p[:, perm], v5p[:, perm] = k5, v5
    moved = paged_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(k5p.reshape(L * P, ps, n_kv, hs)),
        jnp.asarray(v5p.reshape(L * P, ps, n_kv, hs)), 1, pos,
        jnp.asarray(perm[table]), page_size=ps, n_pages=P, kv_mul=1,
        t_len=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(moved))


def test_paged_kernel_ignores_scrap_and_dead_pages():
    """Scrap-page content (dead writes from parked rows / budget-edge
    verify overflows), junk beyond a row's clock inside its LAST live
    page, and unmapped pool pages must all be invisible — poison them
    and require bit-identical output."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        paged_decode_attention_kernel

    L, P, ps, n_kv, hs = 1, 9, 8, 2, 128
    B, max_pages = 2, 3
    k4, v4 = _pool(L, P, ps, n_kv, hs, seed=3)
    table = np.asarray([[2, 5, 7], [4, 1, 3]], np.int32)
    rng = np.random.default_rng(3)
    q = rng.normal(size=(B, 1, n_kv * hs)).astype(np.float32)
    pos = np.asarray([11, 4], np.int32)  # mid-page clocks

    def run(k, v):
        return np.asarray(paged_decode_attention_kernel(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), 0, pos,
            jnp.asarray(table), page_size=ps, n_pages=P, kv_mul=1,
            t_len=1, interpret=True))

    clean = run(k4, v4)
    k4p, v4p = k4.copy(), v4.copy()
    k4p[0], v4p[0] = 1e9, -1e9              # the scrap page
    k4p[6], v4p[6] = 1e9, -1e9              # a page no table maps
    k4p[5, 4:], v4p[5, 4:] = 1e9, -1e9      # row 0's last live page
    #                                         (pos 11 = offset 3): junk
    #                                         beyond the clock
    k4p[1, 5:], v4p[1, 5:] = 1e9, -1e9      # row 1's last live page
    poisoned = run(k4p, v4p)
    np.testing.assert_array_equal(clean, poisoned)


def test_paged_kernel_q8_matches_xla_dequant_fallback():
    """Q8 pages: the in-kernel page-loop dequant must agree with the XLA
    fallback's gather-side dequant (identical codes*delta value map; the
    only deltas are the flash reduction reassociation)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import attention_core
    from distributed_llama_tpu.ops.pallas_paged_attention import \
        paged_decode_attention_kernel_q8
    from distributed_llama_tpu.ops.quants import QK, quantize_q80_jax

    L, P, ps, n_kv, hs, kv_mul = 2, 13, 8, 2, 128, 2
    B, max_pages = 3, 4
    nb = n_kv * hs // QK
    s_virt = max_pages * ps
    k4, v4 = _pool(L, P, ps, n_kv, hs, seed=9)
    kq, kd = quantize_q80_jax(k4.reshape(L * P, ps, n_kv * hs))
    vq, vd = quantize_q80_jax(v4.reshape(L * P, ps, n_kv * hs))
    kq4 = kq.reshape(L * P, ps, n_kv, hs)
    vq4 = vq.reshape(L * P, ps, n_kv, hs)
    table = _scrambled_table(B, max_pages, P, seed=9)
    rng = np.random.default_rng(9)
    q = rng.normal(size=(B, 1, n_kv * kv_mul * hs)).astype(np.float32)
    pos = np.asarray([0, 17, 31], np.int32)

    got = paged_decode_attention_kernel_q8(
        jnp.asarray(q), kq4, kd, vq4, vd, 1, pos, jnp.asarray(table),
        page_size=ps, n_pages=P, kv_mul=kv_mul, t_len=1, interpret=True)

    rows = jnp.asarray((1 * P + table).reshape(-1))

    def deq(codes, d):
        c = jnp.take(codes, rows, axis=0).reshape(B, s_virt, n_kv, hs)
        dd = jnp.take(d, rows, axis=0).reshape(B, s_virt, nb)
        y = (c.astype(jnp.float32).reshape(B, s_virt, nb, QK)
             * dd.astype(jnp.float32)[..., None])
        return y.reshape(B, s_virt, n_kv, hs)

    mask = jnp.arange(s_virt)[None, None, :] <= jnp.asarray(pos)[:, None,
                                                                 None]
    want = attention_core(hs, kv_mul,
                          jnp.asarray(q).reshape(B, 1, n_kv * kv_mul, hs),
                          deq(kq4, kd), deq(vq4, vd), mask)
    np.testing.assert_allclose(
        np.asarray(got).reshape(B, -1),
        np.asarray(want).reshape(B, -1), rtol=1e-5, atol=1e-5)


def test_supports_paged_gating():
    """The routing gate: lane-width head_size, bounded verify windows,
    VMEM scratch budget, and the q8 block-divisibility rule."""
    from distributed_llama_tpu.ops.pallas_attention import _VMEM_BUDGET
    from distributed_llama_tpu.ops.pallas_paged_attention import (
        _paged_scratch_bytes, supports_paged)

    assert supports_paged(16, 4, 128, 1)
    assert supports_paged(16, 4, 128, 8)
    assert not supports_paged(16, 4, 128, 9)       # window too deep
    assert not supports_paged(16, 4, 64, 1)        # sub-lane head size
    assert not supports_paged(16, 4, 128, 0)
    # a page plane too big for the double-buffered scratch budget
    huge_ps = _VMEM_BUDGET // (2 * 2 * 4 * 128 * 4) + 128
    assert not supports_paged(huge_ps, 4, 128, 1)
    assert _paged_scratch_bytes(huge_ps, 4, 128, 4, False) > _VMEM_BUDGET
    # q8: flattened (n_kv, hs) row must divide into 32-value blocks
    assert supports_paged(16, 4, 128, 1, itemsize=1, q8=True)
    assert not supports_paged(16, 3, 136, 1, itemsize=1, q8=True)


@pytest.mark.parametrize("kv_quant", ["f32", "q8"])
def test_paged_kernel_routing_single_chip(kv_quant, monkeypatch):
    """Fast-suite routing gate: the single-chip paged step with the
    Pallas route forced on agrees with the XLA gather route, f32 and q8
    — the tp x scheme grid variant below runs the same drive under
    shard_map (slow-marked; ci.sh runs it)."""
    _routing_case(1, "fused", kv_quant, monkeypatch)


@pytest.mark.parametrize("scheme", ["ref", "fused", "overlap"])
@pytest.mark.parametrize("tp", [1, 2, 4])
@pytest.mark.parametrize("kv_quant", ["f32", "q8"])
def test_paged_kernel_routing_tp_scheme_grid(tp, scheme, kv_quant,
                                             monkeypatch):
    """The integration gate over the tp x scheme x kv-quant grid: the
    sharded paged decode step with the Pallas route forced on
    (DLLAMA_ATTN_KERNEL=pallas, interpret mode off-TPU) agrees with the
    XLA gather route — same ONE maybe_paged_flash_decode gate the
    engine uses, exercised through make_sharded_forward_batch_paged
    under every collective scheme."""
    _routing_case(tp, scheme, kv_quant, monkeypatch)


def _routing_case(tp, scheme, kv_quant, monkeypatch):
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (init_cache_paged,
                                                    init_cache_paged_q8,
                                                    params_to_device)
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.models.synth import synth_params
    from distributed_llama_tpu.parallel import (
        make_mesh, make_sharded_forward_batch_paged, shard_cache_paged,
        shard_params)

    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    spec = TransformerSpec(dim=512, hidden_dim=256, n_layers=2, n_heads=4,
                           n_kv_heads=4, vocab_size=64, seq_len=32)
    assert spec.head_size == 128  # the kernel's lane-width gate
    tree = synth_params(spec, q40=False, seed=2, scale=0.2)
    ps, B = 8, 2
    max_pages = spec.seq_len // ps
    P = B * max_pages + 1
    table = _scrambled_table(B, max_pages, P, seed=tp)
    toks = np.asarray([3, 9], np.int32)
    pos = np.asarray([0, 0], np.int32)

    def drive(mode):
        monkeypatch.setenv("DLLAMA_ATTN_KERNEL", mode)
        if tp == 1:
            import functools

            from distributed_llama_tpu.models.llama import \
                forward_batch_paged

            params = params_to_device(tree)
            step = jax.jit(functools.partial(forward_batch_paged, spec,
                                             ps, kv_quant=kv_quant),
                           donate_argnums=1)
            cache = (init_cache_paged_q8(spec, P, ps) if kv_quant == "q8"
                     else init_cache_paged(spec, P, ps))
        else:
            mesh = make_mesh(tp=tp, devices=jax.devices()[:tp])
            params = shard_params(tree, mesh, scheme=scheme)
            step = make_sharded_forward_batch_paged(
                spec, mesh, ps, scheme=scheme, kv_quant=kv_quant)
            cache = shard_cache_paged(
                init_cache_paged_q8(spec, P, ps) if kv_quant == "q8"
                else init_cache_paged(spec, P, ps), mesh)
        out = []
        p = pos.copy()
        for step_i in range(3):
            lg, cache = step(params, cache, jnp.asarray(toks + step_i),
                             jnp.asarray(p), jnp.asarray(table))
            out.append(np.asarray(lg))
            p = p + 1
        return np.stack(out)

    xla = drive("xla")
    pallas = drive("pallas")
    np.testing.assert_allclose(pallas, xla, rtol=2e-5, atol=2e-5)


def test_f16_bits_widen_exactly_for_every_finite_pattern():
    """The q8 kernel reads its f16 deltas as raw bits (the chip takes no
    f16 kernel argument and has no f16 vectors) and widens them by hand:
    every finite f16 — zeros, subnormals, both signs — must come out the
    f32 numpy gives, bit for bit."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        _f16_bits_to_f32

    bits = np.arange(1 << 16, dtype=np.uint16)
    f16 = bits.view(np.float16)
    finite = np.isfinite(f16)
    got = np.asarray(_f16_bits_to_f32(jnp.asarray(bits.view(np.int16))))
    want = f16.astype(np.float32)
    assert np.array_equal(got[finite].view(np.uint32),
                          want[finite].view(np.uint32))


def test_q80_page_dequant_matches_the_shared_value_map():
    """_dequant_q80_page (the in-kernel decode, built from casts the chip's
    layout pass accepts) is bit-equal to quants.dequantize_q80_planes (the
    XLA routes' decode, whose (ps, nb, QK) reshape the chip refuses)."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.pallas_paged_attention import \
        _dequant_q80_page
    from distributed_llama_tpu.ops.quants import QK, dequantize_q80_planes

    rng = np.random.default_rng(2)
    for ps, n_kv, hs in ((16, 32, 128), (8, 2, 128), (4, 8, 256)):
        nb = n_kv * hs // QK
        codes = jnp.asarray(rng.integers(-127, 128, (ps, n_kv, hs)),
                            jnp.int8)
        d = (rng.normal(size=(ps, nb)) * 0.01).astype(np.float16)
        d[0, :6] = [0.0, -0.0, 6e-8, -3e-5, 6.1e-5, 65504.0]
        d = jnp.asarray(d)
        got = _dequant_q80_page(codes,
                                jax.lax.bitcast_convert_type(d, jnp.int16))
        assert np.array_equal(np.asarray(got),
                              np.asarray(dequantize_q80_planes(codes, d)))
