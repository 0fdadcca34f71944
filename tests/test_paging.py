"""Paged KV cache + radix prefix sharing (ISSUE 6).

Three layers of gates:

* pure-host units: PagePool refcount/free-list invariants, PrefixTree
  match/insert/LRU-eviction semantics, PagedAllocator policy;
* device parity: paged decode logits are BITWISE equal to the contiguous
  cache's, step by ragged step (the property the whole refactor rests on);
* engine behavior: token streams are invisible to paging across every
  scheduler configuration, prefix sharing fires on shared system prompts,
  pages return to the pool on retire AND on mid-prefill cancellation, and
  the memory-model formulas agree at equal capacity.
"""

import numpy as np
import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.runtime.paging import (PagePool, PagedAllocator,
                                                  PrefixTree, SCRAP_PAGE)

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=16)


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


# -- PagePool ---------------------------------------------------------------


def test_pool_alloc_order_refcounts_and_free():
    pool = PagePool(4)
    assert pool.n_free == 4
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (1, 2)  # lowest-first, deterministic
    assert pool.refcount(a) == 1
    pool.retain(a)
    pool.release(a)
    assert pool.refcount(a) == 1  # still held once
    assert pool.n_free == 2
    pool.release(a)
    assert pool.refcount(a) == 0 and pool.n_free == 3
    # freed page is reusable, and the scrap page id is never handed out
    got = {pool.alloc() for _ in range(3)}
    assert SCRAP_PAGE not in got and a in got
    assert pool.alloc() is None  # dry pool reports, not raises


def test_pool_release_unallocated_raises():
    pool = PagePool(2)
    with pytest.raises(ValueError):
        pool.release(1)
    with pytest.raises(ValueError):
        pool.retain(2)


def test_pool_free_list_stays_lowest_first_after_release():
    pool = PagePool(4)
    pages = [pool.alloc() for _ in range(4)]
    for pid in pages:           # release in ALLOC order: appends go high
        pool.release(pid)
    assert [pool.alloc() for _ in range(4)] == pages  # lowest-first again


# -- PrefixTree -------------------------------------------------------------


def _tree(n_pages=8, ps=4):
    pool = PagePool(n_pages)
    return pool, PrefixTree(pool, ps)


def test_tree_insert_match_full_pages_only():
    pool, tree = _tree()
    toks = [1, 5, 9, 14, 23, 40]  # 1.5 pages at ps=4
    pages = [pool.alloc(), pool.alloc()]
    assert tree.insert(toks, pages) == 1  # only the FULL first page adopted
    assert len(tree) == 1
    # match retains a ref for the caller
    got = tree.match(toks)
    assert got == [pages[0]]
    assert pool.refcount(pages[0]) == 3  # owner + tree + matcher
    # a diverging suffix still shares the aligned prefix
    assert tree.match([1, 5, 9, 14, 99, 98]) == [pages[0]]
    # a diverging FIRST page shares nothing
    assert tree.match([2, 5, 9, 14]) == []


def test_tree_two_level_match_and_recency_eviction():
    pool, tree = _tree()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    pages = [pool.alloc(), pool.alloc()]
    tree.insert(toks, pages)
    other = [9, 9, 9, 9]
    p_other = [pool.alloc()]
    tree.insert(other, p_other)
    tree.match(other)  # refresh: 'other' is now most-recent
    pool.release(p_other[0])  # drop the matcher's ref again
    for pid in pages + p_other:
        pool.release(pid)  # owners retire: tree-only refs remain
    # eviction unwinds the LRU chain leaf-first: the [1..8] branch goes
    # before the freshly-touched [9,9,9,9] leaf
    assert tree.evict_lru(2) == 2
    assert tree.match(toks) == []
    assert tree.match(other) == [p_other[0]]
    pool.release(p_other[0])


def test_eviction_order_is_strict_lru_per_touch_ticks():
    """ISSUE 12 satellite: LRU ordering is EXPLICIT — every node touch
    takes its own monotonic tick (no wall clock, no shared walk
    timestamp), so eviction among equal-refcount leaves is a strict
    total order determined by touch history alone, even for leaves
    published in the SAME insert batch."""
    pool, tree = _tree(n_pages=8, ps=2)
    pages = {}
    for toks in ([1, 1], [2, 2], [3, 3]):
        p = [pool.alloc()]
        tree.insert(toks, p)
        pool.release(p[0])
        pages[toks[0]] = p[0]
    # refresh in the order 2, 1: LRU is now 3 < 2 < 1
    for t in (2, 1):
        got = tree.match([t, t])
        pool.release(got[0])
    evicted = []
    for _ in range(3):
        assert tree.evict_lru(1) == 1
        for t, p in pages.items():
            if pool.refcount(p) == 0 and t not in evicted:
                evicted.append(t)
    assert evicted == [3, 2, 1]
    # ... and ticks are strictly per-node: one insert's nodes never tie
    pool2, tree2 = _tree(n_pages=8, ps=2)
    ps2 = [pool2.alloc(), pool2.alloc()]
    tree2.insert([5, 5, 6, 6], ps2)
    ticks = sorted(n.last_used for n in tree2.nodes())
    assert ticks[0] != ticks[1]


def test_tree_interior_nodes_not_evicted_under_live_children():
    pool, tree = _tree()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    pages = [pool.alloc(), pool.alloc()]
    tree.insert(toks, pages)
    pool.release(pages[0])  # owner keeps only the SECOND page pinned
    # page 2 still slot-held (refcount 2): only the leaf would be
    # evictable, but it is pinned -> nothing can be freed
    assert tree.evict_lru(2) == 0
    pool.release(pages[1])
    assert tree.evict_lru(2) == 2  # now leaf, then its parent
    assert len(tree) == 0


def test_tree_clear_releases_everything():
    pool, tree = _tree()
    pages = [pool.alloc(), pool.alloc()]
    tree.insert([1, 2, 3, 4, 5, 6, 7, 8], pages)
    for pid in pages:
        pool.release(pid)
    assert pool.n_free == 6
    assert tree.clear() == 2
    assert pool.n_free == 8 and len(tree) == 0


# -- PagedAllocator ---------------------------------------------------------


def test_allocator_evicts_idle_tree_pages_when_dry():
    a = PagedAllocator(2, page_size=4)
    p1 = a.alloc_page()
    a.insert_prefix([1, 2, 3, 4], [p1])
    a.release_pages([p1])      # slot retires; tree still holds p1
    p2 = a.alloc_page()
    assert a.n_free == 0
    p3 = a.alloc_page()        # dry -> evicts the idle tree leaf
    assert p3 == p1 and a.evictions == 1
    assert a.alloc_page() is None  # truly dry: everything slot-held
    for pid in (p2, p3):
        a.release_pages([pid])


def test_allocator_hit_miss_counters_and_pages_for():
    a = PagedAllocator(8, page_size=4)
    assert (a.pages_for(1), a.pages_for(4), a.pages_for(5)) == (1, 1, 2)
    # counting rides record_admission, NOT match_prefix: a dry-pool
    # requeue re-matches every retry and must not inflate the figures
    assert a.match_prefix([1, 2, 3, 4]) == []
    assert (a.prefix_hits, a.prefix_misses) == (0, 0)
    a.record_admission(0)
    p = a.alloc_page()
    a.insert_prefix([1, 2, 3, 4], [p])
    got = a.match_prefix([1, 2, 3, 4, 9])
    assert got == [p]
    a.record_admission(len(got))
    assert (a.prefix_hits, a.prefix_misses) == (1, 1)
    assert a.hit_rate == 0.5 and a.tokens_saved == 4
    a2 = PagedAllocator(8, page_size=4, prefix_share=False)
    assert a2.match_prefix([1, 2, 3, 4]) == []
    assert a2.insert_prefix([1, 2, 3, 4], [a2.alloc_page()]) == 0


def test_allocator_counters_match_metrics_under_dry_requeues(params):
    """The review-found double-count: with an oversubscribed pool forcing
    dry-pool requeues, the allocator's hit/saved figures (bench + CLI
    summary) must still equal the Prometheus counters — one count per
    STICKING admission, however many retries preceded it."""
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    sys_p = [1] + list(range(20, 28))
    reqs = [sys_p + [40 + i] for i in range(8)]
    reg = Registry()
    eng = ContinuousEngine(SPEC, params, slots=3, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, kv_pages=5, prefill_chunk=4,
                           metrics=reg)
    eng.run(reqs, steps=12)
    a = eng.allocator
    assert reg.get("dllama_prefix_hits_total").value == a.prefix_hits
    assert reg.get("dllama_prefill_tokens_saved_total").value \
        == a.tokens_saved
    assert a.prefix_hits + a.prefix_misses <= len(reqs)


# -- device parity: paged == contiguous, bitwise ----------------------------


@pytest.mark.parametrize("wtype", ["f32", "q40", "f16"])
def test_paged_decode_logits_bitwise_equal_contiguous(wtype):
    """The tentpole property: ragged decode through the page-pool cache
    (scattered physical pages, scrap-parked tails) produces BITWISE the
    contiguous cache's logits, step for step — gathered pages reproduce
    the virtual (B, S) plane exactly and the masked softmax never sees
    the junk beyond a row's clock. Pinned across weight codecs: the Q40
    kernel path and the f16 storage path feed the same cache machinery."""
    import functools

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (forward_batch_paged,
                                                    forward_batch_ragged,
                                                    init_cache_batch,
                                                    init_cache_paged,
                                                    params_to_device)

    tree = synth_params(SPEC, q40=(wtype == "q40"), seed=4, scale=0.3)
    if wtype == "f16":
        for k in ("wq", "wk", "wv", "wo", "w1", "w2", "w3", "wcls"):
            tree[k] = tree[k].astype(np.float16)
    params_dev = params_to_device(tree)
    ps, B = 4, 3
    max_pages = SPEC.seq_len // ps
    cache_c = init_cache_batch(SPEC, B)
    cache_p = init_cache_paged(SPEC, B * max_pages + 1, ps)
    # DELIBERATELY scrambled physical pages: row b's logical page j lives
    # at physical 1 + (j * B + b), so contiguous-looking reads would fail
    table = np.zeros((B, max_pages), np.int32)
    for b in range(B):
        table[b] = 1 + np.arange(max_pages) * B + b
    step_c = jax.jit(functools.partial(forward_batch_ragged, SPEC),
                     donate_argnums=1)
    step_p = jax.jit(functools.partial(forward_batch_paged, SPEC, ps),
                     donate_argnums=1)
    rng = np.random.default_rng(7)
    pos = np.zeros((B,), np.int32)
    for _ in range(12):
        toks = rng.integers(2, 100, (B,)).astype(np.int32)
        lg_c, cache_c = step_c(params_dev, cache_c, jnp.asarray(toks),
                               jnp.asarray(pos))
        lg_p, cache_p = step_p(params_dev, cache_p, jnp.asarray(toks),
                               jnp.asarray(pos), jnp.asarray(table))
        np.testing.assert_array_equal(np.asarray(lg_c), np.asarray(lg_p))
        pos = np.minimum(pos + rng.integers(0, 2, (B,)),
                         SPEC.seq_len - 1).astype(np.int32)


def test_gather_scatter_pages_round_trip(params):
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (gather_pages,
                                                    init_cache_paged,
                                                    scatter_pages)

    ps = 4
    max_pages = SPEC.seq_len // ps
    cache = init_cache_paged(SPEC, max_pages + 1, ps)
    rng = np.random.default_rng(0)
    cache = cache._replace(
        k=jnp.asarray(rng.standard_normal(cache.k.shape), jnp.float32),
        v=jnp.asarray(rng.standard_normal(cache.v.shape), jnp.float32))
    table = jnp.asarray(1 + np.arange(max_pages, dtype=np.int32)[::-1])
    seq = gather_pages(cache, table, ps)
    assert seq.k.shape == (SPEC.n_layers, SPEC.seq_len, SPEC.n_kv_heads,
                           SPEC.head_size)
    back = scatter_pages(cache, seq, table, ps)
    np.testing.assert_array_equal(np.asarray(back.k), np.asarray(cache.k))
    np.testing.assert_array_equal(np.asarray(back.v), np.asarray(cache.v))


def _pool_of(kind: str, n_pages: int, ps: int, seed: int):
    """A pool of ``kind`` with random content, and the gather / scatter
    that serve it: f32 K / V, q8 codes and deltas, ONE latent plane."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.models.latent import LatentCache

    rng = np.random.default_rng(seed)
    L, kv, hs = 2, SPEC.n_kv_heads, 32
    if kind == "q8":
        codes, deltas = (L, n_pages, ps, kv, hs), (L, n_pages, ps, kv)
        pool = llama.PagedKVQ8(*(
            jnp.asarray(rng.integers(-127, 128, codes), jnp.int8) if i % 2 == 0
            else jnp.asarray(rng.uniform(0.01, 0.1, deltas), jnp.float16)
            for i in range(4)))
        return pool, llama.gather_pages_q8, llama.scatter_pages_q8
    shape = (L, n_pages, ps, 24) if kind == "latent" else (L, n_pages, ps,
                                                          kv, hs)
    planes = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
              for _ in range(1 if kind == "latent" else 2)]
    pool = LatentCache(*planes) if kind == "latent" else llama.KVCache(
        *planes)
    return pool, llama.gather_pages, llama.scatter_pages


def _seq_like(seq, seed: int):
    """A sequence cache of ``seq``'s shape with other random content."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return type(seq)(*(jnp.asarray(rng.standard_normal(p.shape), p.dtype)
                       for p in seq))


def _planes_equal(a, b, pages=None):
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        if pages is not None:
            x, y = x[:, pages], y[:, pages]
        np.testing.assert_array_equal(x, y)


POOL_KINDS = ["f32", "q8", "latent"]


@pytest.mark.parametrize("kind", POOL_KINDS)
def test_gather_scatter_defaults_move_the_whole_table(kind):
    """With no range the two are what they were before they took one: the
    take of the table's pages, and the set of all of them."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops.quants import (dequantize_q80_planes,
                                                  quantize_q80_jax)

    ps, n = 4, 6
    pool, gather, scatter = _pool_of(kind, n + 1, ps, seed=1)
    table = jnp.asarray(1 + np.arange(n, dtype=np.int32)[::-1])
    seq = gather(pool, table, ps)

    def taken(plane):
        got = jnp.take(plane, table, axis=1)
        return got.reshape(got.shape[0], n * ps, *got.shape[3:])

    if kind == "q8":
        want = [dequantize_q80_planes(taken(pool.kq), taken(pool.kd)),
                dequantize_q80_planes(taken(pool.vq), taken(pool.vd))]
    else:
        want = [taken(plane) for plane in pool]
    _planes_equal(seq, want)
    new = _seq_like(seq, seed=2)
    back = scatter(pool, new, table, ps)

    def paged(plane):
        return plane.reshape(plane.shape[0], n, ps, *plane.shape[2:])

    if kind == "q8":
        want = []
        for plane in new:
            qs, d = quantize_q80_jax(plane.reshape(*plane.shape[:2], -1))
            want += [paged(qs.reshape(plane.shape)), paged(d)]
    else:
        want = [paged(plane) for plane in new]
    _planes_equal(back, [p.at[:, table].set(w) for p, w in zip(pool, want)])


@pytest.mark.parametrize("kind", POOL_KINDS)
def test_ranged_scatter_writes_its_pages_only(kind):
    """Pages ``[start, stop)`` of the table read as the whole-table scatter
    leaves them; every other page of the pool keeps its bytes."""
    import jax.numpy as jnp

    ps, n = 4, 6
    pool, gather, scatter = _pool_of(kind, n + 3, ps, seed=3)
    table = np.asarray([5, 2, 7, 1, 8, 3], np.int32)
    seq = _seq_like(gather(pool, jnp.asarray(table), ps), seed=4)
    whole = scatter(pool, seq, jnp.asarray(table), ps)
    for lo, hi in ((0, 2), (1, 4), (3, 6), (2, 2)):
        got = scatter(pool, seq, jnp.asarray(table), ps,
                      start=jnp.int32(lo), stop=jnp.int32(hi))
        inside = table[lo:hi]
        outside = np.setdiff1d(np.arange(n + 3), inside)
        _planes_equal(got, whole, pages=inside)
        _planes_equal(got, pool, pages=outside)


@pytest.mark.parametrize("kind", POOL_KINDS)
def test_ranged_gather_brings_the_pages_below_its_stop(kind):
    """Positions under ``stop`` pages read as the whole-table gather's; the
    rest of ``into`` stays as it was."""
    import jax.numpy as jnp

    ps, n = 4, 6
    pool, gather, _ = _pool_of(kind, n + 3, ps, seed=5)
    table = jnp.asarray([5, 2, 7, 1, 8, 3], jnp.int32)
    whole = gather(pool, table, ps)
    junk = _seq_like(whole, seed=6)
    for stop in (0, 1, 3, 6):
        got = gather(pool, table, ps, into=junk, stop=jnp.int32(stop))
        for g, w, j in zip(got, whole, junk):
            g = np.asarray(g)
            np.testing.assert_array_equal(g[:, :stop * ps],
                                          np.asarray(w)[:, :stop * ps])
            np.testing.assert_array_equal(g[:, stop * ps:],
                                          np.asarray(j)[:, stop * ps:])


# -- engine behavior --------------------------------------------------------


def _run(params, reqs, steps, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = ContinuousEngine(SPEC, params, slots=kw.pop("slots", 2),
                           temperature=kw.pop("temperature", 0.0),
                           topp=0.9, seed=3, **kw)
    outs, stats = eng.run(reqs, steps)
    return eng, outs, stats


REQS = [[1, 5, 9], [1, 22], [1, 7, 33, 2], [1, 60], [1, 90, 14]]


@pytest.mark.parametrize("kw", [
    dict(page_size=4),
    dict(page_size=2, block_steps=4),
    dict(page_size=4, prefill_chunk=2),
    dict(page_size=4, block_steps=3, prefill_chunk=2),
    dict(page_size=4, temperature=0.9),
])
def test_paged_streams_match_contiguous(params, kw):
    """Paging must be invisible in every request's token stream — across
    fused chains, admission prefill, and sampled decoding."""
    temp = kw.get("temperature", 0.0)
    _, ref, _ = _run(params, REQS, 8, temperature=temp)
    _, got, _ = _run(params, REQS, 8, **dict(kw))
    assert got == ref


@pytest.mark.parametrize("scheme", ["ref", "fused", "overlap"])
def test_paged_streams_match_over_tp_mesh(params, scheme, monkeypatch):
    """Paged decode under ALL THREE tp collective schemes: attention runs
    before the layer tail, so the scheme's schedule (ref gathers, fused
    combines, overlap's ring + deferred gather carry) never sees the
    page table — streams match the single-chip engine."""
    from distributed_llama_tpu.parallel import make_mesh

    _, ref, _ = _run(params, REQS[:3], 8)
    monkeypatch.setenv("DLLAMA_TP_SCHEME", scheme)
    _, got, _ = _run(params, REQS[:3], 8, mesh=make_mesh(tp=2),
                     page_size=4, prefill_chunk=2, block_steps=3)
    assert got == ref


def _admission_engine(params, kv_quant: str, hold_once: bool, **kw):
    """A one-slot paged engine that snapshots each request's live pool
    positions as it retires (a page freed is soon another request's)."""
    import jax

    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = ContinuousEngine(SPEC, params, slots=1, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, prefill_chunk=4,
                           kv_quant=kv_quant, **kw)
    if hold_once:
        fired = []

        def hold(slot):     # park at the first boundary, then resume
            fired.append(slot)
            return len(fired) == 1

        eng.prefill_hold = hold
    eng.live = []
    retire = eng._retire

    def snapshot(s, quiet):
        pages = np.asarray(s.pages, np.int32)
        eng.live.append([
            np.asarray(p)[:, pages].reshape(p.shape[0], -1,
                                            *p.shape[3:])[:, :s.pos]
            for p in jax.tree.leaves(eng.cache)])
        return retire(s, quiet)

    eng._retire = snapshot
    return eng


def _as_before_the_ranges(eng):
    """The admission the ranges replaced: a scratch of its own, the slot's
    whole table gathered into it and scattered from it, and a q8 pool's
    pages below the start parked on the scrap page for the scatter."""
    gather, scatter, prefill = (eng._gather_pages, eng._scatter_pages,
                                eng._maybe_prefill_slot)

    def whole_gather(cache, table, into=None, stop=None):
        return gather(cache, table)

    def whole_scatter(cache, seq, table, start=None, stop=None):
        if eng.kv_quant == "q8":
            table = table.at[:start].set(SCRAP_PAGE)
        return scatter(cache, seq, table)

    def fresh_scratch(slot_index, s):
        eng._admit_scratch = None
        return prefill(slot_index, s)

    eng._gather_pages, eng._scatter_pages = whole_gather, whole_scatter
    eng._maybe_prefill_slot = fresh_scratch


def _serve(eng, reqs, steps):
    from distributed_llama_tpu.runtime.continuous import Request

    sent = [Request(tokens=list(r), steps=steps) for r in reqs]
    for r in sent:
        eng.submit(r)
    while eng.step_many(1, quiet=True):
        pass
    return [r.out for r in sent]


_SYS = [1] + list(range(20, 28))      # 9 tokens: two full pages at 4 a page
_LONG = [1] + [5 + (i % 20) for i in range(12)]     # 12 positions: 3 chunks


@pytest.mark.parametrize("kv_quant,reqs,hold,gathers,moved", [
    # nothing shared: no gather, and the pages the prompts cover scattered
    ("f32", [[1, 5, 9, 14, 3, 8, 2], _LONG, [1, 60, 61, 62]], False, 0,
     2 + 3 + 1),
    ("q8", [[1, 5, 9, 14, 3, 8, 2], _LONG], False, 0, 2 + 3),
    # the second prompt finds the first's two pages: they are gathered, and
    # the page its own chunk filled scattered
    ("f32", [_SYS + [40, 41], _SYS + [50, 51]], False, 1, 3 + 2 + 1),
    ("q8", [_SYS + [40, 41], _SYS + [50, 51]], False, 1, 3 + 2 + 1),
    # parked after its first chunk and resumed: the page it already holds
    # is gathered again (the scratch may have served another slot)
    ("f32", [_LONG], True, 1, 1 + 1 + 2),
], ids=["unshared", "unshared-q8", "shared", "shared-q8", "resumed"])
def test_admission_moves_the_pages_its_prompt_covers(params, kv_quant, reqs,
                                                     hold, gathers, moved):
    """Over a kept scratch full of large finite junk (no NaN: a masked
    position weighs 0, and 0 x NaN is NaN) the streams and every live pool
    position are what the whole-table admission leaves."""
    import jax
    import jax.numpy as jnp

    ref = _admission_engine(params, kv_quant, hold)
    _as_before_the_ranges(ref)
    want = _serve(ref, reqs, 16)
    eng = _admission_engine(params, kv_quant, hold)
    junk = jax.tree.map(lambda p: np.full_like(p, 3e4), eng._gather_pages(
        eng.cache, jnp.zeros((eng._max_pages,), jnp.int32)))
    prefill = eng._maybe_prefill_slot

    def junk_scratch(slot_index, s):    # as if it had served another slot
        eng._admit_scratch = jax.tree.map(jnp.asarray, junk)
        return prefill(slot_index, s)

    eng._maybe_prefill_slot = junk_scratch
    assert _serve(eng, reqs, 16) == want
    assert len(eng.live) == len(ref.live) == len(reqs)
    for got, exp in zip(eng.live, ref.live):
        for g, e in zip(got, exp):
            np.testing.assert_array_equal(g, e)
    st = eng.stats
    assert st.admit_prefills == len(reqs) + hold
    assert (st.admit_gathers, st.admit_pages_moved) == (gathers, moved)
    assert st.admit_pages_table == 2 * eng._max_pages * st.admit_prefills
    assert (f"{moved} of {st.admit_pages_table} table pages moved"
            in st.admission_clause)
    assert f"{gathers} admissions gathered" in st.admission_clause
    assert ref.stats.admit_gathers == gathers     # the same admissions
    assert eng.audit_pages() == []


def test_admission_scratch_is_kept_while_rows_remain(params):
    """Admissions share ONE scratch sequence while the engine has rows (a
    burst allocates one, not one each), and an idle engine holds none."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, prefill_chunk=4)
    gather, fills = eng._gather_pages, []

    def counted(cache, table, into=None, stop=None):
        fills.append(into is None)
        return gather(cache, table, into=into, stop=stop)

    eng._gather_pages = counted
    reqs = [Request(tokens=list(r), steps=n)    # the second ends first
            for r, n in (([1, 5, 9, 14, 3, 8, 2], 16),
                         ([1, 60, 61, 62, 63, 64], 9), (_LONG, 16))]
    for r in reqs:
        eng.submit(r)
    eng.step_many(1, quiet=True)        # two admissions in one round
    assert fills == [True] and eng._admit_scratch is not None
    while eng.step_many(1, quiet=True):
        pass
    assert fills == [True]              # the third took the kept one
    assert eng._admit_scratch is None and eng.stats.admit_prefills == 3
    eng.submit(Request(tokens=[1, 70, 71, 72, 73, 74, 75], steps=16))
    while eng.step_many(1, quiet=True):
        pass
    assert fills == [True, True]        # from idle: zeros again


def test_fail_all_clears_tree_and_frees_pool(params):
    """fail_all tears down the radix tree with the rest of the engine
    state: a post-fault loop restarts from a fully-free pool."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, prefill_chunk=4)
    eng.run([[1] + list(range(20, 28))], 12)  # publishes prompt pages
    assert len(eng.allocator.tree) > 0
    eng.submit(Request(tokens=[1, 5], steps=4))
    eng._admit()
    eng.fail_all("fault")
    assert len(eng.allocator.tree) == 0
    assert eng.allocator.n_free == eng.allocator.n_pages


def test_kv_pages_without_page_size_rejected(params):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    with pytest.raises(ValueError, match="kv_pages requires page_size"):
        ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                         seed=3, kv_pages=8)


def test_paged_rejects_sp_mesh_and_ragged_page_size(params):
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    with pytest.raises(ValueError, match="sp=1"):
        ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                         seed=3, mesh=make_mesh(sp=2, tp=2), page_size=4)
    with pytest.raises(ValueError, match="must divide"):
        ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                         seed=3, page_size=5)


def test_shared_system_prompt_hits_prefix_tree(params):
    """The serving win: same-system-prompt requests map shared pages
    (copy-free), skip their prefill, and still stream identically."""
    sys_p = [1] + list(range(20, 28))  # 2 full pages at ps=4
    reqs = [sys_p + [40 + i] for i in range(5)]
    _, ref, _ = _run(params, reqs, 12)
    eng, got, _ = _run(params, reqs, 12, page_size=4, prefill_chunk=4)
    assert got == ref
    a = eng.allocator
    assert a.prefix_hits >= 3  # all but the concurrently-admitted first two
    assert a.tokens_saved >= 3 * 8
    assert a.hit_rate > 0


def test_oversubscribed_pool_more_slots_at_equal_pages(params):
    """4 slots over a 2-sequence page budget: the concurrency lever. All
    requests complete, streams match, and the pool never leaks."""
    sys_p = [1] + list(range(20, 28))
    reqs = [sys_p + [40 + i] for i in range(6)]
    _, ref, _ = _run(params, reqs, 12)
    eng, got, st = _run(params, reqs, 12, slots=4, page_size=4, kv_pages=8,
                        prefill_chunk=4)
    assert got == ref
    assert st.max_active > 2  # actually used the extra slots
    a = eng.allocator
    # retired slots dropped their refs: only tree-held pages stay out
    assert a.n_free + len(a.tree) == a.n_pages


def test_pool_capacity_clamps_budget_like_seq_len(params):
    """A request whose step budget exceeds what the pool can ever hold is
    clamped to the pool's positions at admission — the same contract as
    the existing seq_len clamp — instead of being admitted and then
    killed mid-stream by the deadlock breaker."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, kv_pages=2)  # 8 positions
    big = eng.submit(Request(tokens=[1, 5, 9], steps=14))
    ok = eng.submit(Request(tokens=[1, 5], steps=4))  # one page: no clash
    while eng.step_once():
        pass
    assert big.done.is_set() and big.error is None
    assert len(big.out) <= 8  # ran to the pool edge, no further
    assert ok.done.is_set() and ok.error is None and ok.out
    # solo prefix: the clamped stream equals a solo run at the clamped
    # budget (pausing/clamping stayed stream-invisible)
    solo, _ = ContinuousEngine(SPEC, params, slots=1, temperature=0.0,
                               topp=0.9, seed=3).run([[1, 5, 9]], 8)
    assert big.out == solo[0]
    a = eng.allocator
    assert a.n_free + len(a.tree) == 2


def test_dry_pool_requeues_and_completes_fcfs(params):
    """Admissions the pool cannot serve yet wait at the queue head and
    complete once running requests retire — no deadlock, no failure.
    3 requests x 2 pages each (budget 8 at ps=4) through a 4-page pool:
    the third waits for a retirement, then runs."""
    reqs = [[1, 5, 9], [1, 22, 7], [1, 60, 3]]
    _, ref, _ = _run(params, reqs, 8)
    eng, got, _ = _run(params, reqs, 8, slots=3, page_size=4, kv_pages=4,
                       prefix_share=False)
    assert got == ref
    assert eng.allocator.n_free == 4  # nothing leaked, tree empty
    assert len(eng.allocator.tree) == 0


def test_starved_slot_pauses_until_pages_free(params):
    """Mid-decode growth beyond the pool pauses the starved slot (frozen
    through the step, stream-invisible) until a retirement frees pages;
    only a true all-slots deadlock fails a request — the youngest."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    # staggered budgets: req0 needs 2 pages total, req1 needs 3; pool of
    # 4 forces req1 to pause at its third page until req0 retires
    _, ref, _ = _run(params, [[1, 5, 9]], 12, slots=1, prefix_share=False,
                     page_size=4, kv_pages=4)
    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, kv_pages=4,
                           prefix_share=False)
    short = eng.submit(Request(tokens=[1, 22, 7], steps=6))
    long = eng.submit(Request(tokens=[1, 5, 9], steps=12))
    while eng.step_once():
        pass
    assert short.error is None and long.error is None
    assert long.out == ref[0]  # pausing never showed up in the stream
    assert eng.allocator.n_free == 4

    # true deadlock: both slots starved at once -> youngest fails, the
    # older survivor completes on the freed pages
    eng2 = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                            topp=0.9, seed=3, page_size=4, kv_pages=2,
                            prefix_share=False)
    a = eng2.submit(Request(tokens=[1, 5], steps=8))
    b = eng2.submit(Request(tokens=[1, 7], steps=8))
    while eng2.step_once():
        pass
    assert a.error is None and a.out
    assert b.error is not None and "exhausted" in b.error
    assert eng2.allocator.n_free == 2


def test_cancelled_prefill_returns_pages_to_pool(params):
    """ISSUE 6 satellite: a request whose consumer vanishes DURING
    admission prefill must hand its pages back immediately (slot refs
    dropped at the admission check, not at the next chain boundary)."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, prefill_chunk=4,
                           block_steps=4)
    req = Request(tokens=[1] + list(range(30, 38)), steps=12)
    # the consumer disconnects while prefill echoes stream out — the
    # closest deterministic stand-in for a socket dying mid-prefill
    req.on_token = lambda t: setattr(req, "cancelled", True)
    eng.submit(req)
    live = eng.submit(Request(tokens=[1, 5], steps=6))
    while eng.step_many(4):
        pass
    assert req.done.is_set() and req.cancelled
    assert live.done.is_set() and live.error is None
    a = eng.allocator
    # every page is back (free) or idle-shared (tree, refcount 1) — the
    # cancelled slot pinned nothing past its retirement
    assert a.n_free + len(a.tree) == a.n_pages
    for s in eng._pool:
        assert s.pages == []


def test_paged_engine_survives_reuse_with_warm_tree(params):
    """A second run against the same engine matches the first (prefix
    sharing from the warm tree is stream-invisible)."""
    sys_p = [1] + list(range(20, 28))
    reqs = [sys_p + [40 + i] for i in range(4)]
    eng, first, _ = _run(params, reqs, 12, page_size=4, prefill_chunk=4)
    second, _ = eng.run(reqs, 12)
    assert second == first
    assert eng.allocator.prefix_hits > 0


# -- memory model -----------------------------------------------------------


def test_page_pool_bytes_equal_contiguous_at_default_sizing():
    from distributed_llama_tpu.analysis.memory_model import (
        DEFAULT_PAGE_SIZE, default_kv_pages, kv_cache_device_bytes,
        kv_page_pool_bytes)
    from distributed_llama_tpu.analysis.shardcheck import (
        check_paged_equivalence, model_spec)

    for model in ("7b", "13b", "70b"):
        for tp in (1, 2, 4, 8):
            spec = model_spec(model, "q40")
            contig = kv_cache_device_bytes(spec, tp, batch=4)
            paged = kv_page_pool_bytes(
                spec, tp, default_kv_pages(spec, 4, DEFAULT_PAGE_SIZE),
                DEFAULT_PAGE_SIZE, include_scrap=False)
            assert paged == contig, (model, tp)
            # the scrap page is charged when the engine allocates it
            with_scrap = kv_page_pool_bytes(
                spec, tp, default_kv_pages(spec, 4, DEFAULT_PAGE_SIZE),
                DEFAULT_PAGE_SIZE)
            page_bytes = (2 * spec.n_layers * DEFAULT_PAGE_SIZE
                          * (spec.n_kv_heads // tp) * spec.head_size * 4)
            assert with_scrap - contig == page_bytes
            assert check_paged_equivalence(spec, tp, "cfg", contig // 4) \
                == []


def test_shardcheck_flags_paged_formula_drift():
    from distributed_llama_tpu.analysis.shardcheck import (
        check_paged_equivalence, model_spec)

    spec = model_spec("7b", "q40")
    findings = check_paged_equivalence(spec, 1, "cfg", 12345)  # wrong bytes
    assert findings and findings[0].rule == "KV-PAGED"
    ragged = model_spec("7b", "q40")
    ragged = type(ragged)(**{**ragged.__dict__, "seq_len": 2050})
    findings = check_paged_equivalence(ragged, 1, "cfg", 0)
    assert findings and "not a multiple" in findings[0].detail


def test_device_footprint_paged_kv_term():
    from distributed_llama_tpu.analysis.memory_model import (
        default_kv_pages, device_footprint)
    from distributed_llama_tpu.analysis.shardcheck import model_spec

    spec = model_spec("7b", "q40")
    contig = device_footprint(spec, 4, "fused", batch=2)
    paged = device_footprint(spec, 4, "fused", batch=2, kv_page_size=16)
    page_bytes = (2 * spec.n_layers * 16 * (spec.n_kv_heads // 4)
                  * spec.head_size * 4)
    assert paged.kv_cache_bytes == contig.kv_cache_bytes + page_bytes
    half = device_footprint(spec, 4, "fused", batch=2, kv_page_size=16,
                            kv_pages=default_kv_pages(spec, 1, 16))
    assert half.kv_cache_bytes < contig.kv_cache_bytes
