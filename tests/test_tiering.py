"""Hierarchical KV tiering (ISSUE 12): HBM ⇄ host RAM ⇄ disk.

Four layers of gates:

* pure-host units: HostPagePool free-list/ownership invariants,
  DiskPageStore CRC'd store/load round-trips, strict-LRU demotion order;
* tier-invariant properties: demote→promote round-trips are BYTE-exact
  (f32 bitwise, Q8 code-exact — the payload is the page wire layout, no
  re-encode anywhere on the path), a CRC-damaged disk page re-derives
  via prefill instead of crashing, and the three-tier audit closes the
  ledger after arbitrary churn;
* scheduler semantics: admission PAUSEs until the async promotion upload
  lands (pages-starved semantics; pinned deterministically by gating the
  PageUploader), and streams are bitwise invisible to tiering;
* the capacity claim: at a working set ~10x the HBM pool, prefix-hit
  prefill savings hold at the all-HBM ceiling while the drop-on-evict
  baseline recomputes everything.
"""

import os
import threading

import numpy as np
import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.runtime.paging import (DiskPageStore,
                                                  HostPagePool,
                                                  PagedAllocator,
                                                  TIER_DISK, TIER_HBM,
                                                  TIER_HOST)

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=32)
PS = 4


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


def _engine(params, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    base = dict(slots=2, temperature=0.0, topp=0.9, seed=3,
                prefill_chunk=PS, page_size=PS)
    base.update(kw)
    eng = ContinuousEngine(SPEC, params, **base)
    _ENGINES.append(eng)
    return eng


_ENGINES = []


@pytest.fixture(autouse=True)
def _engines_closed():
    """A tiered engine owns a PageUploader thread: stop each one this test
    made, so none outlives it in the worker."""
    yield
    while _ENGINES:
        _ENGINES.pop().close()


def _waves(n_prefix, tails=(3, 9)):
    """Two passes over n_prefix distinct 2-page shared prefixes: pass 1
    publishes, pass 2 revisits every one (by then cold prefixes have
    spilled — or died, on a drop-on-evict pool)."""
    return [[[1] + [(7 * i + j) % 90 + 5 for j in range(2 * PS)]
             + [t + i % 40] for i in range(n_prefix)] for t in tails]


# -- HostPagePool -----------------------------------------------------------


def test_host_pool_ids_lowest_first_and_accounting():
    pool = HostPagePool(3)
    a = pool.store(("a",))
    b = pool.store(("b",))
    assert (a, b) == (0, 1)
    assert pool.load(a) == ("a",)
    assert pool.free(a) == ("a",)
    assert pool.store(("c",)) == 0  # freed id reused, lowest-first
    pool.store(("d",))
    assert pool.store(("overflow",)) is None  # full reports, not raises
    assert pool.n_free == 0 and pool.n_live == 3
    assert pool.audit() == []


# -- DiskPageStore ----------------------------------------------------------


def _payload(seed):
    rng = np.random.RandomState(seed)
    return (rng.randn(2, PS, 2, 16).astype(np.float32),
            rng.randn(2, PS, 2, 16).astype(np.float32))


def test_disk_store_round_trip_bitwise(tmp_path):
    store = DiskPageStore(str(tmp_path))
    p = _payload(0)
    ref = store.store(p)
    got = store.load(ref)
    assert all(np.array_equal(a, b) and a.dtype == b.dtype
               for a, b in zip(got, p))
    assert store.audit() == []
    store.free(ref)
    assert not store.live(ref)


def test_disk_store_crc_corruption_loads_none(tmp_path):
    store = DiskPageStore(str(tmp_path))
    ref = store.store(_payload(1))
    path, off = ref[0], ref[1]
    with open(path, "r+b") as fh:
        fh.seek(off + 5)
        byte = fh.read(1)
        fh.seek(off + 5)
        fh.write(bytes([byte[0] ^ 0xFF]))
    assert store.load(ref) is None  # damage -> None, never wrong bytes
    assert store.crc_failures == 1
    assert store.audit() != []  # the audit's read-back flags it too


def test_disk_store_budget_and_dead_segment_reclaim(tmp_path):
    store = DiskPageStore(str(tmp_path), budget_bytes=3000)
    small = (np.zeros(256, np.float32),)  # 1024 B records
    r1, r2 = store.store(small), store.store(small)
    assert store.store(small) is None  # budget: 3072 > 3000
    store.free(r1)
    r3 = store.store(small)
    assert r3 is not None
    # a fully-dead sealed segment unlinks (bound append-only growth):
    # rotate to a fresh segment, then kill every record in the old one
    store.SEGMENT_BYTES = 1  # every store from now on seals + rotates
    store.free(r3)
    r4 = store.store(small)
    assert r4 is not None and r4[0] != r2[0]  # rotated
    seg1 = r2[0]
    store.free(r2)  # last live record of segment 1 dies
    assert not os.path.exists(seg1)
    assert store.live(r4) and store.audit() == []


# -- strict LRU -------------------------------------------------------------


def test_demotion_order_is_strict_lru():
    """Per-touch monotonic ticks: demotion victims leave in exact
    recency order even when published in one insert batch."""
    alloc = PagedAllocator(8, 2, host_pages=8)
    alloc.bind_device_io(lambda pid: (np.full((1,), pid, np.float32),))
    pages = [alloc.alloc_page() for _ in range(3)]
    alloc.insert_prefix([1, 2, 3, 4, 5, 6], pages)  # 3 nodes, one insert
    alloc.release_pages(pages)
    # touch the MIDDLE window's chain only: [1,2] then [3,4] refresh
    got = alloc.match_prefix([1, 2, 3, 4])
    alloc.release_pages(got)
    order = []
    orig_store = alloc.host.store

    def spy(payload):
        order.append(int(payload[0][0]))
        return orig_store(payload)

    alloc.host.store = spy
    alloc.demote_cold(3)
    # LRU = the untouched deepest window first (oldest tick), then the
    # refreshed chain bottom-up by touch order
    assert order == [pages[2], pages[0], pages[1]]
    assert alloc.audit([]) == []


# -- tier-invariant properties ----------------------------------------------


def test_demote_promote_round_trip_bitwise_f32():
    """HBM -> host -> disk -> HBM moves the exact page bytes: the staged
    promotion payload is bit-identical to what demotion fetched."""
    alloc = PagedAllocator(2, 2, host_pages=1)
    payloads = {}

    def fetch(pid):
        payloads[pid] = _payload(pid)
        return payloads[pid]

    alloc.bind_device_io(fetch)
    pages = [alloc.alloc_page(), alloc.alloc_page()]
    alloc.insert_prefix([1, 2, 3, 4], pages)
    alloc.release_pages(pages)
    alloc.demote_cold(2)  # both out of HBM; host holds 1, 1 dropped
    assert alloc.tier_page_counts()[TIER_HOST] == 1
    got = alloc.match_prefix([1, 2, 3, 4])
    assert len(got) >= 1
    jobs = alloc.take_staged_promotions()
    for job in jobs:
        orig = payloads[pages[0]]
        assert all(np.array_equal(a, b) for a, b in zip(job.staged, orig))
        alloc.promotion_applied(job)
    alloc.release_pages(got)
    assert alloc.audit([]) == []


def test_demote_promote_round_trip_bitwise_through_disk(tmp_path):
    alloc = PagedAllocator(2, 2, disk_dir=str(tmp_path))
    payloads = {}

    def fetch(pid):
        payloads[pid] = _payload(100 + pid)
        return payloads[pid]

    alloc.bind_device_io(fetch)
    pages = [alloc.alloc_page()]
    alloc.insert_prefix([1, 2], pages)
    alloc.release_pages(pages)
    alloc.demote_cold(1)
    assert alloc.tier_page_counts()[TIER_DISK] == 1
    got = alloc.match_prefix([1, 2])
    (job,) = alloc.take_staged_promotions()
    assert all(np.array_equal(a, b) and a.dtype == b.dtype
               for a, b in zip(job.staged, payloads[pages[0]]))
    alloc.promotion_applied(job)
    alloc.release_pages(got)
    assert alloc.audit([]) == []


def test_engine_streams_invisible_to_tiering_f32(params, tmp_path):
    """The whole-engine parity gate: a three-tier engine under heavy
    spill churn emits BITWISE the streams of an all-HBM engine — and the
    drop-on-evict baseline proves the savings are real, not residual."""
    w1, w2 = _waves(8)
    ref = _engine(params, kv_pages=64)
    r1, _ = ref.run(w1, steps=16)
    r2, _ = ref.run(w2, steps=16)
    ceiling = ref.allocator.tokens_saved

    eng = _engine(params, kv_pages=8, kv_host_pages=6,
                  kv_disk_dir=str(tmp_path))
    t1, _ = eng.run(w1, steps=16)
    eng.allocator.reset_counters()
    t2, _ = eng.run(w2, steps=16)
    a = eng.allocator
    assert (t1, t2) == (r1, r2)
    assert sum(a.demotions.values()) > 0
    assert sum(a.promotions.values()) > 0
    assert (a.tokens_saved_by_tier[TIER_HOST]
            + a.tokens_saved_by_tier[TIER_DISK]) > 0
    assert eng.audit_pages() == []

    drop = _engine(params, kv_pages=8)
    d1, _ = drop.run(w1, steps=16)
    drop.allocator.reset_counters()
    d2, _ = drop.run(w2, steps=16)
    assert (d1, d2) == (r1, r2)
    assert ceiling > 0 and drop.allocator.tokens_saved == 0


def test_engine_q8_pages_value_exact_through_tiers(params, tmp_path):
    """Q8 pools spill their CODES+DELTAS verbatim: a tiered q8 engine's
    greedy streams equal the untiered q8 engine's exactly (the payload
    is never re-quantized on the demote/promote path)."""
    w1, w2 = _waves(8)
    ref = _engine(params, kv_pages=64, kv_quant="q8")
    r1, _ = ref.run(w1, steps=16)
    r2, _ = ref.run(w2, steps=16)
    eng = _engine(params, kv_pages=8, kv_host_pages=6, kv_quant="q8",
                  kv_disk_dir=str(tmp_path))
    t1, _ = eng.run(w1, steps=16)
    t2, _ = eng.run(w2, steps=16)
    assert (t1, t2) == (r1, r2)
    assert sum(eng.allocator.promotions.values()) > 0
    assert eng.audit_pages() == []


def test_engine_streams_invisible_under_tp_mesh(params, tmp_path):
    """ISSUE 12's tp leg: sharded pool planes demote through the same
    fetch (np gather over the sharded page) and promote through
    parallel/tp.stage_page_planes (payload device_put pre-sharded on the
    kv-head axis) — streams stay bitwise the single-chip run's."""
    from distributed_llama_tpu.parallel import make_mesh

    w1, w2 = _waves(6)
    ref = _engine(params, kv_pages=64)
    r1, _ = ref.run(w1, steps=16)
    r2, _ = ref.run(w2, steps=16)
    eng = _engine(params, kv_pages=8, kv_host_pages=6,
                  kv_disk_dir=str(tmp_path), mesh=make_mesh(tp=2))
    t1, _ = eng.run(w1, steps=16)
    t2, _ = eng.run(w2, steps=16)
    assert (t1, t2) == (r1, r2)
    assert sum(eng.allocator.promotions.values()) > 0
    assert eng.audit_pages() == []


def test_disk_crc_corruption_rederives_via_prefill(params, tmp_path):
    """A CRC-damaged disk page must degrade to recompute: the hit falls
    back to prefill, streams stay correct, nothing crashes, and the
    audit is clean afterwards (the dead record is dropped)."""
    w1, w2 = _waves(6)
    ref = _engine(params, kv_pages=64)
    r1, _ = ref.run(w1, steps=16)
    r2, _ = ref.run(w2, steps=16)

    # disk-only tier so every demotion lands in a segment file
    eng = _engine(params, kv_pages=8, kv_disk_dir=str(tmp_path))
    t1, _ = eng.run(w1, steps=16)
    a = eng.allocator
    assert a.tier_page_counts()[TIER_DISK] > 0
    # smash one byte in every live record of every segment
    for (path, off), length in list(a.disk._live.items()):
        with open(path, "r+b") as fh:
            fh.seek(off + length // 2)
            byte = fh.read(1)
            fh.seek(off + length // 2)
            fh.write(bytes([byte[0] ^ 0xFF]))
    t2, _ = eng.run(w2, steps=16)
    assert (t1, t2) == (r1, r2)  # re-derived, not wrong, not crashed
    assert a.crc_drops > 0
    assert eng.audit_pages() == []


# -- admission PAUSE until promoted -----------------------------------------


def test_admission_pauses_until_promotion_lands(params, tmp_path):
    """Hold the PageUploader's gate: a request whose shared prefix is
    mid-promotion rides dispatches masked inactive (stats.pauses moves,
    no tokens sample) and resumes bitwise once the upload lands."""
    from distributed_llama_tpu.runtime.continuous import Request

    prefix = [1] + [11 + j for j in range(2 * PS)]
    ref = _engine(params, kv_pages=64)
    (want,), _ = ref.run([prefix + [99]], steps=16)

    eng = _engine(params, kv_pages=8, kv_host_pages=8,
                  kv_disk_dir=str(tmp_path))
    eng.run([prefix + [42]], steps=16)  # publish the prefix
    assert eng.allocator.demote_cold(2) == 2  # spill it
    gate = threading.Event()  # held: staging stalls
    eng._uploader.gate = gate
    req = Request(tokens=prefix + [99], steps=16)
    eng.submit(req)
    pauses0 = eng.stats.pauses
    for _ in range(3):
        eng.step_once()
    assert not req.done.is_set()
    assert eng.stats.pauses > pauses0  # rode dispatches masked inactive
    assert req.n_sampled == 0  # nothing sampled while paused
    slot = next(s for s in eng._pool if s.req is req)
    assert eng.allocator.slot_pending(slot.pages)
    gate.set()
    for _ in range(200):
        if eng.step_once() == 0:
            break
    assert req.done.is_set() and req.error is None
    assert req.out == want  # bitwise the all-HBM stream
    assert sum(eng.allocator.promotions.values()) >= 2
    assert eng.audit_pages() == []


# -- working-set sweep ------------------------------------------------------


def test_savings_hold_at_10x_hbm_working_set(params, tmp_path):
    """The ISSUE 12 acceptance shape: 20 prefixes x 2 pages = 40 prefix
    pages against an 8-page pool (10x with the tails) — tiered savings
    within 20% of the all-HBM ceiling, drop baseline at zero."""
    w1, w2 = _waves(20)
    ref = _engine(params, kv_pages=64)
    ref.run(w1, steps=16)
    ref.allocator.reset_counters()
    ref.run(w2, steps=16)
    ceiling = ref.allocator.tokens_saved
    assert ceiling == 20 * 2 * PS  # every prefix re-hit in full

    eng = _engine(params, kv_pages=8, kv_host_pages=10,
                  kv_disk_dir=str(tmp_path))
    eng.run(w1, steps=16)
    eng.allocator.reset_counters()
    eng.run(w2, steps=16)
    assert eng.allocator.tokens_saved >= 0.8 * ceiling
    assert eng.audit_pages() == []

    drop = _engine(params, kv_pages=8)
    drop.run(w1, steps=16)
    drop.allocator.reset_counters()
    drop.run(w2, steps=16)
    assert drop.allocator.tokens_saved <= 0.2 * ceiling


# -- observability ----------------------------------------------------------


def test_tier_metrics_exposition_and_counters(params, tmp_path):
    from distributed_llama_tpu.obs.metrics import Registry

    reg = Registry()
    eng = _engine(params, kv_pages=8, kv_host_pages=6,
                  kv_disk_dir=str(tmp_path), metrics=reg)
    w1, w2 = _waves(8)
    eng.run(w1, steps=16)
    eng.run(w2, steps=16)
    text = reg.expose()
    assert 'dllama_kv_tier_pages{tier="host"}' in text
    assert "dllama_tier_promotions_total" in text
    assert "dllama_tier_demotions_total" in text
    assert 'dllama_prefill_tokens_saved_by_tier_total{tier="disk"}' in text
    a = eng.allocator

    def sample(name):
        for line in text.splitlines():
            if line.startswith(name) and not line.startswith("#"):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError(f"{name} not exposed")

    assert sample("dllama_tier_promotions_total") == sum(
        a.promotions.values())
    assert sample("dllama_tier_demotions_total") == sum(
        a.demotions.values())


def test_untiered_engine_exposes_tier_series_flat(params):
    """Layout-invariant scrape surface: no tiers -> the series exist at
    zero and never move (dashboards survive the knob)."""
    from distributed_llama_tpu.obs.metrics import Registry

    reg = Registry()
    eng = _engine(params, kv_pages=16, metrics=reg)
    eng.run(_waves(3)[0], steps=12)
    text = reg.expose()
    assert "dllama_tier_promotions_total 0" in text
    assert "dllama_tier_demotions_total 0" in text


# -- knob validation --------------------------------------------------------


def test_tier_knobs_require_paged_cache(params):
    with pytest.raises(ValueError, match="kv-page-size"):
        _engine(params, page_size=0, kv_host_pages=4)
    with pytest.raises(ValueError, match="kv_disk_dir"):
        _engine(params, kv_disk_bytes=1 << 20)
