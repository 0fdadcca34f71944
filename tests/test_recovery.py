"""Crash recovery (ISSUE 9): ContinuousEngine.recover must replay
journaled requests BITWISE — the continued stream equals the
uninterrupted run's, greedy trivially and sampled via coin-cursor replay
— across cache layouts (contiguous, paged, speculative), double crashes,
and the graceful-drain suspend path."""

import os

import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs.metrics import Registry
from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                      Request)
from distributed_llama_tpu.runtime.journal import (RequestJournal,
                                                   load_journal)

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=32)


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


def _make(params, journal=None, **overrides):
    kw = dict(slots=2, temperature=0.8, topp=0.9, seed=11,
              metrics=Registry(), prefill_chunk=4, page_size=4,
              kv_pages=24)
    kw.update(overrides)
    eng = ContinuousEngine(SPEC, params, journal=journal, **kw)
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


def _reqs():
    """One greedy, one seeded-sampled — both must replay bitwise."""
    return [Request(tokens=[1, 9, 17, 25], steps=24, temperature=0.0,
                    topp=0.9, seed=501),
            Request(tokens=[1, 9, 17, 42], steps=24, temperature=0.9,
                    topp=0.9, seed=502)]


def _drain(eng):
    while eng.step_many(eng.block_steps, quiet=True):
        pass


def _reference(params, **overrides):
    eng = _make(params, **overrides)
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    return [r.out for r in reqs]


def _interrupted(params, path, n_iters=9, **overrides):
    """Simulated SIGKILL: journal + engine, step a few times, abandon the
    process state (no close, no retire) — only the journal survives."""
    journal = RequestJournal(path)
    eng = _make(params, journal=journal, **overrides)
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    for _ in range(n_iters):
        eng.step_many(eng.block_steps, quiet=True)
    assert all(not r.done.is_set() for r in reqs), \
        "interrupt point too late: nothing left to recover"
    assert all(r.n_sampled >= 2 for r in reqs)
    return journal


def _recover_and_finish(params, path, **overrides):
    journal = RequestJournal(path)
    eng = _make(params, journal=journal, **overrides)
    n = eng.recover()
    with eng._lock:
        recovered = list(eng._queue)
    _drain(eng)
    return eng, n, [r.out for r in recovered]


@pytest.mark.parametrize("layout", ["paged", "contiguous", "speculative"])
def test_recovered_streams_bitwise_identical(params, tmp_path, layout):
    overrides = {"paged": {},
                 "contiguous": {"page_size": 0, "kv_pages": 0},
                 "speculative": {"spec_k": 3}}[layout]
    ref = _reference(params, **overrides)
    path = str(tmp_path / "j.journal")
    _interrupted(params, path, **overrides)
    eng, n, outs = _recover_and_finish(params, path, **overrides)
    assert n == 2
    assert outs[0] == ref[0]  # greedy
    assert outs[1] == ref[1]  # seeded-sampled: coin-cursor replay
    assert eng.audit_pages() == []
    if eng._obs is not None:
        assert eng._obs.recoveries.value == 2


def test_double_crash_replays_exactly_one_life_per_request(params,
                                                          tmp_path):
    """Crash, recover, crash AGAIN mid-replay, recover: every recovery
    closes the previous life with a `recovered` retire and re-admits one
    fresh entry, so the third process still sees exactly two live
    requests and still converges on the reference streams."""
    ref = _reference(params)
    path = str(tmp_path / "j.journal")
    _interrupted(params, path)
    # second life: recover, then die mid-replay
    j2 = RequestJournal(path)
    eng2 = _make(params, journal=j2)
    assert eng2.recover() == 2
    for _ in range(3):
        eng2.step_many(eng2.block_steps, quiet=True)
    # third life: exactly two live entries (old lives retired 'recovered')
    j3 = RequestJournal(path)
    assert len(j3.incomplete()) == 2
    eng3 = _make(params, journal=j3)
    assert eng3.recover() == 2
    with eng3._lock:
        recovered = list(eng3._queue)
    _drain(eng3)
    assert [r.out for r in recovered] == ref
    assert eng3.audit_pages() == []


def test_crash_immediately_after_recover_leaves_no_duplicates(params,
                                                              tmp_path):
    """Die the instant recover() returns — before a single step or a
    clean close: the recovers-carrying admits already closed the old
    lives, so the next process sees exactly one live entry per request
    (not a duplicate pair per request)."""
    ref = _reference(params)
    path = str(tmp_path / "j.journal")
    _interrupted(params, path)
    j2 = RequestJournal(path)
    eng2 = _make(params, journal=j2)
    assert eng2.recover() == 2  # and "crash": no steps, no close
    j3 = RequestJournal(path)
    assert len(j3.incomplete()) == 2
    eng3 = _make(params, journal=j3)
    assert eng3.recover() == 2
    with eng3._lock:
        recovered = list(eng3._queue)
    _drain(eng3)
    assert [r.out for r in recovered] == ref
    assert eng3.audit_pages() == []


def test_suspend_journals_remainder_for_recovery(params, tmp_path):
    """The graceful-drain wrap-up: suspend() wakes waiters with an error
    but writes NO retirement — the journal carries the work to the next
    process, which continues bitwise."""
    ref = _reference(params)
    path = str(tmp_path / "j.journal")
    journal = RequestJournal(path)
    eng = _make(params, journal=journal)
    reqs = _reqs()
    for r in reqs:
        eng.submit(r)
    for _ in range(6):
        eng.step_many(eng.block_steps, quiet=True)
    n = eng.suspend()
    assert n == 2
    assert all(r.done.is_set() and r.error is not None for r in reqs)
    assert eng.audit_pages() == []
    journal.close()
    assert len([e for e in load_journal(path) if e.status is None]) == 2
    _, n2, outs = _recover_and_finish(params, path)
    assert n2 == 2 and outs == ref


def test_suspend_without_journal_refuses(params):
    eng = _make(params)
    with pytest.raises(ValueError, match="journal"):
        eng.suspend()
    with pytest.raises(ValueError, match="journal"):
        eng.recover()


def test_post_recovery_ids_do_not_alias(params, tmp_path):
    """A recovered engine numbers new requests past every journaled id —
    new records must never alias an old request's history."""
    path = str(tmp_path / "j.journal")
    _interrupted(params, path)
    journal = RequestJournal(path)
    eng = _make(params, journal=journal)
    eng.recover()
    extra = Request(tokens=[1, 3, 5], steps=6, temperature=0.0)
    eng.submit(extra)
    _drain(eng)
    journal.close()
    rids = [e.rid for e in load_journal(path)]
    assert len(rids) == len(set(rids))
    assert extra.index == max(rids)
    assert extra.out  # the fresh request actually ran


def test_recovery_rides_prefix_tree(params, tmp_path):
    """Recovered prompts re-derive their KV through admission prefill and
    the radix tree — the first recovered sibling publishes its prefix,
    later ones share it (the property that makes recovery cheap)."""
    path = str(tmp_path / "j.journal")
    journal = RequestJournal(path)
    eng = _make(params, journal=journal, slots=4)
    shared = [1, 7, 7, 7, 7, 7, 7, 7, 7]  # two full pages of prefix
    reqs = [Request(tokens=shared + [20 + i], steps=20, temperature=0.0)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    for _ in range(5):
        eng.step_many(eng.block_steps, quiet=True)
    journal2 = RequestJournal(path)
    eng2 = _make(params, journal=journal2, slots=4)
    assert eng2.recover() == 3
    _drain(eng2)
    # the recovered siblings shared prompt pages through the tree
    assert eng2.allocator.prefix_hits >= 1
    assert eng2.audit_pages() == []


# ---------------------------------------- config fingerprint guard (PR 10)


def _fingerprint(seed_policy="explicit:11", scheme="single", **over):
    import dataclasses

    from distributed_llama_tpu.runtime.journal import config_fingerprint

    spec = dataclasses.replace(SPEC, **{k: v for k, v in over.items()
                                        if hasattr(SPEC, k)}) \
        if over else SPEC
    return config_fingerprint(spec, scheme, seed_policy,
                              weights_digest="abcd1234deadbeef")


def test_recover_matching_config_proceeds(params, tmp_path):
    """The WAL header records the serving-config fingerprint; a restart
    under the SAME config recovers normally."""
    path = str(tmp_path / "j")
    j = RequestJournal(path, config=_fingerprint())
    eng = _make(params, journal=j)
    eng.submit(_reqs()[0])
    eng.step_many(1, quiet=True)
    # simulated crash; same config on restart
    j2 = RequestJournal(path, config=_fingerprint())
    assert j2.header_config == _fingerprint()
    eng2 = _make(params, journal=j2)
    assert eng2.recover() == 1
    _drain(eng2)


def test_recover_refuses_mismatched_config(params, tmp_path):
    """A journal with LIVE work recorded under a different config (pinned
    seed, scheme, weight digest, dims...) must REFUSE recovery with the
    drifted keys named — no more silently-wrong bitwise replays across
    config changes."""
    from distributed_llama_tpu.runtime.journal import JournalConfigMismatch

    path = str(tmp_path / "j")
    j = RequestJournal(path, config=_fingerprint("explicit:11"))
    eng = _make(params, journal=j)
    eng.submit(_reqs()[0])
    eng.step_many(1, quiet=True)
    # restart pinned to a different seed: every NEW request's stream
    # would re-derive differently
    j2 = RequestJournal(path, config=_fingerprint("explicit:99"))
    eng2 = _make(params, journal=j2)
    with pytest.raises(JournalConfigMismatch, match="seed_policy"):
        eng2.recover()
    # a scheme change refuses too, naming the key
    j3 = RequestJournal(path, config=_fingerprint(scheme="overlap"))
    eng3 = _make(params, journal=j3)
    with pytest.raises(JournalConfigMismatch, match="tp_scheme"):
        eng3.recover()


def test_recover_refuses_kv_quant_change(params, tmp_path):
    """The ISSUE 11 fingerprint key: a KV-dtype change (f32 journal under
    q8 serving, or the reverse) flips every logit past position 0, so
    recovery refuses with ``kv_quant`` named. The key is omitted at f32,
    so pre-PR-11 journals keep recovering under f32 serving (the legacy
    compatibility contract); the full engine-level drill — live q8
    engine included — runs in tests/test_kv_quant.py."""
    from distributed_llama_tpu.runtime.journal import JournalConfigMismatch

    assert "kv_quant" not in _fingerprint()  # f32 = legacy-compatible
    path = str(tmp_path / "j")
    j = RequestJournal(path, config=_fingerprint())
    eng = _make(params, journal=j)
    eng.submit(_reqs()[0])
    eng.step_many(1, quiet=True)
    # restart with q8 KV pages: same dims, same seed policy, different
    # cache numerics — refuse, naming the key
    from distributed_llama_tpu.runtime.journal import config_fingerprint

    q8_cfg = config_fingerprint(SPEC, "single", "explicit:11",
                                weights_digest="abcd1234deadbeef",
                                kv_quant="q8")
    j2 = RequestJournal(path, config=q8_cfg)
    eng2 = _make(params, journal=j2)
    with pytest.raises(JournalConfigMismatch, match="kv_quant"):
        eng2.recover()


def test_recover_adopts_config_when_nothing_live(params, tmp_path):
    """A config change over a journal with NOTHING incomplete has nothing
    to corrupt: recover() adopts the serving config (header re-stamped)
    instead of stranding the deployment — the advertised-bitwise
    fused→overlap upgrade must not require deleting journals."""
    path = str(tmp_path / "j")
    j = RequestJournal(path, config=_fingerprint(scheme="fused"))
    eng = _make(params, journal=j)
    req = _reqs()[0]
    eng.submit(req)
    _drain(eng)
    assert req.done.is_set()
    j.close()
    # restart under a new scheme: zero live entries -> adopt, recover 0
    new_cfg = _fingerprint(scheme="overlap")
    j2 = RequestJournal(path, config=new_cfg)
    eng2 = _make(params, journal=j2)
    assert eng2.recover() == 0
    assert j2.header_config == new_cfg
    j2.close()
    # the adopted header survives reopen: the NEXT crash compares
    # against the config its requests actually ran under
    j3 = RequestJournal(path)
    assert j3.header_config == new_cfg
    j3.close()


def test_recover_legacy_header_unchecked(params, tmp_path):
    """Pre-fingerprint journals (no config in the header) recover without
    the guard — refusing every existing journal on upgrade would drop
    in-flight work the operator kept on purpose."""
    path = str(tmp_path / "j")
    j = RequestJournal(path)  # legacy: no config recorded
    eng = _make(params, journal=j)
    eng.submit(_reqs()[0])
    eng.step_many(1, quiet=True)
    j2 = RequestJournal(path, config=_fingerprint())
    assert j2.header_config is None  # the header stays legacy
    eng2 = _make(params, journal=j2)
    assert eng2.recover() == 1
    _drain(eng2)


def test_compaction_preserves_recorded_config(params, tmp_path):
    """The compaction rewrite must carry the fingerprint forward — a
    rotated journal that silently dropped its config would skip the
    guard on the next restart."""
    path = str(tmp_path / "j")
    j = RequestJournal(path, config=_fingerprint())
    j.admit(0, [1, 5], steps=4, temperature=0.0, topp=0.9, seed=100)
    j.retire(0, "done")
    j.compact()
    j.close()
    j2 = RequestJournal(path)
    assert j2.header_config == _fingerprint()
    j2.close()


# --------------------------------------------- KV tiering interop (ISSUE 12)


def test_recovery_promotes_disk_resident_prefix_bitwise(params, tmp_path):
    """A recovered request whose shared prefix pages sit on DISK promotes
    them through the same async path as live admissions — and the
    continued stream is still bitwise the uninterrupted run's. Sequence:
    serve + publish the prefix, spill it to disk, crash mid-decode,
    recover into the SAME engine state (tree with disk-tier nodes) —
    recovery's forced-token replay admission must hit the spilled
    prefix, promote it, and converge on the reference."""
    from distributed_llama_tpu.runtime.paging import TIER_DISK

    prefix = [1, 9, 17, 25, 2, 4, 6, 8]  # two full pages at ps=4
    tiered = dict(kv_pages=8, kv_disk_dir=str(tmp_path / "kv"))

    # reference: the uninterrupted run (all-HBM — tiering is invisible)
    ref_eng = _make(params, kv_pages=24)
    ref_req = Request(tokens=list(prefix) + [3], steps=24,
                      temperature=0.9, topp=0.9, seed=502)
    ref_eng.submit(ref_req)
    _drain(ref_eng)

    path = str(tmp_path / "j.journal")
    journal = RequestJournal(path)
    eng = _make(params, journal=journal, **tiered)
    # publish the prefix via a first request, then spill it to disk
    warm = Request(tokens=list(prefix) + [7], steps=24, temperature=0.0,
                   topp=0.9, seed=501)
    eng.submit(warm)
    _drain(eng)
    assert eng.allocator.demote_cold(2) == 2
    assert eng.allocator.tier_page_counts()[TIER_DISK] > 0
    # now the request that will crash mid-decode
    victim = Request(tokens=list(prefix) + [3], steps=24,
                     temperature=0.9, topp=0.9, seed=502)
    eng.submit(victim)
    for _ in range(6):
        eng.step_many(eng.block_steps, quiet=True)
    assert not victim.done.is_set() and victim.n_sampled >= 2
    # simulated SIGKILL: abandon the engine; only the journal survives.
    # The fresh process re-publishes the prefix (a sibling request),
    # spills it to disk again, THEN recovers — the recovered admission
    # must promote from disk.
    j2 = RequestJournal(path)
    eng2 = _make(params, journal=j2, **tiered)
    warm2 = Request(tokens=list(prefix) + [7], steps=24, temperature=0.0,
                    topp=0.9, seed=501)
    eng2.submit(warm2)
    _drain(eng2)
    assert eng2.allocator.demote_cold(2) == 2
    assert eng2.allocator.tier_page_counts()[TIER_DISK] > 0
    assert eng2.recover() == 1
    with eng2._lock:
        (rec,) = list(eng2._queue)
    _drain(eng2)
    assert rec.out == ref_req.out  # bitwise through the disk promotion
    assert eng2.allocator.promotions[TIER_DISK] > 0
    assert eng2.audit_pages() == []


def test_fingerprint_kv_tiers_keys_omitted_when_off(params, tmp_path):
    """ISSUE 12 satellite: the kv_tiers fingerprint keys are omitted when
    tiering is off — legacy journals keep recovering — and a tier-budget
    change under live work refuses with the key named."""
    from distributed_llama_tpu.runtime.journal import (
        JournalConfigMismatch, config_fingerprint)

    base = _fingerprint()
    assert "kv_host_pages" not in base and "kv_disk" not in base

    def tiered_cfg(host_pages):
        return config_fingerprint(SPEC, "single", "explicit:11",
                                  weights_digest="abcd1234deadbeef",
                                  kv_host_pages=host_pages, kv_disk=True)

    path = str(tmp_path / "j")
    j = RequestJournal(path, config=tiered_cfg(64))
    eng = _make(params, journal=j)
    eng.submit(_reqs()[0])
    eng.step_many(1, quiet=True)
    # restart with a different host budget: refuse, naming the key
    j2 = RequestJournal(path, config=tiered_cfg(128))
    eng2 = _make(params, journal=j2)
    with pytest.raises(JournalConfigMismatch, match="kv_host_pages"):
        eng2.recover()
    # restart under untiered serving: kv keys absent on one side -> named
    j3 = RequestJournal(path, config=_fingerprint())
    eng3 = _make(params, journal=j3)
    with pytest.raises(JournalConfigMismatch, match="kv_disk"):
        eng3.recover()
