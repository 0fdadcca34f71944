"""Continuous batching: scheduling must be invisible in each request's
output — every request's token stream equals running it alone."""

import numpy as np
import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=16)


@pytest.fixture(scope="module")
def params():
    return synth_params(SPEC, q40=False, seed=4, scale=0.3)


@pytest.fixture(scope="module")
def params_dev(params):
    from distributed_llama_tpu.models.llama import params_to_device

    return params_to_device(params)


def test_forward_batch_ragged_matches_singles(params_dev):
    """Rows at DIFFERENT positions must each match the single-sequence
    forward at that position."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (forward,
                                                    forward_batch_ragged,
                                                    init_cache,
                                                    init_cache_batch)

    B = 3
    hists = {0: [7, 11, 5], 1: [17], 2: [40, 88]}  # row b is at pos len(b)
    tokens_now = jnp.asarray([9, 3, 77], dtype=jnp.int32)

    singles, caches = [], []
    for b in range(B):
        c = init_cache(SPEC)
        for p, t in enumerate(hists[b]):
            _, c = forward(SPEC, params_dev, c, jnp.asarray([t], jnp.int32),
                           jnp.int32(p))
        caches.append(c)
        lg, c2 = forward(SPEC, params_dev, c, tokens_now[b][None],
                         jnp.int32(len(hists[b])))
        singles.append((np.asarray(lg[0]), c2))

    cache_b = init_cache_batch(SPEC, B)._replace(
        k=jnp.stack([c.k for c in caches], axis=1),
        v=jnp.stack([c.v for c in caches], axis=1))
    pos_vec = jnp.asarray([len(hists[b]) for b in range(B)], jnp.int32)
    lg_b, cache_b2 = forward_batch_ragged(SPEC, params_dev, cache_b,
                                          tokens_now, pos_vec)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(lg_b[b]), singles[b][0],
                                   rtol=2e-5, atol=2e-5)
        # the written cache column must land at each row's own position
        np.testing.assert_allclose(
            np.asarray(cache_b2.k[:, b, :len(hists[b]) + 1]),
            np.asarray(singles[b][1].k[:, :len(hists[b]) + 1]),
            rtol=1e-5, atol=1e-5)


def test_continuous_more_requests_than_slots(params, params_dev):
    """5 ragged requests through 2 slots, greedy: each output must equal the
    per-step reference loop's (generate()) output for that prompt alone."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import forward, init_cache
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    steps = 8
    reqs = [[1, 5, 9], [1, 22], [1, 7, 33, 2], [1, 60], [1, 90, 14]]

    # reference: plain single-sequence greedy decode per request
    singles = []
    for req in reqs:
        c = init_cache(SPEC)
        token, pos, out = req[0], 0, []
        while pos < steps:
            lg, c = forward(SPEC, params_dev, c,
                            jnp.asarray([token], jnp.int32), jnp.int32(pos))
            nxt = req[pos + 1] if pos + 1 < len(req) else int(
                np.argmax(np.asarray(lg[0])))
            pos += 1
            if nxt == 1:
                break
            out.append(nxt)
            token = nxt
        singles.append(out)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3)
    outs, stats = eng.run(reqs, steps)
    assert outs == singles
    assert stats.max_active <= 2
    # with 5 requests x 8 positions through 2 slots the scheduler must
    # actually overlap work (fewer steps than serial, more than one batch)
    assert steps <= stats.steps <= 5 * steps


@pytest.mark.parametrize("sp,tp", [(1, 2), (2, 1), (2, 2)])
def test_continuous_over_mesh_matches_single_chip(params, sp, tp):
    """The same request stream through an sp/tp sharded ragged step must be
    token-identical to the single-chip continuous engine (per-row position
    clocks through the sequence-chunked cache) — with and without sharded
    admission prefill."""
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    steps = 8
    reqs = [[1, 5, 9], [1, 22], [1, 7, 33, 2]]
    ref_eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                               topp=0.9, seed=3)
    ref, _ = ref_eng.run(reqs, steps)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3, mesh=make_mesh(sp=sp, tp=tp))
    outs, _ = eng.run(reqs, steps)
    assert outs == ref

    eng_p = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                             topp=0.9, seed=3, mesh=make_mesh(sp=sp, tp=tp),
                             prefill_chunk=2)
    outs_p, stats_p = eng_p.run(reqs, steps)
    assert outs_p == ref
    # the prefilled rows skipped their prompt steps on the device
    assert stats_p.steps < eng.stats.steps


@pytest.mark.parametrize("temp,block,tp", [(0.0, 4, 1), (0.9, 4, 1),
                                           (0.9, 3, 1), (0.9, 4, 2)])
def test_continuous_block_steps_matches_per_step(params, temp, block, tp):
    """Fused K-step chains == per-step scheduling, token for token, across
    mixed prompts (more requests than slots, ragged lengths, budget and
    prompt retirements at non-boundary steps); the tp case runs the chain
    over the sharded batch step (the PARITY.md composition claim)."""
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    steps = 10
    mesh = make_mesh(tp=tp) if tp > 1 else None
    reqs = [[1, 5, 9], [1, 22], [1, 7, 33, 2, 9, 14], [1, 60], [1, 90, 14]]
    ref, ref_stats = ContinuousEngine(SPEC, params, slots=2,
                                      temperature=temp, topp=0.9,
                                      seed=3).run(reqs, steps)
    got, _ = ContinuousEngine(SPEC, params, slots=2, temperature=temp,
                              topp=0.9, seed=3, mesh=mesh,
                              block_steps=block).run(reqs, steps)
    assert got == ref


def test_continuous_block_steps_per_request_overrides(params):
    """Per-request temperature/topp/seed ride through the fused chain (the
    traced-sampler path) identically to the per-step host sampler."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    def run_engine(block):
        eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                               topp=0.9, seed=5, block_steps=block)
        reqs = [Request(tokens=[1, 5, 9], steps=8, temperature=0.9,
                        topp=0.9, seed=11),
                Request(tokens=[1, 22], steps=8),  # greedy (engine default)
                Request(tokens=[1, 7, 33], steps=8, temperature=0.7,
                        topp=2.0, seed=13)]  # multinomial walk
        for r in reqs:
            eng.submit(r)
        while eng.step_many(block):
            pass
        return [r.out for r in reqs]

    assert run_engine(4) == run_engine(1)


def test_continuous_block_steps_with_prefill(params):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    steps = 10
    reqs = [[1, 5, 9, 14, 23, 40, 7, 11], [1, 22], [1, 7, 33, 2, 9]]
    ref, _ = ContinuousEngine(SPEC, params, slots=2, temperature=0.9,
                              topp=0.9, seed=3).run(reqs, steps)
    got, _ = ContinuousEngine(SPEC, params, slots=2, temperature=0.9,
                              topp=0.9, seed=3, prefill_chunk=4,
                              block_steps=4).run(reqs, steps)
    assert got == ref


@pytest.mark.parametrize("case_seed", [0, 1, 2])
def test_continuous_randomized_workloads_agree(params, case_seed):
    """Seeded fuzz: random ragged request mixes must produce identical
    per-request streams across every scheduler configuration (per-step,
    fused chains, prefill on/off) — the composition surface squared."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    rng = np.random.default_rng(1000 + case_seed)
    n_req = int(rng.integers(3, 7))
    reqs = []
    for _ in range(n_req):
        plen = int(rng.integers(1, 9))
        reqs.append([1] + list(rng.integers(3, SPEC.vocab_size - 1,
                                            plen - 1)))
    steps = int(rng.integers(4, SPEC.seq_len))
    slots = int(rng.integers(1, 4))
    temp = float(rng.choice([0.0, 0.9]))

    def outputs(**kw):
        return ContinuousEngine(SPEC, params, slots=slots, temperature=temp,
                                topp=0.9, seed=7, **kw).run(reqs, steps)[0]

    ref = outputs()
    assert outputs(block_steps=int(rng.integers(2, 6))) == ref
    assert outputs(prefill_chunk=int(rng.integers(2, 6))) == ref
    assert outputs(block_steps=4, prefill_chunk=3) == ref
    # everything at once: sharded step + fused chains + admission prefill
    from distributed_llama_tpu.parallel import make_mesh

    assert outputs(mesh=make_mesh(sp=2, tp=2), block_steps=3,
                   prefill_chunk=2) == ref


def test_continuous_bf16_cache_greedy_matches_f32(params):
    """--kv-cache-dtype bf16 through the continuous engine (per-row cache
    writes cast, fused chains, admission prefill): greedy streams on this
    tiny model should survive the cache rounding and match f32."""
    import jax.numpy as jnp

    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    steps = 8
    reqs = [[1, 5, 9], [1, 22], [1, 7, 33, 2]]
    ref, _ = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                              topp=0.9, seed=3).run(reqs, steps)
    got, _ = ContinuousEngine(SPEC, params, slots=2, temperature=0.0,
                              topp=0.9, seed=3,
                              cache_dtype=jnp.bfloat16,
                              prefill_chunk=2, block_steps=4).run(reqs,
                                                                  steps)
    assert got == ref


def test_continuous_pos_never_reaches_seq_len(params):
    """A retired row's clock can hit seq_len; the freed slot must be parked
    back at pos 0 before the next device step — pos == seq_len reaching the
    flash kernel would DMA past the end of the cache row on TPU."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.0, topp=0.9,
                           seed=3)
    seen = []
    real_step = eng._decode

    def spy(params_, cache, prev_picked, blk):
        seen.append(np.asarray(blk)[:, 1].max())    # [override | pos]
        return real_step(params_, cache, prev_picked, blk)

    eng._decode = spy
    # steps == seq_len, desynced slots (one row retires early via its
    # shorter budget path while the other keeps going)
    reqs = [[1, 5, 9], [1, 22], [1, 7, 33, 2]]
    outs, _ = eng.run(reqs, steps=SPEC.seq_len)
    assert all(o is not None for o in outs)
    assert max(seen) < SPEC.seq_len


@pytest.mark.parametrize("temp", [0.0, 0.9])
def test_continuous_admission_prefill_matches_plain(params, temp):
    """prefill_chunk engine == step-by-step engine, token for token, across
    mixed prompt lengths (incl. one long enough for multiple chunks, one
    too short to engage prefill, and one longer than the budget)."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    steps = 10
    reqs = [[1, 5, 9, 14, 23, 40, 7, 11], [1, 22],
            [1] + list(range(20, 33)),  # 14 tokens: exceeds steps budget
            [1, 7, 33, 2, 9]]
    ref, ref_stats = ContinuousEngine(SPEC, params, slots=2,
                                      temperature=temp, topp=0.9,
                                      seed=3).run(reqs, steps)
    got, stats = ContinuousEngine(SPEC, params, slots=2, temperature=temp,
                                  topp=0.9, seed=3,
                                  prefill_chunk=4).run(reqs, steps)
    assert got == ref
    # the prefilled rows skipped their prompt steps on the device, but the
    # token count keeps its meaning across the toggle
    assert stats.steps < ref_stats.steps
    assert stats.tokens == ref_stats.tokens


def test_continuous_sampled_matches_generate(params):
    """Sampled decoding (temp>0): request i's stream == generate() run with
    the per-request seed — the scheduler must not disturb RNG consumption."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    class _Tok:
        def encode(self, text, bos=True, eos=False):
            return [1] + [3 + b for b in text.encode()]

        def decode_piece(self, prev, tok):
            return b"?"

    steps, seed = 8, 41
    prompts = ["ab", "x", "hello"]
    tok = _Tok()

    singles = []
    for i, p in enumerate(prompts):
        eng = Engine(SPEC, params)
        sampler = Sampler(SPEC.vocab_size, temperature=0.9, topp=0.9,
                          seed=seed + i)
        out, _ = generate(eng, tok, sampler, p, steps, quiet=True)
        singles.append(out)

    ceng = ContinuousEngine(SPEC, params, slots=2, temperature=0.9, topp=0.9,
                            seed=seed)
    outs, _ = ceng.run([tok.encode(p) for p in prompts], steps)
    assert outs == singles


def test_use_native_sampler_plumbed_to_slots(params):
    """use_native_sampler=False (the multi-host pin, cli.py) must reach every
    admitted slot's Sampler — native and numpy can diverge by ulps across
    libm builds, so SPMD hosts must all take the numpy path (ADVICE r1)."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, params, slots=2, temperature=0.9, topp=0.9,
                           seed=3, use_native_sampler=False)
    for r in ([1, 5], [1, 7]):
        eng.submit(Request(tokens=list(r), steps=4))
    eng._admit()
    samplers = [s.sampler for s in eng._pool if not s.free]
    assert samplers and all(s.use_native is False for s in samplers)
    # default stays native (single-host fast path)
    eng2 = ContinuousEngine(SPEC, params, slots=1, temperature=0.9, topp=0.9,
                            seed=3)
    eng2.submit(Request(tokens=[1, 5], steps=4))
    eng2._admit()
    assert eng2._pool[0].sampler.use_native is True


# -- step_once runs one step ahead of the host on greedy rows (PR 30) --------
#
# With every occupied row at temperature 0, step n+1 is launched on step n's
# picks while they are still on the device, and step n lands while n+1 runs.
# Scheduling stays invisible: each request's stream is the stream of the
# SYNCHRONOUS iteration (every step launched and landed in one call), which
# the same engine runs when ``_runs_ahead`` says no, and of the request run
# alone on the host's argmax.

AHEAD = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=128, seq_len=32)
# greedy streams of AHEAD's seed-4 weights that stop on BOS, and one of
# EXPERT's; the others below run to their budgets
BOS_STOPS = {"dense": [1, 10, 60], "expert": [1, 171, 38]}


@pytest.fixture(scope="module")
def ahead_kinds():
    """kind -> (spec, weights, engine keywords)."""
    from distributed_llama_tpu.ops.quants import FloatType

    expert = TransformerSpec(dim=128, hidden_dim=64, n_layers=2, n_heads=2,
                             n_kv_heads=2, vocab_size=256, seq_len=32,
                             weights_float_type=FloatType.Q40, n_experts=4,
                             n_active_experts=2, qk_norm=True)
    dense = synth_params(AHEAD, q40=False, seed=4, scale=0.3)
    paged = dict(page_size=4, prefill_chunk=4)
    return {"paged": (AHEAD, dense, paged),
            "contiguous": (AHEAD, dense, dict(prefill_chunk=4)),
            "expert": (expert, synth_params(expert, q40=True, seed=11),
                       paged)}


def _ahead_engine(kinds, kind, sync=False, slots=3, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    spec, tree, base = kinds[kind]
    eng = ContinuousEngine(spec, tree, slots=slots, temperature=0.0,
                           topp=0.9, seed=3, **{**base, **kw})
    if sync:    # the rule says no: every step is launched and landed at once
        eng._runs_ahead = lambda riding, paused: False
    return eng


def _requests(kind, n=7):
    """``n`` greedy requests of uneven prompts and budgets: short prompts
    that ride forced tokens, long ones that take the admission prefill,
    one that stops on BOS."""
    from distributed_llama_tpu.runtime.continuous import Request

    rng = np.random.default_rng(30)
    top = 250 if kind == "expert" else 120
    reqs = [Request(tokens=list(BOS_STOPS["expert" if kind == "expert"
                                          else "dense"]), steps=24)]
    for i in range(n - 1):
        plen = int(rng.integers(1, 12))
        reqs.append(Request(
            tokens=[1] + [int(t) for t in rng.integers(3, top, plen)],
            steps=int(rng.integers(plen + 2, 30))))
    return reqs


def _drain(eng):
    while eng.step_once():
        pass
    assert eng._flight is None


def _mid_flight(eng, reqs):
    """Three requests up front, the rest submitted one per two iterations,
    while a step is in flight."""
    for r in reqs[:3]:
        eng.submit(r)
    for r in reqs[3:]:
        eng.step_once()
        eng.step_once()
        eng.submit(r)
    _drain(eng)


def _cancel(eng, reqs):
    """The second request is cancelled between a launch and its landing."""
    for r in reqs:
        eng.submit(r)
    for _ in range(5):
        eng.step_once()
    assert eng._flight is None or reqs[1] in eng._flight.reqs
    eng.cancel(reqs[1])
    _drain(eng)


def _starved(eng, reqs):
    """Two pages for the short request and a third page the long one must
    wait for in a pool of four, then a request that takes the freed slot."""
    from distributed_llama_tpu.runtime.continuous import Request

    reqs[:] = [Request(tokens=[1, 22, 7], steps=6),
               Request(tokens=[1, 5, 9], steps=12),
               Request(tokens=[1, 60], steps=10)]
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    assert eng.stats.pauses > 0     # a row did ride a step masked


SCENARIOS = {
    # name: (driver, engine keywords, kinds it applies to)
    "admissions_mid_flight": (_mid_flight, {}, ("paged", "contiguous",
                                                "expert")),
    "cancel_in_flight": (_cancel, {}, ("paged", "contiguous", "expert")),
    "page_starved_pause": (_starved, dict(slots=2, kv_pages=4,
                                          prefix_share=False,
                                          prefill_chunk=0),
                           ("paged", "expert")),
}


@pytest.mark.parametrize("kind,scenario", [
    (k, name) for name, (_, _, on) in SCENARIOS.items() for k in on])
def test_run_ahead_streams_equal_the_synchronous_iteration(ahead_kinds, kind,
                                                           scenario):
    drive, kw, _ = SCENARIOS[scenario]
    want, got = _requests(kind), _requests(kind)
    sync = _ahead_engine(ahead_kinds, kind, sync=True, **kw)
    drive(sync, want)
    eng = _ahead_engine(ahead_kinds, kind, **kw)
    drive(eng, got)
    assert sync.stats.steps_ahead == 0 and eng.stats.steps_ahead > 0
    for w, g in zip(want, got):
        assert g.done.is_set() and g.error == w.error
        if g.cancelled:     # a prefix of its stream, wherever it was cut
            assert g.out == w.out[:len(g.out)] or w.out == g.out[:len(w.out)]
        else:
            assert g.out == w.out
    assert len(got) == len(want)
    if scenario == "admissions_mid_flight":
        # the BOS stop is told by the token alone: its row of the step
        # launched ahead is thrown away; budget stops hand their slot over
        assert len(got[0].out) < 23 and eng.stats.rows_dropped_ahead >= 1
        # the same row-steps, landed rows counted as ever; a request that
        # arrives under a running step joins one step later
        assert eng.stats.sum_active == sync.stats.sum_active
        assert 0 <= eng.stats.steps - sync.stats.steps <= len(got) - 3
    assert eng.audit_pages() == []


@pytest.mark.parametrize("kind", ["paged", "contiguous"])
def test_run_ahead_streams_equal_each_request_alone(ahead_kinds, kind,
                                                    params_dev_ahead):
    """The oracle that knows no scheduler: each request alone, token by
    token, on the host's argmax of the single-sequence forward."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import forward, init_cache

    reqs = _requests(kind)
    eng = _ahead_engine(ahead_kinds, kind)
    _mid_flight(eng, reqs)
    assert eng.stats.steps_ahead > 0.8 * eng.stats.steps
    for r in reqs:
        c, token, out = init_cache(AHEAD), r.tokens[0], []
        for pos in range(min(r.steps, AHEAD.seq_len)):
            lg, c = forward(AHEAD, params_dev_ahead, c,
                            jnp.asarray([token], jnp.int32), jnp.int32(pos))
            token = (r.tokens[pos + 1] if pos + 1 < len(r.tokens)
                     else int(np.argmax(np.asarray(lg[0]))))
            if token == 1:
                break
            out.append(token)
        assert r.out == out


@pytest.fixture(scope="module")
def params_dev_ahead(ahead_kinds):
    from distributed_llama_tpu.models.llama import params_to_device

    return params_to_device(ahead_kinds["paged"][1])


@pytest.mark.parametrize("kind", ["paged", "expert"])
def test_prefix_published_at_retire_is_reused_when_run_ahead(ahead_kinds,
                                                             kind):
    """No admission prefill: a prompt's pages reach the radix tree when
    its request retires. The request that takes the freed slot, same
    prompt, must find them, run ahead or not, and read the same stream."""
    from distributed_llama_tpu.runtime.continuous import Request

    top = 250 if kind == "expert" else 120
    prompt = [1] + [int(t) for t in
                    np.random.default_rng(5).integers(3, top, 13)]

    def run(sync):
        eng = _ahead_engine(ahead_kinds, kind, sync=sync, slots=2,
                            prefill_chunk=0)
        reqs = [Request(tokens=list(prompt), steps=18),
                Request(tokens=[1, 9, 17], steps=30),
                Request(tokens=list(prompt) + [7], steps=24)]
        for r in reqs:
            eng.submit(r)
        _drain(eng)
        return eng, [r.out for r in reqs]

    sync, want = run(True)
    eng, got = run(False)
    assert got == want and eng.stats.steps_ahead > 0
    assert eng.allocator.prefix_hits == sync.allocator.prefix_hits == 1
    assert eng.allocator.tokens_saved == sync.allocator.tokens_saved == 12


@pytest.mark.parametrize("kind", ["paged", "contiguous", "expert"])
def test_recovery_after_a_stop_with_a_step_in_flight(ahead_kinds, kind,
                                                     tmp_path):
    """The process dies with a step launched and not landed: that step is
    not journaled, as one never run, and the next life's streams are the
    uninterrupted run's."""
    from distributed_llama_tpu.runtime.journal import RequestJournal

    ref = _requests(kind, n=4)
    plain = _ahead_engine(ahead_kinds, kind)
    for r in ref:
        plain.submit(r)
    _drain(plain)

    path = str(tmp_path / "requests.journal")
    eng = _ahead_engine(ahead_kinds, kind, journal=RequestJournal(path))
    first = _requests(kind, n=4)
    for r in first:
        eng.submit(r)
    for _ in range(6):
        eng.step_once()
    assert eng._flight is not None and eng._flight.ahead   # and it "dies"
    live = [r for r in first if not r.done.is_set()]
    assert live and any(r.n_sampled for r in live)

    eng2 = _ahead_engine(ahead_kinds, kind, journal=RequestJournal(path))
    assert eng2.recover() == len(live)
    with eng2._lock:
        recovered = list(eng2._queue)
    _drain(eng2)
    want = {tuple(r.tokens): r.out for r in ref}
    for r, old in zip(recovered, live):
        assert r.out == want[tuple(old.tokens)]
    assert eng2.audit_pages() == []


class _NeverFetched:
    """Stands in for a step's logits where no row needs them."""

    def __array__(self, *a, **k):
        raise AssertionError("the logits crossed to the host")


@pytest.mark.parametrize("kind", ["paged", "contiguous", "expert"])
def test_all_greedy_run_counts_its_steps_ahead_and_fetches_no_logits(
        ahead_kinds, kind):
    eng = _ahead_engine(ahead_kinds, kind)
    real, launches = eng._decode, []

    def no_logits(*args):
        logits, *rest = real(*args)
        launches.append(1)
        return (_NeverFetched(), *rest)

    eng._decode = no_logits
    reqs = _requests(kind)[1:]          # budget stops only
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    st = eng.stats
    # one episode: the first step rides the host's tokens, every later one
    # the picks still on the device, and one landing launches nothing
    assert st.steps == len(launches) and st.steps_ahead == st.steps - 1
    assert st.rows_dropped_ahead == 0


@pytest.mark.parametrize("kind", ["paged", "contiguous", "expert"])
def test_paged_steps_count_the_positions_their_rows_read(ahead_kinds, kind):
    """``ContinuousStats.paged_kv_positions``: position + 1 a riding row of
    every launched step of a plain KV page pool, read back here from each
    launch's staged block (a row that does not take part rides on the scrap
    page alone); nothing without pages, and nothing on the counters of the
    other pools (tests/test_latent.py, test_sambay.py and test_laguna.py
    hold it at 0 on theirs)."""
    from distributed_llama_tpu.runtime.paging import SCRAP_PAGE

    eng = _ahead_engine(ahead_kinds, kind)
    real, read = eng._decode, []

    def staged(*args):
        blk = np.asarray(args[3])
        rides = (blk[:, 2:] != SCRAP_PAGE).any(axis=1)
        read.append(int((blk[rides, 1] + 1).sum()))
        return real(*args)

    eng._decode = staged
    for r in _requests(kind):
        eng.submit(r)
    _drain(eng)
    st = eng.stats
    if kind == "contiguous":
        assert st.paged_kv_positions == 0
        return
    assert st.paged_kv_positions == sum(read) > st.sum_active
    assert st.latent_positions == st.shared_kv_positions == 0


def test_a_row_with_a_temperature_holds_the_iteration_synchronous(
        ahead_kinds):
    """While a row with a temperature is active no step is launched ahead
    and it samples on the host from the logits, to the coin, as ``generate``
    does alone; the greedy rows beside it read the streams they read
    without it; when it has gone the iteration runs ahead again."""
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import Request
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    class _Tok:
        def encode(self, text, bos=True, eos=False):
            return [1] + [3 + b for b in text.encode()]

        def decode_piece(self, prev, tok):
            return b"?"

    alone = _ahead_engine(ahead_kinds, "paged")
    greedy = _requests("paged", n=4)
    for r in greedy:
        alone.submit(r)
    _drain(alone)

    reg = Registry()
    eng = _ahead_engine(ahead_kinds, "paged", slots=4, metrics=reg)
    warm = Request(tokens=_Tok().encode("hello"), steps=12, temperature=0.9,
                   topp=0.9, seed=41)
    beside = _requests("paged", n=4)
    eng.submit(warm)
    for r in beside:
        eng.submit(r)
    while not warm.done.is_set():
        eng.step_once()
        assert eng._flight is None
    assert eng.stats.steps_ahead == 0 and eng.stats.steps >= 7
    _drain(eng)
    assert [r.out for r in beside] == [r.out for r in greedy]
    assert 0 < eng.stats.steps_ahead < eng.stats.steps
    want, _ = generate(Engine(AHEAD, ahead_kinds["paged"][1]), _Tok(),
                       Sampler(AHEAD.vocab_size, 0.9, 0.9, 41), "hello", 12,
                       quiet=True)
    assert warm.out == want
    # /metrics carries both counters
    assert reg.get("dllama_serve_steps_ahead_total").value == \
        eng.stats.steps_ahead
    assert reg.get("dllama_serve_rows_dropped_ahead_total").value == \
        eng.stats.rows_dropped_ahead
    text = reg.expose()
    assert "dllama_serve_steps_ahead_total" in text
    assert "dllama_serve_rows_dropped_ahead_total" in text


def test_a_chain_after_per_step_calls_lands_the_step_in_flight(ahead_kinds):
    """``step_many(k > 1)`` on an engine that ``step_once`` left a step in
    flight on lands it first; the streams are the per-step ones."""
    want, got = _requests("paged"), _requests("paged")
    ref = _ahead_engine(ahead_kinds, "paged")
    for r in want:
        ref.submit(r)
    _drain(ref)
    eng = _ahead_engine(ahead_kinds, "paged")
    for r in got:
        eng.submit(r)
    for _ in range(4):
        eng.step_once()
    assert eng._flight is not None
    while eng.step_many(3):
        assert eng._flight is None
    assert [r.out for r in got] == [r.out for r in want]


def test_a_fault_under_a_handed_over_row_fails_it_with_the_rest(ahead_kinds):
    """A row that has left the pool to land with the step in flight is
    still the engine's to fail when the next launch raises: no client is
    left waiting on a request no pool slot holds."""
    from distributed_llama_tpu.runtime.continuous import Request

    eng = _ahead_engine(ahead_kinds, "paged", slots=2)
    reqs = [Request(tokens=[1, 5, 9], steps=6),
            Request(tokens=[1, 22, 7], steps=20),
            Request(tokens=[1, 60], steps=9)]
    for r in reqs:
        eng.submit(r)

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    def about_to_hand_over():
        flight = eng._flight
        return flight is not None and any(eng._stops_known(s)
                                          for _, s in flight.rode())

    while not about_to_hand_over():
        eng.step_once()
    eng._decode = boom
    with pytest.raises(RuntimeError, match="injected"):
        eng.step_once()
    assert eng._leaving and not reqs[0].done.is_set()
    eng.fail_all("scheduler fault")
    assert all(r.done.is_set() and r.error == "scheduler fault"
               for r in reqs)
    assert eng._flight is None and not eng._leaving
    assert eng.audit_pages() == [] and eng.allocator.n_free == \
        eng.allocator.n_pages
