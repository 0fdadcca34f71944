"""An expert spec (OLMoE's block: routed experts, top-k kept as they are,
q/k-norm) through the normal path against the plain float32 reference
(models/reference_olmoe.py), on LOGITS, at a toy size on the CPU.

Tolerance. Logits here are O(1) (max |logit| about 3). The program and the
reference are both float32 and differ by accumulation order: 3e-6 measured.
``TOL`` = 5e-5 leaves a factor of ten and is two orders under what a bf16
router or a bf16 expert dot gives (1e-2: test_bf16_*_fails prove both fail).

Near-ties. Top-k is discontinuous: where the router's k-th and (k+1)-th
logits differ by less than the two implementations' rounding, they may keep
different experts and every later position differs. So a sequence is
compared only up to the first position whose smallest router margin over
the layers (``r_(k) - r_(k+1)``, in the router's logits) is under
``MARGIN_EPS`` (1e-4: over ten times the 3e-6 the two differ by); each test
asserts that this is most of the sequence. The logit tolerance is never
widened for a flipped expert.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (Q40Weight, load_model,
                                              read_spec, to_kernel_layout_nb,
                                              write_model)
from distributed_llama_tpu.models import reference_olmoe
from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                params_to_device)
from distributed_llama_tpu.models.spec import HEADER_BYTES, TransformerSpec
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import pallas_moe
from distributed_llama_tpu.ops.quants import FloatType, dequantize_q40

TOL = 5e-5
MARGIN_EPS = 1e-4
SEQ = 48


def toy_spec(qk_norm=True, **kw):
    base = dict(dim=256, hidden_dim=128, n_layers=4, n_heads=4, n_kv_heads=4,
                vocab_size=512, seq_len=64,
                weights_float_type=FloatType.Q40, n_experts=8,
                n_active_experts=2, qk_norm=qk_norm)
    base.update(kw)
    return TransformerSpec(**base)


SPEC = toy_spec()


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=11)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)


@pytest.fixture(scope="module")
def want(tree, tokens):
    """Reference logits, margins, routed ids of the test sequence."""
    return reference_olmoe.forward(tree, SPEC, tokens)


def compared(margins, at_least):
    n = reference_olmoe.compared_positions(margins, MARGIN_EPS)
    assert n >= at_least, (f"only {n} of {len(margins)} positions before a "
                           f"router margin under {MARGIN_EPS}")
    return n


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    """The XLA expert scan (codec leaves) and the grouped Pallas kernels in
    interpret mode (nb-major leaves): the layout follows the mode."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", request.param)
    return request.param


# -- (i), (v): the whole sequence through ``forward``, q/k-norm on and off ----

@pytest.mark.parametrize("qk_norm", [True, False])
def test_forward_whole_sequence(kernel_mode, qk_norm):
    spec = toy_spec(qk_norm=qk_norm)
    tree = synth_params(spec, q40=True, seed=11)
    toks = np.random.default_rng(5).integers(3, spec.vocab_size, SEQ)
    ref, margins, _ = reference_olmoe.forward(tree, spec, toks)
    params = params_to_device(tree, spec=spec)
    assert ("moe_w13" in params) == (kernel_mode == "pallas")
    assert ("rms_q" in params) == qk_norm
    got, _ = jax.jit(lambda p, c, t: forward(spec, p, c, t, jnp.int32(0)))(
        params, init_cache(spec), jnp.asarray(toks, jnp.int32))
    n = compared(margins, SEQ * 3 // 4)   # T = 48 > 32: wide slots
    assert np.abs(np.asarray(got)[:n] - ref[:n]).max() < TOL


def test_qk_norm_changes_the_logits(tree, tokens, want):
    """The gains are in the computation: without them the logits move."""
    off = toy_spec(qk_norm=False)
    ref_off, _, _ = reference_olmoe.forward(
        {k: v for k, v in tree.items() if k not in ("rms_q", "rms_k")},
        off, tokens)
    assert np.abs(ref_off - want[0]).max() > 100 * TOL


# -- (ii): Engine.prefill in chunks, then Engine.infer through the cache -----

def test_engine_prefill_then_infer(kernel_mode, tree, tokens, want):
    from distributed_llama_tpu.runtime.generate import Engine

    ref, margins, _ = want
    eng = Engine(SPEC, tree)
    n_pre = 40
    eng.prefill([int(t) for t in tokens[:n_pre]], chunk=8)
    n = compared(margins, SEQ * 3 // 4)
    worst = 0.0
    for pos in range(n_pre, n):
        logits = eng.infer(int(tokens[pos]), pos)
        worst = max(worst, float(np.abs(logits - ref[pos]).max()))
    assert n > n_pre and worst < TOL
    # (vi) T = 1: k distinct experts a layer, every step
    steps = n - n_pre
    k, L = SPEC.n_active_experts, SPEC.n_layers
    assert eng.moe_pairs == eng.moe_active == steps * k * L


# -- (iii), (vi): ContinuousEngine, paged, uneven lengths ---------------------

def _served_rows(tree, slots, prompts, steps, **kw):
    """Run ``prompts`` through a paged ContinuousEngine and record every
    decode dispatch: (tokens, pos, the request in each slot, logits,
    counts)."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, tree, slots=slots, temperature=0.0,
                           topp=0.9, seed=3, page_size=4, prefill_chunk=4,
                           **kw)
    seen, step = [], eng._decode

    def recording(params, cache, prev_picked, blk):
        # rows are [override | pos | page table]; a row run ahead takes
        # the previous step's pick where its override is -1
        logits, picked, cache, counts = step(params, cache, prev_picked, blk)
        blk = np.asarray(blk)
        toks = np.where(blk[:, 0] >= 0, blk[:, 0], np.asarray(prev_picked))
        seen.append((toks, blk[:, 1], [s.req for s in eng._pool],
                     np.asarray(logits), np.asarray(counts)))
        return logits, picked, cache, counts

    eng._decode = recording
    reqs = [eng.submit(Request(tokens=list(p), steps=steps))
            for p in prompts]
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    return eng, reqs, seen


def test_continuous_paged_logits_and_counters(kernel_mode, tree):
    from distributed_llama_tpu.obs.metrics import Registry

    rng = np.random.default_rng(9)
    prompts = [[1] + [int(t) for t in rng.integers(3, 500, n)]
               for n in (2, 9, 5, 13, 4)]       # five requests, four slots
    reg = Registry()
    eng, reqs, seen = _served_rows(tree, 4, prompts, 24, metrics=reg)
    k, L, E = SPEC.n_active_experts, SPEC.n_layers, SPEC.n_experts
    # each request's sequence: its prompt, then the token fed at each later
    # position
    seqs = {id(r): {i: t for i, t in enumerate(r.tokens)} for r in reqs}
    for toks, pos, owners, _, _ in seen:
        for b, req in enumerate(owners):
            if req is not None:
                seqs[id(req)].setdefault(int(pos[b]), int(toks[b]))
    refs = {}
    for r in reqs:
        seq = [seqs[id(r)][i] for i in range(len(seqs[id(r)]))]
        logits, margins, routed = reference_olmoe.forward(tree, SPEC, seq)
        refs[id(r)] = (logits, compared(margins, len(seq) * 3 // 4), routed)
    worst, n_rows, n_count_steps = 0.0, 0, 0
    for toks, pos, owners, logits, counts in seen:
        assert counts.shape == (L, E) and counts.sum() == 4 * k * L
        usable = all(req is not None and int(pos[b]) < refs[id(req)][1]
                     for b, req in enumerate(owners))
        if usable:   # every row known: the counts are the reference's
            want = np.zeros((L, E), np.int64)
            for b, req in enumerate(owners):
                for layer in range(L):
                    want[layer, refs[id(req)][2][int(pos[b]), layer]] += 1
            assert (counts == want).all()
            n_count_steps += 1
        for b, req in enumerate(owners):
            if req is None or int(pos[b]) >= refs[id(req)][1]:
                continue
            worst = max(worst, float(np.abs(
                logits[b] - refs[id(req)][0][int(pos[b])]).max()))
            n_rows += 1
    assert n_rows > 60 and n_count_steps > 5 and worst < TOL
    st = eng.stats
    assert st.moe_pairs == st.steps * 4 * k * L          # rows x k x layers
    assert st.moe_active == sum(int((c > 0).sum()) for *_, c in seen)
    assert st.moe_load.sum() == st.moe_pairs and st.moe_load.shape == (E,)
    assert reg.get("dllama_moe_routed_pairs_total").value == st.moe_pairs
    assert reg.get("dllama_moe_active_experts_total").value == st.moe_active
    text = reg.expose()
    assert 'dllama_moe_expert_rows_total{expert="7"}' in text
    # how the slot kernel engaged: four rows a dispatch, slots of 8
    assert st.moe_slots == st.moe_active     # no expert can take over 8 rows
    assert st.moe_single_row_slots == sum(int((c == 1).sum())
                                          for *_, c in seen) > 0
    assert reg.get("dllama_moe_slots_total").value == st.moe_slots
    assert reg.get("dllama_moe_single_row_slots_total").value == \
        st.moe_single_row_slots


def test_dense_engine_exposes_moe_totals_at_zero():
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.obs.trace import EngineMetrics

    reg = Registry()
    EngineMetrics(reg)
    assert reg.get("dllama_moe_routed_pairs_total").value == 0
    assert "dllama_moe_expert_rows_total" not in reg.expose()


# -- a bf16 router and a bf16 expert dot both fail the tolerance -------------

def test_bf16_expert_dot_fails(tree, tokens, want):
    from distributed_llama_tpu.ops.linear import matmul_precision

    params = params_to_device(tree, spec=SPEC)

    def run(p, c, t):
        with matmul_precision("bf16"):
            return forward(SPEC, p, c, t, jnp.int32(0))

    got, _ = jax.jit(run)(params, init_cache(SPEC),
                          jnp.asarray(tokens, jnp.int32))
    assert np.abs(np.asarray(got)[:4] - want[0][:4]).max() > 10 * TOL


def test_bf16_router_fails(tree, tokens, want, monkeypatch):
    def bf16_route(gate, xb, k, router=None, bias=None):
        logits = jnp.einsum("ed,td->te", gate.astype(jnp.bfloat16),
                            xb.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)

    monkeypatch.setattr(pallas_moe, "route", bf16_route)
    params = params_to_device(tree, spec=SPEC)
    got, _ = jax.jit(lambda p, c, t: forward(SPEC, p, c, t, jnp.int32(0)))(
        params, init_cache(SPEC), jnp.asarray(tokens, jnp.int32))
    assert np.abs(np.asarray(got) - want[0]).max() > 10 * TOL


@pytest.mark.parametrize("kind", ["expert", "dense"])
def test_slot_counters_over_a_served_run(kind, tree):
    """``moe_slots`` / ``moe_single_row_slots`` are the host's arithmetic on
    the counts each step returns: sum(ceil(count / 8)) and the slots of one
    row, at 12 rows a dispatch (an expert can take over 8); a dense engine
    reads 0."""
    from distributed_llama_tpu.obs.metrics import Registry

    reg = Registry()
    rng = np.random.default_rng(5)
    prompts = [[1] + [int(t) for t in rng.integers(3, 500, 3)]
               for _ in range(12)]
    if kind == "dense":
        from distributed_llama_tpu.runtime.continuous import (
            ContinuousEngine, Request)

        spec = toy_spec(qk_norm=False, n_experts=0, n_active_experts=0,
                        weights_float_type=FloatType.F32)
        eng = ContinuousEngine(spec, synth_params(spec, q40=False, seed=2),
                               slots=2, temperature=0.0, topp=0.9, seed=3,
                               page_size=4, prefill_chunk=4, metrics=reg)
        for p in prompts[:2]:
            eng.submit(Request(tokens=list(p), steps=8))
        while eng.step_once():
            pass
        st = eng.stats
        assert st.steps > 0
        assert st.moe_slots == st.moe_single_row_slots == st.moe_pairs == 0
        assert st.prefill_chunks > 0
        assert st.moe_chunk_pairs == st.moe_chunk_slots == 0
        assert reg.get("dllama_moe_slots_total").value == 0
        assert reg.get("dllama_moe_single_row_slots_total").value == 0
        assert st.moe_diag_slots == 0 == reg.get(
            "dllama_moe_diag_slots_total").value
        return
    eng, _, seen = _served_rows(tree, 12, prompts, 10, metrics=reg)
    st = eng.stats
    counts = [c for *_, c in seen]
    assert st.moe_slots == sum(int((-(-c // 8)).sum()) for c in counts)
    assert st.moe_single_row_slots == sum(int((c % 8 == 1).sum())
                                          for c in counts)
    assert st.moe_slots > st.moe_active      # some expert took over 8 rows
    assert 0 < st.moe_single_row_slots < st.moe_slots
    assert reg.get("dllama_moe_slots_total").value == st.moe_slots
    assert reg.get("dllama_moe_single_row_slots_total").value == \
        st.moe_single_row_slots


def test_chunk_counters_are_read_at_a_landing_not_in_admit(tree, monkeypatch):
    """``moe_chunk_pairs`` / ``moe_chunk_slots``: an admission chunk's
    (L, E) counts stay on the device through ``_admit`` and are counted
    where the scheduler waits anyway, at the landing of the step launched
    behind the chunk; the decode counters leave chunks out. Chunks of 40
    rows (the wide grid) and a per-token tail (one row a slot)."""
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    reg = Registry()
    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=4, prefill_chunk=40, metrics=reg)
    chunks, deferred, fwd, admit = [], [], eng._prefill_fwd, eng._admit

    def recording(params, cache, part, *rest):
        out = fwd(params, cache, part, *rest)
        chunks.append((int(part.shape[0]), out[2]))
        return out

    def watched():
        st, n = eng.stats, len(chunks)
        before = (st.moe_chunk_pairs, st.moe_chunk_slots)
        admit()
        if len(chunks) > n:    # chunks enqueued: none of them is read yet
            assert (st.moe_chunk_pairs, st.moe_chunk_slots) == before
            assert len(eng._chunk_moe) >= len(chunks) - n
            deferred.append(len(chunks) - n)

    eng._prefill_fwd, eng._admit = recording, watched
    rng = np.random.default_rng(3)
    reqs = [eng.submit(Request(
        tokens=[1] + [int(t) for t in rng.integers(3, 500, n)], steps=52))
        for n in (44, 8, 41)]     # 40 + 4 x 1; one padded chunk; 40 + 1
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    st, k, L, E = eng.stats, SPEC.n_active_experts, SPEC.n_layers, \
        SPEC.n_experts
    assert sorted(t for t, _ in chunks) == [1] * 5 + [40] * 3
    assert sum(deferred) == len(chunks) == st.prefill_chunks
    assert not eng._chunk_moe
    pairs = sum(int(np.asarray(c).sum()) for _, c in chunks)
    slots = sum(pallas_moe.slot_census(
        np.asarray(c), pallas_moe.slot_cap(t, k, E))[0] for t, c in chunks)
    assert st.moe_chunk_pairs == pairs == (5 + 3 * 40) * k * L
    assert st.moe_chunk_slots == slots
    # a 40-row chunk fills fewer slots than pairs; a one-row one as many
    assert 5 * k * L < slots < pairs
    assert st.moe_pairs == st.steps * 2 * k * L       # decode steps alone
    assert reg.get("dllama_moe_chunk_pairs_total").value == pairs
    assert reg.get("dllama_moe_chunk_slots_total").value == slots


# -- (iv): the grouped kernels, interpret mode, against a per-pair loop ------

def _expert_stack(seed=3, L=2, E=8, hidden=128, dim=256):
    rng = np.random.default_rng(seed)

    def q40(d, n):
        from distributed_llama_tpu.ops.quants import quantize_q40

        return Q40Weight(*quantize_q40(
            (rng.standard_normal((L, E, d, n)) / np.sqrt(n)
             ).astype(np.float32)))

    w1, w2, w3 = q40(hidden, dim), q40(dim, hidden), q40(hidden, dim)
    nb1, nb3 = to_kernel_layout_nb(w1), to_kernel_layout_nb(w3)
    w13 = type(nb1)(np.concatenate([nb1.qs_t, nb3.qs_t], -1),
                    np.concatenate([nb1.scale, nb3.scale], -1))
    dense = [dequantize_q40(w.qs, w.d16).astype(np.float64)
             for w in (w1, w2, w3)]
    return w13, to_kernel_layout_nb(w2), dense


def _pair_loop(dense, layer, x, topw, topi):
    w1, w2, w3 = (w[layer] for w in dense)
    y = np.zeros((x.shape[0], w2.shape[1]))
    for t in range(x.shape[0]):
        for wgt, e in zip(topw[t], topi[t]):
            if e < 0:                       # held elsewhere: contributes 0
                continue
            g, u = w1[e] @ x[t], w3[e] @ x[t]
            y[t] += wgt * (w2[e] @ (g / (1 + np.exp(-g)) * u))
    return y


def _routing(case, rows, k=2, n_experts=8, seed=0):
    rng = np.random.default_rng(seed)
    if case in ("random", "held_share"):
        topi = np.stack([rng.choice(n_experts, k, replace=False)
                         for _ in range(rows)])
        if case == "held_share":            # experts 5.. live on other chips
            topi = np.where(topi < 5, topi, -1)
    elif case == "rows_share_every_expert":
        topi = np.tile(np.array([[5, 2]]), (rows, 1))
    elif case.startswith("fill"):
        # expert 5 takes exactly n rows (one slot up to the capacity, two
        # one past it; ``fillcap``: the dispatch's own capacity); the other
        # choices go round the rest
        cap = pallas_moe.slot_cap(rows, k, n_experts)
        n = min({"cap": cap, "cap+1": cap + 1}.get(
            case[4:]) or int(case[4:]), rows)
        others = [e for e in range(n_experts) if e != 5]
        topi = np.array([[5 if t < n else others[(2 * t + 1) % 7],
                          others[(2 * t) % 7]] for t in range(rows)])
    else:                                   # one expert takes every row
        topi = np.stack([[3, (4 + t) % n_experts if (4 + t) % n_experts != 3
                          else 0] for t in range(rows)])
    topw = rng.random((rows, k)).astype(np.float32)
    return np.where(topi >= 0, topw, 0).astype(np.float32), \
        topi.astype(np.int32)


SLOT_CASES = ["random", "rows_share_every_expert",
              "one_expert_takes_every_row", "held_share", "fill1", "fill2",
              "fill7", "fill8", "fill9"]
# a dispatch wider than MOE_SLOT_T_MAX rows (a prefill chunk) takes the same
# grid at the capacity its shape gives; 13 rows: the narrow grid, rows past
# a sublane tile
WIDE_CASES = ["random", "one_expert_takes_every_row", "held_share", "fill1",
              "fillcap", "fillcap+1"]


@pytest.mark.parametrize("rows,case", [
    *((r, c) for r in (1, 3, 8, 16) for c in SLOT_CASES),  # 1: one-row body
    *((r, c) for r in (13, 40, 128) for c in WIDE_CASES)])
def test_slot_kernel_matches_pair_loop(rows, case):
    w13, w2, dense = _expert_stack()
    x = np.random.default_rng(rows).standard_normal(
        (rows, 256)).astype(np.float32)
    topw, topi = _routing(case, rows)
    layer = 1
    got, counts = pallas_moe._experts_slots(
        jnp.asarray([layer], jnp.int32), w13, w2, jnp.asarray(x),
        jnp.asarray(topw), jnp.asarray(topi), 8, True)
    want = _pair_loop(dense, layer, x.astype(np.float64), topw, topi)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert (np.asarray(counts) == np.bincount(topi[topi >= 0],
                                              minlength=8)).all()


def _q40_leaf(rng, n_exp, d, nb):
    """(nb-major leaf, float64 dense (E, d, n)) of a random expert stack."""
    from distributed_llama_tpu.ops.quants import quantize_q40

    n = nb * 32
    w = Q40Weight(*quantize_q40((rng.standard_normal((1, n_exp, d, n))
                                 / np.sqrt(n)).astype(np.float32)))
    return (to_kernel_layout_nb(w),
            dequantize_q40(w.qs, w.d16).astype(np.float64)[0])


def _slot_call(leaf, slot_expert, live, fills, xs, block_rows=128):
    """One call of the slot kernel, traced anew (a test may have changed
    ``MOE_DIAG_ROWS``, which the jitted entry's cache does not see)."""
    return np.asarray(jax.jit(functools.partial(
        pallas_moe.moe_q40_slots.__wrapped__, block_rows=block_rows,
        interpret=True))(
        jnp.zeros((1,), jnp.int32), jnp.asarray(slot_expert, jnp.int32),
        jnp.int32(live), jnp.asarray(fills, jnp.int32), leaf.qs_t,
        leaf.scale, jnp.asarray(xs)))


@pytest.mark.parametrize("fill", [1, 2, 3, 4, 5, 6, 7, 8])
# DeepSeek-V3's w13 and w2, MiMo's w13, OLMoE's w2
@pytest.mark.parametrize("nb", [224, 64, 128, 32])
def test_slot_call_matches_dense_at_deepseeks_block_counts(nb, fill):
    """One call of the slot kernel on a leaf of ``nb`` blocks a row at a toy
    ``d``: a slot of ``fill`` rows (1 or 2: the block-diagonal body; 3 to 8
    the tile), a full tile, a dead slot behind them."""
    rng = np.random.default_rng(nb + fill)
    leaf, dense = _q40_leaf(rng, 3, 128, nb)
    xs = rng.standard_normal((3, 8, nb * 32)).astype(np.float32)
    # slot 2 is dead
    got = _slot_call(leaf, [0, 2, 2], 2, [fill, 8, 0], xs)
    for a, (e, live) in enumerate(((0, fill), (2, 8))):
        want = xs[a, :live].astype(np.float64) @ dense[e].T
        assert np.abs(got[a, :live] - want).max() < 1e-5


MIXED_FILLS = [1, 5, 3, 8, 2, 4, 7, 6, 0, 0]          # the last two dead
MIXED_EXPERTS = [0, 0, 1, 1, 1, 2, 2, 2, 2, 2]


@pytest.mark.parametrize("nb", [32, 64, 128, 224])
def test_mixed_fills_in_one_grid_and_their_distance(nb, monkeypatch):
    """Every live-row count in ONE grid of two row tiles a slot (the planes
    are built at a slot's first tile and read at its second), against the
    float64 product: the block-diagonal body (1 or 2 rows) is no further
    from it than ``_row_body`` is at one row, the body's own class (both
    fold the ``- 8`` out of raw codes; the tile multiplies code - 8 and
    reads closer still, and no fill is past ITS distance by more than that
    class allows); the fuller slots are the parent's tile bit for bit; dead
    slots write nothing (interpret mode leaves NaN where nothing was
    written)."""
    rng = np.random.default_rng(nb)
    leaf, dense = _q40_leaf(rng, 3, 256, nb)
    xs = rng.standard_normal((len(MIXED_FILLS), 8, nb * 32)
                             ).astype(np.float32)

    def distances(got):
        out = {}
        for a, (e, fill) in enumerate(zip(MIXED_EXPERTS, MIXED_FILLS)):
            if fill:
                want = xs[a, :fill].astype(np.float64) @ dense[e].T
                out[fill] = (np.abs(got[a, :fill] - want).max()
                             / np.abs(want).max())
        return out

    got = _slot_call(leaf, MIXED_EXPERTS, 8, MIXED_FILLS, xs)
    assert np.isnan(got[8:]).all()
    new = distances(got)
    monkeypatch.setattr(pallas_moe, "MOE_DIAG_ROWS", 0)   # the parent's
    old_got = _slot_call(leaf, MIXED_EXPERTS, 8, MIXED_FILLS, xs)
    old = distances(old_got)
    assert np.isnan(old_got[8:]).all()
    assert new[1] <= 1.1 * old[1] < 1e-6
    assert new[2] <= 1.1 * max(old[2], old[1])
    for a, fill in enumerate(MIXED_FILLS):
        if fill > 2:
            assert (got[a, :fill] == old_got[a, :fill]).all()


def test_a_leaf_off_the_8_grid_keeps_the_row_body(monkeypatch):
    """The body is picked statically by the leaf's block count, so only one
    of the two part-filled bodies is traced: at 12 blocks a row
    ``_diag_body`` is never reached, at 16 ``_row_body`` is not."""
    def never(*a, **k):
        raise AssertionError("traced")

    rng = np.random.default_rng(12)
    for nb, body in ((12, "_diag_body"), (16, "_row_body")):
        assert (pallas_moe.diag_rows(8, nb) == 0) == (nb == 12)
        leaf, dense = _q40_leaf(rng, 2, 128, nb)
        xs = rng.standard_normal((3, 8, nb * 32)).astype(np.float32)
        with monkeypatch.context() as m:
            m.setattr(pallas_moe, body, never)
            got = _slot_call(leaf, [0, 1, 1], 2, [1, 3, 0], xs)
        for a, (e, live) in enumerate(((0, 1), (1, 3))):
            want = xs[a, :live].astype(np.float64) @ dense[e].T
            assert np.abs(got[a, :live] - want).max() < 1e-5
    # a wide dispatch's slots (a chunk's) keep the parent's bodies
    assert pallas_moe.diag_rows(32, 64) == 0 == pallas_moe.diag_rows(16, 64)
    assert pallas_moe.diag_rows(8, 64) == 2 and pallas_moe.diag_rows(1, 64) == 1


def _count_equations(jaxpr) -> tuple[int, int]:
    """(equations, those that hold a jaxpr of their own: a branch, a loop, a
    jitted call) of a jaxpr, through every sub-jaxpr its equations hold (a
    ``pallas_call``'s kernel, a branch, a loop's body)."""
    def subs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from subs(x)

    total = nested = 0
    for e in jaxpr.eqns:
        inner = [j for v in e.params.values() for j in subs(v)]
        counts = [_count_equations(j) for j in inner]
        total += 1 + sum(c[0] for c in counts)
        nested += bool(inner) + sum(c[1] for c in counts)
    return total, nested


def _kernel_equations(cap: int) -> tuple[int, int, str]:
    """(equations in the slot kernel's body, its nested jaxprs, the call's
    name) at OLMoE's ``w13`` shape (64 blocks a row, 2048 rows, one
    row tile up to 16 rows a slot) and ``cap`` rows a slot."""
    nb, d, a = 64, 2048, 16
    sd = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(functools.partial(
        pallas_moe.moe_q40_slots.__wrapped__,
        block_rows=pallas_moe._slot_block_rows(d, nb, cap),
        interpret=False))(
        sd((1,), jnp.int32), sd((a,), jnp.int32), sd((), jnp.int32),
        sd((a,), jnp.int32), sd((1, 4, 16, nb, d), jnp.uint8),
        sd((1, 4, nb, d), jnp.float32), sd((a, cap, nb * 32), jnp.float32))
    call, = (e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call")
    return (*_count_equations(call.params["jaxpr"]), call.params["name"])


# What a run pays at start-up for every program that holds the kernel is the
# tracing and lowering of its body, which only the chip's set-up showed (PR
# 53: a body a row count up to 3, ``setup_s`` +5.5 s in OLMoE's cell:
# refused). What costs is what is traced on its own (a branch, a loop, a
# jitted ``jnp`` call: each a jaxpr of its own) more than the equations: PR
# 54's first form, one body whose row count was a loop bound, held as many
# equations as this one and traced as slowly as PR 53's (PERF.md section 7);
# 18 of this kernel's 24 are ``ops/pallas_q40._diag_planes_nb``'s ``//``,
# ``%`` and ``jnp.where`` (ROADMAP S9 c). (equations, nested jaxprs) by rows
# a slot: the parent's (commit 9a7a6d5) beside this tree's; PR 53's as
# refused: (1045, 67) and (1193, 69).
PARENT_SLOT_KERNEL = {8: (309, 2), 32: (457, 4)}
SLOT_KERNEL = {8: (340, 24), 32: (457, 4)}


@pytest.mark.parametrize("cap", [8, 32])
def test_the_slot_kernels_traced_size_is_pinned(cap, monkeypatch):
    *count, name = _kernel_equations(cap)
    assert name == ("moe_q40_slots" if cap == 8 else "moe_q40_grouped")
    assert tuple(count) == SLOT_KERNEL[cap]
    assert count[0] <= 1.5 * PARENT_SLOT_KERNEL[cap][0]
    if cap == 32:        # a chunk's kernel is the parent's, body for body
        assert tuple(count) == PARENT_SLOT_KERNEL[cap]
    monkeypatch.setattr(pallas_moe, "MOE_DIAG_ROWS", 0)
    assert _kernel_equations(cap)[:2] == PARENT_SLOT_KERNEL[cap]


@pytest.mark.parametrize("rows", [40, 13])   # a wide dispatch, a narrow one
def test_fast_prefill_mode_reaches_the_tile_at_every_width(rows):
    """``bf16`` (fast-prefill's ``matmul_mode``) multiplies the tile in
    bfloat16: off the float32 result by bfloat16's rounding and no more;
    without it the same call is the float32 one."""
    w13, w2, dense = _expert_stack()
    x = np.random.default_rng(rows).standard_normal(
        (rows, 256)).astype(np.float32)
    topw, topi = _routing("random", rows, seed=2)
    want = _pair_loop(dense, 0, x.astype(np.float64), topw, topi)
    err = {}
    for bf16 in (False, True):
        got, counts = pallas_moe._experts_slots(
            jnp.asarray([0], jnp.int32), w13, w2, jnp.asarray(x),
            jnp.asarray(topw), jnp.asarray(topi), 8, True, bf16)
        err[bf16] = np.abs(np.asarray(got) - want).max()
        assert int(np.asarray(counts).sum()) == rows * 2
    assert err[False] < 1e-5 < 1e-3 < err[True] < 5e-2


def test_a_wide_dispatch_is_the_sum_of_its_rows(monkeypatch):
    """``moe_ffn`` over a 128-row chunk against 128 one-row dispatches
    (the T == 1 grid: one row a slot, the exact body)."""
    from distributed_llama_tpu.models.llama import (layer_view,
                                                    split_layer_weights)

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    params = params_to_device(synth_params(SPEC, q40=True, seed=11),
                              spec=SPEC)
    stacked, scanned = split_layer_weights(params)
    lw = layer_view(stacked, {k: v[2] for k, v in scanned.items()}, 2)
    h = jnp.asarray(np.random.default_rng(7).standard_normal(
        (128, SPEC.dim)).astype(np.float32))
    wide, counts = jax.jit(lambda h: pallas_moe.moe_ffn(SPEC, lw, h))(h)
    one = jax.jit(lambda r: pallas_moe.moe_ffn(SPEC, lw, r))
    rows = [one(h[t:t + 1]) for t in range(128)]
    assert np.abs(np.asarray(wide) - np.concatenate(
        [np.asarray(y) for y, _ in rows])).max() < 1e-5
    assert (np.asarray(counts) == sum(np.asarray(c) for _, c in rows)).all()


@pytest.mark.parametrize("rows,k,n_experts,cap", [
    (16, 8, 64, 4), (1, 8, 64, 1), (3, 2, 8, 3), (32, 8, 64, 4),
    (16, 2, 8, 4), (16, 8, 64, 8), (32, 8, 64, 8), (3, 2, 8, 8),
    (16, 2, 8, 8), (32, 8, 32, 8)])
def test_build_slots_places_every_pair_once(rows, k, n_experts, cap):
    rng = np.random.default_rng(rows * k)
    topi = np.stack([rng.choice(n_experts, k, replace=False)
                     for _ in range(rows)]).astype(np.int32)
    (slot_expert, n_slots, fill, slot_rows, pair_slot, pair_lane,
     counts) = map(np.asarray, pallas_moe.build_slots(
         jnp.asarray(topi), n_experts, cap))
    a = pallas_moe.max_slots(rows, k, n_experts, cap)
    assert slot_expert.shape == (a,) and n_slots <= a
    assert n_slots == sum(-(-c // cap) for c in counts)
    assert (np.diff(slot_expert) >= 0).all()          # experts ascending
    assert (slot_expert[n_slots:] == slot_expert[n_slots - 1]).all()
    assert (fill[:n_slots] >= 1).all() and not fill[n_slots:].any()
    assert (np.bincount(slot_expert[:n_slots], weights=fill[:n_slots],
                        minlength=n_experts) == counts).all()
    places = set()
    for t in range(rows):
        for j in range(k):
            s, lane = pair_slot[t, j], pair_lane[t, j]
            assert s < n_slots and slot_expert[s] == topi[t, j]
            assert slot_rows[s, lane] == t and lane < fill[s]
            places.add((s, lane))
    assert len(places) == rows * k                    # no two pairs collide


@pytest.mark.parametrize("rows,n_experts,cap", [
    (16, 64, 4), (16, 64, 8),
    # a 128-row chunk: OLMoE's 64 experts, DeepSeek-V3's 32 held
    *((128, e, c) for e in (64, 32) for c in (8, 16, 32))])
def test_worst_case_routing_fits_the_static_slot_bound(rows, n_experts, cap):
    """Every row to the same k experts (the most slots one expert takes),
    and every expert one row past a whole number of slots (the most
    part-filled slots): both inside ``max_slots``."""
    bound = pallas_moe.max_slots(rows, 8, n_experts, cap)
    topi = np.tile(np.arange(8, dtype=np.int32), (rows, 1))
    _, n_slots, fill, *_ = pallas_moe.build_slots(
        jnp.asarray(topi), n_experts, cap)
    assert int(n_slots) == 8 * (rows // cap) <= bound
    assert (np.asarray(fill)[:int(n_slots)] == cap).all()
    # spread: row t takes experts 8 t .. 8 t + 7 (mod E)
    topi = (8 * np.arange(rows)[:, None] + np.arange(8)) % n_experts
    _, n_slots, *_ = pallas_moe.build_slots(
        jnp.asarray(topi, jnp.int32), n_experts, cap)
    per = rows * 8 // n_experts
    assert int(n_slots) == n_experts * -(-per // cap) <= bound


def test_slot_cap_of_a_narrow_dispatch_is_pinned():
    """T == 1 and T <= 32 lower to the programs they always did: one row,
    one sublane tile, whatever k and E; wider, a multiple of 8."""
    for k, e in ((8, 64), (8, 32), (2, 8)):
        assert pallas_moe.slot_cap(1, k, e) == 1
        assert {pallas_moe.slot_cap(t, k, e) for t in range(2, 33)} == {8}
        for t in (33, 40, 128, 256):
            cap = pallas_moe.slot_cap(t, k, e)
            assert cap % 8 == 0 and 8 < cap <= -(-t // 8) * 8


@pytest.mark.parametrize("nb", [64, 20])   # a leaf the body takes; one off the grid
@pytest.mark.parametrize("rows,slots", [(1, 1), (16, 8), (32, 8),
                                        (40, 24), (128, 32)])
def test_slot_census_counts_what_build_slots_builds(rows, slots, nb):
    """The host's arithmetic for the counters against the device's slots."""
    assert pallas_moe.slot_cap(rows, 4, 16) == slots
    rng = np.random.default_rng(rows)
    topi = np.stack([rng.choice(16, 4, replace=False) for _ in range(rows)])
    counts = np.bincount(topi.ravel(), minlength=16)
    top = pallas_moe.diag_rows(slots, nb)
    assert top == (min(slots, 2) if nb == 64 and slots <= 8 else 0)
    live, single, diag = pallas_moe.slot_census(counts, slots, top)
    _, n_slots, fill, *_ = pallas_moe.build_slots(
        jnp.asarray(topi, jnp.int32), 16, slots)
    fill = np.asarray(fill)
    assert live == int(n_slots) == sum(-(-c // slots) for c in counts)
    assert single == int((fill == 1).sum())
    assert diag == int(((fill >= 1) & (fill <= top)).sum())
    assert (diag > 0) == (top > 0) and diag <= live


@pytest.mark.parametrize("hidden,took", [(128, False), (256, True)])
def test_the_engines_census_asks_both_leaves(hidden, took):
    """A slot counts as one that took the block-diagonal body where BOTH of
    an expert's leaves admit it (``w13`` has dim / 32 blocks a row, ``w2``
    hidden / 32): the toy spec's hidden 128 is 4 blocks, off the grid."""
    from types import SimpleNamespace

    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    eng = SimpleNamespace(spec=SimpleNamespace(
        n_active_experts=2, dim=256, hidden_dim=hidden))
    local = np.array([[0, 1, 4, 5, 9, 12, 8, 3]])      # rows an expert, 1 layer
    live, single, diag = ContinuousEngine._slot_census(eng, local, 16)
    assert (live, single) == (9, 2)          # 9 and 12 open a second slot
    assert diag == (2 if took else 0)        # last fills 1, 4, 5, 1, 4, 3


# -- (vii): what refuses an expert spec --------------------------------------

def test_tp_refuses_an_expert_spec(tree):
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.tp import (param_specs,
                                                   validate_sharding)
    from distributed_llama_tpu.runtime.generate import Engine

    mesh = make_mesh(tp=2)
    with pytest.raises(ValueError, match="one chip only"):
        validate_sharding(SPEC, mesh)
    with pytest.raises(ValueError, match="one chip only"):
        param_specs(tree)
    with pytest.raises(ValueError, match="one chip only"):
        Engine(SPEC, tree, mesh=mesh)


def test_cli_tp_refuses_an_expert_model(tmp_path, capsys):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, SPEC, seed=1)
    write_synth_tokenizer(tok, SPEC.vocab_size)
    rc = cli.main(["inference", "--model", model, "--tokenizer", tok,
                   "--prompt", "hi", "--steps", "4", "--tp", "2",
                   "--weights-float-type", "q40"])
    assert rc == 2 and "one chip only" in capsys.readouterr().err


def test_fused_forward_switch_refuses_an_expert_spec(tree, monkeypatch):
    from distributed_llama_tpu.runtime.generate import Engine

    monkeypatch.setenv("DLLAMA_LAYER_FUSION", "on")
    with pytest.raises(ValueError, match="DLLAMA_LAYER_FUSION"):
        Engine(SPEC, tree)
    params = params_to_device(tree)            # packed without the spec
    with pytest.raises(ValueError, match="DLLAMA_LAYER_FUSION"):
        forward(SPEC, params, init_cache(SPEC), jnp.asarray([5], jnp.int32),
                jnp.int32(0))


def test_spec_rejects_half_an_expert_description():
    with pytest.raises(ValueError, match="n_active_experts"):
        toy_spec(n_active_experts=0)
    with pytest.raises(ValueError, match="n_active_experts"):
        toy_spec(n_experts=2, n_active_experts=3)


# -- (viii): the file format --------------------------------------------------

@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_write_load_round_trip(tmp_path, ftype):
    spec = toy_spec(weights_float_type=ftype, n_layers=2)
    dense = synth_params(spec, q40=False, seed=4)
    path = str(tmp_path / "m.bin")
    write_model(path, spec, dense)
    assert read_spec(path, ftype) == spec
    got_spec, got = load_model(path, weights_float_type=ftype)
    assert got_spec == spec and set(got) == set(dense)
    for name, val in dense.items():
        have = got[name]
        if isinstance(have, Q40Weight):
            assert have.qs.shape[:-2] == val.shape[:-1]
            have = dequantize_q40(have.qs, have.d16)
            assert np.abs(have - val).max() < 0.03   # Q40 rounding: delta / 2
        else:
            assert np.array_equal(have, val)
    ref, _, _ = reference_olmoe.forward(got, spec, [1, 7, 9])
    assert np.isfinite(ref).all()


def test_old_header_loads_byte_for_byte(tmp_path):
    """A dense spec writes the 28-byte header and reads it back: nothing of
    the extension reaches a file that has no experts."""
    spec = TransformerSpec(64, 128, 2, 4, 2, 96, 32, FloatType.F32)
    assert not spec.extended and len(spec.header()) == HEADER_BYTES == 28
    assert spec.header() == np.array([64, 128, 2, 4, 2, 96, 32],
                                     "<i4").tobytes()
    path = str(tmp_path / "d.bin")
    write_model(path, spec, synth_params(spec, q40=False, seed=2))
    got_spec, got = load_model(path)
    assert got_spec == spec and "moe_gate" not in got and "rms_q" not in got
    with open(path, "rb") as fh:
        assert fh.read(28) == spec.header()


def test_extended_header_and_sizes():
    ext = SPEC.header()
    assert len(ext) == SPEC.header_bytes == 52
    assert TransformerSpec.from_header(ext, FloatType.Q40) == SPEC
    assert np.frombuffer(ext, "<i4")[0] < 0       # no dim reads as this
    with pytest.raises(ValueError, match="version"):
        TransformerSpec.from_header(
            ext[:4] + np.array([9], "<i4").tobytes() + ext[8:])
    # the published widths: 3.83 GB of Q40 matmul weights, 4.26 GB a file
    olmoe = TransformerSpec(2048, 1024, 16, 16, 16, 50304, 4096,
                            FloatType.Q40, n_experts=64, n_active_experts=8,
                            qk_norm=True)
    matmul = olmoe.n_layers * sum(
        c * olmoe.matmul_bytes(s) for s, c in olmoe.matmul_shape_counts()
    ) + olmoe.matmul_bytes((olmoe.vocab_size, olmoe.dim))
    assert round(matmul / 1e9, 2) == 3.83
    assert round(olmoe.file_size() / 1e9, 2) == 4.26


def test_tensor_byte_ranges_cover_an_expert_file():
    from distributed_llama_tpu.io.loader import tensor_byte_ranges

    ranges = tensor_byte_ranges(SPEC)
    assert ranges[0].offset == SPEC.header_bytes
    assert sum(r.nbytes for r in ranges) + SPEC.header_bytes \
        == SPEC.file_size()
    per_layer = [r.name for r in ranges if r.layer == 0]
    assert per_layer[:9] == ["rms_att", "rms_ffn", "rms_q", "rms_k", "wq",
                             "wk", "wv", "wo", "moe_gate"]
    assert per_layer[9:12] == ["moe_w1", "moe_w2", "moe_w3"]
    assert len(per_layer) == 9 + 3 * SPEC.n_experts


def test_synth_model_file_runs_through_the_cli(tmp_path, capsys):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    size = write_synth_q40_model(model, SPEC, seed=1)
    assert size == SPEC.file_size()
    write_synth_tokenizer(tok, SPEC.vocab_size)
    rc = cli.main(["inference", "--model", model, "--tokenizer", tok,
                   "--prompt", "hello", "--steps", "12", "--tp", "1",
                   "--temperature", "0", "--weights-float-type", "q40"])
    out = capsys.readouterr().out
    assert not rc and "Routed experts:" in out and "nExperts: 8" in out


def test_converter_permutes_the_qk_gains_with_the_rows():
    """convert.py's interleaving of wq / wk rows, applied to the gains: the
    norm-then-rotate of the published rotate-half form and of this
    program's interleaved pairs give the same attention scores."""
    from distributed_llama_tpu.convert import HFCheckpoint

    un = HFCheckpoint._unpermute
    n_heads, hs, n = 2, 8, 16
    rng = np.random.default_rng(0)
    w, gain = rng.standard_normal((n_heads * hs, n)), rng.random(n_heads * hs)
    x = rng.standard_normal(n)
    published = (w @ x) * gain                     # rotate-half order
    ours = (un(None, w, n_heads) @ x) * un(None, gain, n_heads)
    assert np.allclose(un(None, published, n_heads), ours)
    half = published.reshape(n_heads, 2, hs // 2)
    pairs = ours.reshape(n_heads, hs // 2, 2)
    assert np.allclose(half[:, 0], pairs[..., 0])
    assert np.allclose(half[:, 1], pairs[..., 1])


# -- the analysis tools count an expert spec or refuse it --------------------

def test_memory_model_counts_the_experts():
    from distributed_llama_tpu.analysis import memory_model

    olmoe = TransformerSpec(2048, 1024, 16, 16, 16, 50304, 4096,
                            FloatType.Q40, n_experts=64, n_active_experts=8,
                            qk_norm=True)
    got = memory_model.weights_device_bytes(olmoe, 1)
    with pytest.raises(ValueError, match="one chip only"):
        memory_model.weights_device_bytes(olmoe, 4)
    assert 3.8e9 < got < 4.4e9


def test_body_policy_counts_the_experts(monkeypatch):
    from distributed_llama_tpu.ops import linear
    from distributed_llama_tpu.ops.linear import q40_body_policy

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    olmoe = TransformerSpec(2048, 1024, 16, 16, 16, 50304, 4096,
                            FloatType.Q40, n_experts=64, n_active_experts=8,
                            qk_norm=True)
    policy, reason = q40_body_policy(olmoe, rows=16)
    assert policy == "d-major" and "3.8 GB" in reason
    monkeypatch.setattr(linear, "Q40_I4_MAX_PACKED_GB", 3.0)
    assert "exceeds" in q40_body_policy(olmoe, rows=16)[1]
