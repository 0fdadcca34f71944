"""The step program is told which rows ride (PR 63): the staged block's last
column, what the dense Q40 calls of a part-filled dispatch pick their body
by (``tests/test_q40_live_rows.py`` holds the kernel), and
``ContinuousStats.dense_diag_steps``, the landed steps whose dense leaves
took the stacked block-diagonal body. A toy model whose every leaf packs
nb-major at 8 blocks a row, the Pallas kernels in interpret mode."""

import numpy as np
import pytest

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.ops.quants import FloatType

SPEC = TransformerSpec(dim=256, hidden_dim=256, n_layers=2, n_heads=2,
                       n_kv_heads=2, vocab_size=256, seq_len=32,
                       weights_float_type=FloatType.Q40)
PAGED = dict(page_size=4, prefill_chunk=0)


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=63, scale=0.3)


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")


def _engine(tree, slots=4, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    return ContinuousEngine(SPEC, tree, slots=slots, temperature=0.0,
                            topp=0.9, seed=3, **{**PAGED, **kw})


def _requests(lengths, steps=10):
    from distributed_llama_tpu.runtime.continuous import Request

    rng = np.random.default_rng(7)
    return [Request(tokens=[1] + [int(t) for t in rng.integers(3, 250, n)],
                    steps=steps if isinstance(steps, int) else steps[i])
            for i, n in enumerate(lengths)]


def _record_launches(eng):
    """Every launch as (staged block, the flight's rows, the pool then as
    (free, request) a slot, whether it was launched on a step in flight,
    its logits)."""
    seen, launch, decode = [], eng._launch, eng._decode
    last = {}

    def staged(*args):
        last["blk"] = np.asarray(args[3]).copy()
        out = decode(*args)
        last["logits"] = out[0]
        return out

    def recording(prev, paused=()):
        pool = [(s.free, s.req) for s in eng._pool]
        flight = launch(prev, paused)
        if flight is not None:
            seen.append((last["blk"], list(flight.rows), pool,
                         prev is not None, last["logits"]))
        return flight

    eng._decode, eng._launch = staged, recording
    return seen


def _drain(eng):
    while eng.step_once():
        pass


def _cancel_in_flight(eng, reqs):
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step_once()
    assert eng._flight is not None and reqs[1] in eng._flight.reqs
    runs_ahead = eng._runs_ahead

    def cancelling(riding, paused):
        # after the iteration's sweep and before the launch behind the step
        # in flight: the row is still in the pool when the block is staged
        eng.cancel(reqs[1])
        return runs_ahead(riding, paused)

    eng._runs_ahead = cancelling
    _drain(eng)


def _starved(eng, reqs):
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    assert eng.stats.pauses > 0


SCENARIOS = {
    # name: (requests, engine keywords, driver or None)
    "free_rows": (lambda: _requests([2]), {}, None),
    "run_ahead": (lambda: _requests([2, 5, 3], [12, 9, 14]), {}, None),
    "cancelled_ahead": (lambda: _requests([2, 3, 2], 16), {},
                        _cancel_in_flight),
    "paused": (lambda: _requests([2, 2, 1], [6, 12, 10]),
               dict(slots=2, kv_pages=4, prefix_share=False), _starved),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_staged_block_marks_the_rows_that_ride(tree, scenario):
    """The block's last column is 1 on exactly the rows the flight carries:
    0 on a free row, on a row paused for a page, and on a row of the step
    in flight that was cancelled before the launch behind it (which rides
    on the scrap page at position 0); a row launched on the previous
    step's pick (override -1) is live."""
    from distributed_llama_tpu.runtime.paging import SCRAP_PAGE

    make, kw, drive = SCENARIOS[scenario]
    eng, reqs = _engine(tree, **kw), make()
    seen = _record_launches(eng)
    if drive is None:
        for r in reqs:
            eng.submit(r)
        _drain(eng)
    else:
        drive(eng, reqs)
    assert seen
    for blk, rows, pool, ahead, _ in seen:
        live = [r is not None for r in rows]
        assert blk[:, -1].tolist() == [int(v) for v in live]
        for b, (row, (free, _)) in enumerate(zip(rows, pool)):
            if free:
                assert row is None
                assert (blk[b, 2:-1] == SCRAP_PAGE).all()
    some = lambda test: any(test(*launch) for launch in seen)  # noqa: E731
    if scenario == "free_rows":
        assert all(blk[:, -1].tolist() == [1, 0, 0, 0] for blk, *_ in seen)
    if scenario == "run_ahead":
        assert eng.stats.steps_ahead > 0
        assert some(lambda blk, rows, pool, ahead, _: ahead and any(
            blk[b, 0] == -1 and blk[b, -1] == 1 for b in range(4)))
    if scenario == "cancelled_ahead":
        # still in the pool at the launch, masked like a free slot
        assert some(lambda blk, rows, pool, ahead, _: ahead and any(
            not free and req is reqs[1] and rows[b] is None
            and blk[b, :2].tolist() == [0, 0]
            and (blk[b, 2:-1] == SCRAP_PAGE).all()
            for b, (free, req) in enumerate(pool)))
    if scenario == "paused":
        # it keeps its token, position and pages, and does not ride
        assert some(lambda blk, rows, pool, ahead, _: not ahead and any(
            not free and rows[b] is None and blk[b, -1] == 0
            and (blk[b, 2:-1] != SCRAP_PAGE).any()
            for b, (free, _) in enumerate(pool)))


def test_dense_diag_steps_counts_what_the_kernel_took(tree, pallas):
    """Steps of one, two and three live rows of four: the counter is the
    landed steps of one or two, and on exactly those the program ran the
    stacked body, which gives a dead row zeros where the tile gives it the
    product of whatever it holds."""
    from distributed_llama_tpu.io.loader import Q40KernelNb
    from distributed_llama_tpu.obs.metrics import Registry

    reg = Registry()
    eng = _engine(tree, metrics=reg)
    assert isinstance(eng.params["wcls"], Q40KernelNb)
    assert eng._dense_diag_rows == 2
    seen = _record_launches(eng)
    for r in _requests([2, 2, 2], [14, 10, 6]):
        eng.submit(r)
    _drain(eng)
    by_live = {}
    for blk, rows, _, _, logits in seen:
        live = sum(r is not None for r in rows)
        dead = np.asarray(logits)[[r is None for r in rows]]
        by_live[live] = by_live.get(live, 0) + 1
        assert bool(dead.any()) == (live > 2), live
    assert set(by_live) == {1, 2, 3}
    st = eng.stats
    # every launch of this drive landed (no row stopped on a BOS)
    assert st.steps == sum(by_live.values())
    assert st.dense_diag_steps == by_live[1] + by_live[2]
    assert reg.get("dllama_dense_diag_steps_total").value == \
        st.dense_diag_steps


def test_a_paused_row_beside_a_live_one_is_a_step_of_one(tree, pallas):
    eng = _engine(tree, slots=2, kv_pages=4, prefix_share=False)
    seen = _record_launches(eng)
    _starved(eng, _requests([2, 2, 1], [6, 12, 10]))
    masked = [sum(r is not None for r in rows) for _, rows, pool, _, _ in seen
              if sum(not free for free, _ in pool) == 2]
    assert 1 in masked      # two rows in the pool, one of them riding
    assert eng.stats.dense_diag_steps == eng.stats.steps > 0


def test_no_step_counts_where_no_leaf_takes_the_body(tree):
    """The XLA matmul path (this backend's default: d-major leaves) has no
    live-row body; a mesh's program is not told its rows."""
    from distributed_llama_tpu.runtime.continuous import _dense_diag_rows

    eng = _engine(tree)
    assert eng._dense_diag_rows == 0
    for r in _requests([2]):
        eng.submit(r)
    _drain(eng)
    assert eng.stats.steps > 0 and eng.stats.dense_diag_steps == 0
    assert _dense_diag_rows({"rms": np.zeros(4)}, 8) == 0


_ONE_ROW = None     # ``inference``'s one-row step on SPEC, jitted once


@pytest.mark.parametrize("riders", [0, 1, 2])
def test_served_tokens_equal_inference_whatever_rides_beside(tree, pallas,
                                                             riders):
    """A request's greedy tokens through the part-filled dispatch (alone
    and with one co-rider: the stacked body; with two: the tile) are the
    tokens of ``inference``'s one-row step on the same packed tree."""
    import functools

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import forward, init_cache

    global _ONE_ROW
    if _ONE_ROW is None:    # one trace for the three cases
        _ONE_ROW = jax.jit(functools.partial(forward, SPEC))
    reqs = _requests([3, 2, 4][:riders + 1], 12)
    eng = _engine(tree)
    for r in reqs:
        eng.submit(r)
    _drain(eng)
    st = eng.stats      # the requests ride side by side to the same budget
    assert st.dense_diag_steps == (st.steps if riders < 2 else 0)
    r = reqs[0]
    cache, token, out = init_cache(SPEC), r.tokens[0], []
    for pos in range(r.steps):
        lg, cache = _ONE_ROW(eng.params, cache,
                             jnp.asarray([token], jnp.int32), jnp.int32(pos))
        token = (r.tokens[pos + 1] if pos + 1 < len(r.tokens)
                 else int(np.argmax(np.asarray(lg[0, :SPEC.vocab_size]))))
        if token == 1:
            break
        out.append(token)
    assert r.out == out
