"""A part-filled dense decode dispatch picks its Q40 body by the LIVE rows
(PR 63): ``ops/linear.live_rows`` tells the forward being traced which rows
ride, and ``ops/pallas_q40._q40_matmul_nbmajor`` runs the stacked
block-diagonal product on one or two of them where it ran the 8-row tile.
Interpret mode, small leaves; ``tests/test_chip_compile.py`` holds the
chip's compiler to the real shapes, ``tests/test_dense_diag_steps.py`` the
engine to its staged block and its counter."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.io.loader import Q40KernelNb
from distributed_llama_tpu.ops import pallas_q40 as pq
from distributed_llama_tpu.ops.linear import live_rows

# name: (d, blocks a row, layers or None for a 2-D leaf)
LEAVES = {"stacked-tail": (256, 40, 3),     # 40 blocks: one turn and a tail
          "2d": (128, 16, None),            # under one turn: no loop at all
          "stacked-turns": (128, 72, 2)}    # two turns and a tail
LAYER = 1


@pytest.fixture(scope="module")
def leaves():
    rng = np.random.default_rng(63)
    out = {}
    for name, (d, nb, layers) in LEAVES.items():
        lead = () if layers is None else (layers,)
        qs = rng.integers(0, 256, (*lead, 16, nb, d), dtype=np.uint8)
        scale = (rng.standard_normal((*lead, nb, d)) * 0.02).astype(
            np.float16).astype(np.float32)
        x = rng.standard_normal((8, nb * 32)).astype(np.float32)
        out[name] = (Q40KernelNb(jnp.asarray(qs), jnp.asarray(scale)), x,
                     None if layers is None else jnp.int32(LAYER))
    return out


def _float64(w, x, layer):
    """The dequantized float64 product, on the host."""
    qs, scale = np.asarray(w.qs_t), np.asarray(w.scale, np.float64)
    if layer is not None:
        qs, scale = qs[LAYER], scale[LAYER]
    codes = np.concatenate([(qs & 0xF).astype(np.float64) - 8,
                            (qs >> 4).astype(np.float64) - 8])  # (32, nb, d)
    wf = (codes * scale[None]).transpose(2, 1, 0).reshape(scale.shape[-1], -1)
    return x.astype(np.float64) @ wf.T


_TOLD = {}      # (leaf's arrays, rows) -> the jitted call: the mask is data


def _told(w, x, layer, mask):
    """``q40_matmul`` traced as a step's forward is: told which rows ride."""
    def fwd(x, mask):
        with live_rows(mask):
            return pq.q40_matmul(w, x, interpret=True, layer=layer)
    key = (id(w.qs_t), x.shape[0])
    if key not in _TOLD:
        _TOLD[key] = jax.jit(fwd)
    return np.asarray(_TOLD[key](jnp.asarray(x),
                                 jnp.asarray(mask, jnp.int32)))


def _tile(w, x, layer):
    return np.asarray(pq.q40_matmul(w, jnp.asarray(x), interpret=True,
                                    layer=layer))


@pytest.mark.parametrize("rows", [(5,), (0,), (7,), (2, 6), (0, 7), (3, 4)],
                         ids=lambda r: "rows" + "-".join(map(str, r)))
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_stacked_body_is_the_float64_product_on_the_live_rows(leaves, leaf,
                                                              rows):
    """One and two live rows at scattered indices of 8: the live rows are
    the dequantized product (nearer to it than 1e-6 of the largest output,
    as the tile is), the dead rows come back zero."""
    w, x, layer = leaves[leaf]
    mask = [int(b in rows) for b in range(8)]
    got, want = _told(w, x, layer, mask), _float64(w, x, layer)
    live, dead = list(rows), [b for b in range(8) if b not in rows]
    top = np.abs(want).max()
    assert np.abs(got[live] - want[live]).max() < 1e-6 * top
    assert np.abs(got[live] - _tile(w, x, layer)[live]).max() < 1e-6 * top
    assert not got[dead].any()


@pytest.mark.parametrize("mask", [None, (0,) * 8, (1, 0, 1, 0, 1, 0, 0, 0),
                                  (1,) * 8],
                         ids=["untold", "none-live", "three-live", "full"])
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_a_fuller_or_untold_dispatch_is_the_tile_bit_for_bit(leaves, leaf,
                                                             mask):
    """A live count over the body's top (or none live), and a call that is
    told nothing, give today's tile to the bit, dead rows included."""
    w, x, layer = leaves[leaf]
    got = _tile(w, x, layer) if mask is None else _told(w, x, layer, mask)
    assert np.array_equal(got, _tile(w, x, layer))


def test_a_dispatch_padded_to_eight_rows_keeps_its_live_rows(leaves):
    """Four slots: the rows are padded to the 8-row tile and the live
    indices still point at the caller's rows."""
    w, x, layer = leaves["stacked-tail"]
    got = _told(w, x[:4], layer, [0, 0, 1, 0])
    want = _float64(w, x[:4], layer)
    assert got.shape == want.shape
    assert np.abs(got[2] - want[2]).max() < 1e-6 * np.abs(want).max()
    assert not got[[0, 1, 3]].any()


def _jaxpr(w, rows, told):
    """The call's jaxpr at ``rows`` rows, traced where a forward spoke of
    ``told`` rows (a census that exists already: only a call that reads it
    holds it) or of none."""
    def call(x):
        return pq.q40_matmul(w, x, interpret=True)
    x = jnp.zeros((rows, w.qs_t.shape[-2] * 32), jnp.float32)
    if told is None:
        text = str(jax.make_jaxpr(call)(x))
    else:
        with live_rows(jnp.ones((told,), jnp.int32)):
            text = str(jax.make_jaxpr(call)(x))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


@pytest.mark.parametrize("rows,told", [(16, 16), (32, 32), (1, 1), (8, 4),
                                       (128, 8)])
def test_only_a_dispatch_of_the_told_rows_up_to_eight_sees_it(leaves, rows,
                                                              told):
    """``t > MULTI_T_MAX`` (a 16- or 32-slot step, a chunk), one row, and a
    call on other rows than the forward spoke of lower to the jaxpr of a
    call that is told nothing; an 8-row dispatch of the told rows holds
    the second body."""
    w = leaves["2d"][0]
    assert _jaxpr(w, rows, told) == _jaxpr(w, rows, None)
    assert "_q40_live_nb" not in _jaxpr(w, rows, None)
    assert "_q40_live_nb_2d" in _jaxpr(w, 8, 8)
    assert "_q40_live_nb" not in _jaxpr(w, 8, None)


@pytest.mark.parametrize("mask,want", [
    ((0, 0, 0, 0), (0, 0, 0)), ((0, 0, 1, 0), (1, 2, 2)),
    ((1, 0, 0, 1), (2, 0, 3)), ((0, 1, 1, 1), (3, 1, 2)),
    ((1, 1, 1, 1, 1, 1, 1, 1), (8, 0, 1)), ((0, 5), (1, 1, 1))])
def test_live_census_counts_and_finds_the_first_live_rows(mask, want):
    assert tuple(np.asarray(pq.live_census(jnp.asarray(mask)))) == want


def test_live_census_of_a_wider_stack():
    got = pq.live_census(jnp.asarray([0, 1, 0, 1, 1, 0, 0, 0]), top=4)
    assert tuple(np.asarray(got)) == (3, 1, 3, 4, 1)


@pytest.mark.parametrize("t,d,nb,want", [
    (8, 4096, 128, 2), (2, 4096, 448, 2), (5, 256, 8, 2),
    (1, 4096, 128, 0),        # one row: the matvec
    (16, 4096, 128, 0),       # over MULTI_T_MAX: the shape decides
    (8, 4096, 12, 0),         # blocks off the 8 grid: no groups of 8
    (8, 192, 128, 0)])        # no row tile of 128 lanes divides d
def test_live_rows_top_is_what_the_call_sees(t, d, nb, want):
    assert pq.live_rows_top(t, d, nb) == want


def test_the_expert_slots_share_the_stacked_product():
    """One product, two callers: the slot kernel's part-filled body is the
    dense dispatch's (``_diag_product``), not a copy of it."""
    from distributed_llama_tpu.ops import pallas_moe as pm

    assert pm._diag_product is pq._diag_product
    assert pm._diag_planes is pq._diag_planes
