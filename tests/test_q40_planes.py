"""The dense nb-major T > 1 Q40 tile's nibble planes a dot (PR 51,
``ops/pallas_q40._pick_planes``): the rule as a pure function of what a
call observes, the row tile re-derived beside it, and the tile at the
rule's pick against the float64 product and against a dot a plane, over
the block counts and row counts the benchmark's cells run.

Interpret mode on the CPU: values and shapes are looked at, never times.
"""

import numpy as np
import pytest

from q40_cell_leaves import (ARM_IDS, ARMS, CELL_NB, CONFIGS,
                             check_tile_near_float64, dense_leaves,
                             rule_triples)


@pytest.mark.parametrize("block_t", [8, 16, 32, 64, 128])
@pytest.mark.parametrize("nb", CELL_NB + (20, 256, 512, 576))
def test_rule_is_a_pure_function_and_returns_a_divisor_of_16(nb, block_t,
                                                             monkeypatch):
    """``_pick_planes`` reads (blocks a row, rows a t-tile) and nothing
    else: no environment, no flag, the same answer twice; a divisor of the
    16 nibble planes; whole sublane tiles in the merged view (a block count
    off the 8 grid keeps a dot a plane); and, where it merges at all, a
    contraction that is a whole number of 128-deep pushes."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    g = pq._pick_planes(nb, block_t)
    assert g in pq._PLANES and pq.NJ % g == 0
    assert pq._pick_planes(nb, block_t) == g
    monkeypatch.setenv("DLLAMA_Q40_PLANES", "16")   # nothing reads such a thing
    assert pq._pick_planes(nb, block_t) == g
    if g > 1:
        assert nb % 8 == 0 and g * nb % 128 == 0
        # the smallest G that fills the pushes: more planes a dot read
        # level or behind on the chip (PERF.md section 7)
        assert all(h * nb % 128 for h in pq._PLANES if h < g)
    if nb % 128 == 0 and block_t < 128:
        assert g == 1          # a plane IS whole pushes: today's dots


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_leaf_of_a_configuration_places_under_the_boundary(config):
    """Every (dense leaf, dispatch width) of a benchmark configuration gets
    a row tile that divides the leaf, rides the lanes and keeps the merged
    group's float32 planes (G nb R words) under the expert slots' measured
    boundary and the tile's own rows x nb one, with the 256-row cap under a
    full t-tile: no leaf falls to dequantize-then-dot for its planes."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    leaves = dense_leaves(config)
    assert leaves
    for d, nb in leaves:
        if pq._pick_rows_nb(d, nb) is None:
            continue            # no row tile at any G (a 576-row latent leaf)
        for t in (8, 16, 32, 128, 512):
            bt = pq._pick_block_t(t, nb)
            g = pq._pick_planes(nb, bt)
            rows = pq._pick_rows_mxu(d, nb, bt, g)
            assert rows is not None, (d, nb, t)
            assert rows % 128 == 0 and d % rows == 0, (d, nb, t, rows)
            assert g * nb * rows <= pq._MERGED_WORDS_CAP, (d, nb, t, g, rows)
            assert rows * nb <= pq._MATMUL_ROWSXNB_CAP, (d, nb, t, rows)
            assert bt >= 128 or rows <= 256, (d, nb, t, rows)
            # and no smaller than a dot a plane's tile: grid steps would
            # eat what the deeper contraction gives
            assert rows == pq._pick_rows_mxu(d, nb, bt, 1), (d, nb, t)


def test_the_cells_block_counts_are_the_configurations():
    """``CELL_NB`` is no list kept by hand alone: every block count the
    cells' dispatches meet is in it or on the 128 grid's multiples the
    rule leaves a dot a plane (256, 512) or is 32 or 576 (in the rule's
    own cases above), and the rule merges planes in most of them."""
    triples = rule_triples()
    met = {nb for nb, _, _ in triples}
    assert met <= set(CELL_NB) | {32, 256, 512, 576}, met
    assert set(CELL_NB) <= met | {56}        # 56: Yi's shard, met above too
    assert sum(g > 1 for _, _, g in triples) > len(triples) // 2


@pytest.mark.parametrize("rows,bf16", ARMS, ids=ARM_IDS)
@pytest.mark.parametrize("nb", CELL_NB)
def test_tile_at_the_rules_planes_is_as_near_float64_as_a_dot_a_plane(
        nb, rows, bf16):
    """``q40_cell_leaves.check_tile_near_float64`` through the 2-D call
    (test_q40_planes_stacked.py: through the stacked one)."""
    check_tile_near_float64(nb, rows, bf16, stacked=False)


@pytest.mark.parametrize("planes", [2, 4, 8, 16])
def test_planes_are_laid_in_the_slot_tiles_order(planes):
    """``_mxu_nb_planes`` at G planes a dot puts value j' of a group's
    block b at column j' nb + b: at 16 the expert slots' ``_merged_planes``
    to the bit, and the same bytes as a dot a plane's at every G."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops import pallas_moe as pm
    from distributed_llama_tpu.ops import pallas_q40 as pq

    nb, t = 24, 16
    x = np.random.default_rng(planes).standard_normal(
        (t, 32 * nb)).astype(np.float32)
    lo1, hi1, _ = pq._mxu_nb_planes(jnp.asarray(x), nb, t, True)
    lo, hi, rows = pq._mxu_nb_planes(jnp.asarray(x), nb, t, True, planes)
    assert rows == t and lo.shape == (16 // planes, t, planes * nb)
    for one, merged in ((lo1, lo), (hi1, hi)):
        one, merged = np.asarray(one), np.asarray(merged)
        for j in range(16):
            np.testing.assert_array_equal(
                merged[j // planes, :, j % planes * nb:(j % planes + 1) * nb],
                one[j])
    if planes == 16:
        mlo, mhi = pm._merged_planes(jnp.asarray(x), nb)
        np.testing.assert_array_equal(np.asarray(lo)[0], np.asarray(mlo))
        np.testing.assert_array_equal(np.asarray(hi)[0], np.asarray(mhi))
    # parity: the three pieces stacked a t-tile, the same columns
    plo, _, srows = pq._mxu_nb_planes(jnp.asarray(x), nb, t, False, planes)
    assert srows == pq._stack_rows(t)
    assert plo.shape == (16 // planes, srows, planes * nb)
    assert plo.dtype == jnp.bfloat16


@pytest.mark.parametrize("stacked", [False, True], ids=["2d", "stacked"])
@pytest.mark.parametrize("t", [8, 16, 32, 128])
@pytest.mark.parametrize("nb", [24, 64, 80, 160])
def test_one_pallas_call_a_leaf_under_the_old_name_at_the_rules_planes(
        nb, t, stacked):
    """Whatever the rule picks, a dispatch is still ONE Pallas call under
    the jitted name the benchmark's readers find (``_q40_mxu_nb_2d`` /
    ``_q40_mxu_nb_stacked``), and its planes are laid as the rule says."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.analysis.jaxpr_contracts import walk_fn_eqns
    from distributed_llama_tpu.io.loader import Q40KernelNb
    from distributed_llama_tpu.ops import pallas_q40 as pq

    d = 256
    lead = (2,) if stacked else ()
    w = Q40KernelNb(jax.ShapeDtypeStruct((*lead, 16, nb, d), jnp.uint8),
                    jax.ShapeDtypeStruct((*lead, nb, d), jnp.float32))
    args = (w, jax.ShapeDtypeStruct((t, nb * 32), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))

    def fn(w, x, layer):
        return pq.q40_matmul(w, x, interpret=True,
                             layer=layer if stacked else None)

    name = "_q40_mxu_nb_stacked" if stacked else "_q40_mxu_nb_2d"
    text = str(jax.make_jaxpr(fn)(*args))
    assert text.count(f"name={name}") == 1
    calls = [e for e in walk_fn_eqns(fn, *args)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    g = pq._pick_planes(nb, pq._pick_block_t(t, nb))
    planes = [v.aval.shape for v in calls[0].invars
              if v.aval.dtype == jnp.bfloat16]
    assert planes == [(16 // g, t // pq._pick_block_t(t, nb)
                       * pq._stack_rows(pq._pick_block_t(t, nb)),
                       g * nb)] * 2
