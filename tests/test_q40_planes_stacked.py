"""The dense T > 1 Q40 tile at the rule's planes a dot against float64 and
against a dot a plane, through the STACKED call (one layer of a stack, by
scalar prefetch); test_q40_planes.py has the 2-D call and the rule."""

import pytest

from q40_cell_leaves import ARM_IDS, ARMS, CELL_NB, check_tile_near_float64


@pytest.mark.parametrize("rows,bf16", ARMS, ids=ARM_IDS)
@pytest.mark.parametrize("nb", CELL_NB)
def test_stacked_tile_at_the_rules_planes_is_as_near_float64_as_a_dot_a_plane(
        nb, rows, bf16):
    check_tile_near_float64(nb, rows, bf16, stacked=True)
