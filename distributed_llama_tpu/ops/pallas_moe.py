"""Routed experts (mixture-of-experts FFN) over Q40 weights.

The FFN sub-block of an expert spec (``TransformerSpec.n_experts > 0``):

  r = W_g x                      router logits, float32 at HIGHEST: it decides
                                 WHICH experts run, so no Q40 and no bf16
  p = softmax(r)                 over all E experts
  keep the k largest p AS THEY ARE (no renormalisation)
  y = sum_e p_e * w2_e( silu(w1_e x) * w3_e x )      over the kept e

``TransformerSpec.router`` states the other kind (``route``): sigmoid
scores, a choice on score + bias limited to the best groups, weights from
the unbiased scores, renormalised and scaled. And the spec's layout may
hold a SHARE of the experts (``n_experts_held`` from ``layout.offset``): the
router keeps its full width, the layer computes the pairs that landed on
the experts held here and drops the rest (no stand-in for the chips that
hold the others, no exchange), and the counts returned keep the router's
width, so the share of pairs that landed here can be read.

Expert weights stay Q40, stacked (L, E, ...) in the nb-major kernel layout
(io/loader.Q40KernelNb: the output dim rides the lanes, so OLMoE's block
counts 64 and 32 pad nothing and the chip stores the stack as it is packed;
a d-major leaf of that shape would be copied at the top of every step,
ops/linear.q40_leaf_layout). ``w1`` and ``w3`` are fused at load into
``moe_w13`` (ops/linear.fuse_q40_layer_matmuls). An expert that is NOT
gated (``spec.activation.gated`` false: ``w2_e(act(w1_e x))``, one up matrix
and no product) has no ``w3``: its two calls run over ``moe_w1`` and
``moe_w2`` as they are, and where the model's layout pads block counts
(ops/linear.Q40Layout.pad_blocks) a hidden width off the grid is packed with
zero rows / zero blocks up to it (ops/linear.pack_q40_params: exact, act(0)
= 0 meets zero rows of ``w2``) and the rows are padded with zeros to the
up stack's blocks.

ONE grouped matmul at every dispatch width: only the (row, expert) pairs
the router chose are computed. The dispatch's pairs are grouped into SLOTS
of one expert and up to C rows; the grid walks the slots from a
scalar-prefetched list, experts ascending, so a distinct expert's tile is
fetched ONCE (a second slot of the same expert repeats the block index and
Pallas skips the copy; in the decode slots' kernel, whose grid walks a
slot's row tiles before the next slot, once a slot: ``moe_q40_slots``),
never all E and never once per pair. The slot count
is a static bound (every expert active, every capacity overflowing); the
live count is data, and the steps past it are skipped and repeat the last
live slot's block indices, so they move nothing. A full slot opens another:
no pair is dropped.

C is read from the dispatch's static shape (``slot_cap``): one row at
T == 1; one sublane tile, ``MOE_SLOT_ROWS`` = 8, up to ``MOE_SLOT_T_MAX``
= 32 rows (decode, verify, small mixed dispatches: the call is named
``moe_q40_slots`` in a capture); wider (a prefill chunk: ``moe_q40_grouped``)
twice the rows an expert expects, T k / E, from 16 to 32, because the tile's
time grows with its rows and an expert's second slot unpacks its tile again
(OLMoE's 128-row chunk, 16 rows an expert and the busiest twice that: 32;
a share of DeepSeek-V3's experts: 32 by its shape, 4 rows an expert in
fact). Until PR 36 a dispatch wider than 32 rows ran EVERY held expert over
EVERY row and weighted the rows an expert was not routed by 0: 8 and 32
times the routed work of those two chunks.

The slot's LIVE ROW COUNT (a fourth prefetched list, ``fill``) and the
leaf's block count alone pick its body (``diag_rows``). In the decode slots'
kernel (up to 8 rows a slot, and the one row of T == 1) on a leaf whose
block count is a multiple of 8 (every model's), a slot of 1 or 2 live rows
takes ``_diag_body``: the dense T = 1 matvec's block-diagonal MXU product
(ops/pallas_q40, PR 49: raw codes pushed to the MXU against a row's three
bf16 pieces laid block-diagonal over groups of 8 blocks, the ``- 8`` fold and
the scale applied to a block's (8, R) product and not to a weight), ONE
static body of two stacked rows, 48 left-hand rows a group; the live rows'
planes are built in the kernel by a loop over them. (PR 53 unrolled a body a
row count up to 3: faster at 3 rows, within 2 to 5 % at 1 and 2, and refused
for what three bodies cost every program's tracing at start-up; one body
whose row count is a loop bound, PR 54's first form, ran at half the speed:
PERF.md sections 6 and 7.) On any other leaf a slot of one row takes
``_row_body`` (the nb-major vector matvec's arithmetic, 8 vector operations a
packed byte), picked statically, so only one of the two is traced. Any
fuller slot, and every slot of over one row in a wider dispatch's kernel (a
chunk's, whose slots are mostly full: it is the kernel it was, body for
body), takes the MXU tile ``_mxu_body_merged`` (the dense T > 1 tile's
arithmetic, ``ops/pallas_q40._planes_dot``, with all 16 nibble planes in one
contraction; a dense leaf merges as many as its block count asks for, PR 51)
over the smallest of 8 / 16 / 32 / C rows that holds it (``_tile_rows``: a
part-filled slot of a wide dispatch pays for its rows, not for C). On a v5e
the tile streams an expert at 370-390 GB/s whatever it holds (it multiplies
the scale onto every weight before the dot and is bound by that unpack),
``_row_body`` at 520-610 and the block-diagonal body at 630-840 (PERF.md
section 7 has it by leaf and fill: PRs 53 and 54).

All are the float32 arithmetic of the dense Q40 kernels: exact on the VPU
and in the block-diagonal product (a code times a bf16 piece is exact and
the MXU adds in float32), and the tile's five bf16 passes (``ops/pallas_q40._five_pass_dot``:
a Q40 weight, code x scale, has 15 significant bits and IS two bf16 numbers,
so it is split in two exactly and multiplied by the row's three pieces: what
``Precision.HIGHEST``'s six passes add, without its three-way split of the
weight; under fast-prefill's ``matmul_mode() == "bf16"`` one pass); none
dequantizes an expert to HBM. Off the Pallas path (codec or dense leaves:
the CPU tests, F32 files) ``_experts_xla`` scans the experts one at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.spans import SCOPE_MOE_EXPERTS, SCOPE_MOE_ROUTER
from .linear import (StackedQ40, ffn_activation, gated_product, matmul,
                     matmul_mode, silu)
from .pallas_q40 import (_MATMUL_ROWSXNB_CAP, _T1_GROUP, _VMEM64_PARAMS, NJ,
                         _diag_planes, _diag_product, _diag_scratch,
                         _mask_pieces, _planes_dot)

MOE_SLOT_ROWS = 8                # rows of a narrow dispatch's slot: one sublane tile
MOE_SLOT_T_MAX = 32              # widest dispatch whose slots are one such tile
MOE_WIDE_ROWS = 32               # most rows a wider one's slot is worth
MOE_SLOT_TILE_MAX = 2048         # largest row tile measured (PERF.md section 7)
MOE_DIAG_ROWS = 2                # fullest slot of the block-diagonal body


def route(gate: jax.Array, xb: jax.Array, k: int, router=None, bias=None):
    """Router of one layer over rows ``xb`` (T, dim): (T, k) weights and
    expert ids. ``router`` None (or the default record): the k largest
    softmax probabilities as they are. Else (``models/spec.Router``):
    s = sigmoid or softmax of the logits; the choice is on c = s + ``bias``;
    with groups, a group's score is the sum of its two largest c, the
    ``groups_kept`` best groups stay and every other expert's c is -inf;
    the k largest c are chosen (``lax.top_k``: the lower index wins a tie);
    their weights are the UNBIASED s, over their sum (+ 1e-20) if
    ``renormalise``, times ``scale``."""
    logits = jnp.einsum("ed,td->te", gate.astype(jnp.float32),
                        xb.astype(jnp.float32),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
    if router is None or (router.scoring == "softmax" and router.groups == 1
                          and not router.renormalise and bias is None
                          and router.scale == 1.0):
        return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    s = (jax.nn.sigmoid(logits) if router.scoring == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    c = s if bias is None else s + bias.astype(jnp.float32)
    if router.groups > 1:
        per = c.reshape(c.shape[0], router.groups, -1)
        score = jnp.sum(jax.lax.top_k(per, min(2, per.shape[-1]))[0], axis=-1)
        _, kept = jax.lax.top_k(score, router.groups_kept)
        keep = jnp.any(kept[..., None] == jnp.arange(router.groups), axis=1)
        c = jnp.where(keep[..., None], per, -jnp.inf).reshape(c.shape)
    _, topi = jax.lax.top_k(c, k)
    topw = jnp.take_along_axis(s, topi, axis=1)
    if router.renormalise:
        topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-20)
    return topw * jnp.float32(router.scale), topi


def slot_cap(t: int, k: int, n_experts: int) -> int:
    """Rows a slot carries, from what a dispatch shows statically: its T
    rows, the k pairs a row, the ``n_experts`` held. One row at T == 1, one
    sublane tile up to ``MOE_SLOT_T_MAX`` rows (decode, verify). A wider
    dispatch (a prefill chunk): the multiple of 8 that holds TWICE the
    rows an expert expects, T k / n_experts (a router is skewed: OLMoE's
    busiest experts take twice the mean of a chunk's rows, and a second
    slot unpacks the expert's tile again), from 16 to ``MOE_WIDE_ROWS``:
    the tile's time grows with its rows, and past 32 faster than the rows
    (PERF.md section 7, PR 36: 128 rows on 64 experts of OLMoE's ``w13``
    0.64 ms at 32 rows a slot, 0.82 at 16, 1.07 at 64). A part-filled slot
    pays for the rows it holds, not for the capacity (``_tile_rows``), so
    a share of DeepSeek-V3's experts, which expect 32 rows by their shape
    and see 4, lose nothing to it. Where most experts take every row
    (k over half of them) one slot holds all T rows: the every-expert
    kernel's work, at its price."""
    if t <= MOE_SLOT_T_MAX:
        return MOE_SLOT_ROWS if t > 1 else 1
    expect = -(-t * k // n_experts)
    if 2 * expect > t:
        return -(-t // 8) * 8
    return min(max(-(-2 * expect // 8) * 8, 16), MOE_WIDE_ROWS)


def diag_rows(cap: int, *nbs: int) -> int:
    """Most live rows of a slot that takes the block-diagonal body
    (``_diag_body``) at ``cap`` rows a slot on a leaf of ``nb`` blocks a
    row (on every one of several: the engine's counter asks both of an
    expert's); 0: no slot does. What the kernel sees decides: the block
    count (the body walks groups of 8 blocks: ``ops/pallas_q40._t1_mxu``'s
    rule; any other leaf keeps ``_row_body``) and the capacity (the decode slots'
    kernel only: a wider dispatch's slots, ``moe_q40_grouped``, are mostly
    full and every chunk program would pay the body's tracing). Up to
    ``MOE_DIAG_ROWS`` = 2 rows, which the ONE body stacks: at 1 and 2 rows
    it is 1.4 to 2 times ahead of ``_row_body`` and the tile on every expert
    leaf; a body of its own for 3 and for 4 rows was ahead of the tile by
    a third and a tenth and cost every program that holds the kernel its
    tracing (PERF.md section 7, PRs 53 and 54)."""
    if any(nb % _T1_GROUP for nb in nbs) or cap > MOE_SLOT_ROWS:
        return 0
    return min(MOE_DIAG_ROWS, cap)


def slot_census(counts, cap: int, top: int = 0) -> tuple[int, int, int]:
    """(live slots, slots of one row, slots of 1 to ``top`` rows) of
    dispatches whose routed rows per held expert are ``counts`` (any shape;
    numpy, on the host) at ``cap`` rows a slot: what ``build_slots`` builds
    from them, for the counters. With ``top`` the ``diag_rows`` of the
    expert leaves, the third is the slots that took the block-diagonal
    body."""
    live = int((-(-counts // cap)).sum())
    if cap == 1:                     # a row a slot
        return live, live, live if top else 0
    last = counts % cap              # an expert's last slot, if part filled
    return (live, int((last == 1).sum()),
            int(((last >= 1) & (last <= top)).sum()))


def max_slots(t: int, k: int, n_experts: int, cap: int) -> int:
    """Static bound on the slots of a T-row dispatch: sum_e ceil(r_e / cap)
    with sum r_e = T k pairs over at most min(E, T k) active experts."""
    pairs = t * k
    active = min(n_experts, pairs)
    return min(pairs, -(-(pairs + active * (cap - 1)) // cap))


def build_slots(topi: jax.Array, n_experts: int, cap: int):
    """Group the (T, k) routed pairs by expert into slots of ``cap`` rows.
    A pair whose id is -1 (it landed on an expert held elsewhere) takes no
    slot: its ``pair_slot`` is the out-of-range ``A``.

    Returns ``slot_expert`` (A,) expert of each slot, ascending, the slots
    past the live count repeating the last live expert (no new tile is
    fetched for them); ``n_slots`` () live slots; ``fill`` (A,) the live
    rows of each slot (1 to cap; 0 past the live count); ``slot_rows``
    (A, cap) the row each lane computes (lanes no pair fills compute row 0
    and are never read back); ``pair_slot`` / ``pair_lane`` (T, k) where
    each pair's result lands; ``counts`` (E,) rows routed to each expert."""
    t, k = topi.shape
    a = max_slots(t, k, n_experts, cap)
    flat = topi.reshape(-1).astype(jnp.int32)                    # (P,)
    onehot = (flat[:, None] == jnp.arange(n_experts, dtype=jnp.int32)
              ).astype(jnp.int32)                                # (P, E)
    counts = onehot.sum(axis=0)
    # rank of a pair among its expert's pairs, in row order
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                               flat[:, None], axis=1)[:, 0]
    per = (counts + cap - 1) // cap                # slots of each expert
    ends = jnp.cumsum(per)
    n_slots = ends[-1]
    slot = jnp.where(flat >= 0, (ends - per)[flat] + rank // cap, a)
    lane = rank % cap
    slot_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(a, dtype=jnp.int32), side="right"),
        jnp.maximum(jnp.max(flat), 0)).astype(jnp.int32)
    # rows left for a slot of its expert; past the live count the repeated
    # expert has none left
    fill = jnp.clip(counts[slot_expert] - cap * (
        jnp.arange(a, dtype=jnp.int32) - (ends - per)[slot_expert]), 0, cap)
    slot_rows = jnp.zeros((a, cap), jnp.int32).at[slot, lane].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    return (slot_expert, n_slots.astype(jnp.int32), fill.astype(jnp.int32),
            slot_rows, slot.reshape(t, k), lane.reshape(t, k), counts)


# -- the slot kernel (the MXU tile; a body for part-filled slots beside it) -----

def _diag_body(qs_ref, s_ref, out_ref, l_scr, xs_scr):
    """A slot's first ``top`` rows against one row tile: the dense T = 1
    matvec's block-diagonal MXU product (ops/pallas_q40's section comments,
    PR 49) with the rows STACKED in the left-hand side
    (``ops/pallas_q40._diag_product``, which a part-filled dense dispatch
    runs too), its planes and block sums built a slot by
    ``ops/pallas_q40._diag_planes``. Exact in float32, as ``_row_body``.
    Writes rows 0 to ``top`` of ``out_ref``; a row past the slot's live
    ones reads the planes an earlier slot left and is never read back."""
    out_ref[0:xs_scr.shape[0], :] = _diag_product(qs_ref, s_ref, l_scr,
                                                  xs_scr)


def _row_body(qs_ref, s, xp_ref, out_ref):
    """ONE row against the tile: ``_matvec_body_nb``'s arithmetic
    (ops/pallas_q40: unpack each nibble plane once, an (nb, R) accumulator,
    the -8 folded into one xsum term, a sublane reduction; 8 vector
    operations a packed byte) with the row's planes PACKED ON THE LANES:
    ``xp_ref`` (nb, 128) holds block b's value j under the low nibbles at
    column j, under the high ones at 16 + j, and the block's sum at 32.
    (The 2-D matvec's (NJ, nb, 1) planes pad each value to 128 lanes:
    megabytes a slot at an expert's nb, more than the tile they multiply.)
    Writes row 0 of ``out_ref``."""
    acc = None
    for j in range(NJ):
        q = qs_ref[j].astype(jnp.int32)              # (nb, R)
        a = ((q & 0xF).astype(jnp.float32) * xp_ref[:, j][:, None]
             + (q >> 4).astype(jnp.float32) * xp_ref[:, NJ + j][:, None])
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xp_ref[:, 2 * NJ][:, None]
    out_ref[0:1, :] = jnp.sum(acc * s, axis=0, keepdims=True)   # (1, R)


def _row_planes(x: jax.Array, nb: int) -> jax.Array:
    """(..., n) rows -> (..., nb, 128) packed planes of ``_row_body``."""
    x3 = x.astype(jnp.float32).reshape(*x.shape[:-1], nb, 2 * NJ)
    packed = jnp.concatenate([x3, jnp.sum(x3, axis=-1, keepdims=True)], -1)
    return jnp.pad(packed, [(0, 0)] * (packed.ndim - 1)
                   + [(0, 128 - packed.shape[-1])])


def _tile_rows(c: int) -> tuple[int, ...]:
    """Row counts the MXU tile is compiled at inside a slot of ``c`` rows:
    a slot runs the smallest that holds its live rows. The tile's time
    grows with the rows it multiplies (1 : 1.0 : 1.1 : 1.7 : 3.3 at 8 / 16
    / 32 / 64 / 128 on DeepSeek-V3's ``w13``), and most slots of a wide
    dispatch are part filled (PERF.md section 7, PR 36: a kernel with a
    64-row body as well ran slower, not faster)."""
    return tuple(r for r in (8, 16, 32) if r < c) + (c,)


def _kernel_moe_slots(layer_ref, sexp_ref, n_ref, fill_ref, qs_ref,
                      scale_ref, *refs, bf16, top):
    """One row tile of one slot, its body picked by the slot's live rows
    (``fill_ref``; 0 past the live count: nothing runs). ``top`` 0: a slot
    of one row takes ``_row_body``, the grid is (row tiles, slots) and
    ``refs`` are, at C > 1 rows a slot, the merged planes xlo, xhi
    (C, NJ * nb) of the slot's rows; always the packed planes (nb, 128) of
    its FIRST row; out. ``top`` > 0 (``diag_rows``): a slot of up to ``top``
    rows takes ``_diag_body``, the grid is (slots, row tiles) and in place
    of the packed planes come the slot's first ``top`` rows as they are
    (top, n / 128, 128), and after out the body's three scratch buffers.
    A fuller slot runs the MXU tile either way."""
    del layer_ref, sexp_ref, n_ref  # consumed by the index maps
    if top:
        *tile_refs, x_ref, out_ref, l_scr, xs_scr, sum_scr = refs
        rows = fill_ref[pl.program_id(0)]
        part = (rows >= 1) & (rows <= top)

        # at the slot's first row tile, for its later ones to read
        @pl.when(part & (pl.program_id(1) == 0))
        def _():
            _diag_planes(x_ref, l_scr, xs_scr, sum_scr, rows)

        @pl.when(part)
        def _():
            _diag_body(qs_ref, scale_ref, out_ref, l_scr, xs_scr)
    else:
        *tile_refs, row_ref, out_ref = refs
        rows = fill_ref[pl.program_id(1)]

        @pl.when(rows == 1)
        def _():
            _row_body(qs_ref, scale_ref[...], row_ref, out_ref)

    if not tile_refs:  # at T == 1 a slot never holds a second row
        return
    sizes = _tile_rows(out_ref.shape[0])
    for lo, hi in zip((max(top, 1),) + sizes, sizes):
        whole = hi == sizes[-1]

        @pl.when(rows > lo if whole else (rows > lo) & (rows <= hi))
        def _(hi=hi, whole=whole):
            # the first ``hi`` rows of the slot (a multiple of 8: whole
            # sublane tiles); the rows past them are never read back
            cut = (lambda r: r) if whole else (lambda r: r.at[pl.ds(0, hi)])
            _mxu_body_merged(qs_ref, scale_ref[...], *map(cut, tile_refs),
                             cut(out_ref), bf16)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "bf16"))
def moe_q40_slots(layer, slot_expert, n_slots, fill, qs_t, scale, xs,
                  rows=None, *, block_rows, interpret, bf16=False):
    """out[a, c] = dequant(w[layer, slot_expert[a]]) @ (row c of slot a) for
    the live slots a < n_slots and their live rows c < fill[a] (the rest of
    ``out`` is not written, or holds what a row never read back gave).
    ``qs_t`` (L, E, NJ, nb, d) / ``scale`` (L, E, nb, d) nb-major. A slot's
    rows: ``xs`` (A, C, n) holds them, or ``xs`` (T, n) is the dispatch's
    rows and ``rows`` (A, C) says which each slot takes (the planes are
    then built once a row and gathered, not once a slot lane); C is one row
    (T == 1) or a multiple of 8 (``slot_cap``). The tile multiplies the
    rows' three bf16 pieces by the weight's two (``_mxu_body_merged``);
    ``bf16``: one piece a side (fast-prefill); the part-filled slots'
    bodies stay exact. In a capture the call is ``moe_q40_slots`` up to one
    sublane tile a slot (the decode steps' kernel, which the benchmark's
    roofline shares find by that name) and ``moe_q40_grouped`` beyond (a
    chunk's). Where ``diag_rows`` admits the leaf the grid walks (slots,
    row tiles), so that a slot's rows are fetched and its block-diagonal
    planes built once a slot and not once a tile (DeepSeek-V3's ``w13`` is
    eight tiles a slot); a second slot of one expert, rare at 8 rows a
    slot, then fetches the expert's tiles again. Elsewhere (row tiles,
    slots): the slots of one expert follow each other with the same weight
    block index, which skips the re-fetch."""
    nb, d = qs_t.shape[-2], qs_t.shape[-1]
    a, c = xs.shape[:2] if rows is None else rows.shape
    n_tiles, top = d // block_rows, diag_rows(c, nb)

    def at(g, N):
        # a dead slot repeats the last live one's block indices: nothing
        # is fetched for it and nothing written back
        return jnp.maximum(jnp.minimum(g, N[0] - 1), 0)

    def tile(g, i, N):
        # ... slots outermost, that is the last live slot's LAST tile
        return jnp.where(g < N[0], i, n_tiles - 1) if top else i

    def spec(shape, index):
        """``index`` (slot g, row tile i, N) under the grid's order."""
        if top:
            return pl.BlockSpec(shape, lambda g, i, L, S, N, F:
                                index(g, i, L, S, N))
        return pl.BlockSpec(shape, lambda i, g, L, S, N, F:
                            index(g, i, L, S, N))

    planes = [] if c == 1 else [p if rows is None else p[rows]
                                for p in _merged_planes(xs, nb)]
    scratch = []
    if top:
        raw = xs.astype(jnp.float32).reshape(*xs.shape[:-1], nb // 4, 128)
        planes.append(raw if rows is None else raw[rows[:, :top]])
        scratch = _diag_scratch(nb, top)
    else:
        planes.append(_row_planes(xs[:, 0], nb) if rows is None
                      else _row_planes(xs, nb)[rows[:, 0]])
    blocks = [p.shape[1:] for p in planes]
    if top:   # of all C rows where ``xs`` holds them the first ``top``: no copy
        blocks[-1] = (top, nb // 4, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(a, n_tiles) if top else (n_tiles, a),
        # leading dims are squeezed (None): the body sees (NJ, nb, rows)
        # codes, (nb, rows) scales and one slot's planes
        in_specs=[
            spec((None, None, NJ, nb, block_rows),
                 lambda g, i, L, S, N: (L[0], S[g], 0, 0, tile(g, i, N))),
            spec((None, None, nb, block_rows),
                 lambda g, i, L, S, N: (L[0], S[g], 0, tile(g, i, N))),
        ] + [spec((None,) + block, lambda g, i, L, S, N, z=(0,) * len(block):
                  (at(g, N),) + z) for block in blocks],
        out_specs=spec((None, c, block_rows),
                       lambda g, i, L, S, N: (at(g, N), 0, tile(g, i, N))),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_kernel_moe_slots, bf16=bf16, top=top),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((a, c, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
        name="moe_q40_slots" if c <= MOE_SLOT_ROWS else "moe_q40_grouped",
    )(layer, slot_expert, n_slots.reshape(1), fill, qs_t, scale, *planes)


# -- the MXU tile ---------------------------------------------------------------

def _mxu_body_merged(qs_ref, s, xlo_ref, xhi_ref, out_ref, bf16: bool):
    """The dense tile's arithmetic (ops/pallas_q40._planes_dot: dequantize
    the tile to float32, exact, and multiply by ``_five_pass_dot``: the
    weight as its TWO bf16 pieces against the rows' three, five single bf16
    passes; one piece a side, one pass, under fast-prefill's ``bf16``) with
    ALL 16 nibble planes merged into the contraction: qs_ref (NJ, nb, R)
    codes, s (nb, R) scales, xlo/xhi (bt, NJ * nb) float32 with value j of
    block b at column j * nb + b; out (bt, R). A dot a plane contracts over
    nb, which is 64 or 32 for an expert: half or a quarter of an MXU pass's
    rows, 32 passes a tile. One (3 bt, NJ * nb) x (NJ * nb, R) dot per
    nibble half and weight piece fills them (measured on OLMoE's chunk:
    PERF.md section 6, PR 26; since PR 51 a dense leaf merges as many planes
    as ``ops/pallas_q40._pick_planes`` says for its block count and rows).
    The rows are split HERE, once a row tile: a slot has one to eight of
    them, and three pieces a slot gathered outside cost XLA more than these
    four operations a value (PERF.md section 7, PR 38); a dense leaf's are
    split once a call outside."""
    xs = [x_ref[...] if bf16 else
          jnp.concatenate(_mask_pieces(x_ref[...], 3), axis=0)
          for x_ref in (xlo_ref, xhi_ref)]
    out_ref[...] = _planes_dot(qs_ref[...].astype(jnp.int32), s, *xs,
                               out_ref.shape[0], bf16)


def _merged_planes(x: jax.Array, nb: int):
    """(..., T, n) rows -> xlo, xhi (..., T, NJ * nb): value j (xhi: 16 + j)
    of block b at column j * nb + b."""
    x4 = x.astype(jnp.float32).reshape(*x.shape[:-1], nb, 2, NJ)
    flat = jnp.swapaxes(x4, -3, -1)                  # (..., T, NJ, 2, nb)
    return (flat[..., 0, :].reshape(*x.shape[:-1], NJ * nb),
            flat[..., 1, :].reshape(*x.shape[:-1], NJ * nb))


# -- the expert layer ---------------------------------------------------------

def _slot_block_rows(d: int, nb: int, cap: int = MOE_SLOT_ROWS) -> int | None:
    """Row tile of the slot kernel at ``cap`` rows a slot: the largest
    multiple of 128 dividing ``d`` under the MXU body's measured rows x nb
    boundary (``_MATMUL_ROWSXNB_CAP``) and ``MOE_SLOT_TILE_MAX``. Not the
    dense MXU rule's 256 rows: an expert's tile is small (OLMoE's ``w13``
    at 256 rows is 328 KB, 1.2 us of the tile's time against a grid step's
    fixed 0.35), and both bodies ran faster the larger the tile on all four
    expert leaves of the benchmark (2048 / 2048 on OLMoE, 512 / 1792 on
    DeepSeek-V3's share: my chip run, PR 34). Past 16 rows a slot HALF that
    boundary: at a mid-sized row count Mosaic keeps more of the unpacked
    planes live (ops/pallas_q40._pick_block_rows met it at 32 rows), and
    the chip's compiler once refused DeepSeek-V3's ``w13`` at 32 rows and
    the full 512-row tile (76.8 MB of scoped VMEM; my chip run, PR 36)."""
    budget = _MATMUL_ROWSXNB_CAP // (1 if cap <= 16 else 2)
    limit = min(budget // nb, MOE_SLOT_TILE_MAX)
    return next((r for r in range(min(d, limit) // 128 * 128, 0, -128)
                 if d % r == 0), None)


def shape_places(d: int, nb: int) -> bool:
    """True when the slot kernel can tile a (d, nb * 32) expert tensor at
    every capacity: ops/linear.pack_q40_params packs an expert stack
    nb-major only then (else it stays codec and takes ``_experts_xla``)."""
    return _slot_block_rows(d, nb, MOE_WIDE_ROWS) is not None


def _experts_slots(layer, w13, w2, xb, topw, topi, n_experts, interpret,
                   bf16=False, act=silu, gated=True, product=None):
    """``w13``: the fused [gate | up] stack, or, not ``gated``, the one up
    stack ``moe_w1``. ``product``: a gated expert's ``(gate, up) ->
    hidden`` (ops/linear.gated_product; default ``act(gate) * up``)."""
    product = product or (lambda gate, up: act(gate) * up)
    t, k = topi.shape
    cap = slot_cap(t, k, n_experts)
    (slot_expert, n_slots, fill, slot_rows, pair_slot, pair_lane,
     counts) = build_slots(topi, n_experts, cap)
    n_in = w13.qs_t.shape[-2] * 2 * NJ
    if xb.shape[-1] < n_in:   # zero blocks past the input width (ops/linear.
        xb = jnp.pad(xb, ((0, 0), (0, n_in - xb.shape[-1])))  # Q40Layout)

    def call(w, xs, rows=None):
        nb, d = w.qs_t.shape[-2:]
        return moe_q40_slots(layer, slot_expert, n_slots, fill, w.qs_t,
                             w.scale, xs, rows,
                             block_rows=_slot_block_rows(d, nb, cap),
                             interpret=interpret, bf16=bf16)

    h13 = call(w13, xb, slot_rows)                       # (A, C, 2 hidden)
    hid = h13.shape[-1] // 2
    # the activation between the two calls: a PolyNorm's mean runs over a
    # slot row's own hidden width (a lane no pair fills reads 0)
    out = call(w2, product(h13[..., :hid], h13[..., hid:]) if gated
               else act(h13))                                # (A, C, dim)
    picked = out[pair_slot, pair_lane]                   # (T, k, dim)
    # a pair that took no slot reads whatever lies at the clamped index
    picked = jnp.where((topi >= 0)[..., None], picked, 0.0)
    return jnp.sum(picked * topw[..., None], axis=1), counts


def _routing_mask(topw, topi, n_experts):
    """(T, E) weight of each expert for each row (0 where not routed) and
    the (E,) count of rows routed to each."""
    onehot = topi[..., None] == jnp.arange(n_experts, dtype=topi.dtype)
    return (jnp.sum(jnp.where(onehot, topw[..., None], 0.0), axis=1),
            jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32))


def _experts_xla(lw, xb, topw, topi, n_experts, act=silu, gated=True,
                 product=None):
    """One expert at a time through ``ops/linear.matmul`` (codec Q40 or
    dense leaves): every row through every expert, weighted 0 where it was
    not routed. Holds one dequantized expert at a time. ``product`` as
    ``_experts_slots``'s."""
    wmask, counts = _routing_mask(topw, topi, n_experts)
    product = product or (lambda gate, up: act(gate) * up)

    def body(acc, ws):
        w1, w2, *w3, m = ws
        h = matmul(w1, xb)
        h = product(h, matmul(w3[0], xb)) if gated else act(h)
        return acc + matmul(w2, h * m[:, None]), None

    acc, _ = jax.lax.scan(body, jnp.zeros_like(xb, dtype=jnp.float32),
                          (lw["moe_w1"], lw["moe_w2"],
                           *([lw["moe_w3"]] if gated else []), wmask.T))
    return acc, counts


def moe_ffn(spec, lw: dict, xb: jax.Array):
    """The routed-expert FFN of one layer over normalised rows ``xb``
    ((T, dim) or (B, T, dim)): returns (y like xb, counts (E,) int32 of
    rows routed to each expert in this dispatch, over the router's FULL
    width). Where the spec holds a share of the experts, y is the part the
    held experts give."""
    lead = xb.shape[:-1]
    x2 = xb.reshape(-1, xb.shape[-1])
    k, held = spec.n_active_experts, spec.n_experts_held
    with jax.named_scope(SCOPE_MOE_ROUTER):
        # the Router record alone decides: the default one is the old path
        topw, topi = route(lw["moe_gate"], x2, k, spec.router,
                           lw.get("moe_bias"))
        routed = None
        if held != spec.n_experts:
            routed = jnp.sum(topi[..., None] == jnp.arange(
                spec.n_experts, dtype=topi.dtype), axis=(0, 1),
                dtype=jnp.int32)
            local = topi - spec.layout.offset
            here = (local >= 0) & (local < held)
            topi = jnp.where(here, local, -1)
            topw = jnp.where(here, topw, 0.0)
    n_exp = held
    gated = spec.activation.gated
    act = ffn_activation(spec, lw)
    product = gated_product(spec, lw) if gated else None
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        w13 = lw.get("moe_w13" if gated else "moe_w1")
        w2 = lw.get("moe_w2")
        if isinstance(w13, StackedQ40) and isinstance(w2, StackedQ40):
            interpret = jax.default_backend() != "tpu"
            layer = jnp.asarray(w13.layer, dtype=jnp.int32).reshape(1)
            y, counts = _experts_slots(layer, w13.w, w2.w, x2, topw, topi,
                                       n_exp, interpret,
                                       matmul_mode() == "bf16", act, gated,
                                       product)
        elif "moe_w1" not in lw or isinstance(lw["moe_w1"], StackedQ40):
            raise NotImplementedError(
                "expert stacks packed for the kernels without their fused "
                "moe_w13 (ops/linear.fuse_q40_layer_matmuls)")
        else:
            y, counts = _experts_xla(lw, x2, topw, topi, n_exp, act, gated,
                                     product)
    return y.reshape(*lead, -1), counts if routed is None else routed
