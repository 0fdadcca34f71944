"""Pallas TPU kernel: fused Q40 dequant + matmul.

The TPU analog of the reference's hot NEON kernel ``matmulQ40vQ80``
(src/funcs.cpp:185-260): weights stay packed in HBM (0.5625 bytes/value) and
the nibble-unpack + f16-delta scale happens in VMEM on the way into the dot —
HBM traffic per token is the packed bytes, not dequantized f32. This is what
makes single-token decode HBM-bound at the Q40 size instead of the f32 size
(the dequantize-then-dot XLA fallback in ops/linear.py materializes f32 tiles).

Mosaic constraint that shapes this kernel: there is no supported way to
expand per-block scales (R, nb) to per-value (R, nb*16) inside the kernel
(minor-dim broadcast+reshape is an "unsupported shape cast"). So instead of
one wide dot over all 32 values per block, the grid carries the nibble
position j = 0..15 as its innermost axis and every step is pure 2D:

  qs_t   (16, d, nb) uint8  — qs_t[j, r, b] packs values x[b*32+j] (low
                               nibble) and x[b*32+j+16] (high nibble)
  scale  (d, nb) float32    — per-block deltas (f32: Mosaic has no f16
                               vectors; the f16->f32 upconvert is exact)
  xlo/xhi (16, t, nb) f32   — xlo[j, t, b] = x[t, b*32+j], xhi: +16

  step (ti, i):  out[ti, i] = sum_j  xlo[j] @ ((lo(qs_t[j]) - 8) * scale).T
                                  +  xhi[j] @ ((hi(qs_t[j]) - 8) * scale).T

The (16, d, nb) weight tiling is prepared ONCE at load time
(io.loader.to_kernel_layout); feeding a codec-layout Q40Weight works but
re-tiles on every call — fine under test, wrong for the per-token hot loop.

Grid: (t tiles, d tiles), one step per output tile with the 16 nibble planes
unrolled in the body — the packed bytes of a whole tile arrive as one big
DMA that Pallas double-buffers across grid steps. Non-TPU backends run in
interpret mode (tests); the numerics are the exact Q40 value map, so parity
with the XLA path is bit-tight at f32.

That is the d-major layout (``Q40Kernel``), whose one-row body multiplies
and adds on the vector unit and is bound by it, not by HBM. Every benchmark
cell packs the nb-MAJOR layout (``Q40KernelNb``: qs_t (16, nb, d), the
output dim minor), and there both contractions run on the MXU in exact
bf16 pieces of what the operands hold: a one-row dispatch pushes each raw
code once against the row's three pieces laid block-diagonal and scales a
block's product (``_matvec_body_nb_mxu``, PR 49: level with its tile's
DMAs on Mistral-7B's ``w13`` and 9 to 19 % over them on a layer's other
leaves, 72 % of the HBM roofline over the step where the vector body read
63); a dispatch of up to 8 rows of which one or two are LIVE runs the same
product with those rows stacked (``_q40_live_nb``, PR 63); a wider one
dequantizes the tile and multiplies its two pieces by the rows' three
(``_five_pass_dot``, PR 38), G nibble planes to a dot so that the
contraction is whole 128-deep MXU pushes (``_pick_planes``, PR 51: G from
the leaf's blocks a row and the rows of a t-tile; a dot a plane where a
plane is whole pushes already or the tile is bound by the unpack).
``q40_matmul``'s docstring has the dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..io.loader import (Q40Kernel, Q40KernelNb, Q40KernelNbI4, Q40Weight,
                         to_kernel_layout)

QK = 32
NJ = 16  # nibble positions per block byte-plane


def _matvec_body(qs3, s, xlo_ref, xhi_ref, xsum_ref, out_ref):
    """Shared T=1 body: qs3 (NJ, R, nb) codes view, s (R, nb) f32 scales,
    xsum (1, nb) per-block input sums.

    The -8 code offset is factored out of the per-plane loop:
      sum_j (code-8)*x = sum_j code*x - 8*sum_j x
    so the hot loop multiplies RAW codes (saves two vector subtracts per
    byte-plane — this loop is VPU-unpack-bound, not HBM-bound, at matvec
    shapes) and the correction lands once per block via the precomputed
    input sum."""
    acc = None
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)             # (R, nb)
        wlo = (q & 0xF).astype(jnp.float32)
        whi = (q >> 4).astype(jnp.float32)
        a = wlo * xlo_ref[j] + whi * xhi_ref[j]  # x rows (1, nb) bcast over R
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xsum_ref[...]              # (R, nb) - (1, nb) bcast
    out_ref[...] = jnp.sum(acc * s, axis=1, keepdims=True)  # (R, 1)


def _kernel_matvec(qs_ref, scale_ref, xlo_ref, xhi_ref, xsum_ref, out_ref):
    """T=1 specialization: pure VPU multiply-accumulate, no MXU.

    Thin M=1 dots waste the MXU (it processes 128-row tiles); for a matvec
    the whole contraction is elementwise work: accumulate the UNSCALED codes
    against x across the 16 nibble planes (the per-block scale is j-invariant,
    so it factors out), apply the scale once, lane-reduce. ~2.4x faster than
    the dot formulation on v5e at 7B shapes.
    """
    _matvec_body(qs_ref, scale_ref[...], xlo_ref, xhi_ref, xsum_ref, out_ref)


def _kernel_matvec_stacked(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref,
                           xsum_ref, out_ref):
    """Stacked-layer matvec: the layer index arrives as a prefetched scalar
    that the BlockSpec index maps use to DMA the right layer's tiles straight
    out of the stacked (L, ...) arrays — no XLA dynamic-slice copy of the
    whole layer's weights per scan step (which would triple weight HBM
    traffic: read stack + write slice + read slice)."""
    del layer_ref  # consumed by the index maps
    _matvec_body(qs_ref[0], scale_ref[0], xlo_ref, xhi_ref, xsum_ref, out_ref)


def _matvec_body_multi(qs3, s, xlo_ref, xhi_ref, xsum_ref, out_ref):
    """Small-T (2..8) body: the matvec VPU formulation with one accumulator
    per batch row, so the nibble unpack (the VPU bottleneck) is paid ONCE
    for all T rows instead of per row. out (R, T); xlo/xhi (NJ, T, nb);
    xsum (T, nb). ~3x the bytes/s of the MXU body at T=4 on v5e."""
    t = xlo_ref.shape[1]
    accs = [None] * t
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)                 # (R, nb)
        wlo = (q & 0xF).astype(jnp.float32)
        whi = (q >> 4).astype(jnp.float32)
        for ti in range(t):
            a = wlo * xlo_ref[j, ti] + whi * xhi_ref[j, ti]
            accs[ti] = a if accs[ti] is None else accs[ti] + a
    cols = []
    for ti in range(t):
        acc = accs[ti] - 8.0 * xsum_ref[ti]          # (R, nb) - (nb,)
        cols.append(jnp.sum(acc * s, axis=1, keepdims=True))
    out_ref[...] = jnp.concatenate(cols, axis=1)     # (R, T)


def _kernel_multi(qs_ref, scale_ref, xlo_ref, xhi_ref, xsum_ref, out_ref):
    _matvec_body_multi(qs_ref, scale_ref[...], xlo_ref, xhi_ref, xsum_ref,
                       out_ref)


def _kernel_multi_stacked(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref,
                          xsum_ref, out_ref):
    del layer_ref  # consumed by the index maps
    _matvec_body_multi(qs_ref[0], scale_ref[0], xlo_ref, xhi_ref, xsum_ref,
                       out_ref)


def _matvec_body_nb(qs3, s, xlo_ref, xhi_ref, xsum_ref, out_ref):
    """T=1 VECTOR body for the nb-MAJOR layout (io.loader.Q40KernelNb): qs3
    (NJ, nb, R) codes, s (nb, R) f32 scales, xlo/xhi (NJ, nb, 1), xsum
    (nb, 1). Same math as _matvec_body with the tile transposed: the
    output dim R rides the LANES (128-aligned for every Llama d), so
    awkward nb values (160 at 13B) cost no tile padding. The reduction
    runs over sublanes (axis 0) instead of lanes. Since PR 49 it serves a
    block count that is no multiple of 8 only (no model's: the tests');
    every other leaf takes ``_matvec_body_nb_mxu`` below, which beat it on
    every leaf timed (PERF.md section 7)."""
    acc = None
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)                 # (nb, R)
        wlo = (q & 0xF).astype(jnp.float32)
        whi = (q >> 4).astype(jnp.float32)
        a = wlo * xlo_ref[j] + whi * xhi_ref[j]      # (nb, 1) bcast over R
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xsum_ref[...]                  # (nb, R) - (nb, 1)
    out_ref[...] = jnp.sum(acc * s, axis=0, keepdims=True)  # (1, R)


def _kernel_matvec_nb(qs_ref, scale_ref, xlo_ref, xhi_ref, xsum_ref,
                      out_ref):
    _matvec_body_nb(qs_ref, scale_ref[...], xlo_ref, xhi_ref, xsum_ref,
                    out_ref)


def _kernel_matvec_nb_stacked(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref,
                              xsum_ref, out_ref):
    del layer_ref  # consumed by the index maps
    _matvec_body_nb(qs_ref[0], scale_ref[0], xlo_ref, xhi_ref, xsum_ref,
                    out_ref)


# -- the T=1 nb-major body: raw codes on the MXU, the row block-diagonal -----
#
# The vector body above spends 4.5 vector operations a weight, 2 of them the
# multiply and the add, and is bound by them (62 % of the HBM roofline on
# Mistral-7B's tree). They stay off the MXU there only because a Q40 scale
# belongs to a (block, output row) pair, so a contraction over a row's blocks
# mixes scales. At ONE row the contraction can be split by block for nothing:
# for a group of 8 blocks (one float32 sublane tile) the 32 nibble slabs
# (8, R) of the group, laid one under the other, are a (256, R) right-hand
# side whose row v 8 + g is code v of block g (a renumbering of whole vector
# registers), and the row's activation laid BLOCK-DIAGONAL,
#   L[p 8 + g, v 8 + g'] = piece_p(x[block g, v]) where g == g', else 0
# (p the three bf16 pieces of a float32, ``_mask_pieces``), is a (24, 256)
# left-hand side. ONE dot L @ codes gives, in row p 8 + g, piece p's part of
# block g's UNSCALED sum for each of the R outputs: every weight is pushed
# to the MXU once, as its raw code 0..15 (a bf16 number, float32-held, so
# nothing is cast on the vector unit); a code times a piece is exact in
# float32 and the MXU adds in float32. The vector unit is left the unpack
# (widen, mask or shift, convert: 2.5 operations a weight) and, a GROUP and
# not a weight, the three slabs' sum, the ``- 8`` fold and the scale.
#
# L is built IN the kernel, at the first row tile, into scratch that the
# later tiles read (a TPU grid runs in order on one core): outside it cost
# XLA 5 to 16 us a call in copies, as the vector body's planes did and do
# (its x planes lie (nb, 1), lane-padded: 7 MB of DMA a call at 448 blocks
# a row). The row arrives as it is, (n / 128, 128). PERF.md section 7 has
# the table that chose the group, the row tile and where L is made.

_T1_CHUNK = 32   # blocks a load: one uint8 sublane tile (a tail may be less)
_T1_GROUP = 8    # blocks a dot: one float32 sublane tile


def _diag_planes_nb(x_ref, l_scr, xs_scr, nb: int):
    """Fill ``l_scr`` (nb / 8, 24, 256) with the block-diagonal planes of the
    row and ``xs_scr`` (nb, 1) with 8 x each block's sum (the ``- 8`` fold).
    ``x_ref`` (n / 128, 128): row q holds blocks 4 q .. 4 q + 3, so a group
    of 8 blocks is two rows. Each block's 32 values are spread to lanes
    v 8 + g by a product with a 0 / 1 matrix (one term a column: exact on
    bf16 pieces), four groups a product."""
    f32 = jnp.float32
    g = _T1_GROUP
    sub = jax.lax.broadcasted_iota(jnp.int32, (_T1_CHUNK, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_T1_CHUNK, 128), 1)
    own = (lane // QK) == (sub % 4)              # a block's lanes of its row
    low = sub[:g] < 4                            # a group's first row of two
    spread = (jax.lax.broadcasted_iota(jnp.int32, (128, 256), 0) % QK
              == jax.lax.broadcasted_iota(jnp.int32, (128, 256), 1)
              // g).astype(f32)
    diag = (jax.lax.broadcasted_iota(jnp.int32, (g, 256), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (g, 256), 1) % g)
    dn = (((1,), (0,)), ((), ()))

    def chunk(x8, g0, start, groups):
        # whole chunks at a time, as the body's turn and for its reason
        n = g * groups
        row = [jnp.broadcast_to(jax.lax.slice_in_dim(x8, r, r + 1), (g, 128))
               for r in range(2 * groups)]
        a = jnp.where(own[:n], jnp.concatenate(
            [jnp.where(low, row[2 * k], row[2 * k + 1])
             for k in range(groups)]), 0.0)      # (n, 128): a block a row
        xs_scr[pl.ds(start, n), :] = 8.0 * jnp.sum(a, axis=1, keepdims=True)
        planes = jax.lax.dot_general(jnp.concatenate(_mask_pieces(a, 3)),
                                     spread, dn, preferred_element_type=f32)
        for p in range(3):                       # rows: piece, group, block
            piece = jax.lax.slice_in_dim(planes, p * n, (p + 1) * n)
            l_scr[pl.ds(g0, groups), pl.ds(p * g, g), :] = jnp.where(
                diag, piece.reshape(groups, g, 256), 0.0)

    full, tail = divmod(nb, _T1_CHUNK)

    def loop(c, carry):
        chunk(x_ref[pl.ds(pl.multiple_of(c * 8, 8), 8), :], c * 4,
              pl.multiple_of(c * _T1_CHUNK, _T1_CHUNK), 4)
        return carry

    if full:
        jax.lax.fori_loop(0, full, loop, 0)
    if tail:
        chunk(x_ref[full * 8:full * 8 + tail // 4, :], full * 4,
              full * _T1_CHUNK, tail // _T1_GROUP)


def _matvec_body_nb_mxu(qs_ref, s_ref, x_ref, out_ref, l_scr, xs_scr):
    """T=1 body for the nb-major layout, nb a multiple of 8: qs_ref
    (NJ, nb, R) uint8 codes and s_ref (nb, R) f32 scales (refs: the groups
    are walked with ``fori_loop``, 32 blocks a turn, so a leaf of 544 blocks
    a row traces as fast as one of 56), x_ref (n / 128, 128) the row, out
    (1, R); l_scr / xs_scr as ``_diag_planes_nb`` fills them at the first
    row tile. See the section comment above."""
    nb, rows = s_ref.shape
    f32 = jnp.float32
    dn = (((1,), (0,)), ((), ()))
    g = _T1_GROUP

    @pl.when(pl.program_id(0) == 0)
    def _():
        _diag_planes_nb(x_ref, l_scr, xs_scr, nb)

    def turn(start, blocks, g0, acc):
        # whole planes at a time: an operation traced is set-up time on
        # every run, and a slab a plane a group made 300 of them a turn
        q = qs_ref[:, pl.ds(start, blocks), :].astype(jnp.int32)
        codes = jnp.concatenate([(q & 0xF).astype(f32),
                                 (q >> 4).astype(f32)])  # (32, blocks, R)
        for k in range(blocks // g):
            rhs = jax.lax.slice_in_dim(codes, k * g, (k + 1) * g, axis=1)
            p3 = jax.lax.dot_general(
                l_scr[g0 + k], rhs.reshape(2 * NJ * g, rows), dn,
                preferred_element_type=f32).reshape(3, g, rows)
            r = pl.ds(start + k * g, g)
            blk = (p3[2] + p3[1]) + p3[0]               # small pieces first
            acc = acc + (blk - xs_scr[r, :]) * s_ref[r, :]
        return acc

    full, tail = divmod(nb, _T1_CHUNK)
    acc = jnp.zeros((g, rows), f32)
    if full:
        acc = jax.lax.fori_loop(
            0, full, lambda c, acc: turn(
                pl.multiple_of(c * _T1_CHUNK, _T1_CHUNK), _T1_CHUNK, c * 4,
                acc), acc)
    if tail:
        acc = turn(full * _T1_CHUNK, tail, full * 4, acc)
    out_ref[...] = jnp.sum(acc, axis=0, keepdims=True)           # (1, R)


def _kernel_matvec_nb_mxu_stacked(layer_ref, qs_ref, scale_ref, x_ref,
                                  out_ref, l_scr, xs_scr):
    del layer_ref  # consumed by the index maps
    _matvec_body_nb_mxu(qs_ref.at[0], scale_ref.at[0], x_ref, out_ref, l_scr,
                        xs_scr)


# -- the same product with a few rows STACKED in the left-hand side -------------
#
# A dispatch that is compiled for 8 rows and carries one or two (an expert's
# part-filled slot, ops/pallas_moe, PR 54; a part-filled dense decode
# dispatch, ``_live_nb_call`` below, PR 63) pays the T > 1 tile for all 8:
# the tile is bound by its unpack and its time does not depend on the rows
# it carries. The block-diagonal product above serves them: each row's
# planes take 24 left-hand rows of a group's ONE dot, the codes are
# unpacked once for all of them, and the fold and the scale are applied to
# a block's (8, R) product a row.

def _diag_product(qs_ref, s_ref, l_scr, xs_scr):
    """(top, R) float32: ``top`` stacked rows against one row tile.
    qs_ref (NJ, nb, R) uint8 codes, s_ref (nb, R) f32 scales; l_scr
    (nb / 8, 24 top, 256) the rows' block-diagonal planes, 24 left-hand
    rows a stacked row, and xs_scr (top, nb, 1) their block sums
    (``_diag_planes``). In a group's turn the 32 blocks' codes are unpacked
    once and a group of 8 blocks meets ONE dot of 24 ``top`` left-hand rows;
    the ``- 8`` fold and the scale are applied to a block's (8, R) product.
    Exact in float32. A stacked row past the live ones reads the planes an
    earlier call or slot left: a row of the product depends on its own
    planes alone, and the caller does not read it back."""
    nb, r = s_ref.shape
    top = xs_scr.shape[0]
    f32 = jnp.float32
    dn = (((1,), (0,)), ((), ()))
    g = _T1_GROUP

    def turn(start, blocks, g0, acc):
        # whole planes at a time: an operation traced is set-up time on
        # every run (``_matvec_body_nb_mxu``'s turn; the rows' accumulators
        # as one (top, 8, R) value for the same reason)
        q = qs_ref[:, pl.ds(start, blocks), :].astype(jnp.int32)
        codes = jnp.concatenate([(q & 0xF).astype(f32),
                                 (q >> 4).astype(f32)])  # (32, blocks, R)
        for k in range(blocks // g):
            rhs = jax.lax.slice_in_dim(codes, k * g, (k + 1) * g, axis=1)
            p = jax.lax.dot_general(
                l_scr[g0 + k], rhs.reshape(2 * NJ * g, r), dn,
                preferred_element_type=f32).reshape(top, 3, g, r)
            b = pl.ds(start + k * g, g)
            blk = (p[:, 2] + p[:, 1]) + p[:, 0]         # small pieces first
            acc = acc + (blk - xs_scr[:, b, :]) * s_ref[b, :]
        return acc

    full, tail = divmod(nb, _T1_CHUNK)
    acc = jnp.zeros((top, g, r), f32)
    if full:
        acc = jax.lax.fori_loop(
            0, full, lambda c, acc: turn(
                pl.multiple_of(c * _T1_CHUNK, _T1_CHUNK), _T1_CHUNK, c * 4,
                acc), acc)
    if tail:
        acc = turn(full * _T1_CHUNK, tail, full * 4, acc)
    return jnp.sum(acc, axis=1)


def _diag_planes(x_ref, l_scr, xs_scr, sum_scr, rows, row_of=lambda k: k):
    """Build the block-diagonal planes and block sums of the first ``rows``
    stacked rows (data) into ``l_scr`` / ``xs_scr`` (``_diag_product``),
    stacked row k from row ``row_of(k)`` of x_ref (.., n / 128, 128) as it
    is; a row a turn of ONE loop (``_diag_planes_nb``, as the T = 1 matvec
    builds its own: traced once whatever rides)."""
    nb = xs_scr.shape[1]

    def build(k, carry):
        lhs = l_scr.at[:, pl.ds(pl.multiple_of(24 * k, 8), 24), :]
        _diag_planes_nb(x_ref.at[row_of(k)], lhs, sum_scr, nb)
        # (the chip's compiler refuses a VIEW of a one-lane buffer at a row
        # that is data: the sums land in a row's worth and are copied)
        xs_scr[k] = sum_scr[...]
        return carry

    jax.lax.fori_loop(0, rows, build, 0)


def _diag_scratch(nb: int, top: int):
    """Scratch of ``top`` stacked rows: planes, block sums, one row's sums."""
    return [pltpu.VMEM((nb // _T1_GROUP, 3 * _T1_GROUP * top,
                        QK * _T1_GROUP), jnp.float32),
            pltpu.VMEM((top, nb, 1), jnp.float32),
            pltpu.VMEM((nb, 1), jnp.float32)]


# -- the T>1 tile's dot: five bf16 passes, exact on what a Q40 weight holds ---
#
# A Q40 weight is code x scale, code an integer in [-8, 7] and scale a
# float16 upconverted exactly (io/loader): at most 4 + 11 = 15 significant
# bits, so it IS the sum of two bf16 numbers, its top 8 bits and the rest.
# A float32 activation is the sum of three. Precision.HIGHEST knows neither:
# it splits BOTH operands three ways on the vector unit (7 operations a
# weight, of a tile whose unpack costs 4.5) and runs six passes, three of
# them against a weight piece that is always zero. The bodies below split
# the weight in two (a mask and a subtraction), stack the activation's three
# pieces along the rows so each weight piece is pushed to the MXU once, and
# add x_hi w_hi + x_mid w_hi + x_lo w_hi + x_hi w_lo + x_mid w_lo in
# float32: everything HIGHEST's six passes add (x_lo w_lo, 2^-24 of a
# product, is what it drops too).
#
# How the pieces reach the MXU was settled on the chip (PERF.md section 7,
# PR 38's table): a dense leaf's rows are split ONCE a call outside the
# kernel (it walks d / 256 row tiles of the same rows) and stored bfloat16
# (half the planes' bytes and vector registers); an expert slot's are split
# in the kernel (one to eight row tiles a slot: gathering three pieces a
# slot cost XLA more than the split costs the tile); the weight's stay
# FLOAT32 holding bf16 values and the dot runs at DEFAULT precision,
# Mosaic's one bf16 pass, whose rounding of an operand that is a bf16 number
# already is exact and costs the vector unit nothing: 6.5 operations a
# weight where HIGHEST spent 11.5 (the tile is bound by them up to 32 rows,
# and by the MXU's five passes at a chunk's 128).

def _mask_pieces(x: jax.Array, n: int):
    """float32 -> ``n`` float32 arrays holding bf16 values, each the top 8
    bits of what the ones before left (a mask: truncation, so what is left
    keeps its sign and loses 8 bits a piece; no cast to bfloat16 and back,
    which XLA may elide), the last one all of it: their sum is ``x``
    exactly, and the last IS a bf16 number when ``x`` has at most 8 n
    significant bits. Two pieces of a Q40 weight (15 bits at most), three of
    any float32. Lowers in a kernel and outside one."""
    pieces = []
    for _ in range(n - 1):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        pieces.append(jax.lax.bitcast_convert_type(
            jax.lax.bitwise_and(bits, jnp.uint32(0xFFFF0000)), jnp.float32))
        x = jax.lax.sub(x, pieces[-1])
    return (*pieces, x)


def _stack_rows(block_t: int) -> int:
    """Rows one t-tile's three pieces take in the stacked planes: 3 bt,
    rounded up to bfloat16's 16-row sublane tile (an 8-row dispatch: 32,
    the last 8 zeros)."""
    return -(-3 * block_t // 16) * 16


def _stack_pieces(p: jax.Array, block_t: int) -> jax.Array:
    """(..., t, k) float32 -> (..., t / bt * S, k) bfloat16, S =
    ``_stack_rows(bt)``: for each t-tile of ``block_t`` rows its rows' hi
    pieces, then mid, then lo, then the padding."""
    *lead, t, k = p.shape
    s = _stack_rows(block_t)
    x = jnp.stack(_mask_pieces(p, 3), axis=-3)       # (..., 3, t, k)
    x = x.reshape(*lead, 3, t // block_t, block_t, k)
    x = jnp.moveaxis(x, -4, -3).reshape(*lead, t // block_t, 3 * block_t, k)
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, s - 3 * block_t), (0, 0)])
    return x.reshape(*lead, t // block_t * s, k).astype(jnp.bfloat16)


def _five_pass_dot(x3: jax.Array, w: jax.Array, rows: int) -> jax.Array:
    """(rows, R) float32: ``rows`` activation rows against a float32 Q40
    tile ``w`` (K, R) in five single bf16 passes, as two dots: ``x3``
    (>= 3 rows, K) holds the rows' [hi; mid; lo] pieces (bf16 numbers, as
    bfloat16 or float32), so each weight piece is pushed to the MXU once:
    x3 . w_hi and [hi; mid] . w_lo. The five slabs are added the small
    terms first."""
    dn = (((1,), (0,)), ((), ()))
    w_hi, w_lo = _mask_pieces(w, 2)
    add = jax.lax.add

    def cut(m, lo, hi):      # rows lo..hi (lax: an operator or an index is
        return jax.lax.slice(m, (lo, 0), (hi, m.shape[1]))   # a jitted call)

    a = jax.lax.dot_general(x3, w_hi, dn, preferred_element_type=jnp.float32)
    b = jax.lax.dot_general(cut(x3, 0, 2 * rows), w_lo, dn,
                            preferred_element_type=jnp.float32)
    return add(add(add(cut(a, 2 * rows, 3 * rows), cut(b, rows, 2 * rows)),
                   add(cut(a, rows, 2 * rows), cut(b, 0, rows))),
               cut(a, 0, rows))


def _planes_dot(q, s, xlo, xhi, rows: int, bf16: bool) -> jax.Array:
    """(rows, R) float32: G nibble planes of a tile against their columns of
    the rows, ONE contraction G nb deep a nibble half. ``q`` (G, nb, R) int32
    holds the planes' bytes, ``s`` (nb, R) the scales; ``xlo`` / ``xhi``
    (.., G nb) the rows' values under the low / high nibbles, value j of
    block b at column j nb + b (``_merged_planes``). The planes are
    dequantized to float32 (exact) and viewed (G nb, R), a renumbering of
    whole sublane tiles where nb is a multiple of 8. Parity: ``xlo`` / ``xhi``
    hold the rows' [hi; mid; lo] pieces and each half is ``_five_pass_dot``;
    ``bf16`` (fast-prefill): (rows, G nb), one piece a side, one pass. The
    dense tile calls it once a group of planes (``_matmul_body_nb``), the
    expert slots' once a tile (ops/pallas_moe._mxu_body_merged)."""
    g, nb, r = q.shape
    dn = (((1,), (0,)), ((), ()))
    acc = None
    lax = jax.lax   # (primitives: an operator on a tracer is a jitted call,
    #                  traced on every run; the jaxpr is the operators')
    for x, codes in ((xlo, lax.bitwise_and(q, 0xF)),
                     (xhi, lax.shift_right_arithmetic(q, 4))):
        w = lax.mul(lax.convert_element_type(lax.sub(codes, 8), jnp.float32),
                    s[None]).reshape(g * nb, r)
        if bf16:
            a = jax.lax.dot_general(x.astype(jnp.bfloat16),
                                    w.astype(jnp.bfloat16), dn,
                                    preferred_element_type=jnp.float32)
        else:
            a = _five_pass_dot(x, w, rows)
        acc = a if acc is None else lax.add(acc, a)
    return acc


def _matmul_body_nb(qs3, s, xlo_ref, xhi_ref, out_ref, bf16=False):
    """T>1 MXU body, nb-major: qs3 (NJ, nb, R), s (nb, R); out (bt, R). The
    contraction is a STANDARD (M,K)x(K,N) dot (x rows against weights K x R)
    over G nibble planes at a time, K = G nb (``_planes_dot``), NJ / G
    groups a nibble half; G is read off the planes, xlo/xhi (NJ / G, .,
    G nb), which ``_mxu_nb_planes`` lays as ``_pick_planes`` says. G = 1 is
    a dot a plane (every leaf until PR 51), G = 16 the expert slots' form.
    Parity mode (``bf16`` False): xlo/xhi (NJ / G, S, G nb) bfloat16 hold
    the rows' three pieces stacked (``_stack_pieces``) and each group is
    ``_five_pass_dot``: the planes are dequantized to float32 (exact), split
    in TWO bf16 pieces (exact: a Q40 weight has 15 bits) and multiplied in
    five single bf16 passes, as close to float64 as HIGHEST's six and
    without its three-way split of the weight. ``bf16`` (fast-prefill):
    xlo/xhi (NJ / G, bt, G nb) float32, one piece a side, one pass, the
    same G. PERF.md section 7 has what a deeper contraction buys by shape."""
    bt = out_ref.shape[0]
    g = NJ // xlo_ref.shape[0]
    acc = None
    for k in range(NJ // g):
        q = qs3[k * g:(k + 1) * g].astype(jnp.int32)     # (G, nb, R)
        a = _planes_dot(q, s, xlo_ref[k], xhi_ref[k], bt, bf16)
        acc = a if acc is None else jax.lax.add(acc, a)
    out_ref[...] = acc


def _kernel_mxu_nb(qs_ref, scale_ref, xlo_ref, xhi_ref, out_ref, *,
                   bf16=False):
    _matmul_body_nb(qs_ref, scale_ref[...], xlo_ref, xhi_ref, out_ref, bf16)


def _kernel_mxu_nb_stacked(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref,
                           out_ref, *, bf16=False):
    del layer_ref
    _matmul_body_nb(qs_ref[0], scale_ref[0], xlo_ref, xhi_ref, out_ref, bf16)


# Where the bodies meet, chosen by T (what a dispatch observes; an nb-major
# dispatch of 2..8 rows that is told its live rows picks by them as well:
# ``_q40_matmul_nbmajor``).
# d-major leaves: the VPU multi body (one accumulator a row) up to
# MULTI_T_MAX rows, the MXU body beyond, where the per-row accumulators
# crowd VMEM. nb-major leaves: the matvec at T = 1 and the MXU body
# (_matmul_body_nb) for every T > 1, rows padded to a multiple of 8, so a
# 2..8-row decode dispatch is ONE 8-row tile of the body a 16-row dispatch
# and a prefill chunk run (_q40_matmul_nbmajor). An nb-major VPU multi body
# served T = 2..4 until PR 32 and overflowed scoped VMEM at 8; on the chip
# the MXU tile beat it at 3 and 4 rows on every 7B leaf (by 24-76 %) and at
# 2 rows over a layer's four leaves (by 8 %), so it went (PERF.md section 7).
MULTI_T_MAX = 8

# Raised scoped-VMEM limit for the T>1 kernels (MXU prefill bodies and the
# T<=8 VPU multi bodies batched decode uses) and the nb-major matvecs (a
# 5.9 MB tile twice buffered beside 1.7 MB of planes at 544 blocks a row;
# the vector body's lane-padded x planes alone passed 16 MiB there, PR 31):
# Mosaic's conservative stack accounting rejects several measured-fine tile
# sets at the default 16 MB (e.g. 22.6M at w2's nb=344/bt=32 prefill tile,
# 26.3M at the 13B B=2 multi tile) though v5e has 128 MB physical.
# Same approach as ops/pallas_layer._VMEM_LIMIT.
_VMEM64_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def to_i4_planes(tree):
    """Re-express every Q40KernelNb leaf of a param tree (or a single leaf)
    as its signed-int4 plane form; every other leaf stays as it is (see
    chain_weight_prep for why a d-major leaf has no such form). Jit-internal
    only — see Q40KernelNbI4's device-only caveat."""
    def planes(qs_t):
        # cast each nibble plane to int4 BEFORE the concat: an int32
        # intermediate of the whole concat is 8x the packed bytes and
        # OOMs 13B (24.3 GB observed); int4-typed pieces keep transients
        # at half the u8 size
        q = qs_t.astype(jnp.int32)
        lo = ((q & 0xF) - 8).astype(jnp.int4)
        hi = ((q >> 4) - 8).astype(jnp.int4)
        return jnp.concatenate([lo, hi], axis=-3)

    def conv(v):
        if isinstance(v, Q40KernelNb):
            return Q40KernelNbI4(planes(v.qs_t), v.scale)
        return v

    if isinstance(tree, (Q40Kernel, Q40KernelNb)):
        return conv(tree)
    return {k: conv(v) for k, v in tree.items()}


def chain_weight_prep(params, i4: bool):
    """Decode-chain weight prep, run INSIDE the jitted chain (this runtime
    cannot pass int4 across a jit boundary). With ``i4`` (the engine's
    resolved ``Q40Layout.i4_chain``, ops/linear.q40_body_policy) every
    Q40KernelNb leaf is re-expressed as (code - 8) signed-int4 planes
    (to_i4_planes); the T=1 matvec body then needs ONE convert + mul + add
    per plane instead of convert/mask/shift/2xconvert/2xmul/2xadd —
    measured 701 GB/s vs 638 on the 13B w13 shape, against a 746 GB/s DMA
    floor (probe since deleted; runtime of round 5). Cost: the conversion
    pass (~0.06 ms/token amortized over a 64-step chain) and TRANSIENT
    extra HBM for the i4 copy while the chain runs (~+50% of the codes'
    bytes; the u8 originals remain the placed arguments: fine at 7B, OOMs
    13B). Exact same
    integers — parity is bit-tight with the u8 bodies.

    NB-MAJOR LEAVES ONLY: the d-major s4 body measured ~6x SLOWER than u8
    on hardware (64 vs 10.3 ms/token at 7B — Mosaic's s4->f32 unpack on
    (rows, nb) tiles is pathological; BASELINE.md r5), so it went (PR 43)
    and a d-major leaf passes through as it is."""
    return to_i4_planes(params) if i4 else params


def _matvec_body_nb_i4(qs4, s, x32_ref, out_ref):
    """T=1 nb-major int4 body: qs4 (32, nb, R), s (nb, R), x32 (32, nb, 1);
    out (1, R). The 'i4' winner of the nb-major body probe (probe since
    deleted; runtime of round 5) verbatim."""
    acc = None
    for j in range(2 * NJ):
        w = qs4[j].astype(jnp.float32)               # (nb, R)
        a = w * x32_ref[j]                           # (nb, 1) bcast over R
        acc = a if acc is None else acc + a
    out_ref[...] = jnp.sum(acc * s, axis=0, keepdims=True)  # (1, R)


def _kernel_matvec_nb_i4_stacked(layer_ref, qs_ref, scale_ref, x32_ref,
                                 out_ref):
    del layer_ref
    _matvec_body_nb_i4(qs_ref[0], scale_ref[0], x32_ref, out_ref)


def _kernel_matvec_nb_i4(qs_ref, scale_ref, x32_ref, out_ref):
    _matvec_body_nb_i4(qs_ref, scale_ref[...], x32_ref, out_ref)


def _matmul_body(qs3, s, xlo_ref, xhi_ref, out_ref, bf16=False):
    """Shared T>1 MXU body: qs3 (NJ, R, nb) codes view, s (R, nb) scales.

    ``bf16`` (fast-prefill, ops/linear.matmul_precision): bf16 MXU passes
    with f32 accumulation instead of the 3-pass HIGHEST f32 discipline —
    T>8 prefill is MXU-bound, so this is the big lever. The flag is threaded
    EXPLICITLY from q40_matmul (where the trace-time contextvar is read)
    because _q40_matmul_2d/_q40_matmul_stacked are themselves jitted and
    their trace cache cannot see the contextvar — a cached parity trace
    would silently serve the bf16 program (and did, round 2).
    """
    dn = (((1,), (1,)), ((), ()))                # contract both minor dims
    wdt = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else jax.lax.Precision.HIGHEST
    acc = None
    # unrolled over the 16 nibble planes: one grid step computes the whole
    # output tile, so the packed bytes stream in as few large DMAs and the
    # compiler can software-pipeline unpack against the MXU
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)             # (R, nb)
        wlo = (((q & 0xF) - 8).astype(jnp.float32) * s).astype(wdt)
        whi = (((q >> 4) - 8).astype(jnp.float32) * s).astype(wdt)
        # parity mode: HIGHEST = true f32 MXU passes; decode is HBM-bound on
        # the packed weights, so the extra passes don't move the bottleneck
        a = jax.lax.dot_general(xlo_ref[j].astype(wdt), wlo, dn,
                                preferred_element_type=jnp.float32,
                                precision=prec)
        a = a + jax.lax.dot_general(xhi_ref[j].astype(wdt), whi, dn,
                                    preferred_element_type=jnp.float32,
                                    precision=prec)
        acc = a if acc is None else acc + a
    out_ref[...] = acc


def _kernel(qs_ref, scale_ref, xlo_ref, xhi_ref, out_ref, *, bf16=False):
    _matmul_body(qs_ref, scale_ref[...], xlo_ref, xhi_ref, out_ref, bf16)


def _kernel_stacked(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref, out_ref,
                    *, bf16=False):
    del layer_ref  # consumed by the index maps
    _matmul_body(qs_ref[0], scale_ref[0], xlo_ref, xhi_ref, out_ref, bf16)


def _split_x(x: jax.Array, nb: int) -> tuple[jax.Array, jax.Array]:
    """(T, n) f32 -> xlo/xhi (16, T, nb) in kernel plane order."""
    t = x.shape[0]
    x3 = x.reshape(t, nb, QK)
    xlo = jnp.transpose(x3[:, :, :NJ], (2, 0, 1))
    xhi = jnp.transpose(x3[:, :, NJ:], (2, 0, 1))
    return xlo, xhi


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_t", "interpret",
                                    "bf16"))
def _q40_matmul_2d(qs_t, scale, x, *, block_rows, block_t, interpret,
                   bf16=False):
    _, d, nb = qs_t.shape
    t = x.shape[0]
    xlo, xhi = _split_x(x.astype(jnp.float32), nb)
    if t == 1:
        xsum = jnp.sum(xlo[:, 0] + xhi[:, 0], axis=0, keepdims=True)  # (1, nb)
        out = pl.pallas_call(
            _kernel_matvec,
            grid=(d // block_rows,),
            in_specs=[
                pl.BlockSpec((NJ, block_rows, nb), lambda i: (0, i, 0)),
                pl.BlockSpec((block_rows, nb), lambda i: (i, 0)),
                pl.BlockSpec((NJ, 1, nb), lambda i: (0, 0, 0)),
                pl.BlockSpec((NJ, 1, nb), lambda i: (0, 0, 0)),
                pl.BlockSpec((1, nb), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
            interpret=interpret,
        )(qs_t, scale, xlo, xhi, xsum)
        return out.reshape(1, d)
    if t <= MULTI_T_MAX:
        xsum = jnp.sum(xlo + xhi, axis=0)            # (t, nb)
        out = pl.pallas_call(
            _kernel_multi,
            grid=(d // block_rows,),
            in_specs=[
                pl.BlockSpec((NJ, block_rows, nb), lambda i: (0, i, 0)),
                pl.BlockSpec((block_rows, nb), lambda i: (i, 0)),
                pl.BlockSpec((NJ, t, nb), lambda i: (0, 0, 0)),
                pl.BlockSpec((NJ, t, nb), lambda i: (0, 0, 0)),
                pl.BlockSpec((t, nb), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, t), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((d, t), jnp.float32),
            # wide-nb 13B shapes (w2 nb=432 at t=2) measure ~26M of scoped
            # stack against the 16M default — raise like the MXU kernels
            compiler_params=_VMEM64_PARAMS,
            interpret=interpret,
        )(qs_t, scale, xlo, xhi, xsum)
        return jnp.transpose(out)                    # (t, d)
    grid = (t // block_t, d // block_rows)
    out = pl.pallas_call(
        functools.partial(_kernel, bf16=bf16),
        compiler_params=_VMEM64_PARAMS,
        grid=grid,
        in_specs=[
            pl.BlockSpec((NJ, block_rows, nb), lambda ti, i: (0, i, 0)),
            pl.BlockSpec((block_rows, nb), lambda ti, i: (i, 0)),
            pl.BlockSpec((NJ, block_t, nb), lambda ti, i: (0, ti, 0)),
            pl.BlockSpec((NJ, block_t, nb), lambda ti, i: (0, ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, block_rows), lambda ti, i: (ti, i)),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        interpret=interpret,
    )(qs_t, scale, xlo, xhi)
    return out


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_t", "interpret",
                                    "bf16"))
def _q40_matmul_stacked(layer, qs_t, scale, x, *, block_rows, block_t,
                        interpret, bf16=False):
    _, _, d, nb = qs_t.shape
    t = x.shape[0]
    xlo, xhi = _split_x(x.astype(jnp.float32), nb)
    if t == 1:
        xsum = jnp.sum(xlo[:, 0] + xhi[:, 0], axis=0, keepdims=True)  # (1, nb)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // block_rows,),
            in_specs=[
                pl.BlockSpec((1, NJ, block_rows, nb),
                             lambda i, L: (L[0], 0, i, 0)),
                pl.BlockSpec((1, block_rows, nb), lambda i, L: (L[0], i, 0)),
                pl.BlockSpec((NJ, 1, nb), lambda i, L: (0, 0, 0)),
                pl.BlockSpec((NJ, 1, nb), lambda i, L: (0, 0, 0)),
                pl.BlockSpec((1, nb), lambda i, L: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, 1), lambda i, L: (i, 0)),
        )
        out = pl.pallas_call(
            _kernel_matvec_stacked, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
            interpret=interpret,
        )(layer, qs_t, scale, xlo, xhi, xsum)
        return out.reshape(1, d)
    if t <= MULTI_T_MAX:
        xsum = jnp.sum(xlo + xhi, axis=0)            # (t, nb)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // block_rows,),
            in_specs=[
                pl.BlockSpec((1, NJ, block_rows, nb),
                             lambda i, L: (L[0], 0, i, 0)),
                pl.BlockSpec((1, block_rows, nb), lambda i, L: (L[0], i, 0)),
                pl.BlockSpec((NJ, t, nb), lambda i, L: (0, 0, 0)),
                pl.BlockSpec((NJ, t, nb), lambda i, L: (0, 0, 0)),
                pl.BlockSpec((t, nb), lambda i, L: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, t), lambda i, L: (i, 0)),
        )
        out = pl.pallas_call(
            _kernel_multi_stacked, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((d, t), jnp.float32),
            compiler_params=_VMEM64_PARAMS, interpret=interpret,
        )(layer, qs_t, scale, xlo, xhi, xsum)
        return jnp.transpose(out)                    # (t, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // block_t, d // block_rows),
        in_specs=[
            pl.BlockSpec((1, NJ, block_rows, nb),
                         lambda ti, i, L: (L[0], 0, i, 0)),
            pl.BlockSpec((1, block_rows, nb), lambda ti, i, L: (L[0], i, 0)),
            pl.BlockSpec((NJ, block_t, nb), lambda ti, i, L: (0, ti, 0)),
            pl.BlockSpec((NJ, block_t, nb), lambda ti, i, L: (0, ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, block_rows),
                               lambda ti, i, L: (ti, i)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_stacked, bf16=bf16), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(layer, qs_t, scale, xlo, xhi)


# T>1 tile cap: the MXU body materializes f32 (rows, nb) wlo/whi temporaries
# per unrolled plane on the scoped-VMEM stack; rows*nb above ~128k blows the
# 16MB limit at 7B shapes (observed: 512x344 -> 16.9M)
_MATMUL_ROWSXNB_CAP = 131072

# 2..MULTI_T_MAX rows, d-major VPU multi body: rows*nb*t words a tile. The
# compiler keeps several unrolled-plane temporaries live next to the t
# accumulators; 300k was sized against the old 16 MB scoped limit, and the
# multi kernels now run with the raised _VMEM64_PARAMS (wide-nb shapes
# measured ~26M), so it is a tile-size heuristic, not a hard ceiling:
# measured flat 300k/600k/1200k at 13B B=2 (probe since deleted; runtime of
# round 5) — tile granularity is not that path's limiter
_MULTI_ROWSXNBXT_CAP = 300_000

# Most rows a tile takes: the tuned d-major pick, and the nb-major VECTOR
# matvec's, T > 1 tile's and int4 body's (the MXU matvec: _pick_rows_t1).
# More rows trade grid steps for longer per-tile DMAs; the scoped-VMEM word
# budgets apply on top. io/kernel_cache.layout_key writes it into the key.
_TILE_ROWS_CAP = 768


def _pick_block_rows(d: int, t: int = 1, nb: int = 128,
                     block_t: int | None = None) -> int | None:
    """Output-tile rows, up to ~768/tile (amortizes grid-step overhead while
    keeping the unpack working set in VMEM).

    Three paths, three constraints (the t > 1 rules only bite on real TPU;
    interpret mode doesn't check):
    * t == 1 (matvec): out block (rows, 1) — rows is second-minor, any
      multiple-of-8 divisor works.
    * 1 < t <= MULTI_T_MAX (small-T VPU body): out block (rows, t) with the
      full t minor — rows again multiple-of-8, but the t per-row (rows, nb)
      f32 accumulators cap rows*nb*t for scoped-VMEM headroom.
    * t > MULTI_T_MAX (MXU body): out block (t_tile, rows) — rows is MINOR
      and must be a multiple of 128 or the whole d, with its own rows*nb cap
      for the f32 wlo/whi temporaries.
    """
    if t == 1:
        # rows*nb VMEM budget: the double-buffered tile set is ~(16+4) bytes
        # per (row, block) — 16 u8 code planes + one f32 scale — so 360k
        # keeps it under ~14.4 MB of the 16 MB scoped limit. Only binds at
        # very wide inputs (nb=896 at 70B's hidden/8=28672-wide w2 slice:
        # an uncapped 512-row tile measured 17.5 MB and failed to compile)
        step, cap = 8, max(8, 360_000 // nb)
    elif t <= MULTI_T_MAX:
        step, cap = 8, max(8, _MULTI_ROWSXNBXT_CAP // (t * nb))
    else:
        # MXU path. With a FULL 128-row t-tile Mosaic pipelines the
        # unrolled-plane f32 temporaries within the budget; at smaller
        # t-tiles it keeps more of them live and big row tiles overflow
        # scoped VMEM. Measured boundary: (nb=128, bt=32, rows=640) needs
        # 17.6M and fails to compile; (nb=128, bt=32, rows=256) passes;
        # (nb=344, bt=64, rows=256) is the round-1-proven 7B w2 prefill
        # tile; (nb=128, bt=128, rows=640) passes. So: full-bt keeps the
        # rows*nb word cap, small-bt caps rows at 256.
        if (block_t or 128) >= 128:
            step, cap = 128, _MATMUL_ROWSXNB_CAP // nb
        else:
            step, cap = 128, 256
    top = (min(d, _TILE_ROWS_CAP, cap) // step) * step
    for cand in range(top, 0, -step):
        if d % cand == 0:
            return cand
    # small odd dims: a full-d block is legal when it fits the same budget
    return d if d <= min(_TILE_ROWS_CAP, cap) else None


def kernel_supports(d: int, n: int) -> bool:
    """Whether pre-tiling a (d, n) weight to the kernel layout pays off:
    decided by the T=1 matvec path (the per-token hot loop). Other T values
    that the tiling rules can't handle (e.g. d=1376 = 11008/tp8 has no
    multiple-of-128 divisor for the T>8 MXU path) fall back INSIDE
    q40_matmul to a dequantize-then-dot on the packed weight, so prefill
    still works on any packed shape."""
    return _pick_block_rows(d, 1, n // QK) is not None


def _pick_block_t(t: int, nb: int) -> int:
    # cap the T tile so the xlo/xhi plane-sets (2 x NJ*bt*nb f32, DOUBLE
    # buffered by the pipeline) stay within a few MB of VMEM next to the
    # packed weight tile (observed: bt=256 at nb=128 -> 16.9M scoped OOM)
    cap = max(8, (3 * 1024 * 1024) // (NJ * nb * 8))
    if t <= min(cap, 128):
        return t
    for cand in (128, 64, 32, 16, 8):
        if cand <= cap and t % cand == 0:
            return cand
    return t


def _dequant_matmul(w: Q40Kernel, x2: jax.Array,
                    layer: jax.Array | None) -> jax.Array:
    """XLA fallback on an already-packed weight: dequantize the (layer's)
    kernel-layout blocks inline and dot. Used only for (d, t) combos the
    tiling rules can't place (see q40_matmul)."""
    from .quants import dequantize_q40_jax

    if layer is not None:
        w = Q40Kernel(w.qs_t[layer], w.scale[layer])
    qs = jnp.transpose(w.qs_t, (1, 2, 0))            # (d, nb, 16)
    wf = dequantize_q40_jax(qs, w.scale)
    # fast-prefill applies to ALL dispatch targets — without this the
    # tp-sharded band shapes that land here (e.g. d=1376=11008/8, no legal
    # MXU tiling) would silently run at parity speed
    return _precision_dot(wf, x2)


def _precision_dot(wf, x2):
    """Dequant-fallback einsum honoring the fast-prefill precision mode —
    THE one copy of this dispatch for the dequantize-then-dot paths (the
    kernel bodies carry their own threaded ``bf16`` flag)."""
    from .linear import matmul_mode

    if matmul_mode() == "bf16":
        return jnp.einsum("dn,tn->td", wf.astype(jnp.bfloat16),
                          x2.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("dn,tn->td", wf, x2.astype(jnp.float32),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def _pick_rows_nb(d: int, nb: int, words: int = 360_000,
                  most: int = _TILE_ROWS_CAP) -> int | None:
    """Row tile of an nb-major kernel: rows ride the LANES, so they must
    be a multiple of 128 — a d with no multiple-of-128 divisor (including
    every d < 128) returns None and the caller routes to the dequant
    fallback. The defaults are the VECTOR matvec's budget, sized for its
    whole-plane temporaries (rows*nb under the ~(16+4)-bytes-per-word
    scoped-VMEM budget the d-major matvec has, at most 768 rows); the T > 1
    tile and the int4 chain body start from them too. The MXU matvec
    (``_pick_rows_t1``) asks for its own."""
    top = min(d, most, max(128, words // nb))
    for cand in range(top - top % 128, 0, -128):
        if d % cand == 0:
            return cand
    return None


def _t1_mxu(nb: int) -> bool:
    """Whether a T = 1 nb-major leaf of ``nb`` blocks a row takes the MXU
    body (``_matvec_body_nb_mxu``): its groups are 8 blocks. The vector
    body keeps the rest (no model's leaf: an input width off the 256 grid).
    Nothing else decides: on the chip the MXU body won on every leaf of
    both decode cells, by 1.13 times (Mistral-7B's classifier) to 2.6
    (Yi-34B's 256-row ``wk`` shard, whose call was its XLA copies), PERF.md
    section 7."""
    return nb % _T1_GROUP == 0


def _pick_rows_t1(d: int, nb: int) -> int | None:
    """Row tile of the T = 1 nb-major matvec. The MXU body holds 32 blocks
    of a tile at a time, so its tile is bounded by the pipeline and not by
    temporaries: up to 1024 rows and 288k words (a 5.9 MB tile of codes and
    scales, twice buffered). A grid step costs about 0.4 us and the first
    tile's DMA and the last tile's products overlap nothing, so on the chip
    Mistral-7B's ``w13`` (128 blocks a row) ran 115 us at 512 rows and 103
    at 1024 or 2048, its ``w2`` (448) 64 / 60 / 62 at 256 / 512 / 1024
    (PERF.md section 7)."""
    if _t1_mxu(nb):
        return _pick_rows_nb(d, nb, words=288 * 1024, most=1024)
    return _pick_rows_nb(d, nb)


def _dequant_nb(qs_t, scale):
    """jnp dequant of an nb-major (16, nb, d) plane set -> f32 (d, n)."""
    lo = ((qs_t & 0xF).astype(jnp.int8) - jnp.int8(8))
    hi = ((qs_t >> 4).astype(jnp.int8) - jnp.int8(8))
    codes = jnp.concatenate([lo, hi], axis=0)        # (32, nb, d): j then j+16
    w = codes.astype(jnp.float32) * scale[None]
    d = scale.shape[-1]
    return jnp.transpose(w, (2, 1, 0)).reshape(d, -1)


def _t1_scratch(nb: int):
    """The MXU matvec's scratch: the row's block-diagonal planes and the
    blocks' sums (``_diag_planes_nb``)."""
    return [pltpu.VMEM((nb // _T1_GROUP, 3 * _T1_GROUP, QK * _T1_GROUP),
                       jnp.float32),
            pltpu.VMEM((nb, 1), jnp.float32)]


def _vector_planes_nb(x, nb: int):
    """The vector matvec's x planes: xlo / xhi (NJ, nb, 1), xsum (nb, 1)."""
    xlo, xhi = _split_x(x.astype(jnp.float32), nb)   # (NJ, 1, nb)
    xlo = jnp.transpose(xlo, (0, 2, 1))              # (NJ, nb, 1)
    xhi = jnp.transpose(xhi, (0, 2, 1))
    xsum = jnp.sum(xlo[:, :, 0] + xhi[:, :, 0], axis=0)[:, None]  # (nb, 1)
    return xlo, xhi, xsum


def _matvec_nb_call(layer, qs_t, scale, x, block_rows, interpret):
    """The T = 1 nb-major ``pallas_call``: a 2-D leaf (``layer`` None) or
    one layer of a stack, which the scalar-prefetched index picks; the MXU
    body or the vector body, which ``_t1_mxu`` picks."""
    nb, d = qs_t.shape[-2:]
    pre = () if layer is None else (layer,)

    def tile(*shape):        # a row tile of the leaf (of the picked layer)
        return pl.BlockSpec(
            (1,) * len(pre) + shape + (block_rows,),
            lambda i, *L: (*(l[0] for l in L), *(0,) * len(shape), i))

    def whole(*shape):       # what every row tile reads whole
        return pl.BlockSpec(shape, lambda i, *L: (0,) * len(shape))

    if _t1_mxu(nb):
        kernel = _kernel_matvec_nb_mxu_stacked if pre else _matvec_body_nb_mxu
        planes = (x.astype(jnp.float32).reshape(nb // 4, 128),)
        specs, scratch = [whole(nb // 4, 128)], _t1_scratch(nb)
    else:
        kernel = _kernel_matvec_nb_stacked if pre else _kernel_matvec_nb
        planes = _vector_planes_nb(x, nb)
        specs, scratch = [whole(NJ, nb, 1), whole(NJ, nb, 1), whole(nb, 1)], []
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pre), grid=(d // block_rows,),
            in_specs=[tile(NJ, nb), tile(nb), *specs],
            out_specs=pl.BlockSpec((1, block_rows), lambda i, *L: (0, i)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(*pre, qs_t, scale, *planes)                     # (1, d)


# the benchmark finds the T = 1 calls by these two names
# (benchmark/harness/reduce_trace.py: a device operation is Q40's by the
# jitted function's name), so both bodies run under them
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _q40_matvec_nb_2d(qs_t, scale, x, *, block_rows, interpret):
    return _matvec_nb_call(None, qs_t, scale, x, block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _q40_matvec_nb_stacked(layer, qs_t, scale, x, *, block_rows, interpret):
    return _matvec_nb_call(layer, qs_t, scale, x, block_rows, interpret)


# -- a part-filled decode dispatch: the body picked by its LIVE rows -----------
#
# A decode dispatch of 2 to MULTI_T_MAX rows is compiled for all of them and
# often carries one or two (a chat server's pool: 1.65 rows a dispatch in
# ``mistral7b.serve-chat``). Which rows ride is data the step program holds
# (``ops/linear.live_rows``: the staged block's last column), so where the
# forward says so the dispatch below picks by it, as the expert slot kernel
# picks by a slot's fill: up to ``LIVE_ROWS_MAX`` live rows run the stacked
# block-diagonal product above on THOSE rows (``_q40_live_nb``), a fuller
# dispatch the tile as ever. One ``lax.cond`` a call, so that each body
# keeps its own row tile and the tile's x planes are cut only where it runs
# (PERF.md section 7 has the one-leaf table that chose it).

LIVE_ROWS_MAX = 2   # fullest dispatch of the stacked body: ONE body stacks two


def live_census(mask: jax.Array, top: int = LIVE_ROWS_MAX) -> jax.Array:
    """(1 + top,) int32 of a dispatch whose rows ``mask`` (B,) marks live:
    how many are, and the indices of the first ``top`` of them in row order
    (past the live count: the first live row's again, a row that exists)."""
    alive = mask.reshape(-1) > 0
    count = jnp.sum(alive)
    rank = jnp.cumsum(alive) * alive                 # 1.. on the live rows
    idx = [jnp.argmax(rank == k + 1) for k in range(top)]
    return jnp.stack([count] + [jnp.where(k < count, i, idx[0])
                                for k, i in enumerate(idx)]).astype(jnp.int32)


def live_rows_top(t: int, d: int, nb: int) -> int:
    """Most live rows of a ``t``-row dispatch that take the stacked body on
    an nb-major leaf (d, nb blocks a row); 0: the dispatch never does (one
    row, more than ``MULTI_T_MAX``, a block count off the 8 grid or a ``d``
    no row tile places). What the call itself sees decides, and the engine's
    counter asks the same question (runtime/continuous)."""
    if not 1 < t <= MULTI_T_MAX or not _t1_mxu(nb):
        return 0
    return LIVE_ROWS_MAX if _pick_rows_t1(d, nb) else 0


def _kernel_live_nb(*refs, stacked: bool):
    """One row tile of a part-filled dispatch: live_ref (1 + top,) = [live
    count | the live rows' indices]; x_ref (t, n / 128, 128) ALL the
    dispatch's rows as they are, of which the live ones are read by index;
    out (t, R): a live row's product at its index, zeros everywhere else
    (nothing unspecified leaves the call)."""
    if stacked:
        _, live_ref, qs_ref, s_ref, x_ref, out_ref, l_scr, xs_scr, sum_scr = \
            refs
        qs_ref, s_ref = qs_ref.at[0], s_ref.at[0]
    else:
        live_ref, qs_ref, s_ref, x_ref, out_ref, l_scr, xs_scr, sum_scr = refs
    count = live_ref[0]

    # at the call's first row tile, for its later ones to read
    @pl.when(pl.program_id(0) == 0)
    def _():
        _diag_planes(x_ref, l_scr, xs_scr, sum_scr, count,
                     lambda k: live_ref[1 + k])

    rows = _diag_product(qs_ref, s_ref, l_scr, xs_scr)           # (top, R)
    at = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    out = jnp.zeros(out_ref.shape, jnp.float32)
    for k in range(rows.shape[0]):
        out = jnp.where((at == live_ref[1 + k]) & (count > k),
                        rows[k:k + 1], out)
    out_ref[...] = out


def _live_nb_call(live, layer, qs_t, scale, x, block_rows, interpret):
    """The part-filled dispatch's ``pallas_call``: ``live`` (1 + top,)
    ``live_census`` of the rows of ``x`` (t, n), ``top`` read off its
    length; a 2-D leaf (``layer`` None) or one layer of a stack."""
    nb, d = qs_t.shape[-2:]
    t, top = x.shape[0], live.shape[0] - 1
    pre = (live,) if layer is None else (layer, live)

    def tile(*shape):        # a row tile of the leaf (of the picked layer)
        return pl.BlockSpec(
            (1,) * (len(pre) - 1) + shape + (block_rows,),
            lambda i, *S: (*(l[0] for l in S[:-1]), *(0,) * len(shape), i))

    return pl.pallas_call(
        functools.partial(_kernel_live_nb, stacked=layer is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pre), grid=(d // block_rows,),
            in_specs=[tile(NJ, nb), tile(nb),
                      pl.BlockSpec((t, nb // 4, 128),
                                   lambda i, *S: (0, 0, 0))],
            out_specs=pl.BlockSpec((t, block_rows), lambda i, *S: (0, i)),
            scratch_shapes=_diag_scratch(nb, top)),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(*pre, qs_t, scale, x.astype(jnp.float32).reshape(t, nb // 4, 128))


# a capture finds these two by ``q40`` in their names, as it does the tile
# and the matvec (benchmark/harness/reduce_trace.classify)
@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _q40_live_nb_2d(live, qs_t, scale, x, *, block_rows, interpret):
    return _live_nb_call(live, None, qs_t, scale, x, block_rows, interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _q40_live_nb_stacked(live, layer, qs_t, scale, x, *, block_rows,
                         interpret):
    return _live_nb_call(live, layer, qs_t, scale, x, block_rows, interpret)


# Nibble planes one dot of the T > 1 tile may contract over: divisors of NJ.
_PLANES = (1, 2, 4, 8, 16)

# Most float32 words one merged group's planes may hold, G nb R: the expert
# slots' measured boundary at 32 rows a slot (ops/pallas_moe._slot_block_rows:
# all 16 planes of rows x nb = _MATMUL_ROWSXNB_CAP / 2 words). No leaf of
# the nine benchmark configurations comes within a quarter of it at today's
# row tiles; the chip's compiler wants 1 to 7 MiB of scoped VMEM for them.
_MERGED_WORDS_CAP = NJ * _MATMUL_ROWSXNB_CAP // 2


def _pick_planes(nb: int, block_t: int) -> int:
    """Nibble planes G one dot of the T > 1 nb-major tile contracts over
    (``_matmul_body_nb``), from what a call observes: the leaf's blocks a
    row and the rows of a t-tile. Nothing else picks.

    A dot a plane (G = 1) contracts ``nb`` deep, and the MXU pays whole
    128-deep pushes: a plane of 80 blocks pushes 1.6 times its weights, one
    of 16 eight times. The candidate is the SMALLEST G that makes ``G nb``
    a whole number of pushes (1 where nb is on the 128 grid, 16 at nb 24 or
    56): on the chip it won or tied at every (leaf, rows) read, and more
    planes a dot than that read level or up to 3 % behind, with G times the
    float32 planes live. It is taken

    * at a t-tile of 32 rows or more, where the MXU's pushes show: x 1.06
      to 1.38 at 32 rows on nb 80 / 160 / 192 / 224 / 288 / 320 / 448 / 544
      / 576 (nb 112: level), x 1.8 to 4.5 up to 64; x 1.1 to 1.6 and x 2 to
      7 at a chunk's 128, by the padding a plane had;
    * under 32 rows only where a plane fills at most HALF a push (nb up to
      64: x 1.0 to 1.6 at 8 rows): there the tile is bound by the unpack,
      which merging does not touch, and on every wider leaf a deeper
      contraction read 1 to 8 % SLOWER at 8 and 16 rows.

    A block count off the 8 grid (no model's) keeps G = 1: the merged view
    renumbers whole sublane tiles. So does a leaf whose merged group would
    not fit a 256-row tile under ``_MERGED_WORDS_CAP`` (344 blocks a row, G
    16: no benchmark leaf): a smaller row tile was not measured against the
    deeper dot. PERF.md section 7 has the table."""
    if nb % 8:
        return 1
    fill = next(g for g in _PLANES if g * nb % 128 == 0)
    if block_t < 32 and 2 * nb > 128:
        return 1
    return fill if fill * nb * 256 <= _MERGED_WORDS_CAP else 1


def _pick_rows_mxu(d: int, nb: int, block_t: int, planes: int) -> int | None:
    """Row tile of the T > 1 nb-major tile: rows ride the lanes (a multiple
    of 128 dividing ``d``, else None and the caller dequantizes and dots).
    The body's float32 temporaries obey the measured rows x nb boundary of
    the d-major path (``_MATMUL_ROWSXNB_CAP``; ``_pick_rows_nb``'s matvec
    budget is looser), and at a t-tile short of the full 128 rows Mosaic
    keeps more of them live: 256 rows at most there (see
    ``_pick_block_rows``; on the chip 512 rows read 8 to 13 % slower than
    256 at 8 and 32 rows on 128 blocks a row). A merged group of ``planes``
    planes keeps its words under ``_MERGED_WORDS_CAP`` too, which no leaf
    ``_pick_planes`` merges comes near at these tiles."""
    rows = _pick_rows_nb(d, nb)
    if rows is None:
        return None
    cap = min(_MATMUL_ROWSXNB_CAP // nb, _MERGED_WORDS_CAP // (planes * nb))
    if block_t < 128:
        cap = min(cap, 256)
    return next((r for r in range(min(rows, cap - cap % 128), 0, -128)
                 if d % r == 0), None)


def _mxu_nb_planes(x, nb: int, block_t: int, bf16: bool, planes: int = 1):
    """The nb-major MXU body's x planes and the rows of one t-tile in them,
    ``planes`` (G) nibble planes merged into a dot's contraction:
    (NJ / G, t, G nb) float32 under ``bf16`` (fast-prefill: one piece, cast
    in the kernel), value j' of a group's block b at column j' nb + b
    (ops/pallas_moe._merged_planes' order within the group), else the three
    bf16 pieces of every row, stacked a t-tile (``_stack_pieces``), split
    ONCE a call here and not once a row tile in the kernel. The same bytes
    at every G; in VMEM fewer the larger G where nb is under 128, since a
    block pads its minor dim to 128 lanes."""
    x = x.astype(jnp.float32)
    if planes == 1:
        xlo, xhi = _split_x(x, nb)                   # (NJ, t, nb) — natural
    else:
        # ONE transpose from the rows: cutting the planes first and merging
        # them after cost XLA up to 50 us a call more (PERF.md section 7)
        t, groups = x.shape[0], NJ // planes
        x5 = x.reshape(t, nb, 2, groups, planes)
        xlo, xhi = (jnp.transpose(x5[:, :, half], (2, 0, 3, 1))
                    .reshape(groups, t, planes * nb) for half in (0, 1))
    if bf16:
        return xlo, xhi, block_t
    return (_stack_pieces(xlo, block_t), _stack_pieces(xhi, block_t),
            _stack_rows(block_t))


def _mxu_nb_call(layer, qs_t, scale, x, block_rows, block_t, planes,
                 interpret, bf16):
    """The T > 1 nb-major ``pallas_call``: a 2-D leaf (``layer`` None) or
    one layer of a stack, which the scalar-prefetched index picks."""
    nb, d = qs_t.shape[-2:]
    t = x.shape[0]
    pre = () if layer is None else (layer,)
    xlo, xhi, x_rows = _mxu_nb_planes(x, nb, block_t, bf16, planes)

    def tile(*shape):        # a row tile of the leaf (of the picked layer)
        return pl.BlockSpec(
            (1,) * len(pre) + shape + (block_rows,),
            lambda ti, i, *L: (*(l[0] for l in L), *(0,) * len(shape), i))

    x_spec = pl.BlockSpec((NJ // planes, x_rows, planes * nb),
                          lambda ti, i, *L: (0, ti, 0))
    return pl.pallas_call(
        functools.partial(_kernel_mxu_nb_stacked if pre else _kernel_mxu_nb,
                          bf16=bf16),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pre),
            grid=(t // block_t, d // block_rows),
            in_specs=[tile(NJ, nb), tile(nb), x_spec, x_spec],
            out_specs=pl.BlockSpec((block_t, block_rows),
                                   lambda ti, i, *L: (ti, i))),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(*pre, qs_t, scale, xlo, xhi)


# the benchmark finds the T > 1 tile by these two names, as it does the
# matvec by the two above
@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_t", "interpret",
                                    "bf16", "planes"))
def _q40_mxu_nb_2d(qs_t, scale, x, *, block_rows, block_t, interpret,
                   bf16=False, planes=1):
    return _mxu_nb_call(None, qs_t, scale, x, block_rows, block_t, planes,
                        interpret, bf16)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_t", "interpret",
                                    "bf16", "planes"))
def _q40_mxu_nb_stacked(layer, qs_t, scale, x, *, block_rows, block_t,
                        interpret, bf16=False, planes=1):
    return _mxu_nb_call(layer, qs_t, scale, x, block_rows, block_t, planes,
                        interpret, bf16)


def _q40_matmul_nbmajor(w: Q40KernelNb, x: jax.Array,
                        interpret: bool | None,
                        layer: jax.Array | None) -> jax.Array:
    """nb-major dispatch: every T on a kernel, the body picked by T, at
    T = 1 by whether the leaf's block count is a multiple of 8, and at 2 to
    ``MULTI_T_MAX`` rows by the dispatch's LIVE rows where the forward being
    traced told them (``ops/linear.live_rows``; since PR 63).
    T = 1 the matvec: the MXU body (``_matvec_body_nb_mxu``: raw codes
    pushed once against the row's three bf16 pieces laid block-diagonal,
    the scale applied a block) where ``_t1_mxu(nb)``, which is every leaf
    of every model; the vector body for a block count off the 8 grid.
    Nothing else picks: no leaf timed on the chip lost to the vector body
    (``_t1_mxu`` says by how much). Anything wider the MXU tile with the
    standard (M,K)x(K,N) dot (``_five_pass_dot``: the weight's two bf16
    pieces against the rows' three, split once a call, exact on both sides
    and as close to float64 as HIGHEST; one piece a side where the caller
    traced under bf16 precision, the same planes a dot): rows are padded to
    a multiple of 8, so a 2..8-row decode dispatch is ONE 8-row t-tile of
    the body a 16-row dispatch and a prefill chunk run. How many nibble
    planes one dot contracts over is ``_pick_planes(nb, block_t)``'s, the
    row tile ``_pick_rows_mxu``'s: still ONE Pallas call a leaf, under the
    same two names. That tile's time does not depend on the rows it
    carries, so a dispatch of 2 to ``MULTI_T_MAX`` rows whose forward said
    which of them ride (the serving step program: a pool is seldom full)
    holds a second call beside it under one ``lax.cond`` on the live count,
    which is data: 1 to ``LIVE_ROWS_MAX`` live rows run the T = 1 product
    with those rows stacked (``_q40_live_nb``: the live rows read by index,
    every dead row written as zeros), anything fuller the tile. A call
    that is told nothing, or told of other rows than it is given, traces
    what it always traced. Dequantize-then-dot serves a chunk traced under
    bf16 precision (see q40_matmul) and a ``d`` the row tiler cannot
    place."""
    from .linear import dispatch_live_rows, matmul_mode

    qs_t, scale = w.qs_t, w.scale
    nb, d = qs_t.shape[-2], qs_t.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if x2.shape[-1] < nb * QK:
        # a leaf packed with zero blocks past its input width
        # (ops/linear.Q40Layout.pad_blocks): zeros meet them
        x2 = jnp.pad(x2, ((0, 0), (0, nb * QK - x2.shape[-1])))
    given = x2.shape[0]
    # what the forward being traced said of THESE rows, if it said anything
    live = dispatch_live_rows(given) if live_rows_top(given, d, nb) else None
    if given > 1 and given % 8 != 0:
        x2 = jnp.pad(x2, ((0, (-given) % 8), (0, 0)))
    t = x2.shape[0]

    def rows_of(out):        # the rows the caller gave, in its shape
        return (out if t == given else out[:given]).reshape(*lead, d)

    bf16 = matmul_mode() == "bf16"
    block_t = _pick_block_t(t, nb)
    planes = 1
    if t == 1:
        rows = _pick_rows_t1(d, nb)
    elif bf16 and t > MULTI_T_MAX:
        # a chunk under bf16 precision dequantizes once and dots (q40_matmul
        # says why); a decode dispatch of up to MULTI_T_MAX rows never does
        rows = None
    else:
        planes = _pick_planes(nb, block_t)
        rows = _pick_rows_mxu(d, nb, block_t, planes)
    if rows is not None:
        pre = () if layer is None else (
            jnp.asarray(layer, dtype=jnp.int32).reshape(1),)
        if t == 1:
            call = _q40_matvec_nb_stacked if pre else _q40_matvec_nb_2d
            return rows_of(call(*pre, qs_t, scale, x2, block_rows=rows,
                                interpret=interpret))
        tile = functools.partial(
            _q40_mxu_nb_stacked if pre else _q40_mxu_nb_2d, block_rows=rows,
            block_t=block_t, interpret=interpret, bf16=bf16, planes=planes)
        if live is None:
            out = tile(*pre, qs_t, scale, x2)
        else:
            few = functools.partial(
                _q40_live_nb_stacked if pre else _q40_live_nb_2d,
                block_rows=_pick_rows_t1(d, nb), interpret=interpret)
            out = jax.lax.cond(
                (live[0] >= 1) & (live[0] < live.shape[0]), few,
                lambda lv, *ops: tile(*ops), live, *pre, qs_t, scale, x2)
        return rows_of(out)
    if layer is not None:
        qs_t = qs_t[layer]
        scale = scale[layer]
    wf = _dequant_nb(qs_t, scale)
    return rows_of(_precision_dot(wf, x2))


def _dequant_i4(w: Q40KernelNbI4) -> jax.Array:
    """f32 dense weight from int4 planes (the T>1 / untileable fallback):
    plane index IS the in-block value position (0..31)."""
    vals = w.qs4.astype(jnp.float32)
    # (..., 32, nb, d) -> (..., d, nb, 32)
    vals = jnp.moveaxis(jnp.moveaxis(vals, -3, -1), -3, -2)
    scale = jnp.swapaxes(w.scale, -1, -2)
    w_f = vals * scale[..., None]
    return w_f.reshape(*w_f.shape[:-2], w_f.shape[-2] * 32)


def _q40_matmul_i4(w: Q40KernelNbI4, x, interpret, layer):
    """Dispatch for the int4-plane layout (chain-internal, T=1 hot path;
    anything else takes the dequantize-then-dot fallback)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d = w.logical_shape[-2]
    nb = w.scale.shape[-2]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if x2.shape[0] == 1:
        rows = _pick_rows_nb(d, nb)
        if rows:
            if layer is not None:
                out = _q40_matvec_nb_i4_stacked(
                    jnp.asarray(layer, jnp.int32).reshape(1), w.qs4,
                    w.scale, x2, block_rows=rows, interpret=interpret)
            else:
                out = _q40_matvec_nb_i4_2d(
                    w.qs4, w.scale, x2, block_rows=rows,
                    interpret=interpret)
            return out.reshape(*lead, d)
    wf = _dequant_i4(w)
    if layer is not None:
        wf = wf[layer]
    return jnp.einsum("dn,tn->td", wf, x2.astype(jnp.float32),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST) \
        .reshape(*lead, d)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _q40_matvec_nb_i4_2d(qs4, scale, x, *, block_rows, interpret):
    nj2, nb, d = qs4.shape
    xlo, xhi = _split_x(x.astype(jnp.float32), nb)   # (NJ, 1, nb)
    x32 = jnp.transpose(jnp.concatenate([xlo, xhi], axis=0),
                        (0, 2, 1))                   # (32, nb, 1)
    out = pl.pallas_call(
        _kernel_matvec_nb_i4,
        grid=(d // block_rows,),
        in_specs=[
            pl.BlockSpec((nj2, nb, block_rows), lambda i: (0, 0, i)),
            pl.BlockSpec((nb, block_rows), lambda i: (0, i)),
            pl.BlockSpec((nj2, nb, 1), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(qs4, scale, x32)
    return out


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _q40_matvec_nb_i4_stacked(layer, qs4, scale, x, *, block_rows,
                              interpret):
    _, nj2, nb, d = qs4.shape
    xlo, xhi = _split_x(x.astype(jnp.float32), nb)
    x32 = jnp.transpose(jnp.concatenate([xlo, xhi], axis=0), (0, 2, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // block_rows,),
        in_specs=[
            pl.BlockSpec((1, nj2, nb, block_rows),
                         lambda i, L: (L[0], 0, 0, i)),
            pl.BlockSpec((1, nb, block_rows), lambda i, L: (L[0], 0, i)),
            pl.BlockSpec((nj2, nb, 1), lambda i, L: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_rows), lambda i, L: (0, i)),
    )
    return pl.pallas_call(
        _kernel_matvec_nb_i4_stacked, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(layer, qs4, scale, x32)


def q40_matmul(w: Q40Kernel | Q40KernelNb | Q40KernelNbI4 | Q40Weight,
               x: jax.Array, interpret: bool | None = None,
               layer: jax.Array | None = None) -> jax.Array:
    """out[..., d] = dequant(w)(d, n) @ x[..., n], packed weights end to end.

    x may be (n,) or (..., n); leading dims are flattened into T for the
    kernel and restored after. ``w`` should be a pre-tiled leaf on the hot
    path; a Q40Weight is accepted and re-tiled per call (tests only).

    ``layer``: when given, ``w`` holds stacked per-layer weights (qs_t
    (L, 16, d, nb)) and the kernel DMAs layer ``layer`` directly out of the
    stack via scalar prefetch — the zero-copy path for lax.scan over layers.

    The body is picked by the leaf's layout and T, each with a ``_2d`` and
    a ``_stacked`` wrapper; the row tile by the pickers:

    ============== ================== ============== ================
    leaf           T = 1              2..8 rows      more rows
    ============== ================== ============== ================
    Q40KernelNb    _q40_matvec_nb     _q40_mxu_nb(*) _q40_mxu_nb
    Q40KernelNbI4  _q40_matvec_nb_i4  dequant + dot  dequant + dot
    Q40Kernel      _kernel_matvec     _kernel_multi  _kernel
    ============== ================== ============== ================

    (*) where the forward being traced told which of the rows are live
    (``ops/linear.live_rows``: the serving step program), ``_q40_live_nb``
    on 1 or 2 live rows and the tile on more, picked on the device by the
    live count (``_q40_matmul_nbmajor``).

    ``_q40_matvec_nb`` is two bodies under one name, picked by the leaf's
    block count alone (``_t1_mxu``): the MXU matvec (raw codes against the
    row laid block-diagonal, ``_matvec_body_nb_mxu``) where it is a
    multiple of 8, as every model's is, with ``_pick_rows_t1``'s row tile;
    the vector body (``_matvec_body_nb``) else. The other T = 1 bodies
    multiply and add on the vector unit.

    Two exceptions, both dequantize-then-dot in XLA: a ``d`` no tiler
    places, and a chunk (T > 8) traced under bf16 precision
    (``--fast-prefill``), where unpacking the weight ONCE into an HBM temp
    and letting XLA tile a dense dot both ways beat the grids, which
    re-stream one operand t/bt or d/rows times (7B on v5e, tok/s at chunk
    480/960/1920: 3255/4055/4487 against the plain grid's 2408/3565/4249;
    BASELINE.md r3, probe since deleted; runtime of round 5). In float32
    parity the dense dot's HIGHEST passes run on 4x the temp bytes and the
    packed grid stays ahead, so it keeps the chunk."""
    if isinstance(w, Q40KernelNbI4):
        return _q40_matmul_i4(w, x, interpret, layer)
    if isinstance(w, Q40KernelNb):
        return _q40_matmul_nbmajor(w, x, interpret, layer)
    if isinstance(w, Q40Weight):
        w = to_kernel_layout(w)
    qs_t, scale = w.qs_t, w.scale
    d, nb = qs_t.shape[-2], qs_t.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # read the trace-time precision flag HERE (q40_matmul is inlined in the
    # caller's trace) and thread it as a static arg — the inner jits below
    # cache traces and cannot see the contextvar
    from .linear import matmul_mode

    bf16 = matmul_mode() == "bf16"
    lead = x.shape[:-1]
    n = x.shape[-1]
    x2 = x.reshape(-1, n)
    t = x2.shape[0]
    if t > MULTI_T_MAX and bf16:
        return _dequant_matmul(w, x2, layer).reshape(*lead, d)
    if t > MULTI_T_MAX and t % 8 != 0:
        # pad to a multiple of 8 so the MXU path always has an under-cap
        # t-tile divisor (a full-t block of awkward length can exceed the
        # scoped-VMEM plane budget); the pad rows are zeros, sliced off below
        pad = (-t) % 8
        out = q40_matmul(w, jnp.pad(x2, ((0, pad), (0, 0))),
                         interpret=interpret, layer=layer)
        return out[:t].reshape(*lead, d)
    block_t = _pick_block_t(t, nb)
    block_rows = _pick_block_rows(d, t, nb, block_t)
    if block_rows is None:
        # this (d, t) combo has no legal tiling (e.g. TP-shard dims with
        # no multiple-of-128 divisor at MXU T): dequantize-then-dot on
        # the packed weight — correctness everywhere, kernel speed on
        # the shapes that matter
        return _dequant_matmul(w, x2, layer).reshape(*lead, d)
    if layer is not None:
        if qs_t.ndim != 4:
            raise ValueError("layer= requires stacked (L, 16, d, nb) weights")
        lidx = jnp.asarray(layer, dtype=jnp.int32).reshape(1)
        out = _q40_matmul_stacked(lidx, qs_t, scale, x2,
                                  block_rows=block_rows, block_t=block_t,
                                  interpret=interpret, bf16=bf16)
    else:
        out = _q40_matmul_2d(qs_t, scale, x2, block_rows=block_rows,
                             block_t=block_t, interpret=interpret, bf16=bf16)
    return out.reshape(*lead, d)
