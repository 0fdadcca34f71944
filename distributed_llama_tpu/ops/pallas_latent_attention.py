"""Pallas TPU kernel: flash-decode over a paged LATENT cache.

A latent-attention spec (``TransformerSpec.latent``) caches, in a full
layer, a plane ``[c_kv | k_rope]`` of ``kv_rank + rope_dim`` values a
position (``runtime/continuous.sequence_caches`` says what of it a spec with
layer kinds keeps) that all H query heads read, and whose first ``kv_rank`` columns are also the
values (the absorbed schedule: models/latent.py). The paged kernel of
ops/pallas_paged_attention.py reads a K page and a V page of ``n_kv x hs``
and folds them a query head at a time on the VPU; at 128 heads over one key
head that is 128 passes over every page. Here a row's H queries are ONE
(H, width) matrix: a block of pages lands once, the scores are an MXU
product (H, width) x (width, positions), the values another.

What the fold multiplies (``_fold``). The configuration's precision is
float32: six bf16 piece products an operation, which HIGHEST precision
gave until PR 61. The fold forms the SAME six itself: each operand is cut in
three pieces that ARE bf16 numbers (``pallas_q40._mask_pieces``: a float32
is their sum exactly; the queries once a grid program, the landed block once
a turn for both products, the block's weights p once a turn), the small
operand's pieces are stacked along the rows, and each piece of the block is
pushed to the MXU once: [hi; mid; lo] . page_hi, [hi; mid] . page_mid,
hi . page_lo (``pallas_head_major_attention._dot6``), everything HIGHEST's
six passes add (mid lo, lo mid and lo lo, 2^-24 of a product and under, are
what it drops too). The pieces stay FLOAT32 and the dots run at DEFAULT
precision, Mosaic's one bf16 pass, whose rounding of an operand that is a
bf16 number already is exact; every product is exact in the MXU's float32
accumulator and the six slabs are added the small ones first. Max, exp, the
masks and the carry stay on the vector unit in float32. What it bought,
alone on the chip (PERF.md section 7, PR 61): 9 % at 32 heads, nothing at 80
and 128. ISSUE 61 reckoned that HIGHEST loads every 128 x 128 tile of the
block six times and that the loads set the time; the times say its lowering
loads a tile no oftener than this does (the kernel's time follows the rows
STREAMED, products x heads, at the four MXUs' full rate: nine products cost
half as much again as six), and that what is left beside the stream is the
vector unit's work a TURN whatever the turn holds: the values' six slabs of
(heads, kv_rank) popped and added, the carry rescaled. So a turn is 256
positions, two MXU tiles (``BLOCK_POSITIONS``): 12 % off the 128-position
turn at 80 heads 4,900 deep, 24 % at 32 heads 1,000 deep, 6 to 8 % at 128
heads, level at 32 heads 300 deep; 512 gives 18 % at depth and LOSES 15 % at
300 positions, where most of a turn is masked and no copy hides behind a
fold.

grid = (B,): program b walks row b's live pages through its page-table row,
``group`` pages a block (256 positions) so that a dot has two MXU tiles,
double-buffered on ``pallas_attention._flash_walk``. Table entries past a
row's live pages point at the scrap page; their positions are masked.

A SLIDING layer of a latent spec with layer kinds keeps a ring of the last
``window`` latent rows a sequence in place of a plane (models/latent.py). A
ring is one block of this walk: ``latent_ring_decode`` is the same fold
(``_fold``) over the ring as it lies, grid = (B,), the rings' blocks
pipelined from one row to the next, and a slot past ``pos`` unseen until the
ring has wrapped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _VMEM64_PARAMS, NEG_INF, _flash_walk
from .pallas_head_major_attention import _dot6, _stack3
from .pallas_q40 import _mask_pieces

BLOCK_POSITIONS = 256     # positions a block of pages holds: two MXU tiles
KERNEL_NAME = "mla_paged_attn_decode"
RING_KERNEL_NAME = "mla_ring_attn_decode"


def _fold(q3, page, key_pos, last, carry, kv_rank: int):
    """One landed block into the running (m, l, o): q3 (3 H, W) the scaled
    queries' bf16 pieces [hi; mid; lo], page (blk, W) latent rows, key_pos
    (1, blk) what each row is compared by (a plane's: its position; a
    ring's: its slot) against ``last``. Both products are ``_dot6``'s six
    exact piece products against ONE cut of the block in three (the module
    docstring)."""
    m_old, l_old, o_old = carry
    pieces = _mask_pieces(page, 3)
    s = _dot6(q3, pieces, 1)                                # (H, blk)
    s = jnp.where(key_pos <= last, s, NEG_INF)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_new = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
    o_new = o_old * corr + _dot6(
        _stack3(p), [w[:, :kv_rank] for w in pieces], 0)    # (H, kv_rank)
    return m_new, l_new, o_new


def _empty(n_heads: int, kv_rank: int):
    return (jnp.full((n_heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((n_heads, 1), jnp.float32),
            jnp.zeros((n_heads, kv_rank), jnp.float32))


def _kernel(layer_ref, pos_ref, table_ref, q_ref, c_hbm, out_ref, buf, sems,
            *, page_size: int, n_pages: int, group: int, kv_rank: int):
    """q_ref (1, H, W) scaled queries [q_lat | q_rope]; c_hbm (L*P, ps, W);
    out_ref (1, H, kv_rank); buf (2, group * ps, W); sems (2, group)."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    max_pages = table_ref.shape[1]
    blk = group * page_size
    n_blocks = (pos // page_size) // group + 1
    n_heads = q_ref.shape[1]
    q3 = _stack3(q_ref[0])

    def copies(slot, i):
        out = []
        for g in range(group):
            page = table_ref[b, jnp.minimum(i * group + g, max_pages - 1)]
            out.append(pltpu.make_async_copy(
                c_hbm.at[layer_ref[0] * n_pages + page],
                buf.at[slot, pl.ds(g * page_size, page_size)],
                sems.at[slot, g]))
        return out

    def start_dma(slot, i):
        for c in copies(slot, i):
            c.start()

    def wait_dma(slot, i):
        for c in copies(slot, i):
            c.wait()

    def update(i, slot, carry):
        key_pos = i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        return _fold(q3, buf[slot], key_pos, pos, carry, kv_rank)

    _, l_fin, o_fin = _flash_walk(n_blocks, start_dma, wait_dma, update,
                                  _empty(n_heads, kv_rank))
    out_ref[0] = o_fin / l_fin


@functools.partial(jax.jit, static_argnames=("page_size", "n_pages",
                                             "kv_rank", "interpret"))
def latent_paged_decode(q, c3, layer, pos, table, *, page_size: int,
                        n_pages: int, kv_rank: int,
                        interpret: bool | None = None):
    """softmax(q . c) c[:, :kv_rank] of each row's live positions.

    q (B, H, W) float32 queries [q_lat | q_rope] ALREADY scaled; c3
    (L*P, ps, W) the pool's carry view; pos (B,) each row's newest
    position (written before the call); table (B, max_pages) int32.
    Returns (B, H, kv_rank) float32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, n_heads, width = q.shape
    group = max(1, BLOCK_POSITIONS // page_size)
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, n_pages=n_pages,
                          group=group, kv_rank=kv_rank),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n_heads, width), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_heads, kv_rank), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_heads, kv_rank), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, group * page_size, width), c3.dtype),
            pltpu.SemaphoreType.DMA((2, group)),
        ],
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(pos, jnp.int32).reshape(B),
      jnp.asarray(table, jnp.int32), q.astype(jnp.float32), c3)


def _ring_kernel(layer_ref, pos_ref, q_ref, w_ref, out_ref, *, kv_rank: int):
    """q_ref (1, H, W); w_ref (1, window, W) row b's ring of the layer, its
    block chosen through ``layer_ref``; out_ref (1, H, kv_rank). Position p
    lies at slot p mod window, so slots 0 .. min(pos, window - 1) are the
    sequence's own and the softmax takes them in whatever order."""
    del layer_ref
    window = w_ref.shape[1]
    last = jnp.minimum(pos_ref[pl.program_id(0)], window - 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
    _, l_fin, o_fin = _fold(_stack3(q_ref[0]), w_ref[0], slot, last,
                            _empty(q_ref.shape[1], kv_rank), kv_rank)
    out_ref[0] = o_fin / l_fin


@functools.partial(jax.jit, static_argnames=("kv_rank", "interpret"))
def latent_ring_decode(q, w3, layer, pos, *, kv_rank: int,
                       interpret: bool | None = None):
    """softmax(q . w) w[:, :kv_rank] of each row's ring.

    q (B, H, W) float32 scaled queries; w3 (layers * B, window, W) the
    rings' carry view, row b of sliding layer ``layer`` at layer * B + b;
    pos (B,) each row's newest position (written before the call). Returns
    (B, H, kv_rank) float32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, n_heads, width = q.shape
    window = w3.shape[1]
    return pl.pallas_call(
        functools.partial(_ring_kernel, kv_rank=kv_rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[
                pl.BlockSpec((1, n_heads, width),
                             lambda b, layer, pos: (b, 0, 0)),
                pl.BlockSpec((1, window, width),
                             lambda b, layer, pos: (layer[0] * B + b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, n_heads, kv_rank),
                                   lambda b, layer, pos: (b, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, n_heads, kv_rank), jnp.float32),
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
        name=RING_KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(pos, jnp.int32).reshape(B), q.astype(jnp.float32), w3)
