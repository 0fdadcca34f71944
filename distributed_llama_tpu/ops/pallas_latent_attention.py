"""Pallas TPU kernel: flash-decode over a paged LATENT cache.

A latent-attention spec (``TransformerSpec.latent``) caches, in a full
layer, a plane ``[c_kv | k_rope]`` of ``kv_rank + rope_dim`` values a
position (``runtime/continuous.sequence_caches`` says what of it a spec with
layer kinds keeps) that all H query heads read, and whose first ``kv_rank`` columns are also the
values (the absorbed schedule: models/latent.py). The paged kernel of
ops/pallas_paged_attention.py reads a K page and a V page of ``n_kv x hs``
and folds them a query head at a time on the VPU; at 128 heads over one key
head that is 128 passes over every page. Here a row's H queries are ONE
(H, width) matrix: a block of pages lands once, the scores are one MXU dot
(H, width) x (width, positions), the values another, float32 at HIGHEST.

grid = (B,): program b walks row b's live pages through its page-table row,
``group`` pages a block (128 positions) so that a dot has an MXU's rows,
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

BLOCK_POSITIONS = 128     # positions a block of pages holds
KERNEL_NAME = "mla_paged_attn_decode"
RING_KERNEL_NAME = "mla_ring_attn_decode"


def _fold(q, page, key_pos, last, carry, kv_rank: int):
    """One landed block into the running (m, l, o): q (H, W) scaled, page
    (blk, W) latent rows, key_pos (1, blk) what each row is compared by
    (a plane's: its position; a ring's: its slot) against ``last``."""
    m_old, l_old, o_old = carry
    s = jax.lax.dot_general(
        q, page, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)                # (H, blk)
    s = jnp.where(key_pos <= last, s, NEG_INF)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_new = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
    o_new = o_old * corr + jax.lax.dot_general(
        p, page[:, :kv_rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)                # (H, kv_rank)
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
    q = q_ref[0]
    n_heads = q.shape[0]

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
        return _fold(q, buf[slot], key_pos, pos, carry, kv_rank)

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
    _, l_fin, o_fin = _fold(q_ref[0], w_ref[0], slot, last,
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
