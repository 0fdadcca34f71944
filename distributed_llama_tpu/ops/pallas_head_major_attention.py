"""Flash-decode attention over HEAD-MAJOR caches: one query position a row
over a contiguous plane (rows, n_kv, S, hs) or over pages (P, n_kv,
page_size, hs), grouped-query, float32.

Why another layout than ``pallas_attention.py`` / ``pallas_paged_attention
.py`` ((rows, S, n_kv, hs) and (P, page_size, n_kv, hs)): a hybrid spec's
attention has 10 KV heads (pairs) of 128 (models/sambay.py). With the head
count second-minor the chip tiles (10, 128) as (16, 128): the cache takes
1.6 x its bytes in HBM and in every read, and XLA, which prefers the other
order, copied the whole pool and every ring around each kernel call (the
described-chip compile of the 32-row step showed 8.2 GiB of temporaries,
PR 37). Head-major, the two minor dims are (positions, 128): whole tiles
whatever the head count, the default layout is the one the kernels take, a
page is one contiguous block, and the scores of a chunk lie (n_kv, chunk)
with the positions along lanes.

Both kernels walk a row's live chunks (or pages) with
``pallas_attention._flash_walk``'s double-buffered DMA loop and keep the
running (m, l, o) of each query head of a group; scores are scaled by
1 / sqrt(hs). Every position up to a row's ``last`` is attended.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _VMEM_BUDGET, NEG_INF, _flash_walk
from .pallas_q40 import _VMEM64_PARAMS

ROWS_KERNEL = "hm_attn_rows_decode"
PAGED_KERNEL = "hm_attn_paged_decode"


def _fold(q, k, v, valid, carry, kv_mul: int):
    """One landed chunk into the carry. q (n_kv, kv_mul, hs); k, v (n_kv,
    C, hs); valid (n_kv, C); carry: per query head of a group (m (n_kv, 1),
    l (n_kv, 1), o (n_kv, hs))."""
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    out = []
    for i in range(kv_mul):
        m_old, l_old, o_old = carry[i]
        s = jnp.sum(k * q[:, i, :][:, None, :], axis=-1) * scale
        s = jnp.where(valid, s, NEG_INF)                    # (n_kv, C)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_old - m_new)
        l_new = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
        o_new = o_old * corr + jnp.sum(p[:, :, None] * v, axis=1)
        out.append((m_new, l_new, o_new))
    return tuple(out)


def _init(n_kv: int, hs: int, kv_mul: int):
    return tuple((jnp.full((n_kv, 1), NEG_INF, jnp.float32),
                  jnp.zeros((n_kv, 1), jnp.float32),
                  jnp.zeros((n_kv, hs), jnp.float32))
                 for _ in range(kv_mul))


def _write_out(final, out_ref, kv_mul: int):
    for i in range(kv_mul):
        _, l_i, o_i = final[i]
        out_ref[0, :, i, :] = o_i / l_i


def _pair(k_src, v_src, k_buf, v_buf, sems, slot):
    return (pltpu.make_async_copy(k_src, k_buf.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(v_src, v_buf.at[slot], sems.at[slot, 1]))


def _rows_kernel(layer_ref, last_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf,
                 v_buf, sems, *, chunk: int, kv_mul: int, batch: int):
    """grid=(B,): program b walks the live chunks of plane layer * B + b.
    q_ref / out_ref (1, n_kv, kv_mul, hs); k / v_hbm (rows, n_kv, S, hs);
    k / v_buf (2, n_kv, chunk, hs)."""
    b = pl.program_id(0)
    row, last = layer_ref[0] * batch + b, last_ref[b]
    q = q_ref[0]
    n_kv, _, hs = q.shape

    def copies(slot, i):
        at = pl.ds(i * chunk, chunk)
        return _pair(k_hbm.at[row, :, at], v_hbm.at[row, :, at], k_buf,
                     v_buf, sems, slot)

    def update(i, slot, carry):
        pos = i * chunk + jax.lax.broadcasted_iota(jnp.int32,
                                                   (n_kv, chunk), 1)
        return _fold(q, k_buf[slot].astype(jnp.float32),
                     v_buf[slot].astype(jnp.float32), pos <= last, carry,
                     kv_mul)

    final = _flash_walk(
        last // chunk + 1, lambda s, i: [c.start() for c in copies(s, i)],
        lambda s, i: [c.wait() for c in copies(s, i)], update,
        _init(n_kv, hs, kv_mul))
    _write_out(final, out_ref, kv_mul)


def _chunk(seq_len: int, n_kv: int, hs: int, itemsize: int) -> int | None:
    """The largest chunk of positions that divides ``seq_len`` with both
    slots of K and V inside the scratch budget."""
    for c in (512, 256, 128, 64, 32, 16, 8):
        if seq_len % c == 0 and 4 * c * n_kv * hs * itemsize <= _VMEM_BUDGET:
            return c
    return None


def supports(seq_len: int, n_kv: int, head_size: int,
             itemsize: int = 4) -> bool:
    return head_size % 128 == 0 and _chunk(seq_len, n_kv, head_size,
                                           itemsize) is not None


@functools.partial(jax.jit, static_argnames=("kv_mul", "interpret"))
def rows_decode_attention(q, k4, v4, layer, last, *, kv_mul: int,
                          interpret: bool | None = None):
    """q (B, n_kv * kv_mul, hs) over planes layer * B + b of k4 / v4 (rows,
    n_kv, S, hs), positions 0 .. last[b]. Returns (B, n_q * hs)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, n_kv, S, hs = k4.shape
    B = q.shape[0]
    chunk = _chunk(S, n_kv, hs, k4.dtype.itemsize)
    if chunk is None:
        raise ValueError(f"no chunking of S={S} fits VMEM at n_kv={n_kv}, "
                         f"hs={hs} (gate with supports())")
    block = pl.BlockSpec((1, n_kv, kv_mul, hs), lambda b: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, chunk=chunk, kv_mul=kv_mul, batch=B),
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM), block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, kv_mul, hs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, n_kv, chunk, hs), k4.dtype),
                        pltpu.VMEM((2, n_kv, chunk, hs), k4.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
        name=ROWS_KERNEL,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.broadcast_to(jnp.asarray(last, jnp.int32), (B,)),
      q.reshape(B, n_kv, kv_mul, hs).astype(jnp.float32), k4, v4)
    return out.reshape(B, -1)


def _paged_kernel(pos_ref, table_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf,
                  v_buf, sems, *, page_size: int, kv_mul: int):
    """grid=(B,): program b walks its live pages through the table. k /
    v_hbm (P, n_kv, page_size, hs); k / v_buf (2, n_kv, page_size, hs)."""
    b = pl.program_id(0)
    last = jnp.minimum(pos_ref[b], table_ref.shape[1] * page_size - 1)
    q = q_ref[0]
    n_kv, _, hs = q.shape

    def copies(slot, i):
        page = table_ref[b, i]
        return _pair(k_hbm.at[page], v_hbm.at[page], k_buf, v_buf, sems,
                     slot)

    def update(i, slot, carry):
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, page_size), 1)
        return _fold(q, k_buf[slot].astype(jnp.float32),
                     v_buf[slot].astype(jnp.float32), pos <= last, carry,
                     kv_mul)

    final = _flash_walk(
        last // page_size + 1,
        lambda s, i: [c.start() for c in copies(s, i)],
        lambda s, i: [c.wait() for c in copies(s, i)], update,
        _init(n_kv, hs, kv_mul))
    _write_out(final, out_ref, kv_mul)


def supports_paged(page_size: int, n_kv: int, head_size: int,
                   itemsize: int = 4) -> bool:
    return (head_size % 128 == 0 and page_size % 8 == 0
            and 4 * page_size * n_kv * head_size * itemsize <= _VMEM_BUDGET)


@functools.partial(jax.jit, static_argnames=("kv_mul", "interpret"))
def paged_decode_attention(q, k4, v4, pos, table, *, kv_mul: int,
                           interpret: bool | None = None):
    """q (B, n_kv * kv_mul, hs) over the pages ``table`` (B, max_pages) maps
    of the pool k4 / v4 (P, n_kv, page_size, hs), positions 0 .. pos[b].
    Returns (B, n_q * hs)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, n_kv, ps, hs = k4.shape
    B = q.shape[0]
    block = pl.BlockSpec((1, n_kv, kv_mul, hs), lambda b: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=ps, kv_mul=kv_mul),
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM), block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, kv_mul, hs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, n_kv, ps, hs), k4.dtype),
                        pltpu.VMEM((2, n_kv, ps, hs), k4.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
        name=PAGED_KERNEL,
    )(jnp.asarray(pos, jnp.int32).reshape(B), jnp.asarray(table, jnp.int32),
      q.reshape(B, n_kv, kv_mul, hs).astype(jnp.float32), k4, v4)
    return out.reshape(B, -1)
