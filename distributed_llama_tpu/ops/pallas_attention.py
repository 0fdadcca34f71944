"""Pallas TPU kernel: single-token flash-decode attention over the stacked
KV cache.

The XLA attention path reads the ENTIRE static (seq_len, n_kv, hs) cache
plane every token (static shapes force it), so decode attention costs
seq_len-proportional HBM traffic even at pos=3. This kernel is the
TPU-native replacement for the hot T=1 case: it DMAs only the ceil((pos+1)/C)
LIVE chunks of K/V out of the stacked (L, S, n_kv, hs) HBM cache (layer and
pos arrive as scalars; a lax.fori_loop with a data-dependent trip count walks
the chunks, double-buffered), accumulating flash-style running (m, l, o)
per head in VMEM. Attention cost becomes pos-proportional — the shape of the
reference's own per-position attention loop (transformer-tasks.cpp:246-276),
which scans exactly 0..pos, not 0..seqLen.

Numerics: f32 throughout, max-subtracted softmax, every position 0..pos
attended and a masked position's weight exactly 0 — same math as
models/llama.attention_core (the parity anchor; the interpret-mode test
checks element-level agreement, and the distance from a float64 attention is
pinned beside the vector-unit fold's this kernel had until PR 59).

A landed chunk (C, n_kv, hs) is folded by the head-major kernels' ``_fold``
(ops/pallas_head_major_attention.py, whose docstring has the argument): both
contractions on the MXU, K and V read once for all ``kv_mul`` query heads of
a group (padded to a sublane tile), every product exact (each operand cut in
its three bf16 pieces, the nine piece products summed in float32, the small
ones first). ``_heads`` is the bridge from the cache's layout, heads
second-minor, to the fold's (n_kv, C, hs): one strided read a KV head where
the heads are whole sublane tiles, one relayout of the loaded slot otherwise
(a tp rank's 2 or 10 heads). A bf16 cache's chunk is widened into one
float32 slot a side first. Until PR 59 the fold multiplied and lane-reduced
the chunk on the vector unit a query head at a time and ran 8 (Mistral) to
35 (a Yi-34B tp-4 rank) times over its bytes at the decode cells' depths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_head_major_attention import (_TILE, _VMEM_BUDGET,  # noqa: F401
                                          NEG_INF, _flash_walk, _fold, _heads,
                                          _stacked_queries)
# The raised scoped-VMEM limit (v5e has 128 MB physical): with the DEFAULT
# 16 MB limit, shapes whose scratch sits near the 12 MB budget can exceed the
# limit once the compiler's own temporaries stack on top — measured: 13B tp=4
# rank (n_kv=10, hs=128, f32 cache, chunk 512) needed 16.07 MB and fell back
# to the XLA attention path, costing ~4 ms/token rank time. ONE shared
# constant with the matmul kernels: a missed copy reintroduces exactly this
# silent-fallback class of bug.
from .pallas_q40 import _VMEM64_PARAMS


def _kernel(layer_ref, pos_ref, q3_ref, k_hbm, v_hbm, out_ref,
            k_buf, v_buf, sems, *wide, chunk: int, batch: int):
    """Per-row flash decode over the rank-4 (L*B, S, n_kv, hs) cache (the
    single sequence's stacked (L, S, n_kv, hs) cache is B = 1).

    grid=(B,): program b walks the live chunks of row layer*batch+b
    (prefix-indexed DMAs, double-buffered, one copy a side a turn) at ITS
    position pos_ref[b] (identical values in the lockstep case; ragged for
    continuous batching); each landed slot (chunk, n_kv, hs) is read
    head-major (``_heads``) into the head-major kernels' exact MXU ``_fold``.
    q3_ref (1, n_kv, 3 R, hs): a KV head's query heads, padded to R rows, in
    stacked pieces; out_ref (1, n_kv, kv_mul, hs); k/v_buf (2, chunk, n_kv,
    hs) VMEM scratch; sems (2, 2) DMA semaphores (slot x {k, v}); ``wide``:
    nothing for a float32 cache, else the two float32 slots (chunk, n_kv, hs)
    a landed bf16 slot is widened into."""
    b = pl.program_id(0)
    row, pos = layer_ref[0] * batch + b, pos_ref[b]
    q3 = q3_ref[0]
    n_kv, rows3, hs = q3.shape
    rows = rows3 // 3

    def copies(slot, i):
        at = pl.ds(i * chunk, chunk)
        return (pltpu.make_async_copy(k_hbm.at[row, at], k_buf.at[slot],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[row, at], v_buf.at[slot],
                                      sems.at[slot, 1]))

    def landed(slot):
        if not wide:
            return (_heads(w.at[slot], n_kv) for w in (k_buf, v_buf))
        for w, buf in zip(wide, (k_buf, v_buf)):
            w[...] = buf[slot].astype(jnp.float32)
        return (_heads(w, n_kv) for w in wide)

    key = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
    init = (jnp.full((n_kv, rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((n_kv, rows, 1), jnp.float32),
            jnp.zeros((n_kv, rows, hs), jnp.float32))
    _, l_fin, o_fin = _flash_walk(
        pos // chunk + 1,                              # live chunks only
        lambda slot, i: [c.start() for c in copies(slot, i)],
        lambda slot, i: [c.wait() for c in copies(slot, i)],
        lambda i, slot, carry: _fold(q3, *landed(slot),
                                     i * chunk + key <= pos, carry),
        init)
    out_ref[0] = (o_fin / l_fin)[:, :out_ref.shape[2]]


def _call(q, k4, v4, layer, pos, kv_mul: int, interpret):
    """The ``pallas_call`` both decode entries share: q (B, n_q, hs) over
    rows of the (R, S, n_kv, hs) caches, grid=(B,); layer and clocks in
    SMEM, a row's stacked queries and its output a block, the caches left in
    HBM. Returns (B, n_q * hs) float32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, S, n_kv, hs = k4.shape
    B = q.shape[0]
    chunk = _chunk(S, n_kv, hs, k4.dtype.itemsize)
    if chunk is None:
        raise ValueError(
            f"no cache chunking fits VMEM for seq_len={S}, n_kv={n_kv}, "
            f"hs={hs} (gate with supports())")
    q3 = _stacked_queries(q, n_kv, kv_mul, hs)
    # scratch matches the cache dtype (bf16 caches halve the DMA); a landed
    # bf16 slot is widened into one float32 slot a side for the fold
    slot = pltpu.VMEM((2, chunk, n_kv, hs), k4.dtype)
    wide = [] if k4.dtype == jnp.float32 else [
        pltpu.VMEM((chunk, n_kv, hs), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, batch=B),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, *q3.shape[1:]), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_kv, kv_mul, hs), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, kv_mul, hs), jnp.float32),
        scratch_shapes=[slot, slot, pltpu.SemaphoreType.DMA((2, 2)), *wide],
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), pos, q3, k4, v4)
    return out.reshape(B, n_kv * kv_mul * hs)


@functools.partial(jax.jit, static_argnames=("kv_mul", "interpret"))
def decode_attention_batch(q, k4, v4, layer, pos, *, kv_mul: int,
                           interpret: bool | None = None):
    """Batched flash-decode attention over the rank-4 (L*B, S, n_kv, hs)
    cache carried by models/llama.forward_batch.

    q: (B, n_q, hs) f32; pos: scalar (shared clock, lockstep batch) or (B,)
    (per-row clocks, continuous batching). Returns (B, n_q * hs) f32.
    Live-chunk walking per row, like decode_attention.
    """
    B = q.shape[0]
    return _call(q, k4, v4, layer,
                 jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)), kv_mul,
                 interpret)


def maybe_flash_decode(q2, k_all, v_all, idx, pos, *, seq_len: int,
                       head_size: int, t_len: int, n_kv: int, kv_mul: int,
                       batch: bool = False):
    """The ONE gate for routing decode attention to the flash kernel.

    Returns the attention output, or None when the caller must take its XLA
    fallback (kernel disabled or shape unsupported). All three decode paths
    (single-chip, TP shard-local, batched) call this so the mode/shape
    gating can never drift between them.

    q2 arrives in the caller's natural shape — (T, n_q*hs) or (T, n_q, hs)
    for the single/TP paths, (B, n_q*hs)/(B, n_q, hs) with ``batch=True``
    (rank-4 (L*B, S, n_kv, hs) caches) — and is reshaped here, so call
    sites carry no per-site shape logic.
    """
    if (attn_kernel_mode() != "pallas"
            or not supports(seq_len, head_size, t_len, n_kv,
                            k_all.dtype.itemsize)):
        return None
    if batch:
        q2 = q2.reshape(q2.shape[0], -1, head_size)
        return decode_attention_batch(q2, k_all, v_all, idx, pos,
                                      kv_mul=kv_mul)
    return decode_attention(q2.reshape(-1, head_size), k_all, v_all, idx,
                            pos, kv_mul=kv_mul)


def attn_kernel_mode() -> str:
    """'pallas' (flash-decode kernel) or 'xla' (full-cache einsum).

    DLLAMA_ATTN_KERNEL=pallas|xla|auto; auto = pallas on TPU, xla elsewhere.
    """
    import os

    env = os.environ.get("DLLAMA_ATTN_KERNEL", "auto")
    if env == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return env


def _scratch_bytes(chunk: int, n_kv: int, hs: int, itemsize: int) -> int:
    """2 slots x {K, V} x (chunk, n_kv, hs) in the cache dtype, plus, under
    a cache that is not float32, the float32 slot a side a landed chunk is
    widened into."""
    wide = 2 * chunk * n_kv * hs * 4 if itemsize != 4 else 0
    return 2 * 2 * chunk * n_kv * hs * itemsize + wide


_TURN_BYTES = 512 * 1024  # a side's slot up to which a turn of 256 pays


def _chunk(seq_len: int, n_kv: int, hs: int, itemsize: int = 4) -> int | None:
    """Positions a turn lands: the fold's tile of 128, or 256 where a side's
    slot of 256 stays within ``_TURN_BYTES`` (4 KV heads of 128 in float32,
    8 in bf16), cut to what divides ``seq_len`` and fits the VMEM budget;
    None where nothing does.

    From the call's shapes alone: no flag, no argument. Alone on the chip
    (us a call at 130 / 255 / 1,024 / 4,000 positions of 4,096; PERF.md
    section 7) a turn of few heads is its fixed cost, so fewer and longer
    ones win at depth and lose nothing where the plane is shallow (a tp
    rank's 2 KV heads: 5.3 / 5.6 / 9.1 / 24.8 in turns of 128, 5.6 / 5.5 /
    8.0 / 19.7 in 256; 4 heads: 6.6 / 6.1 / 11.4 / 32.4 and 6.5 / 6.6 / 11.1
    / 28.5), while a turn of 8 heads is its copy (7.9 / 7.9 / 16.4 / 49.8 and
    8.8 / 8.0 / 16.8 / 50.8, the copies alone 7.2 to 9.0 / 49.1 to 53.1).
    Turns of 512, the chunk until PR 59, land two to four times the live
    bytes at the decode cells' 129 to 256 positions with nothing to hide the
    copy behind (8 heads 13.0 / 12.9, 2 heads 7.4 / 7.1, one head 10.4 / 10.3
    for 7.2 / 6.8) and are ahead of 256 at 4,000 positions of 2 heads alone
    (17.5 for 19.7)."""
    if itemsize == 2 and n_kv % 8 and n_kv not in (2, 4):
        # the chip tiles a bf16 cache 16 heads (or 2, or 4) a tile and
        # refuses a copy of any other head count (tests/test_chip_compile.py)
        return None
    for c in (256, 128, 64, 32, 16, 8):
        if (seq_len % c == 0
                and (c <= _TILE or c * n_kv * hs * itemsize <= _TURN_BYTES)
                and _scratch_bytes(c, n_kv, hs, itemsize) <= _VMEM_BUDGET):
            return c
    if (seq_len <= 8
            and _scratch_bytes(seq_len, n_kv, hs, itemsize) <= _VMEM_BUDGET):
        return seq_len
    return None


def supports(seq_len: int, head_size: int, t_len: int,
             n_kv: int = 32, itemsize: int = 2) -> bool:
    """The kernel handles T=1 decode with lane-width head_size and a cache
    the chunking divides within the VMEM scratch budget; callers fall back
    to the XLA path otherwise. ``itemsize`` defaults to the smaller (bf16)
    cache: if the bf16 chunking fits, so does some f32 chunking and vice
    versa for these shapes — decode_attention re-derives the real chunk."""
    return (t_len == 1 and head_size % 128 == 0
            and _chunk(seq_len, n_kv, head_size, itemsize) is not None)


@functools.partial(jax.jit, static_argnames=("kv_mul", "interpret"))
def decode_attention(q, k_all, v_all, layer, pos, *, kv_mul: int,
                     interpret: bool | None = None):
    """Flash-decode attention of one query token against the live prefix of
    layer ``layer``'s cache.

    q: (n_q, hs) f32 (n_q = n_kv * kv_mul, grouped so query head
    g*kv_mul+m attends kv head g — the attention_core contract);
    k_all/v_all: (L, S, n_kv, hs) stacked caches; pos: the query's absolute
    position (keys 0..pos are visible). Returns (1, n_q * hs) f32.

    ``interpret=None`` auto-selects interpret mode off-TPU (like q40_matmul),
    so DLLAMA_ATTN_KERNEL=pallas works everywhere.
    """
    return _call(q[None], k_all, v_all, layer,
                 jnp.asarray(pos, jnp.int32).reshape(1), kv_mul, interpret)


# --------------------------------------------------------------------------
# Prefill flash attention (T > 8), VERDICT r4 #5.
#
# The blockwise live-prefix prefill path (models/llama._attention_blockwise)
# builds its flash partials from XLA einsums: every KV block materializes a
# (T, n_q, block) score plane plus separate m/l/o merge traffic through HBM,
# and the surrounding reshapes/transposes land in the profiler's layout
# bucket (~38% of chunk-1920 op time is attention + glue + layout; probe
# since deleted; runtime of round 5). This kernel runs the whole
# online-softmax walk in VMEM: grid over (kv head, q block), and per invocation an in-kernel
# double-buffered DMA loop (the decode kernel's machinery) walks ONLY the
# live KV blocks. Scores never touch HBM; the causal
# bound clamps the walk exactly like blockwise_chunk_partials' n_live.
#
# Layout: Mosaic blocks the LAST TWO dims of an operand, so q/out are
# carried group-major — the wrapper transposes (T, n_q, hs) to
# (n_kv, T, kv_mul*hs) on the way in and back on the way out (two real
# layout passes XLA usually fuses into neighbors; they replace the
# per-KV-block score/merge reshapes of the einsum path). The q block is
# as tall as VMEM allows (default: the whole chunk), so each kv head's
# cache plane streams from HBM once per chunk.
#
# Numerics: same contract as ring._partial_attention — bf16 MXU passes with
# f32 accumulation under fast-prefill, HIGHEST-precision f32 dots in parity
# mode; softmax stats and merges always f32. Reassociation-only deltas vs
# the dense path (the documented prefill tolerance).
# --------------------------------------------------------------------------

def _prefill_kernel(pos_ref, q_ref, k_hbm, v_hbm, out_ref, k_buf, v_buf,
                    sems, *, bq: int, bk: int, kv_mul: int, hs: int,
                    bf16: bool, heads: int):
    """One (kv-head group g, q block qb) tile: flash walk over live KV
    blocks for ``heads`` kv heads.

    q_ref/out_ref: (heads, bq, kv_mul*hs) VMEM blocks of the group-major
    (n_kv, T, kv_mul*hs) planes (the last two dims must be the blocked
    ones — Mosaic's (8, 128)-divisibility rule); k_hbm/v_hbm:
    (S, n_kv, hs) in HBM; sems: (2, 2) DMA semaphores (slot x {k, v}).

    f32 cache: heads == 1, the DMA slices one head and k/v_buf are
    (2, bk, hs). A bf16 cache is tiled (8,128)(2,1) in HBM — heads 2r and
    2r+1 share one 32-bit sublane — and the chip's compiler refuses a DMA
    that slices fewer than 8 heads ("Slice shape along dimension 1 must be
    aligned to tiling (8), but is 1"). So the bf16 walk takes a whole tile:
    heads == 8, DMA'd as the 4 uint32 pair-rows of the bitcast
    (S, n_kv/2, hs) view into (2, bk, 4, hs) uint32 buffers; each head
    widens to f32 in VMEM (low half = even head, high half = odd; bf16 ->
    f32 is a 16-bit shift, exact).
    """
    g = pl.program_id(0)
    qb = pl.program_id(1)
    pos = pos_ref[0]
    S = k_hbm.shape[0]
    wdt = jnp.bfloat16 if bf16 else jnp.float32
    prec = None if bf16 else jax.lax.Precision.HIGHEST
    dn = (((1,), (1,)), ((), ()))      # contract hs x hs
    dn_pv = (((1,), (0,)), ((), ()))   # (bq, bk) @ (bk, hs)
    scale = 1.0 / jnp.sqrt(jnp.float32(hs))

    if heads == 1:
        def src(hbm, i):
            return hbm.at[pl.ds(i * bk, bk), g]

        def landed(buf, slot):
            return [buf[slot]]                          # (bk, hs)
    else:
        rows = heads // 2
        k_hbm, v_hbm = k_hbm.bitcast(jnp.uint32), v_hbm.bitcast(jnp.uint32)

        def src(hbm, i):
            return hbm.at[pl.ds(i * bk, bk), pl.ds(g * rows, rows)]

        def landed(buf, slot):
            out = []
            for r in range(rows):
                pair = buf[slot, :, r, :]               # (bk, hs) uint32
                out.append(jax.lax.bitcast_convert_type(
                    pair << 16, jnp.float32))
                out.append(jax.lax.bitcast_convert_type(
                    pair & jnp.uint32(0xFFFF0000), jnp.float32))
            return out

    # causal bound: the deepest query row of this block sees keys
    # 0 .. pos + qb*bq + bq - 1 (the chunk's keys are already in the cache)
    n_blk = jnp.clip((pos + qb * bq + bq + bk - 1) // bk, 1, S // bk)
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    q_pos_rows = pos + qb * bq + rows_iota             # (bq, 1)

    def k_dma(slot, i):
        return pltpu.make_async_copy(src(k_hbm, i), k_buf.at[slot],
                                     sems.at[slot, 0])

    def v_dma(slot, i):
        return pltpu.make_async_copy(src(v_hbm, i), v_buf.at[slot],
                                     sems.at[slot, 1])

    k_dma(0, 0).start()
    v_dma(0, 0).start()

    def body(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            nxt = jax.lax.rem(i + 1, 2)
            k_dma(nxt, i + 1).start()
            v_dma(nxt, i + 1).start()

        k_dma(slot, i).wait()
        v_dma(slot, i).wait()
        ks, vs = landed(k_buf, slot), landed(v_buf, slot)
        key_pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = key_pos <= q_pos_rows                  # (bq, bk)

        out = []
        for h in range(heads):
            k = ks[h].astype(wdt)                      # (bk, hs)
            v = vs[h].astype(wdt)
            for j in range(kv_mul):
                m_old, l_old, o_old = carry[h * kv_mul + j]
                qj = q_ref[h, :, j * hs:(j + 1) * hs].astype(wdt)  # (bq, hs)
                s = jax.lax.dot_general(qj, k, dn,
                                        preferred_element_type=jnp.float32,
                                        precision=prec) * scale
                s = jnp.where(valid, s, NEG_INF)
                # block 0 holds key 0, visible to every query row, so m is
                # finite from the first walked block on (no -inf guard)
                m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)                 # (bq, bk)
                corr = jnp.exp(m_old - m_new)          # (bq, 1)
                l_new = l_old * corr + jnp.sum(p, axis=1, keepdims=True)
                po = jax.lax.dot_general(p.astype(wdt), v, dn_pv,
                                         preferred_element_type=jnp.float32,
                                         precision=prec)
                out.append((m_new, l_new, o_old * corr + po))
        return tuple(out)

    init = tuple((jnp.full((bq, 1), NEG_INF, jnp.float32),
                  jnp.zeros((bq, 1), jnp.float32),
                  jnp.zeros((bq, hs), jnp.float32))
                 for _ in range(heads * kv_mul))
    final = jax.lax.fori_loop(0, n_blk, body, init)
    for h in range(heads):
        for j in range(kv_mul):
            _, l_j, o_j = final[h * kv_mul + j]
            out_ref[h, :, j * hs:(j + 1) * hs] = o_j / l_j


# q-block rows: bounded so (bq, bk) score temporaries + q/out blocks stay
# comfortably inside the 64 MB scoped-VMEM limit at 8 accumulators per tile
_PREFILL_BQ_CAP = 1920


def _prefill_heads(n_kv: int, itemsize: int) -> int | None:
    """kv heads one kernel program walks: 1 for a 32-bit cache, a whole
    8-head HBM tile for bf16 (see _prefill_kernel); None = unsupported."""
    if itemsize == 4:
        return 1
    return 8 if itemsize == 2 and n_kv % 8 == 0 else None


def _pick_prefill_bq(t_len: int, n_acc: int) -> int | None:
    """``n_acc``: (m, l, o) accumulators live per program = heads*kv_mul."""
    cap = min(_PREFILL_BQ_CAP, max(128, 245_760 // (n_acc * 16)))
    for cand in range(min(t_len, cap), 7, -1):
        if t_len % cand == 0 and cand % 8 == 0:
            return cand
    return None


def _pick_prefill_bk(seq_len: int) -> int | None:
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if seq_len % cand == 0:
            return cand
    return None


def supports_prefill(seq_len: int, head_size: int, t_len: int,
                     kv_mul: int, n_kv: int = 8, itemsize: int = 4) -> bool:
    heads = _prefill_heads(n_kv, itemsize)
    return (t_len > 8 and head_size % 128 == 0 and heads is not None
            and _pick_prefill_bq(t_len, heads * kv_mul) is not None
            and _pick_prefill_bk(seq_len) is not None)


@functools.partial(jax.jit, static_argnames=("kv_mul", "bf16", "interpret"))
def prefill_attention(q, k_cache, v_cache, pos, *, kv_mul: int,
                      bf16: bool = False, interpret: bool | None = None):
    """Flash prefill attention of T queries at positions pos..pos+T-1
    against one layer's cache (keys 0..pos+T-1 live; the chunk's own keys
    are already written).

    q: (T, n_q, hs) f32; k/v_cache: (S, n_kv, hs) (f32 or bf16).
    Returns (T, n_q, hs) f32. Gate with supports_prefill().
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t_len, n_q, hs = q.shape
    S, n_kv, _ = k_cache.shape
    assert n_q == n_kv * kv_mul, (n_q, n_kv, kv_mul)
    heads = _prefill_heads(n_kv, k_cache.dtype.itemsize)
    bq = _pick_prefill_bq(t_len, heads * kv_mul)
    bk = _pick_prefill_bk(S)
    buf = (pltpu.VMEM((2, bk, hs), k_cache.dtype) if heads == 1
           else pltpu.VMEM((2, bk, heads // 2, hs), jnp.uint32))
    # group-major carry: Mosaic blocks the LAST TWO dims, so the kv-head
    # axis must lead — (T, n_kv*kv_mul, hs) -> (n_kv, T, kv_mul*hs)
    qg = jnp.transpose(q.astype(jnp.float32)
                       .reshape(t_len, n_kv, kv_mul * hs), (1, 0, 2))
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bk=bk, kv_mul=kv_mul,
                          hs=hs, bf16=bf16, heads=heads),
        grid=(n_kv // heads, t_len // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((heads, bq, kv_mul * hs), lambda g, qb: (g, qb, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((heads, bq, kv_mul * hs),
                               lambda g, qb: (g, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((n_kv, t_len, kv_mul * hs),
                                       jnp.float32),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qg, k_cache, v_cache)
    return jnp.transpose(out, (1, 0, 2)).reshape(t_len, n_q, hs)
