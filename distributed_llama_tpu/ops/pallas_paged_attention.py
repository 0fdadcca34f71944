"""Pallas TPU kernel: flash-decode attention over the PAGED page-pool KV
cache (ISSUE 11 — the vLLM/PagedAttention move, Kwon et al. SOSP'23).

PR 6 made the paged pool the production layout but left it on the slowest
attention path: ``models/llama.paged_decode_attention`` falls back to an
XLA gather that materializes the row's whole virtual (B, S, n_kv, hs)
plane in HBM every token (``jnp.take`` over the pool), because the
contiguous flash kernel (ops/pallas_attention.py) assumes one contiguous
cache row. This kernel walks the page table DIRECTLY: block = page is the
natural tiling, and the DMA loop indexes each K/V page plane through the
per-row int32 table — page i+1 prefetches while page i reduces, riding
the SAME double-buffered machinery as the contiguous kernel
(``pallas_attention._flash_walk``) with flash-decoding-style (Dao et al.)
split-KV (m, l, o) accumulation. HBM traffic becomes pos-proportional
again (live pages only) and the gather copy disappears.

Shapes: ONE kernel covers both hot paged shapes — single-token decode
(t_len=1, the forward_batch_paged step) and the (B, K) speculative-verify
window (t_len=K, forward_batch_spec_paged; query i of a row sees virtual
positions 0..pos+i, the stacked causal windows of sequential decode).

KV dtypes: f32/bf16 pages DMA raw planes; Q8 pages
(``DLLAMA_KV_QUANT=q8``) DMA the int8 code planes PLUS the per-position
f16 Q80 block-delta planes and dequantize inside the page loop
(``_dequant_q80_page``) — bit for bit the ``codes.astype(f32) *
delta.astype(f32)`` value map of the XLA fallback's gather-side dequant
(ops/quants.dequantize_q80_planes), so both routes see identical f32 K/V
values. The chip shapes how: it takes no f16 kernel argument and has no
f16 vectors, so the delta planes travel as their raw int16 bits and are
widened by hand, and its layout pass refuses the (ps, nb, QK) reshape of
the codes, so the deltas are spread to the codes' shape instead.

Parity contract (tests/test_pallas_paged_attention.py): the kernel is
INVARIANT to physical page placement — any permutation of the pool that
updates the table produces bitwise-identical output — and element-level
equal to the XLA gather path at the documented flash tolerance (the
split-KV accumulation reassociates the softmax sums across page
boundaries; the reduction-order deltas are ~1e-7 at f32, the same
reassociation-only contract as the prefill flash kernel). The XLA gather
fallback itself stays BITWISE equal to the contiguous cache — the PR 6
gate — and is what CPU engines run (``attn_kernel_mode()`` auto-selects
'xla' off-TPU, exactly like the contiguous kernel's gate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.quants import QK
from .pallas_attention import (_VMEM64_PARAMS, _VMEM_BUDGET, NEG_INF,
                               _flash_walk, attn_kernel_mode)

KV_QUANTS = ("f32", "q8")  # the --kv-quant vocabulary (f32 = cache dtype)


def kv_quant_mode() -> str:
    """The KV page quantization in effect: DLLAMA_KV_QUANT=f32|q8,
    overridden by the CLI --kv-quant flag (which sets the env var, the
    DLLAMA_TP_SCHEME pattern — one resolution point, launch scripts and
    flags agree). Unknown values raise: a typo would otherwise silently
    serve f32 pages and read as 'no capacity win'."""
    import os

    env = os.environ.get("DLLAMA_KV_QUANT") or "f32"  # '' = unset
    if env not in KV_QUANTS:
        raise ValueError(f"DLLAMA_KV_QUANT={env!r}: expected "
                         f"{'|'.join(KV_QUANTS)}")
    return env


def _paged_scratch_bytes(page_size: int, n_kv: int, hs: int,
                         itemsize: int, q8: bool) -> int:
    """2 slots x {K, V} page planes, plus the Q8 scale planes (f16, one
    delta per QK values of the flattened (n_kv, hs) position row)."""
    planes = 2 * 2 * page_size * n_kv * hs * itemsize
    if q8:
        planes += 2 * 2 * page_size * (n_kv * hs // QK) * 2
    return planes


def supports_paged(page_size: int, n_kv: int, head_size: int, t_len: int,
                   itemsize: int = 4, q8: bool = False) -> bool:
    """The kernel handles decode/verify windows up to 8 queries with
    lane-width head_size and a page plane whose double-buffered scratch
    fits the VMEM budget; Q8 pages additionally need the flattened
    (n_kv, hs) row to divide into Q80 blocks. Callers take the XLA gather
    fallback otherwise — same gating contract as the contiguous
    ``supports()``."""
    if q8 and (n_kv * head_size) % QK:
        return False
    return (1 <= t_len <= 8 and head_size % 128 == 0
            and _paged_scratch_bytes(page_size, n_kv, head_size, itemsize,
                                     q8) <= _VMEM_BUDGET)


def _flash_pages(b, pos, q, table_ref, layer_ref, read_page, *,
                 page_size: int, n_pages: int, max_pages: int, kv_mul: int,
                 t_len: int):
    """The paged flash walk for one batch row: double-buffered page DMA
    through the table (``_flash_walk`` — the contiguous kernel's loop),
    (m, l, o) accumulation widened to t_len queries. ``read_page`` is the
    dtype hook: (slot, i, row) -> (start, wait) where wait(slot) returns
    the landed page as f32 (k, v) planes — raw planes for f32/bf16 pages,
    in-loop Q80 dequant for q8 pages. q: (t_len, n_kv, kv_mul, hs)."""
    n_kv, hs = q.shape[1], q.shape[3]
    scale = 1.0 / jnp.sqrt(jnp.float32(hs))
    s_virt = max_pages * page_size
    # live pages: the deepest query's position, clamped into the virtual
    # plane (a budget-edge verify window walks every mapped page; its
    # beyond-plane dead writes went to the scrap page and are never read)
    last = jnp.minimum(pos + t_len - 1, s_virt - 1)
    n_live = last // page_size + 1
    q_pos = pos + jax.lax.broadcasted_iota(jnp.int32, (t_len, 1, 1), 0)

    def row_of(i):
        # the page-table indirection: logical page i of row b lives at
        # physical plane table[b, i] of layer layer_ref[0]
        return layer_ref[0] * n_pages + table_ref[b, i]

    def start_dma(slot, i):
        read_page(slot, row_of(i)).start()

    def wait_dma(slot, i):
        read_page(slot, row_of(i)).wait()

    def update(i, slot, carry):
        k, v = read_page.landed(slot)                # (ps, n_kv, hs) f32
        key_pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, n_kv), 0)
        valid = key_pos[None] <= q_pos               # (t, ps, n_kv)
        out = []
        for mqi in range(kv_mul):
            m_old, l_old, o_old = carry[mqi]         # (t,n_kv),(t,n_kv),
            #                                          (t,n_kv,hs)
            qm = q[:, :, mqi, :]                     # (t, n_kv, hs)
            s = jnp.sum(k[None] * qm[:, None], axis=-1) * scale
            s = jnp.where(valid, s, NEG_INF)         # (t, ps, n_kv)
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])          # (t, ps, n_kv)
            corr = jnp.exp(m_old - m_new)            # (t, n_kv)
            l_new = l_old * corr + jnp.sum(p, axis=1)
            po = jnp.sum(p[..., None] * v[None], axis=1)   # (t, n_kv, hs)
            o_new = o_old * corr[..., None] + po
            out.append((m_new, l_new, o_new))
        return tuple(out)

    init = tuple((jnp.full((t_len, n_kv), NEG_INF, jnp.float32),
                  jnp.zeros((t_len, n_kv), jnp.float32),
                  jnp.zeros((t_len, n_kv, hs), jnp.float32))
                 for _ in range(kv_mul))
    return _flash_walk(n_live, start_dma, wait_dma, update, init)


class _RawPages:
    """f32/bf16 page reader: one K + one V plane DMA per page."""

    def __init__(self, k_hbm, v_hbm, k_buf, v_buf, sems):
        self.k_hbm, self.v_hbm = k_hbm, v_hbm
        self.k_buf, self.v_buf = k_buf, v_buf
        self.sems = sems

    def __call__(self, slot, row):
        reader = self

        class _Pair:
            def start(self):
                pltpu.make_async_copy(reader.k_hbm.at[row],
                                      reader.k_buf.at[slot],
                                      reader.sems.at[slot, 0]).start()
                pltpu.make_async_copy(reader.v_hbm.at[row],
                                      reader.v_buf.at[slot],
                                      reader.sems.at[slot, 1]).start()

            def wait(self):
                pltpu.make_async_copy(reader.k_hbm.at[row],
                                      reader.k_buf.at[slot],
                                      reader.sems.at[slot, 0]).wait()
                pltpu.make_async_copy(reader.v_hbm.at[row],
                                      reader.v_buf.at[slot],
                                      reader.sems.at[slot, 1]).wait()

        return _Pair()

    def landed(self, slot):
        return (self.k_buf[slot].astype(jnp.float32),
                self.v_buf[slot].astype(jnp.float32))


class _Q8Pages:
    """Q8 page reader: int8 code planes + Q80 delta planes as f16 bits
    (4 DMAs per page), dequantized on land with the exact XLA-fallback
    value map (_dequant_q80_page)."""

    def __init__(self, kq_hbm, kd_hbm, vq_hbm, vd_hbm, kq_buf, kd_buf,
                 vq_buf, vd_buf, sems):
        self.planes = ((kq_hbm, kq_buf, 0), (kd_hbm, kd_buf, 1),
                       (vq_hbm, vq_buf, 2), (vd_hbm, vd_buf, 3))
        self.sems = sems
        # page-invariant: built once per program, not once per page
        _, _, n_kv, hs = kq_buf.shape
        self.masks = _q80_spread_masks(n_kv, hs)

    def __call__(self, slot, row):
        reader = self

        class _Quad:
            def start(self):
                for hbm, buf, j in reader.planes:
                    pltpu.make_async_copy(hbm.at[row], buf.at[slot],
                                          reader.sems.at[slot, j]).start()

            def wait(self):
                for hbm, buf, j in reader.planes:
                    pltpu.make_async_copy(hbm.at[row], buf.at[slot],
                                          reader.sems.at[slot, j]).wait()

        return _Quad()

    def landed(self, slot):
        (_, kq_buf, _), (_, kd_buf, _), (_, vq_buf, _), (_, vd_buf, _) = \
            self.planes
        # the deltas land as raw f16 BITS (int16 planes, see
        # paged_decode_attention_kernel_q8) and are widened by hand
        return (_dequant_q80_page(kq_buf[slot], kd_buf[slot], self.masks),
                _dequant_q80_page(vq_buf[slot], vd_buf[slot], self.masks))


def _f16_bits_to_f32(bits):
    """Exact f16 -> f32 from the 16 raw bits (any integer dtype), in
    integer ops the chip's vector unit has. Normals move the exponent and
    mantissa fields into f32 position (+112 exponent rebias); f16
    subnormals are mantissa * 2^-24, built from the integer so no f32
    subnormal is ever formed (the vector unit flushes those). inf/nan are
    not mapped: a Q80 delta is amax/127 of finite cache values."""
    h = bits.astype(jnp.int32)
    mag = h & 0x7FFF
    normal = jax.lax.bitcast_convert_type((mag << 13) + (112 << 23),
                                          jnp.float32)
    sub = (mag & 0x3FF).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    f = jnp.where(mag >> 10 == 0, sub, normal)
    return jnp.where(h & 0x8000 != 0, -f, f)


def _q80_spread_masks(n_kv: int, hs: int):
    """The two selections _dequant_q80_page spreads deltas with, for a
    (n_kv, hs) position row of nb = n_kv*hs/QK blocks: ``own`` (1, n_kv,
    nb) keeps head h's blocks of the delta row, ``spread`` (nb, hs) f32
    sends block j to the QK lanes it scales."""
    nb, per_head = n_kv * hs // QK, hs // QK
    head = jax.lax.broadcasted_iota(jnp.int32, (n_kv, nb), 0)
    blk = jax.lax.broadcasted_iota(jnp.int32, (n_kv, nb), 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (nb, hs), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nb, hs), 1)
    return ((blk // per_head == head)[None],
            (j % per_head == lane // QK).astype(jnp.float32))


def _dequant_q80_page(codes, d, masks=None):
    """In-kernel Q80 page decode: codes (ps, n_kv, hs) int8, d (ps, nb)
    f16 bits with nb = n_kv*hs/QK block deltas over the flattened
    (n_kv, hs) row. Same values as quants.dequantize_q80_planes, which
    reshapes the codes to (ps, nb, QK) — a minor-dim split the chip's
    layout pass refuses ("infer-vector-layout: unsupported shape cast").
    Here the deltas are spread to the codes' (ps, n_kv, hs) shape instead,
    with casts the pass supports (``masks``, _q80_spread_masks): mask the
    delta row per kv head, then one exact 0/1 selection dot sends delta j
    to the QK lanes of its block. Every output is a single delta times
    1.0, so HIGHEST keeps it bit-exact."""
    ps, n_kv, hs = codes.shape
    nb = d.shape[-1]
    own, spread = masks or _q80_spread_masks(n_kv, hs)
    d32 = _f16_bits_to_f32(d)                               # (ps, nb)
    rows = jnp.where(own, d32[:, None, :], 0.0)             # (ps, n_kv, nb)
    scale = jax.lax.dot_general(
        rows.reshape(ps * n_kv, nb), spread, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    return codes.astype(jnp.float32) * scale.reshape(ps, n_kv, hs)


def _write_flash_out(final, out_ref, kv_mul: int):
    """THE (m, l, o) -> output normalization epilogue, shared by the f32
    and q8 kernels so a change to the finalization cannot drift between
    the two routes (they differ ONLY in how pages land in VMEM)."""
    for mqi in range(kv_mul):
        _, l_i, o_i = final[mqi]
        out_ref[0, :, :, mqi, :] = o_i / l_i[..., None]


def _kernel_paged(layer_ref, pos_ref, table_ref, q_ref, k_hbm, v_hbm,
                  out_ref, k_buf, v_buf, sems, *, page_size: int,
                  kv_mul: int, n_pages: int, t_len: int):
    """grid=(B,): program b flash-walks its live pages through the table.
    q_ref/out_ref: per-b (1, t_len, n_kv, kv_mul, hs) VMEM blocks;
    k/v_hbm: (L*P, ps, n_kv, hs) pool planes in HBM; k/v_buf: (2, ps,
    n_kv, hs) VMEM scratch; sems (2, 2) DMA semaphores (slot x {k, v})."""
    b = pl.program_id(0)
    reader = _RawPages(k_hbm, v_hbm, k_buf, v_buf, sems)
    final = _flash_pages(b, pos_ref[b], q_ref[0], table_ref, layer_ref,
                         reader, page_size=page_size, n_pages=n_pages,
                         max_pages=table_ref.shape[1], kv_mul=kv_mul,
                         t_len=t_len)
    _write_flash_out(final, out_ref, kv_mul)


def _kernel_paged_q8(layer_ref, pos_ref, table_ref, q_ref, kq_hbm, kd_hbm,
                     vq_hbm, vd_hbm, out_ref, kq_buf, kd_buf, vq_buf,
                     vd_buf, sems, *, page_size: int, kv_mul: int,
                     n_pages: int, t_len: int):
    """_kernel_paged's Q8 twin: int8 code + int16 (f16 bits) delta planes
    per page, dequantized inside the page loop; sems (2, 4)."""
    b = pl.program_id(0)
    reader = _Q8Pages(kq_hbm, kd_hbm, vq_hbm, vd_hbm, kq_buf, kd_buf,
                      vq_buf, vd_buf, sems)
    final = _flash_pages(b, pos_ref[b], q_ref[0], table_ref, layer_ref,
                         reader, page_size=page_size, n_pages=n_pages,
                         max_pages=table_ref.shape[1], kv_mul=kv_mul,
                         t_len=t_len)
    _write_flash_out(final, out_ref, kv_mul)


@functools.partial(jax.jit, static_argnames=("page_size", "n_pages",
                                             "kv_mul", "t_len",
                                             "interpret"))
def paged_decode_attention_kernel(q, k4, v4, layer, pos, table, *,
                                  page_size: int, n_pages: int,
                                  kv_mul: int, t_len: int = 1,
                                  interpret: bool | None = None):
    """Paged flash-decode attention over the rank-4 (L*P, ps, n_kv, hs)
    pool planes carried by models/llama.forward_batch_paged.

    q: (B, t_len, n_q*hs) f32; pos: (B,) per-row clocks; table:
    (B, max_pages) int32 physical page ids in logical order. Returns
    (B, t_len, n_q * hs) f32. Gate with supports_paged()."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    LP, ps, n_kv, hs = k4.shape
    B = q.shape[0]
    qg = q.reshape(B, t_len, n_kv, kv_mul, hs).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel_paged, page_size=page_size,
                          kv_mul=kv_mul, n_pages=n_pages, t_len=t_len),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, t_len, n_kv, kv_mul, hs),
                         lambda b: (b, 0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, t_len, n_kv, kv_mul, hs),
                               lambda b: (b, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, t_len, n_kv, kv_mul, hs),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, ps, n_kv, hs), k4.dtype),
            pltpu.VMEM((2, ps, n_kv, hs), k4.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(pos, jnp.int32).reshape(B),
      jnp.asarray(table, jnp.int32), qg, k4, v4)
    return out.reshape(B, t_len, n_kv * kv_mul * hs)


@functools.partial(jax.jit, static_argnames=("page_size", "n_pages",
                                             "kv_mul", "t_len",
                                             "interpret"))
def paged_decode_attention_kernel_q8(q, kq4, kd4, vq4, vd4, layer, pos,
                                     table, *, page_size: int,
                                     n_pages: int, kv_mul: int,
                                     t_len: int = 1,
                                     interpret: bool | None = None):
    """Q8 twin of paged_decode_attention_kernel: pool planes are the Q80
    int8 codes (L*P, ps, n_kv, hs) plus f16 block deltas (L*P, ps, nb),
    dequantized inside the kernel's page loop."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    LP, ps, n_kv, hs = kq4.shape
    nb = n_kv * hs // QK
    B = q.shape[0]
    qg = q.reshape(B, t_len, n_kv, kv_mul, hs).astype(jnp.float32)

    def f16_bits(d):
        # the chip takes no f16 kernel argument ("Only arguments with ...
        # bfloat16 or 32-bit element types are supported") and has no f16
        # vectors: hand the delta planes over as their raw bits — a
        # same-width bitcast, no copy
        return jax.lax.bitcast_convert_type(d, jnp.int16)

    out = pl.pallas_call(
        functools.partial(_kernel_paged_q8, page_size=page_size,
                          kv_mul=kv_mul, n_pages=n_pages, t_len=t_len),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, t_len, n_kv, kv_mul, hs),
                         lambda b: (b, 0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, t_len, n_kv, kv_mul, hs),
                               lambda b: (b, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, t_len, n_kv, kv_mul, hs),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, ps, n_kv, hs), jnp.int8),
            pltpu.VMEM((2, ps, nb), jnp.int16),
            pltpu.VMEM((2, ps, n_kv, hs), jnp.int8),
            pltpu.VMEM((2, ps, nb), jnp.int16),
            pltpu.SemaphoreType.DMA((2, 4)),
        ],
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(pos, jnp.int32).reshape(B),
      jnp.asarray(table, jnp.int32), qg, kq4, f16_bits(kd4), vq4,
      f16_bits(vd4))
    return out.reshape(B, t_len, n_kv * kv_mul * hs)


def would_use_paged_kernel(page_size: int, n_kv: int, head_size: int,
                           t_len: int, itemsize: int = 4,
                           q8: bool = False) -> bool:
    """The routing gate's VERDICT, queryable without running it: mode
    check + shape support exactly as maybe_paged_flash_decode applies
    them. Anything that needs to predict the route (the engine's q8
    fallback warning, future bench columns) asks HERE instead of
    re-deriving the gate — one source of truth, no drift."""
    return (attn_kernel_mode() == "pallas"
            and supports_paged(page_size, n_kv, head_size, t_len,
                               itemsize, q8=q8))


def maybe_paged_flash_decode(q, planes, idx, pos, table, *, page_size: int,
                             n_pages: int, head_size: int, t_len: int,
                             n_kv: int, kv_mul: int, kv_quant: str = "f32"):
    """The ONE gate for routing paged decode/verify attention to the paged
    flash kernel — models/llama.paged_decode_attention and
    spec_verify_attention (and through them BOTH tp factories,
    make_sharded_forward_batch_paged / make_sharded_verify, under all
    three collective schemes) call this, so the mode/shape gating can
    never drift between the five call sites.

    q: (B, t_len, n_q*hs); ``planes`` is (k4, v4) for f32/bf16 pages or
    (kq4, kd4, vq4, vd4) for Q8 pages — the rank-4 (L*P, ps, ...) carry
    views. Returns (B, t_len, n_q*hs) f32, or None when the caller must
    take its XLA gather fallback (kernel disabled or shape unsupported).
    """
    q8 = kv_quant == "q8"
    itemsize = 1 if q8 else planes[0].dtype.itemsize
    if not would_use_paged_kernel(page_size, n_kv, head_size, t_len,
                                  itemsize, q8=q8):
        return None
    B = q.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if q8:
        kq4, kd4, vq4, vd4 = planes
        return paged_decode_attention_kernel_q8(
            q, kq4, kd4, vq4, vd4, idx, pos_b, table, page_size=page_size,
            n_pages=n_pages, kv_mul=kv_mul, t_len=t_len)
    k4, v4 = planes
    return paged_decode_attention_kernel(
        q, k4, v4, idx, pos_b, table, page_size=page_size,
        n_pages=n_pages, kv_mul=kv_mul, t_len=t_len)
