"""Pallas TPU kernel: flash-decode attention over the PAGED page-pool KV
cache, read through the page table (the vLLM / PagedAttention move, Kwon et
al. SOSP'23): no gathered copy of a row's cache, HBM traffic proportional to
the live positions.

One kernel covers both paged shapes: the one-token decode step (t_len 1,
``forward_batch_paged``) and the speculative-verify window (t_len 2..8,
``forward_batch_spec_paged``; query i of a row sees positions 0..pos+i). The
pool keeps its layout, (L*P, page_size, n_kv, hs): a page's positions
outermost, the KV heads second-minor. grid=(B,): program b walks row b's live
pages with ``pallas_attention._flash_walk``'s double-buffered DMA loop and
keeps the running (m, l, o) of every query row; scores are scaled by
1 / sqrt(hs).

Pages a turn. A page is smaller than the fold's tile (16 positions of the
MXU's 128), so a loop turn lands ``_pages_a_turn`` pages (the head-major
kernels' rule: 8 at 16 a page, one at 128) one after the other in a slot of
C = G x page_size positions, (C, n_kv, hs), each page its own copy through
the table, a turn's copies sharing a semaphore a slot and side. A turn's
pages past the row's last live one are NOT copied (one loop over the turn's
live pages starts the copies, one waits for them): the table's entries past
it are never read, their place in the slot keeps what an earlier turn or row
left there, and their positions are masked. A masked position's weight is
exactly 0 and 0 x NaN would be NaN, so what is left there must be finite on
the V side: the V slots start a call as zeros (``clear``) and only pool pages
ever land in them; a NaN score from stale K is masked like any other.

The fold is the head-major kernels' (``pallas_head_major_attention._fold``,
as it lies): both contractions on the MXU, K and V read once for all the
query rows of a group (a KV head's ``kv_mul`` query heads x ``t_len`` queries,
padded to a sublane tile). Every product keeps all 24 bits of both operands
(that module's docstring has the argument): each operand is cut in its three
bf16 pieces, the small operand's pieces (the queries, cut once a call outside
the kernel; the turn's weights p) stacked along the rows against each piece
of K or V, the nine piece products each exact in the MXU's float32
accumulator and added the small ones first. It wants K and V head-major,
(n_kv, C, hs); the pool's heads are second-minor, and ``_heads`` is the
bridge: where they are whole sublane tiles (n_kv % 8 == 0) the landed slot
IS (C x n_kv, hs) with a position's heads on consecutive rows, so a KV
head's K or V is ONE strided read of it (every n_kv-th row: a register a
load, as many as reading the slot whole) and nothing is shuffled; a head
count that is not whole tiles (a tp rank's) takes one relayout of the loaded
slot. Alone on the chip (PERF.md section 7) the strided reads are within 7 to
13 % of the copies alone; the same reads feeding a product a head 2 to 6 %
ahead of that, at seven times the program's tracing (3 s of a serving
cell's set-up on the chip's host); the relayout 1 to 5 % behind; a head
INDEXED out of the slot (``k[:, h, :]``, of the ref or of its value) 1.7 to
2.8 times slower: Mosaic gathers it a row at a time. What differs between
OLMoE (16 KV heads x 1), Mistral (8 x 4), a verify window and a ``serve
--tp`` rank (n_kv / tp heads) is the shape the kernel is traced with: no
flag, no environment variable.

KV dtypes: f32/bf16 pages DMA raw planes, and a float32 slot is read where
it landed (the chip has no strided load of 16-bit rows: a bf16 slot is
widened into one float32 slot a side first); Q8 pages
(``DLLAMA_KV_QUANT=q8``) DMA the int8 code planes PLUS the per-position f16
Q80 block-delta planes and dequantize a landed slot into the same float32
slots (``_dequant_q80_page``), bit for bit the
``codes.astype(f32) * delta.astype(f32)`` value map of the XLA fallback's
gather-side dequant (ops/quants.dequantize_q80_planes), so both routes see
identical f32 K/V values and the one fold sees float32. The chip shapes how:
it takes no f16 kernel argument and has no f16 vectors, so the delta planes
travel as their raw int16 bits and are widened by hand, and its layout pass
refuses the (ps, nb, QK) reshape of the codes, so the deltas are spread to
the codes' shape instead.

Parity contract (tests/test_pallas_paged_attention.py): the kernel is
INVARIANT to physical page placement (any permutation of the pool that
updates the table produces bitwise-identical output) and element-level
equal to the XLA gather path at the flash tolerance (the split-KV
accumulation reassociates the softmax sums across turns); its distance from
a float64 attention is the float32 sums' alone. The XLA gather fallback
stays BITWISE equal to the contiguous cache and is what CPU engines run
(``attn_kernel_mode()`` auto-selects 'xla' off-TPU).
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
from .pallas_head_major_attention import (_fold, _group_rows, _heads,
                                          _pages_a_turn, _stack3)

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
    """2 slots x {K, V} planes of a turn's ``_pages_a_turn`` pages each, plus
    the Q8 scale planes (f16, one delta per QK values of the flattened
    (n_kv, hs) position row), plus, where a page is not float32 as it lies,
    the one float32 slot a side a landed turn is widened into."""
    positions = _pages_a_turn(page_size) * page_size
    planes = 2 * 2 * positions * n_kv * hs * itemsize
    if q8:
        planes += 2 * 2 * positions * (n_kv * hs // QK) * 2
    if itemsize != 4:
        planes += 2 * positions * n_kv * hs * 4
    return planes


def supports_paged(page_size: int, n_kv: int, head_size: int, t_len: int,
                   itemsize: int = 4, q8: bool = False) -> bool:
    """The kernel handles decode/verify windows up to 8 queries with
    lane-width head_size and a turn's pages whose double-buffered scratch
    fits the VMEM budget; Q8 pages additionally need the flattened
    (n_kv, hs) row to divide into Q80 blocks. Callers take the XLA gather
    fallback otherwise — same gating contract as the contiguous
    ``supports()``."""
    if q8 and (n_kv * head_size) % QK:
        return False
    return (1 <= t_len <= 8 and head_size % 128 == 0
            and _paged_scratch_bytes(page_size, n_kv, head_size, itemsize,
                                     q8) <= _VMEM_BUDGET)


def _flash_pages(pos_ref, table_ref, layer_ref, q3_ref, out_ref, reader, *,
                 page_size: int, n_pages: int, kv_mul: int, t_len: int):
    """grid=(B,): program b walks row b's live pages through the table,
    ``_pages_a_turn`` a turn (``_flash_walk``: the contiguous kernel's
    double-buffered loop), folds each landed slot into the running (m, l, o)
    of its query rows and writes them out normalised. q3_ref (1, n_kv, 3 R,
    hs): a KV head's R rows are (query, head of the group), padded;
    out_ref (1, n_kv, t_len * kv_mul, hs). ``reader`` is the dtype hook:
    ``copies(slot, g, row)`` are the DMAs that land pool plane ``row`` as
    page g of a slot, ``landed(slot)`` the slot's (k, v) as float32 refs
    (C, n_kv, hs): the raw planes themselves for f32 pages, bf16 pages
    widened and q8 pages dequantized into ``wide``."""
    b = pl.program_id(0)
    q3 = q3_ref[0]
    n_kv, rows3, hs = q3.shape
    rows, live = rows3 // 3, t_len * kv_mul
    group = _pages_a_turn(page_size)
    chunk = group * page_size
    # the deepest query's position, clamped into the virtual plane (a
    # budget-edge verify window walks every mapped page; its beyond-plane
    # dead writes went to the scrap page and are never read)
    last = jnp.minimum(pos_ref[b] + t_len - 1,
                       table_ref.shape[1] * page_size - 1)
    last_page = last // page_size
    # the last position a query row attends: its own, never past the plane
    # (a padded row: the deepest query's)
    r = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
    limit = jnp.minimum(pos_ref[b] + r // kv_mul, last)
    key = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)

    def each_copy(slot, i, act):
        # a turn's pages past the row's last live one are not copied: their
        # place in the slot keeps what an earlier turn (or an earlier row)
        # left there, and their positions are masked. ONE loop over the
        # turn's live pages, traced once (a ``pl.when`` a page is a traced
        # branch a page and call site: 3 s of a serving cell's set-up on
        # the chip's host, PERF.md section 7)
        def page(g, _):
            # the page-table indirection: logical page i of row b lives at
            # physical plane table[b, i] of layer layer_ref[0]
            row = layer_ref[0] * n_pages + table_ref[b, i * group + g]
            for c in reader.copies(slot, g, row):
                act(c)

        jax.lax.fori_loop(0, jnp.minimum(group, last_page + 1 - i * group),
                          page, None)

    # what a masked position leaves in a slot must be finite on the V side
    # (its weight is exactly 0, and 0 x NaN would be NaN; a NaN score is
    # masked): the slots start the call as zeros and only pages land there
    pl.when(b == 0)(reader.clear)

    init = (jnp.full((n_kv, rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((n_kv, rows, 1), jnp.float32),
            jnp.zeros((n_kv, rows, hs), jnp.float32))
    _, l_fin, o_fin = _flash_walk(
        last_page // group + 1,
        lambda slot, i: each_copy(slot, i, lambda c: c.start()),
        lambda slot, i: each_copy(slot, i, lambda c: c.wait()),
        lambda i, slot, carry: _fold(
            q3, *(_heads(w, n_kv) for w in reader.landed(slot)),
            i * chunk + key <= limit, carry),
        init)
    out_ref[0] = (o_fin / l_fin)[:, :live]


class _Pages:
    """What the two page readers share: ``planes``, the (pool plane in HBM,
    its slots (2, C, ...) in VMEM) pairs a page is copied through, one
    semaphore a slot and plane; ``wide``, the (k, v) float32 slots (C, n_kv,
    hs) a landed turn is made float32 in where it does not land so."""

    def __init__(self, planes, sems, wide, page_size):
        self.planes, self.sems = planes, sems
        self.wide, self.ps = wide, page_size

    def copies(self, slot, g, row):
        at = pl.ds(g * self.ps, self.ps)
        return [pltpu.make_async_copy(hbm.at[row], buf.at[slot, at],
                                      self.sems.at[slot, j])
                for j, (hbm, buf) in enumerate(self.planes)]

    def clear(self):
        """Zero what scales a slot's V: the last plane's slots."""
        buf = self.planes[-1][1]
        buf[...] = jnp.zeros(buf.shape, buf.dtype)


class _RawPages(_Pages):
    """f32/bf16 page reader: one K + one V plane DMA per page into slots
    k / v_buf (2, C, n_kv, hs); sems (2, 2): slot x {k, v}. f32 pages are
    read where they land (no ``wide``); bf16 pages are widened."""

    def landed(self, slot):
        if not self.wide:
            return tuple(buf.at[slot] for _, buf in self.planes)
        for (_, buf), w in zip(self.planes, self.wide):
            w[...] = buf[slot].astype(jnp.float32)
        return self.wide


class _Q8Pages(_Pages):
    """Q8 page reader: int8 code planes + Q80 delta planes as f16 bits
    (4 DMAs per page: K codes, K deltas, V codes, V deltas) into slots
    (2, C, n_kv, hs) and (2, C, nb), dequantized on land with the exact
    XLA-fallback value map (_dequant_q80_page); sems (2, 4). ``clear``
    zeroes V's deltas: zeros scale whatever codes a slot holds to 0.0."""

    def __init__(self, planes, sems, wide, page_size):
        super().__init__(planes, sems, wide, page_size)
        # page-invariant: built once per program, not once per turn
        self.masks = _q80_spread_masks(*planes[0][1].shape[2:])

    def landed(self, slot):
        # the deltas land as raw f16 BITS (int16 planes, see
        # paged_decode_attention_kernel_q8) and are widened by hand
        (_, kq_buf), (_, kd_buf), (_, vq_buf), (_, vd_buf) = self.planes
        for w, q_buf, d_buf in zip(self.wide, (kq_buf, vq_buf),
                                   (kd_buf, vd_buf)):
            w[...] = _dequant_q80_page(q_buf[slot], d_buf[slot], self.masks)
        return self.wide


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


def _kernel_paged(layer_ref, pos_ref, table_ref, q3_ref, k_hbm, v_hbm,
                  out_ref, k_buf, v_buf, sems, *wide, page_size: int,
                  **walk):
    """k/v_hbm: (L*P, ps, n_kv, hs) pool planes in HBM."""
    _flash_pages(pos_ref, table_ref, layer_ref, q3_ref, out_ref,
                 _RawPages(((k_hbm, k_buf), (v_hbm, v_buf)), sems, wide,
                           page_size),
                 page_size=page_size, **walk)


def _kernel_paged_q8(layer_ref, pos_ref, table_ref, q3_ref, kq_hbm, kd_hbm,
                     vq_hbm, vd_hbm, out_ref, kq_buf, kd_buf, vq_buf,
                     vd_buf, sems, *wide, page_size: int, **walk):
    """_kernel_paged's Q8 twin: int8 code + int16 (f16 bits) delta planes
    per page, dequantized a landed slot."""
    _flash_pages(pos_ref, table_ref, layer_ref, q3_ref, out_ref,
                 _Q8Pages(((kq_hbm, kq_buf), (kd_hbm, kd_buf),
                           (vq_hbm, vq_buf), (vd_hbm, vd_buf)), sems, wide,
                          page_size),
                 page_size=page_size, **walk)


def _paged_call(kernel, q, planes, scratch, layer, pos, table, *,
                page_size: int, n_pages: int, kv_mul: int, t_len: int,
                interpret: bool | None):
    """The ``pallas_call`` both kernels share: layer, clocks and table in
    SMEM, a row's queries a KV head (``t_len x kv_mul`` rows, padded to a
    sublane tile and cut in stacked pieces here, once a call), the pool
    planes left in HBM. Returns (B, t_len, n_q * hs)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, live = q.shape[0], t_len * kv_mul
    n_kv, hs = planes[0].shape[2:]
    qg = q.reshape(B, t_len, n_kv, kv_mul, hs).astype(jnp.float32)
    qg = jnp.swapaxes(qg, 1, 2).reshape(B, n_kv, live, hs)
    q3 = _stack3(jnp.pad(qg, ((0, 0), (0, 0),
                              (0, _group_rows(live) - live), (0, 0))))
    out = pl.pallas_call(
        functools.partial(kernel, page_size=page_size, n_pages=n_pages,
                          kv_mul=kv_mul, t_len=t_len),
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
        + [pl.BlockSpec((1, *q3.shape[1:]), lambda b: (b, 0, 0, 0))]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(planes),
        out_specs=pl.BlockSpec((1, n_kv, live, hs), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, live, hs), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=_VMEM64_PARAMS,
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(pos, jnp.int32).reshape(B),
      jnp.asarray(table, jnp.int32), q3, *planes)
    out = jnp.swapaxes(out.reshape(B, n_kv, t_len, kv_mul, hs), 1, 2)
    return out.reshape(B, t_len, n_kv * kv_mul * hs)


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
    _, ps, n_kv, hs = k4.shape
    positions = _pages_a_turn(ps) * ps
    slot = pltpu.VMEM((2, positions, n_kv, hs), k4.dtype)
    wide = [] if k4.dtype == jnp.float32 else [
        pltpu.VMEM((positions, n_kv, hs), jnp.float32)] * 2
    return _paged_call(
        _kernel_paged, q, (k4, v4),
        [slot, slot, pltpu.SemaphoreType.DMA((2, 2)), *wide],
        layer, pos, table, page_size=page_size, n_pages=n_pages,
        kv_mul=kv_mul, t_len=t_len, interpret=interpret)


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
    _, ps, n_kv, hs = kq4.shape
    positions = _pages_a_turn(ps) * ps

    def f16_bits(d):
        # the chip takes no f16 kernel argument ("Only arguments with ...
        # bfloat16 or 32-bit element types are supported") and has no f16
        # vectors: hand the delta planes over as their raw bits — a
        # same-width bitcast, no copy
        return jax.lax.bitcast_convert_type(d, jnp.int16)

    codes = pltpu.VMEM((2, positions, n_kv, hs), jnp.int8)
    deltas = pltpu.VMEM((2, positions, n_kv * hs // QK), jnp.int16)
    wide = pltpu.VMEM((positions, n_kv, hs), jnp.float32)
    return _paged_call(
        _kernel_paged_q8, q, (kq4, f16_bits(kd4), vq4, f16_bits(vd4)),
        [codes, deltas, codes, deltas, pltpu.SemaphoreType.DMA((2, 4)),
         wide, wide],
        layer, pos, table, page_size=page_size, n_pages=n_pages,
        kv_mul=kv_mul, t_len=t_len, interpret=interpret)


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
