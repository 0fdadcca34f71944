"""Flash-decode attention over HEAD-MAJOR caches: one query position a row
over a contiguous plane (rows, n_kv, S, hs) or over pages (P, n_kv,
page_size, hs), grouped-query, float32. K and V may differ in their last
dim (``hk`` / ``hv``: a mixer-kinds spec's K heads of 192, held in 256
lanes, beside V heads of 128: the first contraction runs over ``hk``, the
second onto ``hv``; ``head_size`` says what the scores are scaled by where
K's lanes hold more than a head: the queries are padded with zeros to
them), and a
query head may have a SINK (``sink`` (n_q,): one more column of its softmax
that carries no value): the walk's carry then starts at m = sink, l = 1,
o = 0 where it otherwise starts at m = -inf, l = 0, o = 0, and nothing else
of the kernel knows of it.

Why another layout than ``pallas_attention.py`` / ``pallas_paged_attention
.py`` ((rows, S, n_kv, hs) and (P, page_size, n_kv, hs)): a hybrid spec's
attention has 10 KV heads (pairs) of 128 (models/sambay.py). With the head
count second-minor the chip tiles (10, 128) as (16, 128): the cache takes
1.6 x its bytes in HBM and in every read, and XLA, which prefers the other
order, copied the whole pool and every ring around each kernel call (the
described-chip compile of the 32-row step showed 8.2 GiB of temporaries,
PR 37). Head-major, the two minor dims are (positions, 128): whole tiles
whatever the head count, the default layout is the one the kernels take, a
page is one contiguous block, and the scores of a chunk lie (group, chunk)
a KV head with the positions along lanes.

Both kernels walk a row's live positions a CHUNK at a time with
``_flash_walk``'s double-buffered DMA loop (it, ``_fold`` and ``_heads``
lie here for every flash-decode kernel to import: the contiguous kernels
of ``pallas_attention.py``, the paged ones, the latent ones) and keep the
running (m, l, o) of every query head; scores are scaled by 1 / sqrt(head
size). Every position up to a row's ``last`` is attended.

Both last dims are whole 128-lane tiles. The chip tiles a last dim of 192
in 256 lanes in HBM whatever the array's shape says, and Mosaic refuses a
copy whose last dim is not whole tiles (``Slice shape along dimension 3
must be aligned to tiling (128), but is 192``: the described-chip compile,
PR 48), so a cache whose heads are 192 wide holds them in 256 lanes, the
last 64 zero (``models/spec.cache_lanes``): a copy moves a third more than
K's published bytes, and a roofline share reckoned on the published bytes
reads that as lost (PERF.md section 7 has what a plane cut in a 64 and a
128 part would take instead).

How a chunk is chosen (from the call's shapes alone: no flag, no argument).
A plane is cut in the largest chunk of 512 positions or fewer that divides
it and leaves AT LEAST FOUR a plane, but for a plane of 128 positions, the
fold's one tile, which is cut in two (``_chunk``: 128 for a 512-slot ring,
512 for a sequence's 8,704, 64 for a 128-slot ring): a walk is the first chunk's
copy, then copies hidden behind folds, then the last chunk's fold, so the
fewer chunks a plane has the more of it is exposed (a 512-slot ring alone
on the chip, of the HBM roofline: one chunk of 512 58 %, two of 256 66 %,
four of 128 70 %; the copies alone 78 %; PERF.md section 7), and a fold of
few positions leaves most of the MXU's 128-position tile empty (a 128-slot
ring of K 256 lanes / V 128 over 8 KV heads alone on the chip, PR 48: four
chunks of 32 137 us a layer, two of 64 116, one of 128 128). A page is
smaller than the fold's tile (16 positions of the MXU's 128), so the paged
kernel lands ``_pages_a_turn`` pages a turn side by side in one (n_kv, G x
page_size, hs) slot, G the pages that make 128 positions (8 at 16 a page: 81
% of the roofline at a depth of 1,700, where 4 read 75, 16 77 and 32 68). A
chunk's pages past the row's last live one are that last live page copied
again: the table's entries past it are never read, what a slot holds is
always the row's own finite K / V, and their positions are masked (a masked
position's weight is exactly 0, and 0 x NaN would be NaN).

How a chunk is folded (``_fold``): both contractions on the MXU, K and V
read once for all the query heads of a group. The configuration's
precision is float32, so every product keeps all 24 bits of both operands:
each operand is cut in three pieces that ARE bf16 numbers
(``pallas_q40._mask_pieces``: a float32 is their sum exactly), the small
operand's pieces (the queries, cut once a call outside the kernel; the
chunk's weights p) are stacked along rows against each piece of K or V, so
three dots give all NINE piece products (Precision.HIGHEST keeps six), each
exact in the MXU's float32 accumulator, and the nine slabs are added the
small ones first. The pieces stay float32 and the dots run at DEFAULT
precision, Mosaic's one bf16 pass, which rounds an operand that is a bf16
number already to itself (PERF.md section 7 has the candidates' times).
Max, exp and the sums stay on the vector unit, on (n_kv, group, chunk).
``_dot6`` is the same stack against pieces cut by the caller, in HIGHEST's
six products: the latent kernels' fold (``pallas_latent_attention.py``: 32
to 128 query heads over ONE plane, where the nine would load no tile less
and stream half as many rows again).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_q40 import _VMEM64_PARAMS, _mask_pieces

ROWS_KERNEL = "hm_attn_rows_decode"
PAGED_KERNEL = "hm_attn_paged_decode"
_TILE = 128         # positions the MXU holds still at once: the fold's tile
NEG_INF = float("-inf")
_VMEM_BUDGET = 12 * 1024 * 1024  # scratch budget: bounds the DMA chunk size


def _flash_walk(n_chunks, start_dma, wait_dma, update, init):
    """THE double-buffered flash DMA loop, shared by the contiguous kernels
    (ops/pallas_attention.py), the paged kernels
    (ops/pallas_paged_attention.py) and the ones here: start chunk
    0, then per iteration prefetch chunk i+1 into the other slot while
    chunk i is reduced into the carry. ``start_dma(slot, i)`` issues the
    copies for chunk i, ``wait_dma(slot, i)`` blocks on them, and
    ``update(i, slot, carry)`` folds the landed chunk into the running
    (m, l, o) state."""
    start_dma(0, 0)

    def body(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_chunks)
        def _():
            start_dma(jax.lax.rem(i + 1, 2), i + 1)

        wait_dma(slot, i)
        return update(i, slot, carry)

    return jax.lax.fori_loop(0, n_chunks, body, init)


def _group_rows(kv_mul: int) -> int:
    """Rows a group's query heads take in the stacked pieces: ``kv_mul``
    rounded up to float32's 8-row sublane tile, so each piece's slab is
    whole vector registers."""
    return -(-kv_mul // 8) * 8


def _stack3(x):
    """(..., R, K) float32 -> (..., 3 R, K): the three bf16 pieces of ``x``
    along the rows, [hi; mid; lo]."""
    return jnp.concatenate(_mask_pieces(x, 3), axis=-2)


def _dot9(x3, w, contract: int):
    """(n, R, N) float32: the product of x (n, R, K), handed over as its
    stacked pieces ``x3`` (n, 3 R, K), and ``w`` over ``w``'s dim
    ``contract`` (the other is N), batched over n, EXACT in every product:
    one dot of x3 against each of w's three pieces gives the nine piece
    products, each of two bf16 numbers, summed over K in float32; the slabs
    are added in the order of their size, the smallest first."""
    r = x3.shape[1] // 3
    dn = (((2,), (contract,)), ((0,), (0,)))
    hi, mid, lo = (jax.lax.dot_general(x3, p, dn,
                                       preferred_element_type=jnp.float32)
                   for p in _mask_pieces(w, 3))
    at = lambda a, i: a[:, i * r:(i + 1) * r]               # noqa: E731
    return ((((at(lo, 2) + (at(lo, 1) + at(mid, 2)))
              + (at(lo, 0) + at(mid, 1) + at(hi, 2)))
             + (at(mid, 0) + at(hi, 1))) + at(hi, 0))


def _dot6(x3, pieces, contract: int):
    """(R, N) float32: the product of x (R, K), handed over as its stacked
    pieces ``x3`` (3 R, K), and w, handed over as its three ``pieces`` (hi,
    mid, lo), over their dim ``contract`` (the other is N), in the SIX piece
    products ``Precision.HIGHEST`` keeps, each piece of w pushed to the MXU
    ONCE: x3 . hi (hi hi, mid hi, lo hi), [hi; mid] . mid (hi mid, mid
    mid) and hi . lo. What is left out (mid lo, lo mid, lo lo: 2^-24 of a
    product and under) is what HIGHEST's six passes leave out too. Each
    product is of two bf16 numbers, summed over K in float32; the slabs are
    added in the order of their size, the smallest first."""
    r = x3.shape[0] // 3
    dn = (((1,), (contract,)), ((), ()))
    hi, mid, lo = (jax.lax.dot_general(x3[:n * r], p, dn,
                                       preferred_element_type=jnp.float32)
                   for n, p in zip((3, 2, 1), pieces))
    return (((hi[2 * r:] + mid[r:] + lo) + (hi[r:2 * r] + mid[:r]))
            + hi[:r])


def _fold(q3, k, v, valid, carry, head_size=None):
    """One landed chunk into the carry. q3 (n_kv, 3 R, hk) the group's
    queries in stacked pieces; k (n_kv, C, hk), v (n_kv, C, hv); valid (1,
    1, C); carry m, l (n_kv, R, 1) and o (n_kv, R, hv); scores are scaled
    by 1 / sqrt(head_size) (default: K's last dim)."""
    m_old, l_old, o_old = carry
    scale = 1.0 / jnp.sqrt(jnp.float32(head_size or k.shape[-1]))
    s = jnp.where(valid, _dot9(q3, k, 2) * scale, NEG_INF)  # (n_kv, R, C)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_new = l_old * corr + jnp.sum(p, axis=2, keepdims=True)
    o_new = o_old * corr + _dot9(_stack3(p), v, 1)
    return m_new, l_new, o_new


def _heads(w, n_kv: int):
    """A landed slot whose heads are second-minor, a float32 ref (C, n_kv,
    hs), as the value (n_kv, C, hs) ``_fold`` takes.

    Where a position's heads are whole sublane tiles (n_kv % 8 == 0: every
    single-chip pool or cache) the slot is (C x n_kv, hs) as it lies, a
    position's heads on consecutive rows, so a head's K or V is ONE strided
    read of it (every n_kv-th row) and nothing is shuffled. Any other head
    count (a tp rank's 2 or 10) is padded to a tile in the slot, which is then
    NOT those rows (the chip compiles the view and reads wrong rows at 10
    heads: distance 0.59, PERF.md section 7): one relayout of the loaded
    slot."""
    chunk, _, hs = w.shape
    if n_kv % 8:
        return jnp.swapaxes(w[...], 0, 1)
    rows = w.reshape(chunk * n_kv, hs)
    return jnp.stack([rows[pl.ds(h, chunk, stride=n_kv), :]
                      for h in range(n_kv)])


def _walk(n_chunks, copies, last, q3_ref, sink_ref, k_buf, v_buf, out_ref,
          head_size):
    """Walk ``n_chunks`` chunks of a slot's size (``copies(slot, i)``: the
    DMAs that land chunk i in k / v_buf[slot]) into a fresh carry, positions
    past ``last`` masked, and write the group's heads out. ``sink_ref``
    (n_kv, R, 1) or None: the carry then starts as if a column of that
    score and no value had been folded already."""
    q3 = q3_ref[0]
    n_kv, r3, _ = q3.shape
    rows, kv_mul, chunk = r3 // 3, out_ref.shape[2], k_buf.shape[2]
    hv = v_buf.shape[3]
    if sink_ref is None:
        m0 = jnp.full((n_kv, rows, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((n_kv, rows, 1), jnp.float32)
    else:
        m0 = sink_ref[...]
        l0 = jnp.ones((n_kv, rows, 1), jnp.float32)
    init = (m0, l0, jnp.zeros((n_kv, rows, hv), jnp.float32))

    def update(i, slot, carry):
        pos = i * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk),
                                                   2)
        return _fold(q3, k_buf[slot].astype(jnp.float32),
                     v_buf[slot].astype(jnp.float32), pos <= last, carry,
                     head_size)

    _, l_fin, o_fin = _flash_walk(
        n_chunks, lambda s, i: [c.start() for c in copies(s, i)],
        lambda s, i: [c.wait() for c in copies(s, i)], update, init)
    out_ref[0] = (o_fin / l_fin)[:, :kv_mul]


def _with_sink(kernel, sink: bool):
    """``kernel`` as the ``pallas_call`` hands it its refs: with the sink's
    operand after the queries, or without one."""
    if sink:
        return kernel

    def no_sink(a_ref, b_ref, q3_ref, *rest, **kw):
        return kernel(a_ref, b_ref, q3_ref, None, *rest, **kw)

    return no_sink


def _rows_kernel(layer_ref, last_ref, q3_ref, sink_ref, k_hbm, v_hbm,
                 out_ref, k_buf, v_buf, sems, *, chunk: int, batch: int,
                 head_size: int):
    """grid=(B,): program b walks the live chunks of plane layer * B + b.
    q3_ref (1, n_kv, 3 R, hk); out_ref (1, n_kv, kv_mul, hv); k / v_hbm
    (rows, n_kv, S, hk | hv); k / v_buf (2, n_kv, chunk, hk | hv)."""
    b = pl.program_id(0)
    row, last = layer_ref[0] * batch + b, last_ref[b]

    def copies(slot, i):
        at = pl.ds(i * chunk, chunk)
        return (pltpu.make_async_copy(k_hbm.at[row, :, at], k_buf.at[slot],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[row, :, at], v_buf.at[slot],
                                      sems.at[slot, 1]))

    _walk(last // chunk + 1, copies, last, q3_ref, sink_ref, k_buf, v_buf,
          out_ref, head_size)


def _chunk(seq_len: int, n_kv: int, hs: int, itemsize: int,
           hv: int = 0) -> int | None:
    """The largest chunk of positions that divides ``seq_len``, leaves at
    least four a plane (copies behind folds; a plane under 32 is cut in
    eights; a plane of 128, one fold's tile, in two) and has both slots of
    K (last dim ``hs``) and V (``hv``, default ``hs``) inside the scratch
    budget."""
    for c in (512, 256, 128, 64, 32, 16, 8):
        if (seq_len % c == 0
                and (seq_len >= 4 * c or c == 8 or seq_len == 2 * c == 128)
                and 2 * c * n_kv * (hs + (hv or hs)) * itemsize
                <= _VMEM_BUDGET):
            return c
    return None


def _whole_tiles(head_size: int, v_head_size: int) -> bool:
    """Whether K's and V's last dims (``v_head_size`` 0: V is as wide as K)
    are whole 128-lane tiles."""
    return not (head_size % _TILE or v_head_size % _TILE)


def supports(seq_len: int, n_kv: int, head_size: int, itemsize: int = 4,
             v_head_size: int = 0) -> bool:
    """``head_size`` / ``v_head_size``: the planes' last dims as held."""
    return _whole_tiles(head_size, v_head_size) and _chunk(
        seq_len, n_kv, head_size, itemsize, v_head_size) is not None


def _call(kernel, name, B, n_kv, kv_mul, hk, hv, slot, dtype, interpret,
          sink: bool):
    """The ``pallas_call`` both kernels share: two scalar operands in SMEM,
    the stacked queries a row (and, with ``sink``, the sinks (n_kv, R, 1),
    whole, in VMEM), K and V left in HBM; two slots of ``slot`` positions
    each for K and for V."""
    rows = _group_rows(kv_mul)
    sinks = [pl.BlockSpec((n_kv, rows, 1), lambda b: (0, 0, 0))] if sink \
        else []
    return pl.pallas_call(
        _with_sink(kernel, sink), grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, n_kv, 3 * rows, hk),
                               lambda b: (b, 0, 0, 0)),
                  *sinks,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_kv, kv_mul, hv),
                               lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, kv_mul, hv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, n_kv, slot, hk), dtype),
                        pltpu.VMEM((2, n_kv, slot, hv), dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
        compiler_params=_VMEM64_PARAMS, interpret=interpret, name=name)


def _stacked_queries(q, n_kv: int, kv_mul: int, hk: int):
    """q (B, n_kv * kv_mul [x] head) -> (B, n_kv, 3 R, hk): each group's
    heads padded to R rows (zeros: their scores are 0 and nothing reads
    them) and to K's ``hk`` lanes (zeros against K's zeros), and cut in
    pieces, once a call."""
    qg = q.reshape(q.shape[0], n_kv, kv_mul, -1).astype(jnp.float32)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, _group_rows(kv_mul) - kv_mul),
                      (0, hk - qg.shape[-1])))
    return _stack3(qg)


def _head_size(q, n_q: int) -> int:
    """A query head's size, from q (B, n_q [x] head)."""
    return q.size // (q.shape[0] * n_q)


def _sink_operand(sink, n_kv: int, kv_mul: int) -> list:
    """[] or [the sinks (n_q,) as (n_kv, R, 1)]: a padded head's is 0, and
    nothing reads it."""
    if sink is None:
        return []
    s = jnp.asarray(sink, jnp.float32).reshape(n_kv, kv_mul)
    return [jnp.pad(s, ((0, 0), (0, _group_rows(kv_mul) - kv_mul)))[
        ..., None]]


@functools.partial(jax.jit, static_argnames=("kv_mul", "interpret"))
def rows_decode_attention(q, k4, v4, layer, last, sink=None, *, kv_mul: int,
                          interpret: bool | None = None):
    """q (B, n_kv * kv_mul, head) over planes layer * B + b of k4 (rows,
    n_kv, S, hk >= head) / v4 (rows, n_kv, S, hv), positions 0 .. last[b];
    ``sink`` (n_q,) or None. Returns (B, n_q * hv)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, n_kv, S, hk = k4.shape
    hv = v4.shape[-1]
    B = q.shape[0]
    chunk = _chunk(S, n_kv, hk, k4.dtype.itemsize, hv)
    if chunk is None:
        raise ValueError(f"no chunking of S={S} fits VMEM at n_kv={n_kv}, "
                         f"hs={hk} / {hv} (gate with supports())")
    out = _call(functools.partial(_rows_kernel, chunk=chunk, batch=B,
                                  head_size=_head_size(q, n_kv * kv_mul)),
                ROWS_KERNEL, B, n_kv, kv_mul, hk, hv, chunk, k4.dtype,
                interpret, sink is not None)(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.broadcast_to(jnp.asarray(last, jnp.int32), (B,)),
        _stacked_queries(q, n_kv, kv_mul, hk),
        *_sink_operand(sink, n_kv, kv_mul), k4, v4)
    return out.reshape(B, -1)


def _pages_a_turn(page_size: int) -> int:
    """Pages landed side by side a loop turn: what makes the fold's tile."""
    return max(1, _TILE // page_size)


def _paged_kernel(pos_ref, table_ref, q3_ref, sink_ref, k_hbm, v_hbm, out_ref,
                  k_buf, v_buf, sems, *, page_size: int, group: int,
                  head_size: int):
    """grid=(B,): program b walks its live pages through the table,
    ``group`` a turn. k / v_hbm (P, n_kv, page_size, hk | hv); k / v_buf (2,
    n_kv, group * page_size, hk | hv). A turn's copies share a semaphore a slot
    and side (each wait takes its own copy's bytes off it)."""
    b = pl.program_id(0)
    last = jnp.minimum(pos_ref[b], table_ref.shape[1] * page_size - 1)
    last_page = last // page_size

    def copies(slot, i):
        out = []
        for g in range(group):
            page = table_ref[b, jnp.minimum(i * group + g, last_page)]
            at = pl.ds(g * page_size, page_size)
            out += [pltpu.make_async_copy(k_hbm.at[page],
                                          k_buf.at[slot, :, at],
                                          sems.at[slot, 0]),
                    pltpu.make_async_copy(v_hbm.at[page],
                                          v_buf.at[slot, :, at],
                                          sems.at[slot, 1])]
        return out

    _walk(last_page // group + 1, copies, last, q3_ref, sink_ref, k_buf,
          v_buf, out_ref, head_size)


def supports_paged(page_size: int, n_kv: int, head_size: int,
                   itemsize: int = 4, v_head_size: int = 0) -> bool:
    return (_whole_tiles(head_size, v_head_size) and page_size % 8 == 0
            and 2 * _pages_a_turn(page_size) * page_size * n_kv
            * (head_size + (v_head_size or head_size)) * itemsize
            <= _VMEM_BUDGET)


@functools.partial(jax.jit, static_argnames=("kv_mul", "interpret"))
def paged_decode_attention(q, k4, v4, pos, table, sink=None, *, kv_mul: int,
                           interpret: bool | None = None):
    """q (B, n_kv * kv_mul, head) over the pages ``table`` (B, max_pages)
    maps of the pool k4 (P, n_kv, page_size, hk >= head) / v4 (P, n_kv,
    page_size, hv), positions 0 .. pos[b]; ``sink`` (n_q,) or None. Returns
    (B, n_q * hv)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, n_kv, ps, hk = k4.shape
    B = q.shape[0]
    group = _pages_a_turn(ps)
    out = _call(functools.partial(_paged_kernel, page_size=ps, group=group,
                                  head_size=_head_size(q, n_kv * kv_mul)),
                PAGED_KERNEL, B, n_kv, kv_mul, hk, v4.shape[-1], group * ps,
                k4.dtype, interpret, sink is not None)(
        jnp.asarray(pos, jnp.int32).reshape(B), jnp.asarray(table, jnp.int32),
        _stacked_queries(q, n_kv, kv_mul, hk),
        *_sink_operand(sink, n_kv, kv_mul), k4, v4)
    return out.reshape(B, -1)
