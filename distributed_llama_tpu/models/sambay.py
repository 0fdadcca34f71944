"""The forward pass of a hybrid spec (``TransformerSpec.hybrid``: SambaY,
arXiv:2507.06607, as Phi-4-mini-flash-reasoning lays it out): Mamba layers
interleaved with differential attention over a window, ONE full-attention
layer whose K / V are the model's only growing cache, and then a
cross-decoder of Gated Memory Units and cross-attention layers that read
that one cache and the memory ``m`` of the last Mamba layer.
``models/reference_sambay.py`` states every layer in full; this module runs
the same function through the caches:

* ``conv`` / ``ssm``: a Mamba layer's last ``d_conv - 1`` inputs and its
  (d_state, d_inner) state, float32, fixed size (ops/mamba.py);
* ``wk`` / ``wv``: a window layer's ring of the last ``window`` positions'
  K / V (position p at slot p mod window; with no positional encoding the
  order of the slots means nothing to a softmax);
* ``k`` / ``v``: the full layer's K / V of every position: contiguous
  (``inference``, an admission's scratch sequence) or a page pool
  (``serve``), written by that one layer and read by it and by every
  cross-attention layer.

A sequence's cache (``init_cache(spec)``) is conv (M, d_conv - 1, d_inner),
ssm (M, d_state, d_inner), wk / wv (W, KV pairs, window, 2 head), k / v (1,
KV pairs, seq_len, 2 head); ``batch`` rows add an axis after the first; the
pool is k / v (1, pages, KV pairs, page_size, 2 head) beside ``slots`` rows
of the rest. K and V are held HEAD-MAJOR (the KV pair before the position:
ops/pallas_head_major_attention.py says why: ten heads second-minor would
be stored as sixteen). A row's first position finds its state and ring
empty whatever they hold.

DIFFERENTIAL ATTENTION THROUGH THE SOFTMAX KERNELS. Query heads (2j, 2j + 1)
= (q1, q2) of pair j, KV heads (2g, 2g + 1) = (k1, k2), v = (v1 | v2). With
q1' = (q1 | 0), q2' = (0 | q2) and k' = (k1 | k2), q1' . k' = q1 . k1 and
q2' . k' = q2 . k2: the two score maps are ordinary grouped-query attention
of ``n_heads`` heads of 2 head over ``n_kv_heads / 2`` KV heads of 2 head,
whose K and V rows are the projections as they come. So the cache is an
ordinary KV cache at head size 2 head (128 at the published widths, the
lane width the decode kernels want), every cached byte is read once, and
the decode kernels are plain grouped-query flash-decode kernels
(ops/pallas_head_major_attention.py; queries scaled by sqrt 2 for their
1 / sqrt(2 head)). What follows the two maps (a1 - lambda a2, the
sub-norm, (1 - lambda_init)) is elementwise here.

A PROMPT'S LINEAR PREFILL. Only the layers up to the full one, and its K / V
projection, keep something of a position; the full layer's query side and
the cross-decoder after it are read at the position whose logits are
wanted. A prefill chunk (``xdec=False``) therefore runs the self-decoder
alone and returns no logits: ``Engine.prefill`` and ``serve``'s admission
fill the caches for all but the prompt's last token, which then takes the
decode step like any other token: the cross-decoder runs at ONE position a
prompt, exactly (nothing later reads what was skipped).

Layers run kind by kind in the order of the list: a repeating unit of the
list ((mamba, swa) x 8, then (gmu, xattn) x 7 at the published depth) is one
``lax.scan`` over its repeats, each kind's weights a stack of its own
(``params[kind]``); ``m`` and the full layer's K / V ride in the carry.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..obs.spans import SCOPE_ATTN, SCOPE_EMBED, SCOPE_FFN, SCOPE_LOGITS
from ..ops import mamba as ssm_ops
from ..ops.linear import matmul, silu
from .kindscan import insert_sequence, merge_lead, run_layers  # noqa: F401
from .spec import TransformerSpec

HIGHEST = jax.lax.Precision.HIGHEST


class HybridCache(NamedTuple):
    conv: jax.Array   # (M, [B,] d_conv - 1, d_inner) f32
    ssm: jax.Array    # (M, [B,] d_state, d_inner) f32
    wk: jax.Array     # (W, [B,] KV pairs, window, 2 head)
    wv: jax.Array
    k: jax.Array      # (1, [B,] KV pairs, seq_len, 2 head), or the pool
    v: jax.Array      # (1, pages, KV pairs, page_size, 2 head)


def pair_shape(spec: TransformerSpec) -> tuple[int, int, int]:
    """(query heads, KV heads, head size) of the grouped-query attention
    the two score maps are (module docstring)."""
    return spec.n_heads, spec.n_kv_heads // 2, 2 * spec.head_size


def _zeros(spec: TransformerSpec, lead: tuple, kv: tuple, dtype):
    """A cache whose state and rings have ``lead`` row axes and whose K / V
    planes are ``kv`` (KV pairs and 2 head follow)."""
    hy = spec.hybrid
    _, n_kv, hs = pair_shape(spec)
    m, w = hy.count("mamba"), hy.count("swa")
    ring = (w, *lead, n_kv, hy.window, hs)
    z = jnp.zeros
    return HybridCache(
        z((m, *lead, hy.d_conv - 1, hy.d_inner), jnp.float32),
        z((m, *lead, hy.d_state, hy.d_inner), jnp.float32),
        z(ring, dtype), z(ring, dtype), z(kv, dtype), z(kv, dtype))


def init_cache(spec: TransformerSpec, batch: int | None = None,
               dtype=jnp.float32) -> HybridCache:
    """One sequence's cache, or ``batch`` rows' (contiguous K / V)."""
    lead = () if batch is None else (batch,)
    _, n_kv, hs = pair_shape(spec)
    return _zeros(spec, lead, (1, *lead, n_kv, spec.seq_len, hs), dtype)


def init_cache_paged(spec: TransformerSpec, slots: int, n_pages: int,
                     page_size: int, dtype=jnp.float32) -> HybridCache:
    """``slots`` rows of state and ring, and the full layer's page pool
    (page 0 is the scrap page, as in a KV pool)."""
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    _, n_kv, hs = pair_shape(spec)
    return _zeros(spec, (slots,), (1, n_pages, n_kv, page_size, hs), dtype)


def state_bytes(cache: HybridCache) -> tuple[int, int]:
    """(recurrent state, window rings) resident bytes."""
    return (int(cache.conv.nbytes + cache.ssm.nbytes),
            int(cache.wk.nbytes + cache.wv.nbytes))


# -- pieces of a layer -----------------------------------------------------------

def layernorm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * gain + bias


def _ffn(spec, lw, h):
    from .llama import _swiglu

    with jax.named_scope(SCOPE_FFN):
        return h + _swiglu(spec, lw, layernorm(h, lw["ln2_g"], lw["ln2_b"],
                                               spec.norm_eps))


def _f32_rows(x, w):
    """x (R, n) @ w (d, n)^T in float32 at highest precision (the small
    state-space projections are neither quantized nor taken in bf16)."""
    return jnp.einsum("rn,dn->rd", x, w, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _ssm_inputs(hy, lw, xs):
    """xs (R, d_inner) after the conv and its silu -> (delta, B, C)."""
    dbc = _f32_rows(xs, lw["x_proj"])
    dr, ds = hy.dt_rank, hy.d_state
    delta = jax.nn.softplus(_f32_rows(dbc[:, :dr], lw["dt_proj"])
                            + lw["dt_b"])
    return delta, dbc[:, dr:dr + ds], dbc[:, dr + ds:]


def lambda_init(layer) -> jax.Array:
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def padded_queries(spec, q):
    """q (R, dim) -> (R, n_heads * 2 head): [q1 | 0], [0 | q2] a pair,
    scaled so that the kernels' 1 / sqrt(2 head) leaves 1 / sqrt(head)."""
    hs = spec.head_size
    qp = q.reshape(q.shape[0], spec.n_heads // 2, 2, hs) * math.sqrt(2.0)
    zero = jnp.zeros_like(qp[:, :, 0])
    return jnp.stack([jnp.concatenate([qp[:, :, 0], zero], axis=-1),
                      jnp.concatenate([zero, qp[:, :, 1]], axis=-1)],
                     axis=2).reshape(q.shape[0], -1)


def diff_combine(spec, lw, layer, ao):
    """The two maps' outputs ao (R, n_heads * 2 head) -> (R, dim): a1 -
    lambda a2, the sub-norm and its gain, (1 - lambda_init)."""
    hs2 = 2 * spec.head_size
    a = ao.reshape(ao.shape[0], spec.n_heads // 2, 2, hs2)
    li = lambda_init(layer)
    lam = lw["lam"]
    lam_full = (jnp.exp(jnp.sum(lam[0] * lam[1]))
                - jnp.exp(jnp.sum(lam[2] * lam[3])) + li)
    a = a[:, :, 0] - lam_full * a[:, :, 1]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                          + spec.norm_eps)
    return ((1.0 - li) * a * lw["subln"]).reshape(ao.shape[0], -1)


def _split_qkv(spec, lw, u):
    d, kv = spec.dim, spec.kv_dim
    qkv = matmul(lw["wqkv"], u) + lw["bqkv"]
    return qkv[:, :d], qkv[:, d:d + kv], qkv[:, d + kv:]


def _as_rows(spec, x, dtype):
    """(R, kv_dim) -> (R, KV pairs, 1, 2 head) as the caches hold a row."""
    _, n_kv, hs = pair_shape(spec)
    return x.reshape(x.shape[0], n_kv, 1, hs).astype(dtype)


def _write_rows(plane, new, rows, cols):
    """new (B, n, 1, h) into plane (rows, n, S, h) at (rows[b], :, cols[b]):
    B in-place row writes, not a scatter (models/llama.py says why)."""
    for b in range(new.shape[0]):
        plane = jax.lax.dynamic_update_slice(
            plane, new[b:b + 1], (rows[b], 0, cols[b], 0))
    return plane


def _attend_rows(shape, q, k_all, v_all, idx, last, sink=None):
    """Row b's query over slots 0 .. last[b] of its plane idx * B + b of
    the contiguous (rows, n, S, h) caches, ``shape`` the attention's (query
    heads, KV heads, head size; K's lanes past it are zeros and V's last
    dim is ``v_all``'s), ``sink`` a
    score a query head or None (``attention_core``): the flash-decode
    kernel on the chip, a masked einsum elsewhere."""
    from ..ops import pallas_head_major_attention as hm
    from ..ops.pallas_attention import attn_kernel_mode
    from .llama import attention_core

    n_q, n_kv, hs = shape
    B, S = q.shape[0], k_all.shape[2]
    if attn_kernel_mode() == "pallas" and hm.supports(
            S, n_kv, k_all.shape[-1], k_all.dtype.itemsize, v_all.shape[-1]):
        return hm.rows_decode_attention(q, k_all, v_all, idx, last, sink,
                                        kv_mul=n_q // n_kv)
    k_c = jax.lax.dynamic_slice_in_dim(k_all, idx * B, B, 0)
    if k_c.shape[-1] != hs:     # a head held in whole lane tiles
        k_c = k_c[..., :hs]
    v_c = jax.lax.dynamic_slice_in_dim(v_all, idx * B, B, 0)
    mask = jnp.arange(S)[None, None, :] <= last[:, None, None]
    return attention_core(hs, n_q // n_kv, q.reshape(B, 1, n_q, hs),
                          jnp.swapaxes(k_c, 1, 2), jnp.swapaxes(v_c, 1, 2),
                          mask, sink).reshape(B, -1)


def _attend_pages(shape, page_size, q, k_all, v_all, pos_b, table,
                  sink=None):
    """Row b's query over positions 0 .. pos_b[b] of its pages in the pool
    (pages, n, page_size, h), ``shape`` and ``sink`` as in
    ``_attend_rows``: the paged kernel on the chip, a gather of the row's
    virtual plane elsewhere."""
    from ..ops import pallas_head_major_attention as hm
    from ..ops.pallas_attention import attn_kernel_mode
    from .llama import attention_core

    n_q, n_kv, hs = shape
    B = q.shape[0]
    if attn_kernel_mode() == "pallas" and hm.supports_paged(
            page_size, n_kv, k_all.shape[-1], k_all.dtype.itemsize,
            v_all.shape[-1]):
        return hm.paged_decode_attention(q, k_all, v_all, pos_b, table, sink,
                                         kv_mul=n_q // n_kv)
    s_virt = table.shape[1] * page_size

    def plane(pool):
        pages = jnp.take(pool, table.reshape(-1), axis=0).reshape(
            B, table.shape[1], n_kv, page_size, pool.shape[-1])
        return jnp.swapaxes(pages, 2, 3).reshape(B, s_virt, n_kv,
                                                 pool.shape[-1])

    k_c, v_c = plane(k_all), plane(v_all)
    if k_c.shape[-1] != hs:
        k_c = k_c[..., :hs]
    mask = jnp.arange(s_virt)[None, None, :] <= pos_b[:, None, None]
    return attention_core(hs, n_q // n_kv, q.reshape(B, 1, n_q, hs),
                          k_c, v_c, mask, sink).reshape(B, -1)


class _Carry(NamedTuple):
    x: jax.Array
    m: jax.Array        # the memory layer's scan output (R, d_inner)
    conv: jax.Array     # (M * B, d_conv - 1, d_inner)
    ssm: jax.Array      # (M * B, d_state, d_inner)
    wk: jax.Array       # (W * B, n, window, h)
    wv: jax.Array
    k: jax.Array        # (B, n, S, h) or the pool (P, n, page_size, h)
    v: jax.Array
    low: jax.Array      # the step's health reading (forward_batch_sambay)


def _run(spec, params, carry: _Carry, layer_fn, upto: int | None = None):
    """Every layer (or those before layer ``upto``) through ``layer_fn(kind,
    lw, carry, layer, idx)``, a repeating unit of the list a scan
    (``models/kindscan.py``: a layer's one stack is its kind's)."""
    return run_layers(
        [(k,) for k in spec.hybrid.kinds], params.__getitem__, carry,
        lambda sig, lw, c, layer, idx: layer_fn(sig[0], lw, c, layer,
                                                idx[sig[0]]), upto)


def _logits(spec, params, x):
    with jax.named_scope(SCOPE_LOGITS):
        x = layernorm(x, params["rms_final"], params["rms_final_b"],
                      spec.norm_eps)
        return matmul(params["wcls"], x)


# -- the decode step ---------------------------------------------------------------

def forward_batch_sambay(spec: TransformerSpec, params: dict[str, Any],
                         cache: HybridCache, tokens: jax.Array,
                         pos_vec: jax.Array, table: jax.Array | None = None,
                         active: jax.Array | None = None, *,
                         page_size: int = 0, health: bool = False):
    """One token for each of B rows at its own position: against the
    contiguous batched cache (``init_cache(spec, batch)``), or with
    ``table`` (B, max_pages) against the page pool. A row at position 0
    finds its state and ring empty; a row whose ``active`` ((B,), nonzero =
    takes part; default all) is 0 rides the step and leaves its state as it
    is (its ring and page writes land where its own re-run, or nobody,
    reads them). ``health`` adds, as a (1,) array, the smallest over the
    Mamba layers and the active rows of the mean over channels of the
    SLOWEST state's decay exp(delta A_1) in this step: near 0, a row's whole
    state is forgotten in one token; 1, nothing is."""
    hy = spec.hybrid
    B = tokens.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    live = jnp.ones((B,), bool) if active is None else active != 0
    fresh = pos_b == 0
    rows = jnp.arange(B)
    W = hy.window
    paged = table is not None
    x = params["tok_embedding"][tokens].astype(jnp.float32)
    dt = cache.k.dtype
    shape = pair_shape(spec)

    def layer_fn(kind, lw, c: _Carry, layer, idx):
        u = layernorm(c.x, lw["ln1_g"], lw["ln1_b"], spec.norm_eps)
        with jax.named_scope(SCOPE_ATTN):
            if kind == "mamba":
                xz = matmul(lw["in_proj"], u)
                xs, z = xz[:, :hy.d_inner], xz[:, hy.d_inner:]
                old = jax.lax.dynamic_slice_in_dim(c.conv, idx * B, B, 0)
                old = jnp.where((fresh & live)[:, None, None], 0.0, old)
                win = jnp.concatenate([old, xs[:, None]], axis=1)
                c = c._replace(conv=jax.lax.dynamic_update_slice_in_dim(
                    c.conv, jnp.where(live[:, None, None], win[:, 1:], old),
                    idx * B, 0))
                xs = silu(jnp.sum(win * lw["conv_w"], axis=1)
                          + lw["conv_b"])
                delta, b_t, c_t = _ssm_inputs(hy, lw, xs)
                y, ssm = ssm_ops.scan_decode(idx, c.ssm, lw["a_log"], xs,
                                             delta, b_t, c_t, fresh, live)
                y = y + lw["d_skip"] * xs
                decay = jnp.min(jnp.where(live, jnp.mean(jnp.exp(
                    -delta * jnp.exp(jnp.min(lw["a_log"]))), axis=-1), 1.0))
                c = c._replace(
                    ssm=ssm, low=jnp.minimum(c.low, decay),
                    m=jnp.where(layer == hy.memory_layer, y, c.m)
                    if hy.memory_layer is not None else c.m)
                mix = matmul(lw["out_proj"], y * silu(z))
            elif kind == "gmu":
                mix = matmul(lw["out_proj"],
                             silu(matmul(lw["in_proj"], u)) * c.m)
            else:
                if kind == "xattn":
                    q = matmul(lw["wq"], u) + lw["bq"]
                else:
                    q, k, v = _split_qkv(spec, lw, u)
                    k, v = _as_rows(spec, k, dt), _as_rows(spec, v, dt)
                q = padded_queries(spec, q)
                if kind == "swa":
                    wk = _write_rows(c.wk, k, idx * B + rows, pos_b % W)
                    wv = _write_rows(c.wv, v, idx * B + rows, pos_b % W)
                    c = c._replace(wk=wk, wv=wv)
                    ao = _attend_rows(shape, q, wk, wv, idx,
                                      jnp.minimum(pos_b, W - 1))
                elif paged:
                    if kind == "full":
                        page = jnp.take_along_axis(
                            table, (pos_b // page_size)[:, None],
                            axis=1)[:, 0]
                        c = c._replace(
                            k=_write_rows(c.k, k, page, pos_b % page_size),
                            v=_write_rows(c.v, v, page, pos_b % page_size))
                    ao = _attend_pages(shape, page_size, q, c.k, c.v, pos_b,
                                       table)
                else:
                    if kind == "full":
                        c = c._replace(k=_write_rows(c.k, k, rows, pos_b),
                                       v=_write_rows(c.v, v, rows, pos_b))
                    ao = _attend_rows(shape, q, c.k, c.v, 0, pos_b)
                mix = matmul(lw["wo"], diff_combine(spec, lw, layer, ao)) \
                    + lw["bo"]
        return c._replace(x=_ffn(spec, lw, c.x + mix))

    carry = _Carry(x, jnp.zeros((B, hy.d_inner), jnp.float32),
                   merge_lead(cache.conv, 2), merge_lead(cache.ssm, 2),
                   merge_lead(cache.wk, 2), merge_lead(cache.wv, 2),
                   merge_lead(cache.k, 2), merge_lead(cache.v, 2),
                   jnp.float32(1.0))
    carry = _run(spec, params, carry, layer_fn)
    logits = _logits(spec, params, carry.x)
    out = HybridCache(*(new.reshape(old.shape) for new, old in zip(
        carry[2:8], cache)))
    return (logits, out, carry.low[None]) if health else (logits, out)


# -- a chunk of one sequence ---------------------------------------------------------

def _ring_positions(W: int, pos):
    """The position each ring slot holds before a chunk that starts at
    ``pos`` (negative: none yet)."""
    s = jnp.arange(W)
    return pos - 1 - (pos - 1 - s) % W


def ring_plan(W: int, pos, n_valid, T: int):
    """What a chunk of T positions from ``pos`` (the first ``n_valid`` the
    sequence's) reads of a window layer and leaves in its ring: the (T, W +
    T) mask over [ring as it stands | the chunk's own keys], and (from_chunk
    (1, W, 1), take (W,)): slot s takes the chunk's row ``take[s]``, the
    newest valid position congruent to s, where ``from_chunk`` says the
    chunk has one."""
    positions = pos + jnp.arange(T)
    ring_pos = _ring_positions(W, pos)
    see_ring = (ring_pos[None, :] >= 0) & (
        positions[:, None] - ring_pos[None, :] < W)
    t = jnp.arange(T)
    see_own = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < W)
    last = pos + n_valid - 1
    newest = last - (last - jnp.arange(W)) % W
    return (jnp.concatenate([see_ring, see_own], axis=1),
            (newest >= pos)[None, :, None], jnp.clip(newest - pos, 0, T - 1))


def forward_sambay(spec: TransformerSpec, params: dict[str, Any],
                   cache: HybridCache, tokens: jax.Array, pos: jax.Array,
                   n_valid=None, *, xdec: bool = True,
                   health: bool = False):
    """T tokens of ONE sequence at positions pos .. pos + T - 1 against its
    cache (``init_cache(spec)``). T = 1 is the decode step at one row. Of a
    chunk's positions the first ``n_valid`` (default all) are the
    sequence's and the rest padding that reaches neither a state, a ring
    nor the K / V. ``xdec=False`` runs the layers up to the full one's K / V
    projection alone and returns logits of shape (0, vocab): what a prefill
    needs (module docstring). ``pos == 0`` finds the state empty."""
    t_len = tokens.shape[0]
    batched = HybridCache(*(a[:, None] for a in cache))
    if t_len == 1:
        logits, out, *low = forward_batch_sambay(
            spec, params, batched, tokens, jnp.reshape(pos, (1,)),
            health=health)
        return (logits, HybridCache(*(a[:, 0] for a in out)), *low)
    from .llama import attention_core, causal_cache_mask

    hy = spec.hybrid
    pad = -t_len % ssm_ops.SUBLANES
    if pad:
        tokens = jnp.concatenate([tokens, jnp.zeros((pad,), tokens.dtype)])
    T = t_len + pad
    n_valid = t_len if n_valid is None else jnp.minimum(n_valid, t_len)
    pos = jnp.asarray(pos, jnp.int32)
    fresh = pos == 0
    positions = pos + jnp.arange(T)
    valid = jnp.arange(T) < n_valid
    W, S = hy.window, spec.seq_len
    n_q, n_kv, hs = pair_shape(spec)
    dt = cache.k.dtype
    with jax.named_scope(SCOPE_EMBED):
        x = params["tok_embedding"][tokens].astype(jnp.float32)
    # a window layer's keys (the ring as it stands, then the chunk's own)
    # and the ring after it
    win_mask, from_chunk, take = ring_plan(W, pos, n_valid, T)
    kv_at = jnp.where(valid, positions, S)     # padding is dropped
    heads_first = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731

    def layer_fn(kind, lw, c: _Carry, layer, idx):
        u = layernorm(c.x, lw["ln1_g"], lw["ln1_b"], spec.norm_eps)
        with jax.named_scope(SCOPE_ATTN):
            if kind == "mamba":
                xz = matmul(lw["in_proj"], u)
                xs, z = xz[:, :hy.d_inner], xz[:, hy.d_inner:]
                old = jax.lax.dynamic_index_in_dim(c.conv, idx, 0, False)
                old = jnp.where(fresh, 0.0, old)
                run = jnp.concatenate([old, xs], axis=0)
                c = c._replace(conv=jax.lax.dynamic_update_slice_in_dim(
                    c.conv, jax.lax.dynamic_slice_in_dim(
                        run, n_valid, hy.d_conv - 1, 0)[None], idx, 0))
                xs = silu(sum(run[j:j + T] * lw["conv_w"][j]
                              for j in range(hy.d_conv)) + lw["conv_b"])
                delta, b_t, c_t = _ssm_inputs(hy, lw, xs)
                y, ssm = ssm_ops.scan_chunk(idx, c.ssm, lw["a_log"], xs,
                                            delta, b_t, c_t, fresh, n_valid)
                y = y + lw["d_skip"] * xs
                c = c._replace(
                    ssm=ssm, m=jnp.where(layer == hy.memory_layer, y, c.m)
                    if hy.memory_layer is not None else c.m)
                mix = matmul(lw["out_proj"], y * silu(z))
            elif kind == "gmu":
                mix = matmul(lw["out_proj"],
                             silu(matmul(lw["in_proj"], u)) * c.m)
            else:
                if kind == "xattn":
                    q = matmul(lw["wq"], u) + lw["bq"]
                else:
                    q, k, v = _split_qkv(spec, lw, u)
                    k = k.reshape(T, n_kv, hs).astype(dt)
                    v = v.reshape(T, n_kv, hs).astype(dt)
                q = padded_queries(spec, q).reshape(T, n_q, hs)
                if kind == "swa":
                    wk = jax.lax.dynamic_index_in_dim(c.wk, idx, 0, False)
                    wv = jax.lax.dynamic_index_in_dim(c.wv, idx, 0, False)
                    ao = attention_core(
                        hs, n_q // n_kv, q,
                        jnp.concatenate([heads_first(wk), k]),
                        jnp.concatenate([heads_first(wv), v]), win_mask)
                    c = c._replace(
                        wk=jax.lax.dynamic_update_slice_in_dim(
                            c.wk, jnp.where(from_chunk, heads_first(k[take]),
                                            wk)[None], idx, 0),
                        wv=jax.lax.dynamic_update_slice_in_dim(
                            c.wv, jnp.where(from_chunk, heads_first(v[take]),
                                            wv)[None], idx, 0))
                else:
                    if kind == "full":
                        c = c._replace(
                            k=c.k.at[0, :, kv_at].set(k, mode="drop"),
                            v=c.v.at[0, :, kv_at].set(v, mode="drop"))
                        if not xdec:   # the K / V projection was all of it
                            return c
                    ao = attention_core(hs, n_q // n_kv, q,
                                        heads_first(c.k[0]),
                                        heads_first(c.v[0]),
                                        causal_cache_mask(S, pos, T))
                mix = matmul(lw["wo"], diff_combine(spec, lw, layer, ao)) \
                    + lw["bo"]
        return c._replace(x=_ffn(spec, lw, c.x + mix))

    carry = _Carry(x, jnp.zeros((T, hy.d_inner), jnp.float32), cache.conv,
                   cache.ssm, cache.wk, cache.wv, cache.k, cache.v,
                   jnp.float32(1.0))
    carry = _run(spec, params, carry, layer_fn,
                 upto=None if xdec else hy.full_layer + 1)
    out = HybridCache(*carry[2:8])
    if xdec:
        logits = _logits(spec, params, carry.x[:t_len])
    else:
        logits = jnp.zeros((0, spec.vocab_size), jnp.float32)
    return (logits, out, carry.low[None]) if health else (logits, out)


# the names ``models/llama.slot_model`` gives both slot-and-pages forwards
forward_batch = forward_batch_sambay
forward_chunk = forward_sambay
