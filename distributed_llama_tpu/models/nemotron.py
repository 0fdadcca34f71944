"""The forward pass of an ssd spec (``TransformerSpec.ssd``: Nemotron-H's
layout as NVIDIA-Nemotron-3-Nano-30B-A3B lays it out): a layer is ONE mixer
under one pre-norm and one residual add, ``x <- x + mixer(RMSNorm(x))``, of
three kinds in a list that need not be periodic:

* "mamba2": a Mamba-2 (SSD) mixer (``ops/mamba2.py``): projections in,
  a causal depthwise convolution over [x | B | C], a state (heads, head_dim,
  d_state) with a scalar decay a head, a gate and a grouped RMSNorm,
  projection out;
* "full": grouped-query softmax attention, causal, with NO positional
  encoding, K / V of its own (``models/laguna.py``'s full kind without its
  RoPE, gate and sink: the same head-major caches and kernels);
* "experts": routed experts and one shared expert (``ops/pallas_moe.
  moe_ffn`` and ``models/llama._swiglu``, as every expert spec), which a
  spec whose activation is not gated computes as ``w2(relu(w1 u)^2)``.

``models/reference_nemotron.py`` states every layer in full; this module
runs the same function through the caches. A sequence's cache
(``init_cache(spec)``) is conv (M, d_conv - 1, conv_dim): a Mamba-2 layer's
last inputs of the convolution, before its activation; ssm (M, heads,
head_dim, d_state); both float32 and of fixed size; and k / v (F, KV heads,
seq_len, head), EACH attention layer's K / V of every position, head-major:
contiguous (``inference``, an admission's scratch sequence) or a page pool a
layer (``serve``: one page table a sequence, the same page id in every
attention layer's pool). M and F count the Mamba-2 and attention layers;
``batch`` rows add an axis after the first. A row's first position finds its
state and conv rows empty whatever they hold.

Weights are a stack a kind (``params["mamba2"]`` / ``["full"]`` /
``["experts"]``); layers run in the order of the list, a repeating unit of it
one ``lax.scan`` over its repeats (``models/kindscan.py``, whose layer
signature here is ONE stack). Why a module of its own and not a wider
``models/laguna.py``: that forward's layer is an attention mixer AND an FFN
with rings, gates and a RoPE a kind in its carry, none of which a layer here
has, and its two-stack signature is what ``_tail`` is built on; what the two
share (the head-major caches, ``_attend_live``, the held-head helpers, the
classifier) is imported from it.

A prompt's chunks (``forward_chunk``) fill the state (the SSD chunk form,
XLA matrix products), the conv rows and every attention layer's K / V for
all but the prompt's last token, which takes the decode step like any other
token.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..obs.spans import (SCOPE_ATTN, SCOPE_EMBED, SCOPE_FFN, SCOPE_SSD_CONV,
                         SCOPE_SSD_GATE_NORM, SCOPE_SSD_PROJ, SCOPE_SSD_SCAN)
from ..ops import mamba2 as ssd_ops
from ..ops.linear import matmul, rmsnorm, silu
from .kindscan import insert_sequence, merge_lead, run_layers  # noqa: F401
from .laguna import _attend_live, _counts0, _held, _logits, _values
from .latent import chunk_attn_block
from .sambay import _attend_pages, _attend_rows, _f32_rows, _write_rows
from .spec import TransformerSpec


class SsdCache(NamedTuple):
    conv: jax.Array   # (M, [B,] d_conv - 1, conv_dim) f32
    ssm: jax.Array    # (M, [B,] heads, head_dim, d_state) f32
    k: jax.Array      # (F, [B,] KV heads, seq_len, head), or the pools
    v: jax.Array      # (F, pages, KV heads, page_size, head)


def _zeros(spec: TransformerSpec, lead: tuple, kv_lead: tuple, kv: int,
           dtype) -> SsdCache:
    sd = spec.ssd
    m, f = sd.count("mamba2"), sd.count("full")
    plane = (f, *kv_lead, spec.n_kv_heads, kv, spec.head_size)
    return SsdCache(
        jnp.zeros((m, *lead, sd.d_conv - 1, sd.conv_dim), jnp.float32),
        jnp.zeros((m, *lead, sd.heads, sd.head_dim, sd.d_state),
                  jnp.float32),
        jnp.zeros(plane, dtype), jnp.zeros(plane, dtype))


def init_cache(spec: TransformerSpec, batch: int | None = None,
               dtype=jnp.float32) -> SsdCache:
    """One sequence's cache, or ``batch`` rows' (contiguous K / V)."""
    lead = () if batch is None else (batch,)
    return _zeros(spec, lead, lead, spec.seq_len, dtype)


def init_cache_paged(spec: TransformerSpec, slots: int, n_pages: int,
                     page_size: int, dtype=jnp.float32) -> SsdCache:
    """``slots`` rows of state, and a page pool an attention layer (page 0
    of each is its scrap page, as in a KV pool)."""
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    return _zeros(spec, (slots,), (n_pages,), page_size, dtype)


def state_bytes(cache: SsdCache) -> tuple[int, int]:
    """(recurrent state: the Mamba-2 states and conv rows, window rings:
    none) resident bytes."""
    return int(cache.conv.nbytes + cache.ssm.nbytes), 0


# -- pieces of a layer -----------------------------------------------------------

def _run(spec, params, carry, layer_fn):
    """Every layer through ``layer_fn(kind, lw, carry, idx)``, ``idx`` the
    layer's place among the layers of its kind."""
    return run_layers(
        [(k,) for k in spec.ssd.kinds], params.__getitem__, carry,
        lambda sig, lw, c, layer, idx: layer_fn(sig[0], lw, c, idx[sig[0]]))


def _qkv(spec, lw, u):
    """u (R, dim) normed -> q (R, heads * head), k, v (R, KV heads, head)."""
    hs, n_kv = spec.head_size, spec.n_kv_heads
    if "wqkv" in lw:    # load-time fusion (ops/linear)
        qkv = matmul(lw["wqkv"], u)
        q, k, v = jnp.split(qkv, [spec.n_heads * hs,
                                  (spec.n_heads + n_kv) * hs], axis=-1)
    else:
        q, k, v = (matmul(lw[n], u) for n in ("wq", "wk", "wv"))
    r = u.shape[0]
    return q, k.reshape(r, n_kv, hs), v.reshape(r, n_kv, hs)


def _ssd_inputs(spec, lw, u):
    """u (R, dim) normed -> (z (R, d_inner), xBC (R, conv_dim) before the
    convolution, dt (R, heads) before its bias and softplus)."""
    with jax.named_scope(SCOPE_SSD_PROJ):
        zx = matmul(lw["in_zx"], u)
        di = spec.ssd.d_inner
        return zx[:, :di], zx[:, di:], _f32_rows(u, lw["in_dt"])


def _split_xbc(spec, xbc):
    """xBC (R, conv_dim) after the convolution -> x (R, H, P), B, C (R, G,
    N)."""
    sd = spec.ssd
    r, di, gn = xbc.shape[0], sd.d_inner, sd.groups * sd.d_state
    return (xbc[:, :di].reshape(r, sd.heads, sd.head_dim),
            xbc[:, di:di + gn].reshape(r, sd.groups, sd.d_state),
            xbc[:, di + gn:].reshape(r, sd.groups, sd.d_state))


def _gate_norm_out(spec, lw, y, z):
    """y (R, d_inner) times silu(z), the RMSNorm in groups of d_inner /
    groups with its gain (gate, THEN norm), and the projection out."""
    sd = spec.ssd
    with jax.named_scope(SCOPE_SSD_GATE_NORM):
        g = (y * silu(z)).reshape(y.shape[0], sd.groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + spec.norm_eps)
        g = g.reshape(y.shape) * lw["norm_g"]
    with jax.named_scope(SCOPE_SSD_PROJ):
        return matmul(lw["out_proj"], _maybe_q80(spec, g))


def _maybe_q80(spec, x):
    from .llama import _maybe_q80 as q80

    return q80(spec, x)


def _experts(spec, lw, u, counts, idx):
    """The expert mixer of the normed rows u: the routed experts held here
    plus the shared one; an (E,) routed-rows count goes to row ``idx`` of
    ``counts`` (L_e, E)."""
    from ..ops.pallas_moe import moe_ffn
    from .llama import _swiglu

    with jax.named_scope(SCOPE_FFN):
        u = _maybe_q80(spec, u)
        y, c = moe_ffn(spec, lw, u)
        if "sh_w2" in lw:
            y = y + _swiglu(spec, lw, u, "sh_")
    if counts is not None:
        counts = jax.lax.dynamic_update_slice(counts, c[None], (idx, 0))
    return y, counts


class _Carry(NamedTuple):
    x: jax.Array
    conv: jax.Array     # (M * B, d_conv - 1, conv_dim)
    ssm: jax.Array      # (M * B, H, P, N)
    k: jax.Array        # (F * B, n, S, h) or the pools (F * pages, n, page, h)
    v: jax.Array
    low: jax.Array      # the step's health reading (forward_batch)
    counts: Any         # (L_e, E) routed-rows counts, or None


# -- the decode step ---------------------------------------------------------------

def forward_batch(spec: TransformerSpec, params: dict[str, Any],
                  cache: SsdCache, tokens: jax.Array, pos_vec: jax.Array,
                  table: jax.Array | None = None,
                  active: jax.Array | None = None, *, page_size: int = 0,
                  health: bool = False, moe_counts: bool = False):
    """One token for each of B rows at its own position: against the
    contiguous batched cache (``init_cache(spec, batch)``), or with
    ``table`` (B, max_pages) against the page pools. A row at position 0
    finds its state and conv rows empty; a row whose ``active`` ((B,),
    nonzero = takes part; default all) is 0 rides the step and leaves its
    state as it is (its page writes land where its own re-run, or nobody,
    reads them). ``health`` adds, as a (1,) array, the smallest over the
    Mamba-2 layers and the active rows of the mean over heads of the decay
    exp(dt A) in this step: near 0, a row's whole state is forgotten in one
    token; 1, nothing is. ``moe_counts`` adds the (L_e, E) int32 count of
    rows routed to each expert. Returns (logits, cache[, health][, counts])."""
    sd = spec.ssd
    B = tokens.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    live = jnp.ones((B,), bool) if active is None else active != 0
    fresh = pos_b == 0
    rows = jnp.arange(B)
    paged = table is not None
    n_pool = cache.k.shape[1]
    x = params["tok_embedding"][tokens].astype(jnp.float32)
    dt_kv = cache.k.dtype
    shape = (spec.n_heads, spec.n_kv_heads, spec.head_size)

    def layer_fn(kind, lw, c: _Carry, idx):
        u = rmsnorm(c.x, lw["rms_att"], spec.norm_eps)
        if kind == "experts":
            y, counts = _experts(spec, lw, u, c.counts, idx)
            return c._replace(x=c.x + y, counts=counts)
        with jax.named_scope(SCOPE_ATTN):
            if kind == "mamba2":
                z, xbc, dt = _ssd_inputs(spec, lw, u)
                with jax.named_scope(SCOPE_SSD_CONV):
                    old = jax.lax.dynamic_slice_in_dim(c.conv, idx * B, B, 0)
                    old = jnp.where((fresh & live)[:, None, None], 0.0, old)
                    win = jnp.concatenate([old, xbc[:, None]], axis=1)
                    c = c._replace(conv=jax.lax.dynamic_update_slice_in_dim(
                        c.conv, jnp.where(live[:, None, None], win[:, 1:],
                                          old), idx * B, 0))
                    xbc = silu(jnp.sum(win * lw["conv_w"], axis=1)
                               + lw["conv_b"])
                with jax.named_scope(SCOPE_SSD_SCAN):
                    xh, b_t, c_t = _split_xbc(spec, xbc)
                    dt = jax.nn.softplus(dt + lw["dt_bias"])
                    y, ssm = ssd_ops.scan_decode(idx, c.ssm, lw["a_log"], xh,
                                                 dt, b_t, c_t, fresh, live)
                    y = y + lw["d_skip"][:, None] * xh
                    decay = jnp.min(jnp.where(live, jnp.mean(jnp.exp(
                        -dt * jnp.exp(lw["a_log"])), axis=-1), 1.0))
                c = c._replace(ssm=ssm, low=jnp.minimum(c.low, decay))
                mix = _gate_norm_out(spec, lw, y.reshape(B, sd.d_inner), z)
            else:
                q, k, v = _qkv(spec, lw, u)
                k = _held(k[:, :, None], dt_kv)
                v = _held(v[:, :, None], dt_kv)
                if paged:
                    own = table + idx * n_pool      # this layer's pool
                    page = jnp.take_along_axis(
                        own, (pos_b // page_size)[:, None], axis=1)[:, 0]
                    c = c._replace(
                        k=_write_rows(c.k, k, page, pos_b % page_size),
                        v=_write_rows(c.v, v, page, pos_b % page_size))
                    ao = _attend_pages(shape, page_size, q, c.k, c.v, pos_b,
                                       own)
                else:
                    c = c._replace(
                        k=_write_rows(c.k, k, idx * B + rows, pos_b),
                        v=_write_rows(c.v, v, idx * B + rows, pos_b))
                    ao = _attend_rows(shape, q, c.k, c.v, idx, pos_b)
                mix = matmul(lw["wo"], _maybe_q80(spec, ao))
        return c._replace(x=c.x + mix)

    carry = _Carry(x, merge_lead(cache.conv, 2), merge_lead(cache.ssm, 2),
                   merge_lead(cache.k, 2), merge_lead(cache.v, 2),
                   jnp.float32(1.0), _counts0(spec, moe_counts))
    carry = _run(spec, params, carry, layer_fn)
    logits = _logits(spec, params, carry.x)
    out = SsdCache(*(new.reshape(old.shape) for new, old in zip(
        carry[1:5], cache)))
    more = [carry.low[None]] if health else []
    if carry.counts is not None:
        more.append(carry.counts)
    return (logits, out, *more)


# -- a chunk of one sequence ---------------------------------------------------------

def forward_chunk(spec: TransformerSpec, params: dict[str, Any],
                  cache: SsdCache, tokens: jax.Array, pos: jax.Array,
                  n_valid=None, *, xdec: bool = True, health: bool = False,
                  moe_counts: bool = False):
    """T tokens of ONE sequence at positions pos .. pos + T - 1 against its
    cache (``init_cache(spec)``). T = 1 is the decode step at one row. Of a
    chunk's positions the first ``n_valid`` (default all) are the
    sequence's and the rest padding that reaches neither a state, the conv
    rows nor the K / V. ``pos == 0`` finds the state empty. ``xdec=False``
    (the name a hybrid spec's chunk gave it: what a prefill needs) leaves
    the classifier out and returns logits of shape (0, vocab). Results as
    ``forward_batch``."""
    t_len = tokens.shape[0]
    if t_len == 1:
        batched = SsdCache(*(a[:, None] for a in cache))
        logits, out, *more = forward_batch(
            spec, params, batched, tokens, jnp.reshape(pos, (1,)),
            health=health, moe_counts=moe_counts)
        return (logits, SsdCache(*(a[:, 0] for a in out)), *more)
    from .llama import attention_core, causal_cache_mask

    sd = spec.ssd
    T, S, hs = t_len, spec.seq_len, spec.head_size
    n_valid = t_len if n_valid is None else jnp.minimum(n_valid, t_len)
    pos = jnp.asarray(pos, jnp.int32)
    fresh = pos == 0
    positions = pos + jnp.arange(T)
    valid = jnp.arange(T) < n_valid
    kv_mul = spec.n_heads // spec.n_kv_heads
    dt_kv = cache.k.dtype
    block = chunk_attn_block(S, T)
    with jax.named_scope(SCOPE_EMBED):
        x = params["tok_embedding"][tokens].astype(jnp.float32)
    kv_at = jnp.where(valid, positions, S)     # padding is dropped
    heads_first = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731

    def layer_fn(kind, lw, c: _Carry, idx):
        u = rmsnorm(c.x, lw["rms_att"], spec.norm_eps)
        if kind == "experts":
            y, counts = _experts(spec, lw, u, c.counts, idx)
            return c._replace(x=c.x + y, counts=counts)
        with jax.named_scope(SCOPE_ATTN):
            if kind == "mamba2":
                z, xbc, dt = _ssd_inputs(spec, lw, u)
                with jax.named_scope(SCOPE_SSD_CONV):
                    old = jax.lax.dynamic_index_in_dim(c.conv, idx, 0, False)
                    run = jnp.concatenate([jnp.where(fresh, 0.0, old), xbc])
                    c = c._replace(conv=jax.lax.dynamic_update_slice_in_dim(
                        c.conv, jax.lax.dynamic_slice_in_dim(
                            run, n_valid, sd.d_conv - 1, 0)[None], idx, 0))
                    xbc = silu(sum(run[j:j + T] * lw["conv_w"][j]
                                   for j in range(sd.d_conv)) + lw["conv_b"])
                with jax.named_scope(SCOPE_SSD_SCAN):
                    xh, b_t, c_t = _split_xbc(spec, xbc)
                    dt = jnp.where(valid[:, None], jax.nn.softplus(
                        dt + lw["dt_bias"]), 0.0)
                    h_prev = jax.lax.dynamic_index_in_dim(c.ssm, idx, 0,
                                                          False)
                    y, h_next = ssd_ops.ssd_chunk(
                        jnp.where(fresh, 0.0, h_prev), lw["a_log"], xh, dt,
                        b_t, c_t, sd.chunk)
                    y = y + lw["d_skip"][:, None] * xh
                c = c._replace(ssm=jax.lax.dynamic_update_slice_in_dim(
                    c.ssm, h_next[None], idx, 0))
                mix = _gate_norm_out(spec, lw, y.reshape(T, sd.d_inner), z)
            else:
                q, k, v = _qkv(spec, lw, u)
                q = q.reshape(T, spec.n_heads, hs)
                k, v = _held(k, dt_kv), _held(v, dt_kv)
                c = c._replace(
                    k=c.k.at[idx, :, kv_at].set(k, mode="drop"),
                    v=c.v.at[idx, :, kv_at].set(v, mode="drop"))
                k_p = jax.lax.dynamic_index_in_dim(c.k, idx, 0, False)
                v_p = jax.lax.dynamic_index_in_dim(c.v, idx, 0, False)
                if block is None:
                    ao = attention_core(hs, kv_mul, q,
                                        _values(heads_first(k_p), hs),
                                        _values(heads_first(v_p), hs),
                                        causal_cache_mask(S, pos, T))
                else:
                    ao = _attend_live(kv_mul, hs, q, k_p, v_p, pos, block)
                mix = matmul(lw["wo"], _maybe_q80(spec, ao.reshape(T, -1)))
        return c._replace(x=c.x + mix)

    carry = _Carry(x, cache.conv, cache.ssm, cache.k, cache.v,
                   jnp.float32(1.0), _counts0(spec, moe_counts))
    carry = _run(spec, params, carry, layer_fn)
    out = SsdCache(*carry[1:5])
    logits = (_logits(spec, params, carry.x) if xdec
              else jnp.zeros((0, spec.vocab_size), jnp.float32))
    more = [carry.low[None]] if health else []
    if carry.counts is not None:
        more.append(carry.counts)
    return (logits, out, *more)
