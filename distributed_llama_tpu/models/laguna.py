"""The forward pass of a mixer-kinds spec (``TransformerSpec.mixers``:
Laguna-XS.2's layout and MiMo-V2-Flash's): grouped-query softmax attention
whose KIND is a layer's ("full": causal over every position, K / V of its
own; "sliding": the last ``window`` positions), each kind with a head count,
a KV head count and a RoPE of its own, V heads that may be narrower than K
heads, a learned softmax sink a query head where the kind has one
(``lw["sink"]``: one more column, no value), the output scaled by
``value_scale``, and a per-head sigmoid gate on it, around the FFN that
``spec.layout`` says (a leading dense SwiGLU, then routed experts with a
shared one: ``models/llama._post_attention`` and ``ops/pallas_moe.moe_ffn``,
as every expert spec). ``models/reference_laguna.py`` states every layer in
full; this module runs the same function through the caches:

* ``wk`` / ``wv``: a sliding layer's ring of the last ``window`` positions'
  K / V (position p at slot p mod window; K is rotated before it is
  written, so the order of the slots means nothing to a softmax);
* ``k`` / ``v``: EACH full layer's K / V of every position: contiguous
  (``inference``, an admission's scratch sequence) or a page pool a layer
  (``serve``: one page table a sequence, the same page id in every full
  layer's pool).

A sequence's cache (``init_cache(spec)``) is wk / wv (W, KV heads, window,
head), k / v (F, KV heads, seq_len, head), W and F the counts of sliding and
full layers, KV heads the KIND's and head K's or V's size
(``spec.kv_shape``); ``batch`` rows add an axis after the first; the pool is
k / v (F, pages, KV heads, page_size, head) beside ``slots`` rows of rings. A
head wider than one 128-lane tile is held in whole tiles, zeros past its
values (``spec.cache_lanes``: K of 192 in 256). K and V
are held HEAD-MAJOR and read by the kernels of
``ops/pallas_head_major_attention.py`` that a hybrid spec's attention takes
(``models/sambay._attend_rows`` / ``_attend_pages``: the flash-decode
kernels on the chip, a masked einsum elsewhere), here at two group sizes in
one program (``kv_mul`` = a kind's heads over the KV heads).

Weights are a stack a mixer kind (``params["full"]`` / ``params["sliding"]``:
``wq`` and ``wo`` differ in shape by kind) and a stack an FFN kind
(``params["dense"]`` and the top-level expert stacks). Layers run in the
order of the list: a repeating unit of it (sliding x 3, full at the
published pattern) is one ``lax.scan`` over its repeats
(``models/kindscan.py``, as a hybrid spec's list).

A prompt's chunks (``forward_chunk``) fill the rings and every full layer's
K / V for all but the prompt's last token, which takes the decode step like
any other token; a chunk's full-attention walks the blocks of its plane up
to ``pos + T`` only (``_attend_live``).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..obs.spans import (SCOPE_ATTN, SCOPE_ATTN_GATE, SCOPE_ATTN_SCALE,
                         SCOPE_EMBED, SCOPE_LOGITS, scope_rope)
from ..ops.linear import matmul, rmsnorm
from .kindscan import insert_sequence, merge_lead, run_layers  # noqa: F401
from .latent import _rope, chunk_attn_block, rope_table
from .sambay import _attend_pages, _attend_rows, _write_rows, ring_plan
from .spec import TransformerSpec, cache_lanes

HIGHEST = jax.lax.Precision.HIGHEST
TOP_LEVEL = ("tok_embedding", "rms_final", "wcls")


class MixCache(NamedTuple):
    wk: jax.Array     # (W, [B,] KV heads, window, head)
    wv: jax.Array
    k: jax.Array      # (F, [B,] KV heads, seq_len, head), or the pools
    v: jax.Array      # (F, pages, KV heads, page_size, head)


def _zeros(spec: TransformerSpec, lead: tuple, kv_lead: tuple, kv: int,
           dtype):
    """Rings (W, *lead, KV heads, window, head) and K / V (F, *kv_lead, KV
    heads, ``kv`` positions, head), each kind's KV heads, K's and V's head."""
    mx = spec.mixers
    n_s, k_s, v_s = spec.kv_shape("sliding")
    n_f, k_f, v_f = spec.kv_shape("full")
    ring = (mx.count("sliding"), *lead, n_s, mx.window)
    full = (mx.count("full"), *kv_lead, n_f, kv)
    return MixCache(*(jnp.zeros((*shape, cache_lanes(head)), dtype)
                      for shape, head in ((ring, k_s), (ring, v_s),
                                          (full, k_f), (full, v_f))))


def init_cache(spec: TransformerSpec, batch: int | None = None,
               dtype=jnp.float32) -> MixCache:
    """One sequence's cache, or ``batch`` rows' (contiguous K / V)."""
    lead = () if batch is None else (batch,)
    return _zeros(spec, lead, lead, spec.seq_len, dtype)


def init_cache_paged(spec: TransformerSpec, slots: int, n_pages: int,
                     page_size: int, dtype=jnp.float32) -> MixCache:
    """``slots`` rows of rings, and a page pool a full layer (page 0 of
    each is its scrap page, as in a KV pool)."""
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    return _zeros(spec, (slots,), (n_pages,), page_size, dtype)


def state_bytes(cache: MixCache) -> tuple[int, int]:
    """(recurrent state: none, window rings) resident bytes."""
    return 0, int(cache.wk.nbytes + cache.wv.nbytes)


# -- the list of kinds as scans ------------------------------------------------

def layer_stacks(spec: TransformerSpec) -> list:
    """[(mixer stack, FFN stack)] a layer: where ``layer_plans`` puts its
    two runs of tensors."""
    k = spec.n_dense_layers
    return [(kind, "dense" if i < k else "")
            for i, kind in enumerate(spec.mixers.kinds)]


def _stack(params: dict, name: str) -> dict:
    if name:
        return params[name]
    return {k: v for k, v in params.items()
            if k not in TOP_LEVEL and not isinstance(v, dict)}


def _run(spec, params, carry, layer_fn):
    """Every layer through ``layer_fn(kind, lw, carry, mixer index, FFN
    index)``, a repeating unit of the list a scan (``models/kindscan.py``:
    ``lw`` holds the layer's mixer leaves and its FFN leaves)."""
    return run_layers(
        layer_stacks(spec), functools.partial(_stack, params), carry,
        lambda sig, lw, c, layer, idx: layer_fn(sig[0], lw, c, idx[sig[0]],
                                                idx[sig[1]]))


# -- pieces of a layer -----------------------------------------------------------

def rope_tables(spec: TransformerSpec) -> dict:
    """{kind: (frequencies (rotary / 2,), cos / sin factor)}: a kind's own
    base, rotary share and YaRN (``models/latent.rope_table``; the
    reference keeps its own copy and the tests hold the two together)."""
    mx = spec.mixers
    out = {}
    for kind in set(mx.kinds):
        mk = mx.of(kind)
        freq, m, m_all = rope_table(mx.rotary(kind), mk.rope_theta,
                                    mk.rope_scaling)
        out[kind] = (freq, m / m_all)
    return out


def _rotate(x, positions, table):
    """x (R, heads, head) -> the same with its leading rotary dimensions
    turned (interleaved pairs), row r at positions[r]."""
    freq, factor = table
    rot = 2 * len(freq)
    turned = _rope(x[..., :rot], positions, freq, factor)
    if rot == x.shape[-1]:
        return turned
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _qkv(spec, lw, kind: str, h):
    """h (R, dim) normed -> q (R, heads, head), k (R, KV heads, head), v
    (R, KV heads, V's head) of a ``kind`` layer."""
    heads = spec.mixers.of(kind).heads
    n_kv, hs, hv = spec.kv_shape(kind)
    if "wqkv" in lw:    # load-time fusion (ops/linear)
        qkv = matmul(lw["wqkv"], h)
        q, k, v = jnp.split(qkv, [heads * hs, (heads + n_kv) * hs], axis=-1)
    else:
        q, k, v = (matmul(lw[n], h) for n in ("wq", "wk", "wv"))
    r = h.shape[0]
    return (q.reshape(r, heads, hs), k.reshape(r, n_kv, hs),
            v.reshape(r, n_kv, hv))


def _held(x, dtype):
    """x (..., head) as the cache holds a head: ``dtype``, and zeros up to
    ``cache_lanes``."""
    pad = cache_lanes(x.shape[-1]) - x.shape[-1]
    x = x.astype(dtype)
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _values(a, head: int):
    """A held K or V (..., lanes) back at its ``head`` values."""
    return a if a.shape[-1] == head else a[..., :head]


def _scaled(spec, ao):
    """The attention output times ``value_scale`` (on V or on the sum, the
    same number)."""
    if spec.mixers.value_scale == 1.0:
        return ao
    with jax.named_scope(SCOPE_ATTN_SCALE):
        return ao * jnp.float32(spec.mixers.value_scale)


def head_gate(lw, h):
    """(R, heads) in (0, 1): a sigmoid of the normed layer input, float32
    at highest precision (``w_hgate`` is not quantized)."""
    return jax.nn.sigmoid(jnp.einsum(
        "rn,hn->rh", h, lw["w_hgate"], precision=HIGHEST,
        preferred_element_type=jnp.float32))


def _gated(spec, lw, h, ao, heads: int, live=None):
    """ao (R, heads * head) times each head's gate; and the gates'
    (smallest, mean) over the ``live`` rows ((1.0, 0.5) without a gate)."""
    if not spec.mixers.gate:
        return ao, jnp.float32(1.0), jnp.float32(0.5)
    with jax.named_scope(SCOPE_ATTN_GATE):
        g = head_gate(lw, h)
        out = (ao.reshape(ao.shape[0], heads, -1) * g[..., None]).reshape(
            ao.shape)
        if live is None:
            return out, jnp.min(g), jnp.mean(g)
        n = jnp.maximum(jnp.sum(live), 1) * heads
        return (out, jnp.min(jnp.where(live[:, None], g, 1.0)),
                jnp.sum(jnp.where(live[:, None], g, 0.0)) / n)


def _tail(spec, lw, x, ao, counts, fidx):
    """``wo``, the residual and the layer's FFN; an expert layer's (E,)
    routed-rows counts go to row ``fidx`` of ``counts`` (L_e, E)."""
    from .llama import _post_attention

    if counts is None or "moe_gate" not in lw:
        return _post_attention(spec, lw, x, ao), counts
    x, c = _post_attention(spec, lw, x, ao, True)
    return x, jax.lax.dynamic_update_slice(counts, c[None], (fidx, 0))


def _logits(spec, params, x):
    with jax.named_scope(SCOPE_LOGITS):
        return matmul(params["wcls"],
                      rmsnorm(x, params["rms_final"], spec.norm_eps))


def _counts0(spec, moe_counts: bool):
    if not (moe_counts and spec.n_experts):
        return None
    return jnp.zeros((spec.n_expert_layers, spec.n_experts), jnp.int32)


class _Carry(NamedTuple):
    x: jax.Array
    wk: jax.Array       # (W * B, n, window, h)
    wv: jax.Array
    k: jax.Array        # (F * B, n, S, h) or the pools (F * P, n, page, h)
    v: jax.Array
    gmin: jax.Array     # the step's gate gauges (forward_batch)
    gsum: jax.Array
    counts: Any         # (L_e, E) routed-rows counts, or None


# -- the decode step ---------------------------------------------------------------

def forward_batch(spec: TransformerSpec, params: dict[str, Any],
                  cache: MixCache, tokens: jax.Array, pos_vec: jax.Array,
                  table: jax.Array | None = None,
                  active: jax.Array | None = None, *, page_size: int = 0,
                  health: bool = False, moe_counts: bool = False):
    """One token for each of B rows at its own position: against the
    contiguous batched cache (``init_cache(spec, batch)``), or with
    ``table`` (B, max_pages) against the page pools. A row's first
    positions find of its ring only what it wrote itself (slots 0 .. pos);
    a row whose ``active`` ((B,), nonzero = takes part; default all) is 0
    rides the step: its ring and page writes land where its own re-run, or
    nobody, reads them. ``health`` adds, as a (2,) array, the smallest
    gate value the step applied over its layers, active rows and heads,
    and their mean; ``moe_counts`` the (L_e, E) int32 count of rows routed
    to each expert. Returns (logits, cache[, health][, counts])."""
    mx = spec.mixers
    B = tokens.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    live = jnp.ones((B,), bool) if active is None else active != 0
    rows = jnp.arange(B)
    W = mx.window
    paged = table is not None
    n_pool = cache.k.shape[1]
    tables = rope_tables(spec)
    x = params["tok_embedding"][tokens].astype(jnp.float32)
    dt = cache.k.dtype

    def layer_fn(kind, lw, c: _Carry, idx, fidx):
        heads = mx.of(kind).heads
        shape = (heads, *spec.kv_shape(kind)[:2])
        sink = lw.get("sink")       # (heads,) where the kind has one
        h = rmsnorm(c.x, lw["rms_att"], spec.norm_eps)
        with jax.named_scope(SCOPE_ATTN):
            q, k, v = _qkv(spec, lw, kind, h)
            with jax.named_scope(scope_rope(kind)):
                q = _rotate(q, pos_b, tables[kind]).reshape(B, -1)
                k = _rotate(k, pos_b, tables[kind])
            k, v = _held(k[:, :, None], dt), _held(v[:, :, None], dt)
            if kind == "sliding":
                wk = _write_rows(c.wk, k, idx * B + rows, pos_b % W)
                wv = _write_rows(c.wv, v, idx * B + rows, pos_b % W)
                c = c._replace(wk=wk, wv=wv)
                ao = _attend_rows(shape, q, wk, wv, idx,
                                  jnp.minimum(pos_b, W - 1), sink)
            elif paged:
                own = table + idx * n_pool      # this layer's pool
                page = jnp.take_along_axis(
                    own, (pos_b // page_size)[:, None], axis=1)[:, 0]
                c = c._replace(
                    k=_write_rows(c.k, k, page, pos_b % page_size),
                    v=_write_rows(c.v, v, page, pos_b % page_size))
                ao = _attend_pages(shape, page_size, q, c.k, c.v, pos_b, own,
                                   sink)
            else:
                c = c._replace(k=_write_rows(c.k, k, idx * B + rows, pos_b),
                               v=_write_rows(c.v, v, idx * B + rows, pos_b))
                ao = _attend_rows(shape, q, c.k, c.v, idx, pos_b, sink)
            ao, lo, mean = _gated(spec, lw, h, _scaled(spec, ao), heads,
                                  live)
        x, counts = _tail(spec, lw, c.x, ao, c.counts, fidx)
        return c._replace(x=x, counts=counts, gmin=jnp.minimum(c.gmin, lo),
                          gsum=c.gsum + mean)

    carry = _Carry(x, merge_lead(cache.wk, 2), merge_lead(cache.wv, 2),
                   merge_lead(cache.k, 2), merge_lead(cache.v, 2),
                   jnp.float32(1.0), jnp.float32(0.0),
                   _counts0(spec, moe_counts))
    carry = _run(spec, params, carry, layer_fn)
    logits = _logits(spec, params, carry.x)
    out = MixCache(*(new.reshape(old.shape) for new, old in zip(
        carry[1:5], cache)))
    more = [jnp.stack([carry.gmin, carry.gsum / spec.n_layers])] if health \
        else []
    if carry.counts is not None:
        more.append(carry.counts)
    return (logits, out, *more)


# -- a chunk of one sequence ---------------------------------------------------------

def _attend_live(kv_mul: int, hv: int, q, k_plane, v_plane, pos, block: int,
                 sink=None):
    """q (T, heads, head) at positions pos .. pos + T - 1 over the
    head-major planes (KV heads, S, head): a walk over the blocks 0 ..
    (pos + T - 1) // block that a query of the chunk can see, with a
    running (m, l, o) (``parallel.ring._lse_merge``), as a latent spec's
    chunk walks its plane (``models/latent.attend_live``). The products
    are ``attention_core``'s (float32, HIGHEST); with ``sink`` (heads,) the
    walk starts at m = sink, l = 1: the column is folded before any
    block."""
    from ..parallel.ring import _lse_merge

    t_len, heads, hs = q.shape
    n_kv, S, _ = k_plane.shape
    qg = q.reshape(t_len, n_kv, kv_mul, hs) / jnp.sqrt(jnp.float32(hs))
    q_pos = pos + jnp.arange(t_len)
    n_live = jnp.minimum((pos + t_len + block - 1) // block, S // block)

    def body(carry):
        b, m, l, o = carry
        kb = _values(jax.lax.dynamic_slice_in_dim(
            k_plane, b * block, block, 1), hs).astype(jnp.float32)
        vb = _values(jax.lax.dynamic_slice_in_dim(
            v_plane, b * block, block, 1), hv).astype(jnp.float32)
        s = jnp.einsum("tgmd,gsd->tgms", qg, kb, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        seen = (b * block + jnp.arange(block))[None, :] <= q_pos[:, None]
        s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
        pm = jnp.max(s, axis=-1, keepdims=True)
        # a row that sees nothing of this block: exp(-inf - 0) = 0
        p = jnp.exp(s - jnp.where(jnp.isfinite(pm), pm, 0.0))
        po = jnp.einsum("tgms,gsd->tgmd", p, vb, precision=HIGHEST,
                        preferred_element_type=jnp.float32)
        return (b + 1, *_lse_merge(m, l, o, pm,
                                   jnp.sum(p, axis=-1, keepdims=True), po))

    lead = (t_len, n_kv, kv_mul)
    if sink is None:
        m0 = jnp.full((*lead, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((*lead, 1), jnp.float32)
    else:
        m0 = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            n_kv, kv_mul, 1), (*lead, 1))
        l0 = jnp.ones((*lead, 1), jnp.float32)
    _, _, l, o = jax.lax.while_loop(
        lambda c: c[0] < n_live, body,
        (jnp.int32(0), m0, l0, jnp.zeros((*lead, hv), jnp.float32)))
    return (o / l).reshape(t_len, heads * hv)


def forward_chunk(spec: TransformerSpec, params: dict[str, Any],
                  cache: MixCache, tokens: jax.Array, pos: jax.Array,
                  n_valid=None, *, xdec: bool = True, health: bool = False,
                  moe_counts: bool = False):
    """T tokens of ONE sequence at positions pos .. pos + T - 1 against its
    cache (``init_cache(spec)``). T = 1 is the decode step at one row. Of a
    chunk's positions the first ``n_valid`` (default all) are the
    sequence's and the rest padding that reaches neither a ring nor the
    K / V. ``xdec=False`` (the name a hybrid spec's chunk gave it: what a
    prefill needs) leaves the classifier out and returns logits of shape
    (0, vocab). Results as ``forward_batch``."""
    t_len = tokens.shape[0]
    if t_len == 1:
        batched = MixCache(*(a[:, None] for a in cache))
        logits, out, *more = forward_batch(
            spec, params, batched, tokens, jnp.reshape(pos, (1,)),
            health=health, moe_counts=moe_counts)
        return (logits, MixCache(*(a[:, 0] for a in out)), *more)
    from .llama import attention_core, causal_cache_mask

    mx = spec.mixers
    T = t_len
    n_valid = t_len if n_valid is None else jnp.minimum(n_valid, t_len)
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos + jnp.arange(T)
    valid = jnp.arange(T) < n_valid
    W, S = mx.window, spec.seq_len
    hs = spec.head_size
    dt = cache.k.dtype
    tables = rope_tables(spec)
    block = chunk_attn_block(S, T)
    with jax.named_scope(SCOPE_EMBED):
        x = params["tok_embedding"][tokens].astype(jnp.float32)
    # a sliding layer's keys (the ring as it stands, then the chunk's own)
    # and the ring after it
    win_mask, from_chunk, take = ring_plan(W, pos, n_valid, T)
    kv_at = jnp.where(valid, positions, S)     # padding is dropped
    heads_first = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731

    def layer_fn(kind, lw, c: _Carry, idx, fidx):
        heads = mx.of(kind).heads
        n_kv, _, hv = spec.kv_shape(kind)
        sink = lw.get("sink")
        h = rmsnorm(c.x, lw["rms_att"], spec.norm_eps)
        with jax.named_scope(SCOPE_ATTN):
            q, k, v = _qkv(spec, lw, kind, h)
            with jax.named_scope(scope_rope(kind)):
                q = _rotate(q, positions, tables[kind])
                k = _held(_rotate(k, positions, tables[kind]), dt)
            v = _held(v, dt)
            if kind == "sliding":
                wk = jax.lax.dynamic_index_in_dim(c.wk, idx, 0, False)
                wv = jax.lax.dynamic_index_in_dim(c.wv, idx, 0, False)
                ao = attention_core(
                    hs, heads // n_kv, q,
                    _values(jnp.concatenate([heads_first(wk), k]), hs),
                    _values(jnp.concatenate([heads_first(wv), v]), hv),
                    win_mask, sink)
                c = c._replace(
                    wk=jax.lax.dynamic_update_slice_in_dim(
                        c.wk, jnp.where(from_chunk, heads_first(k[take]),
                                        wk)[None], idx, 0),
                    wv=jax.lax.dynamic_update_slice_in_dim(
                        c.wv, jnp.where(from_chunk, heads_first(v[take]),
                                        wv)[None], idx, 0))
            else:
                c = c._replace(
                    k=c.k.at[idx, :, kv_at].set(k, mode="drop"),
                    v=c.v.at[idx, :, kv_at].set(v, mode="drop"))
                k_p = jax.lax.dynamic_index_in_dim(c.k, idx, 0, False)
                v_p = jax.lax.dynamic_index_in_dim(c.v, idx, 0, False)
                if block is None:
                    ao = attention_core(hs, heads // n_kv, q,
                                        _values(heads_first(k_p), hs),
                                        _values(heads_first(v_p), hv),
                                        causal_cache_mask(S, pos, T), sink)
                else:
                    ao = _attend_live(heads // n_kv, hv, q, k_p, v_p, pos,
                                      block, sink)
            ao, lo, mean = _gated(spec, lw, h, _scaled(spec, ao), heads,
                                  valid)
        x, counts = _tail(spec, lw, c.x, ao, c.counts, fidx)
        return c._replace(x=x, counts=counts, gmin=jnp.minimum(c.gmin, lo),
                          gsum=c.gsum + mean)

    carry = _Carry(x, cache.wk, cache.wv, cache.k, cache.v, jnp.float32(1.0),
                   jnp.float32(0.0), _counts0(spec, moe_counts))
    carry = _run(spec, params, carry, layer_fn)
    out = MixCache(*carry[1:5])
    logits = (_logits(spec, params, carry.x) if xdec
              else jnp.zeros((0, spec.vocab_size), jnp.float32))
    more = [jnp.stack([carry.gmin, carry.gsum / spec.n_layers])] if health \
        else []
    if carry.counts is not None:
        more.append(carry.counts)
    return (logits, out, *more)
