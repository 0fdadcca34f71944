"""The forward pass of a kda spec (``TransformerSpec.kda``: Ling-3.0-flash's
layout): pre-norm residual blocks whose mixer is a layer's KIND
(``spec.latent.kinds``), around the FFN that ``spec.layout`` says (a
leading dense SwiGLU, then routed experts with a shared one and a clamp a
layer: ``models/llama._post_attention``, as every expert spec):

* "kda": a Kimi-Delta-Attention layer (``ops/kda.py``): ONE projection in
  [q | k | v | a | g], a depthwise causal convolution and SiLU over each of
  q, k and v, L2-normed q and k, a per-channel decay and a per-head write
  strength, the delta rule on a state (heads, head_dim, head_dim), the
  output's RMSNorm (one group) times a sigmoid gate, projection out. No
  positional encoding.
* "full": latent attention (``models/latent.py``'s projections, absorbed
  products, page kernel and chunk walk, imported) with no query rank and a
  sigmoid gate a HEAD on the heads' outputs.

``models/reference_kda.py`` states every layer in full; this module runs
the same function through the caches. A sequence's cache
(``init_cache(spec)``) is conv (K, d_conv - 1, 3 heads head_dim): a KDA
layer's last inputs of the three convolutions, before their activation; s
(K, heads, head_dim, head_dim): its state; both float32 and of fixed size;
and c (F, seq_len, plane): EACH latent layer's row [c_kv | k_rope] of every
position, contiguous (``inference``, an admission's scratch sequence) or a
page pool a layer (``serve``: (F, pages, page_size, plane), one page table
a sequence). K and F count the KDA and the latent layers; ``serve``'s rows
add an axis after the first of conv and s. A row's first position finds
its state and conv rows empty whatever they hold.

Why a module of its own and not a "kda" branch in ``models/latent.py``'s
``_run``: that forward's layer IS a latent mixer (its carry holds rings,
residual streams, noise heads and an elementwise gate's gauges, and it
projects q and the latent row before it asks the kind anything), and its
stacks hold the mixer's tensors with the FFN's; a KDA layer shares none of
that but the FFN. What the two share (``latent_qkv``, ``attend`` /
``attend_live``, ``paged_decode_attention``, ``attention_out``, the plane's
width) is imported, as ``models/nemotron.py`` takes its attention from
``models/laguna.py``. Weights are a stack a mixer kind (``params["kda"]`` /
``params["full"]``) and a stack an FFN kind (``params["dense"]`` and the
top-level expert stacks); the layers run in the order of the list, a
repeating unit of it (five KDA layers and a latent one at the published
pattern) one ``lax.scan`` over its repeats (``models/kindscan.py``).

A prompt's chunks (``forward_chunk``) fill the states (the chunk form), the
conv rows and every latent layer's plane for all but the prompt's last
token, which takes the decode step like any other token.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..obs.spans import (SCOPE_ATTN, SCOPE_ATTN_GATE, SCOPE_EMBED,
                         SCOPE_KDA_CONV, SCOPE_KDA_GATE, SCOPE_KDA_OUT_NORM,
                         SCOPE_KDA_PROJ, SCOPE_KDA_SCAN, SCOPE_LOGITS)
from ..ops import kda as kda_ops
from ..ops.linear import matmul, rmsnorm, silu
from .kindscan import merge_lead, run_layers
from .latent import (attend, attend_live, attention_out, chunk_attn_block,
                     latent_qkv, paged_decode_attention, plane_width)
from .sambay import _f32_rows
from .spec import TransformerSpec

TOP_LEVEL = ("tok_embedding", "rms_final", "wcls")
L2_EPS = 1e-6


class KdaCache(NamedTuple):
    conv: jax.Array   # (K, [B,] d_conv - 1, 3 heads head_dim) f32
    s: jax.Array      # (K, [B,] heads, head_dim, head_dim) f32
    c: jax.Array      # (F, seq_len, plane), or the pool (F, P, page, plane)


def _zeros(spec: TransformerSpec, lead: tuple, shape: tuple,
           dtype) -> KdaCache:
    kd, la = spec.kda, spec.latent
    n_kda = la.count("kda")
    return KdaCache(
        jnp.zeros((n_kda, *lead, kd.d_conv - 1, 3 * kd.width), jnp.float32),
        jnp.zeros((n_kda, *lead, kd.heads, kd.head_dim, kd.head_dim),
                  jnp.float32),
        jnp.zeros((la.count("full"), *shape, plane_width(spec)), dtype))


def init_cache(spec: TransformerSpec, dtype=jnp.float32) -> KdaCache:
    """One sequence's cache (contiguous planes)."""
    return _zeros(spec, (), (spec.seq_len,), dtype)


def init_cache_paged(spec: TransformerSpec, slots: int, n_pages: int,
                     page_size: int, dtype=jnp.float32) -> KdaCache:
    """``slots`` rows of state and conv rows, and a page pool a latent
    layer (page 0 of each is its scrap page, as in a KV pool)."""
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    return _zeros(spec, (slots,), (n_pages, page_size), dtype)


def state_bytes(cache: KdaCache) -> tuple[int, int]:
    """(recurrent state: the KDA states and conv rows, window rings: none)
    resident bytes."""
    return int(cache.conv.nbytes + cache.s.nbytes), 0


def insert_sequence(cache: KdaCache, one: KdaCache, row, table: jax.Array,
                    page_size: int) -> KdaCache:
    """Put a sequence's cache (``init_cache(spec)``, prefilled) into row
    ``row`` of the paged cache: its state and conv rows whole, its planes
    (F, seq_len, plane) page by page into the pools through ``table``
    (max_pages,) (entries past the sequence's pages point at the scrap
    page)."""
    def rows(whole, part):
        return jax.lax.dynamic_update_slice(
            whole, part[:, None].astype(whole.dtype),
            (0, row) + (0,) * (part.ndim - 1))

    f, _, width = one.c.shape
    paged = one.c.reshape(f, table.shape[0], page_size, width)
    return KdaCache(rows(cache.conv, one.conv), rows(cache.s, one.s),
                    cache.c.at[:, table].set(paged.astype(cache.c.dtype)))


# -- the list of kinds as scans ------------------------------------------------

def layer_stacks(spec: TransformerSpec) -> list:
    """[(mixer stack, FFN stack)] a layer: where ``layer_plans`` puts its
    two runs of tensors."""
    k = spec.n_dense_layers
    return [(kind, "dense" if i < k else "")
            for i, kind in enumerate(spec.latent.kinds)]


def _stack(params: dict, name: str) -> dict:
    if name:
        return params[name]
    return {k: v for k, v in params.items()
            if k not in TOP_LEVEL and not isinstance(v, dict)}


class _Carry(NamedTuple):
    x: jax.Array
    conv: jax.Array     # (K * B, d_conv - 1, 3 w)
    s: jax.Array        # (K * B, H, D, D)
    c: jax.Array        # the latent layers' planes or pool, as the entry views it
    health: jax.Array   # (3,): smallest head gate, its means' sum, smallest decay
    counts: Any         # (L_e, E) routed-rows counts, or None


def _run(spec, params, carry: _Carry, mixer_fn) -> _Carry:
    """Every layer: ``mixer_fn(kind, lw, carry, h, idx)`` -> (the mixer's
    output before ``wo``, carry) of the normed rows ``h``, then ``wo``, the
    residual and the FFN (``models/llama._post_attention``)."""
    from .llama import _post_attention

    def layer_fn(sig, lw, c: _Carry, layer, idx):
        kind, ffn = sig
        with jax.named_scope(SCOPE_ATTN):
            h = rmsnorm(c.x, lw["rms_att"], spec.norm_eps)
            ao, c = mixer_fn(kind, lw, c, h, idx[kind])
        want = c.counts is not None and "moe_gate" in lw
        x = _post_attention(spec, lw, c.x, ao, want)
        if want:
            x, n = x
            c = c._replace(counts=jax.lax.dynamic_update_slice(
                c.counts, n[None], (idx[ffn], 0)))
        return c._replace(x=x)

    return run_layers(layer_stacks(spec), functools.partial(_stack, params),
                      carry, layer_fn)


def _carry0(spec, x, conv, s, c, moe_counts: bool) -> _Carry:
    counts = (jnp.zeros((spec.n_expert_layers, spec.n_experts), jnp.int32)
              if moe_counts else None)
    return _Carry(x, conv, s, c, jnp.asarray([1.0, 0.0, 1.0], jnp.float32),
                  counts)


def _results(spec, logits, cache, carry: _Carry, health: bool):
    """(logits, cache[, (smallest head gate, mean head gate, smallest
    decay) where asked for][, the routed-rows counts])."""
    more = []
    if health:
        n_full = max(spec.latent.count("full"), 1)
        more.append(carry.health * jnp.asarray([1.0, 1.0 / n_full, 1.0]))
    if carry.counts is not None:
        more.append(carry.counts)
    return (logits, cache, *more)


# -- pieces of a KDA layer -------------------------------------------------------

def _kda_project(spec, lw, h):
    """h (R, dim) normed -> (qkv (R, 3 w) before the convolutions, a (R, w)
    the decay's logits before their bias, z (R, w) the output gate's, beta
    (R, H) the write strength's)."""
    w = spec.kda.width
    with jax.named_scope(SCOPE_KDA_PROJ):
        proj = matmul(lw["in_qkvag"], h)
        return (proj[:, :3 * w], proj[:, 3 * w:4 * w], proj[:, 4 * w:],
                _f32_rows(h, lw["w_beta"]))


def _kda_gates(spec, lw, qkv, a, beta):
    """qkv (R, 3 w) after the convolutions and SiLU -> q, k (R, H, D)
    L2-normed (q scaled by D^-1/2), v (R, H, D), g (R, H, D) <= 0 the
    decay's exponent, b (R, H)."""
    kd = spec.kda
    shape = (qkv.shape[0], kd.heads, kd.head_dim)
    with jax.named_scope(SCOPE_KDA_GATE):
        q, k, v = (qkv[:, i * kd.width:(i + 1) * kd.width].reshape(shape)
                   for i in range(3))

        def l2(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

        g = kd.lower_bound * jax.nn.sigmoid(
            jnp.exp(lw["a_log"])[None, :, None]
            * (a + lw["dt_bias"]).reshape(shape))
        return (l2(q) * kd.head_dim ** -0.5, l2(k), v, g,
                jax.nn.sigmoid(beta))


def _kda_out(spec, lw, o, z):
    """o (R, H, D) -> (R, w): the RMSNorm over all w outputs with its gain,
    times sigmoid(z)."""
    with jax.named_scope(SCOPE_KDA_OUT_NORM):
        return rmsnorm(o.reshape(o.shape[0], -1), lw["norm_g"],
                       spec.norm_eps) * jax.nn.sigmoid(z)


def _head_gate(spec, lw, h, ao, live, health):
    """ao (R, H v_dim) times sigmoid(h w_hgate) a head; the gate's
    smallest value and the sum of its means over the ``live`` rows go to
    ``health``."""
    if not spec.latent.head_gate:
        return ao, health
    with jax.named_scope(SCOPE_ATTN_GATE):
        gate = jax.nn.sigmoid(_f32_rows(h, lw["w_hgate"]))     # (R, H)
        n = jnp.maximum(jnp.sum(live), 1) * gate.shape[-1]
        lo = jnp.min(jnp.where(live[:, None], gate, 1.0))
        mean = jnp.sum(jnp.where(live[:, None], gate, 0.0)) / n
        health = health.at[0].min(lo).at[1].add(mean)
        r = ao.shape[0]
        return (ao.reshape(r, gate.shape[1], -1) * gate[..., None]).reshape(
            r, -1), health


def _state_kernel() -> bool:
    from ..ops.pallas_attention import attn_kernel_mode

    return attn_kernel_mode() == "pallas"


def _decay_floor(health, g, live):
    """``health`` with the smallest, over the live rows, of the mean decay
    exp(g) of this layer's step: near exp(lower_bound), a row's whole
    state is forgotten in one token."""
    low = jnp.min(jnp.where(live, jnp.mean(jnp.exp(g), axis=(1, 2)), 1.0))
    return health.at[2].min(low)


# -- the decode step ---------------------------------------------------------------

def forward_batch(spec: TransformerSpec, params: dict[str, Any],
                  cache: KdaCache, tokens: jax.Array, pos_vec: jax.Array,
                  table: jax.Array, active: jax.Array | None = None, *,
                  page_size: int, health: bool = False,
                  moe_counts: bool = False):
    """One token for each of B rows at its own position against the rows'
    states and the page pools (``table`` (B, max_pages)). A row at position
    0 finds its state and conv rows empty; a row whose ``active`` ((B,),
    nonzero = takes part; default all) is 0 rides the step and leaves its
    state as it is (its page writes land where its own re-run, or nobody,
    reads them). ``health`` adds a (3,) array: the smallest and the mean
    head gate of the latent layers and the smallest, over the KDA layers
    and the active rows, of the mean decay exp(g) of this step;
    ``moe_counts`` adds the (L_e, E) int32 count of rows routed to each
    expert. Returns (logits, cache[, health][, counts])."""
    B = tokens.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    live = jnp.ones((B,), bool) if active is None else active != 0
    fresh = pos_b == 0
    F, P, ps, width = cache.c.shape
    kernel = _state_kernel()
    x = params["tok_embedding"][tokens].astype(jnp.float32)

    def mixer_fn(kind, lw, c: _Carry, h, idx):
        if kind == "kda":
            qkv, a, z, beta = _kda_project(spec, lw, h)
            with jax.named_scope(SCOPE_KDA_CONV):
                old = jax.lax.dynamic_slice_in_dim(c.conv, idx * B, B, 0)
                old = jnp.where((fresh & live)[:, None, None], 0.0, old)
                win = jnp.concatenate([old, qkv[:, None]], axis=1)
                c = c._replace(conv=jax.lax.dynamic_update_slice_in_dim(
                    c.conv, jnp.where(live[:, None, None], win[:, 1:], old),
                    idx * B, 0))
                qkv = silu(jnp.sum(win * lw["conv_w"], axis=1))
            q, k, v, g, b = _kda_gates(spec, lw, qkv, a, beta)
            with jax.named_scope(SCOPE_KDA_SCAN):
                o, s = kda_ops.scan_decode(idx, c.s, q, k, v, g, b, fresh,
                                           live, kernel=kernel)
            c = c._replace(s=s, health=_decay_floor(c.health, g, live))
            return _kda_out(spec, lw, o, z), c
        q, row = latent_qkv(spec, lw, None, pos_b, h)
        o_lat, c3 = paged_decode_attention(spec, page_size, P, q, row, c.c,
                                           idx, pos_b, table)
        ao, gauges = _head_gate(spec, lw, h, attention_out(spec, lw, o_lat),
                                live, c.health)
        return ao, c._replace(c=c3, health=gauges)

    carry = _run(spec, params, _carry0(
        spec, x, merge_lead(cache.conv, 2), merge_lead(cache.s, 2),
        cache.c.reshape(F * P, ps, width), moe_counts), mixer_fn)
    with jax.named_scope(SCOPE_LOGITS):
        logits = matmul(params["wcls"], rmsnorm(carry.x, params["rms_final"],
                                                spec.norm_eps))
    out = KdaCache(carry.conv.reshape(cache.conv.shape),
                   carry.s.reshape(cache.s.shape),
                   carry.c.reshape(cache.c.shape))
    return _results(spec, logits, out, carry, health)


# -- a chunk of one sequence ---------------------------------------------------------

def forward_chunk(spec: TransformerSpec, params: dict[str, Any],
                  cache: KdaCache, tokens: jax.Array, pos: jax.Array,
                  n_valid=None, *, xdec: bool = True, health: bool = False,
                  moe_counts: bool = False):
    """T tokens of ONE sequence at positions pos .. pos + T - 1 against its
    cache (``init_cache(spec)``). T = 1 is the decode step at one row (the
    state kernel); a longer dispatch runs the chunk form. Of a chunk's
    positions the first ``n_valid`` (default all) are the sequence's and
    the rest padding that reaches neither a state, the conv rows nor a
    plane. ``pos == 0`` finds the state empty. ``xdec=False`` (the name a
    hybrid spec's chunk gave it: what a prefill needs) leaves the
    classifier out and returns logits of shape (0, vocab). Results as
    ``forward_batch``."""
    from .llama import causal_cache_mask

    kd = spec.kda
    T, S = tokens.shape[0], spec.seq_len
    n_valid = T if n_valid is None else jnp.minimum(n_valid, T)
    pos = jnp.asarray(pos, jnp.int32)
    fresh = pos == 0
    positions = pos + jnp.arange(T)
    valid = jnp.arange(T) < n_valid
    kv_at = jnp.where(valid, positions, S)     # padding is dropped
    block = chunk_attn_block(S, T)
    kernel = _state_kernel()
    with jax.named_scope(SCOPE_EMBED):
        x = params["tok_embedding"][tokens].astype(jnp.float32)

    def mixer_fn(kind, lw, c: _Carry, h, idx):
        if kind == "kda":
            qkv, a, z, beta = _kda_project(spec, lw, h)
            with jax.named_scope(SCOPE_KDA_CONV):
                old = jax.lax.dynamic_index_in_dim(c.conv, idx, 0, False)
                run = jnp.concatenate([jnp.where(fresh, 0.0, old), qkv])
                c = c._replace(conv=jax.lax.dynamic_update_slice_in_dim(
                    c.conv, jax.lax.dynamic_slice_in_dim(
                        run, n_valid, kd.d_conv - 1, 0)[None], idx, 0))
                qkv = silu(sum(run[j:j + T] * lw["conv_w"][j]
                               for j in range(kd.d_conv)))
            q, k, v, g, b = _kda_gates(spec, lw, qkv, a, beta)
            with jax.named_scope(SCOPE_KDA_SCAN):
                if T == 1:
                    o, s = kda_ops.scan_decode(
                        idx, c.s, q, k, v, g, b, fresh[None], valid,
                        kernel=kernel)
                else:
                    s0 = jax.lax.dynamic_index_in_dim(c.s, idx, 0, False)
                    o, s1 = kda_ops.kda_chunk(
                        jnp.where(fresh, 0.0, s0), q, k, v,
                        jnp.where(valid[:, None, None], g, 0.0),
                        jnp.where(valid[:, None], b, 0.0), kda_ops.CHUNK)
                    s = jax.lax.dynamic_update_slice_in_dim(c.s, s1[None],
                                                            idx, 0)
            c = c._replace(s=s, health=_decay_floor(c.health, g, valid))
            return _kda_out(spec, lw, o, z), c
        q, row = latent_qkv(spec, lw, None, positions, h)
        c_all = c.c.at[idx, kv_at].set(row.astype(c.c.dtype), mode="drop")
        plane = jax.lax.dynamic_index_in_dim(c_all, idx, 0, keepdims=False)
        o_lat = (attend(spec, q, plane, causal_cache_mask(S, pos, T))
                 if block is None else attend_live(spec, q, plane, pos,
                                                   block))
        ao, gauges = _head_gate(spec, lw, h, attention_out(spec, lw, o_lat),
                                valid, c.health)
        return ao, c._replace(c=c_all, health=gauges)

    carry = _run(spec, params, _carry0(spec, x, cache.conv, cache.s, cache.c,
                                       moe_counts), mixer_fn)
    if xdec:
        with jax.named_scope(SCOPE_LOGITS):
            logits = matmul(params["wcls"], rmsnorm(
                carry.x, params["rms_final"], spec.norm_eps))
    else:
        logits = jnp.zeros((0, spec.vocab_size), jnp.float32)
    return _results(spec, logits, KdaCache(carry.conv, carry.s, carry.c),
                    carry, health)
