"""The forward pass of a latent-attention spec (``TransformerSpec.latent``):
low-rank q, ONE cached plane ``[c_kv | k_rope]`` a layer in place of K and
V, k leading dense layers and then expert layers (two ``lax.scan``s over two
stacks of weights: ``params["dense"]`` and the top-level keys; per-layer
kinds of any pattern can take the two's place), a router of the spec's kind
with a shared expert and a share of the routed experts (ops/pallas_moe).
A spec with ``hyper`` carries n residual streams (n, R, dim) through both
scans in place of the one (R, dim); ops/hyper.py has the residual function,
which is the plain add for every other spec.
``models/reference_latent.py`` states the layer in full, EXPANDED (every
position's keys and values formed from its latent row). Here every
dispatch, decode step and prefill chunk alike, runs the ABSORBED schedule:

  q_lat_h = W_UK,h^T q_nope,h          (W_UK,h / W_UV,h: the two halves of
  score   = q_lat . c_kv + q_rope . k_rope          W_kvb's rows for head h)
  o_h     = W_UV,h (softmax . c_kv)

so a cached position is read once, as it lies, by all heads, and is never
expanded: H query heads over one key head of ``latent.width`` whose values
are its first ``kv_rank`` columns. ``wkv_b`` is held as the two float32
stacks the absorbed products need (``w_uk`` / ``w_uv`` (L, H, nope | v,
kv_rank): a Q40 value dequantizes exactly to float32), made once at load
(``prepare_latent_params``).

The contiguous cache (``inference``; an admission's gathered sequence) is
(L, S, width); the page pool (``serve``) (L, P, page_size, width) behind the
same page tables, allocator, gather and scatter as a KV pool. Decode over
pages is the Pallas kernel of ops/pallas_latent_attention.py on the chip and
an XLA gather elsewhere.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import SCOPE_ATTN, SCOPE_EMBED, SCOPE_LOGITS
from ..ops.hyper import fan_out, fold_in, residual_in
from ..ops.linear import matmul, rmsnorm
from .spec import TransformerSpec

HIGHEST = jax.lax.Precision.HIGHEST


class LatentCache(NamedTuple):
    c: jax.Array  # (L, S, plane) f32, or the pool (L, P, page_size, plane)


def plane_width(spec: TransformerSpec) -> int:
    """The cached plane's minor dim: ``latent.width`` values a position, in
    whole 128-lane tiles (576 -> 640). The chip stores a minor dim of 576
    in 640 lanes whatever it is told, and the decode kernel's page copies
    must cover whole tiles; the columns past ``width`` hold zeros (a row is
    written padded, a query is padded with zeros), so they add nothing to a
    score."""
    return -(-spec.latent.width // 128) * 128


def init_cache(spec: TransformerSpec, dtype=jnp.float32) -> LatentCache:
    return LatentCache(jnp.zeros(
        (spec.n_layers, spec.seq_len, plane_width(spec)), dtype))


def init_cache_paged(spec: TransformerSpec, n_pages: int, page_size: int,
                     dtype=jnp.float32) -> LatentCache:
    """The page pool: physical page p of layer l is the (page_size, width)
    plane at [l, p] (page 0 is the scrap page, as in a KV pool)."""
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    return LatentCache(jnp.zeros(
        (spec.n_layers, n_pages, page_size, plane_width(spec)), dtype))


def rope_table(rope_dim: int, theta: float, rs):
    """(frequencies (rope_dim / 2,) float32, YaRN's m(mscale), its
    m(mscale_all_dim)) of a RoPE over ``rope_dim`` dimensions at base
    ``theta``: plain (``rs`` None: both m are 1), or YaRN's blend of f and
    f / factor over the correction range."""
    import math

    half = rope_dim // 2
    freq = np.power(float(theta), -np.arange(half, dtype=np.float64) / half)
    if rs is None:
        return freq.astype(np.float32), 1.0, 1.0
    # the pair whose wavelength makes `turns` rotations over the original
    # positions: pairs below `low` keep f, above `high` take f / factor
    edge = [rope_dim * math.log(rs.original_positions / (2 * math.pi * n))
            / (2 * math.log(theta))
            for n in (rs.beta_fast, rs.beta_slow)]
    low = max(math.floor(edge[0]), 0)
    high = min(math.ceil(edge[1]), rope_dim - 1)
    slow = np.clip((np.arange(half) - low) / (high - low or 1e-3), 0.0, 1.0)
    freq = freq * (1.0 - slow) + freq / rs.factor * slow
    m = [0.1 * a * math.log(rs.factor) + 1.0 if rs.factor > 1 else 1.0
         for a in (rs.mscale, rs.mscale_all_dim)]
    return freq.astype(np.float32), m[0], m[1]


def rope_frequencies(spec: TransformerSpec):
    """(frequencies (rope_dim / 2,) float32, cos / sin factor, attention
    scale): plain RoPE, or YaRN's blend with the scale's m^2 (the reference
    states both and keeps its own copy: the tests hold the two together and
    pin the published model's numbers by hand)."""
    import math

    la = spec.latent
    freq, m, m_all = rope_table(la.rope_dim, spec.rope_theta,
                                spec.rope_scaling)
    return freq, m / m_all, m_all * m_all / math.sqrt(la.qk_dim)


def _rope(x: jax.Array, positions: jax.Array, freq, factor) -> jax.Array:
    """Interleaved-pair RoPE of x (R, ..., rope_dim), row r at positions[r]."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq)
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def absorb_weights(spec: TransformerSpec, lw: dict[str, Any]):
    """(w_uk (H, nope, kv_rank), w_uv (H, v, kv_rank)) of one layer, from
    the prepared stacks or from ``wkv_b`` as the file has it."""
    if "w_uk" in lw:
        return lw["w_uk"], lw["w_uv"]
    from ..ops.linear import dequantize_weight

    la = spec.latent
    w = dequantize_weight(lw["wkv_b"]).astype(jnp.float32).reshape(
        spec.n_heads, la.nope_dim + la.v_dim, la.kv_rank)
    return w[:, :la.nope_dim], w[:, la.nope_dim:]


def prepare_latent_params(spec: TransformerSpec, params: dict) -> dict:
    """``wkv_b`` of every stack as the float32 ``w_uk`` / ``w_uv`` the
    absorbed products read, and a Q40 ``wkv_a`` with zero rows up to the
    plane's width (``plane_width``: the latent row then comes out of the
    projection as the cache holds it, and the leaf sits on the 128-row
    grid its neighbours pack on; ``latent_qkv`` reads the first ``width``
    outputs). Host side, once, before packing and placement."""
    from ..io.loader import Q40Weight
    from ..ops.quants import dequantize_q40

    out = {k: prepare_latent_params(spec, v) if isinstance(v, dict) else v
           for k, v in params.items()}
    row = out.get("wkv_a")
    if isinstance(row, Q40Weight):
        pad = [(0, 0)] * (row.qs.ndim - 3) + [
            (0, plane_width(spec) - row.qs.shape[-3])]
        out["wkv_a"] = Q40Weight(np.pad(row.qs, pad + [(0, 0), (0, 0)]),
                                 np.pad(row.d16, pad + [(0, 0)]))
    w = out.pop("wkv_b", None)
    if w is not None:
        la = spec.latent
        w = dequantize_q40(w.qs, w.d16) if isinstance(w, Q40Weight) \
            else np.asarray(w, np.float32)
        w = w.reshape(w.shape[0], spec.n_heads, la.nope_dim + la.v_dim,
                      la.kv_rank)
        out["w_uk"] = np.ascontiguousarray(w[:, :, :la.nope_dim])
        out["w_uv"] = np.ascontiguousarray(w[:, :, la.nope_dim:])
    return out


def latent_qkv(spec: TransformerSpec, lw: dict[str, Any], x: jax.Array,
               positions: jax.Array):
    """Rows x (R, dim), row r at positions[r] -> (q (R, H, plane) SCALED
    absorbed queries [q_lat | q_rope | 0], row (R, plane) [c_kv | k_rope |
    0]: what the cache holds of each row; ``plane_width`` says why the
    zeros)."""
    la, nh, eps = spec.latent, spec.n_heads, spec.norm_eps
    freq, factor, scale = rope_frequencies(spec)
    h = rmsnorm(x, lw["rms_att"], eps)
    c_q = rmsnorm(matmul(lw["wq_a"], h), lw["rms_q_a"], eps)
    q = matmul(lw["wq_b"], c_q).reshape(-1, nh, la.qk_dim)
    # wkv_a's outputs past ``width`` are zero rows (prepare_latent_params)
    kv = matmul(lw["wkv_a"], h)[:, :la.width]
    c_kv = rmsnorm(kv[:, :la.kv_rank], lw["rms_kv_a"], eps)
    k_rope = _rope(kv[:, la.kv_rank:], positions, freq, factor)
    q_rope = _rope(q[..., la.nope_dim:], positions, freq, factor)
    w_uk, _ = absorb_weights(spec, lw)
    q_lat = jnp.einsum("rhn,hnc->rhc", q[..., :la.nope_dim], w_uk,
                       precision=HIGHEST, preferred_element_type=jnp.float32)
    pad = plane_width(spec) - la.width
    q = jnp.concatenate([q_lat, q_rope], axis=-1) * jnp.float32(scale)
    row = jnp.concatenate([c_kv, k_rope], axis=-1)
    return (jnp.pad(q, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(row, ((0, 0), (0, pad))))


def attend(spec: TransformerSpec, q: jax.Array, plane: jax.Array,
           mask: jax.Array) -> jax.Array:
    """Absorbed attention in XLA: q (..., T, H, width) scaled, plane
    (..., S, width), mask (..., T, S) -> (..., T, H, kv_rank)."""
    plane = plane.astype(jnp.float32)
    scores = jnp.einsum("...thw,...sw->...hts", q, plane, precision=HIGHEST,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[..., None, :, :], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...hts,...sc->...thc", att,
                      plane[..., :spec.latent.kv_rank], precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def chunk_attn_block(seq_len: int, t_len: int) -> int | None:
    """The block in which a chunk of ``t_len`` queries walks the plane
    (``attend_live``), from the call's shapes alone: T itself where it
    divides ``seq_len`` (a chunk at pos = k T walks exactly k + 1 blocks),
    else ``models/llama._pick_attn_block``'s. None is the whole plane
    (``attend``): T <= 8, the rule of ``models/llama.attention``, or a
    ``seq_len`` no block divides."""
    from .llama import _pick_attn_block

    if t_len <= 8:
        return None
    return t_len if seq_len % t_len == 0 else _pick_attn_block(seq_len)


def chunk_walked_positions(seq_len: int, pos: int, t_len: int) -> int:
    """Positions of the plane that the attention of a chunk of ``t_len``
    rows at ``pos`` reads, a layer: the host's count of what the walk
    does (``ContinuousStats.chunk_walked_positions``)."""
    block = chunk_attn_block(seq_len, t_len)
    if block is None:
        return seq_len
    return min(-(-(pos + t_len) // block) * block, seq_len)


def attend_live(spec: TransformerSpec, q: jax.Array, plane: jax.Array,
                pos: jax.Array, block: int) -> jax.Array:
    """``attend`` for a chunk of ONE sequence, q (T, H, width) scaled at
    positions pos .. pos + T - 1 against plane (S, width): a walk over the
    blocks 0 .. (pos + T - 1) // block that a query of the chunk can see,
    with a running (m, l, o) (``parallel.ring._lse_merge``). A block past
    them is never read; a position inside them that no query sees weighs
    exactly 0, as under ``attend``'s mask. The products are ``attend``'s
    (float32, HIGHEST): only the order of the softmax's sums differs."""
    from ..parallel.ring import _lse_merge

    t_len, n_heads, _ = q.shape
    rank = spec.latent.kv_rank
    q_pos = pos + jnp.arange(t_len)
    n_live = jnp.minimum((pos + t_len + block - 1) // block,
                         plane.shape[0] // block)

    def body(carry):
        b, m, l, o = carry
        blk = jax.lax.dynamic_slice_in_dim(plane, b * block, block,
                                           0).astype(jnp.float32)
        s = jnp.einsum("thw,sw->ths", q, blk, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
        seen = (b * block + jnp.arange(block))[None, :] <= q_pos[:, None]
        s = jnp.where(seen[:, None, :], s, -jnp.inf)
        pm = jnp.max(s, axis=-1, keepdims=True)
        # a row that sees nothing of this block: exp(-inf - 0) = 0
        p = jnp.exp(s - jnp.where(jnp.isfinite(pm), pm, 0.0))
        po = jnp.einsum("ths,sc->thc", p, blk[:, :rank], precision=HIGHEST,
                        preferred_element_type=jnp.float32)
        return (b + 1, *_lse_merge(m, l, o, pm,
                                   jnp.sum(p, axis=-1, keepdims=True), po))

    init = (jnp.int32(0),
            jnp.full((t_len, n_heads, 1), -jnp.inf, jnp.float32),
            jnp.zeros((t_len, n_heads, 1), jnp.float32),
            jnp.zeros((t_len, n_heads, rank), jnp.float32))
    _, _, l, o = jax.lax.while_loop(lambda c: c[0] < n_live, body, init)
    return o / l        # every query sees position 0: l > 0


def attention_out(spec: TransformerSpec, lw: dict[str, Any],
                  o_lat: jax.Array) -> jax.Array:
    """(R, H, kv_rank) -> (R, H * v_dim): each head's W_UV."""
    _, w_uv = absorb_weights(spec, lw)
    out = jnp.einsum("rhc,hvc->rhv", o_lat, w_uv, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return out.reshape(out.shape[0], -1)


def _stacks(spec: TransformerSpec, params: dict[str, Any]):
    """[(first layer, depth, stacked, scanned)] of the two layer stacks."""
    from .llama import split_layer_weights

    out = []
    if spec.n_dense_layers:
        out.append((0, spec.n_dense_layers,
                    *split_layer_weights(params["dense"])))
    out.append((spec.n_dense_layers, spec.n_expert_layers,
                *split_layer_weights(params)))
    return out


def _scan_layers(spec, params, carry, attend_layer, moe_counts):
    """Both stacks' ``lax.scan``: ``attend_layer(lw, h, layer, *planes)``
    -> (attention output (R, H * v), *planes), h the attention sub-layer's
    input: the carry x (R, dim), or the mix of its streams (n, R, dim) that
    a spec with ``hyper`` reads (ops/hyper.py). Returns (carry, the expert
    layers' (L_e, E) routed-rows counts or None)."""
    from .llama import _post_attention, layer_view

    counts = None
    for first, depth, stacked, scanned in _stacks(spec, params):
        want = moe_counts and "moe_gate" in scanned

        def body(carry, per_layer, first=first, stacked=stacked, want=want):
            x, *planes = carry
            idx, lw_slice = per_layer
            lw = layer_view(stacked, lw_slice, idx)
            h, coef = residual_in(spec, lw, "att", x)
            with jax.named_scope(SCOPE_ATTN):
                ao, *planes = attend_layer(lw, h, idx + first, *planes)
            x = _post_attention(spec, lw, x, ao, want, coef)
            x, c = x if want else (x, None)
            return (x, *planes), c

        carry, c = jax.lax.scan(
            body, carry, (jnp.arange(depth, dtype=jnp.int32), scanned))
        counts = c if want else counts
    return carry, counts


def forward_latent(spec: TransformerSpec, params: dict[str, Any],
                   cache: LatentCache, tokens: jax.Array, pos: jax.Array, *,
                   moe_counts: bool = False):
    """``models/llama.forward`` for a latent spec: T tokens of ONE sequence
    at positions pos..pos+T-1 against the contiguous (L, S, width) cache.
    A chunk (T > 8) attends the blocks up to pos + T only
    (``attend_live``); a step scores the whole plane."""
    from .llama import causal_cache_mask

    t_len = tokens.shape[0]
    positions = pos + jnp.arange(t_len)
    with jax.named_scope(SCOPE_EMBED):
        x = fan_out(spec, params["tok_embedding"][tokens].astype(jnp.float32))
    block = chunk_attn_block(spec.seq_len, t_len)
    if block is None:
        mask = causal_cache_mask(spec.seq_len, pos, t_len)

    def attend_layer(lw, x, layer, c_all):
        q, row = latent_qkv(spec, lw, x, positions)
        c_all = jax.lax.dynamic_update_slice(
            c_all, row[None].astype(c_all.dtype), (layer, pos, 0))
        plane = jax.lax.dynamic_index_in_dim(c_all, layer, 0, keepdims=False)
        o_lat = (attend(spec, q, plane, mask) if block is None
                 else attend_live(spec, q, plane, pos, block))
        return attention_out(spec, lw, o_lat), c_all

    (x, c_all), counts = _scan_layers(spec, params, (x, cache.c),
                                      attend_layer, moe_counts)
    with jax.named_scope(SCOPE_LOGITS):
        x = rmsnorm(fold_in(spec, x), params["rms_final"], spec.norm_eps)
        logits = matmul(params["wcls"], x)
    if moe_counts:
        return logits, LatentCache(c_all), counts
    return logits, LatentCache(c_all)


def paged_decode_attention(spec: TransformerSpec, page_size: int,
                           n_pages: int, q: jax.Array, row: jax.Array,
                           c3: jax.Array, layer, pos_b: jax.Array,
                           table: jax.Array):
    """Write each row's latent at (its page, its offset) of the (L*P, ps,
    width) carry, then attend over the row's pages: the kernel on the chip,
    a gather of the row's virtual plane elsewhere."""
    from ..ops.pallas_attention import attn_kernel_mode

    B = q.shape[0]
    new = row.astype(c3.dtype)[:, None, :]
    page_b = jnp.take_along_axis(table, (pos_b // page_size)[:, None],
                                 axis=1)[:, 0]
    off_b = pos_b % page_size
    for b in range(B):     # B in-place row writes, not a scatter (llama.py)
        c3 = jax.lax.dynamic_update_slice(
            c3, new[b:b + 1], (layer * n_pages + page_b[b], off_b[b], 0))
    if attn_kernel_mode() == "pallas":
        from ..ops.pallas_latent_attention import latent_paged_decode

        return latent_paged_decode(
            q, c3, layer, pos_b, table, page_size=page_size,
            n_pages=n_pages, kv_rank=spec.latent.kv_rank), c3
    s_virt = table.shape[1] * page_size
    rows = (layer * n_pages + table).reshape(-1)
    planes = jnp.take(c3, rows, axis=0).reshape(B, s_virt, -1)
    mask = jnp.arange(s_virt)[None, None, :] <= pos_b[:, None, None]
    return attend(spec, q[:, None], planes, mask)[:, 0], c3


def forward_batch_latent_paged(spec: TransformerSpec, page_size: int,
                               params: dict[str, Any], cache: LatentCache,
                               tokens: jax.Array, pos_vec: jax.Array,
                               table: jax.Array, *,
                               moe_counts: bool = False):
    """``models/llama.forward_batch_paged`` for a latent spec: one token
    for each of B rows at its own position against the page pool."""
    B = tokens.shape[0]
    x = fan_out(spec, params["tok_embedding"][tokens].astype(jnp.float32))
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    L, P, ps, width = cache.c.shape

    def attend_layer(lw, x, layer, c3):
        q, row = latent_qkv(spec, lw, x, pos_b)
        o_lat, c3 = paged_decode_attention(spec, page_size, P, q, row, c3,
                                           layer, pos_b, table)
        return attention_out(spec, lw, o_lat), c3

    (x, c3), counts = _scan_layers(
        spec, params, (x, cache.c.reshape(L * P, ps, width)), attend_layer,
        moe_counts)
    x = rmsnorm(fold_in(spec, x), params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)
    cache = LatentCache(c3.reshape(L, P, ps, width))
    return (logits, cache, counts) if moe_counts else (logits, cache)
