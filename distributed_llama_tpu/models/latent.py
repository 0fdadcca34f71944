"""The forward pass of a latent-attention spec (``TransformerSpec.latent``):
low-rank q, ONE cached row ``[c_kv | k_rope]`` a position and layer in
place of K and V, k leading dense layers and then expert layers (two stacks
of weights: ``params["dense"]`` and the top-level keys), a router of the
spec's kind with a shared expert and a share of the routed experts
(ops/pallas_moe). A layer's KIND (``LatentAttn.kinds``) is "full" (causal
over every position: a plane of rows, in pages under ``serve``) or
"sliding" (the last ``window`` positions: a RING of rows, position p at
slot p mod window; k_rope is rotated before it is written, so the order of
the slots means nothing to a softmax). The layers run in the order of the
list, a repeating unit of (kind, stack) a ``lax.scan``
(``models/kindscan.py``); a list of one kind is its trivial case, the two
scans over the two stacks.
A spec with ``hyper`` carries n residual streams (n, R, dim) through the
scans in place of the one (R, dim); ops/hyper.py has the residual function,
which is the plain add for every other spec.
``wkv_b`` expands a row to ``latent_groups`` heads of [k_nope | v], each
shared by n_heads / groups query heads (a head its own: DeepSeek-V3's). With
``noise_heads`` the last head of a group is a NOISE head: a finished
softmax head that is subtracted from each of the group's signal heads,
times a per-token lambda = sigmoid(h w_lambda); with ``gate`` the signal
heads' output is multiplied elementwise by sigmoid(h wg) before ``wo``
(``models/reference_motif.py`` states both).
``models/reference_latent.py`` states the layer in full, EXPANDED (every
position's keys and values formed from its latent row). Here every
dispatch, decode step and prefill chunk alike, runs the ABSORBED schedule:

  q_lat_h = W_UK,h^T q_nope,h          (W_UK,h / W_UV,h: the two halves of
  score   = q_lat . c_kv + q_rope . k_rope          W_kvb's rows for head h)
  o_h     = W_UV,h (softmax . c_kv)

so a cached position is read once, as it lies, by all heads, and is never
expanded: H query heads over one key head of ``latent.width`` whose values
are its first ``kv_rank`` columns. ``wkv_b`` is held as the two float32
stacks the absorbed products need (``w_uk`` / ``w_uv`` (L, G, nope | v,
kv_rank), G the KV groups: a Q40 value dequantizes exactly to float32),
made once at load (``prepare_latent_params``). A group's heads share
``W_UV``, so the noise head is subtracted in the LATENT space, (signal
heads, kv_rank) from (H, kv_rank), before the one ``W_UV`` product.

The contiguous cache (``inference``; an admission's gathered or scratch
sequence) is (F, S, width), F the full layers; the page pool (``serve``)
(F, P, page_size, width) behind the same page tables, allocator, gather and
scatter as a KV pool. A spec with sliding layers (``spec.slotted``) keeps
beside it the rings (W_layers, [rows,] window, width): ``LatentRings``, a
slot of fixed size a sequence, which ``models/llama.slot_model`` hands the
engines through ``forward_batch`` / ``forward_chunk`` / ``insert_sequence``
as it does a mixer-kinds spec's. Decode over pages and rings is the Pallas
kernels of ops/pallas_latent_attention.py on the chip and XLA elsewhere.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.spans import (SCOPE_ATTN, SCOPE_ATTN_DIFF, SCOPE_ATTN_GATE,
                         SCOPE_EMBED, SCOPE_LOGITS, SCOPE_RING_WRITE)
from ..ops.hyper import fan_out, fold_in, residual_in
from ..ops.linear import matmul, rmsnorm
from .kindscan import run_layers
from .spec import MIXER_KINDS, TransformerSpec

HIGHEST = jax.lax.Precision.HIGHEST


TOP_LEVEL = ("tok_embedding", "rms_final", "wcls")


class LatentCache(NamedTuple):
    c: jax.Array  # (F, S, plane) f32, or the pool (F, P, page_size, plane)


class LatentRings(NamedTuple):
    """The cache of a spec with sliding layers: the full layers' planes as
    ``LatentCache`` holds them, and a ring a sliding layer."""
    c: jax.Array
    w: jax.Array  # (W_layers, [rows,] window, plane)


def plane_width(spec: TransformerSpec) -> int:
    """The cached plane's minor dim: ``latent.width`` values a position, in
    whole 128-lane tiles (576 -> 640). The chip stores a minor dim of 576
    in 640 lanes whatever it is told, and the decode kernel's page copies
    must cover whole tiles; the columns past ``width`` hold zeros (a row is
    written padded, a query is padded with zeros), so they add nothing to a
    score."""
    return -(-spec.latent.width // 128) * 128


def _zeros(spec: TransformerSpec, lead: tuple, shape: tuple, dtype):
    """Planes (F, *shape, width) and, a spec with sliding layers, rings
    (W_layers, *lead, window, width) beside them."""
    kinds, width = spec.latent_kinds, plane_width(spec)
    c = jnp.zeros((kinds.count("full"), *shape, width), dtype)
    if not spec.slotted:
        return LatentCache(c)
    return LatentRings(c, jnp.zeros(
        (kinds.count("sliding"), *lead, spec.latent.window, width), dtype))


def gate_offset(spec: TransformerSpec) -> int:
    """Where the elementwise gate's rows start in a prepared ``wkv_a``
    (``prepare_latent_params`` lays ``wg`` behind it: both read the normed
    layer input, and one Q40 call is cheaper than two): the plane's width
    in whole 1024-row steps, so that the leaf's row count keeps large
    tiles (640 + 8,192 = 69 tiles of 128 would take the smallest)."""
    return -(-plane_width(spec) // 1024) * 1024


def init_cache(spec: TransformerSpec, dtype=jnp.float32):
    """One sequence's cache (contiguous planes)."""
    return _zeros(spec, (), (spec.seq_len,), dtype)


def init_cache_paged(spec: TransformerSpec, slots: int, n_pages: int,
                     page_size: int, dtype=jnp.float32):
    """The page pool: physical page p of full layer l is the (page_size,
    width) plane at [l, p] (page 0 is the scrap page, as in a KV pool),
    and ``slots`` rows of rings where the spec has sliding layers."""
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    return _zeros(spec, (slots,), (n_pages, page_size), dtype)


def state_bytes(cache) -> tuple[int, int]:
    """(recurrent state: none, window rings) resident bytes."""
    return 0, int(cache.w.nbytes)


def insert_sequence(cache: LatentRings, one: LatentRings, row,
                    table: jax.Array, page_size: int) -> LatentRings:
    """Put a sequence's cache (``init_cache(spec)``, prefilled) into row
    ``row`` of the paged cache: its rings whole, its planes (F, seq_len,
    width) page by page into the pool through ``table`` (max_pages,)
    (entries past the sequence's pages point at the scrap page)."""
    f, _, width = one.c.shape
    paged = one.c.reshape(f, table.shape[0], page_size, width)
    return LatentRings(
        cache.c.at[:, table].set(paged.astype(cache.c.dtype)),
        jax.lax.dynamic_update_slice(
            cache.w, one.w[:, None].astype(cache.w.dtype), (0, row, 0, 0)))


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
    """(w_uk (G, nope, kv_rank), w_uv (G, v, kv_rank)) of one layer, G its
    KV groups, from the prepared stacks or from ``wkv_b`` as the file has
    it."""
    if "w_uk" in lw:
        return lw["w_uk"], lw["w_uv"]
    from ..ops.linear import dequantize_weight

    la = spec.latent
    w = dequantize_weight(lw["wkv_b"]).astype(jnp.float32).reshape(
        spec.latent_groups, la.nope_dim + la.v_dim, la.kv_rank)
    return w[:, :la.nope_dim], w[:, la.nope_dim:]


def prepare_latent_params(spec: TransformerSpec, params: dict) -> dict:
    """``wkv_b`` of every stack as the float32 ``w_uk`` / ``w_uv`` the
    absorbed products read, and a Q40 ``wkv_a`` with zero rows up to the
    plane's width (``plane_width``: the latent row then comes out of the
    projection as the cache holds it, and the leaf sits on the 128-row
    grid its neighbours pack on; ``latent_qkv`` reads the first ``width``
    outputs), with the elementwise gate's ``wg`` laid behind it where the
    spec has one (``gate_offset``). Host side, once, before packing and
    placement."""
    from ..io.loader import Q40Weight
    from ..ops.quants import dequantize_q40

    out = {k: prepare_latent_params(spec, v) if isinstance(v, dict) else v
           for k, v in params.items()}
    row, gate = out.get("wkv_a"), out.get("wg")
    if isinstance(row, Q40Weight):
        fuse = isinstance(gate, Q40Weight)
        rows = gate_offset(spec) if fuse else plane_width(spec)
        pad = [(0, 0)] * (row.qs.ndim - 3) + [(0, rows - row.qs.shape[-3])]
        qs = np.pad(row.qs, pad + [(0, 0), (0, 0)])
        d16 = np.pad(row.d16, pad + [(0, 0)])
        if fuse:    # the gate's rows behind the plane's (``gate_offset``)
            gate = out.pop("wg")
            qs = np.concatenate([qs, gate.qs], axis=-3)
            d16 = np.concatenate([d16, gate.d16], axis=-2)
        out["wkv_a"] = Q40Weight(qs, d16)
    w = out.pop("wkv_b", None)
    if w is not None:
        la = spec.latent
        w = dequantize_q40(w.qs, w.d16) if isinstance(w, Q40Weight) \
            else np.asarray(w, np.float32)
        w = w.reshape(w.shape[0], spec.latent_groups,
                      la.nope_dim + la.v_dim, la.kv_rank)
        out["w_uk"] = np.ascontiguousarray(w[:, :, :la.nope_dim])
        out["w_uv"] = np.ascontiguousarray(w[:, :, la.nope_dim:])
    return out


def latent_qkv(spec: TransformerSpec, lw: dict[str, Any], x: jax.Array,
               positions: jax.Array, h: jax.Array | None = None,
               kv: jax.Array | None = None):
    """Rows x (R, dim), row r at positions[r] -> (q (R, H, plane) SCALED
    absorbed queries [q_lat | q_rope | 0], row (R, plane) [c_kv | k_rope |
    0]: what the cache holds of each row; ``plane_width`` says why the
    zeros). ``h``: the rows normed already (``rms_att``) and ``kv``:
    ``wkv_a``'s projection of them, where the caller reads them too."""
    la, nh, eps = spec.latent, spec.n_heads, spec.norm_eps
    freq, factor, scale = rope_frequencies(spec)
    if h is None:
        h = rmsnorm(x, lw["rms_att"], eps)
    if "wq" in lw:      # no query rank (a kda spec's): ONE matrix
        q = matmul(lw["wq"], h)
    else:
        c_q = rmsnorm(matmul(lw["wq_a"], h), lw["rms_q_a"], eps)
        q = matmul(lw["wq_b"], c_q)
    q = q.reshape(-1, nh, la.qk_dim)
    # wkv_a's outputs past ``width`` are zero rows (prepare_latent_params)
    kv = (matmul(lw["wkv_a"], h) if kv is None else kv)[:, :la.width]
    c_kv = rmsnorm(kv[:, :la.kv_rank], lw["rms_kv_a"], eps)
    k_rope = _rope(kv[:, la.kv_rank:], positions, freq, factor)
    q_rope = _rope(q[..., la.nope_dim:], positions, freq, factor)
    w_uk, _ = absorb_weights(spec, lw)
    q_lat = _by_group(spec, "rgpn,gnc->rgpc", q[..., :la.nope_dim], w_uk)
    pad = plane_width(spec) - la.width
    q = jnp.concatenate([q_lat, q_rope], axis=-1) * jnp.float32(scale)
    row = jnp.concatenate([c_kv, k_rope], axis=-1)
    return (jnp.pad(q, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(row, ((0, 0), (0, pad))))


def _by_group(spec: TransformerSpec, eq: str, a: jax.Array, w: jax.Array):
    """a (R, heads, n) times each head's GROUP's w (G, ...) by ``eq`` over
    (r, g, p: the head's place in its group, ...) -> (R, heads, ...). A head
    its own group is the one product over heads that it was."""
    if w.shape[0] == a.shape[1]:
        eq = eq.replace("p", "")
        return jnp.einsum(eq, a, w, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    r, heads, n = a.shape
    out = jnp.einsum(eq, a.reshape(r, w.shape[0], -1, n), w,
                     precision=HIGHEST, preferred_element_type=jnp.float32)
    return out.reshape(r, heads, -1)


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


def signal_lambda(lw: dict[str, Any], h: jax.Array) -> jax.Array:
    """(R, signal heads) in (0, 1): each signal head's share of its
    group's noise head, a sigmoid of the normed layer input, float32 at
    highest precision (``w_lambda`` is not quantized)."""
    return jax.nn.sigmoid(jnp.einsum(
        "rn,sn->rs", h, lw["w_lambda"], precision=HIGHEST,
        preferred_element_type=jnp.float32))


def attention_out(spec: TransformerSpec, lw: dict[str, Any],
                  o_lat: jax.Array, h: jax.Array | None = None) -> jax.Array:
    """(R, H, kv_rank) -> (R, signal heads * v_dim): the noise heads
    subtracted from their groups' signal heads in the latent space (``h``
    (R, dim) the normed layer input that lambda reads), then each head's
    group's W_UV."""
    _, w_uv = absorb_weights(spec, lw)
    if not spec.latent.noise_heads:
        out = _by_group(spec, "rgpc,gvc->rgpv", o_lat, w_uv)
        return out.reshape(out.shape[0], -1)
    with jax.named_scope(SCOPE_ATTN_DIFF):
        r, _, rank = o_lat.shape
        o = o_lat.reshape(r, spec.latent_groups, -1, rank)
        lam = signal_lambda(lw, h).reshape(r, spec.latent_groups, -1, 1)
        d_lat = (o[:, :, :-1] - lam * o[:, :, -1:]).reshape(r, -1, rank)
        return _by_group(spec, "rgpc,gvc->rgpv", d_lat, w_uv).reshape(r, -1)


def output_gate(spec: TransformerSpec, lw: dict[str, Any], h: jax.Array,
                kv: jax.Array, ao: jax.Array, live: jax.Array | None = None):
    """ao (R, signal heads * v_dim) times sigmoid(h wg), elementwise; and
    the gate's (smallest, mean) over the ``live`` rows (default all). The
    gate's logits are ``wg``'s projection of ``h``, or the columns of
    ``kv`` (``wkv_a``'s) behind ``gate_offset`` where the two were laid in
    one leaf."""
    with jax.named_scope(SCOPE_ATTN_GATE):
        g = jax.nn.sigmoid(matmul(lw["wg"], h) if "wg" in lw
                           else kv[:, gate_offset(spec):])
        if live is None:
            return ao * g, (jnp.min(g), jnp.mean(g))
        n = jnp.maximum(jnp.sum(live), 1) * g.shape[-1]
        return ao * g, (jnp.min(jnp.where(live[:, None], g, 1.0)),
                        jnp.sum(jnp.where(live[:, None], g, 0.0)) / n)


def layer_sigs(spec: TransformerSpec) -> list:
    """[(kind, FFN stack)] a layer: its signature for ``kindscan``. The
    kind names no weights (a full and a sliding layer's tensors are the
    same), so its "stack" is empty and its index counts the layers of the
    kind before: a full layer's plane, a sliding layer's ring."""
    k = spec.n_dense_layers
    return [(kind, "dense" if i < k else "")
            for i, kind in enumerate(spec.latent_kinds)]


def _stack(params: dict, name: str) -> dict:
    if name in MIXER_KINDS:
        return {}
    if name:
        return params[name]
    return {k: v for k, v in params.items()
            if k not in TOP_LEVEL and not isinstance(v, dict)}


class _Carry(NamedTuple):
    x: jax.Array        # (R, dim), or the streams (n, R, dim)
    c: jax.Array        # the full layers' planes or pool, as the entry views it
    w: Any              # the sliding layers' rings likewise (None: no such layer)
    counts: Any         # (L_e, E) routed-rows counts, or None
    gate: Any           # (smallest, sum of means) of the gate, or None


def _run(spec, params, x, cache, attend_kind, positions, moe_counts: bool,
         live=None):
    """Every layer, a repeating unit of the list a scan (``models/
    kindscan.py``): the residual path, the low-rank projections, the
    kind's attention ``attend_kind(kind, index among the kind's layers, q,
    row, carry)`` -> (o_lat (R, H, kv_rank), carry), the noise heads' fold,
    gate and ``wo``, the FFN. ``cache`` is (planes, rings or None) as the
    entry views them. Returns the carry."""
    from .llama import _post_attention

    la = spec.latent
    reads_h = bool(la.noise_heads or la.gate)
    counts = (jnp.zeros((spec.n_expert_layers, spec.n_experts), jnp.int32)
              if moe_counts else None)
    gauges = (jnp.float32(1.0), jnp.float32(0.0)) if la.gate else None

    def layer_fn(sig, lw, c: _Carry, layer, idx):
        kind, ffn = sig
        h, coef = residual_in(spec, lw, "att", c.x)
        with jax.named_scope(SCOPE_ATTN):
            hn = kv = None
            if reads_h:     # lambda and the gate read the normed input too
                hn = rmsnorm(h, lw["rms_att"], spec.norm_eps)
                kv = matmul(lw["wkv_a"], hn)
            q, row = latent_qkv(spec, lw, h, positions, hn, kv)
            o_lat, c = attend_kind(kind, idx[kind], q, row, c)
            ao = attention_out(spec, lw, o_lat, hn)
            if la.gate:
                ao, g = output_gate(spec, lw, hn, kv, ao, live)
                c = c._replace(gate=(jnp.minimum(c.gate[0], g[0]),
                                     c.gate[1] + g[1]))
        want = c.counts is not None and "moe_gate" in lw
        x = _post_attention(spec, lw, c.x, ao, want, coef)
        if want:
            x, n = x
            c = c._replace(counts=jax.lax.dynamic_update_slice(
                c.counts, n[None], (idx[ffn], 0)))
        return c._replace(x=x)

    return run_layers(layer_sigs(spec), functools.partial(_stack, params),
                      _Carry(x, *cache, counts, gauges), layer_fn)


def _results(spec, logits, cache, carry: _Carry, health: bool):
    """(logits, cache[, the gate's (smallest, mean) where asked for][, the
    routed-rows counts])."""
    more = []
    if health:
        lo, total = carry.gate or (jnp.float32(1.0),
                                   jnp.float32(0.5 * spec.n_layers))
        more.append(jnp.stack([lo, total / spec.n_layers]))
    if carry.counts is not None:
        more.append(carry.counts)
    return (logits, cache, *more)


def forward_latent(spec: TransformerSpec, params: dict[str, Any], cache,
                   tokens: jax.Array, pos: jax.Array, n_valid=None, *,
                   xdec: bool = True, health: bool = False,
                   moe_counts: bool = False):
    """``models/llama.forward`` for a latent spec: T tokens of ONE sequence
    at positions pos..pos+T-1 against its contiguous cache
    (``init_cache(spec)``). In a full layer a chunk (T > 8) attends the
    blocks up to pos + T only (``attend_live``) and a step scores the whole
    plane; a sliding layer reads its ring as it stands and the chunk's own
    rows (``models/sambay.ring_plan``) and leaves the ring the newest
    ``window`` positions. Of a chunk's positions the first ``n_valid``
    (default all) are the sequence's and the rest padding that reaches
    neither a ring nor a plane. ``xdec=False`` (the name a hybrid spec's
    chunk gave it: what a prefill needs) leaves the classifier out and
    returns logits of shape (0, vocab); ``health`` adds the elementwise
    gate's (smallest, mean) as a (2,) array, ``moe_counts`` the (L_e, E)
    routed-rows counts."""
    from .llama import causal_cache_mask

    t_len = tokens.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos + jnp.arange(t_len)
    S = spec.seq_len
    with jax.named_scope(SCOPE_EMBED):
        x = fan_out(spec, params["tok_embedding"][tokens].astype(jnp.float32))
    block = chunk_attn_block(S, t_len)
    if block is None:
        mask = causal_cache_mask(S, pos, t_len)
    valid = None
    if n_valid is not None:
        n_valid = jnp.minimum(n_valid, t_len)
        valid = jnp.arange(t_len) < n_valid
        kv_at = jnp.where(valid, positions, S)      # padding is dropped
    rings = getattr(cache, "w", None)
    if rings is not None:
        from .sambay import ring_plan

        win_mask, from_chunk, take = ring_plan(
            spec.latent.window, pos, t_len if n_valid is None else n_valid,
            t_len)

    def attend_kind(kind, idx, q, row, c: _Carry):
        if kind == "sliding":
            ring = jax.lax.dynamic_index_in_dim(c.w, idx, 0, False)
            o_lat = attend(spec, q, jnp.concatenate(
                [ring, row.astype(ring.dtype)]), win_mask)
            with jax.named_scope(SCOPE_RING_WRITE):
                ring = jnp.where(from_chunk[0], row[take].astype(ring.dtype),
                                 ring)
                return o_lat, c._replace(
                    w=jax.lax.dynamic_update_slice_in_dim(c.w, ring[None],
                                                          idx, 0))
        if valid is None:
            c_all = jax.lax.dynamic_update_slice(
                c.c, row[None].astype(c.c.dtype), (idx, pos, 0))
        else:
            c_all = c.c.at[idx, kv_at].set(row.astype(c.c.dtype),
                                           mode="drop")
        plane = jax.lax.dynamic_index_in_dim(c_all, idx, 0, keepdims=False)
        o_lat = (attend(spec, q, plane, mask) if block is None
                 else attend_live(spec, q, plane, pos, block))
        return o_lat, c._replace(c=c_all)

    carry = _run(spec, params, x, (cache.c, rings), attend_kind, positions,
                 moe_counts, valid)
    if xdec:
        with jax.named_scope(SCOPE_LOGITS):
            x = rmsnorm(fold_in(spec, carry.x), params["rms_final"],
                        spec.norm_eps)
            logits = matmul(params["wcls"], x)
    else:
        logits = jnp.zeros((0, spec.vocab_size), jnp.float32)
    out = (LatentCache(carry.c) if rings is None
           else LatentRings(carry.c, carry.w))
    return _results(spec, logits, out, carry, health)


forward_chunk = forward_latent      # ``models/llama.slot_model``'s name


def paged_decode_attention(spec: TransformerSpec, page_size: int,
                           n_pages: int, q: jax.Array, row: jax.Array,
                           c3: jax.Array, layer, pos_b: jax.Array,
                           table: jax.Array):
    """Write each row's latent at (its page, its offset) of the (F*P, ps,
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


def ring_decode_attention(spec: TransformerSpec, q: jax.Array,
                          row: jax.Array, w3: jax.Array, layer,
                          pos_b: jax.Array):
    """Write each row's latent at slot pos mod window of its ring (plane
    layer * B + b of the (W_layers*B, window, width) carry), then attend
    over the slots the sequence has written: the ring kernel on the chip,
    a masked einsum elsewhere."""
    from ..ops.pallas_attention import attn_kernel_mode

    B, window = q.shape[0], w3.shape[1]
    new = row.astype(w3.dtype)[:, None, :]
    with jax.named_scope(SCOPE_RING_WRITE):
        for b in range(B):
            w3 = jax.lax.dynamic_update_slice(
                w3, new[b:b + 1], (layer * B + b, pos_b[b] % window, 0))
    if attn_kernel_mode() == "pallas":
        from ..ops.pallas_latent_attention import latent_ring_decode

        return latent_ring_decode(q, w3, layer, pos_b,
                                  kv_rank=spec.latent.kv_rank), w3
    rings = jax.lax.dynamic_slice_in_dim(w3, layer * B, B, 0)
    mask = jnp.arange(window)[None, None, :] <= jnp.minimum(
        pos_b, window - 1)[:, None, None]
    return attend(spec, q[:, None], rings, mask)[:, 0], w3


def forward_batch(spec: TransformerSpec, params: dict[str, Any], cache,
                  tokens: jax.Array, pos_vec: jax.Array, table: jax.Array,
                  active: jax.Array | None = None, *, page_size: int,
                  health: bool = False, moe_counts: bool = False):
    """``models/llama.forward_batch_paged`` for a latent spec: one token
    for each of B rows at its own position against the page pool (and the
    rows' rings). A row whose ``active`` ((B,), nonzero = takes part;
    default all) is 0 rides the step: its ring and page writes land where
    its own re-run, or nobody, reads them. Results as ``forward_latent``."""
    B = tokens.shape[0]
    x = fan_out(spec, params["tok_embedding"][tokens].astype(jnp.float32))
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    live = None if active is None else active != 0
    F, P, ps, width = cache.c.shape
    rings = getattr(cache, "w", None)

    def attend_kind(kind, idx, q, row, c: _Carry):
        if kind == "sliding":
            o_lat, w3 = ring_decode_attention(spec, q, row, c.w, idx, pos_b)
            return o_lat, c._replace(w=w3)
        o_lat, c3 = paged_decode_attention(spec, page_size, P, q, row, c.c,
                                           idx, pos_b, table)
        return o_lat, c._replace(c=c3)

    carry = _run(
        spec, params, x,
        (cache.c.reshape(F * P, ps, width),
         None if rings is None else rings.reshape(-1, *rings.shape[2:])),
        attend_kind, pos_b, moe_counts, live)
    x = rmsnorm(fold_in(spec, carry.x), params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)
    c = carry.c.reshape(F, P, ps, width)
    out = (LatentCache(c) if rings is None
           else LatentRings(c, carry.w.reshape(rings.shape)))
    return _results(spec, logits, out, carry, health)
