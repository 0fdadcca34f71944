"""Functional JAX Llama-2 forward pass (7B/13B/70B incl. GQA).

The single-chip "program" that replaces the reference's 32-step root task table
(src/transformer-tasks.cpp:485-518): one traced function, `lax.scan` over
stacked layer weights, static shapes throughout. Numerics follow the parity
contract in SURVEY.md §5:

* RoPE: interleaved (i, i+1) pairs, freq = 10000^-( (i mod headSize)/headSize ),
  q rotated over the full dim, k over kvDim (transformer-tasks.cpp:228-242).
* Attention: score = q.k/sqrt(headSize); GQA maps query head h to kv head
  h // kvMul (transformer-tasks.cpp:214,254,268). KV cache copies kvDim floats
  (the reference's dim-float memcpy at transformer-tasks.cpp:224-225 is the
  documented over-read bug; we implement the spec, not the bug).
* SwiGLU: silu(w1 x) * (w3 x), silu(x) = x/(1+e^-x).
* rmsnorm with eps=1e-5 added after the mean.
* When buffer_float_type == Q80, matmul inputs pass through Q80
  quantize->dequantize at the points the reference feeds quantized buffers to
  its kernels (the quantize* tasks).

The forward consumes T tokens at positions pos..pos+T-1 against a seq_len-sized
KV cache — T=1 is single-token decode (the reference's only mode), T>1 is
chunked prefill (a capability the reference lacks; it replays the decode path
per prompt token, tokenizer.cpp:352-366).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..io.loader import Q40Kernel, Q40KernelNb, Q40KernelNbI4
# the single-chip forward emits the SAME canonical trace scopes as the tp
# forward (parallel/tp.py), so a --profile capture of either program
# attributes through one vocabulary of scope names (obs/spans.py)
from ..obs.spans import (SCOPE_ATTN, SCOPE_ATTN_SINK, SCOPE_EMBED, SCOPE_FFN,
                         SCOPE_LOGITS)
from ..ops.hyper import residual_in, residual_out
from ..ops.linear import (StackedQ40, fake_quant_q80, ffn_activation,
                          gated_product, matmul,
                          rmsnorm)
from ..ops.quants import FloatType
from .spec import TransformerSpec


class KVCache(NamedTuple):
    k: jax.Array  # (n_layers, seq_len, n_kv_heads, head_size) f32
    v: jax.Array


class StateCache(NamedTuple):
    """A retention spec's per-sequence memory: a state of fixed size a
    layer (ops/retention.py has the layout), whatever the context."""
    s: jax.Array  # (n_layers, [B,] n_kv, n_off, hs, hs) f32: [o, value, key]
    z: jax.Array  # (n_layers, [B,] n_kv, n_off, hs) f32: the normaliser's


def init_state(spec: TransformerSpec, batch: int | None = None) -> StateCache:
    """The empty state of one sequence, or of ``batch`` rows."""
    from ..ops.retention import state_shapes

    s, z = state_shapes(spec.n_kv_heads, spec.head_size)
    lead = (spec.n_layers,) if batch is None else (spec.n_layers, batch)
    return StateCache(jnp.zeros(lead + s, jnp.float32),
                      jnp.zeros(lead + z, jnp.float32))


def slot_model(spec: TransformerSpec):
    """The module that runs a spec whose sequences keep a slot of fixed
    size AND pages (``spec.slotted``): ``models/sambay`` (a hybrid spec),
    ``models/laguna`` (a mixer-kinds spec), ``models/nemotron`` (an ssd
    spec), ``models/kda`` (a kda spec) or ``models/latent`` (a latent spec
    with sliding layers). Each gives ``init_cache``,
    ``init_cache_paged``, ``insert_sequence``, ``state_bytes``,
    ``forward_batch`` and ``forward_chunk``."""
    if spec.mixers:
        from . import laguna

        return laguna
    if spec.ssd:
        from . import nemotron

        return nemotron
    if spec.kda:
        from . import kda

        return kda
    if spec.latent:
        from . import latent

        return latent
    from . import sambay

    return sambay


def slot_counts(spec: TransformerSpec) -> dict:
    """The keyword with which a slotted spec's forwards also hand out an
    expert spec's (L_e, E) routed-rows counts (a mixer-kinds or a latent
    spec's: a hybrid spec has a dense FFN), for the engines'
    ``functools.partial``."""
    return {"moe_counts": True} if (spec.mixers or spec.latent
                                    or spec.ssd) and spec.n_experts else {}


def init_cache(spec: TransformerSpec, dtype=jnp.float32):
    if spec.slotted:    # state / window rings and the full layers' K / V
        return slot_model(spec).init_cache(spec, dtype=dtype)
    if spec.retention:  # float32 whatever ``dtype``: nothing scales with S
        return init_state(spec)
    if spec.latent:     # planes of [c_kv | k_rope] rows in place of K and V
        from .latent import init_cache as init_latent

        return init_latent(spec, dtype)
    shape = (spec.n_layers, spec.seq_len, spec.n_kv_heads, spec.head_size)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def rope_rotate(x: jax.Array, positions: jax.Array, head_size: int,
                theta: float = 10000.0) -> jax.Array:
    """Interleaved-pair RoPE over the leading ``x.shape[-1]`` features.

    x: (T, n), positions: (T,). Pair p = features (2p, 2p+1); the angle uses
    head_dim = (2p) mod head_size, matching the reference's per-element loop.
    """
    n = x.shape[-1]
    pairs = x.reshape(*x.shape[:-1], n // 2, 2)
    i = jnp.arange(0, n, 2, dtype=jnp.float32)  # feature index of each pair
    head_dim = jnp.mod(i, head_size)
    freq = 1.0 / jnp.power(jnp.float32(theta), head_dim / head_size)
    val = positions[:, None].astype(jnp.float32) * freq[None, :]  # (T, n/2)
    fcr, fci = jnp.cos(val), jnp.sin(val)
    v0, v1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([v0 * fcr - v1 * fci, v0 * fci + v1 * fcr],
                     axis=-1).reshape(x.shape)


def _maybe_q80(spec: TransformerSpec, x: jax.Array) -> jax.Array:
    if spec.buffer_float_type == FloatType.Q80:
        return fake_quant_q80(x)
    return x


def attention_core(head_size: int, kv_mul: int, q: jax.Array, k: jax.Array,
                   v: jax.Array, mask: jax.Array,
                   sink: jax.Array | None = None) -> jax.Array:
    """Grouped-GQA causal attention — THE attention math, shared by the
    single-chip, sequence (training), and tensor-parallel paths.

    q: (..., T, n_q, hs) reshaped to kv groups; k/v: (..., S, n_kv, hs) (v's
    last dim may be another: the output's is v's); mask: (T, S) True where
    key position is visible; ``sink`` (n_q,) or None: a score a query head
    that joins its softmax as one more column and carries no value
    (a mixer-kinds spec's: models/laguna.py). Query head h = g*kv_mul+m
    attends kv head g = h//kv_mul (transformer-tasks.cpp:214), via einsum
    against the unexpanded cache (no materialized kv_mul-fold repeat).
    Masking with -inf before the max-subtracted softmax reproduces the
    reference's 0..pos loop bounds exactly. f32 accumulation at HIGHEST
    precision (the logit-parity contract).
    """
    *lead, t_len, n_q, _ = q.shape
    n_kv = k.shape[-2]
    qg = q.reshape(*lead, t_len, n_kv, kv_mul, head_size)
    scale = 1.0 / jnp.sqrt(jnp.float32(head_size))
    # fast-prefill (trace-time flag): bf16 MXU passes for the score and
    # weighted-sum einsums, f32 accumulation + f32 softmax — the same
    # documented-tolerance contract as the matmuls (ops/linear)
    from ..ops.linear import matmul_mode

    prec = (None if matmul_mode() == "bf16"
            else jax.lax.Precision.HIGHEST)
    scores = jnp.einsum("...tgmd,...sgd->...gmts", qg, k,
                        preferred_element_type=jnp.float32,
                        precision=prec) * scale
    scores = jnp.where(mask[..., None, None, :, :], scores, -jnp.inf)
    if sink is None:
        att = jax.nn.softmax(scores, axis=-1)
    else:
        with jax.named_scope(SCOPE_ATTN_SINK):
            col = jnp.broadcast_to(
                sink.astype(jnp.float32).reshape(n_kv, kv_mul, 1, 1),
                (*scores.shape[:-1], 1))
            att = jax.nn.softmax(jnp.concatenate([scores, col], axis=-1),
                                 axis=-1)[..., :-1]
    out = jnp.einsum("...gmts,...sgd->...tgmd", att, v,
                     preferred_element_type=jnp.float32,
                     precision=prec)
    return out.reshape(*lead, t_len, n_q * v.shape[-1])


def causal_cache_mask(seq_len: int, pos: jax.Array, t_len: int) -> jax.Array:
    """(T, S) visibility of cache slots for queries at pos..pos+T-1."""
    q_pos = pos + jnp.arange(t_len)
    return jnp.arange(seq_len)[None, :] <= q_pos[:, None]


def _prefill_attn_mode() -> str:
    """T>8 attention strategy — DLLAMA_PREFILL_ATTN: 'flash' (in-VMEM
    Pallas online-softmax walk over live KV blocks, scores never touch
    HBM — ops/pallas_attention.prefill_attention), 'block' (while_loop of
    XLA einsum partials over live KV blocks), 'dense' (score the whole
    seq_len plane, mask the rest), 'auto' (= flash where the kernel +
    pallas backend apply, else block). Read at trace time — programs
    already traced (an existing Engine's cached jits) keep the mode they
    were traced with; construct a new Engine to change it. Unknown values
    raise (a typo would otherwise silently run a slower path)."""
    import os

    mode = os.environ.get("DLLAMA_PREFILL_ATTN") or "auto"  # '' = unset
    if mode not in ("auto", "flash", "block", "dense"):
        raise ValueError(f"DLLAMA_PREFILL_ATTN={mode!r}: "
                         f"expected auto|flash|block|dense")
    return mode


_flash_degrade_warned = False


def _warn_flash_degrade(spec: TransformerSpec, t_len: int) -> None:
    """One-time loud warning when an EXPLICIT DLLAMA_PREFILL_ATTN=flash
    cannot take the Pallas kernel and degrades to the blockwise XLA walk.
    'auto' degrading silently is by design; an explicit mode falling back
    silently violates the fail-loud policy (_prefill_attn_mode raises on
    typos for the same reason). A warning, not a raise: the walk computes
    the same attention, just slower — aborting a long run over a perf mode
    would be worse. Fires at trace time, once per process."""
    global _flash_degrade_warned
    if _flash_degrade_warned:
        return
    _flash_degrade_warned = True
    import sys

    from ..ops.pallas_attention import attn_kernel_mode

    print(f"⚠️  DLLAMA_PREFILL_ATTN=flash requested but the Pallas prefill "
          f"kernel does not apply (attn kernel mode "
          f"{attn_kernel_mode()!r}, seq_len {spec.seq_len}, head_size "
          f"{spec.head_size}, chunk T={t_len}, kv_mul {spec.kv_mul}); "
          f"falling back to the blockwise XLA walk for this trace. Use "
          f"DLLAMA_PREFILL_ATTN=block to pick the walk explicitly, or "
          f"unset the variable for auto.", file=sys.stderr)


def _pick_attn_block(seq_len: int) -> int | None:
    """Largest KV block <= 512 dividing seq_len (None -> dense path)."""
    for cand in (512, 256, 128, 64, 32):
        if seq_len % cand == 0:
            return cand
    return None


def _attention_blockwise(spec: TransformerSpec, q: jax.Array,
                         k_cache: jax.Array, v_cache: jax.Array,
                         pos: jax.Array, t_len: int,
                         block: int) -> jax.Array:
    """Prefill attention with work bounded by the LIVE prefix: a while_loop
    over ceil((pos+T)/block) KV blocks with running-LSE accumulation
    (parallel.ring._partial_attention — the same flash partials the sp and
    ring paths use), merged block by block.

    The dense path (attention_core) scores every one of seq_len cache slots
    and masks the dead ones — at seq_len 8192 an early chunk of a
    long-context prefill wastes ~4x its attention FLOPs and score traffic
    on masked keys (measured ~35% of deep-chunk op time, BASELINE.md r3
    ladder note 4). Same masking contract, f32 accumulation; online-softmax
    reassociation only (prefill parity tolerances unchanged). The walk
    itself is parallel.ring.blockwise_chunk_partials (shared with the
    sp-sharded path), with chunk_start=0 for the unsharded plane.
    """
    from ..ops.linear import matmul_mode
    from ..parallel.ring import blockwise_chunk_partials  # lazy: no cycle

    q_pos = pos + jnp.arange(t_len)
    _, l, o = blockwise_chunk_partials(
        spec.head_size, spec.kv_mul, q, k_cache, v_cache, jnp.int32(0),
        q_pos, block=block, bf16=matmul_mode() == "bf16")
    return (o / jnp.maximum(l, 1e-38)).reshape(t_len, -1)


def attention(spec: TransformerSpec, q: jax.Array, k_cache: jax.Array,
              v_cache: jax.Array, pos: jax.Array, t_len: int) -> jax.Array:
    """Causal attention of t_len new queries against the full cache.

    q: (T, n_heads, head_size); caches: (seq_len, n_kv_heads, head_size).
    Returns (T, dim). T>8 (prefill chunks) takes the blockwise live-prefix
    path by default; T<=8 and the dense fallback score the full plane.
    """
    mode = _prefill_attn_mode() if t_len > 8 else "dense"
    if mode in ("auto", "flash"):
        from ..ops.pallas_attention import (attn_kernel_mode,
                                            prefill_attention,
                                            supports_prefill)

        if (attn_kernel_mode() == "pallas"
                and supports_prefill(spec.seq_len, spec.head_size, t_len,
                                     spec.kv_mul, n_kv=k_cache.shape[1],
                                     itemsize=k_cache.dtype.itemsize)):
            from ..ops.linear import matmul_mode

            out = prefill_attention(q, k_cache, v_cache, pos,
                                    kv_mul=spec.kv_mul,
                                    bf16=matmul_mode() == "bf16")
            return out.reshape(t_len, -1)
        if mode == "flash":  # explicit request degrading: say so, once
            _warn_flash_degrade(spec, t_len)
        mode = "block" if mode == "auto" else mode
    if mode in ("block", "flash"):  # flash unsupported here: live-prefix walk
        block = _pick_attn_block(spec.seq_len)
        if block is not None:
            return _attention_blockwise(spec, q, k_cache, v_cache, pos,
                                        t_len, block)
    mask = causal_cache_mask(spec.seq_len, pos, t_len)
    return attention_core(spec.head_size, spec.kv_mul, q, k_cache, v_cache,
                          mask)


def _qkv_proj(spec: TransformerSpec, lw: dict[str, Any], x: jax.Array,
              positions: jax.Array):
    """Shared attention input path: norm -> (q80) -> q/k/v matmuls -> RoPE.

    Works on (T, dim) or batched (B, T, dim) activations.
    """
    xb = rmsnorm(x, lw["rms_att"], spec.norm_eps)
    xb = _maybe_q80(spec, xb)
    if "wqkv" in lw:  # load-time fused kernel (ops/linear.fuse_q40_layer_matmuls)
        qkv = matmul(lw["wqkv"], xb)
        kv_dim = spec.n_kv_heads * spec.head_size
        q = qkv[..., :spec.dim]
        k = qkv[..., spec.dim:spec.dim + kv_dim]
        v = qkv[..., spec.dim + kv_dim:]
    else:
        q = matmul(lw["wq"], xb)
        k = matmul(lw["wk"], xb)
        v = matmul(lw["wv"], xb)
    if spec.qk_norm_per_head:
        # ONE gain of head size, each head normed on its own, before RoPE
        def per_head(a, gain):
            heads = a.reshape(*a.shape[:-1], -1, spec.head_size)
            return rmsnorm(heads, gain, spec.norm_eps).reshape(a.shape)

        q = per_head(q, lw["rms_q"])
        k = per_head(k, lw["rms_k"])
    elif spec.qk_norm:
        # gains over the WHOLE projection (not per head), before RoPE
        q = rmsnorm(q, lw["rms_q"], spec.norm_eps)
        k = rmsnorm(k, lw["rms_k"], spec.norm_eps)

    def rot(a):
        return rope_rotate(a, positions, spec.head_size, spec.rope_theta)

    if x.ndim == 3:
        rot_fn = jax.vmap(rot)
    else:
        rot_fn = rot
    return rot_fn(q), rot_fn(k), v


def _swiglu(spec: TransformerSpec, lw: dict[str, Any], xb: jax.Array,
            prefix: str = "") -> jax.Array:
    """w2(act(w1 xb) * w3 xb) (w2(act(w1 xb)) where the spec's FFN is not
    gated), act the spec's activation (SiLU unless it
    states another: ops/linear.ffn_activation), of the leaves ``prefix + w1 | w2 | w3`` (or
    their load-time fusion ``prefix + w13``: linear.fuse_q40_layer_matmuls)."""
    if not spec.activation.gated:   # one up matrix, no product
        act = ffn_activation(spec, lw)
        return matmul(lw[prefix + "w2"], _maybe_q80(
            spec, act(matmul(lw[prefix + "w1"], xb))))
    product = gated_product(spec, lw, prefix)
    if prefix + "w13" in lw:
        h13 = matmul(lw[prefix + "w13"], xb)
        hid = h13.shape[-1] // 2
        hb = product(h13[..., :hid], h13[..., hid:])
    else:
        hb = product(matmul(lw[prefix + "w1"], xb),
                     matmul(lw[prefix + "w3"], xb))
    return matmul(lw[prefix + "w2"], _maybe_q80(spec, hb))


def _post_attention(spec: TransformerSpec, lw: dict[str, Any], x: jax.Array,
                    ao: jax.Array, moe_counts: bool = False, coef=None):
    """Shared layer tail: wo + residual, then the ffn sub-block: SwiGLU, or
    for an EXPERT LAYER (one whose weights hold a router: an expert spec's
    leading dense layers hold none) the router and the routed experts
    (ops/pallas_moe) plus the shared expert where the layer has one.
    ``moe_counts`` (expert layers only) also returns the (E,) int32 count of
    rows routed to each expert: ``(x, counts)``. Both residuals go through
    ops/hyper's ``residual_in`` / ``residual_out``: the plain add, unless
    the spec carries several streams (x is then (n, R, dim) and ``coef``
    what ``residual_in`` gave the caller for the attention sub-layer)."""
    clamp = spec.hyper.stream_clamp if spec.hyper else 0.0
    with jax.named_scope(SCOPE_ATTN):
        ao = _maybe_q80(spec, ao)
        x = residual_out(coef, x, matmul(lw["wo"], ao), clamp)
    h, coef = residual_in(spec, lw, "ffn", x)
    with jax.named_scope(SCOPE_FFN):
        xb = rmsnorm(h, lw["rms_ffn"], spec.norm_eps)
        xb = _maybe_q80(spec, xb)
        if "moe_gate" in lw:
            from ..ops.pallas_moe import moe_ffn

            y, counts = moe_ffn(spec, lw, xb)
            if "sh_w2" in lw:
                y = y + _swiglu(spec, lw, xb, "sh_")
            x = residual_out(coef, x, y, clamp)
            return (x, counts) if moe_counts else x
        return residual_out(coef, x, _swiglu(spec, lw, xb), clamp)


def _layer(spec: TransformerSpec, x: jax.Array, lw: dict[str, Any],
           k_all: jax.Array, v_all: jax.Array, idx, pos: jax.Array,
           positions: jax.Array, moe_counts: bool = False):
    """One transformer layer against the STACKED (L, S, n_kv, hs) caches,
    updated in place at layer ``idx``. This is the body `forward`'s layer
    scan runs (and what the golden-parity test drives with L=1). Returns
    (x, k_all, v_all); under ``moe_counts`` x is _post_attention's pair."""
    t_len = x.shape[0]
    with jax.named_scope(SCOPE_ATTN):
        q, k, v = _qkv_proj(spec, lw, x, positions)
        dt = k_all.dtype  # f32 parity default; bf16 halves cache HBM
        k_new = k.reshape(1, t_len, spec.n_kv_heads,
                          spec.head_size).astype(dt)
        v_new = v.reshape(1, t_len, spec.n_kv_heads,
                          spec.head_size).astype(dt)
        k_all = jax.lax.dynamic_update_slice(k_all, k_new, (idx, pos, 0, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v_new, (idx, pos, 0, 0))

        from ..ops.pallas_attention import maybe_flash_decode

        # flash-decode kernel: reads only the live chunks of the stacked
        # cache (pos-proportional HBM traffic, like the reference's 0..pos
        # attention loop) instead of the full static plane
        ao = maybe_flash_decode(
            q, k_all, v_all, idx, pos, seq_len=spec.seq_len,
            head_size=spec.head_size, t_len=t_len, n_kv=spec.n_kv_heads,
            kv_mul=spec.kv_mul)
        if ao is None:
            k_c = jax.lax.dynamic_index_in_dim(k_all, idx, 0,
                                               keepdims=False)
            v_c = jax.lax.dynamic_index_in_dim(v_all, idx, 0,
                                               keepdims=False)
            ao = attention(spec,
                           q.reshape(t_len, spec.n_heads, spec.head_size),
                           k_c, v_c, pos, t_len)
    x = _post_attention(spec, lw, x, ao, moe_counts)
    return x, k_all, v_all


LAYER_KEYS = ("rms_att", "rms_ffn", "wq", "wk", "wv", "wo", "w1", "w2", "w3",
              # an expert spec's: q/k-norm gains, router, expert stacks
              "rms_q", "rms_k", "moe_gate", "moe_w1", "moe_w2", "moe_w3",
              "w_gate",   # a retention spec's gate
              # a latent spec's: low-rank q, the latent row, the absorbed
              # halves of wkv_b, the router's bias, the shared expert
              "rms_q_a", "rms_kv_a", "wq_a", "wq_b", "wkv_a", "wkv_b",
              "w_uk", "w_uv", "moe_bias", "sh_w1", "sh_w2", "sh_w3",
              # a spec's with several residual streams (ops/hyper.py)
              "hc_att_phi", "hc_att_gate", "hc_att_bias",
              "hc_ffn_phi", "hc_ffn_gate", "hc_ffn_bias")
# load-time fusions (ops/linear) + the megakernel's permuted wo
FUSED_KEYS = ("wqkv", "w13", "wo_mega", "moe_w13", "sh_w13")


def split_layer_weights(params: dict[str, Any]):
    """Partition per-layer weights for the layer scan: stacked Q40Kernel
    weights stay OUTSIDE the scan carry (the kernel indexes the stack
    directly via scalar prefetch — see ops/linear.StackedQ40); everything
    else is scanned normally (sliced per step)."""
    keys = [k for k in LAYER_KEYS + FUSED_KEYS if k in params]
    stacked = {k: params[k] for k in keys
               if isinstance(params[k], (Q40Kernel, Q40KernelNb,
                                         Q40KernelNbI4))}
    scanned = {k: params[k] for k in keys if k not in stacked}
    return stacked, scanned


def layer_view(stacked: dict[str, Any], scanned_slice: dict[str, Any],
               idx) -> dict[str, Any]:
    lw = dict(scanned_slice)
    for k, v in stacked.items():
        lw[k] = StackedQ40(v, idx)
    return lw


def _forward_fused(spec: TransformerSpec, params: dict[str, Any],
                   cache: KVCache, tokens: jax.Array,
                   pos: jax.Array) -> tuple[jax.Array, KVCache]:
    """T=1 decode with the fused per-layer kernels (ops/pallas_layer): two
    pallas_calls per layer (head: rms+wqkv+rope, tail: wo+res+rms+w13+
    silu+w2+res) around the flash-attention kernel — the launch-tax cut of
    VERDICT r2 #2. The residual stream rides in COLUMN form (dim, 1)
    between kernels (the layout the fused kernels exchange; see
    pallas_layer docstring). Same value map as the unfused path."""
    from ..ops.pallas_layer import (q40_head_fused, q40_layer_mega,
                                    q40_tail_fused, rope_freq_cols)

    hs, n_kv, kv_dim = spec.head_size, spec.n_kv_heads, spec.kv_dim
    x = params["tok_embedding"][tokens].astype(jnp.float32)  # (1, dim)
    x_col = jnp.transpose(x)                                 # (dim, 1)
    freq_np, even_np = rope_freq_cols(spec)
    freq_col, even_col = jnp.asarray(freq_np), jnp.asarray(even_np)
    stacked, scanned = split_layer_weights(params)
    use_mega = "wo_mega" in stacked  # prepare_mega_params gated shapes

    from ..ops.pallas_attention import maybe_flash_decode

    def scan_body(carry, per_layer):
        x_col, k_all, v_all = carry
        idx, lw = per_layer
        if use_mega:
            # the endgame: ONE device op for the whole layer — matvec
            # phases, in-kernel RoPE, the flash cache walk, and the cache
            # write all inside a single pallas_call (launch overhead on
            # this runtime is ~10-15 us/op; at 32 layers each op saved is
            # ~0.4 ms/token)
            x_col, k_all, v_all = q40_layer_mega(
                spec, stacked["wqkv"], stacked["wo_mega"], stacked["w13"],
                stacked["w2"], lw["rms_att"][:, None],
                lw["rms_ffn"][:, None], freq_col, even_col, x_col,
                k_all, v_all, idx, pos)
            return (x_col, k_all, v_all), None
        qkv_col = q40_head_fused(spec, stacked["wqkv"],
                                 lw["rms_att"][:, None], freq_col, even_col,
                                 x_col, idx, pos)
        q = jnp.transpose(qkv_col[:spec.dim])                # (1, dim)
        dt = k_all.dtype
        k_new = qkv_col[spec.dim:spec.dim + kv_dim].reshape(
            1, 1, n_kv, hs).astype(dt)
        v_new = qkv_col[spec.dim + kv_dim:].reshape(
            1, 1, n_kv, hs).astype(dt)
        k_all = jax.lax.dynamic_update_slice(k_all, k_new, (idx, pos, 0, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v_new, (idx, pos, 0, 0))
        ao = maybe_flash_decode(
            q, k_all, v_all, idx, pos, seq_len=spec.seq_len, head_size=hs,
            t_len=1, n_kv=n_kv, kv_mul=spec.kv_mul)
        if ao is None:  # interpret/test fallback: XLA attention core
            k_c = jax.lax.dynamic_index_in_dim(k_all, idx, 0, keepdims=False)
            v_c = jax.lax.dynamic_index_in_dim(v_all, idx, 0, keepdims=False)
            ao = attention(spec, q.reshape(1, spec.n_heads, hs), k_c, v_c,
                           pos, 1)
        x_col = q40_tail_fused(spec, stacked["wo"], stacked["w13"],
                               stacked["w2"], lw["rms_ffn"][:, None],
                               jnp.transpose(ao), x_col, idx)
        return (x_col, k_all, v_all), None

    idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)
    (x_col, k_new, v_new), _ = jax.lax.scan(
        scan_body, (x_col, cache.k, cache.v), (idxs, scanned))
    x = rmsnorm(jnp.transpose(x_col), params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)
    return logits, KVCache(k_new, v_new)


def _log_gate(lw: dict[str, Any], x: jax.Array, eps: float) -> jax.Array:
    """log g = log sigmoid(W_g h), h = RMSNorm(x; rms_att): (..., n_kv),
    float32 at HIGHEST precision (the gate sets how long a state
    remembers; it is neither quantized nor taken in bf16)."""
    h = rmsnorm(x, lw["rms_att"], eps)
    return jax.nn.log_sigmoid(jnp.einsum(
        "kd,...d->...k", lw["w_gate"], h,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))


def _merged_state(cache: StateCache):
    """The stacked state as the kernels index it: every leading axis
    (layer[, row], KV head) merged into one, a bitcast."""
    return (cache.s.reshape(-1, *cache.s.shape[-3:]),
            cache.z.reshape(-1, *cache.z.shape[-2:]))


def forward_retention(spec: TransformerSpec, params: dict[str, Any],
                      cache: StateCache, tokens: jax.Array, pos: jax.Array,
                      n_valid=None, *, norm_min: bool = False):
    """``forward`` for a retention spec: T tokens of ONE sequence at
    positions pos..pos+T-1 through the state (L, n_kv, n_off, hs, hs).
    A sequence's first position finds the state empty whatever it holds
    (``pos == 0`` resets it). T = 1 is the recurrent step; T > 1 the
    chunked form, of whose positions the first ``n_valid`` (default all)
    are the sequence's and the rest padding that leaves the state alone.
    ``norm_min`` adds the (L,) smallest normaliser phi(q).z of a T = 1
    step (inf for a chunk: its own positions are not read through z)."""
    from ..ops import retention

    t_len = tokens.shape[0]
    if t_len == 1:     # the batched step at one row: the same kernel call
        return forward_batch_retention(spec, params, cache, tokens,
                                       jnp.reshape(pos, (1,)),
                                       norm_min=norm_min)
    pad = -t_len % retention.SUBLANES
    if pad:
        tokens = jnp.concatenate([tokens, jnp.zeros((pad,), tokens.dtype)])
    n_valid = t_len if n_valid is None else jnp.minimum(n_valid, t_len)
    positions = pos + jnp.arange(t_len + pad)
    with jax.named_scope(SCOPE_EMBED):
        x = params["tok_embedding"][tokens].astype(jnp.float32)
    stacked, scanned = split_layer_weights(params)

    def scan_body(carry, per_layer):
        x, s_all, z_all = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        with jax.named_scope(SCOPE_ATTN):
            q, k, v = _qkv_proj(spec, lw, x, positions)
            ao, s_all, z_all = retention.chunk_attention(
                spec.head_size, spec.kv_mul, q, k, v,
                _log_gate(lw, x, spec.norm_eps), s_all, z_all, idx, pos == 0,
                n_valid)
        return (_post_attention(spec, lw, x, ao), s_all, z_all), None

    idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)
    (x, s_all, z_all), _ = jax.lax.scan(
        scan_body, (x, *_merged_state(cache)), (idxs, scanned))
    with jax.named_scope(SCOPE_LOGITS):
        x = rmsnorm(x[:t_len], params["rms_final"], spec.norm_eps)
        logits = matmul(params["wcls"], x)
    cache = StateCache(s_all.reshape(cache.s.shape),
                       z_all.reshape(cache.z.shape))
    if norm_min:
        return logits, cache, jnp.full((spec.n_layers,), jnp.inf)
    return logits, cache


def forward_batch_retention(spec: TransformerSpec, params: dict[str, Any],
                            cache: StateCache, tokens: jax.Array,
                            pos_vec: jax.Array, active=None, *,
                            norm_min: bool = False):
    """``forward_batch_ragged`` for a retention spec: one token for each of
    B rows at its own position, against the state (L, B, n_kv, n_off, hs,
    hs). A row at position 0 finds its state empty; a row whose ``active``
    ((B,), nonzero = takes part; default all) is 0 rides the step and
    leaves its state as it is. ``norm_min`` adds the (L,) smallest
    normaliser among the active rows."""
    from ..ops import retention

    B = tokens.shape[0]
    x = params["tok_embedding"][tokens].astype(jnp.float32)
    positions = pos_vec if jnp.ndim(pos_vec) == 1 else jnp.full((B,),
                                                                pos_vec)
    live = None if active is None else active != 0
    stacked, scanned = split_layer_weights(params)

    def scan_body(carry, per_layer):
        x, s_all, z_all = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        q, k, v = _qkv_proj(spec, lw, x, positions)
        ao, s_all, z_all, low = retention.decode_attention(
            spec.head_size, spec.kv_mul, q, k, v,
            _log_gate(lw, x, spec.norm_eps), s_all, z_all, idx,
            positions == 0, live)
        return (_post_attention(spec, lw, x, ao), s_all, z_all), low

    idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)
    (x, s_all, z_all), low = jax.lax.scan(
        scan_body, (x, *_merged_state(cache)), (idxs, scanned))
    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)
    cache = StateCache(s_all.reshape(cache.s.shape),
                       z_all.reshape(cache.z.shape))
    return (logits, cache, low) if norm_min else (logits, cache)


def forward(spec: TransformerSpec, params: dict[str, Any], cache: KVCache,
            tokens: jax.Array, pos: jax.Array, *, moe_counts: bool = False):
    """Run T tokens (at absolute positions pos..pos+T-1) through the model.

    Returns (logits (T, vocab) f32, updated cache). jit with spec static.
    ``moe_counts`` (expert specs only; a Python-level switch, so a dense
    spec traces the program it always did) adds a third result: the (L, E)
    int32 count of rows routed to each expert in this dispatch. A
    retention spec takes ``forward_retention`` (every position valid), a
    hybrid spec ``models/sambay.forward_sambay``.
    """
    if spec.hybrid:
        from .sambay import forward_sambay

        return forward_sambay(spec, params, cache, tokens, pos)
    if spec.mixers or spec.ssd or spec.kda:
        return slot_model(spec).forward_chunk(spec, params, cache, tokens,
                                              pos, moe_counts=moe_counts)
    if spec.retention:
        return forward_retention(spec, params, cache, tokens, pos)
    if spec.latent:
        from .latent import forward_latent

        return forward_latent(spec, params, cache, tokens, pos,
                              moe_counts=moe_counts)
    t_len = tokens.shape[0]
    if t_len == 1:
        from ..ops import pallas_layer

        if pallas_layer.fusion_enabled():
            pallas_layer.refuse_expert_spec(spec)
            if pallas_layer.supports(spec, params):
                return _forward_fused(spec, params, cache, tokens, pos)
    positions = pos + jnp.arange(t_len)
    with jax.named_scope(SCOPE_EMBED):
        x = params["tok_embedding"][tokens].astype(jnp.float32)  # (T, dim)

    stacked, scanned = split_layer_weights(params)

    # The full stacked caches ride in the scan CARRY (updated in place by
    # dynamic_update_slice at (layer, pos); the per-layer read is a
    # dynamic-slice XLA fuses into the attention dot). Scanning them as
    # xs/ys instead would materialize a slice copy in and a re-stack out of
    # every layer's (seq_len, n_kv, hs) cache plane per token — measured
    # ~11ms/token extra at 7B/2048 on v5e.
    def scan_body(carry, per_layer):
        x, k_all, v_all = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        x, k_all, v_all = _layer(spec, x, lw, k_all, v_all, idx, pos,
                                 positions, moe_counts)
        x, counts = x if moe_counts else (x, None)
        return (x, k_all, v_all), counts

    idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)
    (x, k_new, v_new), counts = jax.lax.scan(
        scan_body, (x, cache.k, cache.v), (idxs, scanned))

    with jax.named_scope(SCOPE_LOGITS):
        x = rmsnorm(x, params["rms_final"], spec.norm_eps)
        logits = matmul(params["wcls"], x)
    if moe_counts:
        return logits, KVCache(k_new, v_new), counts
    return logits, KVCache(k_new, v_new)


def batch_decode_attention(head_size: int, kv_mul: int, seq_len: int,
                           q: jax.Array, k: jax.Array, v: jax.Array,
                           k_all: jax.Array, v_all: jax.Array, idx,
                           pos: jax.Array):
    """Shared batch-decode attention sub-block: append k/v at (layer ``idx``,
    column ``pos``) of the rank-4 (L*B, S, n_kv, hs) cache carry, then attend
    via the flash kernel (XLA einsum fallback). q (B, n_q*hs); k/v
    (B, n_kv*hs). Returns (ao (B, n_q*hs), k_all, v_all).

    ``pos`` is a scalar (lockstep batch: one shared clock, one cache write
    covering all B rows) or a (B,) vector (continuous batching: per-row
    clocks, one write per row). All batch paths — single-chip lockstep
    (forward_batch), tp-shard-local (parallel/tp.make_sharded_forward_batch,
    with local head counts), and ragged (forward_batch_ragged) — run THIS
    function, so cache indexing/attention semantics cannot drift."""
    B = q.shape[0]
    n_kv = k_all.shape[-2]
    n_q = q.shape[-1] // head_size
    dt = k_all.dtype
    k_new = k.reshape(B, 1, n_kv, head_size).astype(dt)
    v_new = v.reshape(B, 1, n_kv, head_size).astype(dt)
    ragged = jnp.ndim(pos) == 1
    if ragged:
        # per-row columns: B updates, each in place on the carry (a scatter
        # would materialize a second cache-sized buffer — forward_batch
        # docstring)
        for b in range(B):
            k_all = jax.lax.dynamic_update_slice(
                k_all, k_new[b:b + 1], (idx * B + b, pos[b], 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                v_all, v_new[b:b + 1], (idx * B + b, pos[b], 0, 0))
    else:
        k_all = jax.lax.dynamic_update_slice(k_all, k_new,
                                             (idx * B, pos, 0, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v_new,
                                             (idx * B, pos, 0, 0))

    from ..ops.pallas_attention import maybe_flash_decode

    # per-row flash kernel: live-chunk DMA walk, no cache slice copy (the
    # XLA einsum path below doesn't fuse the layer slice read — measured
    # ~10x slower per step at 7B/B=4)
    ao = maybe_flash_decode(
        q, k_all, v_all, idx, pos, seq_len=seq_len, head_size=head_size,
        t_len=1, n_kv=n_kv, kv_mul=kv_mul, batch=True)
    if ao is None:
        k_c = jax.lax.dynamic_slice_in_dim(k_all, idx * B, B, 0)
        v_c = jax.lax.dynamic_slice_in_dim(v_all, idx * B, B, 0)
        if ragged:
            # (B, 1, S): row b sees cache slots 0..pos[b]
            mask = jnp.arange(seq_len)[None, None, :] <= pos[:, None, None]
        else:
            mask = causal_cache_mask(seq_len, pos, 1)
        ao = attention_core(head_size, kv_mul,
                            q.reshape(B, 1, n_q, head_size), k_c, v_c,
                            mask)
    return ao.reshape(B, -1), k_all, v_all


def init_cache_paged(spec: TransformerSpec, n_pages: int, page_size: int,
                     dtype=jnp.float32, slots: int = 0) -> KVCache:
    """Paged pool cache: (L, P, page_size, n_kv, hs) — physical page p of
    layer l is the (page_size, n_kv, hs) plane at [l, p]. ``n_pages`` is
    the TOTAL physical page count including the reserved scrap page 0
    (runtime/paging.SCRAP_PAGE); slots map logical sequence pages onto
    physical pages through an int32 page-table row, so the pool can be
    sized far below slots * seq_len (the HBM lever of vLLM's
    PagedAttention)."""
    if spec.slotted or spec.latent:
        # ``slots`` rows of state and ring beside the pool; a latent
        # spec's ONE plane a page (models/latent.py: no rows without
        # sliding layers)
        model = slot_model(spec)
        return model.init_cache_paged(spec, slots, n_pages, page_size, dtype)
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    shape = (spec.n_layers, n_pages, page_size, spec.n_kv_heads,
             spec.head_size)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


class PagedKVQ8(NamedTuple):
    """Q8-quantized page pool (ISSUE 11): the Q80 wire layout from
    ops/quants.py laid out plane-wise per pool page. ``kq``/``vq`` are the
    int8 code planes with EXACTLY the f32 pool's (L, P, page_size, n_kv,
    hs) geometry (every index computation — page tables, scrap parking,
    rollback truncation — carries over unchanged); ``kd``/``vd`` are the
    f16 block deltas, one per QK values of a position's flattened
    (n_kv * hs) row: (L, P, page_size, n_kv * hs // QK). Per position
    that is kv_dim + 2*kv_dim/QK bytes against the f32 pool's 4*kv_dim —
    a ~3.8x page-byte cut (~1.9x vs bf16), which
    analysis/memory_model.kv_page_pool_bytes prices exactly and the
    engine turns into ~2-4x pool pages at equal HBM."""

    kq: jax.Array  # (L, P, page_size, n_kv, hs) int8 Q80 codes
    kd: jax.Array  # (L, P, page_size, n_kv*hs//QK) f16 block deltas
    vq: jax.Array
    vd: jax.Array


def init_cache_paged_q8(spec: TransformerSpec, n_pages: int,
                        page_size: int) -> PagedKVQ8:
    """Q8 page pool: init_cache_paged's quantized twin. The flattened
    per-position row (n_kv * hs values) must divide into Q80 blocks —
    callers shard kv heads over tp first, so the constraint is on the
    LOCAL width (parallel/tp.py validates the sharded case)."""
    from ..ops.quants import QK

    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    kv_dim = spec.n_kv_heads * spec.head_size
    if kv_dim % QK:
        raise ValueError(
            f"q8 KV pages quantize the flattened (n_kv, hs) position row "
            f"in {QK}-value Q80 blocks: kv_dim={kv_dim} must divide by "
            f"{QK}")
    codes = (spec.n_layers, n_pages, page_size, spec.n_kv_heads,
             spec.head_size)
    deltas = (spec.n_layers, n_pages, page_size, kv_dim // QK)
    return PagedKVQ8(jnp.zeros(codes, jnp.int8),
                     jnp.zeros(deltas, jnp.float16),
                     jnp.zeros(codes, jnp.int8),
                     jnp.zeros(deltas, jnp.float16))


def paged_cache_planes(cache):
    """Flatten a paged pool cache — KVCache (f32/bf16) or PagedKVQ8 —
    into its rank-4 (L*P, page_size, ...) scan-carry views (the
    lane-friendly merge rationale of forward_batch_paged). THE one
    implementation shared by both single-chip paged forwards and both
    tp factories, so a plane-layout change cannot drift between the
    four scan bodies. Returns (planes tuple, n_pages)."""
    if isinstance(cache, PagedKVQ8):
        L, P, ps, n_kv, hs = cache.kq.shape
        nb = cache.kd.shape[-1]
        return (cache.kq.reshape(L * P, ps, n_kv, hs),
                cache.kd.reshape(L * P, ps, nb),
                cache.vq.reshape(L * P, ps, n_kv, hs),
                cache.vd.reshape(L * P, ps, nb)), P
    L, P, ps, n_kv, hs = cache.k.shape
    return (cache.k.reshape(L * P, ps, n_kv, hs),
            cache.v.reshape(L * P, ps, n_kv, hs)), P


def rebuild_paged_cache(planes, n_layers: int):
    """paged_cache_planes' inverse: reassemble the scan-carry views into
    the rank-5 pool cache (2 planes -> KVCache, 4 -> PagedKVQ8)."""
    L = n_layers
    if len(planes) == 4:
        kq4, kd4, vq4, vd4 = planes
        LP, ps, n_kv, hs = kq4.shape
        P = LP // L
        nb = kd4.shape[-1]
        return PagedKVQ8(kq4.reshape(L, P, ps, n_kv, hs),
                         kd4.reshape(L, P, ps, nb),
                         vq4.reshape(L, P, ps, n_kv, hs),
                         vd4.reshape(L, P, ps, nb))
    k4, v4 = planes
    LP, ps, n_kv, hs = k4.shape
    P = LP // L
    return KVCache(k4.reshape(L, P, ps, n_kv, hs),
                   v4.reshape(L, P, ps, n_kv, hs))


def fetch_page_planes(cache, pid: int) -> tuple:
    """Host numpy copy of ONE physical page's planes — the KV-tiering
    demotion read (runtime/paging.PagedAllocator.demote_cold fetches
    through this before releasing the HBM page). The planes come back in
    the page WIRE layout — (k, v) for f32/bf16 pools, (kq, kd, vq, vd)
    for Q8 — so a demote→promote round trip is byte-identical: f32 pages
    bitwise, Q8 pages code-exact (no re-quantization anywhere on the
    path). Host-blocking by design: demotion is a scheduler-thread
    write-behind, not hot-path work."""
    import numpy as np

    if isinstance(cache, PagedKVQ8):
        return tuple(np.asarray(plane[:, pid]) for plane in cache)
    return (np.asarray(cache.k[:, pid]), np.asarray(cache.v[:, pid]))


def write_page_planes(cache, pid, planes):
    """Write one page's planes back into the pool at physical page
    ``pid`` — the KV-tiering promotion apply (the engine jits this with
    the POOL cache donated, so the upload lands in place at a step
    boundary). ``planes`` is fetch_page_planes' tuple (or the
    PageUploader's staged device copies of it)."""
    if isinstance(cache, PagedKVQ8):
        kq, kd, vq, vd = planes
        return PagedKVQ8(cache.kq.at[:, pid].set(kq),
                         cache.kd.at[:, pid].set(kd),
                         cache.vq.at[:, pid].set(vq),
                         cache.vd.at[:, pid].set(vd))
    k, v = planes
    return KVCache(cache.k.at[:, pid].set(k), cache.v.at[:, pid].set(v))


def paged_attention_q8(head_size: int, kv_mul: int, page_size: int,
                       n_pages: int, q: jax.Array, k: jax.Array,
                       v: jax.Array, kq_all, kd_all, vq_all, vd_all,
                       idx, pos: jax.Array, table: jax.Array,
                       span: jax.Array | None = None):
    """Q8-page twin of paged_decode_attention AND spec_verify_attention in
    one function: T=1 is the decode step, T=K the speculative-verify
    window (the location/mask math is spec_verify_attention's, which
    reduces to the decode case at T=1). ``span`` (B,) int32, when given,
    is the mixed-batch write gate: window offsets at or past a row's span
    route their dead quantized writes to the scrap page exactly like
    budget-edge positions (mixed_attention's contract) — None preserves
    the decode/verify behavior where every offset is live.

    Quantize-on-write: each (row, window-offset) position Q80-encodes its
    flattened (n_kv*hs) k/v row — int8 codes into the code plane at the
    page-table-mapped (physical page, offset), f16 block deltas into the
    delta plane at the same coordinates. Dequantize-on-read happens
    inside the paged flash kernel's page loop, or in the XLA gather
    fallback below — SAME value map (codes.astype(f32) * d.astype(f32)),
    so both routes agree and quantization error is paid exactly once per
    written position. q (B, T, n_q*hs); k/v (B, T, n_kv*hs) f32. Returns
    (ao (B, T, n_q*hs), kq_all, kd_all, vq_all, vd_all)."""
    from ..ops.quants import QK, quantize_q80_jax
    from ..runtime.paging import SCRAP_PAGE

    B, t_len = q.shape[0], q.shape[1]
    n_kv = kq_all.shape[-2]
    n_q = q.shape[-1] // head_size
    nb = (n_kv * head_size) // QK
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    max_pages = table.shape[1]
    s_virt = max_pages * page_size
    k_qs, k_d = quantize_q80_jax(k)   # (B,T,nb,QK) int8, (B,T,nb) f16
    v_qs, v_d = quantize_q80_jax(v)
    k_codes = k_qs.reshape(B, t_len, n_kv, head_size)
    v_codes = v_qs.reshape(B, t_len, n_kv, head_size)
    span_b = (None if span is None
              else jnp.broadcast_to(jnp.asarray(span, jnp.int32), (B,)))
    # per-(row, window-offset) writes, in place on the carries — the same
    # B-updates-not-scatter rationale (and the same scrap-page overflow
    # routing) as spec_verify_attention
    for b in range(B):
        for i in range(t_len):
            p = pos_b[b] + i
            logical = jnp.minimum(p // page_size, max_pages - 1)
            live = p < s_virt
            if span_b is not None:
                live = live & (i < span_b[b])
            page = jnp.where(live,
                             jnp.take(table[b], logical), SCRAP_PAGE)
            row = idx * n_pages + page
            off = p % page_size
            kq_all = jax.lax.dynamic_update_slice(
                kq_all, k_codes[b, i][None, None], (row, off, 0, 0))
            kd_all = jax.lax.dynamic_update_slice(
                kd_all, k_d[b, i][None, None], (row, off, 0))
            vq_all = jax.lax.dynamic_update_slice(
                vq_all, v_codes[b, i][None, None], (row, off, 0, 0))
            vd_all = jax.lax.dynamic_update_slice(
                vd_all, v_d[b, i][None, None], (row, off, 0))

    from ..ops.pallas_paged_attention import maybe_paged_flash_decode

    ao = maybe_paged_flash_decode(
        q, (kq_all, kd_all, vq_all, vd_all), idx, pos_b, table,
        page_size=page_size, n_pages=n_pages, head_size=head_size,
        t_len=t_len, n_kv=n_kv, kv_mul=kv_mul, kv_quant="q8")
    if ao is None:
        # XLA fallback: gather the code/delta rows, dequantize (the ONE
        # shared value map, quants.dequantize_q80_planes), and run the
        # shared attention core over the virtual plane — the same mask
        # contract as the f32 paged paths
        from ..ops.quants import dequantize_q80_planes

        rows = (idx * n_pages + table).reshape(-1)
        kq_c = jnp.take(kq_all, rows, axis=0).reshape(B, s_virt, n_kv,
                                                      head_size)
        kd_c = jnp.take(kd_all, rows, axis=0).reshape(B, s_virt, nb)
        vq_c = jnp.take(vq_all, rows, axis=0).reshape(B, s_virt, n_kv,
                                                      head_size)
        vd_c = jnp.take(vd_all, rows, axis=0).reshape(B, s_virt, nb)
        q_pos = pos_b[:, None] + jnp.arange(t_len)[None, :]
        mask = jnp.arange(s_virt)[None, None, :] <= q_pos[:, :, None]
        ao = attention_core(head_size, kv_mul,
                            q.reshape(B, t_len, n_q, head_size),
                            dequantize_q80_planes(kq_c, kd_c),
                            dequantize_q80_planes(vq_c, vd_c), mask)
    return ao, kq_all, kd_all, vq_all, vd_all


def paged_decode_attention(head_size: int, kv_mul: int, page_size: int,
                           n_pages: int, q: jax.Array, k: jax.Array,
                           v: jax.Array, k_all: jax.Array, v_all: jax.Array,
                           idx, pos: jax.Array, table: jax.Array):
    """batch_decode_attention over the PAGED pool: write each row's k/v at
    (physical page ``table[b, pos_b // page_size]``, offset
    ``pos_b % page_size``) of the rank-4 (L*P, page_size, n_kv, hs) carry,
    then attend over the row's gathered page sequence.

    q (B, n_q*hs); k/v (B, n_kv*hs); ``table`` (B, max_pages) int32
    physical page ids in logical order (entries beyond a row's live pages
    point at the scrap page — their junk is masked below). The gathered
    view lays pages out in logical order, so position p of the virtual
    (B, S, n_kv, hs) plane holds exactly the value the contiguous cache
    holds at column p — the ragged mask and attention_core are shared with
    the contiguous path, making the XLA route's paged logits BITWISE equal
    to contiguous logits (the parity gate of tests/test_paging.py, and
    what CPU engines run). On TPU the paged flash-decode Pallas kernel
    (ops/pallas_paged_attention.py, ISSUE 11) takes over via the routing
    gate below: the DMA loop walks the page table directly — live pages
    only, no gather copy — at the documented flash reassociation
    tolerance vs this XLA route.
    """
    B = q.shape[0]
    n_kv = k_all.shape[-2]
    n_q = q.shape[-1] // head_size
    dt = k_all.dtype
    k_new = k.reshape(B, 1, n_kv, head_size).astype(dt)
    v_new = v.reshape(B, 1, n_kv, head_size).astype(dt)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    page_b = jnp.take_along_axis(table, (pos_b // page_size)[:, None],
                                 axis=1)[:, 0]
    off_b = pos_b % page_size
    # per-row writes, each in place on the carry (the same B-updates-not-
    # scatter rationale as the ragged contiguous path, forward_batch)
    for b in range(B):
        row = idx * n_pages + page_b[b]
        k_all = jax.lax.dynamic_update_slice(k_all, k_new[b:b + 1],
                                             (row, off_b[b], 0, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v_new[b:b + 1],
                                             (row, off_b[b], 0, 0))
    from ..ops.pallas_paged_attention import maybe_paged_flash_decode

    # paged flash kernel (ISSUE 11): the DMA loop walks the page table
    # directly — live pages only, no gather copy. One routing gate shared
    # with the verify shape and both tp factories; None = XLA fallback
    # (CPU engines and unsupported shapes), which stays BITWISE equal to
    # the contiguous path (the PR 6 parity gate).
    ao = maybe_paged_flash_decode(
        q.reshape(B, 1, -1), (k_all, v_all), idx, pos_b, table,
        page_size=page_size, n_pages=n_pages, head_size=head_size,
        t_len=1, n_kv=n_kv, kv_mul=kv_mul)
    if ao is not None:
        return ao.reshape(B, -1), k_all, v_all
    s_virt = table.shape[1] * page_size
    rows = (idx * n_pages + table).reshape(-1)            # (B * max_pages,)
    k_c = jnp.take(k_all, rows, axis=0).reshape(B, s_virt, n_kv, head_size)
    v_c = jnp.take(v_all, rows, axis=0).reshape(B, s_virt, n_kv, head_size)
    # (B, 1, S): row b sees virtual positions 0..pos[b] — same mask as the
    # ragged contiguous path, so softmax sees identical live values and
    # exact zeros for everything else
    mask = jnp.arange(s_virt)[None, None, :] <= pos_b[:, None, None]
    ao = attention_core(head_size, kv_mul, q.reshape(B, 1, n_q, head_size),
                        k_c, v_c, mask)
    return ao.reshape(B, -1), k_all, v_all


def forward_batch_paged(spec: TransformerSpec, page_size: int,
                        params: dict[str, Any], cache,
                        tokens: jax.Array, pos_vec: jax.Array,
                        table: jax.Array, *, kv_quant: str = "f32",
                        moe_counts: bool = False):
    """Decode one token per row against the PAGED page-pool cache.
    ``moe_counts`` as in ``forward``: a third result, (L, E) int32.

    forward_batch_ragged's twin for the paged layout: cache planes are
    (L, P, page_size, n_kv, hs) pool pages (init_cache_paged), ``table``
    (B, seq_len // page_size) int32 maps each row's logical pages to
    physical ones (runtime/continuous.py stages it host-side, one upload
    per step). Per-row math is identical to the contiguous path — shared
    _qkv_proj/_post_attention, and paged_decode_attention reproduces
    batch_decode_attention's virtual (B, S) plane exactly — so logits are
    bitwise equal to forward_batch_ragged given the same history (the
    pinned parity gate). jit with (spec, page_size) static and the cache
    donated: the rank-4 page-plane view rides the scan carry in place, so
    J002's zero-copy-per-token contract holds under paging too.

    ``kv_quant='q8'`` (ISSUE 11) swaps the pool for the Q80-quantized
    PagedKVQ8 planes: decode quantizes each position's k/v row on write
    and the attention path dequantizes on read (paged_attention_q8) —
    parity against f32 moves to distribution-pinned tolerance gates, the
    documented quantization contract.
    """
    if spec.mixers or spec.ssd or spec.kda:   # before the latent branch: a
        #                   kda spec's kinds are in the latent list
        return slot_model(spec).forward_batch(
            spec, params, cache, tokens, pos_vec, table, page_size=page_size,
            moe_counts=moe_counts)
    if spec.latent:
        from .latent import forward_batch as forward_batch_latent

        return forward_batch_latent(spec, params, cache, tokens, pos_vec,
                                    table, page_size=page_size,
                                    moe_counts=moe_counts)
    if spec.hybrid:
        from .sambay import forward_batch_sambay

        return forward_batch_sambay(spec, params, cache, tokens, pos_vec,
                                    table, page_size=page_size)
    B = tokens.shape[0]
    x = params["tok_embedding"][tokens].astype(jnp.float32)  # (B, dim)
    positions = pos_vec if jnp.ndim(pos_vec) == 1 else jnp.full((B,),
                                                                pos_vec)
    hs, kv_mul = spec.head_size, spec.kv_mul
    q8 = kv_quant == "q8"
    L = spec.n_layers
    # rank-4 (L*P, page_size, ...) carry views — same layout rationale
    # as forward_batch's (L*B, S, ...) merge: the rank-5 carry provokes a
    # lane-padded normalization copy out of XLA's layout assignment
    planes, P = paged_cache_planes(cache)

    stacked, scanned = split_layer_weights(params)

    def scan_body(carry, per_layer):
        x, *kv = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        q, k, v = _qkv_proj(spec, lw, x, positions)
        if q8:
            ao, *kv = paged_attention_q8(
                hs, kv_mul, page_size, P, q[:, None], k[:, None],
                v[:, None], *kv, idx, pos_vec, table)
            ao = ao.reshape(B, -1)
        else:
            ao, *kv = paged_decode_attention(
                hs, kv_mul, page_size, P, q, k, v, *kv, idx, pos_vec,
                table)
        x = _post_attention(spec, lw, x, ao, moe_counts)
        x, counts = x if moe_counts else (x, None)
        return (x, *kv), counts

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x, *kv), counts = jax.lax.scan(scan_body, (x, *planes),
                                    (idxs, scanned))
    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)
    if moe_counts:
        return logits, rebuild_paged_cache(tuple(kv), L), counts
    return logits, rebuild_paged_cache(tuple(kv), L)


def spec_verify_attention(head_size: int, kv_mul: int, page_size: int,
                          n_pages: int, q: jax.Array, k: jax.Array,
                          v: jax.Array, k_all: jax.Array, v_all: jax.Array,
                          idx, pos: jax.Array, table: jax.Array):
    """paged_decode_attention widened to K queries per row — the
    speculative-verify attention (ISSUE 7): row b scores its current token
    plus K-1 drafted tokens at positions pos_b..pos_b+K-1 in ONE pass,
    with query i seeing virtual positions 0..pos_b+i (the causal window
    sequential decode would have seen at that step), so each position's
    output is BITWISE what K single-token decode steps would produce given
    the same inputs — the losslessness anchor of runtime/speculative.py.

    q (B, K, n_q*hs); k/v (B, K, n_kv*hs); ``table`` as in
    paged_decode_attention. K/V writes land per (row, offset-in-window) at
    the page-table-mapped physical slot; a window position at or past the
    virtual plane (a row decoding at the budget edge) routes its dead
    write to the scrap page instead of clamping onto live pages — the same
    junk-is-invisible contract parked rows rely on. Returns
    (ao (B, K, n_q*hs), k_all, v_all)."""
    B, t_len = q.shape[0], q.shape[1]
    n_kv = k_all.shape[-2]
    n_q = q.shape[-1] // head_size
    dt = k_all.dtype
    k_new = k.reshape(B, t_len, n_kv, head_size).astype(dt)
    v_new = v.reshape(B, t_len, n_kv, head_size).astype(dt)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    max_pages = table.shape[1]
    s_virt = max_pages * page_size
    from ..runtime.paging import SCRAP_PAGE

    # per-(row, window-offset) writes, each in place on the carry — the
    # same B-updates-not-scatter rationale as paged_decode_attention (B and
    # K are static, so the loop unrolls at trace time)
    for b in range(B):
        for i in range(t_len):
            p = pos_b[b] + i
            logical = jnp.minimum(p // page_size, max_pages - 1)
            page = jnp.where(p < s_virt,
                             jnp.take(table[b], logical), SCRAP_PAGE)
            row = idx * n_pages + page
            k_all = jax.lax.dynamic_update_slice(
                k_all, k_new[b, i][None, None], (row, p % page_size, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                v_all, v_new[b, i][None, None], (row, p % page_size, 0, 0))
    from ..ops.pallas_paged_attention import maybe_paged_flash_decode

    # the K-query verify shape rides the SAME paged flash kernel (t_len=K
    # stacked causal windows) through the same routing gate as decode
    ao = maybe_paged_flash_decode(
        q, (k_all, v_all), idx, pos_b, table, page_size=page_size,
        n_pages=n_pages, head_size=head_size, t_len=t_len, n_kv=n_kv,
        kv_mul=kv_mul)
    if ao is not None:
        return ao, k_all, v_all
    rows = (idx * n_pages + table).reshape(-1)            # (B * max_pages,)
    k_c = jnp.take(k_all, rows, axis=0).reshape(B, s_virt, n_kv, head_size)
    v_c = jnp.take(v_all, rows, axis=0).reshape(B, s_virt, n_kv, head_size)
    # (B, K, S): query i of row b sees virtual positions 0..pos_b+i — the
    # per-step causal windows of sequential decode, stacked
    q_pos = pos_b[:, None] + jnp.arange(t_len)[None, :]   # (B, K)
    mask = jnp.arange(s_virt)[None, None, :] <= q_pos[:, :, None]
    ao = attention_core(head_size, kv_mul,
                        q.reshape(B, t_len, n_q, head_size), k_c, v_c, mask)
    return ao, k_all, v_all


def forward_batch_spec_paged(spec: TransformerSpec, page_size: int,
                             params: dict[str, Any], cache,
                             tokens: jax.Array, pos_vec: jax.Array,
                             table: jax.Array, *, kv_quant: str = "f32"):
    """The K-query speculative VERIFY step over the paged pool cache.

    forward_batch_paged's sibling for draft verification (ISSUE 7): row b
    feeds its current token plus K-1 drafted tokens ``tokens[b]`` at
    positions pos_vec[b]..pos_vec[b]+K-1 and gets ALL K next-token logit
    rows from ONE dispatch — the collective-latency amortization lever (a
    dispatch pays the per-layer collective schedule once whether it scores
    1 or K positions; comm_stats.tp_collective_budget(t_len=K) models it).

    tokens (B, K) int32; pos_vec (B,); returns (logits (B, K, vocab), cache).
    Everything except attention treats the B*K query rows as a flat batch
    through the SAME _qkv_proj/_post_attention blocks as decode, so logits
    at position i are bitwise the single-token decode logits given the
    same history — rejected-suffix KV lands beyond the accepted rollback
    point and is masked/overwritten, never read (runtime/continuous.py
    truncates the page table back to the accepted length host-side).
    jit with (spec, page_size) static and the cache donated (J002 holds:
    the rank-4 page-plane view rides the scan carry in place).
    """
    B, K = tokens.shape
    x = params["tok_embedding"][tokens.reshape(-1)].astype(jnp.float32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    positions = (pos_b[:, None]
                 + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
    hs, kv_mul = spec.head_size, spec.kv_mul
    q8 = kv_quant == "q8"
    L = spec.n_layers
    planes, P = paged_cache_planes(cache)

    stacked, scanned = split_layer_weights(params)

    def scan_body(carry, per_layer):
        x, *kv = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        q, k, v = _qkv_proj(spec, lw, x, positions)        # (B*K, ...)
        if q8:
            ao, *kv = paged_attention_q8(
                hs, kv_mul, page_size, P, q.reshape(B, K, -1),
                k.reshape(B, K, -1), v.reshape(B, K, -1), *kv, idx,
                pos_b, table)
        else:
            ao, *kv = spec_verify_attention(
                hs, kv_mul, page_size, P, q.reshape(B, K, -1),
                k.reshape(B, K, -1), v.reshape(B, K, -1), *kv, idx,
                pos_b, table)
        x = _post_attention(spec, lw, x, ao.reshape(B * K, -1))
        return (x, *kv), None

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x, *kv), _ = jax.lax.scan(scan_body, (x, *planes), (idxs, scanned))
    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)                     # (B*K, vocab)
    return logits.reshape(B, K, -1), rebuild_paged_cache(tuple(kv), L)


def mixed_attention(head_size: int, kv_mul: int, page_size: int,
                    n_pages: int, q: jax.Array, k: jax.Array,
                    v: jax.Array, k_all: jax.Array, v_all: jax.Array,
                    idx, pos: jax.Array, table: jax.Array,
                    span: jax.Array):
    """spec_verify_attention generalized to per-row ARBITRARY spans — the
    mixed prefill+decode attention (ISSUE 18): row b contributes
    ``span[b]`` live query positions starting at pos_b (a decode row has
    span 1, the prefill-slice row has span up to the remaining token
    budget, a padded/idle row has span 0), all in ONE (B, T) dispatch
    where T is the dispatch token budget.

    The location math is spec_verify_attention's; the only change is the
    write gate: a window offset at or past a row's span routes its dead
    K/V write to the scrap page (the same junk-is-invisible contract as
    budget-edge positions), so padded offsets never touch live pages.
    The causal masks are untouched — padded queries attend whatever the
    virtual plane holds and produce junk logit rows the engine discards
    host-side (never an empty mask, so softmax stays finite). Live query
    i of row b therefore sees EXACTLY the virtual window sequential
    decode/prefill would have seen at that position, which is what makes
    mixed-dispatch streams bitwise equal to the separate-dispatch engine.
    Returns (ao (B, T, n_q*hs), k_all, v_all)."""
    B, t_len = q.shape[0], q.shape[1]
    n_kv = k_all.shape[-2]
    n_q = q.shape[-1] // head_size
    dt = k_all.dtype
    k_new = k.reshape(B, t_len, n_kv, head_size).astype(dt)
    v_new = v.reshape(B, t_len, n_kv, head_size).astype(dt)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    span_b = jnp.broadcast_to(jnp.asarray(span, jnp.int32), (B,))
    max_pages = table.shape[1]
    s_virt = max_pages * page_size
    from ..runtime.paging import SCRAP_PAGE

    # per-(row, window-offset) writes, each in place on the carry — the
    # same trace-time-unrolled B-updates-not-scatter loop as
    # spec_verify_attention, with the span gate added to the routing
    for b in range(B):
        for i in range(t_len):
            p = pos_b[b] + i
            logical = jnp.minimum(p // page_size, max_pages - 1)
            page = jnp.where((p < s_virt) & (i < span_b[b]),
                             jnp.take(table[b], logical), SCRAP_PAGE)
            row = idx * n_pages + page
            k_all = jax.lax.dynamic_update_slice(
                k_all, k_new[b, i][None, None], (row, p % page_size, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                v_all, v_new[b, i][None, None], (row, p % page_size, 0, 0))
    from ..ops.pallas_paged_attention import maybe_paged_flash_decode

    # the (B, T) window rides the SAME paged flash kernel (stacked causal
    # windows) through the same routing gate as decode/verify
    ao = maybe_paged_flash_decode(
        q, (k_all, v_all), idx, pos_b, table, page_size=page_size,
        n_pages=n_pages, head_size=head_size, t_len=t_len, n_kv=n_kv,
        kv_mul=kv_mul)
    if ao is not None:
        return ao, k_all, v_all
    rows = (idx * n_pages + table).reshape(-1)            # (B * max_pages,)
    k_c = jnp.take(k_all, rows, axis=0).reshape(B, s_virt, n_kv, head_size)
    v_c = jnp.take(v_all, rows, axis=0).reshape(B, s_virt, n_kv, head_size)
    # (B, T, S): query i of row b sees virtual positions 0..pos_b+i — the
    # per-step causal windows of sequential decode, stacked; offsets past
    # span[b] compute junk the engine never reads
    q_pos = pos_b[:, None] + jnp.arange(t_len)[None, :]   # (B, T)
    mask = jnp.arange(s_virt)[None, None, :] <= q_pos[:, :, None]
    ao = attention_core(head_size, kv_mul,
                        q.reshape(B, t_len, n_q, head_size), k_c, v_c, mask)
    return ao, k_all, v_all


def forward_batch_mixed_paged(spec: TransformerSpec, page_size: int,
                              params: dict[str, Any], cache,
                              tokens: jax.Array, pos_vec: jax.Array,
                              span: jax.Array, table: jax.Array, *,
                              kv_quant: str = "f32"):
    """The token-budget MIXED dispatch over the paged pool cache
    (ISSUE 18): one fused forward scores all active decode rows (span 1)
    plus ONE prefill slice (span up to the remaining budget) in a single
    (B, T) window — prefill no longer stalls in-flight decodes behind a
    separate chunk dispatch, and the per-layer collective schedule is
    paid once per budget of tokens (comm_stats.tp_collective_budget at
    t_len=budget models it; contract_mixed_collectives pins it).

    forward_batch_spec_paged's sibling: tokens (B, T) int32 with row b
    live in columns 0..span[b]-1 (junk beyond — embedded and computed but
    write-gated off live pages and discarded host-side); pos_vec (B,);
    span (B,) int32. Returns (logits (B, T, vocab), cache). Everything
    except attention treats the B*T rows as a flat batch through the SAME
    _qkv_proj/_post_attention blocks as decode, so live logit rows are
    bitwise the single-token decode logits given the same history — the
    parity anchor of tests/test_mixed_batch.py. jit with
    (spec, page_size) static and the cache donated (J002 holds: the
    rank-4 page-plane view rides the scan carry in place).
    """
    B, T = tokens.shape
    x = params["tok_embedding"][tokens.reshape(-1)].astype(jnp.float32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos_vec, jnp.int32), (B,))
    span_b = jnp.broadcast_to(jnp.asarray(span, jnp.int32), (B,))
    positions = (pos_b[:, None]
                 + jnp.arange(T, dtype=jnp.int32)[None, :]).reshape(-1)
    hs, kv_mul = spec.head_size, spec.kv_mul
    q8 = kv_quant == "q8"
    L = spec.n_layers
    planes, P = paged_cache_planes(cache)

    stacked, scanned = split_layer_weights(params)

    def scan_body(carry, per_layer):
        x, *kv = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        q, k, v = _qkv_proj(spec, lw, x, positions)        # (B*T, ...)
        if q8:
            ao, *kv = paged_attention_q8(
                hs, kv_mul, page_size, P, q.reshape(B, T, -1),
                k.reshape(B, T, -1), v.reshape(B, T, -1), *kv, idx,
                pos_b, table, span=span_b)
        else:
            ao, *kv = mixed_attention(
                hs, kv_mul, page_size, P, q.reshape(B, T, -1),
                k.reshape(B, T, -1), v.reshape(B, T, -1), *kv, idx,
                pos_b, table, span_b)
        x = _post_attention(spec, lw, x, ao.reshape(B * T, -1))
        return (x, *kv), None

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x, *kv), _ = jax.lax.scan(scan_body, (x, *planes), (idxs, scanned))
    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)                     # (B*T, vocab)
    return logits.reshape(B, T, -1), rebuild_paged_cache(tuple(kv), L)


def _page_range(table: jax.Array, start, stop):
    """``[start, stop)`` of a slot's table as ``fori_loop`` bounds: the
    whole table where a bound is None."""
    return (0 if start is None else start,
            table.shape[0] if stop is None else stop)


def gather_pages(cache: KVCache, table: jax.Array, page_size: int, *,
                 into: KVCache | None = None, stop=None) -> KVCache:
    """Materialize one slot's virtual (L, S, n_kv, hs) sequence cache from
    its pool pages — the admission-prefill seed: chunked prefill of an
    UNSHARED suffix must attend over the shared prefix k/v, and the
    single-sequence prefill program expects a contiguous plane. ``table``
    is the slot's full (max_pages,) logical->physical row; pages
    ``[0, stop)`` of it (``stop`` a traced count; None: the whole table)
    are copied over ``into``, a sequence cache whose other positions stay
    as they are (None: zeros). What lies past the copied pages is never
    read before prefill overwrites it (its chunk at position p writes p
    before any later chunk reads it), so an admission gathers the pages
    below its start and no others. jit with ``into`` donated: the copy is
    in place. A latent pool's one plane (models/latent.LatentCache)
    gathers alike."""
    lo, hi = _page_range(table, None, stop)

    def g(plane, seq):
        L = plane.shape[0]
        if seq is None:
            seq = jnp.zeros((L, table.shape[0] * page_size,
                             *plane.shape[3:]), plane.dtype)

        def page(i, seq):
            got = jax.lax.dynamic_index_in_dim(plane, table[i], axis=1,
                                               keepdims=False)
            return jax.lax.dynamic_update_slice_in_dim(
                seq, got, i * page_size, axis=1)

        return jax.lax.fori_loop(lo, hi, page, seq)

    return type(cache)(*(g(plane, seq) for plane, seq in zip(
        cache, into if into is not None else (None,) * len(cache))))


def scatter_pages(cache: KVCache, seq_cache: KVCache, table: jax.Array,
                  page_size: int, *, start=None, stop=None) -> KVCache:
    """Write a prefilled virtual sequence cache back into the pool at the
    slot's physical pages — gather_pages' inverse (admission-prefill
    insert), over pages ``[start, stop)`` of the table (traced counts;
    None: the table's first / last). Every pool page outside the range
    keeps its bytes: an admission writes the pages its chunks filled and
    no others. Over the whole table, shared prefix pages receive
    byte-identical content (the seed copied them out and prefill never
    touches positions below its start), and table entries parked on the
    scrap page absorb the junk tail. jit with the POOL cache donated: the
    scatter updates in place."""
    lo, hi = _page_range(table, start, stop)

    def s(plane, seq_plane):
        def page(i, plane):
            upd = jax.lax.dynamic_slice_in_dim(seq_plane, i * page_size,
                                               page_size, axis=1)
            return jax.lax.dynamic_update_index_in_dim(plane, upd, table[i],
                                                       axis=1)

        return jax.lax.fori_loop(lo, hi, page, plane)

    return type(cache)(*(s(plane, seq) for plane, seq in zip(cache,
                                                             seq_cache)))


def gather_pages_q8(cache: PagedKVQ8, table: jax.Array, page_size: int, *,
                    into: KVCache | None = None, stop=None) -> KVCache:
    """gather_pages' Q8 twin: materialize one slot's virtual (L, S, n_kv,
    hs) sequence cache FROM the quantized pool, dequantized to f32 — the
    admission-prefill seed (the single-sequence prefill program computes
    in f32 and must attend over the shared prefix's dequantized k/v, the
    same values decode reads). ``into`` / ``stop`` as gather_pages'."""
    from ..ops.quants import dequantize_q80_planes

    L, _, _, n_kv, hs = cache.kq.shape
    lo, hi = _page_range(table, None, stop)

    def g(codes, d, seq):
        if seq is None:
            seq = jnp.zeros((L, table.shape[0] * page_size, n_kv, hs),
                            jnp.float32)

        def page(i, seq):
            qc, dc = (jax.lax.dynamic_index_in_dim(p, table[i], axis=1,
                                                   keepdims=False)
                      for p in (codes, d))
            return jax.lax.dynamic_update_slice_in_dim(
                seq, dequantize_q80_planes(qc, dc), i * page_size, axis=1)

        return jax.lax.fori_loop(lo, hi, page, seq)

    k, v = into if into is not None else (None, None)
    return KVCache(g(cache.kq, cache.kd, k), g(cache.vq, cache.vd, v))


def scatter_pages_q8(cache: PagedKVQ8, seq_cache: KVCache,
                     table: jax.Array, page_size: int, *, start=None,
                     stop=None) -> PagedKVQ8:
    """scatter_pages' Q8 twin: Q80-quantize the prefilled virtual plane
    per position and write codes + block deltas back into the pool at the
    slot's physical pages, over pages ``[start, stop)`` of the table.
    UNLIKE the f32 scatter, re-writing a SHARED prefix page is not
    byte-idempotent (quantize∘dequantize moves codes whose block max
    shrank), so the engine's range starts past the pages an earlier
    encode published — shared pages keep the bytes their first prefiller
    wrote, and every reader sees one deterministic encoding. jit with the
    POOL cache donated."""
    from ..ops.quants import quantize_q80_jax

    L, _, _, n_kv, hs = cache.kq.shape
    lo, hi = _page_range(table, start, stop)

    def s(codes_plane, d_plane, seq_plane):
        def page(i, planes):
            rows = jax.lax.dynamic_slice_in_dim(seq_plane, i * page_size,
                                                page_size, axis=1)
            qs, d = quantize_q80_jax(rows.reshape(L, page_size, n_kv * hs))
            return tuple(
                jax.lax.dynamic_update_index_in_dim(p, u, table[i], axis=1)
                for p, u in zip(planes, (
                    qs.reshape(L, page_size, n_kv, hs), d)))

        return jax.lax.fori_loop(lo, hi, page, (codes_plane, d_plane))

    kq, kd = s(cache.kq, cache.kd, seq_cache.k)
    vq, vd = s(cache.vq, cache.vd, seq_cache.v)
    return PagedKVQ8(kq, kd, vq, vd)


def init_cache_batch(spec: TransformerSpec, batch: int,
                     dtype=jnp.float32):
    """Batched cache: (L, B, S, n_kv, hs) — each (b, layer) row has the same
    (S, n_kv, hs) layout as the single-sequence cache (forward_batch carries
    it as a rank-4 (L*B, S, n_kv, hs) view; see there for why). A retention
    spec's is ``init_state(spec, batch)``: nothing in it scales with S."""
    if spec.retention:
        return init_state(spec, batch)
    if spec.hybrid:
        from .sambay import init_cache as init_hybrid

        return init_hybrid(spec, batch, dtype)
    shape = (spec.n_layers, batch, spec.seq_len, spec.n_kv_heads,
             spec.head_size)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def forward_batch(spec: TransformerSpec, params: dict[str, Any],
                  cache: KVCache, tokens: jax.Array,
                  pos: jax.Array) -> tuple[jax.Array, KVCache]:
    """Decode one token for each of B sequences.

    tokens (B,); pos scalar (lockstep: one SHARED position clock) or (B,)
    (ragged: per-row clocks — continuous batching); cache is
    (L, B, S, n_kv, hs). Returns (logits (B, vocab), cache). The reference
    is strictly batch=1 (one token per task-table cycle, SURVEY.md §2 'no
    batching'); batching is the natural TPU extension — B rows turn the
    per-layer matvecs into MXU matmuls at the same weight traffic, so
    throughput scales ~B until the MXU saturates.

    With the shared clock (lockstep rows; ragged prompts right-pad and
    sample early — runtime/decode.make_batch_decode_loop) the cache update
    is one dynamic_update_slice, which XLA performs IN PLACE on the scan
    carry. The per-row-clock case uses B row updates instead of a scatter,
    which XLA does NOT update in place — it materializes a second
    cache-sized buffer, doubling cache HBM (measured: OOM at B=4/7B/16GB).
    Both live in batch_decode_attention.

    Numerics per row match forward(): same kernels via the T=B path, same
    RoPE/GQA/softmax math (batched einsums over the head-major cache —
    see init_cache_batch for why the layout differs from the B=1 path).
    """
    if spec.retention:
        return forward_batch_retention(spec, params, cache, tokens, pos)
    if spec.hybrid:
        from .sambay import forward_batch_sambay

        return forward_batch_sambay(spec, params, cache, tokens, pos)
    B = tokens.shape[0]
    x = params["tok_embedding"][tokens].astype(jnp.float32)  # (B, dim)
    # each row rotates at its own clock (identical under the shared one)
    positions = pos if jnp.ndim(pos) == 1 else jnp.full((B,), pos)
    n_kv, hs, kv_mul = spec.n_kv_heads, spec.head_size, spec.kv_mul
    L, S = spec.n_layers, spec.seq_len

    # the scan carries a RANK-4 (L*B, S, n_kv, hs) view: with the rank-5
    # carry, XLA's layout assignment propagates a batch-minor operand layout
    # from the attention dot into the whole carried cache and inserts a
    # lane-padded normalization copy (1GB cache -> 137GB allocation at B=4).
    # The merged leading dim mirrors the rank pattern of the B=1 path, which
    # lays out cleanly; the boundary reshapes are bitcasts. Row layer*B+b has
    # the single-sequence (S, n_kv, hs) layout.
    k4 = cache.k.reshape(L * B, S, n_kv, hs)
    v4 = cache.v.reshape(L * B, S, n_kv, hs)

    stacked, scanned = split_layer_weights(params)

    def scan_body(carry, per_layer):
        x, k_all, v_all = carry
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        q, k, v = _qkv_proj(spec, lw, x, positions)
        ao, k_all, v_all = batch_decode_attention(hs, kv_mul, S, q, k, v,
                                                  k_all, v_all, idx, pos)
        x = _post_attention(spec, lw, x, ao)
        return (x, k_all, v_all), None

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x, k4, v4), _ = jax.lax.scan(scan_body, (x, k4, v4), (idxs, scanned))
    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    logits = matmul(params["wcls"], x)
    return logits, KVCache(k4.reshape(L, B, S, n_kv, hs),
                           v4.reshape(L, B, S, n_kv, hs))


def forward_batch_ragged(spec: TransformerSpec, params: dict[str, Any],
                         cache: KVCache, tokens: jax.Array,
                         pos_vec: jax.Array) -> tuple[jax.Array, KVCache]:
    """Decode one token for each of B sequences at PER-ROW positions —
    forward_batch with a (B,) position vector (the continuous-batching step,
    runtime/continuous.py): rows advance on independent clocks, so a
    finished row's slot can be re-used by a new request mid-flight.

    Inactive/parked rows simply keep writing at their current position; a
    newly admitted request starts at pos 0 and only ever attends to slots
    0..pos, so stale cache content beyond a row's clock is invisible.
    """
    return forward_batch(spec, params, cache, tokens, pos_vec)


def forward_seq(spec: TransformerSpec, params: dict[str, Any],
                tokens: jax.Array, positions: jax.Array | None = None,
                attention_fn=None) -> jax.Array:
    """Batched full-sequence forward without a KV cache: (B, T) -> (B, T, vocab).

    The training/evaluation path (the reference is inference-only; training is
    a capability extension). Causal attention inside the T window, same
    numerics as the cached forward — shared attention_core, same precision,
    same Q80 wire-quantization cut points.

    ``positions``/``attention_fn`` parameterize the sequence-parallel
    training path (parallel/sp_train.py): positions are this shard's
    absolute offsets and attention_fn(q, k, v) -> (B, T, n_q*hs) runs ring
    attention across the sp axis — everything else (embedding, layer scan,
    fused-weight handling, SwiGLU tail, final norm/logits) is shared, so
    the two paths cannot drift.
    """
    B, T = tokens.shape
    x = params["tok_embedding"][tokens].astype(jnp.float32)  # (B, T, D)
    if positions is None:
        positions = jnp.arange(T)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]  # (T, T) causal

    stacked, scanned = split_layer_weights(params)

    def body(x, per_layer):
        idx, lw_slice = per_layer
        lw = layer_view(stacked, lw_slice, idx)
        q, k, v = _qkv_proj(spec, lw, x, positions)
        if attention_fn is not None:
            ao = attention_fn(q, k, v)
        else:
            ao = attention_core(
                spec.head_size, spec.kv_mul,
                q.reshape(B, T, spec.n_heads, spec.head_size),
                k.reshape(B, T, spec.n_kv_heads, spec.head_size),
                v.reshape(B, T, spec.n_kv_heads, spec.head_size), mask)
        x = _post_attention(spec, lw, x, ao)
        return x, None

    idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)
    x, _ = jax.lax.scan(body, x, (idxs, scanned))
    x = rmsnorm(x, params["rms_final"], spec.norm_eps)
    return matmul(params["wcls"], x)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=2)
def decode_step(spec: TransformerSpec, params: dict[str, Any], cache: KVCache,
                token: jax.Array, pos: jax.Array) -> tuple[jax.Array, KVCache]:
    """Single-token step: the hot per-token function (T=1)."""
    logits, cache = forward(spec, params, cache, token[None], pos)
    return logits[0], cache


def params_to_device(params: dict[str, Any], dtype=None,
                     spec: TransformerSpec | None = None,
                     layout=None) -> dict[str, Any]:
    """Move a numpy param tree onto the default device as jax arrays.

    Q40 weights are re-tiled to the Pallas kernel layout here (once, host
    side) when the Q40 fast path is active — see ops/linear.pack_q40_params;
    ``layout`` is the engine's resolved Q40Layout. With ``spec`` given, the
    megakernel's permuted-wo stack is prepared too
    (ops/pallas_layer.prepare_mega_params) so T=1 decode can run one fused
    op per layer.
    """
    from ..io.loader import Q40Kernel, Q40Weight
    from ..obs.spans import startup_phase, startup_placed
    from ..ops.linear import fuse_q40_layer_matmuls, pack_q40_params

    with startup_phase("pack"):     # every host repack, not the Q40 one alone
        if "wkv_b" in params or "wkv_b" in params.get("full", ()):
            # a latent spec's absorbed halves (float32; a kda spec's lie
            # in its latent layers' stack)
            from .latent import prepare_latent_params

            if spec is None:
                raise ValueError(
                    "a latent-attention tree needs its spec to be placed: "
                    "params_to_device(params, spec=spec)")
            params = prepare_latent_params(spec, params)
        params = fuse_q40_layer_matmuls(pack_q40_params(
            params, allow_nb_major=True, layout=layout))
        if spec is not None and not spec.planned:
            from ..ops.pallas_layer import prepare_mega_params

            params = prepare_mega_params(spec, params)

    def conv(a):
        x = jnp.asarray(a)
        if dtype is not None and x.dtype in (jnp.float32, jnp.float16):
            x = x.astype(dtype)
        return x

    def place(stack):
        # quantized leaves keep their exact codec/kernel dtypes — the dtype
        # knob is for dense weights only (scales must stay f32/f16); a
        # nested dict is a second stack of layers ("dense")
        return {k: place(v) if isinstance(v, dict)
                else jax.tree_util.tree_map(jnp.asarray, v)
                if isinstance(v, (Q40Weight, Q40Kernel, Q40KernelNb))
                else conv(v) for k, v in stack.items()}

    # the transfers are enqueued, not waited for: ``place`` reads the
    # host's part (PERF.md section 3)
    with startup_phase("place"):
        placed = place(params)
    startup_placed(placed)
    return placed
