"""Fused per-layer Pallas kernels: the launch-tax attack (VERDICT r2 #2).

STATUS: FROZEN as a documented negative (round 5, VERDICT r4 #9). Both
fusion modes lost rigorous end-to-end A/Bs on the real chip: the
megakernel by ~4.6 ms/token (r3: 9.30-9.50 unfused vs 13.92-14.21 fused
at 128-step chains) and the head/tail pair by ~1.1 ms/token (r4: 9.80 vs
10.91 at 64 steps, 9.08 vs 10.13 at 128) — the fused kernels' multi-weight
DMA pipelines stream at ~550-600 GB/s vs the standalone matvec kernels'
~650-670 on the same bytes, which eats more than the saved launches. The
r4 off-arm also re-measured the thing this attack targets: solving
s + C/steps from the 64/128-step pair gives a dispatch-free steady state
~8.36 ms/token against 8.1 ms of profiler op time, i.e. the inter-op
bubble budget is now ~0.25 ms/token. The remaining follow-up ideas
(2-layer grid, cross-kernel prefetch) cannot win against that budget even
at 100% efficiency, so no further fusion hypotheses are planned; the
hardware findings that shaped these kernels (Mosaic lane-split limits,
plane-conversion idioms, dynamic sublane stores, in-kernel RoPE) are
recorded below and in BASELINE.md. The kernels stay opt-in
(DLLAMA_LAYER_FUSION=on|headtail), parity-pinned either way by
tests/test_pallas_layer.py, as the reusable substrate for any future
layer-granularity work.

Single-token decode at 7B ran ~130 device ops/token; round 2's profiler
attribution showed ~2 ms/token of inter-op pipeline bubbles on top of
~8.1 ms of op time (a gap later closed by toolchain/runtime improvements,
see above). These kernels collapse each layer's matvec chain + glue into
TWO pallas_calls (plus the flash-attention kernel between them):

  head:  rmsnorm(x, rms_att) -> wqkv matvec -> RoPE(q, k)
  tail:  wo matvec -> +residual -> rmsnorm(rms_ffn) -> w13 matvec ->
         silu*mul -> w2 matvec -> +residual

Design (hardware-verified on v5e; the probe scripts that established
these constraints were retired with the freeze): Mosaic cannot
lane-split a (1, n) row vector into the matvec plane layout in-kernel, but
it CAN reshape (d, 1) -> (d/32, 32) and 2-D-transpose to (32, d/32). So
every intermediate vector lives in COLUMN form (d, 1):

  * each matvec phase streams row tiles of the packed weight over a 1-D
    grid and accumulates (R, 1) outputs into a column scratch at dynamic
    SUBLANE offsets (supported; dynamic lane offsets are not);
  * the first step of the next phase converts the finished column to the
    (32, nb) plane layout (reshape + transpose) and precomputes the
    per-block input sums for the factored -8 code offset — the same math
    as ops/pallas_q40._matvec_body, verbatim;
  * glue (rmsnorm reductions, silu, residual adds, RoPE pair rotation via
    a (d/2, 2) reshape and a precomputed frequency column) is elementwise
    or reduction work Mosaic handles directly. In-kernel iota is broken on
    this toolchain, so RoPE frequencies arrive as a constant input column.

The weights are the SAME stacked Q40Kernel tensors the unfused path uses
(wqkv/w13 load-time fusions included; w1 and w3 tiles are read from the
single w13 stack through two BlockSpecs at different row offsets), so
enabling fusion changes no load path. Scope: T=1 decode, f32 buffer mode,
unsharded d-major kernel weights (the 7B/70B-rank shapes; 13B's nb-major
layout keeps the unfused path). Value map: identical Q40 dequant and
factored accumulation as pallas_q40; rmsnorm/silu/RoPE are the same f32
formulas, so logits match the unfused path to float-associativity noise
(pinned in tests/test_pallas_layer.py).

Reference anchor: this replaces the per-layer task chain of
transformer-tasks.cpp:161-427 (rms+qkv+rope / att-out+ffn+w2 sequences)
with two device ops instead of ~10.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..io.loader import Q40Kernel

NJ = 16
_EPS = 1e-5
# Mosaic's default scoped-VMEM limit is 16 MB; the fused kernels' phase
# branches make its stack accounting conservative (the unrolled plane
# temporaries of _mv_tile are counted ~per-plane: measured 19.99M at a
# (768, 128) tile that the standalone matvec kernel runs fine). v5e has
# 128 MB of physical VMEM — raise the limit rather than starving the tiles.
_VMEM_LIMIT = 100 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def fusion_mode() -> str:
    """'auto', 'on', 'headtail', or 'off' — DLLAMA_LAYER_FUSION. Read at
    trace/load time; already-built engines keep their mode. Unknown values
    raise (a typo would silently run the unfused path)."""
    mode = os.environ.get("DLLAMA_LAYER_FUSION") or "auto"  # '' = unset
    if mode not in ("auto", "on", "headtail", "off"):
        raise ValueError(f"DLLAMA_LAYER_FUSION={mode!r}: "
                         f"expected auto|on|headtail|off")
    return mode


def fusion_enabled() -> bool:
    """Whether T=1 decode builds the fused-layer program.

    'auto' currently resolves to OFF: at real 7B footprint the megakernel's
    multi-window DMA streams at ~550 GB/s vs the standalone kernels'
    ~670 GB/s (same bytes; measured by a layer-kernel probe, since gone, +
    mega bisections, r3), so fusion does not yet beat the unfused path
    end-to-end. Opt in with DLLAMA_LAYER_FUSION=on (whole-layer megakernel
    when the spec supports it) or =headtail (the two-pallas_call pair with
    the flash-attention kernel between them — r4's launch-tax attempt #2:
    the r3 end-to-end A/B only ever exercised the megakernel). Parity is
    pinned by tests/test_pallas_layer.py for every mode."""
    return fusion_mode() in ("on", "headtail")


def fusion_cache_key() -> str:
    """'off' | 'headtail' | 'mega' — the value that decides the param
    TREE's contents (prepare_mega_params adds wo_mega only under 'mega'),
    for shape-manifest/executable cache keys."""
    if not fusion_enabled():
        return "off"
    return "mega" if fusion_mode() == "on" else "headtail"


def _pick_rows(d: int, cap: int) -> int | None:
    """Largest multiple-of-8 divisor of d up to ``cap`` (row-tile pick: the
    tile is (R, nb) with R on sublanes; the dynamic sublane store offset
    i*R stays 8-aligned)."""
    top = (min(d, cap) // 8) * 8
    for cand in range(top, 0, -8):
        if d % cand == 0:
            return cand
    return None


def _plan(spec):
    """Row tiles for the three phases, or None when the shapes don't fit
    the fused kernels (then the unfused path runs). The caps keep the
    double-buffered tile set + scratches well under the raised scoped-VMEM
    limit (_VMEM_LIMIT). DLLAMA_MEGA_R="r_qkv,r_wo,r_13,r_w2" overrides
    the picks (tile-size experiments; 0 keeps the auto pick)."""
    dim, hid = spec.dim, spec.hidden_dim
    if dim % 32 or hid % 32 or spec.head_size % 2:
        return None
    nb_d, nb_h = dim // 32, hid // 32
    r_wo = _pick_rows(dim, max(8, 130_000 // nb_d))
    r_13 = _pick_rows(hid, max(8, 65_000 // nb_d))
    r_w2 = _pick_rows(dim, max(8, 90_000 // nb_h))
    r_qkv = _pick_rows(dim + 2 * spec.kv_dim, max(8, 130_000 // nb_d))
    if None in (r_wo, r_13, r_w2, r_qkv):
        return None
    plan = dict(r_wo=r_wo, r_13=r_13, r_w2=r_w2, r_qkv=r_qkv,
                nb_d=nb_d, nb_h=nb_h)
    env = os.environ.get("DLLAMA_MEGA_R")
    if env:
        dims = {"r_qkv": dim + 2 * spec.kv_dim, "r_wo": dim, "r_13": hid,
                "r_w2": dim}
        for key, val in zip(("r_qkv", "r_wo", "r_13", "r_w2"),
                            env.split(",")):
            r = int(val)
            if not r:
                continue
            if r % 8 or dims[key] % r:
                raise ValueError(
                    f"DLLAMA_MEGA_R {key}={r} must be a multiple of 8 "
                    f"dividing {dims[key]} (a truncating grid would skip "
                    f"rows silently)")
            plan[key] = r
    return plan


def refuse_expert_spec(spec) -> None:
    """The fused-layer kernels hold a dense SwiGLU tail and softmax
    attention over a KV cache: an expert spec or a retention spec under the
    fusion switch stops here rather than run another program."""
    if spec.retention:
        raise ValueError(
            f"DLLAMA_LAYER_FUSION={fusion_mode()} cannot run a "
            f"power-retention model: the fused-layer kernels attend over a "
            f"KV cache; unset it")
    if spec.n_experts:
        raise ValueError(
            f"DLLAMA_LAYER_FUSION={fusion_mode()} cannot run an expert "
            f"(mixture-of-experts) model: the fused-layer kernels have no "
            f"routed-expert tail; unset it")


def supports(spec, params) -> bool:
    """Fused path precondition: stacked d-major Q40Kernel weights for the
    whole layer chain (wqkv/w13 load-time fusions present) + plannable
    shapes + f32 buffers."""
    from ..ops.quants import FloatType

    if spec.buffer_float_type == FloatType.Q80:
        return False
    if spec.norm_eps != 1e-5 or spec.qk_norm:  # the kernels' own RMSNorm
        return False
    if spec.activation.kind != "silu":  # the tail kernels hard-code SiLU: a
        return False                    # PolyNorm spec takes the unfused path
    for key in ("wqkv", "wo", "w13", "w2"):
        w = params.get(key)
        if not (isinstance(w, Q40Kernel) and w.qs_t.ndim == 4):
            return False
    return _plan(spec) is not None


# ---------------------------------------------------------------------------
# shared in-kernel pieces
# ---------------------------------------------------------------------------


def _to_planes(col):
    """(d, 1) column -> (32, d/32) planes: value 32b+j lands at (j, b) —
    exactly ops/pallas_q40._split_x's layout, built from supported ops
    (reshape splitting sublanes, then a 2-D transpose)."""
    d = col.shape[0]
    return col.reshape(d // 32, 32).T


def _mv_tile(qs3, s, planes, xsum):
    """One (R, nb) output tile of the factored Q40 matvec: qs3 (NJ, R, nb)
    uint8 code planes, s (R, nb) f32 scales, planes (32, nb) input planes,
    xsum (1, nb) per-block input sums. Same math as _matvec_body."""
    acc = None
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)
        wlo = (q & 0xF).astype(jnp.float32)
        whi = (q >> 4).astype(jnp.float32)
        a = wlo * planes[j:j + 1] + whi * planes[j + 16:j + 17]
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xsum
    return jnp.sum(acc * s, axis=1, keepdims=True)  # (R, 1)


def _rms_col(col, w_col, n):
    """rmsnorm of a (d, 1) column against a (d, 1) weight column (eps after
    the mean — the reference's rms(), funcs.cpp:60-62)."""
    ss = jnp.sum(col * col) / n + _EPS
    return col * jax.lax.rsqrt(ss) * w_col


# ---------------------------------------------------------------------------
# tail kernel: wo -> +res -> rms_ffn -> w13 -> silu*mul -> w2 -> +res
# ---------------------------------------------------------------------------


def _tail_kernel(dims, sref, wo_qs, wo_s, w1_qs, w1_s, w3_qs, w3_s, w2_qs,
                 w2_s, ao_col, x_col, wffn_col, out_ref,
                 planes, xsum, planes_h, xsum_h, xnew, hb):
    dim, hid, r_wo, r_13, r_w2 = dims
    g_wo, g_13 = dim // r_wo, hid // r_13
    i = pl.program_id(0)

    # ---- phase starts: column -> planes conversions -----------------------
    @pl.when(i == 0)
    def _():
        p = _to_planes(ao_col[...])
        planes[...] = p
        xsum[...] = jnp.sum(p, axis=0, keepdims=True)

    @pl.when(i == g_wo)
    def _():
        xn = _rms_col(xnew[...], wffn_col[...], dim)
        p = _to_planes(xn)
        planes[...] = p
        xsum[...] = jnp.sum(p, axis=0, keepdims=True)

    @pl.when(i == g_wo + g_13)
    def _():
        p = _to_planes(hb[...])
        planes_h[...] = p
        xsum_h[...] = jnp.sum(p, axis=0, keepdims=True)

    # ---- phase bodies -----------------------------------------------------
    @pl.when(i < g_wo)
    def _():
        out = _mv_tile(wo_qs[0], wo_s[0], planes[...], xsum[...])
        xnew[pl.ds(i * r_wo, r_wo), :] = x_col[...] + out

    @pl.when((i >= g_wo) & (i < g_wo + g_13))
    def _():
        a = _mv_tile(w1_qs[0], w1_s[0], planes[...], xsum[...])
        b = _mv_tile(w3_qs[0], w3_s[0], planes[...], xsum[...])
        h = a / (1.0 + jnp.exp(-a)) * b
        hb[pl.ds((i - g_wo) * r_13, r_13), :] = h

    @pl.when(i >= g_wo + g_13)
    def _():
        k = i - g_wo - g_13
        out = _mv_tile(w2_qs[0], w2_s[0], planes_h[...], xsum_h[...])
        out_ref[...] = xnew[pl.ds(k * r_w2, r_w2), :] + out


@functools.partial(jax.jit, static_argnames=("r_wo", "r_13", "r_w2",
                                             "interpret"))
def _tail_call(layer, wo_qs, wo_s, w13_qs, w13_s, w2_qs, w2_s, ao_col,
               x_col, wffn_col, *, r_wo, r_13, r_w2, interpret):
    L, _, dim, nb_d = wo_qs.shape
    hid2 = w13_qs.shape[2]
    hid = hid2 // 2
    nb_h = w2_qs.shape[3]
    g_wo, g_13, g_w2 = dim // r_wo, hid // r_13, dim // r_w2

    kernel = functools.partial(_tail_kernel,
                               (dim, hid, r_wo, r_13, r_w2))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g_wo + g_13 + g_w2,),
        in_specs=[
            # wo tiles advance through phase 1, freeze elsewhere
            pl.BlockSpec((1, NJ, r_wo, nb_d),
                         lambda i, s: (s[0], 0, jnp.minimum(i, dim // r_wo
                                                            - 1), 0)),
            pl.BlockSpec((1, r_wo, nb_d),
                         lambda i, s: (s[0], jnp.minimum(i, dim // r_wo - 1),
                                       0)),
            # w1 rows: first half of the w13 stack
            pl.BlockSpec((1, NJ, r_13, nb_d),
                         lambda i, s: (s[0], 0,
                                       jnp.clip(i - dim // r_wo, 0,
                                                hid // r_13 - 1), 0)),
            pl.BlockSpec((1, r_13, nb_d),
                         lambda i, s: (s[0],
                                       jnp.clip(i - dim // r_wo, 0,
                                                hid // r_13 - 1), 0)),
            # w3 rows: second half of the SAME stack, offset by hid/r_13
            pl.BlockSpec((1, NJ, r_13, nb_d),
                         lambda i, s: (s[0], 0,
                                       hid // r_13
                                       + jnp.clip(i - dim // r_wo, 0,
                                                  hid // r_13 - 1), 0)),
            pl.BlockSpec((1, r_13, nb_d),
                         lambda i, s: (s[0],
                                       hid // r_13
                                       + jnp.clip(i - dim // r_wo, 0,
                                                  hid // r_13 - 1), 0)),
            # w2 tiles advance through phase 3
            pl.BlockSpec((1, NJ, r_w2, nb_h),
                         lambda i, s: (s[0], 0,
                                       jnp.clip(i - dim // r_wo
                                                - hid // r_13, 0,
                                                dim // r_w2 - 1), 0)),
            pl.BlockSpec((1, r_w2, nb_h),
                         lambda i, s: (s[0],
                                       jnp.clip(i - dim // r_wo
                                                - hid // r_13, 0,
                                                dim // r_w2 - 1), 0)),
            pl.BlockSpec((dim, 1), lambda i, s: (0, 0)),   # ao_col
            # x residual rows, consumed during the wo phase
            pl.BlockSpec((r_wo, 1),
                         lambda i, s: (jnp.minimum(i, dim // r_wo - 1), 0)),
            pl.BlockSpec((dim, 1), lambda i, s: (0, 0)),   # rms_ffn col
        ],
        out_specs=pl.BlockSpec(
            (r_w2, 1),
            lambda i, s: (jnp.clip(i - dim // r_wo - hid // r_13, 0,
                                   dim // r_w2 - 1), 0)),
        scratch_shapes=[
            pltpu.VMEM((32, nb_d), jnp.float32),   # planes (ao, then x)
            pltpu.VMEM((1, nb_d), jnp.float32),    # xsum
            pltpu.VMEM((32, nb_h), jnp.float32),   # planes_h
            pltpu.VMEM((1, nb_h), jnp.float32),    # xsum_h
            pltpu.VMEM((dim, 1), jnp.float32),     # xnew (post-attn resid)
            pltpu.VMEM((hid, 1), jnp.float32),     # hb
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((dim, 1), jnp.float32),
        compiler_params=_PARAMS, interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), wo_qs, wo_s, w13_qs, w13_s,
      w13_qs, w13_s, w2_qs, w2_s, ao_col, x_col, wffn_col)


def q40_tail_fused(spec, wo: Q40Kernel, w13: Q40Kernel, w2: Q40Kernel,
                   rms_ffn_col, ao_col, x_col, layer,
                   interpret: bool | None = None):
    """Fused layer tail: (dim,1) attention output + (dim,1) residual ->
    (dim,1) layer output. Weights are the stacked (L, ...) kernel tensors;
    ``layer`` is the traced scan index (scalar-prefetch DMA, zero-copy)."""
    p = _plan(spec)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _tail_call(layer, wo.qs_t, wo.scale, w13.qs_t, w13.scale,
                      w2.qs_t, w2.scale, ao_col, x_col, rms_ffn_col,
                      r_wo=p["r_wo"], r_13=p["r_13"], r_w2=p["r_w2"],
                      interpret=interpret)


# ---------------------------------------------------------------------------
# head kernel: rms_att -> wqkv -> RoPE(q, k)
# ---------------------------------------------------------------------------


def _rope_rot(seg, posf, freq, even):
    """Interleaved-pair RoPE rotation on a column segment, via sublane
    rolls + a parity mask: Mosaic cannot merge (n/2, 2) back to (n, 1)
    (unsupported shape cast — the failed first design,
    tools/mosaic_probe4.py), so
      even v: seg[v]*cos - seg[v+1]*sin   (up-roll partner)
      odd  v: seg[v-1]*sin + seg[v]*cos   (down-roll partner)
    cos/sin come from a per-VALUE frequency column (in-kernel iota is
    broken on this toolchain); the roll wrap-around contributions are
    killed by the mask. Shared by the head kernel and the megakernel."""
    ang = posf * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    up = pltpu.roll(seg, seg.shape[0] - 1, 0)   # up[v] = seg[v+1]
    down = pltpu.roll(seg, 1, 0)                # down[v] = seg[v-1]
    return seg * c + (-up * s) * even + down * s * (1.0 - even)


def _head_kernel(dims, sref, qkv_qs, qkv_s, x_col, watt_col, freq_col,
                 even_col, out_ref, planes, xsum, qkv):
    dim, kv_dim, dqkv, r_qkv = dims
    g = dqkv // r_qkv
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        xn = _rms_col(x_col[...], watt_col[...], dim)
        p = _to_planes(xn)
        planes[...] = p
        xsum[...] = jnp.sum(p, axis=0, keepdims=True)

    out = _mv_tile(qkv_qs[0], qkv_s[0], planes[...], xsum[...])
    qkv[pl.ds(i * r_qkv, r_qkv), :] = out

    @pl.when(i == g - 1)
    def _():
        # RoPE via _rope_rot; pos arrives via SMEM scalar prefetch.
        pos = sref[1].astype(jnp.float32)
        q = _rope_rot(qkv[pl.ds(0, dim), :], pos, freq_col[0:dim, :],
                      even_col[0:dim, :])
        k = _rope_rot(qkv[pl.ds(dim, kv_dim), :], pos,
                      freq_col[0:kv_dim, :], even_col[0:kv_dim, :])
        out_ref[pl.ds(0, dim), :] = q
        out_ref[pl.ds(dim, kv_dim), :] = k
        out_ref[pl.ds(dim + kv_dim, kv_dim), :] = qkv[
            pl.ds(dim + kv_dim, kv_dim), :]


@functools.partial(jax.jit, static_argnames=("dim", "kv_dim", "r_qkv",
                                             "interpret"))
def _head_call(layer_pos, qkv_qs, qkv_s, x_col, watt_col, freq_col,
               even_col, *, dim, kv_dim, r_qkv, interpret):
    dqkv = qkv_qs.shape[2]
    nb_d = qkv_qs.shape[3]
    g = dqkv // r_qkv
    kernel = functools.partial(_head_kernel, (dim, kv_dim, dqkv, r_qkv))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, NJ, r_qkv, nb_d),
                         lambda i, s: (s[0], 0, i, 0)),
            pl.BlockSpec((1, r_qkv, nb_d), lambda i, s: (s[0], i, 0)),
            pl.BlockSpec((dim, 1), lambda i, s: (0, 0)),
            pl.BlockSpec((dim, 1), lambda i, s: (0, 0)),
            pl.BlockSpec((dim, 1), lambda i, s: (0, 0)),
            pl.BlockSpec((dim, 1), lambda i, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((dqkv, 1), lambda i, s: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((32, nb_d), jnp.float32),
            pltpu.VMEM((1, nb_d), jnp.float32),
            pltpu.VMEM((dqkv, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((dqkv, 1), jnp.float32),
        compiler_params=_PARAMS, interpret=interpret,
    )(layer_pos, qkv_qs, qkv_s, x_col, watt_col, freq_col, even_col)


# ---------------------------------------------------------------------------
# whole-layer megakernel: rms+wqkv+rope -> flash attention + cache write ->
# wo -> +res -> rms+w13 -> silu*mul -> w2 -> +res, ONE pallas_call per layer
# ---------------------------------------------------------------------------


def wo_block_perm(n_heads: int, head_size: int) -> np.ndarray:
    """Column-BLOCK permutation for wo inside the megakernel: kernel block
    b reads original block sigma(b) = (head_size/32)*h + dhi with
    h = b mod n_heads, dhi = b div n_heads. Why: the attention output is
    assembled in VMEM as (n_q, hs); transposing it (supported) and
    lane-concatenating its hs/32 sublane strips yields EXACTLY the plane
    layout of the sigma-permuted blocks — no unsupported sublane/lane merge
    needed. Permuting whole 32-column blocks keeps every Q40 scale group
    intact, so the value map is unchanged."""
    nb = n_heads * head_size // 32
    pieces = head_size // 32
    b = np.arange(nb)
    return (b % n_heads) * pieces + b // n_heads


def permute_wo_blocks(wo: Q40Kernel, n_heads: int,
                      head_size: int) -> Q40Kernel:
    """Reorder wo's column blocks by wo_block_perm (host side, at pack —
    the fancy index + ascontiguousarray is the one conversion point)."""
    sigma = wo_block_perm(n_heads, head_size)
    return Q40Kernel(np.ascontiguousarray(wo.qs_t[..., sigma]),
                     np.ascontiguousarray(wo.scale[..., sigma]))


def _ao_to_planes(ao, n_heads: int, hs: int):
    """(n_q, hs) attention output -> (32, nb) planes matching the
    sigma-permuted wo blocks: transpose to (hs, n_heads), then lane-concat
    the hs/32 sublane strips."""
    ao_t = ao.T  # (hs, n_heads)
    strips = [ao_t[k * 32:(k + 1) * 32, :] for k in range(hs // 32)]
    return jnp.concatenate(strips, axis=1)  # (32, n_heads * hs/32)


def _mega_kernel(cfg, sref, qkv_qs, qkv_s, wo_qs, wo_s, w1_qs, w1_s,
                 w3_qs, w3_s, w2_qs, w2_s, x_rows, x_full, watt_col,
                 wffn_col, freq_col, even_col, k_hbm, v_hbm,
                 out_ref, k_out, v_out,
                 planes, xsum, planes_h, xsum_h, qkv, xnew, hb,
                 k_buf, v_buf, kv_wr, sems, wsem):
    (dim, kv_dim, hid, n_kv, kv_mul, hs, chunk,
     r_qkv, r_wo, r_13, r_w2, skip) = cfg
    dqkv = dim + 2 * kv_dim
    g_qkv = dqkv // r_qkv
    att = g_qkv            # the dedicated attention step
    wo0 = att + 1
    w130 = wo0 + dim // r_wo
    w20 = w130 + hid // r_13
    n_heads = n_kv * kv_mul
    i = pl.program_id(0)
    layer = sref[0]
    pos = sref[1]
    # bisection knob (DLLAMA_MEGA_SKIP): skip named phase BODIES — DMA
    # still streams (index maps drive it), so compute cost isolates from
    # DMA cost. Threaded through cfg (a STATIC jit arg read in
    # q40_layer_mega) so changing the env between calls re-traces instead
    # of silently reusing the previous kernel.
    _skip = set(skip.split(","))

    # ---- phase 1: rms_att -> wqkv tiles -> (last step) RoPE ---------------
    if "qkv" not in _skip:
        @pl.when(i == 0)
        def _():
            xn = _rms_col(x_full[...], watt_col[...], dim)
            p = _to_planes(xn)
            planes[...] = p
            xsum[...] = jnp.sum(p, axis=0, keepdims=True)

        @pl.when(i < g_qkv)
        def _():
            out = _mv_tile(qkv_qs[0], qkv_s[0], planes[...], xsum[...])
            qkv[pl.ds(i * r_qkv, r_qkv), :] = out

    @pl.when(jnp.logical_and(i == g_qkv - 1, "rope" not in _skip))
    def _():
        posf = pos.astype(jnp.float32)
        qkv[pl.ds(0, dim), :] = _rope_rot(qkv[pl.ds(0, dim), :], posf,
                                          freq_col[0:dim, :],
                                          even_col[0:dim, :])
        kseg = _rope_rot(qkv[pl.ds(dim, kv_dim), :], posf,
                         freq_col[0:kv_dim, :], even_col[0:kv_dim, :])
        qkv[pl.ds(dim, kv_dim), :] = kseg
        # stage the new K/V rows in cache layout and LAUNCH the cache
        # writes now — they land while the attention walk below runs
        # (positions <= pos-1 only are read from HBM; the pos term comes
        # from VMEM, so the in-flight write cannot race anything read)
        kv_wr[0] = kseg.reshape(n_kv, hs).astype(k_out.dtype)
        kv_wr[1] = qkv[pl.ds(dim + kv_dim, kv_dim), :].reshape(
            n_kv, hs).astype(v_out.dtype)
        pltpu.make_async_copy(kv_wr.at[0], k_out.at[layer, pos],
                              wsem.at[0]).start()
        pltpu.make_async_copy(kv_wr.at[1], v_out.at[layer, pos],
                              wsem.at[1]).start()

    # ---- phase 2 (one step): flash attention over the live prefix ---------
    @pl.when(jnp.logical_and(i == att, "att" not in _skip))
    def _():
        q2 = qkv[pl.ds(0, dim), :].reshape(n_heads, hs)
        scale = 1.0 / jnp.sqrt(jnp.float32(hs))
        n_chunks = jnp.where(pos > 0, (pos - 1) // chunk + 1, 0)

        def k_dma(slot, c):
            return pltpu.make_async_copy(
                k_hbm.at[layer, pl.ds(c * chunk, chunk)], k_buf.at[slot],
                sems.at[slot, 0])

        def v_dma(slot, c):
            return pltpu.make_async_copy(
                v_hbm.at[layer, pl.ds(c * chunk, chunk)], v_buf.at[slot],
                sems.at[slot, 1])

        @pl.when(n_chunks > 0)
        def _():
            k_dma(0, 0).start()
            v_dma(0, 0).start()

        if kv_mul == 1:
            qg = [q2]
        else:  # GQA: group m's query rows are m, kv_mul+m, ... (stride)
            qg = [jnp.concatenate(
                [q2[g * kv_mul + m:g * kv_mul + m + 1, :]
                 for g in range(n_kv)], axis=0) for m in range(kv_mul)]

        def body(c, carry):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_chunks)
            def _():
                nxt = jax.lax.rem(c + 1, 2)
                k_dma(nxt, c + 1).start()
                v_dma(nxt, c + 1).start()

            k_dma(slot, c).wait()
            v_dma(slot, c).wait()
            k = k_buf[slot].astype(jnp.float32)   # (chunk, n_kv, hs)
            v = v_buf[slot].astype(jnp.float32)
            key_pos = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (chunk, n_kv), 0)
            valid = key_pos < pos                 # strict: pos rides VMEM
            out = []
            for m in range(kv_mul):
                m_old, l_old, o_old = carry[m]
                s = jnp.sum(k * qg[m][None, :, :], axis=-1) * scale
                s = jnp.where(valid, s, NEG_INF)
                m_new = jnp.maximum(m_old,
                                    jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_old - m_new)
                l_new = l_old * corr + jnp.sum(p, axis=0, keepdims=True)
                po = jnp.sum(p[:, :, None] * v, axis=0)
                out.append((m_new, l_new, o_old * corr.T + po))
            return tuple(out)

        init = tuple((jnp.full((1, n_kv), NEG_INF, jnp.float32),
                      jnp.zeros((1, n_kv), jnp.float32),
                      jnp.zeros((n_kv, hs), jnp.float32))
                     for _ in range(kv_mul))
        fin = jax.lax.fori_loop(0, n_chunks, body, init)

        # the pos term from VMEM (never read back from HBM)
        k_self = kv_wr[0].astype(jnp.float32)     # (n_kv, hs)
        v_self = kv_wr[1].astype(jnp.float32)
        rows = []
        for m in range(kv_mul):
            m_old, l_old, o_old = fin[m]
            s = jnp.sum(k_self * qg[m], axis=-1,
                        keepdims=True).T * scale  # (1, n_kv)
            m_new = jnp.maximum(m_old, s)
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_old - m_new)
            l_new = l_old * corr + p
            o_new = o_old * corr.T + p.T * v_self
            rows.append(o_new / l_new.T)          # (n_kv, hs)
        if kv_mul == 1:
            ao = rows[0]
        else:  # interleave groups back to head order g*kv_mul+m
            ao = jnp.concatenate(
                [rows[m][g:g + 1, :] for g in range(n_kv)
                 for m in range(kv_mul)], axis=0)
        p = _ao_to_planes(ao, n_heads, hs)        # sigma-permuted planes
        planes[...] = p
        xsum[...] = jnp.sum(p, axis=0, keepdims=True)

    # the cache-write DMAs started in the RoPE step must land before the
    # kernel ends — waited whenever they were STARTED ("rope" ran), in a
    # block independent of the "att" bisection skip (an "att"-skipped run
    # would otherwise finish with outstanding DMA semaphores and fault)
    @pl.when(jnp.logical_and(i == att, "rope" not in _skip))
    def _():
        pltpu.make_async_copy(kv_wr.at[0], k_out.at[layer, pos],
                              wsem.at[0]).wait()
        pltpu.make_async_copy(kv_wr.at[1], v_out.at[layer, pos],
                              wsem.at[1]).wait()

    # ---- phase 3: wo (sigma-permuted blocks) + residual -------------------
    @pl.when((i >= wo0) & (i < w130) & ("wo" not in _skip))
    def _():
        k = i - wo0
        out = _mv_tile(wo_qs[0], wo_s[0], planes[...], xsum[...])
        xnew[pl.ds(k * r_wo, r_wo), :] = x_rows[...] + out

    # ---- phase 4: rms_ffn -> w13 -> silu*mul ------------------------------
    @pl.when(jnp.logical_and(i == w130, "w13" not in _skip))
    def _():
        xn = _rms_col(xnew[...], wffn_col[...], dim)
        p = _to_planes(xn)
        planes[...] = p
        xsum[...] = jnp.sum(p, axis=0, keepdims=True)

    @pl.when((i >= w130) & (i < w20) & ("w13" not in _skip))
    def _():
        k = i - w130
        a = _mv_tile(w1_qs[0], w1_s[0], planes[...], xsum[...])
        b = _mv_tile(w3_qs[0], w3_s[0], planes[...], xsum[...])
        hb[pl.ds(k * r_13, r_13), :] = a / (1.0 + jnp.exp(-a)) * b

    # ---- phase 5: w2 + residual -------------------------------------------
    @pl.when(jnp.logical_and(i == w20, "w2" not in _skip))
    def _():
        p = _to_planes(hb[...])
        planes_h[...] = p
        xsum_h[...] = jnp.sum(p, axis=0, keepdims=True)

    @pl.when((i >= w20) & ("w2" not in _skip))
    def _():
        k = i - w20
        out = _mv_tile(w2_qs[0], w2_s[0], planes_h[...], xsum_h[...])
        out_ref[...] = xnew[pl.ds(k * r_w2, r_w2), :] + out


NEG_INF = float("-inf")


def _att_chunk(seq_len: int, n_kv: int, hs: int, itemsize: int) -> int | None:
    """Cache chunk for the in-kernel flash walk: 2 slots x {K,V} within a
    few MB next to the weight windows."""
    for c in (256, 128, 64, 32, 16, 8):
        if seq_len % c == 0 and 4 * c * n_kv * hs * itemsize <= 8 << 20:
            return min(c, seq_len)
    return None


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def _mega_call(layer_pos, qkv_qs, qkv_s, wo_qs, wo_s, w13_qs, w13_s,
               w2_qs, w2_s, x_col, watt_col, wffn_col, freq_col, even_col,
               k_cache, v_cache, *, cfg, interpret):
    (dim, kv_dim, hid, n_kv, kv_mul, hs, chunk,
     r_qkv, r_wo, r_13, r_w2, skip) = cfg
    dqkv = dim + 2 * kv_dim
    nb_d, nb_h = dim // 32, hid // 32
    g_qkv, g_wo, g_13, g_w2 = (dqkv // r_qkv, dim // r_wo, hid // r_13,
                               dim // r_w2)
    att = g_qkv
    wo0, w130 = att + 1, att + 1 + g_wo
    w20 = w130 + g_13
    grid = w20 + g_w2

    def frozen(start, g):
        return lambda i, s: (s[0], 0, jnp.clip(i - start, 0, g - 1), 0)

    def frozen_s(start, g):
        return lambda i, s: (s[0], jnp.clip(i - start, 0, g - 1), 0)

    def frozen_off(start, g, off):
        return lambda i, s: (s[0], 0, off + jnp.clip(i - start, 0, g - 1),
                             0)

    def frozen_s_off(start, g, off):
        return lambda i, s: (s[0], off + jnp.clip(i - start, 0, g - 1), 0)

    col = lambda i, s: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, NJ, r_qkv, nb_d), frozen(0, g_qkv)),
            pl.BlockSpec((1, r_qkv, nb_d), frozen_s(0, g_qkv)),
            pl.BlockSpec((1, NJ, r_wo, nb_d), frozen(wo0, g_wo)),
            pl.BlockSpec((1, r_wo, nb_d), frozen_s(wo0, g_wo)),
            pl.BlockSpec((1, NJ, r_13, nb_d), frozen(w130, g_13)),
            pl.BlockSpec((1, r_13, nb_d), frozen_s(w130, g_13)),
            pl.BlockSpec((1, NJ, r_13, nb_d),
                         frozen_off(w130, g_13, hid // r_13)),
            pl.BlockSpec((1, r_13, nb_d),
                         frozen_s_off(w130, g_13, hid // r_13)),
            pl.BlockSpec((1, NJ, r_w2, nb_h), frozen(w20, g_w2)),
            pl.BlockSpec((1, r_w2, nb_h), frozen_s(w20, g_w2)),
            pl.BlockSpec((r_wo, 1),
                         lambda i, s: (jnp.clip(i - wo0, 0, g_wo - 1), 0)),
            pl.BlockSpec((dim, 1), col),  # x_full (rms input)
            pl.BlockSpec((dim, 1), col),  # rms_att
            pl.BlockSpec((dim, 1), col),  # rms_ffn
            pl.BlockSpec((dim, 1), col),  # rope freq
            pl.BlockSpec((dim, 1), col),  # rope parity
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((r_w2, 1),
                         lambda i, s: (jnp.clip(i - w20, 0, g_w2 - 1), 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((32, nb_d), jnp.float32),   # planes (x, then ao, x)
            pltpu.VMEM((1, nb_d), jnp.float32),
            pltpu.VMEM((32, nb_h), jnp.float32),
            pltpu.VMEM((1, nb_h), jnp.float32),
            pltpu.VMEM((dqkv, 1), jnp.float32),    # qkv column
            pltpu.VMEM((dim, 1), jnp.float32),     # xnew
            pltpu.VMEM((hid, 1), jnp.float32),     # hb
            pltpu.VMEM((2, chunk, n_kv, hs), k_cache.dtype),
            pltpu.VMEM((2, chunk, n_kv, hs), v_cache.dtype),
            pltpu.VMEM((2, n_kv, hs), k_cache.dtype),  # staged new K/V
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_mega_kernel, cfg)
    x_out, k_new, v_new = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((dim, 1), jnp.float32),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        # cache in/out aliasing: operand indices count the scalar-prefetch
        # arg and every input in call order — k_cache is operand 17,
        # v_cache 18 (asserted by the cache-content parity test)
        input_output_aliases={17: 1, 18: 2},
        compiler_params=_PARAMS, interpret=interpret,
    )(layer_pos, qkv_qs, qkv_s, wo_qs, wo_s, w13_qs, w13_s,
      w13_qs, w13_s, w2_qs, w2_s, x_col, x_col, watt_col, wffn_col,
      freq_col, even_col, k_cache, v_cache)
    return x_out, k_new, v_new


def _mega_shapes_ok(spec) -> bool:
    return (spec.head_size == 128
            and _att_chunk(spec.seq_len, spec.n_kv_heads, spec.head_size,
                           4) is not None)


def mega_supported(spec, params) -> bool:
    """Whole-layer megakernel preconditions: the head/tail plan + an
    attention chunking + lane-width head size (the flash walk's layout) +
    the sigma-permuted wo stack prepared at load (prepare_mega_params)."""
    return (supports(spec, params) and _mega_shapes_ok(spec)
            and isinstance(params.get("wo_mega"), Q40Kernel))


def prepare_mega_params(spec, params: dict) -> dict:
    """Host-side load step: when the megakernel can serve this spec, add
    the sigma-permuted wo stack as ``wo_mega`` (the megakernel's attention-
    output plane layout — see wo_block_perm). ``wo`` stays for the T>1
    prefill path, which runs the unfused kernels."""
    if fusion_enabled():
        refuse_expert_spec(spec)   # at load: before any program is built
    if not (fusion_mode() == "on" and supports(spec, params)
            and _mega_shapes_ok(spec)):
        return params
    out = dict(params)
    out["wo_mega"] = permute_wo_blocks(params["wo"], spec.n_heads,
                                       spec.head_size)
    return out


def q40_layer_mega(spec, wqkv: Q40Kernel, wo_perm: Q40Kernel,
                   w13: Q40Kernel, w2: Q40Kernel, rms_att_col, rms_ffn_col,
                   freq_col, even_col, x_col, k_cache, v_cache, layer, pos,
                   interpret: bool | None = None):
    """ONE device op for a whole decode layer (VERDICT r2 #2's endgame):
    returns (x_out_col, k_cache, v_cache) with the new K/V written at
    (layer, pos) in the (donated) caches. ``wo_perm`` must be the
    sigma-permuted wo (permute_wo_blocks)."""
    p = _plan(spec)
    chunk = _att_chunk(spec.seq_len, spec.n_kv_heads, spec.head_size,
                       jnp.dtype(k_cache.dtype).itemsize)
    cfg = (spec.dim, spec.kv_dim, spec.hidden_dim, spec.n_kv_heads,
           spec.kv_mul, spec.head_size, chunk,
           p["r_qkv"], p["r_wo"], p["r_13"], p["r_w2"],
           os.environ.get("DLLAMA_MEGA_SKIP", ""))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    layer_pos = jnp.stack([jnp.asarray(layer, jnp.int32),
                           jnp.asarray(pos, jnp.int32)])
    return _mega_call(layer_pos, wqkv.qs_t, wqkv.scale, wo_perm.qs_t,
                      wo_perm.scale, w13.qs_t, w13.scale, w2.qs_t, w2.scale,
                      x_col, rms_att_col, rms_ffn_col, freq_col, even_col,
                      k_cache, v_cache, cfg=cfg, interpret=interpret)


def rope_freq_cols(spec) -> tuple[np.ndarray, np.ndarray]:
    """Per-VALUE RoPE columns for the roll-based in-kernel rotation:
    freq (dim, 1) — value v rotates by pos * 10000^-((v - v%2 mod
    head_size)/head_size), the reference's per-element loop
    (transformer-tasks.cpp:228-242) with each pair's angle repeated for
    both members — and the even-parity mask (dim, 1). The k segment uses
    the first kv_dim rows (the pattern repeats per head)."""
    v = np.arange(spec.dim, dtype=np.float32)
    head_dim = np.mod(v - np.mod(v, 2), spec.head_size)
    freq = (1.0 / np.power(np.float32(spec.rope_theta),
                           head_dim / spec.head_size)).reshape(-1, 1)
    even = (np.arange(spec.dim) % 2 == 0).astype(np.float32).reshape(-1, 1)
    return freq, even


def q40_head_fused(spec, wqkv: Q40Kernel, rms_att_col, freq_col, even_col,
                   x_col, layer, pos, interpret: bool | None = None):
    """Fused layer head: (dim,1) residual stream -> (dim+2*kv_dim, 1)
    RoPE-rotated qkv column."""
    p = _plan(spec)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    layer_pos = jnp.stack([jnp.asarray(layer, jnp.int32),
                           jnp.asarray(pos, jnp.int32)])
    return _head_call(layer_pos, wqkv.qs_t, wqkv.scale, x_col, rms_att_col,
                      freq_col, even_col, dim=spec.dim, kv_dim=spec.kv_dim,
                      r_qkv=p["r_qkv"], interpret=interpret)
