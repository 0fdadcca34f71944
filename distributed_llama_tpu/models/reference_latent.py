"""The plain reference of a latent-attention expert model (DeepSeek-V3's
block): the forward pass in straightforward ``jax.numpy``, float32,
``highest`` matmul precision, with no kernels, no cache and no batching. It
takes the loader's tree (``io/loader.load_model``'s contract for a version-4
spec: the expert layers' stacks at the top level, the leading dense layers'
under ``"dense"``; Q40 leaves as ``(qs, d16)`` pairs or dense arrays) and the
``TransformerSpec``. The tests compare the program with it on logits.

For x (T, dim) at positions 0..T-1, H heads, ``la = spec.latent``:

  attention   h = RMSNorm_att(x); c_q = RMSNorm_qa(W_qa h) (q_rank);
              [q_nope | q_rope]_h = W_qb c_q, a head nope_dim + rope_dim;
              [c_kv | k_rope] = W_kva h, c_kv = RMSNorm_kva(c_kv) (kv_rank);
              RoPE on every head's q_rope and on the ONE k_rope they share;
              [k_nope | v]_h = W_kvb c_kv, a head nope_dim + v_dim;
              score = (q_nope . k_nope + q_rope . k_rope) * scale, causal
              softmax, o_h = sum softmax * v_h; x += W_o [o_1 .. o_H].
              This is the EXPANDED schedule: every position's keys and
              values are formed. The program runs the absorbed one (W_kvb's
              halves moved onto the query and the output) over a cache of
              [c_kv | k_rope] alone.
  RoPE        interleaved pairs (2p, 2p + 1) of the rope part; pair p's
              frequency f_p = theta^(-2p / rope_dim), under YaRN blended
              f_p / factor * (1 - r_p) + f_p * r_p with r_p = 1 - clip((p -
              low) / (high - low), 0, 1) over the published correction range
              (beta_fast and beta_slow rotations over original_positions);
              scale = qk_dim^-1/2 * m^2, m = 0.1 * mscale_all_dim *
              ln(factor) + 1; the cos / sin factor mscale / mscale_all_dim
              is 1 for the published values and is applied as it is.
  dense FFN   x += w2(silu(w1 h) * w3 h), h = RMSNorm_ffn(x)
  expert FFN  s = sigmoid(W_g h) over ALL n_experts (softmax if the spec
              says so); choice on c = s + b: with G groups, a group's score
              is the sum of its two largest c, the groups_kept best groups
              stay, every other expert's c is -inf; the n_active largest c
              are chosen (``lax.top_k``: the lower index wins a tie); their
              weights are the unbiased s, divided by their sum + 1e-20 if
              renormalise, times scale; x += sum_e w_e E_e(h) over the
              chosen experts THIS TREE HOLDS (``layout.offset`` ..
              ``+ held``: a share computes its own experts' part and nothing
              stands in for the rest) + S(h), the shared expert.
  model       pre-norm residual blocks, final RMSNorm, classifier;
              RMSNorm(x) = x / sqrt(mean(x^2) + eps) * gain.

Departures from the publication (deepseek-ai/DeepSeek-V3):
* the multi-token-prediction module (the checkpoint's layer 61) is left out:
  the next-token logits do not depend on it;
* experts outside the kept groups are masked with -inf (the published
  inference/model.py) where the transformers port writes 0.0: the same choice
  unless fewer than n_active experts outside score above 0;
* weights are the file's Q40 values dequantized, not FP8 / bfloat16.

Beside the logits it returns, for each (position, expert layer), the router's
smallest MARGIN: between the last group kept and the first dropped (in group
scores) and between the last expert chosen and the first not (in c). A
comparison with another implementation holds only up to a position whose
margin is under twice what the two routers' scores differ by.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _dense(w) -> jax.Array:
    """A codec leaf as float32 (..., d, n): Q40 value = (nibble - 8) * delta,
    low nibbles are values 0..15 of a block, high nibbles 16..31."""
    if hasattr(w, "qs"):
        qs, d16 = jnp.asarray(w.qs), jnp.asarray(w.d16)
        lo = (qs & 0x0F).astype(jnp.int8) - 8
        hi = (qs >> 4).astype(jnp.int8) - 8
        vals = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
        vals = vals * d16.astype(jnp.float32)[..., None]
        return vals.reshape(*qs.shape[:-2], qs.shape[-2] * 32)
    return jnp.asarray(w).astype(jnp.float32)


def _rmsnorm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope_frequencies(spec) -> tuple[np.ndarray, float, float]:
    """(per-pair frequencies (rope_dim / 2,), the cos / sin factor, the
    attention scale) of ``spec``: plain RoPE, or YaRN as published."""
    la, rs = spec.latent, spec.rope_scaling
    dim = la.rope_dim
    freq = spec.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = la.qk_dim ** -0.5
    if rs is None:
        return freq.astype(np.float32), 1.0, scale

    def correction_dim(rotations):
        return dim * math.log(rs.original_positions
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(spec.rope_theta))

    low = max(math.floor(correction_dim(rs.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rs.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0, 1)
    keep = 1 - ramp                        # r_p: 1 = the frequency as it is
    freq = freq / rs.factor * (1 - keep) + freq * keep

    def mscale(s):
        return 0.1 * s * math.log(rs.factor) + 1.0 if rs.factor > 1 else 1.0

    return (freq.astype(np.float32),
            mscale(rs.mscale) / mscale(rs.mscale_all_dim),
            scale * mscale(rs.mscale_all_dim) ** 2 if rs.mscale_all_dim
            else scale)


def _rope(x, freq, factor):
    """x (T, ..., rope_dim) at positions 0..T-1, interleaved pairs."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(spec, lw, x):
    """x + the latent-attention sub-block, expanded."""
    return x + attention_out(spec, lw, x)


def projections(spec, lw, x):
    """(h the normed input (T, dim), q_nope (T, H, nope), q_rope (T, H,
    rope) rotated, c_kv (T, kv_rank) normed, k_rope (T, rope) rotated, the
    attention scale) of input x: the low-rank q and the latent row, before
    ``wkv_b`` expands it (models/reference_motif.py expands it by group)."""
    la, nh, eps = spec.latent, spec.n_heads, spec.norm_eps
    t = x.shape[0]
    freq, factor, scale = rope_frequencies(spec)
    h = _rmsnorm(x, lw["rms_att"], eps)
    c_q = _rmsnorm(h @ _dense(lw["wq_a"]).T, lw["rms_q_a"], eps)
    q = (c_q @ _dense(lw["wq_b"]).T).reshape(t, nh, la.qk_dim)
    q_nope, q_rope = q[..., :la.nope_dim], _rope(q[..., la.nope_dim:], freq,
                                                 factor)
    kv = h @ _dense(lw["wkv_a"]).T
    c_kv = _rmsnorm(kv[:, :la.kv_rank], lw["rms_kv_a"], eps)
    k_rope = _rope(kv[:, la.kv_rank:], freq, factor)
    return h, q_nope, q_rope, c_kv, k_rope, scale


def attention_out(spec, lw, x):
    """The latent-attention sub-block of input x (which it norms), expanded,
    without the residual (models/reference_hyper.py mixes its own)."""
    la, nh = spec.latent, spec.n_heads
    t = x.shape[0]
    _, q_nope, q_rope, c_kv, k_rope, scale = projections(spec, lw, x)
    kvb = (c_kv @ _dense(lw["wkv_b"]).T).reshape(t, nh,
                                                 la.nope_dim + la.v_dim)
    k_nope, v = kvb[..., :la.nope_dim], kvb[..., la.nope_dim:]
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * scale
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ao = jnp.einsum("hts,shd->thd", att, v).reshape(t, nh * la.v_dim)
    return ao @ _dense(lw["wo"]).T


def _swiglu(h, w1, w2, w3, act=jax.nn.silu):
    """``act`` on the gate projection: SiLU, unless the spec states another
    (models/reference_motif.py's PolyNorm)."""
    return (act(h @ _dense(w1).T) * (h @ _dense(w3).T)) @ _dense(w2).T


def route(spec, gate, bias, h):
    """(weights (T, k), expert ids (T, k), margin (T,)) of rows h."""
    ro, k, n_exp = spec.router, spec.n_active_experts, spec.n_experts
    logits = h @ jnp.asarray(gate, jnp.float32).T
    s = jax.nn.sigmoid(logits) if ro.scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    c = s + jnp.asarray(bias, jnp.float32) if bias is not None else s
    margin = jnp.full(h.shape[:1], jnp.inf)
    if ro.groups > 1:
        per = c.reshape(-1, ro.groups, n_exp // ro.groups)
        score = jax.lax.top_k(per, min(2, per.shape[-1]))[0].sum(-1)
        top, gi = jax.lax.top_k(score, min(ro.groups_kept + 1, ro.groups))
        if ro.groups_kept < ro.groups:
            margin = top[:, ro.groups_kept - 1] - top[:, ro.groups_kept]
        kept = (gi[:, :ro.groups_kept, None]
                == jnp.arange(ro.groups)).any(axis=1)
        c = jnp.where(kept[..., None], per, -jnp.inf).reshape(c.shape)
    top, ids = jax.lax.top_k(c, min(k + 1, n_exp))
    if k < n_exp:
        margin = jnp.minimum(margin, top[:, k - 1] - top[:, k])
    ids = ids[:, :k]
    w = jnp.take_along_axis(s, ids, axis=1)
    if ro.renormalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * ro.scale, ids, margin


def experts(spec, lw, x, shared: bool = True):
    """(x + the expert sub-block, margin (T,), chosen ids (T, k)).
    ``shared`` False leaves the shared expert out (the share test counts
    it once over the shares)."""
    y, margin, ids = experts_out(spec, lw, x, shared)
    return x + y, margin, ids


def experts_out(spec, lw, x, shared: bool = True, act=jax.nn.silu):
    """``experts`` without the residual: the sub-block's output (``act``
    as in ``_swiglu``)."""
    h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
    w, ids, margin = route(spec, lw["moe_gate"], lw.get("moe_bias"), h)
    held, off = spec.n_experts_held, spec.layout.offset
    w1, w2, w3 = (_dense(lw[n]) for n in ("moe_w1", "moe_w2", "moe_w3"))
    y = jnp.zeros_like(x)
    for j in range(spec.n_active_experts):   # a row's j-th expert, in turn
        local = ids[:, j] - off
        here = (local >= 0) & (local < held)
        e = jnp.clip(local, 0, held - 1)
        g = jnp.einsum("thd,td->th", w1[e], h)
        u = jnp.einsum("thd,td->th", w3[e], h)
        out = jnp.einsum("tdh,th->td", w2[e], act(g) * u)
        y = y + jnp.where(here, w[:, j], 0.0)[:, None] * out
    if shared and spec.layout.shared:
        y = y + _swiglu(h, lw["sh_w1"], lw["sh_w2"], lw["sh_w3"], act)
    return y, margin, ids


def _layer_of(stack: dict, i: int) -> dict:
    return {k: jax.tree_util.tree_map(lambda a: a[i], v)
            for k, v in stack.items()
            if k not in ("tok_embedding", "rms_final", "wcls", "dense")}


def forward(tree: dict, spec, tokens):
    """Logits (T, vocab), router margins (T, expert layers) and chosen
    expert ids (T, expert layers, k) of one sequence ``tokens`` (T,)."""
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        for i in range(spec.n_dense_layers):
            lw = _layer_of(tree["dense"], i)
            x = attention(spec, lw, x)
            h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
            x = x + _swiglu(h, lw["w1"], lw["w2"], lw["w3"])
        margins, routed = [], []
        for i in range(spec.n_expert_layers):
            lw = _layer_of(tree, i)
            x, margin, ids = experts(spec, lw, attention(spec, lw, x))
            margins.append(margin)
            routed.append(ids)
        logits = _rmsnorm(x, tree["rms_final"],
                          spec.norm_eps) @ _dense(tree["wcls"]).T
    return (np.asarray(logits), np.stack([np.asarray(m) for m in margins], 1),
            np.stack([np.asarray(r) for r in routed], 1))
