"""The plain reference of an expert model (OLMoE-1B-7B's block): the forward
pass in straightforward ``jax.numpy``, float32, ``highest`` matmul precision,
with no kernels, no cache and no batching tricks. It takes the loader's codec
tree (``io/loader.load_model``'s contract: Q40 leaves as ``(qs, d16)`` pairs,
or dense arrays) and the ``TransformerSpec``, and dequantizes by the codec's
own definition. The tests compare the program with it on logits.

The layer, for x (T, dim) at positions 0..T-1:

  attention   q = RMSNorm_q(wq h), k = RMSNorm_k(wk h), h = RMSNorm_att(x):
              the q/k gains act over the WHOLE projection (dim and kv_dim
              wide, not per head), before RoPE; v = wv h; causal softmax
              attention over heads of size dim / n_heads (query head h reads
              kv head h // kv_mul); x += wo(att)
  FFN         h = RMSNorm_ffn(x); router logits r = W_g h (E x dim, no
              bias); p = softmax(r) over all E experts in float32; the
              n_active largest p are kept AS THEY ARE (no renormalisation);
              x += sum_e p_e * w2_e( silu(w1_e h) * w3_e h ) over the kept e
  model       pre-norm residual blocks, final RMSNorm, classifier, as Llama;
              RMSNorm(x) = x / sqrt(mean(x^2) + 1e-5) * gain

Departures from the published description (allenai/OLMoE-1B-7B-0125):
* RoPE rotates interleaved pairs (features 2p, 2p+1 of a head) where the
  published model rotates halves (p, p + head/2): ``convert.py`` permutes
  the rows of wq / wk, and the q/k-norm gains with them, so both compute
  the same scores;
* weights are the file's Q40 values dequantized, not bfloat16.

Beside the logits it returns, for each (position, layer), the router's
MARGIN between the last expert kept and the first one dropped, in the
router's logits: ``r_(k) - r_(k+1) = log p_(k) - log p_(k+1)``. Top-k is
discontinuous, so a comparison with another implementation is meaningful
only up to the first position whose smallest margin is below twice what the
two routers' logits may differ by; from there on the two may have kept
different experts, which is no error of arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
ROPE_BASE = 10000.0


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


def _rmsnorm(x, gain):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * gain


def _rope(x, head_size):
    """x (T, n) at positions 0..T-1: interleaved pairs, the angle of pair p
    is pos * base^(-((2p) mod head_size) / head_size)."""
    t, n = x.shape
    i = jnp.arange(0, n, 2, dtype=jnp.float32)
    freq = 1.0 / jnp.power(jnp.float32(ROPE_BASE),
                           jnp.mod(i, head_size) / head_size)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x.reshape(t, n // 2, 2)[..., 0], x.reshape(t, n // 2, 2)[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(t, n)


def _attention(spec, lw, x):
    t = x.shape[0]
    hs, n_kv = spec.head_size, spec.n_kv_heads
    h = _rmsnorm(x, lw["rms_att"])
    q, k, v = (h @ _dense(lw[name]).T for name in ("wq", "wk", "wv"))
    if spec.qk_norm:
        q, k = _rmsnorm(q, lw["rms_q"]), _rmsnorm(k, lw["rms_k"])
    q = _rope(q, hs).reshape(t, n_kv, spec.kv_mul, hs)
    k = _rope(k, hs).reshape(t, n_kv, hs)
    v = v.reshape(t, n_kv, hs)
    scores = jnp.einsum("tgmd,sgd->gmts", q, k) / np.sqrt(hs)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ao = jnp.einsum("gmts,sgd->tgmd", att, v).reshape(t, spec.dim)
    return x + ao @ _dense(lw["wo"]).T


def _experts(spec, lw, x):
    """Returns (x + routed FFN, margin (T,), routed expert ids (T, k))."""
    h = _rmsnorm(x, lw["rms_ffn"])
    p = jax.nn.softmax(h @ jnp.asarray(lw["moe_gate"], jnp.float32).T,
                       axis=-1)
    k = spec.n_active_experts
    top, ids = jax.lax.top_k(p, min(k + 1, spec.n_experts))
    margin = (jnp.log(top[:, k - 1]) - jnp.log(top[:, k])
              if k < spec.n_experts else jnp.full(top.shape[:1], jnp.inf))
    w1, w2, w3 = (_dense(lw[n]) for n in ("moe_w1", "moe_w2", "moe_w3"))
    y = jnp.zeros_like(x)
    for j in range(k):                # a row's j-th expert, one at a time
        e = ids[:, j]
        g = jnp.einsum("thd,td->th", w1[e], h)
        u = jnp.einsum("thd,td->th", w3[e], h)
        y = y + top[:, j, None] * jnp.einsum("tdh,th->td", w2[e],
                                             jax.nn.silu(g) * u)
    return x + y, margin, ids[:, :k]


def forward(tree: dict, spec, tokens) -> tuple[np.ndarray, np.ndarray,
                                               np.ndarray]:
    """Logits (T, vocab), router margins (T, L) and routed expert ids
    (T, L, k) of one sequence ``tokens`` (T,), every position attending to
    those before it."""
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        margins, routed = [], []
        for layer in range(spec.n_layers):
            lw = {k: jax.tree_util.tree_map(lambda a: a[layer], v)
                  for k, v in tree.items()
                  if k not in ("tok_embedding", "rms_final", "wcls")}
            x = _attention(spec, lw, x)
            x, margin, ids = _experts(spec, lw, x)
            margins.append(margin)
            routed.append(ids)
        logits = _rmsnorm(x, tree["rms_final"]) @ _dense(tree["wcls"]).T
    return (np.asarray(logits), np.stack([np.asarray(m) for m in margins], 1),
            np.stack([np.asarray(r) for r in routed], 1))


def compared_positions(margins: np.ndarray, epsilon: float) -> int:
    """How many leading positions of a sequence a comparison may use: up to
    (not including) the first whose smallest router margin over the layers
    is under ``epsilon``; a flipped expert there changes every later row."""
    low = np.nonzero(margins.min(axis=1) < epsilon)[0]
    return int(low[0]) if low.size else int(margins.shape[0])
