"""The plain reference of a power-retention model (Brumby-14B's block): the
forward pass in the ATTENTION form, straightforward ``jax.numpy``, float32,
``highest`` matmul precision, O(T^2), with no state, no chunks, no kernels
and no batching tricks. It takes the loader's codec tree (``io/loader``'s
contract: Q40 leaves as ``(qs, d16)`` pairs, or dense arrays) and the
``TransformerSpec``, and dequantizes by the codec's own definition. The
tests compare the program (recurrent step, chunked prefill, ``serve``) with
it on logits.

The layer, for x (T, dim) at positions 0..T-1, KV head j, query head i of
j's group, head size d:

  h = RMSNorm(x; rms_att)
  q_i = RoPE(RMSNorm_d((wq h)_i; rms_q)),  k_j = RoPE(RMSNorm_d((wk h)_j; rms_k))
      RMSNorm_d norms ONE head with one gain of d shared by all heads
  v_j = (wv h)_j,    g_j = sigmoid((w_gate h)_j) in (0, 1),  w_gate (n_kv, dim)
  a[t, s] = (prod_{r = s+1..t} g_j[r]) * (q_i[t] . k_j[s] / sqrt(d))^2,  s <= t
  y_i[t]  = sum_s a[t, s] v_j[s] / (sum_s a[t, s] + 1e-6)
  x += wo concat_i y_i;   x += w2( silu(w1 h') * w3 h' ),  h' = RMSNorm(x; rms_ffn)
  model: pre-norm residual blocks, final RMSNorm, classifier, as Llama;
  RMSNorm(x) = x / sqrt(mean(x^2) + norm_eps) * gain

Departures from the publication (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239, and the ``retention`` package's
``power_retention``), each also under ``assumed`` in the benchmark's
configuration file. The published ``config.json`` has no key for the
retention, so these are written from its equations and not from its code:
* degree 2 and the gate's form: one sigmoid gate a KV head and position
  from a bias-free float32 projection of the normed input (the package
  takes ``log_G``; where the model gets it from is this repo's choice);
* the normaliser: the sum of the weights plus 1e-6 (the package also
  offers a learned scale; none here);
* scores are scaled by 1/sqrt(d) before the square;
* RoPE is kept (Qwen3's, base ``rope_theta``), in the interleaved-pair
  form under the converter's row permutation of wq / wk;
* no switch-over: the publication's code attends over a KV buffer and
  folds it into the state at a fixed length; this is the same function on
  another schedule, and the reference has neither buffer nor state;
* weights are the file's Q40 values dequantized, not bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORMALISER_EPS = 1e-6


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


def _rope(x, head_size, base):
    """x (T, n) at positions 0..T-1: interleaved pairs, the angle of pair p
    is pos * base^(-((2p) mod head_size) / head_size)."""
    t, n = x.shape
    i = jnp.arange(0, n, 2, dtype=jnp.float32)
    freq = 1.0 / jnp.power(jnp.float32(base),
                           jnp.mod(i, head_size) / head_size)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x.reshape(t, n // 2, 2)[..., 0], x.reshape(t, n // 2, 2)[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(t, n)


def retention(q, k, v, log_g, head_size):
    """The attention form. q (T, n_kv, m, d), k and v (T, n_kv, d),
    ``log_g`` (T, n_kv). Returns (T, n_kv, m, d)."""
    t = q.shape[0]
    c = jnp.cumsum(log_g, axis=0)                              # (T, n_kv)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]  # [t, s]
    decay = jnp.where(causal[None], jnp.exp(
        jnp.where(causal[None], c.T[:, :, None] - c.T[:, None, :], 0.0)),
        0.0)                                                   # (n_kv, t, s)
    scores = jnp.einsum("tgmd,sgd->gmts", q, k) / np.sqrt(head_size)
    a = scores * scores * decay[:, None]
    y = jnp.einsum("gmts,sgd->tgmd", a, v)
    total = jnp.transpose(jnp.sum(a, axis=-1), (2, 0, 1))      # (T, n_kv, m)
    return y / (total[..., None] + NORMALISER_EPS)


def _attention(spec, lw, x):
    t = x.shape[0]
    hs, n_kv, eps = spec.head_size, spec.n_kv_heads, spec.norm_eps
    h = _rmsnorm(x, lw["rms_att"], eps)
    q, k, v = (h @ _dense(lw[name]).T for name in ("wq", "wk", "wv"))
    q = _rmsnorm(q.reshape(t, -1, hs), lw["rms_q"], eps).reshape(t, -1)
    k = _rmsnorm(k.reshape(t, -1, hs), lw["rms_k"], eps).reshape(t, -1)
    q = _rope(q, hs, spec.rope_theta).reshape(t, n_kv, spec.kv_mul, hs)
    k = _rope(k, hs, spec.rope_theta).reshape(t, n_kv, hs)
    log_g = jax.nn.log_sigmoid(h @ jnp.asarray(lw["w_gate"], jnp.float32).T)
    y = retention(q, k, v.reshape(t, n_kv, hs), log_g, hs)
    return x + y.reshape(t, spec.dim) @ _dense(lw["wo"]).T


def _ffn(spec, lw, x):
    h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
    w1, w2, w3 = (_dense(lw[n]) for n in ("w1", "w2", "w3"))
    return x + (jax.nn.silu(h @ w1.T) * (h @ w3.T)) @ w2.T


def forward(tree: dict, spec, tokens) -> np.ndarray:
    """Logits (T, vocab) of one sequence ``tokens`` (T,), every position
    reading those before it."""
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        for layer in range(spec.n_layers):
            lw = {k: jax.tree_util.tree_map(lambda a: a[layer], v)
                  for k, v in tree.items()
                  if k not in ("tok_embedding", "rms_final", "wcls")}
            x = _ffn(spec, lw, _attention(spec, lw, x))
        logits = (_rmsnorm(x, tree["rms_final"], spec.norm_eps)
                  @ _dense(tree["wcls"]).T)
    return np.asarray(logits)
