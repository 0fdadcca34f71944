"""The plain reference: the Llama block's forward pass in straightforward
``jax.numpy`` and float32, with no kernels, no cache and no batching tricks,
and ``highest`` matmul precision (on a TPU a float32 matmul otherwise runs
in bf16 passes). Independent of the program: it takes the codec tree
(``weights.py``) and the seven sizes, and dequantizes by the codec's own
definition.

The block (llama2.c / the source paper's converter): RMSNorm with eps 1e-5,
q/k/v projections, interleaved-pair RoPE (pair p = features 2p, 2p+1, angle
pos * base^(-(2p mod head)/head)), grouped-query causal attention (query
head h reads kv head h // kv_mul), output projection, residual, RMSNorm,
SwiGLU w2(silu(w1 x) * w3 x), residual; final RMSNorm and the classifier.

A whole model in float32 does not fit beside the served one (Mistral-7B is
29 GB dense), so the reference runs A LAYER AT A TIME: one layer's codec
slices go to one device, are dequantized there, used for every check
sequence at once, and dropped. Every layer has the same shapes, so there is
one compilation.
"""

from __future__ import annotations

import functools

import numpy as np

EPS = 1e-5


def _dequant(jnp, qs, d16):
    """(d, nb, 16) uint8 + (d, nb) f16 -> (d, nb * 32) f32."""
    lo = (qs & 0x0F).astype(jnp.int8) - 8
    hi = (qs >> 4).astype(jnp.int8) - 8
    vals = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
    vals = vals * d16.astype(jnp.float32)[..., None]
    return vals.reshape(qs.shape[0], -1)


def _rmsnorm(jnp, x, w):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + EPS)) * w


def _rope(jnp, x, positions, head_size, base):
    """x: (B, T, n); interleaved pairs, angle by (2p mod head_size)."""
    n = x.shape[-1]
    i = jnp.arange(0, n, 2, dtype=jnp.float32)
    freq = 1.0 / jnp.power(jnp.float32(base),
                           jnp.mod(i, head_size) / head_size)
    ang = positions[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], n // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _layer(sizes, rope_base, x, rms_att, rms_ffn, w):
    """One block over x (B, T, dim); ``w`` maps name -> (qs, d16)."""
    import jax
    import jax.numpy as jnp

    n_heads, n_kv = sizes["n_heads"], sizes["n_kv_heads"]
    hs = sizes["dim"] // n_heads
    B, T, _ = x.shape
    mm = functools.partial(jnp.einsum, "dn,btn->btd",
                           precision=jax.lax.Precision.HIGHEST)
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    pos = jnp.arange(T)
    xb = _rmsnorm(jnp, x, rms_att)
    q = _rope(jnp, mm(wf["wq"], xb), pos, hs, rope_base)
    k = _rope(jnp, mm(wf["wk"], xb), pos, hs, rope_base)
    v = mm(wf["wv"], xb)
    q = q.reshape(B, T, n_kv, n_heads // n_kv, hs)
    k = k.reshape(B, T, n_kv, hs)
    v = v.reshape(B, T, n_kv, hs)
    scores = jnp.einsum("btgmd,bsgd->bgmts", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hs)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    ao = jnp.einsum("bgmts,bsgd->btgmd", att, v,
                    precision=jax.lax.Precision.HIGHEST)
    x = x + mm(wf["wo"], ao.reshape(B, T, n_heads * hs))
    xb = _rmsnorm(jnp, x, rms_ffn)
    h = jax.nn.silu(mm(wf["w1"], xb)) * mm(wf["w3"], xb)
    return x + mm(wf["w2"], h)


def _head(x, rms_final, wcls_qs, wcls_d16):
    import jax
    import jax.numpy as jnp

    x = _rmsnorm(jnp, x, rms_final)
    return jnp.einsum("vn,btn->btv", _dequant(jnp, wcls_qs, wcls_d16), x,
                      precision=jax.lax.Precision.HIGHEST)


LAYER_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


def logits(tree: dict, sizes: dict, tokens: np.ndarray,
           rope_base: float = 10000.0, device=None) -> np.ndarray:
    """Float32 logits (B, T, vocab) of the full forward pass over
    ``tokens`` (B, T) int, every position attending to those before it."""
    import jax

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    layer = jax.jit(functools.partial(_layer, sizes, float(rope_base)))
    x = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    for i in range(sizes["n_layers"]):
        w = {k: (put(tree[k].qs[i]), put(tree[k].d16[i]))
             for k in LAYER_KEYS}
        x = layer(x, put(tree["rms_att"][i]), put(tree["rms_ffn"][i]), w)
    out = jax.jit(_head)(x, put(tree["rms_final"]), put(tree["wcls"].qs),
                         put(tree["wcls"].d16))
    return np.asarray(out)
