"""The plain reference of a SambaY model (Phi-4-mini-flash-reasoning,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", arXiv:2507.06607): the whole forward at every position in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision, with no
kernels, no cache, no state carried between calls and no batching. It takes
the loader's codec tree (``io/loader``'s contract: a stack of layers a kind,
Q40 leaves as ``(qs, d16)`` pairs or dense arrays) and the ``TransformerSpec``
(``spec.hybrid`` says each layer's kind), and dequantizes by the codec's own
definition. The tests compare the program (``models/sambay.py``: decode
step, chunked prefill, ``serve``) with it on logits.

Every layer i, x (T, dim) at positions 0..T-1, LN = LayerNorm (mean and
variance, gain and bias, eps ``norm_eps``), no positional encoding anywhere:

  h = x + mix_i(LN1(x));   out = h + fc2(silu(g) * u),  [g | u] = fc1(LN2(h))

then a final LN and the classifier (the embedding's rows, no bias). mix_i:

* "mamba" (d_inner, d_state, d_conv, dt_rank):
    [xs | z] = in_proj(u);  xs = silu(conv1d_causal_depthwise(xs) + b_conv)
    [dt | B | C] = x_proj(xs);  delta = softplus(dt_proj(dt) + b_dt)
    A = -exp(A_log);  s_t = exp(delta_t A) s_{t-1} + (delta_t xs_t) B_t^T
    y_t = s_t C_t + D xs_t;   out = out_proj(y_t * silu(z_t))
  The memory layer (the last Mamba layer before the first GMU) also hands on
  m_t = y_t, BEFORE the gate.
* "swa" / "full": differential attention (Diff Transformer,
  arXiv:2410.05258). [q | k | v] = Wqkv(u) + b. Heads of size d = dim /
  n_heads; query heads (2j, 2j + 1) are pair j's (q1, q2), KV heads (2g, 2g
  + 1) are pair g's (k1, k2) and v = (v1 | v2) of 2d; query pair j reads KV
  pair j // (query pairs / KV pairs).
    a = softmax(q1 k1^T / sqrt d) v - lambda softmax(q2 k2^T / sqrt d) v
    o = (1 - lambda_init) RMSNorm_2d(a) * subln
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 i)   (i the layer's index in the model)
  then out_proj(concat o) + b. Mask: causal; "swa" also hides keys more than
  ``window - 1`` positions back (the window counts the current position).
* "xattn": the same with its own Wq (bias), lambdas, gain and out_proj, on
  the "full" layer's k and v, causal.
* "gmu": out_proj(silu(in_proj(u)) * m_t), m the memory layer's, no bias.

Departures from the publication and what is assumed (each also under
``assumed`` in ``benchmark/configs/phi4-mini-flash-q40.json``): the published
``config.json`` has no key for the state-space sizes (the family's defaults
are taken: d_state 16, d_conv 4, expand 2, dt_rank = ceil(dim / 16)), nor one
that says attention is differential or gives the lambda / sub-norm form (the
paper does; this is Diff Transformer's form as the model's code applies it);
which heads pair (consecutive ones, as the Diff Transformer code reshapes
them; a converter for the real checkpoint has to match the checkpoint's);
that the window counts the current position; that the sub-norm is an RMSNorm
with eps ``norm_eps`` and a gain and no bias; weights are the file's Q40
values dequantized, not bfloat16, and the classifier is the Q40 copy of the
embedding the file holds.
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
    return jnp.asarray(w, jnp.float32)


def _layernorm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def mamba(spec, lw, u):
    """(T, dim) -> (out (T, dim), y (T, d_inner): the scan output before the
    gate, which the memory layer hands on)."""
    hy = spec.hybrid
    di, ds, dr, dc = hy.d_inner, hy.d_state, hy.dt_rank, hy.d_conv
    xz = u @ _dense(lw["in_proj"]).T
    xs, z = xz[:, :di], xz[:, di:]
    taps = jnp.asarray(lw["conv_w"])                      # (d_conv, d_inner)
    padded = jnp.concatenate([jnp.zeros((dc - 1, di)), xs])
    xs = sum(padded[j:j + xs.shape[0]] * taps[j] for j in range(dc))
    xs = _silu(xs + lw["conv_b"])
    dbc = xs @ jnp.asarray(lw["x_proj"]).T
    dt, b, c = dbc[:, :dr], dbc[:, dr:dr + ds], dbc[:, dr + ds:]
    delta = jax.nn.softplus(dt @ jnp.asarray(lw["dt_proj"]).T + lw["dt_b"])
    a = -jnp.exp(jnp.asarray(lw["a_log"]))                # (d_state, d_inner)

    def step(s, row):
        d_t, x_t, b_t, c_t = row
        s = jnp.exp(d_t[None, :] * a) * s + b_t[:, None] * (d_t * x_t)[None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((ds, di)), (delta, xs, b, c))
    y = y + lw["d_skip"] * xs
    return (y * _silu(z)) @ _dense(lw["out_proj"]).T, y


def diff_attention(spec, lw, layer: int, q, k, v, window: int | None):
    """q (T, dim), k / v (T, kv_dim) -> (T, dim) before out_proj."""
    hs, t_len = spec.head_size, q.shape[0]
    qp = q.reshape(t_len, spec.n_heads // 2, 2, hs)
    kp = k.reshape(t_len, spec.n_kv_heads // 2, 2, hs)
    vp = v.reshape(t_len, spec.n_kv_heads // 2, 2 * hs)
    group = (spec.n_heads // 2) // (spec.n_kv_heads // 2)
    kp = jnp.repeat(kp, group, axis=1)
    vp = jnp.repeat(vp, group, axis=1)
    pos = jnp.arange(t_len)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[:, None] - pos[None, :] < window
    scores = jnp.einsum("tjsd,ujsd->jstu", qp, kp) / math.sqrt(hs)
    att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("jstu,ujd->tjsd", att, vp)             # (T, pairs, 2, 2d)
    lam = jnp.asarray(lw["lam"])
    li = lambda_init(layer)
    lam_full = jnp.exp(lam[0] @ lam[1]) - jnp.exp(lam[2] @ lam[3]) + li
    a = a[:, :, 0] - lam_full * a[:, :, 1]
    a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + spec.norm_eps)
    return ((1.0 - li) * a * lw["subln"]).reshape(t_len, -1)


def _layer_of(stack: dict, i: int) -> dict:
    return {k: (type(v)(v.qs[i], v.d16[i]) if hasattr(v, "qs") else v[i])
            for k, v in stack.items()}


def forward(tree: dict, spec, tokens) -> np.ndarray:
    """Logits (T, vocab) float32 of ``tokens`` at positions 0..T-1."""
    hy, eps = spec.hybrid, spec.norm_eps
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[
            np.asarray(tokens)]
        seen: dict = {}
        memory = shared_kv = None
        for layer, kind in enumerate(hy.kinds):
            lw = _layer_of(tree[kind], seen.get(kind, 0))
            seen[kind] = seen.get(kind, 0) + 1
            u = _layernorm(x, lw["ln1_g"], lw["ln1_b"], eps)
            if kind == "mamba":
                mix, y = mamba(spec, lw, u)
                if layer == hy.memory_layer:
                    memory = y
            elif kind == "gmu":
                mix = (_silu(u @ _dense(lw["in_proj"]).T) * memory) @ _dense(
                    lw["out_proj"]).T
            else:
                d, kv = spec.dim, spec.kv_dim
                if kind == "xattn":
                    q = u @ _dense(lw["wq"]).T + lw["bq"]
                    k, v = shared_kv
                else:
                    qkv = u @ _dense(lw["wqkv"]).T + lw["bqkv"]
                    q, k, v = qkv[:, :d], qkv[:, d:d + kv], qkv[:, d + kv:]
                    if kind == "full":
                        shared_kv = (k, v)
                a = diff_attention(spec, lw, layer, q, k, v,
                                   hy.window if kind == "swa" else None)
                mix = a @ _dense(lw["wo"]).T + lw["bo"]
            h = x + mix
            gu = _layernorm(h, lw["ln2_g"], lw["ln2_b"], eps) @ _dense(
                lw["w13"]).T
            hid = gu.shape[-1] // 2
            x = h + (_silu(gu[:, :hid]) * gu[:, hid:]) @ _dense(lw["w2"]).T
        x = _layernorm(x, tree["rms_final"], tree["rms_final_b"], eps)
        return np.asarray(x @ _dense(tree["wcls"]).T)
