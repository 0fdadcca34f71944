"""The plain reference of a latent-attention expert model whose residual path
is n parallel streams (manifold-constrained hyper-connections, mHC,
arXiv:2512.24880; Xing4.0's ``hc_mult`` / ``hc_sinkhorn_iters`` / ``hc_eps``
/ ``mhc_h_res_clamp_min|max``): the full forward in ``jax.numpy``, float32,
``highest`` matmul precision, EXPANDED attention, Sinkhorn as a Python loop,
no cache, no kernels, no batching. The sub-layers themselves are
``models/reference_latent.py``'s (their docstring states them); what is
stated here is the path around them. The tests compare the program
(ops/hyper.py, models/latent.py) with it on logits.

Per token, with n = ``hyper.streams`` streams X in R^(n x C), for each of a
layer's two sub-layers F (attention: RMSNorm, latent attention, ``wo``;
feed-forward: RMSNorm, then SwiGLU in the leading dense layers and router +
routed experts + shared expert in the others):

    xhat   = vec(X) / sqrt(mean(vec(X)^2) + eps)                  vec(X) in R^(nC)
    p      = a_pre  * (xhat @ Phi_pre)  + b_pre                    R^n
    q      = a_post * (xhat @ Phi_post) + b_post                   R^n
    Rt     = a_res  * mat(xhat @ Phi_res) + B_res                  R^(n x n)
    H_pre  = sigmoid(p);   H_post = 2 * sigmoid(q)
    H_res  = SK(clip(Rt, clamp_min, clamp_max)):  M = exp(.), then
             ``sinkhorn_iters`` times: divide each column by (its sum +
             hc_eps), then each row by (its sum + hc_eps)
    h      = sum_i H_pre[i] X[i]                                   R^C
    y      = F(h)
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] * y

Entry: X[i] = embedding(token) for every i. Exit: sum_i X[i], then the final
RMSNorm and the classifier. The tree holds, for sub-layer s in (att, ffn),
``hc_<s>_phi`` (2 n + n^2, n C) whose rows are [Phi_pre^T | Phi_post^T |
Phi_res^T (row-major i, j)], ``hc_<s>_gate`` (a_pre, a_post, a_res) and
``hc_<s>_bias`` (b_pre, b_post, B_res row-major).

What the published config does not settle, and the choice made here (each is
listed under ``assumed`` in benchmark/configs/xing4-29b-a4b-q40.json):
* the order inside a Sinkhorn iteration (columns, then rows) and where
  ``hc_eps`` enters (added to each sum before the division);
* the flat norm takes ``rms_norm_eps`` and has no gain;
* entry by replication and exit by sum (the hyper-connections paper's);
* the multi-token-prediction module is left out (reference_latent.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_latent as rl


def sinkhorn(logits, iters: int, eps: float):
    """(T, n, n) -> ``iters`` alternating normalisations of exp(logits):
    columns first, then rows, each sum with ``eps`` added."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)   # each column's sum
        m = m / (m.sum(axis=-1, keepdims=True) + eps)   # each row's sum
    return m


def coefficients(spec, lw, sub: str, x, low: bool = False):
    """(H_pre (T, n), H_post (T, n), H_res (T, n, n)) of streams x (T, n, C).
    ``low`` rounds the projection's two operands to bfloat16 first (the
    control that has the coefficient product alone one precision down)."""
    hc, n = spec.hyper, spec.hyper.streams
    t = x.shape[0]
    flat = x.reshape(t, -1)
    xhat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + spec.norm_eps)
    phi = jnp.asarray(lw[f"hc_{sub}_phi"], jnp.float32)
    if low:
        xhat, phi = (a.astype(jnp.bfloat16).astype(jnp.float32)
                     for a in (xhat, phi))
    z = xhat @ phi.T
    a = jnp.asarray(lw[f"hc_{sub}_gate"], jnp.float32)
    b = jnp.asarray(lw[f"hc_{sub}_bias"], jnp.float32)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    return pre, post, sinkhorn(jnp.clip(res, hc.clamp_min, hc.clamp_max),
                               hc.sinkhorn_iters, hc.eps)


def sublayer(spec, lw, sub: str, x, fn, low: bool = False):
    """X' of streams x (T, n, C) around the sub-layer ``fn`` (its input (T,
    C) -> (its output (T, C), *what else it returns))."""
    pre, post, res = coefficients(spec, lw, sub, x, low)
    h = jnp.einsum("ti,tic->tc", pre, x)
    y, *more = fn(h)
    out = jnp.einsum("tij,tjc->tic", res, x) + post[:, :, None] * y[:, None, :]
    if spec.hyper.stream_clamp:     # the streams written back, clipped
        out = jnp.clip(out, -spec.hyper.stream_clamp, spec.hyper.stream_clamp)
    return (out, *more)


def _dense_ffn(spec, lw, h):
    return (rl._swiglu(rl._rmsnorm(h, lw["rms_ffn"], spec.norm_eps),
                       lw["w1"], lw["w2"], lw["w3"]),)


def forward(tree: dict, spec, tokens, low_projection: bool = False):
    """Logits (T, vocab), router margins (T, expert layers) and chosen
    expert ids (T, expert layers, k) of one sequence ``tokens`` (T,)."""
    tokens = np.asarray(tokens)
    n, low = spec.hyper.streams, low_projection
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        x = jnp.repeat(emb[:, None, :], n, axis=1)     # entry: replication
        margins, routed = [], []
        for layer in range(spec.n_layers):
            dense = layer < spec.n_dense_layers
            lw = rl._layer_of(tree["dense"] if dense else tree,
                              layer - (0 if dense else spec.n_dense_layers))
            (x,) = sublayer(spec, lw, "att", x, lambda h, lw=lw: (
                rl.attention_out(spec, lw, h),), low)
            if dense:
                (x,) = sublayer(spec, lw, "ffn", x, lambda h, lw=lw:
                                _dense_ffn(spec, lw, h), low)
            else:
                x, margin, ids = sublayer(
                    spec, lw, "ffn", x, lambda h, lw=lw: rl.experts_out(
                        spec, lw, h), low)
                margins.append(margin)
                routed.append(ids)
        x = x.sum(axis=1)                               # exit: the sum
        logits = rl._rmsnorm(x, tree["rms_final"],
                             spec.norm_eps) @ rl._dense(tree["wcls"]).T
    return (np.asarray(logits), np.stack([np.asarray(m) for m in margins], 1),
            np.stack([np.asarray(r) for r in routed], 1))
