"""The plain reference of Motif-3-Beta's block (``model_type`` "Motif",
``attention_cls`` "gdla"): grouped differential attention on a latent plane,
"full" and "sliding" layers, PolyNorm in every FFN, and four residual
streams: the full forward in ``jax.numpy``, float32, ``highest`` matmul
precision, EXPANDED (every position's ``k_nope`` / ``v`` formed from its
latent row, the heads of a group written out, the noise head subtracted from
each signal head, the window as a mask), no cache, no kernels, no batching.
What ``models/reference_latent.py`` (the low-rank q, the latent row, RoPE,
the router, the experts' sum) and ``models/reference_hyper.py`` (the
streams) state already is imported, not stated again. The tests compare the
program (models/latent.py) with it on logits.

For h = RMSNorm_att of the streams' mix, H heads in G = ``kv_groups``
groups of P = H / G, ``la = spec.latent``:

  [k_nope | v]_g = W_kvb,g c_kv           a GROUP's keys and values (G of them)
  a_j  = softmax_i((q_nope_j . k_nope_g,i + q_rope_j . k_rope_i) scale) v_g,i
         for head j of group g = j // P, over the positions its layer's kind
         sees: every i <= t ("full") or t - window < i <= t ("sliding")
  lambda = sigmoid(h W_lambda)            one a token and signal head
  d_s  = a_s - lambda_s a_noise(g(s))     s a signal head: one of the first
                                          P - 1 heads of its group; the
                                          group's LAST head is its noise head
  d   <- d * sigmoid(h W_g)               elementwise, where ``la.gate``
  x_att = W_o [d_1 .. d_S]                S = G (P - 1) signal heads
  FFN:  w2(PolyNorm(w1 h') * w3 h'), PolyNorm(z) = s (w0 n(z^3) + w1 n(z^2)
        + w2 n(z) + clip(b, -c, c)), n(u) = u / sqrt(mean(u^2) + eps) over
        the FFN's own width (``spec.activation``; SiLU where it says so)

What the published config does not settle, and the reading taken here (each
is an entry of ``assumed`` in benchmark/configs/motif-3-beta-q40-ep8.json):
* [assumed] ``num_attention_heads`` 80 COUNTS the 16 noise heads (80 = 16 x
  (4 + 1)); the other reading is 80 signal + 16 noise = 96 query heads;
* [assumed] a group's noise head is its LAST head (any other place is a
  permutation of ``wq_b``'s rows);
* [assumed] lambda reads the normed layer input h (Differential Transformer
  V2's), one a token and signal head, no ``lambda_init``, no sub-norm;
* [assumed] the gate multiplies after the subtraction and before ``wo``
  (arXiv:2505.06708's G1), elementwise;
* [assumed] ``apply_yarn_scaling`` false: plain frequencies, scale
  ``qk_dim^-1/2``, no YaRN blend;
* [assumed] layer i is "full" where (i + 1) % ``sliding_window_period`` == 0
  (the converter writes the list; this module reads ``la.kinds``);
* [assumed] the hyper-connections are the paper's (arXiv:2512.24880) with
  no clamp on the logits, and ``hidden_clamp`` clips each sub-layer's
  written-back streams;
* [assumed] PolyNorm's ``polynorm_output_scale`` multiplies the whole sum
  and ``polynorm_bias_clamp`` clips the bias alone; its eps is
  ``rms_norm_eps``; one (w0, w1, w2, b) a layer's FFN, shared by its routed
  and shared experts;
* the multi-token-prediction layer is left out (reference_latent.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_hyper as rh
from . import reference_latent as rl


def polynorm(z, pn_w, act, eps: float):
    """PolyNorm of the gate projection z (..., width): the mean runs over
    the FFN's own width."""
    pn_w = jnp.asarray(pn_w, jnp.float32)

    def n(u):
        return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)

    bias = jnp.clip(pn_w[3], -act.clamp, act.clamp) if act.clamp else pn_w[3]
    return act.scale * (pn_w[0] * n(z ** 3) + pn_w[1] * n(z ** 2)
                        + pn_w[2] * n(z) + bias)


def activation(spec, lw):
    """What a layer's FFN applies to its gate projection."""
    if spec.activation.kind == "silu":
        return jax.nn.silu
    return functools.partial(polynorm, pn_w=lw["pn_w"], act=spec.activation,
                             eps=spec.norm_eps)


def head_outputs(spec, lw, x, kind: str = "full"):
    """(h (T, dim) the normed input, a (T, G, P, v): every head's finished
    softmax output, head p of group g) of a ``kind`` layer, expanded."""
    la, groups = spec.latent, spec.latent_groups
    t = x.shape[0]
    h, q_nope, q_rope, c_kv, k_rope, scale = rl.projections(spec, lw, x)
    kvb = (c_kv @ rl._dense(lw["wkv_b"]).T).reshape(
        t, groups, la.nope_dim + la.v_dim)
    k_nope, v = kvb[..., :la.nope_dim], kvb[..., la.nope_dim:]
    by_group = lambda a: a.reshape(t, groups, -1, a.shape[-1])  # noqa: E731
    scores = (jnp.einsum("tgpd,sgd->gpts", by_group(q_nope), k_nope)
              + jnp.einsum("tgpd,sd->gpts", by_group(q_rope), k_rope)) * scale
    ago = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = ago >= 0
    if kind == "sliding":       # the last ``window``, the current included
        seen &= ago < la.window
    att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return h, jnp.einsum("gpts,sgd->tgpd", att, v)


def attention_out(spec, lw, x, kind: str = "full", lambda_zero: bool = False):
    """The attention sub-block of input x (which it norms), without the
    residual. ``lambda_zero``: the noise heads left out (a control)."""
    la = spec.latent
    t = x.shape[0]
    h, a = head_outputs(spec, lw, x, kind)
    if la.noise_heads:
        signal, noise = a[:, :, :-1], a[:, :, -1:]
        lam = jax.nn.sigmoid(h @ jnp.asarray(lw["w_lambda"], jnp.float32).T)
        lam = 0.0 if lambda_zero else lam.reshape(*signal.shape[:3], 1)
        a = signal - lam * noise
    d = a.reshape(t, -1)
    if la.gate:
        d = d * jax.nn.sigmoid(h @ rl._dense(lw["wg"]).T)
    return d @ rl._dense(lw["wo"]).T


def _dense_ffn(spec, lw, x):
    return (rl._swiglu(rl._rmsnorm(x, lw["rms_ffn"], spec.norm_eps),
                       lw["w1"], lw["w2"], lw["w3"], activation(spec, lw)),)


def _sublayer(spec, lw, sub: str, x, fn):
    """The residual path around ``fn``: the streams' (reference_hyper.py),
    or the plain add where the spec has one stream."""
    if spec.hyper is not None:
        return rh.sublayer(spec, lw, sub, x, fn)
    y, *more = fn(x)
    return (x + y, *more)


def forward(tree: dict, spec, tokens, lambda_zero: bool = False):
    """Logits (T, vocab), router margins (T, expert layers) and chosen
    expert ids (T, expert layers, k) of one sequence ``tokens`` (T,)."""
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        if spec.hyper is not None:      # entry: replication
            x = jnp.repeat(x[:, None, :], spec.hyper.streams, axis=1)
        margins, routed = [], []
        for layer, kind in enumerate(spec.latent_kinds):
            dense = layer < spec.n_dense_layers
            lw = rl._layer_of(tree["dense"] if dense else tree,
                              layer - (0 if dense else spec.n_dense_layers))
            (x,) = _sublayer(spec, lw, "att", x, lambda h, lw=lw, kind=kind: (
                attention_out(spec, lw, h, kind, lambda_zero),))
            if dense:
                (x,) = _sublayer(spec, lw, "ffn", x, lambda h, lw=lw:
                                 _dense_ffn(spec, lw, h))
            else:
                x, margin, ids = _sublayer(
                    spec, lw, "ffn", x, lambda h, lw=lw: rl.experts_out(
                        spec, lw, h, act=activation(spec, lw)))
                margins.append(margin)
                routed.append(ids)
        if spec.hyper is not None:      # exit: the streams' sum
            x = x.sum(axis=1)
        logits = rl._rmsnorm(x, tree["rms_final"],
                             spec.norm_eps) @ rl._dense(tree["wcls"]).T
    return (np.asarray(logits), np.stack([np.asarray(m) for m in margins], 1),
            np.stack([np.asarray(r) for r in routed], 1))
