"""The plain reference of a Kimi-Delta-Attention / latent-attention expert
model (Ling-3.0-flash's block, ``TransformerSpec.kda``): the forward pass in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision, with no
kernels, no cache, no chunk form and no batching. It takes the loader's tree
(a stack a mixer kind under ``"kda"`` / ``"full"``, the leading dense layers'
FFN under ``"dense"``, the expert layers' at the top level; Q40 leaves as
``(qs, d16)`` pairs or dense arrays) and the ``TransformerSpec``. The tests
compare the program with it on logits.

For x (T, dim) at positions 0..T-1, a pre-norm residual block a layer,
``x += mixer(RMSNorm_att(x))`` then ``x += ffn(RMSNorm_ffn(x))``; layer i's
mixer is ``spec.latent.kinds[i]``, H heads, ``kd = spec.kda``, D =
``kd.head_dim`` (key and value channels alike):

  kda     [q | k | v | a | g] = W_in h, each H D wide; q, k and v each pass
          a depthwise causal convolution of ``d_conv`` taps (tap j meets
          the input d_conv - 1 - j positions back; no bias) and SiLU; a
          head's q <- q / ||q|| * D^-1/2, k <- k / ||k|| (the L2 norm with
          1e-6 under the root); the decay's exponent, a head h and key
          channel c: g = lower_bound * sigmoid(exp(a_log[h]) * (a + dt_bias)
          [h, c]), in [lower_bound, 0); b = sigmoid(W_beta h), one a head;
          the RECURRENCE a position at a time on S (H, D, D), from zeros:
            S <- Diag(exp(g_t)) S;  S <- S + b_t k_t (v_t - S^T k_t)^T;
            o_t = S^T q_t
          then o <- RMSNorm over ALL H D outputs (one group, gain norm_g)
          times sigmoid(g), elementwise, and W_o. No positional encoding.
  full    latent attention with no query rank: [q_nope | q_rope]_h = W_q h;
          [c_kv | k_rope] = W_kva h, c_kv = RMSNorm_kva(c_kv); RoPE
          (interleaved pairs, plain) on every head's q_rope and the ONE
          k_rope; [k_nope | v]_h = W_kvb c_kv; score = (q_nope . k_nope +
          q_rope . k_rope) qk_dim^-1/2, causal softmax over the
          MATERIALISED keys and values; a head's output times sigmoid(W_hg
          h)[head], ONE gate a head; W_o.
  FFN     a leading dense layer: w2(silu(w1 h) * w3 h). An expert layer:
          ``reference_latent.route`` (DeepSeek-V3's router: sigmoid scores,
          the choice on s + bias within the kept groups, the weights the
          unbiased s renormalised and scaled), the chosen experts THIS TREE
          HOLDS, and the shared expert; an expert is w2(silu(min(w1 h, L)) *
          clip(w3 h, -L, L)) with L the layer's ``ffn_limit`` entry (0: no
          clamp; [0] the routed experts', [1] the shared expert's).

Departures from the publication (inclusionAI/Ling-3.0-flash; each is under
``assumed`` in benchmark/configs/ling-3-flash-q40-ep8.json with its reason):
* the multi-token-prediction module is left out: next-token logits do not
  depend on it;
* ``group_norm_size`` 1 is read as ONE group over a KDA layer's outputs;
* ``use_qk_norm`` is KDA's L2 norm of q and k alone (no gain on the latent
  layers' expanded keys);
* the decay and the output gate of a KDA layer are full-rank matrices, the
  output gate elementwise; the head-wise gate is the latent layers';
* weights are the file's Q40 values dequantized, not bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .reference_latent import (_dense, _rmsnorm, _rope, rope_frequencies,
                               route)

L2_EPS = 1e-6


def _causal_conv(x, taps):
    """x (T, n) through a depthwise causal convolution, taps (K, n): output
    t is sum_j taps[j] x[t - (K - 1) + j], zeros before position 0."""
    k = taps.shape[0]
    run = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(run[j:j + x.shape[0]] * taps[j] for j in range(k))


def kda_inputs(spec, lw, h):
    """h (T, dim) normed -> (q, k (T, H, D) normed, v (T, H, D), g (T, H,
    D) <= 0 the decay's exponent, b (T, H), z (T, H D) the output gate's
    logits)."""
    kd = spec.kda
    w, shape = kd.width, (h.shape[0], kd.heads, kd.head_dim)
    proj = h @ _dense(lw["in_qkvag"]).T
    qkv = jax.nn.silu(_causal_conv(proj[:, :3 * w],
                                   jnp.asarray(lw["conv_w"], jnp.float32)))
    q, k, v = (qkv[:, i * w:(i + 1) * w].reshape(shape) for i in range(3))

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    a = (proj[:, 3 * w:4 * w] + jnp.asarray(lw["dt_bias"])).reshape(shape)
    g = kd.lower_bound * jax.nn.sigmoid(
        jnp.exp(jnp.asarray(lw["a_log"]))[None, :, None] * a)
    b = jax.nn.sigmoid(h @ jnp.asarray(lw["w_beta"], jnp.float32).T)
    return (l2(q) * kd.head_dim ** -0.5, l2(k), v, g, b, proj[:, 4 * w:])


def kda_mixer(spec, lw, x):
    """The KDA sub-block of input x (which it norms), without the
    residual: the recurrence a position at a time."""
    kd = spec.kda
    h = _rmsnorm(x, lw["rms_att"], spec.norm_eps)
    q, k, v, g, b, z = kda_inputs(spec, lw, h)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs        # (H, D) x 4, (H,)
        s = jnp.exp(g_t)[..., None] * s
        u = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + (b_t[:, None] * k_t)[..., None] * (v_t - u)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((kd.heads, kd.head_dim, kd.head_dim), jnp.float32)
    _, o = jax.lax.scan(step, s0, (q, k, v, g, b))
    o = _rmsnorm(o.reshape(x.shape[0], kd.width), lw["norm_g"],
                 spec.norm_eps) * jax.nn.sigmoid(z)
    return o @ _dense(lw["wo"]).T


def latent_mixer(spec, lw, x):
    """The latent-attention sub-block of input x (which it norms),
    EXPANDED, without the residual."""
    la, nh, eps = spec.latent, spec.n_heads, spec.norm_eps
    t = x.shape[0]
    freq, factor, scale = rope_frequencies(spec)
    h = _rmsnorm(x, lw["rms_att"], eps)
    if la.q_rank:
        h_q = _rmsnorm(h @ _dense(lw["wq_a"]).T, lw["rms_q_a"], eps)
        q = h_q @ _dense(lw["wq_b"]).T
    else:
        q = h @ _dense(lw["wq"]).T
    q = q.reshape(t, nh, la.qk_dim)
    q_nope, q_rope = q[..., :la.nope_dim], _rope(q[..., la.nope_dim:], freq,
                                                 factor)
    kv = h @ _dense(lw["wkv_a"]).T
    c_kv = _rmsnorm(kv[:, :la.kv_rank], lw["rms_kv_a"], eps)
    k_rope = _rope(kv[:, la.kv_rank:], freq, factor)
    kvb = (c_kv @ _dense(lw["wkv_b"]).T).reshape(t, nh,
                                                 la.nope_dim + la.v_dim)
    k_nope, v = kvb[..., :la.nope_dim], kvb[..., la.nope_dim:]
    scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
              + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * scale
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ao = jnp.einsum("hts,shd->thd", att, v)
    if la.head_gate:
        ao = ao * jax.nn.sigmoid(
            h @ jnp.asarray(lw["w_hgate"], jnp.float32).T)[..., None]
    return ao.reshape(t, nh * la.v_dim) @ _dense(lw["wo"]).T


def clamped_swiglu(h, w1, w2, w3, limit=0.0):
    """w2(silu(min(w1 h, L)) * clip(w3 h, -L, L)); L = 0: no clamp. The
    weights dense already; a leading axis of ``w*`` (one matrix a row of
    h) is a row's own expert."""
    if w1.ndim == 3:
        gate = jnp.einsum("thd,td->th", w1, h)
        up = jnp.einsum("thd,td->th", w3, h)
    else:
        gate, up = h @ w1.T, h @ w3.T
    cap = jnp.where(limit > 0, limit, jnp.inf)
    hid = jax.nn.silu(jnp.minimum(gate, cap)) * jnp.clip(up, -cap, cap)
    return (jnp.einsum("tdh,th->td", w2, hid) if w2.ndim == 3
            else hid @ w2.T)


def experts_out(spec, lw, x, shared: bool = True):
    """(the expert sub-block's output of input x, which it norms; the
    router's margin (T,); the chosen ids (T, k)). ``shared`` False leaves
    the shared expert out (the share test counts it once over the
    shares)."""
    h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
    w, ids, margin = route(spec, lw["moe_gate"], lw.get("moe_bias"), h)
    limit = jnp.asarray(lw["ffn_limit"], jnp.float32) if "ffn_limit" in lw \
        else jnp.zeros((2,), jnp.float32)
    held, off = spec.n_experts_held, spec.layout.offset
    w1, w2, w3 = (_dense(lw[n]) for n in ("moe_w1", "moe_w2", "moe_w3"))
    y = jnp.zeros_like(x)
    for j in range(spec.n_active_experts):   # a row's j-th expert, in turn
        local = ids[:, j] - off
        here = (local >= 0) & (local < held)
        e = jnp.clip(local, 0, held - 1)
        out = clamped_swiglu(h, w1[e], w2[e], w3[e], limit[0])
        y = y + jnp.where(here, w[:, j], 0.0)[:, None] * out
    if shared and spec.layout.shared:
        y = y + clamped_swiglu(h, *(_dense(lw[n]) for n in (
            "sh_w1", "sh_w2", "sh_w3")), limit[1])
    return y, margin, ids


def _layer_of(stack: dict, i: int) -> dict:
    return {k: jax.tree_util.tree_map(lambda a: a[i], v)
            for k, v in stack.items() if not isinstance(v, dict)
            and k not in ("tok_embedding", "rms_final", "wcls")}


def forward(tree: dict, spec, tokens):
    """Logits (T, vocab), router margins (T, expert layers) and chosen
    expert ids (T, expert layers, k) of one sequence ``tokens`` (T,)."""
    tokens = np.asarray(tokens)
    seen = {"kda": 0, "full": 0}
    margins, routed = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        for i, kind in enumerate(spec.latent.kinds):
            lw = _layer_of(tree[kind], seen[kind])
            seen[kind] += 1
            x = x + (kda_mixer if kind == "kda" else latent_mixer)(spec, lw,
                                                                   x)
            k = spec.n_dense_layers
            if i < k:
                lw = _layer_of(tree["dense"], i)
                h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
                x = x + clamped_swiglu(h, *(_dense(lw[n]) for n in (
                    "w1", "w2", "w3")))
            else:
                y, margin, ids = experts_out(spec, _layer_of(tree, i - k), x)
                x = x + y
                margins.append(margin)
                routed.append(ids)
        logits = _rmsnorm(x, tree["rms_final"],
                          spec.norm_eps) @ _dense(tree["wcls"]).T
    return (np.asarray(logits), np.stack([np.asarray(m) for m in margins], 1),
            np.stack([np.asarray(r) for r in routed], 1))
