"""The plain reference of an ssd spec (``TransformerSpec.ssd``: Nemotron-H's
layout as NVIDIA-Nemotron-3-Nano-30B-A3B lays it out,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): the whole
forward at every position in straightforward ``jax.numpy``, float32,
``highest`` matmul precision, with no kernels, no cache, nothing carried
between calls and no batching, a layer at a time. It takes the loader's codec
tree (``io/loader``'s contract: a stack a layer kind under ``tree["mamba2"]``
/ ``tree["full"]`` / ``tree["experts"]``; Q40 leaves as ``(qs, d16)`` pairs
or dense arrays) and the ``TransformerSpec``, and dequantizes by the codec's
own definition. The tests compare the program (``models/nemotron.py``: decode
step, chunked prefill, ``serve``) with it on logits.

Every layer i is ONE mixer: ``x <- x + mixer_i(u)``, ``u = RMSNorm(x; g_i,
eps)``, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``. The embedding lookup
is unscaled; a final RMSNorm and an untied classifier follow.

"mamba2" (H heads of P channels, d_inner = H P, G groups, N states, conv
width K over d_inner + 2 G N channels), the RECURRENCE, a position at a time:

  [z (d_inner) | xBC (d_inner + 2 G N)] = W_zx u;   dt (H) = W_dt u
  xBC'_t[c] = silu(sum_j w_conv[j, c] xBC_{t-K+1+j}[c] + b_conv[c])
  [x (H, P) | B (G, N) | C (G, N)] = xBC'_t
  dt_t = softplus(dt_t + dt_bias);   A = -exp(A_log)      (a scalar a head)
  h_t[h] = exp(dt_t[h] A[h]) h_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[h // (H / G)]
  y_t[h] = h_t[h] C_t[h // (H / G)] + D[h] x_t[h]
  y <- y * silu(z);  y <- y / sqrt(mean over each group of d_inner / G (y^2)
    + eps) * g_norm                                       (gate, THEN norm)
  out = W_out y

"full": q = W_q u (n_heads x head), k = W_k u, v = W_v u (n_kv_heads x head),
no bias, no q / k norm, NO positional encoding, causal softmax(q k^T /
sqrt(head)) v with n_heads / n_kv_heads query heads a KV head, out = W_o a.

"experts": s = sigmoid (or softmax) of W_g u in float32, the k largest of
s + b (where the router has a choice bias), weights the unbiased s [over
their sum + 1e-20] times the scale; expert_e(u) = W_down,e act(W_up,e u)
with act = relu(.)^2 (or, a gated spec, W_down,e (silu(W_up,e u) * W_3,e
u)); plus the shared expert of the same form on the same u. Where the file
holds a SHARE of the experts (``layout.held`` from ``layout.offset``) the
sum runs over the chosen experts held here, at the weights the whole router
gave them: one chip's partial sum of an expert-parallel group.

Departures from the published model, each also under ``assumed`` in
``benchmark/configs/nemotron-3-nano-q40-ep2.json``: the residual carry and
the state are float32 (published: bfloat16, ``residual_in_fp32`` false);
weights are the file's Q40 values dequantized; ``in_proj`` is stored as
[z | xBC] rows (Q40) and the ``dt`` rows apart (float32), which changes no
product; ``dt`` is not clamped (``time_step_limit`` is (0, inf)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .reference_laguna import _dense, _layer_of, _rmsnorm, route


def _activate(spec, lw, h, prefix: str):
    """An FFN's hidden values from the rows h: ``relu(w1 h)^2`` or the
    gated ``silu(w1 h) * (w3 h)``; w1 (and w3) (..., hidden, dim)."""
    up = jnp.einsum("...hd,...d->...h", _dense(lw[prefix + "w1"]), h)
    if not spec.activation.gated:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(up) * jnp.einsum(
        "...hd,...d->...h", _dense(lw[prefix + "w3"]), h)


def mamba2(spec, lw, u, precision=None):
    """The mixer's output (T, dim) from the normed rows u (T, dim): the
    recurrence, a position at a time (``precision`` "bfloat16" rounds the
    operands of every product to bfloat16 first: the benchmark's control)."""
    sd = spec.ssd
    H, P, G, N, K = sd.heads, sd.head_dim, sd.groups, sd.d_state, sd.d_conv
    di, T = sd.d_inner, u.shape[0]
    low = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) \
        if precision == "bfloat16" else (lambda a: a)
    zx = low(u) @ low(_dense(lw["in_zx"])).T
    z, xbc = zx[:, :di], zx[:, di:]
    dt = low(u) @ low(jnp.asarray(lw["in_dt"], jnp.float32)).T
    run = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    w = jnp.asarray(lw["conv_w"], jnp.float32)
    xbc = jax.nn.silu(sum(low(run[j:j + T]) * low(w[j]) for j in range(K))
                      + lw["conv_b"])
    x = xbc[:, :di].reshape(T, H, P)
    b = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                    # (T, H)
    a = -jnp.exp(jnp.asarray(lw["a_log"], jnp.float32))         # (H,)

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = jnp.exp(dt_t * a)[:, None, None] * h + low(
            dt_t[:, None] * x_t)[:, :, None] * low(b_t)[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", low(h), low(c_t))

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, b, c, dt))
    y = (y + lw["d_skip"][:, None] * x).reshape(T, di)
    y = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + spec.norm_eps)
    y = y.reshape(T, di) * lw["norm_g"]
    return low(y) @ low(_dense(lw["out_proj"])).T


def attention(spec, lw, u):
    """Causal grouped-query softmax attention of the normed rows u, no
    positional encoding."""
    T, hs = u.shape[0], spec.head_size
    n_kv, mul = spec.n_kv_heads, spec.n_heads // spec.n_kv_heads
    q = (u @ _dense(lw["wq"]).T).reshape(T, n_kv, mul, hs)
    k = (u @ _dense(lw["wk"]).T).reshape(T, n_kv, hs)
    v = (u @ _dense(lw["wv"]).T).reshape(T, n_kv, hs)
    s = jnp.einsum("tgmd,sgd->gmts", q, k) / jnp.sqrt(jnp.float32(hs))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("gmts,sgd->tgmd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, -1) @ _dense(lw["wo"]).T


def experts(spec, lw, u):
    """(the expert mixer's output, margin (T,), chosen ids (T, k)). The
    stacks hold experts ``layout.offset .. + n_experts_held - 1``: a chosen
    expert that is not among them adds nothing here."""
    w, ids, margin = route(spec, lw["moe_gate"], lw.get("moe_bias"), u)
    w2 = _dense(lw["moe_w2"])
    y = jnp.zeros_like(u)
    off, held = spec.layout.offset, spec.n_experts_held
    for j in range(spec.n_active_experts):   # a row's j-th expert, in turn
        here = (ids[:, j] >= off) & (ids[:, j] < off + held)
        e = jnp.where(here, ids[:, j] - off, 0)
        one = {k: jax.tree_util.tree_map(lambda a: a[e], v)
               for k, v in lw.items() if k in ("moe_w1", "moe_w3")}
        hid = _activate(spec, one, u, "moe_")
        y = y + jnp.where(here, w[:, j], 0.0)[:, None] * jnp.einsum(
            "tdh,th->td", w2[e], hid)
    if "sh_w1" in lw:
        y = y + _activate(spec, lw, u, "sh_") @ _dense(lw["sh_w2"]).T
    return y, margin, ids


def forward(tree: dict, spec, tokens):
    """Logits (T, vocab), router margins (T, expert layers) and chosen
    expert ids (T, expert layers, k) of one sequence ``tokens`` (T,)."""
    tokens = np.asarray(tokens)
    seen = dict.fromkeys(("mamba2", "full", "experts"), 0)
    margins, routed = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        for kind in spec.ssd.kinds:
            lw = _layer_of(tree[kind], seen[kind])
            seen[kind] += 1
            u = _rmsnorm(x, lw["rms_att"], spec.norm_eps)
            if kind == "mamba2":
                x = x + mamba2(spec, lw, u)
            elif kind == "full":
                x = x + attention(spec, lw, u)
            else:
                y, margin, ids = experts(spec, lw, u)
                x = x + y
                margins.append(margin)
                routed.append(ids)
        logits = _rmsnorm(x, tree["rms_final"],
                          spec.norm_eps) @ _dense(tree["wcls"]).T
    t, k = len(tokens), spec.n_active_experts
    return (np.asarray(logits),
            np.stack([np.asarray(m) for m in margins], 1) if margins
            else np.zeros((t, 0), np.float32),
            np.stack([np.asarray(r) for r in routed], 1) if routed
            else np.zeros((t, 0, k), np.int32))
