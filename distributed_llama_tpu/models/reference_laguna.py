"""The plain reference of a mixer-kinds model (``TransformerSpec.mixers``:
Laguna-XS.2's layout, https://huggingface.co/poolside/Laguna-XS.2, and
MiMo-V2-Flash's, https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash): the whole
forward at every position in straightforward ``jax.numpy``, float32,
``highest`` matmul precision, with no kernels, no cache, nothing carried
between calls and no batching, a layer at a time. It takes the loader's codec
tree (``io/loader``'s contract: the mixers a stack a kind under
``tree["full"]`` / ``tree["sliding"]``, the leading dense layers' FFNs under
``tree["dense"]``, the expert layers' FFNs the top-level stacks; Q40 leaves
as ``(qs, d16)`` pairs or dense arrays) and the ``TransformerSpec``, and
dequantizes by the codec's own definition. The tests compare the program
(``models/laguna.py``: decode step, chunked prefill, ``serve``) with it on
logits.

Layer l has kind k (``full`` or ``sliding``) with H_k query heads, G_k KV
heads (the spec's ``n_kv_heads`` unless the kind states its own), a K head
size d and a V head size d_v (d unless stated), no biases. x (T, dim) at
positions 0..T-1:

  h = RMSNorm(x);  q = h Wq_k (H_k x d),  key = h Wk (G_k x d),
    v = h Wv (G_k x d_v)
  RoPE on q and key BY KIND: the first ``rotary_dim`` dimensions of a head in
    interleaved pairs (2p, 2p + 1) (the converter's permutation of the
    checkpoint's half-split pairs) at frequency theta_k^(-2p / rotary_dim),
    under YaRN where the kind states it (frequencies blended between f and
    f / factor over the correction range of (beta_fast, beta_slow) rotations
    in ``original_positions``; cos and sin multiplied by the attention
    factor 0.1 mscale ln(factor) + 1, so scores carry its square); the
    other dimensions pass unrotated
  scores = q . key / sqrt d, causal; a ``sliding`` layer reads the last
    ``window`` positions, the current one among them; softmax; head j reads
    KV head j // (H_k / G_k). A kind with a SINK has a learned float32 score
    s_j a query head, which joins head j's softmax as one more column and
    is then dropped (it has no value): a row's weights sum to less than 1
  the head's output times ``value_scale`` (1 unless stated)
  g = sigmoid(h Wg_k), one value a query head (float32); head j's output is
    multiplied by g_j (where the spec has the gate); then
    y = x + concat(o) Wo_k
  h2 = RMSNorm(y).  A leading dense layer: y + SwiGLU_dense(h2).  The others:
    s = sigmoid (or softmax) (h2 Wr), the k largest (of s + b where the
    router has a choice bias b), w = scale s_sel [/ sum(s_sel)],
    y + sum_e w_e SwiGLU^e(h2) [+ SwiGLU^shared(h2)]; where the file holds
    a SHARE of the experts (``layout.held`` from ``layout.offset``), the
    sum runs over the chosen experts held here, at the weights the whole
    router gave them: one chip's partial sum of an expert-parallel group

then the final RMSNorm and the untied classifier.

What is assumed beyond the published ``config.json`` (each also under
``assumed`` in ``benchmark/configs/laguna-xs2-q40.json``): the gate's form
(a sigmoid of the normed layer input, a head a value, applied before Wo: the
sibling Laguna-S-2.1 says ``per_head``); sigmoid router scores with no choice
bias and renormalised top-k weights (the sibling's ``norm_topk_prob``); no
q / k norm; that the window counts the current position; weights are the
file's Q40 values dequantized, not bfloat16. For MiMo-V2-Flash (each also
under ``assumed`` in ``benchmark/configs/mimo-v2-flash-q40-ep8.json``):
rotary pairs interleaved under the converter's permutation; the window
counts the current position; the multi-token-prediction layers are left
out; the checkpoint's tensor names are a guess (``convert.py`` converts no
tensor of it).
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


def rope_table(kind, head_size: int) -> tuple[np.ndarray, float]:
    """(per-pair frequencies (rotary_dim / 2,), the cos / sin factor) of a
    ``MixerKind``: plain RoPE, or YaRN as published."""
    dim = kind.rotary_dim or head_size
    rs = kind.rope_scaling
    freq = kind.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rs is None:
        return freq.astype(np.float32), 1.0

    def correction_dim(rotations):
        return dim * math.log(rs.original_positions
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(kind.rope_theta))

    low = max(math.floor(correction_dim(rs.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rs.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0, 1)
    freq = freq / rs.factor * ramp + freq * (1 - ramp)

    def mscale(s):
        return 0.1 * s * math.log(rs.factor) + 1.0 if rs.factor > 1 else 1.0

    return (freq.astype(np.float32),
            mscale(rs.mscale) / mscale(rs.mscale_all_dim))


def _rope(x, freq, factor):
    """x (T, heads, d) at positions 0..T-1: the leading 2 len(freq)
    dimensions of a head in interleaved pairs, the rest as they are."""
    t, rot = x.shape[0], 2 * len(freq)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    pairs = x[..., :rot].reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                       axis=-1).reshape(*x.shape[:-1], rot)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def attention(spec, lw, kind: str, x, gate: bool = True, rope: bool = True,
              drop=()):
    """x + the attention sub-block of a ``kind`` layer. ``gate`` False
    leaves the per-head gate out and ``rope`` False the kind's RoPE (plain
    RoPE over the whole head at theta 10,000 instead); ``drop`` names what
    else to leave out: "sink" (the softmax's extra column), "value_scale",
    "kv_heads" (the layer reads the FULL kind's count of its KV heads, the
    first ones: the grouping a spec with one KV head count would apply):
    what the tests show to matter."""
    mx, eps = spec.mixers, spec.norm_eps
    mk = mx.of(kind)
    n_kv, d, d_v = spec.kv_shape(kind)
    t = x.shape[0]
    if rope:
        freq, factor = rope_table(mk, d)
    else:
        freq, factor = rope_table(type(mk)(mk.heads), d)
    h = _rmsnorm(x, lw["rms_att"], eps)
    q = _rope((h @ _dense(lw["wq"]).T).reshape(t, mk.heads, d), freq, factor)
    k = _rope((h @ _dense(lw["wk"]).T).reshape(t, n_kv, d), freq, factor)
    v = (h @ _dense(lw["wv"]).T).reshape(t, n_kv, d_v)
    if "kv_heads" in drop:
        n_kv = spec.kv_shape("full")[0]
        k, v = k[:, :n_kv], v[:, :n_kv]
    qg = q.reshape(t, n_kv, mk.heads // n_kv, d)
    scores = jnp.einsum("tgmd,sgd->gmts", qg, k) / math.sqrt(d)
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    see = back >= 0
    if kind == "sliding":
        see = see & (back < mx.window)
    scores = jnp.where(see, scores, -jnp.inf)
    if mk.sink and "sink" not in drop:
        # the sink as the column it is: one more key, with no value
        col = jnp.broadcast_to(jnp.asarray(lw["sink"], jnp.float32).reshape(
            n_kv, -1, 1, 1), (*scores.shape[:-1], 1))
        att = jax.nn.softmax(jnp.concatenate([scores, col], -1), -1)[..., :-1]
    else:
        att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("gmts,sgd->tgmd", att, v).reshape(t, mk.heads, d_v)
    if "value_scale" not in drop:
        o = o * jnp.float32(mx.value_scale)
    if mx.gate and gate:
        g = jax.nn.sigmoid(h @ jnp.asarray(lw["w_hgate"], jnp.float32).T)
        o = o * g[..., None]
    return x + o.reshape(t, -1) @ _dense(lw["wo"]).T


def _swiglu(h, w1, w2, w3):
    return (jax.nn.silu(h @ _dense(w1).T) * (h @ _dense(w3).T)) \
        @ _dense(w2).T


def route(spec, gate, bias, h):
    """(weights (T, k), expert ids (T, k), margin (T,): how far the k-th
    chosen score stands above the best one left out) of rows h."""
    ro, k = spec.router, spec.n_active_experts
    logits = h @ jnp.asarray(gate, jnp.float32).T
    s = jax.nn.sigmoid(logits) if ro.scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    c = s + jnp.asarray(bias, jnp.float32) if bias is not None else s
    top, ids = jax.lax.top_k(c, min(k + 1, spec.n_experts))
    margin = (top[:, k - 1] - top[:, k] if k < spec.n_experts
              else jnp.full(h.shape[:1], jnp.inf))
    ids = ids[:, :k]
    w = jnp.take_along_axis(s, ids, axis=1)
    if ro.renormalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * ro.scale, ids, margin


def experts(spec, lw, x):
    """(x + the expert sub-block, margin (T,), chosen ids (T, k)). The
    stacks hold experts ``layout.offset .. + n_experts_held - 1``: a chosen
    expert that is not among them adds nothing here."""
    h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
    w, ids, margin = route(spec, lw["moe_gate"], lw.get("moe_bias"), h)
    w1, w2, w3 = (_dense(lw[n]) for n in ("moe_w1", "moe_w2", "moe_w3"))
    y = jnp.zeros_like(x)
    off, held = spec.layout.offset, spec.n_experts_held
    for j in range(spec.n_active_experts):   # a row's j-th expert, in turn
        here = (ids[:, j] >= off) & (ids[:, j] < off + held)
        e = jnp.where(here, ids[:, j] - off, 0)
        g = jnp.einsum("thd,td->th", w1[e], h)
        u = jnp.einsum("thd,td->th", w3[e], h)
        y = y + jnp.where(here, w[:, j], 0.0)[:, None] * jnp.einsum(
            "tdh,th->td", w2[e], jax.nn.silu(g) * u)
    if spec.layout.shared:
        y = y + _swiglu(h, lw["sh_w1"], lw["sh_w2"], lw["sh_w3"])
    return x + y, margin, ids


def _layer_of(stack: dict, i: int) -> dict:
    return {k: jax.tree_util.tree_map(lambda a: a[i], v)
            for k, v in stack.items() if not isinstance(v, dict)
            and k not in ("tok_embedding", "rms_final", "wcls")}


def forward(tree: dict, spec, tokens, gate: bool = True, rope: bool = True,
            drop=()):
    """Logits (T, vocab), router margins (T, expert layers) and chosen
    expert ids (T, expert layers, k) of one sequence ``tokens`` (T,);
    ``gate``, ``rope`` and ``drop`` as ``attention`` takes them."""
    tokens = np.asarray(tokens)
    seen = {"full": 0, "sliding": 0}
    margins, routed = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["tok_embedding"], jnp.float32)[tokens]
        for i, kind in enumerate(spec.mixers.kinds):
            x = attention(spec, _layer_of(tree[kind], seen[kind]), kind, x,
                          gate, rope, drop)
            seen[kind] += 1
            if i < spec.n_dense_layers:
                lw = _layer_of(tree["dense"], i)
            else:
                lw = _layer_of(tree, i - spec.n_dense_layers)
            if "moe_gate" in lw:
                x, margin, ids = experts(spec, lw, x)
                margins.append(margin)
                routed.append(ids)
            else:
                h = _rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
                x = x + _swiglu(h, lw["w1"], lw["w2"], lw["w3"])
        logits = _rmsnorm(x, tree["rms_final"],
                          spec.norm_eps) @ _dense(tree["wcls"]).T
    t, k = len(tokens), spec.n_active_experts
    return (np.asarray(logits),
            np.stack([np.asarray(m) for m in margins], 1) if margins
            else np.zeros((t, 0), np.float32),
            np.stack([np.asarray(r) for r in routed], 1) if routed
            else np.zeros((t, 0, k), np.int32))
