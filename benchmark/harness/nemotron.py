"""An ssd expert configuration (NVIDIA-Nemotron-3-Nano-30B-A3B: a layer is
ONE mixer under one pre-norm, a Mamba-2 (SSD) mixer, grouped-query attention
with no positional encoding, or 128 non-gated relu2 experts of which a SHARE
is held, with one shared expert) for the drivers: its sizes and
``TransformerSpec`` from the configuration file, its seeded codec tree, the
benchmark's own copy of the plain float32 reference, the bytes a step must
move, and where a device trace shows each kind of layer. What
``harness/latent.py``, ``laguna.py``, ``mimo.py``, ``weights.py``,
``reference.py`` and ``costs.py`` have that applies (the value recipe, the
dequantizer, the router's choice on the host, the layout of (position,
expert) pairs in blocks, the head, the margin rule, Q40 block bytes) is
imported, not copied.

The layers (``distributed_llama_tpu/models/reference_nemotron.py`` states
them in full), ``u = RMSNorm(x)`` and ``x <- x + mixer(u)`` in each:

  mamba2   [z | xBC] = W_zx u, dt = W_dt u; xBC = silu(conv4(xBC) + b);
           [x | B | C] = xBC; dt = softplus(dt + b_dt); the RECURRENCE
           h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t, y_t = h_t C_t +
           D x_t a head (B, C a group's); y * silu(z), RMSNorm in groups
           with its gain, W_out
  full     q, k, v = W u (no bias, no RoPE); causal softmax(q k^T / sqrt d)
           v, 16 query heads a KV head; W_o
  experts  s = sigmoid(W_r u); the 6 largest of s + b; weights 2.5 s /
           sum(s); sum over the HELD chosen experts of W_down relu(W_up
           u)^2, plus the shared expert of the same form
"""

from __future__ import annotations

import bisect
import concurrent.futures
import functools
import math
import os

import numpy as np

from . import costs, weights
from .laguna import MARGIN_EPSILON, _ein, strict_positions
from .latent import SHARED_MARGIN, _head, _normed, _pair, _rmsnorm
from .mimo import BIAS_STD, held_blocks, route
from .reference import _dequant

__all__ = ["MARGIN_EPSILON", "strict_positions"]

KINDS = ("mamba2", "full", "experts")
LETTERS = {"M": "mamba2", "*": "full", "E": "experts"}
ATTN_KEYS = ("wq", "wk", "wv", "wo")
STATE_KERNEL = "mamba2_decode_step"
PAGED_KERNEL = "hm_attn_paged_decode"
SLOT_KERNEL = "moe_q40_slots"
MOE_KERNEL_PREFIX = "moe_q40"
LANES = 128
QUERY_BLOCK = 1024    # queries the reference's attention scores at a time


def kinds_of(config_or_sizes: dict) -> tuple:
    return tuple(LETTERS[c] for c in
                 config_or_sizes["hybrid_override_pattern"])


def sizes_of(config: dict) -> dict:
    """Everything the spec, the tree and the counts need, flat."""
    c = config
    return {
        "dim": c["hidden_size"],
        "hidden_dim": c["moe_intermediate_size"],
        "shared_hidden": c["n_shared_experts"]
        * c["moe_shared_expert_intermediate_size"],
        "n_layers": c["num_hidden_layers"],
        "hybrid_override_pattern": c["hybrid_override_pattern"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_size": c["head_dim"],
        "vocab_size": c["vocab_size"],
        "seq_len": c["max_position_embeddings"],
        "ssm_heads": c["mamba_num_heads"],
        "ssm_head_dim": c["mamba_head_dim"],
        "ssm_groups": c["n_groups"],
        "ssm_state": c["ssm_state_size"],
        "ssm_conv": c["conv_kernel"],
        "ssm_chunk": c["chunk_size"],
        "n_experts": c["published"]["n_routed_experts"],
        "held": c["n_routed_experts"],
        "offset": c["deployment"]["expert_offset"],
        "n_active_experts": c["num_experts_per_tok"],
        "route_scale": float(c["routed_scaling_factor"]),
        "norm_eps": float(c["norm_eps"]),
    }


def d_inner(sizes: dict) -> int:
    return sizes["ssm_heads"] * sizes["ssm_head_dim"]


def conv_dim(sizes: dict) -> int:
    return d_inner(sizes) + 2 * sizes["ssm_groups"] * sizes["ssm_state"]


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    c = config
    if c.get("model_type") != "nemotron_h":
        raise ValueError("harness/nemotron.py runs model_type nemotron_h")
    if (c.get("weights"), c.get("buffers"), c.get("state"),
            c.get("kv_cache")) != ("q40", "f32", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers, "
                         "state and pages")
    if len(c["hybrid_override_pattern"]) != c["num_hidden_layers"] or set(
            c["hybrid_override_pattern"]) - set(LETTERS):
        raise ValueError("hybrid_override_pattern: one of M, * and E a layer")
    if (c.get("attention_bias") or c.get("mlp_bias") or c.get("use_bias")
            or c.get("mamba_proj_bias") or c.get("tie_word_embeddings")
            or not c.get("use_conv_bias") or not c.get("norm_topk_prob")
            or c.get("mlp_hidden_act") != "relu2"
            or c.get("mamba_hidden_act") != "silu"
            or (c.get("n_group"), c.get("topk_group")) != (1, 1)
            or c.get("n_shared_experts") != 1
            or c.get("layer_norm_epsilon") != c.get("norm_eps")):
        raise ValueError("no projection bias, a conv bias, an untied head, "
                         "relu2 experts with one shared, a renormalised "
                         "top-k with no routing groups, silu in the mixer")


def program_spec(sizes: dict):
    """The program's spec. A program without the record stops HERE (an
    ``ImportError``), before any device is touched."""
    from distributed_llama_tpu.models.spec import (Activation, ExpertLayout,
                                                   Router, SsdLayers,
                                                   TransformerSpec)
    from distributed_llama_tpu.ops.quants import FloatType

    s = sizes
    return TransformerSpec(
        dim=s["dim"], hidden_dim=s["hidden_dim"], n_layers=s["n_layers"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        vocab_size=s["vocab_size"], seq_len=s["seq_len"],
        weights_float_type=FloatType.Q40, buffer_float_type=FloatType.F32,
        n_experts=s["n_experts"], n_active_experts=s["n_active_experts"],
        norm_eps=s["norm_eps"],
        layout=ExpertLayout(0, 0, 1, s["held"] if s["held"] < s["n_experts"]
                            else 0, s["offset"]),
        router=Router("sigmoid", 1, 1, True, s["route_scale"], bias=True),
        activation=Activation("relu2", gated=False),
        ssd=SsdLayers(kinds_of(s), s["ssm_heads"], s["ssm_head_dim"],
                      s["ssm_groups"], s["ssm_state"], s["head_size"],
                      s["ssm_conv"], s["ssm_chunk"], s["shared_hidden"]))


def attn_shapes(sizes: dict) -> list:
    s = sizes
    q, kv = s["n_heads"] * s["head_size"], s["n_kv_heads"] * s["head_size"]
    return [("wq", (q, s["dim"])), ("wk", (kv, s["dim"])),
            ("wv", (kv, s["dim"])), ("wo", (s["dim"], q))]


def mamba_shapes(sizes: dict) -> list:
    """A Mamba-2 layer's two Q40 leaves."""
    di = d_inner(sizes)
    return [("in_zx", (di + conv_dim(sizes), sizes["dim"])),
            ("out_proj", (sizes["dim"], di))]


def plain_ffn_shapes(dim: int, hidden: int, prefix: str = "") -> list:
    """A non-gated FFN's two matrices (``latent.ffn_shapes`` without w3)."""
    return [(prefix + "w1", (hidden, dim)), (prefix + "w2", (dim, hidden))]


def _small_leaf(sizes: dict, name: str, shape: tuple, key) -> np.ndarray:
    """A Mamba-2 layer's float32 leaf as the family initialises it (the
    configuration's ``assumed.seeded_leaves``; ``models/synth.ssd_leaf``
    has the same recipe)."""
    heads = sizes["ssm_heads"]
    if name == "a_log":
        return np.broadcast_to(np.log(np.linspace(
            1.0, 16.0, heads, dtype=np.float32)), shape).copy()
    if name == "dt_bias":
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), heads))
        return np.broadcast_to((dt + np.log(-np.expm1(-dt))).astype(
            np.float32), shape).copy()
    if name == "d_skip":
        return np.ones(shape, np.float32)
    x = np.random.default_rng(key).standard_normal(shape, dtype=np.float32)
    x *= np.float32({"in_dt": sizes["dim"] ** -0.5,
                     "conv_w": 0.5}.get(name, 0.05))
    return x + np.float32(1) if name == "norm_g" else x


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of the spec: a stack a kind under
    ``"mamba2"`` / ``"full"`` / ``"experts"``; every leaf filled per
    (tensor, layer[, expert]) so that the seed alone fixes it. Q40 leaves by
    ``weights._fill_q40``'s recipe (value std 1 / sqrt(n)); gains 1 +- 0.05;
    router rows N(0, 1/sqrt(dim)), its bias N(0, ``mimo.BIAS_STD``)."""
    from distributed_llama_tpu.io.loader import Q40Weight

    s = sizes
    dim, vocab = s["dim"], s["vocab_size"]
    kinds = kinds_of(s)
    depth = {k: kinds.count(k) for k in KINDS}
    tree: dict = {k: {} for k in KINDS}
    tasks = []

    def q40(dst, name, idx, lead, d, n):
        nb = n // weights.QK
        qs = np.empty((*lead, d, nb, 16), np.uint8)
        d16 = np.empty((*lead, d, nb), np.float16)
        dst[name] = Q40Weight(qs, d16)
        for at in np.ndindex(*lead):
            tasks.append((weights._fill_q40, qs[at], d16[at], n,
                          [seed, idx, *at]))

    def dense(dst, name, idx, shape, base):
        out = dst[name] = np.empty(shape, np.float32)
        rows = out.reshape(-1, shape[-1])
        step = max(1, (1 << 22) // shape[-1])
        for lo in range(0, rows.shape[0], step):
            tasks.append((weights._fill_dense, rows[lo:lo + step], base,
                          [seed, idx, lo]))

    dense(tree, "tok_embedding", 0, (vocab, dim), 0.0)
    dense(tree, "rms_final", 3, (dim,), 1.0)
    q40(tree, "wcls", 20, (), vocab, dim)
    for base, kind in ((300, "mamba2"), (400, "full"), (500, "experts")):
        dense(tree[kind], "rms_att", base, (depth[kind], dim), 1.0)
    m, f, e = depth["mamba2"], depth["full"], depth["experts"]
    for i, (name, (d, n)) in enumerate(mamba_shapes(s)):
        q40(tree["mamba2"], name, 310 + i, (m,), d, n)
    for i, (name, shape) in enumerate((
            ("in_dt", (s["ssm_heads"], dim)),
            ("conv_w", (s["ssm_conv"], conv_dim(s))),
            ("conv_b", (conv_dim(s),)), ("dt_bias", (s["ssm_heads"],)),
            ("a_log", (s["ssm_heads"],)), ("d_skip", (s["ssm_heads"],)),
            ("norm_g", (d_inner(s),)))):
        tree["mamba2"][name] = _small_leaf(s, name, (m, *shape),
                                           [seed, 320 + i])
    for i, (name, (d, n)) in enumerate(attn_shapes(s)):
        q40(tree["full"], name, 410 + i, (f,), d, n)
    for i, (name, (d, n)) in enumerate(plain_ffn_shapes(
            dim, s["shared_hidden"], "sh_")):
        q40(tree["experts"], name, 520 + i, (e,), d, n)
    for i, (name, (d, n)) in enumerate(plain_ffn_shapes(
            dim, s["hidden_dim"], "moe_")):
        q40(tree["experts"], name, 530 + i, (e, s["held"]), d, n)
    dense(tree["experts"], "moe_gate", 540, (e, s["n_experts"], dim), 0.0)
    dense(tree["experts"], "moe_bias", 541, (e, s["n_experts"]), 0.0)
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for fut in [pool.submit(fn, *args) for fn, *args in tasks]:
            fut.result()
    tree["wcls"].d16[weights.BOS] = 0     # logit exactly 0: never the argmax
    tree["experts"]["moe_gate"] *= np.float32(1.0 / np.sqrt(dim))
    tree["experts"]["moe_bias"] *= np.float32(BIAS_STD)
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device. A Mamba-2 layer is the RECURRENCE, a
# position at a time under ``lax.scan`` with the rows' states (B, H, P, N)
# in its carry; an attention layer scores one KV group and ``QUERY_BLOCK``
# queries at a time; an expert layer takes the router's top-k on the host
# (``mimo.route``) and runs ONE held expert at a time on the positions that
# chose it, a block of rows at a time (``mimo.held_blocks``: only routed
# pairs are multiplied); the classifier in blocks of the vocabulary. Every
# product goes through ``laguna._ein``: float32 at HIGHEST, or with ``low``
# both operands rounded to bfloat16 first: the control that must FAIL.

def _mamba2(sizes, low, x, lw, w_zx, w_out):
    import jax
    import jax.numpy as jnp

    s, eps = sizes, sizes["norm_eps"]
    H, P, G = s["ssm_heads"], s["ssm_head_dim"], s["ssm_groups"]
    N, K, di = s["ssm_state"], s["ssm_conv"], d_inner(sizes)
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    u = _rmsnorm(jnp, x, lw["rms_att"], eps)
    zx = mm(_dequant(jnp, *w_zx), u)
    z, xbc = zx[..., :di], zx[..., di:]
    dt = mm(lw["in_dt"], u)
    run = jnp.concatenate([jnp.zeros((B, K - 1, xbc.shape[-1])), xbc], 1)
    taps = jnp.stack([run[:, j:j + T] for j in range(K)], axis=-1)
    xbc = jax.nn.silu(ein("btck,kc->btc", taps, lw["conv_w"])
                      + lw["conv_b"])
    xh = xbc[..., :di].reshape(B, T, H, P)
    rep = functools.partial(jnp.repeat, repeats=H // G, axis=2)
    b = rep(xbc[..., di:di + G * N].reshape(B, T, G, N))
    c = rep(xbc[..., di + G * N:].reshape(B, T, G, N))
    dt = jax.nn.softplus(dt + lw["dt_bias"])                 # (B, T, H)
    a = -jnp.exp(lw["a_log"])

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs          # (B, H, P), (B, H, N) x 2, (B, H)
        h = jnp.exp(dt_t * a)[..., None, None] * h + ein(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t)
        return h, ein("bhpn,bhn->bhp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (xh, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + lw["d_skip"][:, None] * xh
    y = (y.reshape(B, T, di) * jax.nn.silu(z)).reshape(B, T, G, di // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    y = y.reshape(B, T, di) * lw["norm_g"]
    return x + mm(_dequant(jnp, *w_out), y)


def _attention(sizes, low, x, rms_att, w):
    import jax
    import jax.numpy as jnp

    s, eps = sizes, sizes["norm_eps"]
    heads, n_kv, d = s["n_heads"], s["n_kv_heads"], s["head_size"]
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    h = _rmsnorm(jnp, x, rms_att, eps)
    q = mm(wf["wq"], h).reshape(B, T, heads, d)
    k = mm(wf["wk"], h).reshape(B, T, n_kv, d)
    v = mm(wf["wv"], h).reshape(B, T, n_kv, d)
    qb = T if T <= QUERY_BLOCK else math.gcd(T, QUERY_BLOCK)
    pos = jnp.arange(T)

    def group(block):
        # one KV group's heads and ``qb`` queries at a time; a head's
        # numbers do not depend on how they are blocked
        qg, kg, vg = block              # (B, T, m, d), (B, T, d), (B, T, d)

        def queries(qpart):
            qq, at = qpart              # (B, qb, m, d), (qb,)
            sc = ein("btmd,bsd->bmts", qq, kg) / math.sqrt(d)
            sc = jnp.where(at[:, None] >= pos[None, :], sc, -jnp.inf)
            return ein("bmts,bsd->btmd", jax.nn.softmax(sc, axis=-1), vg)

        parts = (jnp.moveaxis(qg.reshape(B, T // qb, qb, *qg.shape[2:]),
                              1, 0), pos.reshape(T // qb, qb))
        out = jax.lax.map(queries, parts)         # (T / qb, B, qb, m, d)
        return jnp.moveaxis(out, 0, 1).reshape(B, T, *qg.shape[2:])

    qg = jnp.moveaxis(q.reshape(B, T, n_kv, heads // n_kv, d), 2, 0)
    ao = jax.lax.map(group, (qg, jnp.moveaxis(k, 2, 0),
                             jnp.moveaxis(v, 2, 0)))
    ao = jnp.moveaxis(ao, 0, 2).reshape(B, T, heads * d)
    return x + mm(wf["wo"], ao)


def _relu2_block(low, acc, h, w1, w2):
    """acc + w2(relu(w1 h)^2) of a non-gated FFN (the shared expert)."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    w1, w2 = (_dequant(jnp, *w) for w in (w1, w2))
    return acc + mm(w2, jnp.square(jax.nn.relu(mm(w1, h))))


def _experts(low, x, h, used, expert, at, we, w1, w2):
    """``laguna._experts`` for a non-gated expert of two matrices: x + sum_e
    w_e W_down,e relu(W_up,e h)^2 over a layer's chosen (position, held
    expert) pairs, a block of ``held_blocks`` at a time: the block's expert
    on the block's positions and on no others."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    dim, rows = x.shape[-1], at.shape[1]
    zeros = jnp.zeros((rows, dim), jnp.float32)
    flat = jnp.concatenate([h.reshape(-1, dim), zeros])

    def body(i, acc):
        e, to, weight = expert[i], at[i], we[i]
        a, b = (_dequant(jnp, qs[e], d16[e]) for qs, d16 in (w1, w2))
        out = mm(b, jnp.square(jax.nn.relu(mm(a, flat[to][None]))))[0]
        return acc.at[to].add(weight[:, None] * out, unique_indices=True)

    acc = jax.lax.fori_loop(0, used, body, jnp.concatenate(
        [x.reshape(-1, dim), zeros]))
    return acc[:-rows].reshape(x.shape)


def _scores(low, h, gate):
    import jax

    return jax.nn.sigmoid(_ein(low, "ed,btd->bte", gate, h))


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool):
    """The jitted pieces of one configuration at one precision."""
    import jax

    sizes = dict(sizes)
    return {
        "mamba2": jax.jit(functools.partial(_mamba2, sizes, low),
                          donate_argnums=0),
        "full": jax.jit(functools.partial(_attention, sizes, low),
                        donate_argnums=0),
        "normed": jax.jit(functools.partial(_normed, sizes)),
        "shared": jax.jit(functools.partial(_relu2_block, low),
                          donate_argnums=0),
        "scores": jax.jit(functools.partial(_scores, low)),
        "experts": jax.jit(functools.partial(_experts, low)),
        "head": jax.jit(functools.partial(_head, low))}


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           settle: int | None = None, lengths=None):
    """Float32 logits of the full forward pass over ``tokens`` (B, T), every
    position reading those before it, at the positions ``keep`` ((B, K),
    each row's own; default all), of the experts HELD: ``{precision: (B, K,
    vocab)}``, and the router margins (B, T, expert layers) of the
    "highest" pass; "bfloat16" is the control one precision down.
    ``lengths``: a row's own length (padding past it weighs no expert).
    ``settle`` (a seed) draws an expert
    layer's choice BIAS again until every row's margin is over
    ``latent.SHARED_MARGIN``, and returns nothing."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    key = tuple(sorted(sizes.items()))
    progs = {p: _programs(key, p == "bfloat16") for p in precisions}
    emb = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    xs = {p: emb + 0.0 for p in precisions}
    margins = []
    kinds = kinds_of(sizes)
    seen = dict.fromkeys(KINDS, 0)
    no_flip = np.zeros(tokens.shape, bool)    # ``mimo.route``'s reversals
    ends = (np.full(len(tokens), tokens.shape[1]) if lengths is None
            else np.asarray(lengths))
    live = np.arange(tokens.shape[1])[None, :] < ends[:, None]
    for kind in kinds:
        stack, i = tree[kind], seen[kind]
        seen[kind] += 1
        if kind == "mamba2":
            lw = {k: put(stack[k][i]) for k in (
                "rms_att", "in_dt", "conv_w", "conv_b", "dt_bias", "a_log",
                "d_skip", "norm_g")}
            w_zx, w_out = (tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("in_zx", "out_proj"))
            for p in precisions:
                xs[p] = progs[p]["mamba2"](xs[p], lw, w_zx, w_out)
            del w_zx, w_out
        elif kind == "full":
            w = {k: tuple(put(a) for a in _pair(stack[k], i))
                 for k in ATTN_KEYS}
            g_att = put(stack["rms_att"][i])
            for p in precisions:
                xs[p] = progs[p]["full"](xs[p], g_att, w)
            del w
        else:
            g = put(stack["rms_att"][i])
            hs = {p: progs[p]["normed"](xs[p], g) for p in precisions}
            gate = put(stack["moe_gate"][i])
            scores = {p: np.asarray(progs[p]["scores"](hs[p], gate))
                      for p in precisions}
            attempt = 0
            while True:
                routed = {p: route(sizes, scores[p], stack["moe_bias"][i],
                                   no_flip, live) for p in precisions}
                if settle is None or float(routed["highest"][2].min()) \
                        >= SHARED_MARGIN:
                    break
                attempt += 1
                rng = np.random.default_rng([settle, 541, i, attempt])
                stack["moe_bias"][i] = np.float32(
                    BIAS_STD) * rng.standard_normal(sizes["n_experts"],
                                                    dtype=np.float32)
            if "highest" in routed:
                margins.append(routed["highest"][2])
            shared = tuple(tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("sh_w1", "sh_w2"))
            held = tuple(tuple(put(a) for a in _pair(stack[k], i))
                         for k in ("moe_w1", "moe_w2"))
            for p in precisions:
                ids, w, _ = routed[p]
                xs[p] = progs[p]["shared"](xs[p], hs[p], *shared)
                xs[p] = progs[p]["experts"](xs[p], hs[p], *(
                    put(a) for a in held_blocks(sizes, ids, w, live)), *held)
            del held, shared
        # a layer at a time ON THE DEVICE too (the loop would otherwise run
        # ahead and park every layer's weights there)
        jax.block_until_ready(list(xs.values()))
    if settle is not None:
        return None
    out = {}
    qs, d16 = tree["wcls"].qs, tree["wcls"].d16
    edges = np.linspace(0, qs.shape[0], vocab_blocks + 1).astype(int)
    g_final = put(tree["rms_final"])
    for p in precisions:
        x = xs[p]
        if keep is not None:
            x = jnp.take_along_axis(x, put(np.asarray(keep))[..., None],
                                    axis=1)
        x = progs[p]["normed"](x, g_final)
        res = np.empty(tuple(x.shape[:2]) + (qs.shape[0],), np.float32)
        for lo, hi in zip(edges[:-1], edges[1:]):
            res[..., lo:hi] = np.asarray(progs[p]["head"](
                x, put(qs[lo:hi]), put(d16[lo:hi])))
        out[p] = res
    return out, (np.stack(margins, axis=-1) if margins else None)


def settle_shared_positions(tree: dict, sizes: dict, shared_tokens,
                            seed: int) -> None:
    """``mimo.settle_shared_positions`` on this reference: an expert layer's
    choice bias is drawn again (from the attempt's number, so the seed still
    fixes the tree) until the positions every prompt opens with choose with
    a margin over ``latent.SHARED_MARGIN``."""
    logits(tree, sizes, np.asarray([list(shared_tokens)]), settle=seed)


# -- bytes a step must move, from shapes ---------------------------------------

def _q40_bytes(shapes) -> int:
    return sum(d * n for _, (d, n) in shapes) // costs.Q40_BLOCK \
        * costs.Q40_BLOCK_BYTES


PAD_BLOCKS = 8    # ``ops/linear.Q40Layout.pad_blocks`` of this spec


def _padded(n: int, blocks: int = PAD_BLOCKS) -> int:
    return -(-n // (32 * blocks)) * 32 * blocks


def padded_hidden(sizes: dict) -> int:
    """An expert's hidden width as the PACKED stacks hold it: whole 128-lane
    tiles of ``moe_w1``'s rows that are whole groups of 8 blocks of
    ``moe_w2``'s, zero blocks past 1,856 (``assumed.expert_padding``)."""
    return _padded(sizes["hidden_dim"])


def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE routed expert's two leaves AS READ: up rows
    2,048 over 88 blocks (2,688 values and four zero blocks), down rows
    2,688 over 64 blocks: 6,340,608 B where the published widths are
    5,612,544 (``published_expert_bytes``)."""
    hid = padded_hidden(sizes)
    return (hid * _padded(sizes["dim"]) + sizes["dim"] * hid) \
        // costs.Q40_BLOCK * costs.Q40_BLOCK_BYTES


def published_expert_bytes(sizes: dict) -> int:
    return _q40_bytes(plain_ffn_shapes(sizes["dim"], sizes["hidden_dim"]))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: a Mamba-2 layer's ``in_zx`` and ``out_proj``, an attention
    layer's four, an expert layer's shared expert, the classifier."""
    s = sizes
    kinds = kinds_of(s)
    return (kinds.count("mamba2") * _q40_bytes(mamba_shapes(s))
            + kinds.count("full") * _q40_bytes(attn_shapes(s))
            + kinds.count("experts") * _q40_bytes(plain_ffn_shapes(
                s["dim"], s["shared_hidden"]))
            + _q40_bytes([("wcls", (s["vocab_size"], s["dim"]))]))


def state_call_bytes(sizes: dict, rows: int) -> int:
    """Bytes ONE ``mamba2_decode_step`` call moves at ``rows`` rows: each
    row's state (heads, head_dim, state) float32 read once and written
    once, its two (head_dim, 128-lane) inputs and its output of that
    shape (``ops/mamba2.mamba2_decode_step``'s operands), and its B and C."""
    s = sizes
    lanes = -(-s["ssm_heads"] // LANES) * LANES
    return rows * 4 * (2 * s["ssm_heads"] * s["ssm_head_dim"] * s["ssm_state"]
                       + 3 * s["ssm_head_dim"] * lanes
                       + 2 * s["ssm_groups"] * s["ssm_state"])


def state_step_bytes(sizes: dict, rows: int) -> int:
    """... in every Mamba-2 layer of a decode step."""
    return kinds_of(sizes).count("mamba2") * state_call_bytes(sizes, rows)


def state_row_bytes(sizes: dict) -> int:
    """What one sequence keeps a Mamba-2 layer: the state and the conv
    rows (2,170,880 B at the published sizes)."""
    s = sizes
    return 4 * (s["ssm_heads"] * s["ssm_head_dim"] * s["ssm_state"]
                + (s["ssm_conv"] - 1) * conv_dim(s))


def kv_position_bytes(sizes: dict) -> int:
    """K and V of one position in ONE attention layer, float32 (2,048 B)."""
    return 2 * sizes["n_kv_heads"] * sizes["head_size"] * 4


def full_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of the attention layers' pages a decode step must read ONCE:
    ``positions`` (pos + 1 summed over the rows) of K and V, in every
    attention layer."""
    return positions * kv_position_bytes(sizes) * kinds_of(sizes).count(
        "full")


# -- what a device trace shows ---------------------------------------------------
# The reducer's ops carry the instruction's name and opcode only, so a scope
# is not to be read from them. Kernels are found by name. A layer's ops by
# POSITION among a program run's dense Q40 calls, TWO a layer of every kind
# (mamba2: in_zx ... out_proj; full: wqkv ... wo; experts: the shared
# expert's sh_w1 and sh_w2), and the classifier's one at the end of a decode
# step (an admission chunk has none). A Mamba-2 or an attention layer ends
# with its second call; an expert layer runs on to the next layer's first
# call (or to the run's end): its shared expert and its routed experts do not
# depend on each other, and the compiler put the expert kernels AFTER the
# shared expert's calls (my chip run, PR 55: a reader that closed an expert
# layer at ``sh_w2`` gave the Mamba-2 layers 78 % of the step and the experts
# 5 %). A layer's pre-norm falls to the layer before it: a microsecond.

def _is(op, prefix: str) -> bool:
    return op.label == "custom-call" and op.name.lower().startswith(prefix)


def step_kernel_seconds(trace) -> list[dict]:
    """Per decode step of the traced window that ran the state kernel
    (``reduce_trace.steps``): seconds in the state kernel, in the paged
    kernel, in the slot kernel and in the dense Q40 calls."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        acc = {"state": 0.0, "paged": 0.0, "slots": 0.0, "dense": 0.0}
        for o, s in zip(ops, rt.self_times(ops)):
            if _is(o, STATE_KERNEL):
                acc["state"] += s / 1e9
            elif _is(o, PAGED_KERNEL):
                acc["paged"] += s / 1e9
            elif _is(o, SLOT_KERNEL):
                acc["slots"] += s / 1e9
            elif rt.classify(o) == "q40" and not _is(o, MOE_KERNEL_PREFIX):
                acc["dense"] += s / 1e9
        if acc["state"] > 0:
            out.append(acc)
    return out


def block_seconds(trace, sizes: dict, device: str | None = None) -> dict:
    """Self seconds, over every program run of the traced window on
    ``device`` (default: the first) that is a forward of this model (2 L
    dense Q40 calls, and the classifier's where it is a decode step), of
    the layers of each kind: {"mamba2", "full", "experts"}."""
    from . import reduce_trace as rt

    out = dict.fromkeys(KINDS, 0.0)
    if not trace.devices:
        return out
    kinds = kinds_of(sizes)
    device = device or sorted(trace.devices)[0]
    ops = trace.devices[device]
    starts = [o.start for o in ops]
    for run in trace.modules.get(device, []):
        inside = ops[bisect.bisect_left(starts, run.start):
                     bisect.bisect_right(starts, run.end)]
        selfs = rt.self_times(inside)
        work = [i for i, o in enumerate(inside)
                if rt.classify(o) != "control"]
        dense = [i for i in work if rt.classify(inside[i]) == "q40"
                 and not _is(inside[i], MOE_KERNEL_PREFIX)]
        if len(dense) not in (2 * len(kinds), 2 * len(kinds) + 1):
            continue
        lo = -1
        for layer, kind in enumerate(kinds):
            hi = dense[2 * layer + 1]
            if kind == "experts":
                hi = (dense[2 * layer + 2] - 1 if 2 * layer + 2 < len(dense)
                      else len(inside) - 1)
            out[kind] += sum(selfs[i] for i in work if lo < i <= hi)
            lo = hi
    return {k: v / 1e9 for k, v in out.items()}
