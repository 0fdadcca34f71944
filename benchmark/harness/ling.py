"""A delta-rule / latent-attention expert configuration (Ling-3.0-flash:
Kimi-Delta-Attention layers five to one beside latent attention with no query
rank and a gate a head, a leading dense layer, DeepSeek-V3's grouped sigmoid
router over 512 experts of which ONE routing group is held, a shared expert,
a SwiGLU clamp a layer) for the drivers: its sizes and ``TransformerSpec``
from the configuration file, its seeded codec tree, the benchmark's own copy
of the plain float32 reference, the bytes a step must move, and where a
device trace shows each kind of layer. What ``harness/latent.py``,
``laguna.py``, ``mimo.py``, ``weights.py``, ``reference.py`` and ``costs.py``
have that applies (the value recipe, the dequantizer, the layout of
(position, expert) pairs in blocks, the norm, the head, the RoPE, the margin
rule, Q40 block bytes) is imported, not copied.

The layers (``distributed_llama_tpu/models/reference_kda.py`` states them in
full), pre-norm residual blocks, ``h = RMSNorm(x)``:

  kda      [q | k | v | a | g] = W_in h; q, k, v = silu(conv4(.)) (no bias);
           a head's q / ||q|| / sqrt(128), k / ||k||; the decay's exponent
           g = -5 sigmoid(exp(A_log) (a + dt_bias)) a head and key channel;
           b = sigmoid(W_b h) a head; the RECURRENCE S <- exp(g) S, S <- S +
           b k (v - S^T k)^T, o = S^T q; RMSNorm over all 4,096 outputs
           times sigmoid(g), W_o
  full     q = W_q h (no rank); [c_kv | k_rope] = W_kva h, c_kv normed; plain
           interleaved RoPE; [k_nope | v]_h = W_kvb c_kv; causal softmax of
           (q_nope . k_nope + q_rope . k_rope) / sqrt(192); a head's output
           times sigmoid(W_hg h)[head]; W_o
  FFN      layer 0: w2(silu(w1 h) * w3 h) at 6,144; the others: s =
           sigmoid(W_r h); a group's score the sum of its two largest s + b,
           the 4 best of 8 groups stay, the 8 largest s + b among them are
           chosen; weights 2.5 s / sum(s); the HELD chosen experts (group 0)
           and the shared expert, each w2(silu(min(w1 h, L)) * clip(w3 h,
           -L, L)) with L the layer's limit (0: none)
"""

from __future__ import annotations

import bisect
import concurrent.futures
import functools
import itertools
import math
import os

import numpy as np

from . import costs, weights
from .laguna import MARGIN_EPSILON, _ein, strict_positions
from .latent import (HEAD_BLOCK, SHARED_MARGIN, _head, _normed, _pair,
                     _rmsnorm, _rope, ffn_shapes)
from .mimo import BIAS_STD, held_blocks
from .reference import _dequant

__all__ = ["MARGIN_EPSILON", "strict_positions"]

KINDS = ("kda", "full")
STATE_KERNEL = "kda_decode_step"
LATENT_KERNEL = "mla_paged_attn_decode"
SLOT_KERNEL = "moe_q40_slots"
MOE_KERNEL_PREFIX = "moe_q40"
LANES = 128
L2_EPS = 1e-6
# dense Q40 calls a layer in a program run: a KDA mixer's in_qkvag and wo, a
# latent mixer's wq, wkv_a and wo; an FFN's two (w13 and w2, the shared
# expert's in an expert layer)
CALLS = {"kda": 2, "full": 3}
FFN_CALLS = 2


def kinds_of(sizes: dict) -> tuple:
    """Layer i's mixer: the latent one every ``period``-th layer."""
    return tuple("full" if (i + 1) % sizes["period"] == 0 else "kda"
                 for i in range(sizes["n_layers"]))


def sizes_of(config: dict) -> dict:
    """Everything the spec, the tree and the counts need, flat."""
    c = config
    return {
        "dim": c["hidden_size"],
        "hidden_dim": c["moe_intermediate_size"],
        "n_layers": c["num_hidden_layers"],
        "period": c["layer_group_size"],
        "n_heads": c["num_attention_heads"],
        "head_dim": c["head_dim"],
        "conv": c["short_conv_kernel_size"],
        "lower_bound": float(c["kda_lower_bound"]),
        "vocab_size": c["vocab_size"],
        "seq_len": c["max_position_embeddings"],
        "kv_rank": c["kv_lora_rank"],
        "nope_dim": c["qk_nope_head_dim"],
        "rope_dim": c["qk_rope_head_dim"],
        "v_dim": c["v_head_dim"],
        "dense_layers": c["first_k_dense_replace"],
        "dense_hidden": c["intermediate_size"],
        "n_experts": c["published"]["num_experts"],
        "held": c["num_experts"],
        "offset": c["deployment"]["expert_offset"],
        "n_active_experts": c["num_experts_per_tok"],
        "groups": c["n_group"], "groups_kept": c["topk_group"],
        "route_scale": float(c["routed_scaling_factor"]),
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "limits": tuple(float(x) for x in c["expert_swiglu_limit_list"]),
        "shared_limits": tuple(
            float(x) for x in c["share_expert_swiglu_limit_list"]),
    }


def width(sizes: dict) -> int:
    """heads x head_dim: each of a KDA layer's five projections."""
    return sizes["n_heads"] * sizes["head_dim"]


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    c = config
    if c.get("model_type") != "bailing_hybrid":
        raise ValueError("harness/ling.py runs model_type bailing_hybrid")
    if (c.get("weights"), c.get("buffers"), c.get("state"),
            c.get("latent_cache")) != ("q40", "f32", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers, "
                         "state and latent plane")
    n = c["num_hidden_layers"]
    if (len(c["expert_swiglu_limit_list"]) != n
            or len(c["share_expert_swiglu_limit_list"]) != n
            or n % c["layer_group_size"]):
        raise ValueError("whole periods of layer_group_size layers, and a "
                         "limit a layer in each of the two lists")
    if (c.get("q_lora_rank") is not None or c.get("rope_scaling") is not None
            or c.get("use_bias") or c.get("use_qkv_bias")
            or c.get("tie_word_embeddings") or c.get("hidden_act") != "silu"
            or not c.get("kda_safe_gate") or not c.get("no_kda_lora")
            or not c.get("linear_silu") or not c.get("rope_interleave")
            or not c.get("use_qk_norm") or c.get("group_norm_size") != 1
            or c.get("num_shared_experts") != 1 or c.get("value_norm")
            or c.get("up_proj_norm") or c.get("use_nGPT")
            or c.get("scale_router_input") or not c.get("norm_topk_prob")
            or not c.get("moe_router_enable_expert_bias")
            or (c.get("score_function"), c.get("topk_method")) != (
                "sigmoid", "noaux_tc")
            or c.get("moe_shared_expert_intermediate_size")
            != c.get("moe_intermediate_size")
            or c.get("gated_attention_proj_granularity_type") != "head_wise"):
        raise ValueError(
            "no query rank, no RoPE scaling, no bias, an untied head, the "
            "lower-bound KDA gate at full rank, SiLU after the convolutions, "
            "an L2 q / k norm, one output norm group, one shared expert of "
            "the experts' width, a sigmoid noaux_tc router with a choice "
            "bias and renormalised weights, a head-wise latent gate")
    if c["deployment"]["chips_per_layer"] * c["num_experts"] \
            != c["published"]["num_experts"]:
        raise ValueError("the experts held times the chips that share a "
                         "layer must be the published count")


def program_spec(sizes: dict):
    """The program's spec. A program without the record stops HERE (an
    ``ImportError``), before any device is touched."""
    from distributed_llama_tpu.models.spec import (Activation, ExpertLayout,
                                                   KdaLayers, LatentAttn,
                                                   Router, TransformerSpec)
    from distributed_llama_tpu.ops.quants import FloatType

    s = sizes
    return TransformerSpec(
        dim=s["dim"], hidden_dim=s["hidden_dim"], n_layers=s["n_layers"],
        n_heads=s["n_heads"], n_kv_heads=s["n_heads"],
        vocab_size=s["vocab_size"], seq_len=s["seq_len"],
        weights_float_type=FloatType.Q40, buffer_float_type=FloatType.F32,
        n_experts=s["n_experts"], n_active_experts=s["n_active_experts"],
        rope_theta=s["rope_theta"], norm_eps=s["norm_eps"],
        latent=LatentAttn(0, s["kv_rank"], s["nope_dim"], s["rope_dim"],
                          s["v_dim"], kinds=kinds_of(s), head_gate=True),
        layout=ExpertLayout(s["dense_layers"], s["dense_hidden"], 1,
                            s["held"] if s["held"] < s["n_experts"] else 0,
                            s["offset"]),
        router=Router("sigmoid", s["groups"], s["groups_kept"], True,
                      s["route_scale"], bias=True),
        activation=Activation(limits=True),
        kda=KdaLayers(s["n_heads"], s["head_dim"], s["conv"],
                      lower_bound=s["lower_bound"]))


def kda_shapes(sizes: dict) -> list:
    """A KDA layer's two Q40 leaves."""
    return [("in_qkvag", (5 * width(sizes), sizes["dim"])),
            ("wo", (sizes["dim"], width(sizes)))]


def latent_shapes(sizes: dict) -> list:
    """A latent layer's four Q40 leaves in the file (``wkv_b`` is held as
    float32 by the program)."""
    s, nh = sizes, sizes["n_heads"]
    return [("wq", (nh * (s["nope_dim"] + s["rope_dim"]), s["dim"])),
            ("wkv_a", (s["kv_rank"] + s["rope_dim"], s["dim"])),
            ("wkv_b", (nh * (s["nope_dim"] + s["v_dim"]), s["kv_rank"])),
            ("wo", (s["dim"], nh * s["v_dim"]))]


def layer_limits(sizes: dict) -> np.ndarray:
    """(expert layers, 2): each expert layer's [routed, shared] limit, as
    the tensor ``ffn_limit`` holds the configuration's two lists."""
    k = sizes["dense_layers"]
    return np.asarray([sizes["limits"][k:], sizes["shared_limits"][k:]],
                      np.float32).T.copy()


def _small_leaf(sizes: dict, name: str, shape: tuple, key) -> np.ndarray:
    """A KDA layer's float32 leaf (the configuration's
    ``assumed.seeded_leaves``; ``models/synth.kda_leaf`` has the same
    recipe)."""
    if name == "a_log":
        return np.broadcast_to(np.log(np.linspace(
            0.5, 2.0, shape[-1], dtype=np.float32)), shape).copy()
    if name == "dt_bias":
        return np.broadcast_to(np.linspace(-3.0, 3.0, shape[-1],
                                           dtype=np.float32), shape).copy()
    x = np.random.default_rng(key).standard_normal(shape, dtype=np.float32)
    return x * np.float32({"w_beta": sizes["dim"] ** -0.5, "conv_w": 0.5,
                           "w_hgate": sizes["dim"] ** -0.5}[name])


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of the spec: a stack a mixer kind under
    ``"kda"`` / ``"full"``, the leading dense layers' FFN under ``"dense"``,
    the expert layers' at the top level; every leaf filled per (tensor,
    layer[, expert]) so that the seed alone fixes it. Q40 leaves by
    ``weights._fill_q40``'s recipe (value std 1 / sqrt(n)); gains 1 +- 0.05;
    router rows N(0, 1/sqrt(dim)), its bias N(0, ``mimo.BIAS_STD``)."""
    from distributed_llama_tpu.io.loader import Q40Weight

    s = sizes
    dim, vocab, w = s["dim"], s["vocab_size"], width(s)
    kinds = kinds_of(s)
    n_kda, n_full = kinds.count("kda"), kinds.count("full")
    k, n_exp = s["dense_layers"], s["n_layers"] - s["dense_layers"]
    tree: dict = {"kda": {}, "full": {}, "dense": {}}
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
    dense(tree["kda"], "rms_att", 300, (n_kda, dim), 1.0)
    dense(tree["kda"], "norm_g", 301, (n_kda, w), 1.0)
    for i, (name, (d, n)) in enumerate(kda_shapes(s)):
        q40(tree["kda"], name, 310 + i, (n_kda,), d, n)
    for i, (name, shape) in enumerate((
            ("conv_w", (s["conv"], 3 * w)), ("a_log", (s["n_heads"],)),
            ("dt_bias", (w,)), ("w_beta", (s["n_heads"], dim)))):
        tree["kda"][name] = _small_leaf(s, name, (n_kda, *shape),
                                        [seed, 320 + i])
    dense(tree["full"], "rms_att", 400, (n_full, dim), 1.0)
    dense(tree["full"], "rms_kv_a", 401, (n_full, s["kv_rank"]), 1.0)
    for i, (name, (d, n)) in enumerate(latent_shapes(s)):
        q40(tree["full"], name, 410 + i, (n_full,), d, n)
    tree["full"]["w_hgate"] = _small_leaf(
        s, "w_hgate", (n_full, s["n_heads"], dim), [seed, 420])
    dense(tree["dense"], "rms_ffn", 100, (k, dim), 1.0)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["dense_hidden"])):
        q40(tree["dense"], name, 120 + i, (k,), d, n)
    dense(tree, "rms_ffn", 200, (n_exp, dim), 1.0)
    tree["ffn_limit"] = layer_limits(s)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["hidden_dim"],
                                                  "sh_")):
        q40(tree, name, 220 + i, (n_exp,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["hidden_dim"],
                                                  "moe_")):
        q40(tree, name, 230 + i, (n_exp, s["held"]), d, n)
    dense(tree, "moe_gate", 240, (n_exp, s["n_experts"], dim), 0.0)
    dense(tree, "moe_bias", 241, (n_exp, s["n_experts"]), 0.0)
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for fut in [pool.submit(fn, *args) for fn, *args in tasks]:
            fut.result()
    tree["wcls"].d16[weights.BOS] = 0     # logit exactly 0: never the argmax
    tree["moe_gate"] *= np.float32(1.0 / np.sqrt(dim))
    tree["moe_bias"] *= np.float32(BIAS_STD)
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device. A KDA layer is the RECURRENCE, a position
# at a time under ``lax.scan`` with the rows' states (B, H, D, D) in its
# carry; a latent layer is EXPANDED (keys and values materialised, no
# absorption, no cache), a block of heads at a time; the dense FFN in blocks
# of its hidden width (``latent._swiglu_block``'s plan); an expert layer
# takes the router's grouped top-k on the host (``route``) and runs ONE held
# expert at a time on the positions that chose it, a block of rows at a time
# (``mimo.held_blocks``: only routed pairs are multiplied); the classifier in
# blocks of the vocabulary. Every product goes through ``laguna._ein``:
# float32 at HIGHEST, or with ``low`` both operands rounded to bfloat16
# first: the control that must FAIL.

def _kda(sizes, low, x, lw, w_in, w_out):
    import jax
    import jax.numpy as jnp

    s, eps = sizes, sizes["norm_eps"]
    H, D, K, w = s["n_heads"], s["head_dim"], s["conv"], width(sizes)
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    h = _rmsnorm(jnp, x, lw["rms_att"], eps)
    proj = mm(_dequant(jnp, *w_in), h)
    run = jnp.concatenate([jnp.zeros((B, K - 1, 3 * w)), proj[..., :3 * w]],
                          1)
    taps = jnp.stack([run[:, j:j + T] for j in range(K)], axis=-1)
    qkv = jax.nn.silu(ein("btck,kc->btc", taps, lw["conv_w"]))
    q, k, v = (qkv[..., i * w:(i + 1) * w].reshape(B, T, H, D)
               for i in range(3))

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    q, k = l2(q) * D ** -0.5, l2(k)
    a = (proj[..., 3 * w:4 * w] + lw["dt_bias"]).reshape(B, T, H, D)
    g = s["lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lw["a_log"])[None, None, :, None] * a)
    b = jax.nn.sigmoid(ein("hn,btn->bth", lw["w_beta"], h))

    def step(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs    # (B, H, D) x 4, (B, H)
        st = jnp.exp(g_t)[..., None] * st
        u = ein("bhkv,bhk->bhv", st, k_t)
        st = st + (b_t[..., None] * k_t)[..., None] * (v_t - u)[:, :, None]
        return st, ein("bhkv,bhk->bhv", st, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((B, H, D, D), jnp.float32), tuple(
        jnp.moveaxis(a_, 1, 0) for a_ in (q, k, v, g, b)))
    o = _rmsnorm(jnp, jnp.moveaxis(o, 0, 1).reshape(B, T, w), lw["norm_g"],
                 eps) * jax.nn.sigmoid(proj[..., 4 * w:])
    return x + mm(_dequant(jnp, *w_out), o)


def _latent(sizes, low, x, lw, w):
    import jax
    import jax.numpy as jnp

    s, nh, eps = sizes, sizes["n_heads"], sizes["norm_eps"]
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    rd = s["rope_dim"]
    freq = (s["rope_theta"] ** (-np.arange(0, rd, 2) / rd)).astype(np.float32)
    scale = (s["nope_dim"] + rd) ** -0.5
    h = _rmsnorm(jnp, x, lw["rms_att"], eps)
    q = mm(wf["wq"], h).reshape(B, T, nh, -1)
    q_nope, q_rope = q[..., :s["nope_dim"]], _rope(jnp, q[..., s["nope_dim"]:],
                                                   freq)
    kv = mm(wf["wkv_a"], h)
    c_kv = _rmsnorm(jnp, kv[..., :s["kv_rank"]], lw["rms_kv_a"], eps)
    k_rope = _rope(jnp, kv[..., s["kv_rank"]:], freq)
    kvb = mm(wf["wkv_b"], c_kv).reshape(B, T, nh, -1)
    k_nope, v = kvb[..., :s["nope_dim"]], kvb[..., s["nope_dim"]:]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]

    def heads(block):
        # a block of heads at a time (``latent._attention``'s plan)
        qn, qr, kn, vv = block
        scores = (ein("bthd,bshd->bhts", qn, kn)
                  + ein("bthd,bsd->bhts", qr, k_rope)) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return ein("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), vv)

    hb = math.gcd(nh, HEAD_BLOCK if T <= 1024 else 4)
    split = lambda a: jnp.moveaxis(          # noqa: E731
        a.reshape(B, T, nh // hb, hb, a.shape[-1]), 2, 0)
    ao = jnp.moveaxis(jax.lax.map(heads, tuple(
        split(a) for a in (q_nope, q_rope, k_nope, v))), 0, 2)
    ao = ao.reshape(B, T, nh, -1) * jax.nn.sigmoid(
        ein("hn,btn->bth", lw["w_hgate"], h))[..., None]
    return x + mm(wf["wo"], ao.reshape(B, T, -1))


def _clamped(jnp, gate, up, limit):
    import jax

    cap = jnp.where(limit > 0, limit, jnp.inf)
    return jax.nn.silu(jnp.minimum(gate, cap)) * jnp.clip(up, -cap, cap)


def _swiglu_block(low, acc, h, limit, w1, w2, w3):
    """acc + w2(silu(min(w1 h, L)) * clip(w3 h, -L, L)) of the shared
    expert, or of one block of the dense FFN's hidden width (w1 / w3 its
    rows, w2 its columns; ``limit`` 0: no clamp)."""
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    w1, w2, w3 = (_dequant(jnp, *w) for w in (w1, w2, w3))
    return acc + mm(w2, _clamped(jnp, mm(w1, h), mm(w3, h), limit))


def _experts(low, x, h, limit, used, expert, at, we, w1, w2, w3):
    """``laguna._experts`` with the layer's clamp: x + sum_e w_e E_e(h) over
    a layer's chosen (position, held expert) pairs, a block of
    ``held_blocks`` at a time: the block's expert on the block's positions
    and on no others."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    dim, rows = x.shape[-1], at.shape[1]
    zeros = jnp.zeros((rows, dim), jnp.float32)
    flat = jnp.concatenate([h.reshape(-1, dim), zeros])

    def body(i, acc):
        e, to, weight = expert[i], at[i], we[i]
        a, b, c = (_dequant(jnp, qs[e], d16[e]) for qs, d16 in (w1, w2, w3))
        hr = flat[to][None]
        out = mm(b, _clamped(jnp, mm(a, hr), mm(c, hr), limit))[0]
        return acc.at[to].add(weight[:, None] * out, unique_indices=True)

    acc = jax.lax.fori_loop(0, used, body, jnp.concatenate(
        [x.reshape(-1, dim), zeros]))
    return acc[:-rows].reshape(x.shape)


def _scores(low, h, gate):
    import jax

    return jax.nn.sigmoid(_ein(low, "ed,btd->bte", gate, h))


def route(sizes, scores, bias, live):
    """DeepSeek-V3's choice on the host, ``scores`` (B, T, E) the sigmoid
    scores: on c = scores + bias a group's score is the sum of its two
    largest c, the ``groups_kept`` best groups stay (the lower index wins a
    tie, as ``lax.top_k``), every other expert is out, the k largest c are
    chosen; their weights are the UNBIASED scores over their sum, times the
    scale (0 at a position that is not ``live``). Returns (ids (B, T, k),
    weights, the smallest margin (B, T): the last group kept over the first
    dropped, the k-th chosen over the best one left out)."""
    s, k = sizes, sizes["n_active_experts"]
    groups, kept_n = s["groups"], s["groups_kept"]
    c = scores + bias
    per = c.reshape(*c.shape[:-1], groups, -1)
    score = np.sort(per, axis=-1)[..., -2:].sum(-1)
    order = np.argsort(-score, axis=-1, kind="stable")
    top = np.take_along_axis(score, order, axis=-1)
    margin = (top[..., kept_n - 1] - top[..., kept_n] if kept_n < groups
              else np.full(top.shape[:-1], np.inf, np.float32))
    kept = np.zeros(score.shape, bool)
    np.put_along_axis(kept, order[..., :kept_n], True, axis=-1)
    c = np.where(kept[..., None], per, -np.inf).reshape(c.shape)
    order = np.argsort(-c, axis=-1, kind="stable")[..., :k + 1]
    top = np.take_along_axis(c, order, axis=-1)
    margin = np.minimum(margin, top[..., k - 1] - top[..., k])
    ids = order[..., :k]
    w = np.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + np.float32(1e-20)) * np.float32(
        s["route_scale"])
    return ids, np.where(live[..., None], w, np.float32(0.0)), margin


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool):
    """The jitted pieces of one configuration at one precision."""
    import jax

    sizes = dict(sizes)
    return {
        "kda": jax.jit(functools.partial(_kda, sizes, low), donate_argnums=0),
        "full": jax.jit(functools.partial(_latent, sizes, low),
                        donate_argnums=0),
        "normed": jax.jit(functools.partial(_normed, sizes)),
        "block": jax.jit(functools.partial(_swiglu_block, low),
                         donate_argnums=0),
        "scores": jax.jit(functools.partial(_scores, low)),
        "experts": jax.jit(functools.partial(_experts, low)),
        "head": jax.jit(functools.partial(_head, low))}


def _hashable(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           dense_blocks: int = 4, settle: int | None = None, lengths=None):
    """Float32 logits of the full forward pass over ``tokens`` (B, T), every
    position reading those before it, at the positions ``keep`` ((B, K),
    each row's own; default all), of the experts HELD: ``{precision: (B, K,
    vocab)}``, and the router margins (B, T, expert layers) of the
    "highest" pass; "bfloat16" is the control one precision down.
    ``lengths``: a row's own length (padding past it weighs no expert).
    ``settle`` (a seed) draws an expert layer's choice BIAS again until
    every row's margin is over ``latent.SHARED_MARGIN``, and returns
    nothing."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    progs = {p: _programs(_hashable(sizes), p == "bfloat16")
             for p in precisions}
    emb = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    xs = {p: emb + 0.0 for p in precisions}
    margins = []
    seen = dict.fromkeys(KINDS, 0)
    ends = (np.full(len(tokens), tokens.shape[1]) if lengths is None
            else np.asarray(lengths))
    live = np.arange(tokens.shape[1])[None, :] < ends[:, None]
    for layer, kind in enumerate(kinds_of(sizes)):
        stack, i = tree[kind], seen[kind]
        seen[kind] += 1
        if kind == "kda":
            lw = {k: put(stack[k][i]) for k in (
                "rms_att", "conv_w", "a_log", "dt_bias", "w_beta", "norm_g")}
            w_in, w_out = (tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("in_qkvag", "wo"))
            for p in precisions:
                xs[p] = progs[p]["kda"](xs[p], lw, w_in, w_out)
            del w_in, w_out
        else:
            lw = {k: put(stack[k][i]) for k in ("rms_att", "rms_kv_a",
                                                "w_hgate")}
            w = {k: tuple(put(a) for a in _pair(stack[k], i))
                 for k, _ in latent_shapes(sizes)}
            for p in precisions:
                xs[p] = progs[p]["full"](xs[p], lw, w)
            del w
        dense = layer < sizes["dense_layers"]
        stack = tree["dense"] if dense else tree
        i = layer if dense else layer - sizes["dense_layers"]
        g_ffn = put(stack["rms_ffn"][i])
        hs = {p: progs[p]["normed"](xs[p], g_ffn) for p in precisions}
        if dense:
            hid = stack["w1"].qs.shape[1]
            blocks = dense_blocks
            while hid % (blocks * weights.QK):
                blocks -= 1
            edges = np.linspace(0, hid, blocks + 1).astype(int)
            nb = edges // weights.QK
            for lo, hi, blo, bhi in zip(edges[:-1], edges[1:], nb[:-1],
                                        nb[1:]):
                blk = ((put(stack["w1"].qs[i, lo:hi]),
                        put(stack["w1"].d16[i, lo:hi])),
                       (put(stack["w2"].qs[i, :, blo:bhi]),
                        put(stack["w2"].d16[i, :, blo:bhi])),
                       (put(stack["w3"].qs[i, lo:hi]),
                        put(stack["w3"].d16[i, lo:hi])))
                for p in precisions:
                    xs[p] = progs[p]["block"](xs[p], hs[p], np.float32(0),
                                              *blk)
        else:
            gate = put(stack["moe_gate"][i])
            scores = {p: np.asarray(progs[p]["scores"](hs[p], gate))
                      for p in precisions}
            attempt = 0
            while True:
                routed = {p: route(sizes, scores[p], stack["moe_bias"][i],
                                   live) for p in precisions}
                if settle is None or float(routed["highest"][2].min()) \
                        >= SHARED_MARGIN:
                    break
                attempt += 1
                rng = np.random.default_rng([settle, 241, i, attempt])
                stack["moe_bias"][i] = np.float32(
                    BIAS_STD) * rng.standard_normal(sizes["n_experts"],
                                                    dtype=np.float32)
            if "highest" in routed:
                margins.append(routed["highest"][2])
            limit = np.asarray(stack["ffn_limit"][i], np.float32)
            shared = tuple(tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("sh_w1", "sh_w2", "sh_w3"))
            held = tuple(tuple(put(a) for a in _pair(stack[k], i))
                         for k in ("moe_w1", "moe_w2", "moe_w3"))
            for p in precisions:
                ids, w, _ = routed[p]
                xs[p] = progs[p]["block"](xs[p], hs[p], limit[1], *shared)
                xs[p] = progs[p]["experts"](xs[p], hs[p], limit[0], *(
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


def plane_lanes(sizes: dict) -> int:
    """A cached position's row [c_kv | k_rope] as the chip holds it: 576
    values in 640 lanes."""
    return -(-(sizes["kv_rank"] + sizes["rope_dim"]) // LANES) * LANES


def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE routed expert's three tensors."""
    return _q40_bytes(ffn_shapes(sizes["dim"], sizes["hidden_dim"]))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: a KDA layer's ``in_qkvag`` and ``wo``, a latent layer's ``wq``,
    ``wkv_a`` (at the plane's 640 rows, zero rows past 576) and ``wo``
    (``wkv_b`` is held as float32 and is not among them), the leading
    layer's dense FFN, the expert layers' shared expert, the classifier."""
    s = sizes
    kinds = kinds_of(s)
    full = [("wq", latent_shapes(s)[0][1]),
            ("wkv_a", (plane_lanes(s), s["dim"])),
            ("wo", latent_shapes(s)[3][1])]
    n_exp = s["n_layers"] - s["dense_layers"]
    return (kinds.count("kda") * _q40_bytes(kda_shapes(s))
            + kinds.count("full") * _q40_bytes(full)
            + s["dense_layers"] * _q40_bytes(
                ffn_shapes(s["dim"], s["dense_hidden"]))
            + n_exp * _q40_bytes(ffn_shapes(s["dim"], s["hidden_dim"]))
            + _q40_bytes([("wcls", (s["vocab_size"], s["dim"]))]))


def state_call_bytes(sizes: dict, rows: int) -> int:
    """Bytes ONE ``kda_decode_step`` call moves at ``rows`` rows: each row's
    state (heads, head_dim, head_dim) float32 read once and written once,
    its (head_dim, 4 heads) block of key-channel columns, its v and b (k .
    q) rows (heads, head_dim) and its output of that shape
    (``ops/kda.kda_decode_step``'s operands)."""
    h, d = sizes["n_heads"], sizes["head_dim"]
    return rows * 4 * (2 * h * d * d + d * 4 * h + 3 * h * d)


def state_step_bytes(sizes: dict, rows: int) -> int:
    """... in every KDA layer of a decode step."""
    return kinds_of(sizes).count("kda") * state_call_bytes(sizes, rows)


def state_row_bytes(sizes: dict) -> int:
    """What one sequence keeps a KDA layer: the state and the conv rows
    (2,244,608 B at the published sizes)."""
    return 4 * (width(sizes) * sizes["head_dim"]
                + (sizes["conv"] - 1) * 3 * width(sizes))


def plane_position_bytes(sizes: dict) -> int:
    """One cached position in ONE latent layer, float32 in whole lane tiles
    (2,560 B)."""
    return plane_lanes(sizes) * 4


def latent_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of the latent layers' pages a decode step must read ONCE:
    ``positions`` (pos + 1 summed over the rows) rows, in every latent
    layer."""
    return positions * plane_position_bytes(sizes) * kinds_of(sizes).count(
        "full")


# -- what a device trace shows ---------------------------------------------------
# The reducer's ops carry the instruction's name and opcode only, so a scope
# (``kda.*``) is not to be read from them. Kernels are found by name. A
# layer's ops by POSITION among a program run's dense Q40 calls, which come in
# a fixed order: a KDA mixer's two (in_qkvag ... wo), a latent mixer's three
# (wq, wkv_a ... wo), then the layer's FFN's two (w13, w2; an expert layer's
# are its shared expert's), and the classifier's one at the end of a decode
# step. An admission chunk returns no logits, so it has neither the
# classifier's call nor its LAST layer's FFN, which nothing reads and the
# compiler drops (98 dense calls and 44 expert calls where a decode step has
# 101 and 46: my chip run, PR 60). A mixer ends with its ``wo``; an FFN
# runs on to the next layer's first call (or to the run's end): the routed
# experts and the shared expert do not depend on each other and the compiler
# orders them as it likes (``harness/nemotron.py`` met it). What lies between
# a KDA mixer's two calls is its convolutions, norms and gates, the state
# kernel (a decode step) or the chunk form (an admission chunk), and the
# output's norm: the ``kda.conv`` .. ``kda.out_norm`` scopes.

def _is(op, prefix: str) -> bool:
    return op.label == "custom-call" and op.name.lower().startswith(prefix)


_SEEN: dict = {}   # the last trace and what was reduced from it: ten readers
#                    ask for the same two reductions of a run's one trace


def _once(what, trace, make):
    if _SEEN.get("trace") is not trace:
        _SEEN.clear()
        _SEEN["trace"] = trace
    if what not in _SEEN:
        _SEEN[what] = make()
    return _SEEN[what]


def step_kernel_seconds(trace) -> list[dict]:
    """Per decode step of the traced window that ran the state kernel
    (``reduce_trace.steps``): seconds in the state kernel, in the latent
    page kernel, in the slot kernel and in the dense Q40 calls."""
    return _once("steps", trace, lambda: _step_kernel_seconds(trace))


def _step_kernel_seconds(trace) -> list[dict]:
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        acc = {"state": 0.0, "latent": 0.0, "slots": 0.0, "dense": 0.0}
        for o, s in zip(ops, rt.self_times(ops)):
            if _is(o, STATE_KERNEL):
                acc["state"] += s / 1e9
            elif _is(o, LATENT_KERNEL):
                acc["latent"] += s / 1e9
            elif _is(o, SLOT_KERNEL):
                acc["slots"] += s / 1e9
            elif rt.classify(o) == "q40" and not _is(o, MOE_KERNEL_PREFIX):
                acc["dense"] += s / 1e9
        if acc["state"] > 0:
            out.append(acc)
    return out


def _calls(sizes: dict) -> list:
    """[(kind, first dense call, its ``wo``, the FFN's last)] a layer, as
    indices into a program run's dense Q40 calls."""
    out, at = [], 0
    for kind in kinds_of(sizes):
        out.append((kind, at, at + CALLS[kind] - 1,
                    at + CALLS[kind] + FFN_CALLS - 1))
        at += CALLS[kind] + FFN_CALLS
    return out


def block_seconds(trace, sizes: dict, device: str | None = None) -> dict:
    """Self seconds, over every program run of the traced window on
    ``device`` (default: the first) that is a forward of this model (its
    dense Q40 calls count what ``_calls`` says, and the classifier's where
    it is a decode step; without the last layer's FFN where it is an
    admission chunk, which returns no logits), of the KDA mixers ("kda"),
    the latent mixers
    ("full") and the FFNs ("moe": norm, router, slot building, routed and
    shared experts or the dense layer's SwiGLU, residual); and
    ``chunk_mid`` / ``chunks``: of the admission chunks alone, the seconds
    BETWEEN a KDA mixer's two calls (its convolutions, gates, the chunk
    form and the output's norm), and how many chunks that was."""
    return dict(_once(("blocks", device), trace,
                      lambda: _block_seconds(trace, sizes, device)))


def _block_seconds(trace, sizes: dict, device: str | None) -> dict:
    from . import reduce_trace as rt

    out = {"kda": 0.0, "full": 0.0, "moe": 0.0, "chunk_mid": 0.0}
    chunks = 0
    if not trace.devices:
        return {**out, "chunks": 0}
    layers = _calls(sizes)
    n_calls = layers[-1][3] + 1
    device = device or sorted(trace.devices)[0]
    ops = trace.devices[device]
    starts = [o.start for o in ops]
    for run in trace.modules.get(device, []):
        inside = ops[bisect.bisect_left(starts, run.start):
                     bisect.bisect_right(starts, run.end)]
        kinds = [rt.classify(o) for o in inside]
        dense = [i for i, (o, k) in enumerate(zip(inside, kinds))
                 if k == "q40" and not _is(o, MOE_KERNEL_PREFIX)]
        if len(dense) not in (n_calls - FFN_CALLS, n_calls, n_calls + 1):
            continue
        chunk = len(dense) <= n_calls and not any(
            _is(o, STATE_KERNEL) for o in inside)
        chunks += chunk
        # upto[i]: self time of the work (not "control") ops before op i
        upto = [0.0, *itertools.accumulate(
            0.0 if k == "control" else s
            for k, s in zip(kinds, rt.self_times(inside)))]
        lo = -1
        for n, (kind, first, wo, _) in enumerate(layers):
            hi = dense[wo]
            out[kind] += upto[hi + 1] - upto[lo + 1]      # lo < i <= hi
            if chunk and kind == "kda":
                out["chunk_mid"] += upto[hi] - upto[dense[first] + 1]
            lo = hi
            if n + 1 < len(layers):
                hi = dense[layers[n + 1][1]] - 1
            elif len(dense) > n_calls:      # up to the classifier's call
                hi = dense[n_calls] - 1
            else:
                hi = len(inside) - 1
            out["moe"] += upto[hi + 1] - upto[lo + 1]
            lo = hi
    return {**{k: v / 1e9 for k, v in out.items()}, "chunks": chunks}
