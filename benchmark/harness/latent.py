"""A latent-attention expert configuration (DeepSeek-V3: low-rank q, ONE
cached plane [c_kv | k_rope] a layer, leading dense layers, sigmoid
group-limited routing over 256 experts of which this chip holds a share, a
shared expert) for the drivers: its sizes and ``TransformerSpec`` from the
configuration file, its seeded codec tree, the benchmark's own copy of the
plain float32 reference, and the bytes and operations a step must move.
``harness/model.py``, ``weights.py``, ``reference.py`` and ``costs.py`` know
the dense Llama block only; what they have that applies (value recipe,
dequantizer, tokenizer, Q40 block bytes) is imported, not copied.

The layers (``distributed_llama_tpu/models/reference_latent.py`` states them
in full), on h = RMSNorm(x):

  attention   c_q = RMSNorm(W_qa h); [q_nope | q_rope]_h = W_qb c_q;
              [c_kv | k_rope] = W_kva h, c_kv = RMSNorm(c_kv); interleaved-
              pair RoPE (YaRN frequencies) on q_rope and the shared k_rope;
              [k_nope | v]_h = W_kvb c_kv; score = (q_nope . k_nope + q_rope
              . k_rope) * qk_dim^-1/2 * m^2; causal softmax; W_o [o_1..o_H]
  dense FFN   w2(silu(w1 h) * w3 h), the leading layers
  expert FFN  s = sigmoid(W_g h); choice on s + b: a group's score is the
              sum of its two largest, the best groups stay, the k largest
              among them are chosen; weights s / sum(s) * scale; the held
              experts' part of sum_e w_e E_e(h), plus the shared expert
"""

from __future__ import annotations

import concurrent.futures
import functools
import math
import os

import numpy as np

from . import costs, weights
from .reference import _dequant

ATTN_KEYS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
DECODE_KERNEL = "mla_paged_attn_decode"
SLOT_KERNEL = "moe_q40_slots"
MOE_KERNEL_PREFIX = "moe_q40"

MARGIN_EPSILON = 1e-5
"""A check request is compared strictly up to its first position whose
smallest router margin (group scores or s + b, both of order 0.1 to 1) is
under this; what follows is compared too, and a shortfall there is excused.
Two float32 routers choose differently only where the margin is under what
their scores differ by: the program's float32 paths differ from a reference
by at most a few 1e-5 on final logits of order 1 (PERF.md section 6) and a
sigmoid's slope is at most a quarter, so 1e-5 leaves room."""

HEAD_BLOCK = 16       # heads the reference's attention scores at a time

SHARED_MARGIN = 1e-3
"""``settle_shared_positions``: the margin the positions every prompt
shares (BOS, the tokenizer's leading space) are given."""


def sizes_of(config: dict) -> dict:
    """Everything the spec, the tree and the counts need, flat."""
    pub, dep = config["published"], config["deployment"]
    rs = config["rope_scaling"]
    return {
        "dim": config["hidden_size"],
        "hidden_dim": config["moe_intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "vocab_size": config["vocab_size"],
        "seq_len": config["max_position_embeddings"],
        "n_experts": pub["n_routed_experts"],
        "n_active_experts": config["num_experts_per_tok"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "dense_layers": config["first_k_dense_replace"],
        "dense_hidden": config["intermediate_size"],
        "shared": config["n_shared_experts"],
        "held": config["n_routed_experts"],
        "offset": dep["expert_offset"],
        "groups": config["n_group"], "groups_kept": config["topk_group"],
        "route_scale": float(config["routed_scaling_factor"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "yarn_factor": float(rs["factor"]),
        "yarn_original": int(rs["original_max_position_embeddings"]),
        "yarn_beta_fast": float(rs["beta_fast"]),
        "yarn_beta_slow": float(rs["beta_slow"]),
        "yarn_mscale": float(rs["mscale"]),
        "yarn_mscale_all_dim": float(rs["mscale_all_dim"]),
    }


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    if config.get("model_type") != "deepseek_v3":
        raise ValueError("harness/latent.py runs model_type deepseek_v3")
    if (config.get("weights"), config.get("buffers"),
            config.get("latent_cache")) != ("q40", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers "
                         "and a float32 latent cache")
    if (config.get("scoring_func"), config.get("topk_method"),
            config.get("norm_topk_prob")) != ("sigmoid", "noaux_tc", True):
        raise ValueError("the driver runs sigmoid scores, the bias-corrected "
                         "group-limited choice and renormalised weights")
    if config.get("attention_bias") or config.get("tie_word_embeddings") \
            or config.get("hidden_act") != "silu" \
            or config.get("moe_layer_freq") != 1 \
            or config["rope_scaling"].get("type") != "yarn":
        raise ValueError("no attention bias, no tied embedding, SwiGLU, "
                         "every later layer an expert layer, YaRN")
    if config["deployment"]["chips_per_layer"] * config["n_routed_experts"] \
            != config["published"]["n_routed_experts"]:
        raise ValueError("the experts held times the chips that share a "
                         "layer must be the published count")


def program_spec(sizes: dict):
    """The program's spec. A program without the grouped records stops HERE
    (an ``ImportError``), before any device is touched."""
    from distributed_llama_tpu.models.spec import (ExpertLayout, LatentAttn,
                                                   RopeScaling, Router,
                                                   TransformerSpec)
    from distributed_llama_tpu.ops.quants import FloatType

    s = sizes
    return TransformerSpec(
        dim=s["dim"], hidden_dim=s["hidden_dim"], n_layers=s["n_layers"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        vocab_size=s["vocab_size"], seq_len=s["seq_len"],
        weights_float_type=FloatType.Q40, buffer_float_type=FloatType.F32,
        n_experts=s["n_experts"], n_active_experts=s["n_active_experts"],
        rope_theta=s["rope_theta"], norm_eps=s["norm_eps"],
        latent=LatentAttn(s["q_rank"], s["kv_rank"], s["nope_dim"],
                          s["rope_dim"], s["v_dim"]),
        layout=ExpertLayout(s["dense_layers"], s["dense_hidden"],
                            s["shared"], s["held"], s["offset"]),
        router=Router("sigmoid", s["groups"], s["groups_kept"], True,
                      s["route_scale"], True),
        rope_scaling=RopeScaling(
            s["yarn_factor"], s["yarn_original"], s["yarn_beta_fast"],
            s["yarn_beta_slow"], s["yarn_mscale"], s["yarn_mscale_all_dim"]))


def attn_shapes(sizes: dict) -> list[tuple[str, tuple[int, int]]]:
    s, nh = sizes, sizes["n_heads"]
    return [("wq_a", (s["q_rank"], s["dim"])),
            ("wq_b", (nh * (s["nope_dim"] + s["rope_dim"]), s["q_rank"])),
            ("wkv_a", (s["kv_rank"] + s["rope_dim"], s["dim"])),
            ("wkv_b", (nh * (s["nope_dim"] + s["v_dim"]), s["kv_rank"])),
            ("wo", (s["dim"], nh * s["v_dim"]))]


def ffn_shapes(dim: int, hidden: int, prefix: str = ""):
    return [(prefix + "w1", (hidden, dim)), (prefix + "w2", (dim, hidden)),
            (prefix + "w3", (hidden, dim))]


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of the spec: the expert layers' stacks at
    the top level, the leading dense layers' under ``"dense"``, every leaf
    filled per (tensor, layer[, expert]) so that the seed alone fixes it.
    Q40 leaves by ``weights._fill_q40``'s recipe (value std 1 / sqrt(n));
    gains 1 +- 0.05; router rows N(0, 1/sqrt(dim)); its bias N(0, 0.05), so
    that the choice (on s + b) and the weights (on s) differ."""
    from distributed_llama_tpu.io.loader import Q40Weight

    s = sizes
    dim, vocab = s["dim"], s["vocab_size"]
    k, n_exp = s["dense_layers"], s["n_layers"] - s["dense_layers"]
    tree: dict = {"dense": {}}
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
    for base, dst, depth in ((100, tree["dense"], k), (200, tree, n_exp)):
        for i, (name, width) in enumerate((
                ("rms_att", dim), ("rms_ffn", dim),
                ("rms_q_a", s["q_rank"]), ("rms_kv_a", s["kv_rank"]))):
            dense(dst, name, base + i, (depth, width), 1.0)
        for i, (name, (d, n)) in enumerate(attn_shapes(s)):
            q40(dst, name, base + 10 + i, (depth,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["dense_hidden"])):
        q40(tree["dense"], name, 120 + i, (k,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(
            dim, s["shared"] * s["hidden_dim"], "sh_")):
        q40(tree, name, 220 + i, (n_exp,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["hidden_dim"],
                                                  "moe_")):
        q40(tree, name, 230 + i, (n_exp, s["held"]), d, n)
    dense(tree, "moe_gate", 240, (n_exp, s["n_experts"], dim), 0.0)
    dense(tree, "moe_bias", 241, (n_exp, s["n_experts"]), 0.0)
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
    tree["wcls"].d16[weights.BOS] = 0     # logit exactly 0: never the argmax
    tree["moe_gate"] *= np.float32(1.0 / np.sqrt(dim))
    tree["moe_bias"] *= np.float32(0.05)
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device, inside a layer one tensor group at a
# time: the attention block (0.75 GB of float32 at the published widths),
# the dense FFN in blocks of its hidden width, ONE expert at a time (59 MB
# each, never the stack), the classifier in blocks of the vocabulary. Every
# product goes through ``ein``: float32 at HIGHEST, or with ``low`` both
# operands rounded to bfloat16 first (what one bf16 pass on a TPU computes,
# written out so that a CPU gives the same): the control that must FAIL.

def _ein(low, subscripts, a, b):
    import jax
    import jax.numpy as jnp

    if low:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(subscripts, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rmsnorm(jnp, x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * w


def yarn(sizes: dict):
    """(frequencies (rope_dim / 2,), attention scale) as published: pair p's
    f_p = theta^(-2p / rope_dim), blended f_p / factor * (1 - r_p) + f_p *
    r_p with r_p = 1 - clip((p - low) / (high - low), 0, 1), low and high
    the correction range for beta_fast and beta_slow rotations over the
    original positions; scale = qk_dim^-1/2 * (0.1 mscale_all_dim ln factor
    + 1)^2. (mscale / mscale_all_dim, the cos / sin factor, must be 1.)"""
    s, dim = sizes, sizes["rope_dim"]
    if s["yarn_mscale"] != s["yarn_mscale_all_dim"]:
        raise ValueError("the copy of the reference takes cos / sin as "
                         "they are: mscale must equal mscale_all_dim")
    f = s["rope_theta"] ** (-np.arange(0, dim, 2) / dim)

    def edge(rotations):
        return dim * math.log(s["yarn_original"] / (rotations * 2 * math.pi)
                              ) / (2 * math.log(s["rope_theta"]))

    low = max(math.floor(edge(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(edge(s["yarn_beta_slow"])), dim - 1)
    r = 1 - np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    m = 0.1 * s["yarn_mscale_all_dim"] * math.log(s["yarn_factor"]) + 1.0
    return ((f / s["yarn_factor"] * (1 - r) + f * r).astype(np.float32),
            (s["nope_dim"] + dim) ** -0.5 * m * m)


def _rope(jnp, x, freq):
    """x (B, T, ..., rope_dim) at positions 0..T-1, interleaved pairs."""
    t = x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    ang = ang.reshape(1, t, *([1] * (x.ndim - 3)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(sizes, low, x, rms_att, rms_q_a, rms_kv_a, w):
    import jax
    import jax.numpy as jnp

    s, nh, eps = sizes, sizes["n_heads"], sizes["norm_eps"]
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    freq, scale = yarn(sizes)
    h = _rmsnorm(jnp, x, rms_att, eps)
    c_q = _rmsnorm(jnp, mm(wf["wq_a"], h), rms_q_a, eps)
    q = mm(wf["wq_b"], c_q).reshape(B, T, nh, -1)
    q_nope, q_rope = q[..., :s["nope_dim"]], _rope(jnp, q[..., s["nope_dim"]:],
                                                   freq)
    kv = mm(wf["wkv_a"], h)
    c_kv = _rmsnorm(jnp, kv[..., :s["kv_rank"]], rms_kv_a, eps)
    k_rope = _rope(jnp, kv[..., s["kv_rank"]:], freq)
    kvb = mm(wf["wkv_b"], c_kv).reshape(B, T, nh, -1)
    k_nope, v = kvb[..., :s["nope_dim"]], kvb[..., s["nope_dim"]:]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]

    def heads(block):
        # a block of heads at a time: a (B, H, T, T) float32 score plane is
        # 1.2 GB a row at the window's longest request; a head's numbers do
        # not depend on how the heads are blocked
        qn, qr, kn, vv = block
        scores = (ein("bthd,bshd->bhts", qn, kn)
                  + ein("bthd,bsd->bhts", qr, k_rope)) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return ein("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), vv)

    hb = math.gcd(nh, HEAD_BLOCK)
    split = lambda a: jnp.moveaxis(          # noqa: E731
        a.reshape(B, T, nh // hb, hb, a.shape[-1]), 2, 0)
    ao = jnp.moveaxis(jax.lax.map(heads, tuple(
        split(a) for a in (q_nope, q_rope, k_nope, v))), 0, 2)
    return x + mm(wf["wo"], ao.reshape(B, T, -1))


def _normed(sizes, x, gain):
    import jax.numpy as jnp

    return _rmsnorm(jnp, x, gain, sizes["norm_eps"])


def _swiglu_block(low, acc, h, weight, w1, w2, w3):
    """acc + weight * w2(silu(w1 h) * w3 h) of one expert, or of one block
    of a dense FFN's hidden width (w1 / w3 its rows, w2 its columns)."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    w1, w2, w3 = (_dequant(jnp, *w) for w in (w1, w2, w3))
    y = mm(w2, jax.nn.silu(mm(w1, h)) * mm(w3, h))
    return acc + (y if weight is None else weight[..., None] * y)


def _route(sizes, low, h, gate, bias):
    """Each expert's weight (B, T, E; 0 where not chosen) and the smallest
    margin (B, T): kept against dropped groups, chosen against the rest."""
    import jax
    import jax.numpy as jnp

    s, k = sizes, sizes["n_active_experts"]
    n_exp, groups, kept_n = s["n_experts"], s["groups"], s["groups_kept"]
    sc = jax.nn.sigmoid(_ein(low, "ed,btd->bte", gate, h))
    c = sc + bias
    per = c.reshape(*c.shape[:-1], groups, n_exp // groups)
    score = jax.lax.top_k(per, 2)[0].sum(-1)
    top, gi = jax.lax.top_k(score, min(kept_n + 1, groups))
    margin = (top[..., kept_n - 1] - top[..., kept_n] if kept_n < groups
              else jnp.full(top.shape[:-1], jnp.inf))
    kept = (gi[..., :kept_n, None] == jnp.arange(groups)).any(axis=-2)
    c = jnp.where(kept[..., None], per, -jnp.inf).reshape(c.shape)
    top, ids = jax.lax.top_k(c, k + 1)
    margin = jnp.minimum(margin, top[..., k - 1] - top[..., k])
    chosen = (ids[..., :k, None] == jnp.arange(n_exp)).any(axis=-2)
    w = jnp.where(chosen, sc, 0.0)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * s["route_scale"]
    return w, margin


def _head(low, x, qs, d16):
    import jax.numpy as jnp

    return _ein(low, "vn,btn->btv", _dequant(jnp, qs, d16), x)


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool):
    """The jitted pieces of one configuration at one precision."""
    import jax

    sizes = dict(sizes)
    return {
        "attention": jax.jit(functools.partial(_attention, sizes, low),
                             donate_argnums=0),
        "normed": jax.jit(functools.partial(_normed, sizes)),
        "block": jax.jit(functools.partial(_swiglu_block, low),
                         donate_argnums=0),
        "route": jax.jit(functools.partial(_route, sizes, low)),
        "head": jax.jit(functools.partial(_head, low))}


def _pair(leaf, at):
    return leaf.qs[at], leaf.d16[at]


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           dense_blocks: int = 4, settle: int | None = None):
    """Float32 logits of the full forward pass over ``tokens`` (B, T), every
    position reading those before it, of the experts HELD (the share the
    tree holds: what the others would add is left out, as in the program):
    ``{precision: (B, K, vocab)}`` at the positions ``keep`` ((B, K), each
    row's own; default all) and the smallest router margin (B, T) of the
    "highest" pass. "bfloat16" is the control one precision down. Weights
    go to the device once for all precisions. ``settle`` (a seed) draws a
    layer's router bias again (``settle_shared_positions``) until every
    row's margin is over ``SHARED_MARGIN``, and returns nothing."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    key = tuple(sorted(sizes.items()))
    progs = {p: _programs(key, p == "bfloat16") for p in precisions}
    emb = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    xs = {p: emb + 0.0 for p in precisions}
    margin = None
    held, off = sizes["held"], sizes["offset"]
    for layer in range(sizes["n_layers"]):
        dense = layer < sizes["dense_layers"]
        stack = tree["dense"] if dense else tree
        i = layer if dense else layer - sizes["dense_layers"]
        w = {k: tuple(put(a) for a in _pair(stack[k], i)) for k in ATTN_KEYS}
        gains = [put(stack[k][i]) for k in ("rms_att", "rms_q_a", "rms_kv_a")]
        g_ffn = put(stack["rms_ffn"][i])
        hs = {}
        for p in precisions:
            xs[p] = progs[p]["attention"](xs[p], *gains, w)
            hs[p] = progs[p]["normed"](xs[p], g_ffn)
        del w
        if dense:
            hid = stack["w1"].qs.shape[1]
            while hid % (dense_blocks * weights.QK):
                dense_blocks -= 1
            edges = np.linspace(0, hid, dense_blocks + 1).astype(int)
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
                    xs[p] = progs[p]["block"](xs[p], hs[p], None, *blk)
        else:
            gate = put(stack["moe_gate"][i])
            attempt = 0
            while True:
                bias = put(stack["moe_bias"][i])
                routed = {p: progs[p]["route"](hs[p], gate, bias)
                          for p in precisions}
                if settle is None or float(routed["highest"][1].min()) \
                        >= SHARED_MARGIN:
                    break
                attempt += 1
                stack["moe_bias"][i] = np.float32(0.05) * np.random.default_rng(
                    [settle, 241, i, attempt]).standard_normal(
                        sizes["n_experts"], dtype=np.float32)
            m = routed["highest"][1] if "highest" in routed else None
            if m is not None:
                margin = m if margin is None else jnp.minimum(margin, m)
            used = {p: np.asarray(routed[p][0][..., off:off + held].sum(
                axis=(0, 1)) != 0) for p in precisions}
            shared = tuple(tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("sh_w1", "sh_w2", "sh_w3"))
            for p in precisions:
                xs[p] = progs[p]["block"](xs[p], hs[p], None, *shared)
            for e in range(held):
                if not any(used[p][e] for p in precisions):
                    continue
                blk = tuple(tuple(put(a) for a in _pair(stack[k], (i, e)))
                            for k in ("moe_w1", "moe_w2", "moe_w3"))
                for p in precisions:
                    if used[p][e]:
                        xs[p] = progs[p]["block"](
                            xs[p], hs[p], routed[p][0][..., off + e], *blk)
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
    return out, (None if margin is None else np.asarray(margin))


def settle_shared_positions(tree: dict, sizes: dict, shared_tokens,
                            seed: int) -> None:
    """Every prompt opens with the same tokens (BOS and the tokenizer's
    leading space): a near-tie of the router THERE would be every request's
    (ROADMAP B1). Part of the seeded tree's recipe, then: a layer's router
    bias is drawn again (from the attempt's number, so the seed still fixes
    the tree) until those positions choose with a margin over
    ``SHARED_MARGIN``, layer by layer through the reference."""
    logits(tree, sizes, np.asarray([list(shared_tokens)]), settle=seed)


def strict_positions(margins_row: np.ndarray) -> int:
    """How many leading positions of a request are compared strictly."""
    low = np.nonzero(np.asarray(margins_row) < MARGIN_EPSILON)[0]
    return int(low[0]) if low.size else int(len(margins_row))


# -- bytes and operations a step must move, from shapes ----------------------

def _q40_bytes(shapes) -> int:
    return sum(d * n for _, (d, n) in shapes) // costs.Q40_BLOCK \
        * costs.Q40_BLOCK_BYTES


def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE routed expert's three tensors."""
    return _q40_bytes(ffn_shapes(sizes["dim"], sizes["hidden_dim"]))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: the four Q40 attention leaves of every layer (``wkv_b`` is held
    as float32 and is not among them), the leading layers' dense FFN, the
    expert layers' shared expert, and the classifier."""
    s = sizes
    attn = [x for x in attn_shapes(s) if x[0] != "wkv_b"]
    n_exp = s["n_layers"] - s["dense_layers"]
    return (s["n_layers"] * _q40_bytes(attn)
            + s["dense_layers"] * _q40_bytes(
                ffn_shapes(s["dim"], s["dense_hidden"]))
            + n_exp * _q40_bytes(ffn_shapes(
                s["dim"], s["shared"] * s["hidden_dim"]))
            + _q40_bytes([("wcls", (s["vocab_size"], s["dim"]))]))


def latent_width(sizes: dict) -> int:
    return sizes["kv_rank"] + sizes["rope_dim"]


def latent_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of latent cache a decode step must read ONCE: ``positions``
    (summed over the rows) of ``width`` float32 values, in every layer."""
    return positions * latent_width(sizes) * 4 * sizes["n_layers"]


def latent_step_flops(sizes: dict, positions: float) -> float:
    """Operations of the absorbed decode attention over ``positions``
    (summed over the rows): every head's score over ``width`` and its
    weighted sum over ``kv_rank``, a multiply-add counted as two."""
    return (2.0 * positions * sizes["n_heads"]
            * (latent_width(sizes) + sizes["kv_rank"]) * sizes["n_layers"])


# -- what a device trace shows -------------------------------------------------
# The reducer's ops carry the instruction's name and opcode only. Kernels are
# found by name; a layer's sub-blocks by POSITION among the step's dense Q40
# calls, which come in a fixed order, six a layer: wq_a, wq_b, wkv_a (the
# attention's first leaves), [the latent decode kernel,] wo, then the FFN's
# two (dense: w13, w2; expert: after the expert kernel's calls, the shared
# expert's sh_w13, sh_w2), and the classifier's one at the end.

def _is(op, prefix: str) -> bool:
    return op.label == "custom-call" and op.name.lower().startswith(prefix)


def step_kernel_seconds(trace) -> list[dict]:
    """Per decode step of the traced window that ran the latent kernel
    (``reduce_trace.steps``): seconds in the latent decode kernel, in the
    slot kernel, and in the dense Q40 calls."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        acc = {"latent": 0.0, "slots": 0.0, "dense": 0.0}
        for o, s in zip(ops, rt.self_times(ops)):
            if _is(o, DECODE_KERNEL):
                acc["latent"] += s / 1e9
            elif _is(o, SLOT_KERNEL):
                acc["slots"] += s / 1e9
            elif rt.classify(o) == "q40" and not _is(o, MOE_KERNEL_PREFIX):
                acc["dense"] += s / 1e9
        if acc["latent"] > 0:
            out.append(acc)
    return out


def block_seconds(trace, device: str | None = None) -> dict:
    """Self seconds, over every program run of the traced window on
    ``device`` (default: the first) whose dense Q40 calls have a layer's
    period (decode steps and admission chunks), of the attention sub-blocks
    ("mla": from a layer's first attention leaf to its ``wo``, both
    included) and of the expert sub-blocks ("moe": from the op after ``wo``
    to the next layer's first attention leaf, or the classifier, where the
    layer ran an expert kernel: FFN norm, router, slot building, routed and
    shared experts, combine, residual)."""
    import bisect

    from . import reduce_trace as rt

    out = {"mla": 0.0, "moe": 0.0}
    if not trace.devices:
        return out
    device = device or sorted(trace.devices)[0]
    ops = trace.devices[device]
    starts = [o.start for o in ops]
    for run in trace.modules.get(device, []):
        inside = ops[bisect.bisect_left(starts, run.start):
                     bisect.bisect_right(starts, run.end)]
        selfs = rt.self_times(inside)
        work = [i for i, o in enumerate(inside)
                if rt.classify(o) != "control"]
        moe = [i for i in work if _is(inside[i], MOE_KERNEL_PREFIX)]
        dense = [i for i in work if rt.classify(inside[i]) == "q40"
                 and not _is(inside[i], MOE_KERNEL_PREFIX)]
        if not moe or len(dense) % 6 != 1:    # not a forward of this model
            continue
        for k in range(0, len(dense) - 1, 6):
            lo, hi, nxt = dense[k], dense[k + 3], dense[k + 6]
            out["mla"] += sum(selfs[i] for i in work if lo <= i <= hi)
            if any(hi < m < nxt for m in moe):
                out["moe"] += sum(selfs[i] for i in work if hi < i < nxt)
    return {k: v / 1e9 for k, v in out.items()}
