"""A latent-attention expert configuration with layer KINDS (Motif-3-Beta:
grouped differential attention on a latent plane, 80 query heads over 16
latent KV groups, one noise head a group subtracted from the group's four
signal heads with a per-token lambda, an elementwise gate; "sliding" layers
of 128 positions beside "full" ones; PolyNorm in every FFN; four residual
streams; two dense layers before expert layers that route 8 of 384 by sigmoid
scores with one shared expert; this chip holds 48 of the 384) for the
drivers: its sizes and ``TransformerSpec`` from the configuration file, its
seeded codec tree, the benchmark's own copy of the plain float32 reference,
the bytes and operations a step must move, and where a device trace shows its
parts. What ``harness/hyper.py`` (the streams' coefficients and mixes, their
bytes, the path's ops in a trace), ``laguna.py`` (the router's scores and
host-side choice, the pairs' blocks, the rotation, the control's rounding,
the margin and reversal rules), ``mimo.py`` (the held share's blocks),
``latent.py`` (the norm, the head, the kernels' names) and ``weights.py``
have that applies is imported, not copied.

The layer (``distributed_llama_tpu/models/reference_motif.py`` states it in
full, with every reading the published config does not settle), h the
RMSNorm of the streams' mix, G = 16 groups of P = 5 heads:

  c_q = RMSNorm(h Wqa); q = c_q Wqb: 80 heads of [q_nope 128 | q_rope 64]
  [c | k_r] = h Wkva; c_kv = RMSNorm(c); k_rope = RoPE(k_r), one for all
  [k_nope | v]_g = Wkvb,g c_kv                     (16 groups of 128 + 128)
  a_j = softmax((q_nope_j . k_nope_g + q_rope_j . k_rope) / sqrt 192) v_g
        causal, a sliding layer over the last 128 positions
  d_s = a_s - sigmoid(h Wl)_s a_noise(g(s))        (64 signal heads)
  x_att = (d * sigmoid(h Wg)) Wo
  FFN   w2(PolyNorm(w1 h') * w3 h'); experts: sigmoid(Wr h'), the 8 largest,
        weights renormalised times 2, the chosen experts HELD HERE + shared
"""

from __future__ import annotations

import concurrent.futures
import functools
import math
import os

import numpy as np

from . import costs, hyper, weights
from .laguna import (MARGIN_EPSILON, QUERY_BLOCK, REVERSAL_EPSILON, _ein,
                     _rope, _scores, decisions_to_reverse, route,
                     strict_positions, with_reversals)
from .latent import (DECODE_KERNEL, MOE_KERNEL_PREFIX, SHARED_MARGIN,
                     SLOT_KERNEL, _head, _is, _normed, _pair, _rmsnorm,
                     ffn_shapes)
from .mimo import held_blocks
from .reference import _dequant

__all__ = ["MARGIN_EPSILON", "REVERSAL_EPSILON", "decisions_to_reverse",
           "strict_positions", "with_reversals"]

KINDS = ("full", "sliding")
RING_KERNEL = "mla_ring_attn_decode"
ATTN_KEYS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wg", "wo")
HIGHEST_PASSES = 6
"""bf16 MXU passes a float32 product at ``Precision.HIGHEST`` takes: the
peak such a product can reach is the published bf16 peak over this."""
LAMBDA_SHARES = (0.2, 0.8)
"""Where the mean lambda of a run's check has to lie (the configuration's
``assumed.seeded_lambda``): a noise head that takes nothing tests nothing."""


def kinds_of(sizes: dict) -> tuple:
    """Layer i is "full" where (i + 1) % period == 0 [assumed]."""
    return tuple("full" if (i + 1) % sizes["period"] == 0 else "sliding"
                 for i in range(sizes["n_layers"]))


def sizes_of(config: dict) -> dict:
    """Everything the spec, the tree and the counts need, flat."""
    pub, dep = config["published"], config["deployment"]
    heads, groups = config["num_attention_heads"], config["num_key_value_heads"]
    return {
        "dim": config["hidden_size"],
        "hidden_dim": config["moe_intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": heads, "groups": groups,
        "noise_heads": config["num_noise_heads"] // groups,
        "signal_heads": heads - config["num_noise_heads"],
        "vocab_size": config["vocab_size"],
        "seq_len": config["max_position_embeddings"],
        "n_experts": pub["num_experts"], "held": config["num_experts"],
        "offset": dep["expert_offset"],
        "n_active_experts": config["experts_top_k"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["head_dim"] - config["qk_rope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"], "v_dim": config["v_head_dim"],
        "dense_layers": config["n_dense_first_layers"],
        "dense_hidden": config["intermediate_size"],
        "shared": config["num_shared_experts"],
        "route_scale": float(config["route_scale"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "window": config["sliding_window"],
        "period": config["sliding_window_period"],
        "streams": config["mhc_expansion_rate"],
        "sinkhorn_iters": config["mhc_sinkhorn_iters"],
        "hc_eps": 1e-6, "clamp_min": -math.inf, "clamp_max": math.inf,
        "stream_clamp": float(config["hidden_clamp"]),
        "pn_scale": float(config["polynorm_output_scale"]),
        "pn_clamp": float(config["polynorm_bias_clamp"]),
    }


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    if config.get("model_type") != "Motif":
        raise ValueError("harness/motif.py runs model_type Motif")
    if (config.get("weights"), config.get("buffers"),
            config.get("latent_cache")) != ("q40", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers "
                         "and float32 latent rings and pages")
    if (config["attention_cls"], config["score_func"], config["hidden_act"],
            config["diff_v2"], config["elementwise_attn_output_gate"],
            config["headwise_attn_output_gate"], config["route_norm"],
            config["score_before_experts"], config["mhc_enabled"],
            config["use_sliding_window"], config["sliding_window_pattern"],
            config["interleave_moe_layer_step"],
            config["tie_word_embeddings"]) != (
            "gdla", "sigmoid", "poly_norm", True, True, False, True, False,
            True, True, "interleave", 1, False):
        raise ValueError("grouped differential latent attention with an "
                         "elementwise gate, sigmoid scores renormalised and "
                         "applied after the experts, PolyNorm, streams, "
                         "interleaved windows, an expert layer after every "
                         "leading dense one and an untied head")
    if config["rope_scaling"].get("apply_yarn_scaling") \
            or config["swa_rope_theta"] != config["rope_theta"] \
            or config["num_noise_heads"] != config["num_key_value_heads"]:
        raise ValueError("plain RoPE at one base in both kinds, and one "
                         "noise head a KV group")
    if config["deployment"]["chips_per_layer"] * config["num_experts"] \
            != config["published"]["num_experts"]:
        raise ValueError("the experts held times the chips that share a "
                         "layer must be the published count")


def program_spec(sizes: dict):
    """The program's spec. A program without the fields stops HERE (an
    ``ImportError``), before any device is touched."""
    from distributed_llama_tpu.models import spec as sp
    from distributed_llama_tpu.ops.quants import FloatType

    if not hasattr(sp, "Activation") or "kv_groups" not in getattr(
            sp.LatentAttn, "__dataclass_fields__", {}):
        raise ImportError("the program's LatentAttn has no KV groups, noise "
                          "heads, gate and layer kinds, and its spec no "
                          "activation: it cannot run this configuration")
    s = sizes
    return sp.TransformerSpec(
        dim=s["dim"], hidden_dim=s["hidden_dim"], n_layers=s["n_layers"],
        n_heads=s["n_heads"], n_kv_heads=s["n_heads"],
        vocab_size=s["vocab_size"], seq_len=s["seq_len"],
        weights_float_type=FloatType.Q40, buffer_float_type=FloatType.F32,
        n_experts=s["n_experts"], n_active_experts=s["n_active_experts"],
        rope_theta=s["rope_theta"], norm_eps=s["norm_eps"],
        latent=sp.LatentAttn(
            s["q_rank"], s["kv_rank"], s["nope_dim"], s["rope_dim"],
            s["v_dim"], kv_groups=s["groups"], noise_heads=s["noise_heads"],
            gate=True, kinds=kinds_of(s), window=s["window"]),
        layout=sp.ExpertLayout(
            s["dense_layers"], s["dense_hidden"], s["shared"],
            s["held"] if s["held"] < s["n_experts"] else 0, s["offset"]),
        router=sp.Router("sigmoid", 1, 1, True, s["route_scale"], False),
        hyper=sp.HyperConnections(
            s["streams"], s["sinkhorn_iters"], s["hc_eps"], s["clamp_min"],
            s["clamp_max"], s["stream_clamp"]),
        activation=sp.Activation("polynorm", s["pn_scale"], s["pn_clamp"]))


def attn_shapes(sizes: dict) -> list:
    s, out = sizes, sizes["signal_heads"] * sizes["v_dim"]
    return [("wq_a", (s["q_rank"], s["dim"])),
            ("wq_b", (s["n_heads"] * (s["nope_dim"] + s["rope_dim"]),
                      s["q_rank"])),
            ("wkv_a", (s["kv_rank"] + s["rope_dim"], s["dim"])),
            ("wkv_b", (s["groups"] * (s["nope_dim"] + s["v_dim"]),
                       s["kv_rank"])),
            ("wg", (out, s["dim"])), ("wo", (s["dim"], out))]


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of the spec: the leading dense layers' stacks
    under ``"dense"``, the expert layers' at the top level (the HELD
    experts' stacks; the router's rows at its full width), every leaf filled
    per (tensor, layer[, expert]) so that the seed alone fixes it. Q40
    leaves by ``weights._fill_q40``'s recipe (value std 1 / sqrt(n)); gains
    1 +- 0.05; router rows, ``w_lambda`` rows N(0, 1/sqrt(dim)) (``wg`` is
    a Q40 leaf of the same std): unit-variance logits on unit-RMS input, so
    lambda and the gate spread around 0.5 and move with the token;
    ``pn_w`` = (1/3 + N(0, 0.1)) x 3, N(0, 0.3); the residual path's leaves
    as ``hyper.codec_tree``'s (xing4-29b-a4b-q40's values)."""
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
        dense(dst, "w_lambda", base + 50, (depth, s["signal_heads"], dim),
              0.0)
        dst["pn_w"] = np.stack([_polynorm_leaf(seed, base + 51, i)
                                for i in range(depth)])
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["dense_hidden"])):
        q40(tree["dense"], name, 120 + i, (k,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(
            dim, s["shared"] * s["hidden_dim"], "sh_")):
        q40(tree, name, 220 + i, (n_exp,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["hidden_dim"],
                                                  "moe_")):
        q40(tree, name, 230 + i, (n_exp, s["held"]), d, n)
    dense(tree, "moe_gate", 240, (n_exp, s["n_experts"], dim), 0.0)
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
    tree["wcls"].d16[weights.BOS] = 0     # logit exactly 0: never the argmax
    unit = np.float32(1.0 / np.sqrt(dim))
    tree["moe_gate"] *= unit
    for dst in (tree["dense"], tree):
        dst["w_lambda"] *= unit
    _hyper_leaves(tree, sizes, seed)
    return tree


def _polynorm_leaf(seed: int, idx: int, layer: int) -> np.ndarray:
    x = np.random.default_rng([seed, idx, layer]).standard_normal(
        4, dtype=np.float32)
    x[:3] = np.float32(1 / 3) + np.float32(0.1) * x[:3]
    x[3] *= np.float32(0.3)
    return x


def _hyper_leaves(tree: dict, sizes: dict, seed: int) -> None:
    """``hyper.codec_tree``'s residual-path leaves (its seeds and values)
    into this tree's two stacks."""
    n, k = sizes["streams"], hyper.coefficients(sizes)
    wide = n * sizes["dim"]
    eye = 4.0 * np.eye(n, dtype=np.float32).reshape(-1)
    for base, dst, depth in ((300, tree["dense"], sizes["dense_layers"]),
                             (400, tree, sizes["n_layers"]
                              - sizes["dense_layers"])):
        for j, sub in enumerate(hyper.SUBLAYERS):
            phi = np.empty((depth, k, wide), np.float32)
            bias = np.empty((depth, k), np.float32)
            for i in range(depth):
                rng = np.random.default_rng([seed, base + j, i])
                phi[i] = rng.standard_normal((k, wide), dtype=np.float32) \
                    * np.float32(wide ** -0.5)
                bias[i] = rng.standard_normal(k, dtype=np.float32)
                bias[i, 2 * n:] += eye
            dst[f"hc_{sub}_phi"], dst[f"hc_{sub}_bias"] = phi, bias
            dst[f"hc_{sub}_gate"] = np.full((depth, 3), 0.5, np.float32)


# -- the benchmark's copy of the reference -----------------------------------
# ``harness/laguna.py``'s plan (a layer at a time on one device, inside a
# layer one tensor group at a time; every product through ``laguna._ein``:
# float32 at HIGHEST, or with ``low`` both operands rounded to bfloat16
# first, the control that must FAIL) with this model's attention EXPANDED a
# KV group at a time, ``hyper.py``'s streams around each sub-layer, and
# PolyNorm, whose mean runs over an FFN's whole width (so a dense FFN is
# not cut into blocks of its hidden width, as ``latent._swiglu_block`` cuts
# a SiLU one).

def rope_table(sizes: dict):
    """(frequencies (rope_dim / 2,), 1.0): plain RoPE, no YaRN blend."""
    dim = sizes["rope_dim"]
    f = sizes["rope_theta"] ** (-np.arange(0, dim, 2) / dim)
    return f.astype(np.float32), 1.0


def _polynorm(sizes, z, pn_w):
    import jax.numpy as jnp

    def n(u):
        return u / jnp.sqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                            + sizes["norm_eps"])

    c = sizes["pn_clamp"]
    bias = jnp.clip(pn_w[3], -c, c) if c else pn_w[3]
    return sizes["pn_scale"] * (pn_w[0] * n(z ** 3) + pn_w[1] * n(z ** 2)
                                + pn_w[2] * n(z) + bias)


def _attention(sizes, low, kind, lambda_on, h_in, rms_att, rms_q_a, rms_kv_a,
               w_lambda, w):
    """(the attention sub-layer's output of input ``h_in`` (B, T, C), which
    it norms; the mean lambda). ``lambda_on`` False leaves the noise heads
    out: the second control, which must fail too."""
    import jax
    import jax.numpy as jnp

    s, eps = sizes, sizes["norm_eps"]
    nh, groups = s["n_heads"], s["groups"]
    per = nh // groups
    nope, dv = s["nope_dim"], s["v_dim"]
    B, T, _ = h_in.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    freq, factor = rope_table(sizes)
    scale = 1.0 / math.sqrt(nope + s["rope_dim"])
    h = _rmsnorm(jnp, h_in, rms_att, eps)
    c_q = _rmsnorm(jnp, mm(wf["wq_a"], h), rms_q_a, eps)
    q = mm(wf["wq_b"], c_q).reshape(B, T, nh, -1)
    q = jnp.concatenate([q[..., :nope],
                         _rope(jnp, q[..., nope:], freq, factor)], axis=-1)
    kv = mm(wf["wkv_a"], h)
    c_kv = _rmsnorm(jnp, kv[..., :s["kv_rank"]], rms_kv_a, eps)
    k_rope = _rope(jnp, kv[..., None, s["kv_rank"]:], freq, factor)[..., 0, :]
    kvb = mm(wf["wkv_b"], c_kv).reshape(B, T, groups, nope + dv)
    qb = T if T <= QUERY_BLOCK else math.gcd(T, QUERY_BLOCK)
    pos = jnp.arange(T)

    def group(block):
        # one KV group's five heads and ``qb`` queries at a time; a head's
        # numbers do not depend on how they are blocked
        qg, kvg = block         # (B, T, per, nope + rope), (B, T, nope + dv)
        kg = jnp.concatenate([kvg[..., :nope], k_rope], axis=-1)
        vg = kvg[..., nope:]

        def queries(qpart):
            qq, at = qpart
            back = at[:, None] - pos[None, :]
            see = back >= 0
            if kind == "sliding":
                see = see & (back < s["window"])
            sc = ein("btmd,bsd->bmts", qq, kg) * scale
            att = jax.nn.softmax(jnp.where(see, sc, -jnp.inf), axis=-1)
            return ein("bmts,bsd->btmd", att, vg)

        out = jax.lax.map(queries, (
            jnp.moveaxis(qg.reshape(B, T // qb, qb, *qg.shape[2:]), 1, 0),
            pos.reshape(T // qb, qb)))
        return jnp.moveaxis(out, 0, 1).reshape(B, T, per, dv)

    a = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(B, T, groups, per, -1), 2, 0),
        jnp.moveaxis(kvb, 2, 0)))
    a = jnp.moveaxis(a, 0, 2)                       # (B, T, G, per, dv)
    nz = s["noise_heads"]
    lam = jax.nn.sigmoid(ein("sn,btn->bts", w_lambda, h))
    d = a[..., :per - nz, :]
    if lambda_on:
        d = d - lam.reshape(B, T, groups, per - nz, 1) * a[..., per - nz:, :]
    d = d.reshape(B, T, -1) * jax.nn.sigmoid(mm(wf["wg"], h))
    return mm(wf["wo"], d), jnp.mean(lam)


def _ffn(sizes, low, acc, h, pn_w, w1, w2, w3):
    """acc + w2(PolyNorm(w1 h) * w3 h): a dense FFN, or a shared expert."""
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    w1, w2, w3 = (_dequant(jnp, *w) for w in (w1, w2, w3))
    return acc + mm(w2, _polynorm(sizes, mm(w1, h), pn_w) * mm(w3, h))


def _experts(sizes, low, y, h, pn_w, used, expert, at, we, w1, w2, w3):
    """y + sum_e w_e E_e(h) over a layer's chosen (position, expert) pairs,
    a block of ``laguna.expert_blocks`` at a time (``laguna._experts``'s
    loop) with PolyNorm, whose mean is a pair's own, in SiLU's place."""
    import jax
    import jax.numpy as jnp

    mm = functools.partial(_ein, low, "dn,btn->btd")
    dim, rows = y.shape[-1], at.shape[1]
    zeros = jnp.zeros((rows, dim), jnp.float32)
    flat = jnp.concatenate([h.reshape(-1, dim), zeros])

    def body(i, acc):
        e, to, weight = expert[i], at[i], we[i]
        a, b, c = (_dequant(jnp, qs[e], d16[e]) for qs, d16 in (w1, w2, w3))
        hr = flat[to][None]
        out = mm(b, _polynorm(sizes, mm(a, hr), pn_w) * mm(c, hr))[0]
        return acc.at[to].add(weight[:, None] * out, unique_indices=True)

    acc = jax.lax.fori_loop(0, used, body, jnp.concatenate(
        [y.reshape(-1, dim), zeros]))
    return acc[:-rows].reshape(y.shape)


def _mix_out(sizes, low, x, res, post, y):
    import jax.numpy as jnp

    out = hyper._mix_out(low, x, res, post, y)
    c = sizes["stream_clamp"]
    return jnp.clip(out, -c, c) if c else out


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool, lambda_on: bool):
    """The jitted pieces of one configuration at one precision."""
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes)
    both = (low, False)
    return {
        **{kind: jax.jit(functools.partial(_attention, sizes, low, kind,
                                           lambda_on)) for kind in KINDS},
        "coef": jax.jit(functools.partial(hyper._coef, sizes, both)),
        "mix_in": jax.jit(functools.partial(hyper._mix_in, both)),
        "mix_out": jax.jit(functools.partial(_mix_out, sizes, both),
                           donate_argnums=0),
        "fan_out": jax.jit(functools.partial(hyper._fan_out, sizes)),
        "fold": jax.jit(lambda x: x.sum(axis=2)),
        "zeros": jax.jit(jnp.zeros_like),
        "normed": jax.jit(functools.partial(_normed, sizes)),
        "ffn": jax.jit(functools.partial(_ffn, sizes, low),
                       donate_argnums=0),
        "scores": jax.jit(functools.partial(_scores, low)),
        "experts": jax.jit(functools.partial(_experts, sizes, low),
                           donate_argnums=0),
        "head": jax.jit(functools.partial(_head, low))}


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           dense_blocks: int = 4, settle: int | None = None, flips=(),
           lengths=None, lambda_on: bool = True, stats: dict | None = None):
    """``laguna.logits`` for this model (its arguments and results): float32
    logits of the full forward pass over ``tokens`` (B, T) at the positions
    ``keep``, of the experts HELD, and the router margins (B, T, expert
    layers) of the "highest" pass; "bfloat16" is the control one precision
    down, ``lambda_on`` False the control without the noise heads.
    ``settle`` (a seed) draws an expert layer's router rows again until
    every row's margin is over ``latent.SHARED_MARGIN``, and returns
    nothing. ``stats``, where given, receives ``lambda_mean``: the mean,
    over layers, signal heads and positions of the "highest" pass, of
    lambda."""
    import jax
    import jax.numpy as jnp

    del dense_blocks            # PolyNorm's mean: a dense FFN runs whole
    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    key = tuple(sorted(sizes.items()))
    progs = {p: _programs(key, p == "bfloat16", bool(lambda_on))
             for p in precisions}
    emb = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    xs = {p: progs[p]["fan_out"](emb) for p in precisions}
    del emb
    margins, lams = [], []
    n_exp = sizes["n_layers"] - sizes["dense_layers"]
    flip_of = np.zeros((n_exp, *tokens.shape), bool)
    for b, t, at in flips:
        flip_of[at, b, t] = True
    ends = (np.full(len(tokens), tokens.shape[1]) if lengths is None
            else np.asarray(lengths))
    live = np.arange(tokens.shape[1])[None, :] < ends[:, None]

    def around(stack, i, sub, fn):
        """xs <- X' around sub-layer ``fn`` {precision: h} -> {precision: y}."""
        hc = [put(stack[f"hc_{sub}_{leaf}"][i])
              for leaf in ("phi", "gate", "bias")]
        coefs = {p: progs[p]["coef"](xs[p], *hc) for p in precisions}
        ys = fn({p: progs[p]["mix_in"](xs[p], coefs[p][0])
                 for p in precisions})
        for p in precisions:
            _, post, res = coefs[p]
            xs[p] = progs[p]["mix_out"](xs[p], res, post, ys[p])

    for layer, kind in enumerate(kinds_of(sizes)):
        dense = layer < sizes["dense_layers"]
        stack = tree["dense"] if dense else tree
        i = layer if dense else layer - sizes["dense_layers"]
        pn_w = put(stack["pn_w"][i])

        def attention(hs, stack=stack, i=i, kind=kind):
            w = {k: tuple(put(a) for a in _pair(stack[k], i))
                 for k in ATTN_KEYS}
            gains = [put(stack[k][i])
                     for k in ("rms_att", "rms_q_a", "rms_kv_a", "w_lambda")]
            out = {}
            for p in precisions:
                out[p], lam = progs[p][kind](hs[p], *gains, w)
                if p == "highest":
                    lams.append(lam)
            return out

        def dense_ffn(hs, stack=stack, i=i):
            g = put(stack["rms_ffn"][i])
            blk = tuple(tuple(put(a) for a in _pair(stack[k], i))
                        for k in ("w1", "w2", "w3"))
            return {p: progs[p]["ffn"](
                progs[p]["zeros"](hs[p]), progs[p]["normed"](hs[p], g), pn_w,
                *blk) for p in precisions}

        def expert_ffn(hs, stack=stack, i=i):
            g = put(stack["rms_ffn"][i])
            hn = {p: progs[p]["normed"](hs[p], g) for p in precisions}
            attempt = 0
            while True:
                gate = put(stack["moe_gate"][i])
                routed = {p: route(sizes, np.asarray(
                    progs[p]["scores"](hn[p], gate)), flip_of[i], live)
                    for p in precisions}
                if settle is None or float(routed["highest"][2].min()) \
                        >= SHARED_MARGIN:
                    break
                attempt += 1
                rng = np.random.default_rng([settle, 240, i, attempt])
                stack["moe_gate"][i] = rng.standard_normal(
                    stack["moe_gate"][i].shape, dtype=np.float32) \
                    * np.float32(1.0 / np.sqrt(sizes["dim"]))
            if "highest" in routed:
                margins.append(routed["highest"][2])
            shared = tuple(tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("sh_w1", "sh_w2", "sh_w3"))
            held = tuple(tuple(put(a) for a in _pair(stack[k], i))
                         for k in ("moe_w1", "moe_w2", "moe_w3"))
            ys = {}
            for p in precisions:
                ids, w, _ = routed[p]
                y = progs[p]["ffn"](progs[p]["zeros"](hs[p]), hn[p], pn_w,
                                    *shared)
                ys[p] = progs[p]["experts"](y, hn[p], pn_w, *(
                    put(a) for a in held_blocks(sizes, ids, w, live)), *held)
            return ys

        around(stack, i, "att", attention)
        around(stack, i, "ffn", dense_ffn if dense else expert_ffn)
        # a layer at a time ON THE DEVICE too (the loop would otherwise run
        # ahead and park every layer's weights there)
        jax.block_until_ready(list(xs.values()))
    if stats is not None and lams:
        stats["lambda_mean"] = float(np.mean([float(a) for a in lams]))
    if settle is not None:
        return None
    out = {}
    qs, d16 = tree["wcls"].qs, tree["wcls"].d16
    edges = np.linspace(0, qs.shape[0], vocab_blocks + 1).astype(int)
    g_final = put(tree["rms_final"])
    for p in precisions:
        x = progs[p]["fold"](xs[p])
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
    """``laguna.settle_shared_positions`` on this reference: an expert
    layer's router rows are drawn again (from the attempt's number, so the
    seed still fixes the tree) until the positions every prompt opens with
    choose with a margin over ``latent.SHARED_MARGIN``."""
    logits(tree, sizes, np.asarray([list(shared_tokens)]), settle=seed)


# -- bytes and operations a step must move, from shapes ----------------------

def _q40_bytes(shapes) -> int:
    return sum(d * n for _, (d, n) in shapes) // costs.Q40_BLOCK \
        * costs.Q40_BLOCK_BYTES


def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE routed expert's three leaves (8,847,360)."""
    return _q40_bytes(ffn_shapes(sizes["dim"], sizes["hidden_dim"]))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: each layer's five Q40 attention leaves (``wkv_b`` is held as
    float32 and is not among them; the plane's padding rows of ``wkv_a``
    are not counted), the leading layers' dense FFN, the expert layers'
    shared expert, the classifier."""
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


def attn_step_cost(sizes: dict, kind: str, positions: float) -> tuple:
    """(bytes, operations) of the absorbed decode attention of the layers
    of ``kind`` over ``positions`` (summed over a step's rows: pos + 1 in a
    full layer, min(pos + 1, window) in a sliding one), each ONCE: a
    position is ``width`` float32 values a layer as PUBLISHED (576: the
    cache holds it in 640 lanes, which reads as lost share), and every one
    of the 80 heads scores it over ``width`` and sums it over ``kv_rank``, a
    multiply-add counted as two."""
    n = kinds_of(sizes).count(kind)
    w = latent_width(sizes)
    return (positions * w * 4 * n,
            2.0 * positions * sizes["n_heads"] * (w + sizes["kv_rank"]) * n)


def roofline_seconds(device_kind: str, nbytes: float, flops: float) -> float:
    """The least time ``nbytes`` from HBM and ``flops`` of float32 products
    at HIGHEST (``HIGHEST_PASSES`` bf16 passes each) can take: the larger of
    the two."""
    from .peaks import peak

    return max(nbytes / peak(device_kind, "hbm_bytes_per_s"),
               flops * HIGHEST_PASSES / peak(device_kind, "bf16_flops_per_s"))


# -- what a device trace shows -----------------------------------------------
# Kernels by name. A layer's sub-blocks by POSITION among a program run's
# dense Q40 calls, which come in ``harness/latent.py``'s order, six a layer
# (the program lays ``wg`` behind ``wkv_a`` in one leaf): wq_a, wq_b,
# wkv_a + wg, [the kind's decode kernel,] wo, then the FFN's two (dense:
# w13, w2; expert: after the expert kernel's calls, sh_w13, sh_w2), and the
# classifier's one at the end of a decode step.

def step_kernel_seconds(trace) -> list[dict]:
    """Per decode step of the traced window that ran the ring kernel
    (``reduce_trace.steps``): seconds in the ring kernel, in the paged
    latent kernel, in the slot kernel and in the dense Q40 calls."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        acc = {"ring": 0.0, "full": 0.0, "slots": 0.0, "dense": 0.0}
        for o, s in zip(ops, rt.self_times(ops)):
            if _is(o, RING_KERNEL):
                acc["ring"] += s / 1e9
            elif _is(o, DECODE_KERNEL):
                acc["full"] += s / 1e9
            elif _is(o, SLOT_KERNEL):
                acc["slots"] += s / 1e9
            elif rt.classify(o) == "q40" and not _is(o, MOE_KERNEL_PREFIX):
                acc["dense"] += s / 1e9
        if acc["ring"] > 0:
            out.append(acc)
    return out


def block_seconds(trace, sizes: dict, names: dict | None = None,
                  device: str | None = None) -> dict:
    """Self seconds, over every program run of the traced window on
    ``device`` (default: the first) that is a forward of this model (six
    dense Q40 calls a layer, and the classifier's where it is a decode
    step), of the sliding layers' attention ("sliding") and the full
    layers' ("full"), each from a layer's first attention leaf to its
    ``wo``, both included; of the expert sub-blocks ("moe": from the op
    after ``wo`` to the next layer's first leaf, or the classifier, where
    the layer ran an expert kernel); and, with ``names`` ({instruction:
    scope} of the step's compiled text: ``scoped_instructions``), of the
    ops under the differential fold's and the gate's scopes ("diff") and
    under PolyNorm's ("polynorm"), told by identity as ``harness/hyper.py``
    tells the residual path's."""
    import bisect

    from . import reduce_trace as rt

    out = {"sliding": 0.0, "full": 0.0, "moe": 0.0, "diff": 0.0,
           "polynorm": 0.0}
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
        moe = [i for i in work if _is(inside[i], MOE_KERNEL_PREFIX)]
        dense = [i for i in work if rt.classify(inside[i]) == "q40"
                 and not _is(inside[i], MOE_KERNEL_PREFIX)]
        if not moe or len(dense) not in (6 * len(kinds), 6 * len(kinds) + 1):
            continue
        for layer, kind in enumerate(kinds):
            lo, hi = dense[6 * layer], dense[6 * layer + 3]
            nxt = (dense[6 * layer + 6] if 6 * layer + 6 < len(dense)
                   else len(inside))
            out[kind] += sum(selfs[i] for i in work if lo <= i <= hi)
            if any(hi < m < nxt for m in moe):
                out["moe"] += sum(selfs[i] for i in work if hi < i < nxt)
        for i in work if names else ():
            part = names.get(inside[i].name)
            if part:
                out[part] += selfs[i]
    return {k: v / 1e9 for k, v in out.items()}


SCOPED = {"attn.diff/": "diff", "attn.gate/": "diff",
          "ffn.polynorm/": "polynorm"}


def scoped_instructions(hlo_text: str) -> dict:
    """{instruction name: "diff" | "polynorm"} of a compiled step's
    instructions, outside fused computations, whose ``op_name`` lies under
    the differential fold's, the gate's or PolyNorm's scope
    (``hyper.path_instructions``'s walk with these scopes). A fusion takes
    its root's ``op_name``."""
    out: dict = {}
    fused = False
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():      # a computation's header
            fused = "fused_computation" in line or line.lstrip("%").startswith(
                ("fused", "region_"))
            continue
        scope = hyper._OP_NAME.search(line)
        if fused or scope is None:
            continue
        part = next((v for k, v in SCOPED.items() if k in scope.group(1)),
                    None)
        m = hyper._INSTRUCTION.match(line)
        if part and m and m.group("kind") not in hyper._NO_OP:
            out[m.group("name")] = part
    return out
