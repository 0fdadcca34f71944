"""A mixer-kinds expert configuration whose kinds differ in more than a head
count (MiMo-V2-Flash: full layers of 4 KV heads beside sliding layers of 8
with a learned softmax sink a query head, K heads of 192 beside V heads of
128, RoPE on a head's first 64 values, the attention output scaled by 0.707;
one dense layer before expert layers that route 8 of 256 by sigmoid scores
and a choice bias, no shared expert; this chip holds 32 of the 256) for the
drivers: its sizes and ``TransformerSpec`` from the configuration file, its
seeded codec tree, the benchmark's own copy of the plain float32 reference,
and the bytes a step must move. What ``harness/laguna.py``, ``latent.py``,
``weights.py``, ``reference.py`` and ``costs.py`` have that applies (the value
recipe, the dequantizer, the rotation, the router's scores, the blocked
SwiGLU, the pairs' blocks and the pass over them, the head, the margin and
reversal rules, Q40 block bytes, the kernels' names and seconds) is imported,
not copied.

The layers (``distributed_llama_tpu/models/reference_laguna.py`` states them
in full), layer l of kind k with H heads over G_k KV heads, K heads of d and
V heads of d_v:

  attention   h = RMSNorm(x); q = Wq h (H x d), key = Wk_k h (G_k x d),
              v = Wv_k h (G_k x d_v); RoPE by kind (theta_k) on the first
              ``rotary`` values of a head in interleaved pairs; scores
              q . key / sqrt d, causal, a sliding layer over the last
              ``window`` positions; a sliding head's learned sink s_j joins
              its softmax as ONE MORE COLUMN and is dropped (no value: the
              weights sum to less than 1); o = value_scale * sum p v;
              x + Wo [o_1..o_H] (H x d_v wide)
  dense FFN   w2(silu(w1 h) * w3 h), layer 0
  expert FFN  s = sigmoid(W_r h); the k largest of s + b; weights
              s_sel / sum(s_sel); sum over the chosen experts HELD HERE of
              w_e E_e(h): this chip's partial sum of its group of eight
"""

from __future__ import annotations

import bisect
import concurrent.futures
import functools
import math
import os

import numpy as np

from . import costs, weights
from .laguna import (ATTN_KEYS, KINDS, MARGIN_EPSILON, MOE_KERNEL_PREFIX,
                     QUERY_BLOCK, REVERSAL_EPSILON, _ein, _experts, _is, _rope,
                     _scores, decisions_to_reverse, expert_blocks,
                     step_kernel_seconds, strict_positions, with_reversals)
from .latent import (SHARED_MARGIN, _head, _normed, _pair, _rmsnorm,
                     _swiglu_block, ffn_shapes)
from .reference import _dequant

__all__ = ["MARGIN_EPSILON", "REVERSAL_EPSILON", "decisions_to_reverse",
           "step_kernel_seconds", "strict_positions", "with_reversals"]

BIAS_STD = 0.05
"""The seeded choice bias's standard deviation (ISSUE 48's and
``deepseek-v3-q40-ep8``'s). It skews a layer's load (``moe_load_max_over_mean``
1.8 to 2.1 where Laguna's bias-free router reads 1.09); 0.02 was tried on six
seeds (my chip runs, PR 48, call D): the load evened, a step touched 196 to
215 distinct held experts for 174 to 189 and ran 3 % slower, and the runs
spread as before (PERF.md section 6), so the issue's value stands."""
SINK_SHARES = (0.15, 0.45)
"""A seeded sink's share of its head's mass over a full window of scores of
unit variance, drawn between these (``codec_tree``): a sink of 1 / 129 of
the mass would test nothing, one of nine tenths would leave no attention."""


def kinds_of(config_or_sizes: dict) -> tuple:
    return tuple("sliding" if k else "full"
                 for k in config_or_sizes["hybrid_layer_pattern"])


def sizes_of(config: dict) -> dict:
    """Everything the spec, the tree and the counts need, flat."""
    hd = config["head_dim"]
    freq = list(config["moe_layer_freq"])
    pub, dep = config["published"], config["deployment"]
    rot = int(hd * config["partial_rotary_factor"])
    return {
        "dim": config["hidden_size"],
        "hidden_dim": config["moe_intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "full_kv_heads": config["num_key_value_heads"],
        "sliding_kv_heads": config["swa_num_key_value_heads"],
        "head_size": hd,
        "v_head_size": config["v_head_dim"],
        "vocab_size": config["vocab_size"],
        "seq_len": config["max_position_embeddings"],
        "hybrid_layer_pattern": tuple(config["hybrid_layer_pattern"]),
        "window": config["sliding_window"],
        "full_theta": float(config["rope_theta"]),
        "sliding_theta": float(config["swa_rope_theta"]),
        "rotary": rot - rot % 2,
        "full_sink": bool(config["add_full_attention_sink_bias"]),
        "sliding_sink": bool(config["add_swa_attention_sink_bias"]),
        "value_scale": float(config["attention_value_scale"]),
        "n_experts": pub["n_routed_experts"],
        "held": config["n_routed_experts"],
        "offset": dep["expert_offset"],
        "n_active_experts": config["num_experts_per_tok"],
        "dense_layers": freq.index(1) if 1 in freq else len(freq),
        "dense_hidden": config["intermediate_size"],
        "route_scale": float(config["routed_scaling_factor"] or 1.0),
        "norm_eps": float(config["layernorm_epsilon"]),
    }


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    if config.get("model_type") != "mimo_v2_flash":
        raise ValueError("harness/mimo.py runs model_type mimo_v2_flash")
    if (config.get("weights"), config.get("buffers"),
            config.get("kv_cache")) != ("q40", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers "
                         "and float32 rings and pages")
    n = config["num_hidden_layers"]
    freq = list(config["moe_layer_freq"])
    if not (len(config["hybrid_layer_pattern"]) == len(freq) == n):
        raise ValueError("hybrid_layer_pattern and moe_layer_freq: one "
                         "entry a layer")
    if (config.get("attention_bias") or config.get("tie_word_embeddings")
            or config.get("n_shared_experts")
            or (config["scoring_func"], config["topk_method"],
                config["n_group"], config["topk_group"]) != (
                    "sigmoid", "noaux_tc", 1, 1)
            or not config["norm_topk_prob"]
            or sorted(freq) != freq
            or (config["swa_num_attention_heads"], config["swa_head_dim"],
                config["swa_v_head_dim"]) != (
                    config["num_attention_heads"], config["head_dim"],
                    config["v_head_dim"])):
        raise ValueError("no attention bias, no tied embedding, no shared "
                         "expert, sigmoid scores with a choice bias and no "
                         "groups, renormalised weights, dense layers first, "
                         "and one head count and head size for both kinds")


def program_spec(sizes: dict):
    """The program's spec. A program without the fields stops HERE (an
    ``ImportError``), before any device is touched."""
    from distributed_llama_tpu.models.spec import (ExpertLayout, MixerKind,
                                                   MixerKinds, Router,
                                                   TransformerSpec)
    from distributed_llama_tpu.ops.quants import FloatType

    if "kv_heads" not in getattr(MixerKind, "__dataclass_fields__", {}):
        raise ImportError("the program's MixerKind has no KV head count a "
                          "kind, no sink and no V head size: it cannot run "
                          "this configuration")
    s, hd = sizes, sizes["head_size"]
    rot = 0 if s["rotary"] == hd else s["rotary"]
    return TransformerSpec(
        dim=s["dim"], hidden_dim=s["hidden_dim"], n_layers=s["n_layers"],
        n_heads=s["heads"], n_kv_heads=s["full_kv_heads"],
        vocab_size=s["vocab_size"], seq_len=s["seq_len"],
        weights_float_type=FloatType.Q40, buffer_float_type=FloatType.F32,
        n_experts=s["n_experts"], n_active_experts=s["n_active_experts"],
        norm_eps=s["norm_eps"],
        layout=ExpertLayout(s["dense_layers"], s["dense_hidden"], 0,
                            s["held"] if s["held"] < s["n_experts"] else 0,
                            s["offset"]),
        router=Router("sigmoid", 1, 1, True, s["route_scale"], True),
        mixers=MixerKinds(
            kinds_of(s), s["window"], hd,
            MixerKind(s["heads"], s["full_theta"], rot, None, 0,
                      s["full_sink"]),
            MixerKind(s["heads"], s["sliding_theta"], rot, None,
                      0 if s["sliding_kv_heads"] == s["full_kv_heads"]
                      else s["sliding_kv_heads"], s["sliding_sink"]),
            False, 0 if s["v_head_size"] == hd else s["v_head_size"],
            s["value_scale"]))


def attn_shapes(sizes: dict, kind: str) -> list:
    s = sizes
    n_kv, hd, hv = s[kind + "_kv_heads"], s["head_size"], s["v_head_size"]
    return [("wq", (s["heads"] * hd, s["dim"])), ("wk", (n_kv * hd, s["dim"])),
            ("wv", (n_kv * hv, s["dim"])), ("wo", (s["dim"], s["heads"] * hv))]


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of the spec: the mixers a stack a kind under
    ``"full"`` / ``"sliding"``, the leading dense layers' FFNs under
    ``"dense"``, the expert layers' FFNs at the top level (the HELD experts'
    stacks; the router's rows and bias at its full width); every leaf filled
    per (tensor, layer[, expert]) so that the seed alone fixes it. Q40
    leaves by ``weights._fill_q40``'s recipe (value std 1 / sqrt(n)); gains
    1 +- 0.05; router rows N(0, 1/sqrt(dim)); the choice bias N(0,
    ``BIAS_STD``); a
    kind's sinks ln(window) + 1/2 + logit(r), r uniform in ``SINK_SHARES``:
    over a full window of unit-variance scores (whose exponentials sum to
    about window x e^(1/2)) the sink then holds the share r of its head's
    mass."""
    from distributed_llama_tpu.io.loader import Q40Weight

    s = sizes
    dim, vocab = s["dim"], s["vocab_size"]
    kinds = kinds_of(s)
    k, n_exp = s["dense_layers"], s["n_layers"] - s["dense_layers"]
    tree: dict = {"dense": {}, "full": {}, "sliding": {}}
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
    for base, kind in ((300, "full"), (400, "sliding")):
        depth = kinds.count(kind)
        dense(tree[kind], "rms_att", base, (depth, dim), 1.0)
        for i, (name, (d, n)) in enumerate(attn_shapes(s, kind)):
            q40(tree[kind], name, base + 10 + i, (depth,), d, n)
        if s[kind + "_sink"]:
            lo, hi = SINK_SHARES
            r = np.random.default_rng([seed, base + 30]).uniform(
                lo, hi, (depth, s["heads"]))
            tree[kind]["sink"] = (math.log(s["window"]) + 0.5
                                  + np.log(r / (1 - r))).astype(np.float32)
    dense(tree["dense"], "rms_ffn", 100, (k, dim), 1.0)
    dense(tree, "rms_ffn", 200, (n_exp, dim), 1.0)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["dense_hidden"])):
        q40(tree["dense"], name, 120 + i, (k,), d, n)
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
    tree["moe_bias"] *= np.float32(BIAS_STD)
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# ``harness/laguna.py``'s plan (a layer at a time on one device, inside a
# layer one tensor group at a time; every product through ``laguna._ein``:
# float32 at HIGHEST, or with ``low`` both operands rounded to bfloat16
# first, the control that must FAIL) with this model's attention, its
# router's choice bias and its share of the experts.

def rope_table(sizes: dict, kind: str):
    """(frequencies (rotary / 2,), 1.0): pair p's theta_k^(-2p / rotary),
    no scaling."""
    dim = sizes["rotary"]
    f = sizes[kind + "_theta"] ** (-np.arange(0, dim, 2) / dim)
    return f.astype(np.float32), 1.0


def _attention(sizes, low, kind, sink_on, x, rms_att, sink, w):
    """(x + the attention sub-block of a ``kind`` layer, the mean share of
    a head's softmax mass that lies on the sink). ``sink_on`` False leaves
    the column out: the second control, which must fail too."""
    import jax
    import jax.numpy as jnp

    s, eps = sizes, sizes["norm_eps"]
    heads, n_kv = s["heads"], s[kind + "_kv_heads"]
    d, dv = s["head_size"], s["v_head_size"]
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    freq, factor = rope_table(sizes, kind)
    h = _rmsnorm(jnp, x, rms_att, eps)
    q = _rope(jnp, mm(wf["wq"], h).reshape(B, T, heads, d), freq, factor)
    k = _rope(jnp, mm(wf["wk"], h).reshape(B, T, n_kv, d), freq, factor)
    v = mm(wf["wv"], h).reshape(B, T, n_kv, dv)
    qb = T if T <= QUERY_BLOCK else math.gcd(T, QUERY_BLOCK)
    pos = jnp.arange(T)
    with_sink = s[kind + "_sink"] and sink_on

    def group(block):
        # one KV group's heads and ``qb`` queries at a time (a (B, H, T, T)
        # float32 score plane is 17 GB a row at the window's longest
        # request); a head's numbers do not depend on how they are blocked
        qg, kg, vg, sg = block      # (B, T, m, d), (B, T, d), (B, T, dv), (m,)

        def queries(qpart):
            qq, at = qpart              # (B, qb, m, d), (qb,)
            back = at[:, None] - pos[None, :]
            see = back >= 0
            if kind == "sliding":
                see = see & (back < s["window"])
            sc = ein("btmd,bsd->bmts", qq, kg) / math.sqrt(d)
            sc = jnp.where(see, sc, -jnp.inf)
            if with_sink:
                # the sink as the column it is: one more key, with no value
                col = jnp.broadcast_to(sg[None, :, None, None],
                                       (*sc.shape[:-1], 1))
                att = jax.nn.softmax(jnp.concatenate([sc, col], -1), -1)
                on_sink, att = att[..., -1], att[..., :-1]
            else:
                att = jax.nn.softmax(sc, axis=-1)
                on_sink = jnp.zeros(sc.shape[:-1], jnp.float32)
            return ein("bmts,bsd->btmd", att, vg), jnp.mean(on_sink)

        parts = (jnp.moveaxis(qg.reshape(B, T // qb, qb, *qg.shape[2:]),
                              1, 0), pos.reshape(T // qb, qb))
        out, on_sink = jax.lax.map(queries, parts)   # (T / qb, B, qb, m, dv)
        return (jnp.moveaxis(out, 0, 1).reshape(B, T, -1, dv),
                jnp.mean(on_sink))

    m = heads // n_kv
    qg = jnp.moveaxis(q.reshape(B, T, n_kv, m, d), 2, 0)
    sinks = (jnp.asarray(sink, jnp.float32) if with_sink
             else jnp.zeros((heads,), jnp.float32)).reshape(n_kv, m)
    ao, on_sink = jax.lax.map(group, (qg, jnp.moveaxis(k, 2, 0),
                                      jnp.moveaxis(v, 2, 0), sinks))
    ao = jnp.moveaxis(ao, 0, 2).reshape(B, T, heads * dv) * jnp.float32(
        s["value_scale"])
    return x + mm(wf["wo"], ao), jnp.mean(on_sink)


def route(sizes, scores, bias, flip, live):
    """``laguna.route`` for a router that CHOOSES on ``scores + bias`` and
    weighs by the scores alone (weights scale s / sum(s) over the k chosen,
    whether held here or not): ids, weights (0 at a position that is not
    ``live``) and the margin of the k-th chosen over the best one left out,
    on the biased scores. ``flip``: that decision reversed."""
    k = sizes["n_active_experts"]
    chosen_by = scores + bias
    order = np.argsort(-chosen_by, axis=-1, kind="stable")[..., :k + 1]
    top = np.take_along_axis(chosen_by, order, axis=-1)
    margin = top[..., k - 1] - top[..., k]
    ids = order[..., :k].copy()
    ids[..., k - 1] = np.where(flip, order[..., k], order[..., k - 1])
    w = np.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + np.float32(1e-20)) * np.float32(
        sizes["route_scale"])
    return ids, np.where(live[..., None], w, np.float32(0.0)), margin


def held_blocks(sizes, ids, w, live):
    """``laguna.expert_blocks`` over the pairs whose expert is HELD here,
    the expert numbered in the held stack: each (position, j-th choice) is
    handed over as a position of one choice, live where its expert is held,
    and the rows are then pointed back at the positions (a row that holds
    no pair at its row of zeros past the last position)."""
    off, held, k = sizes["offset"], sizes["held"], ids.shape[-1]
    here = (ids >= off) & (ids < off + held) & live[..., None]
    n_pos, n_pairs = ids.shape[0] * ids.shape[1], ids.size
    used, expert, at, we = expert_blocks(
        np.where(here, ids - off, 0).reshape(ids.shape[0], -1, 1),
        w.reshape(ids.shape[0], -1, 1), here.reshape(ids.shape[0], -1), held)
    at = np.where(at >= n_pairs, at - n_pairs + n_pos, at // k)
    return used, expert, at.astype(np.int32), we


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool, sink_on: bool):
    """The jitted pieces of one configuration at one precision."""
    import jax

    sizes = dict(sizes)
    return {
        **{kind: jax.jit(functools.partial(_attention, sizes, low, kind,
                                           sink_on), donate_argnums=0)
           for kind in KINDS},
        "normed": jax.jit(functools.partial(_normed, sizes)),
        "block": jax.jit(functools.partial(_swiglu_block, low),
                         donate_argnums=0),
        "scores": jax.jit(functools.partial(_scores, low)),
        "experts": jax.jit(functools.partial(_experts, low)),
        "head": jax.jit(functools.partial(_head, low))}


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           dense_blocks: int = 4, settle: int | None = None, flips=(),
           lengths=None, sink: bool = True, stats: dict | None = None):
    """``laguna.logits`` for this model (its arguments and results): float32
    logits of the full forward pass over ``tokens`` (B, T) at the positions
    ``keep``, of the experts HELD, and the router margins (B, T, expert
    layers) of the "highest" pass; "bfloat16" is the control one precision
    down, ``sink`` False the control without the sink. ``settle`` (a seed)
    draws an expert layer's choice BIAS again until every row's margin is
    over ``latent.SHARED_MARGIN``, and returns nothing. ``stats``, where
    given, receives ``sink_mass_share``: the mean share, over the sliding
    layers, their heads and the positions of the "highest" pass, of a
    head's softmax mass on its sink."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    key = tuple(sorted(sizes.items()))
    progs = {p: _programs(key, p == "bfloat16", bool(sink))
             for p in precisions}
    emb = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    xs = {p: emb + 0.0 for p in precisions}
    margins, on_sink = [], []
    seen = {k: 0 for k in KINDS}
    flip_of = np.zeros((sizes["n_layers"] - sizes["dense_layers"],
                        *tokens.shape), bool)
    for b, t, at in flips:
        flip_of[at, b, t] = True
    ends = (np.full(len(tokens), tokens.shape[1]) if lengths is None
            else np.asarray(lengths))
    live = np.arange(tokens.shape[1])[None, :] < ends[:, None]
    for layer, kind in enumerate(kinds_of(sizes)):
        mix, at = tree[kind], seen[kind]
        seen[kind] += 1
        w = {k: tuple(put(a) for a in _pair(mix[k], at)) for k in ATTN_KEYS}
        g_att = put(mix["rms_att"][at])
        sinks = put(mix["sink"][at]) if "sink" in mix else None
        for p in precisions:
            xs[p], share = progs[p][kind](xs[p], g_att, sinks, w)
            if p == "highest" and sizes[kind + "_sink"]:
                on_sink.append(share)
        del w
        dense = layer < sizes["dense_layers"]
        stack = tree["dense"] if dense else tree
        i = layer if dense else layer - sizes["dense_layers"]
        g_ffn = put(stack["rms_ffn"][i])
        hs = {p: progs[p]["normed"](xs[p], g_ffn) for p in precisions}
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
            scores = {p: np.asarray(progs[p]["scores"](hs[p], gate))
                      for p in precisions}
            attempt = 0
            while True:
                routed = {p: route(sizes, scores[p], stack["moe_bias"][i],
                                   flip_of[i], live) for p in precisions}
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
            held = tuple(tuple(put(a) for a in _pair(stack[k], i))
                         for k in ("moe_w1", "moe_w2", "moe_w3"))
            for p in precisions:
                ids, w, _ = routed[p]
                xs[p] = progs[p]["experts"](xs[p], hs[p], *(
                    put(a) for a in held_blocks(sizes, ids, w, live)), *held)
            del held
        # a layer at a time ON THE DEVICE too (the loop would otherwise run
        # ahead and park every layer's weights there)
        jax.block_until_ready(list(xs.values()))
    if stats is not None and on_sink:
        stats["sink_mass_share"] = float(np.mean([float(a) for a in on_sink]))
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
    """``laguna.settle_shared_positions`` on this reference: an expert
    layer's choice bias is drawn again (from the attempt's number, so the
    seed still fixes the tree) until the positions every prompt opens with
    choose with a margin over ``latent.SHARED_MARGIN``."""
    logits(tree, sizes, np.asarray([list(shared_tokens)]), settle=seed)


# -- bytes a step must move, from shapes: PUBLISHED bytes ------------------

def _q40_bytes(shapes) -> int:
    return sum(d * n for _, (d, n) in shapes) // costs.Q40_BLOCK \
        * costs.Q40_BLOCK_BYTES


def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE routed expert's three leaves (14,155,776)."""
    return _q40_bytes(ffn_shapes(sizes["dim"], sizes["hidden_dim"]))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: each layer's four attention leaves (its kind's), the leading
    layers' dense FFN, the classifier."""
    s = sizes
    return (sum(_q40_bytes(attn_shapes(s, k)) for k in kinds_of(s))
            + s["dense_layers"] * _q40_bytes(
                ffn_shapes(s["dim"], s["dense_hidden"]))
            + _q40_bytes([("wcls", (s["vocab_size"], s["dim"]))]))


def kv_position_bytes(sizes: dict, kind: str) -> int:
    """K and V of one position in ONE layer of ``kind``, float32, as
    PUBLISHED: KV heads x (192 + 128) x 4 (5,120 B full, 10,240 B sliding).
    The cache holds a K head in 256 lanes (``kv_held_bytes``); a roofline
    share is reckoned on this number, so the padding reads as lost share."""
    return sizes[kind + "_kv_heads"] * (
        sizes["head_size"] + sizes["v_head_size"]) * 4


def kv_held_bytes(sizes: dict, kind: str) -> int:
    """``kv_position_bytes`` as the cache HOLDS a position: a head wider
    than a 128-lane tile in whole tiles (the program's
    ``models/spec.cache_lanes``, restated: 6,144 B full, 12,288 B sliding)."""
    lanes = lambda h: h if h <= 128 else -(-h // 128) * 128  # noqa: E731
    return sizes[kind + "_kv_heads"] * (
        lanes(sizes["head_size"]) + lanes(sizes["v_head_size"])) * 4


def ring_step_bytes(sizes: dict, positions: float) -> float:
    """Published bytes of window ring a decode step must read ONCE:
    ``positions`` (min(pos + 1, window) summed over the rows) of K and V, in
    every sliding layer."""
    return positions * kv_position_bytes(sizes, "sliding") * kinds_of(
        sizes).count("sliding")


def full_step_bytes(sizes: dict, positions: float) -> float:
    """Published bytes of the full layers' pages a decode step must read
    ONCE: ``positions`` (pos + 1 summed over the rows) of K and V, in every
    full layer."""
    return positions * kv_position_bytes(sizes, "full") * kinds_of(
        sizes).count("full")


# -- what a device trace shows -----------------------------------------------
# ``laguna.step_kernel_seconds`` finds the kernels by name (they are the same
# kernels). A layer's sub-blocks by POSITION among a program run's dense Q40
# calls, which come in a fixed order: ``wqkv`` and ``wo`` in every layer,
# then a DENSE layer's ``w13`` and ``w2`` (an expert layer has no dense call
# of its own: there is no shared expert), and the classifier's one at the end
# of a decode step (an admission chunk has none).

def block_seconds(trace, sizes: dict, device: str | None = None) -> dict:
    """``laguna.block_seconds`` for these layers: self seconds, over every
    program run of the traced window on ``device`` (default: the first)
    that is a forward of this model (2 dense Q40 calls an expert layer and
    4 a dense one, and the classifier's where it is a decode step), of the
    sliding layers' mixers ("sliding") and the full layers' ("full"), each
    from a layer's ``wqkv`` to its ``wo``, both included, and of the expert
    sub-blocks ("moe": from the op after ``wo`` to the next layer's first
    call, or to the classifier's, or to the run's last op)."""
    from . import reduce_trace as rt

    out = {"sliding": 0.0, "full": 0.0, "moe": 0.0}
    if not trace.devices:
        return out
    kinds = kinds_of(sizes)
    k = sizes["dense_layers"]
    first = [4 * min(i, k) + 2 * max(i - k, 0) for i in range(len(kinds) + 1)]
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
        if not moe or len(dense) not in (first[-1], first[-1] + 1):
            continue
        for layer, kind in enumerate(kinds):
            lo, hi = dense[first[layer]], dense[first[layer] + 1]
            nxt = (dense[first[layer + 1]] if first[layer + 1] < len(dense)
                   else len(inside))
            out[kind] += sum(selfs[i] for i in work if lo <= i <= hi)
            if any(hi < m < nxt for m in moe):
                out["moe"] += sum(selfs[i] for i in work if hi < i < nxt)
    return {k: v / 1e9 for k, v in out.items()}
