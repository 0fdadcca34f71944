"""A mixer-kinds expert configuration (Laguna-XS.2: window and full
grouped-query attention layers, each kind with a head count and a RoPE of
its own, a per-head output gate, one dense layer before expert layers with
every one of a layer's 256 experts held and a shared one) for the drivers:
its sizes and ``TransformerSpec`` from the configuration file, its seeded
codec tree, the benchmark's own copy of the plain float32 reference, and the
bytes a step must move. What ``harness/latent.py``, ``weights.py``,
``reference.py`` and ``costs.py`` have that applies (the value recipe, the
dequantizer, the blocked SwiGLU, the head, the margin rule, Q40 block bytes)
is imported, not copied.

The layers (``distributed_llama_tpu/models/reference_laguna.py`` states them
in full), layer l of kind k with H_k heads over ``n_kv`` KV heads of size d:

  attention   h = RMSNorm(x); q = Wq_k h, key = Wk h, v = Wv h; RoPE by
              kind on the first ``rotary`` dimensions of a head (interleaved
              pairs; YaRN frequencies and the attention factor on cos / sin
              where the kind states them); scores q . key / sqrt d, causal,
              a sliding layer over the last ``window`` positions; head j's
              output times sigmoid(Wg_k h)_j; x + Wo_k [o_1..o_H]
  dense FFN   w2(silu(w1 h) * w3 h), layer 0
  expert FFN  s = sigmoid(W_r h); the k largest; weights scale s / sum(s);
              sum_e w_e E_e(h) plus the shared expert
"""

from __future__ import annotations

import bisect
import concurrent.futures
import functools
import math
import os

import numpy as np

from . import costs, weights
from .hyper import REVERSAL_EPSILON   # one epsilon for both reversal rules
from .latent import (MARGIN_EPSILON, SHARED_MARGIN, _head, _normed, _pair,
                     _rmsnorm, _swiglu_block, ffn_shapes)
from .reference import _dequant

__all__ = ["MARGIN_EPSILON", "REVERSAL_EPSILON", "strict_positions"]


def strict_positions(margins_row: np.ndarray) -> int:
    """How many leading positions of a request are compared strictly:
    those before its first router margin (T, expert layers) under
    ``MARGIN_EPSILON``."""
    low = np.nonzero(np.asarray(margins_row).min(axis=-1)
                     < MARGIN_EPSILON)[0]
    return int(low[0]) if low.size else int(len(margins_row))

KINDS = ("full", "sliding")
ATTN_KEYS = ("wq", "wk", "wv", "wo")
RING_KERNEL = "hm_attn_rows_decode"
PAGED_KERNEL = "hm_attn_paged_decode"
SLOT_KERNEL = "moe_q40_slots"
MOE_KERNEL_PREFIX = "moe_q40"
QUERY_BLOCK = 1024    # queries the reference's attention scores at a time
BLOCK_ROWS = 64       # (position, expert) pairs an expert's pass takes at a time


def kinds_of(config_or_sizes: dict) -> tuple:
    return tuple("full" if t == "full_attention" else "sliding"
                 for t in config_or_sizes["layer_types"])


def sizes_of(config: dict) -> dict:
    """Everything the spec, the tree and the counts need, flat."""
    rp = config["rope_parameters"]
    full, slide = rp["full_attention"], rp["sliding_attention"]
    kinds = kinds_of(config)
    heads = dict(zip(kinds, config["num_attention_heads_per_layer"]))
    mlp = config["mlp_layer_types"]
    hd = config["head_dim"]
    return {
        "dim": config["hidden_size"],
        "hidden_dim": config["moe_intermediate_size"],
        "n_layers": config["num_hidden_layers"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_size": hd,
        "vocab_size": config["vocab_size"],
        "seq_len": config["max_position_embeddings"],
        "layer_types": tuple(config["layer_types"]),
        "window": config["sliding_window"],
        "full_heads": heads["full"], "sliding_heads": heads["sliding"],
        "full_theta": float(full["rope_theta"]),
        "sliding_theta": float(slide["rope_theta"]),
        "full_rotary": int(round(hd * full["partial_rotary_factor"])),
        "sliding_rotary": int(round(hd * slide["partial_rotary_factor"])),
        "yarn_factor": float(full["factor"]),
        "yarn_original": int(full["original_max_position_embeddings"]),
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "attention_factor": float(full["attention_factor"]),
        "gate": bool(config["gating"]),
        "n_experts": config["num_experts"],
        "n_active_experts": config["num_experts_per_tok"],
        "dense_layers": len(mlp) - sum(m == "sparse" for m in mlp),
        "dense_hidden": config["intermediate_size"],
        "shared": (config["shared_expert_intermediate_size"]
                   // config["moe_intermediate_size"]),
        "route_scale": float(config["moe_routed_scaling_factor"]),
        "norm_eps": float(config["rms_norm_eps"]),
    }


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    if config.get("model_type") != "laguna":
        raise ValueError("harness/laguna.py runs model_type laguna")
    if (config.get("weights"), config.get("buffers"),
            config.get("kv_cache")) != ("q40", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers "
                         "and float32 rings and pages")
    n = config["num_hidden_layers"]
    if not (len(config["layer_types"]) == len(config["mlp_layer_types"])
            == len(config["num_attention_heads_per_layer"]) == n):
        raise ValueError("layer_types, mlp_layer_types and "
                         "num_attention_heads_per_layer: one entry a layer")
    if config.get("attention_bias") or config.get("tie_word_embeddings") \
            or config.get("moe_apply_router_weight_on_input") \
            or config["rope_parameters"]["full_attention"].get(
                "rope_type") != "yarn":
        raise ValueError("no attention bias, no tied embedding, router "
                         "weights on the output, YaRN on the full layers")
    m = 0.1 * math.log(config["rope_parameters"]["full_attention"]["factor"]
                       ) + 1.0
    if abs(config["rope_parameters"]["full_attention"]["attention_factor"]
           - m) > 1e-6:
        raise ValueError("the attention factor must be 0.1 ln(factor) + 1 "
                         "(the program states it through YaRN's mscale 1)")


def program_spec(sizes: dict):
    """The program's spec. A program without the record stops HERE (an
    ``ImportError``), before any device is touched."""
    from distributed_llama_tpu.models.spec import (ExpertLayout, MixerKind,
                                                   MixerKinds, RopeScaling,
                                                   Router, TransformerSpec)
    from distributed_llama_tpu.ops.quants import FloatType

    s, hd = sizes, sizes["head_size"]
    rot = lambda r: 0 if r == hd else r       # noqa: E731
    return TransformerSpec(
        dim=s["dim"], hidden_dim=s["hidden_dim"], n_layers=s["n_layers"],
        n_heads=s["full_heads"], n_kv_heads=s["n_kv_heads"],
        vocab_size=s["vocab_size"], seq_len=s["seq_len"],
        weights_float_type=FloatType.Q40, buffer_float_type=FloatType.F32,
        n_experts=s["n_experts"], n_active_experts=s["n_active_experts"],
        norm_eps=s["norm_eps"],
        layout=ExpertLayout(s["dense_layers"], s["dense_hidden"],
                            s["shared"]),
        router=Router("sigmoid", 1, 1, True, s["route_scale"]),
        mixers=MixerKinds(
            kinds_of(s), s["window"], hd,
            MixerKind(s["full_heads"], s["full_theta"],
                      rot(s["full_rotary"]),
                      RopeScaling(s["yarn_factor"], s["yarn_original"],
                                  s["yarn_beta_fast"], s["yarn_beta_slow"],
                                  1.0, 0.0)),
            MixerKind(s["sliding_heads"], s["sliding_theta"],
                      rot(s["sliding_rotary"])), s["gate"]))


def attn_shapes(sizes: dict, kind: str) -> list:
    s = sizes
    q, kv = s[kind + "_heads"] * s["head_size"], s["n_kv_heads"] * s[
        "head_size"]
    return [("wq", (q, s["dim"])), ("wk", (kv, s["dim"])),
            ("wv", (kv, s["dim"])), ("wo", (s["dim"], q))]


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of the spec: the mixers a stack a kind under
    ``"full"`` / ``"sliding"``, the leading dense layers' FFNs under
    ``"dense"``, the expert layers' FFNs at the top level; every leaf filled
    per (tensor, layer[, expert]) so that the seed alone fixes it. Q40
    leaves by ``weights._fill_q40``'s recipe (value std 1 / sqrt(n)); gains
    1 +- 0.05; router and head-gate rows N(0, 1/sqrt(dim))."""
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
        if s["gate"]:
            dense(tree[kind], "w_hgate", base + 20,
                  (depth, s[kind + "_heads"], dim), 0.0)
    dense(tree["dense"], "rms_ffn", 100, (k, dim), 1.0)
    dense(tree, "rms_ffn", 200, (n_exp, dim), 1.0)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["dense_hidden"])):
        q40(tree["dense"], name, 120 + i, (k,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(
            dim, s["shared"] * s["hidden_dim"], "sh_")):
        q40(tree, name, 220 + i, (n_exp,), d, n)
    for i, (name, (d, n)) in enumerate(ffn_shapes(dim, s["hidden_dim"],
                                                  "moe_")):
        q40(tree, name, 230 + i, (n_exp, s["n_experts"]), d, n)
    dense(tree, "moe_gate", 240, (n_exp, s["n_experts"], dim), 0.0)
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
    tree["wcls"].d16[weights.BOS] = 0     # logit exactly 0: never the argmax
    scale = np.float32(1.0 / np.sqrt(dim))
    tree["moe_gate"] *= scale
    for kind in KINDS:
        if s["gate"]:
            tree[kind]["w_hgate"] *= scale
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device, inside a layer one tensor group at a
# time: the attention block (a kind's four leaves, 117 or 151 MB of float32
# at the published widths, its scores one KV group and ``QUERY_BLOCK``
# queries at a time), the dense FFN in blocks of its hidden width, the
# router's top-k on the host (``route``), ONE expert at a time on the
# positions that chose it, ``BLOCK_ROWS`` of them at a time (``_experts``:
# 12.6 MB of float32 each, never the 3.2 GB stack; the first form put each
# expert's leaves on the device by themselves, 3,840 a pass, and the check
# took eleven minutes; the second ran each expert on every position), the
# classifier in blocks of the vocabulary. Every product goes through
# ``latent._ein``: float32 at HIGHEST, or with ``low`` both operands rounded
# to bfloat16 first: the control that must FAIL.

def _ein(low, subscripts, a, b):
    """``latent._ein`` with the control's rounding written as
    ``jax.lax.reduce_precision`` (operands rounded to bfloat16's 8 bits of
    mantissa, the product and the sum in float32: what one bf16 pass
    computes): a dot of bfloat16 arrays inside a ``lax.map`` is refused by
    the CPU's runtime, and a cast there and back is folded away by the
    chip's compiler (the hybrid harness found both)."""
    import jax
    import jax.numpy as jnp

    if low:
        a, b = (jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
                for x in (a, b))
    return jnp.einsum(subscripts, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rope_table(sizes: dict, kind: str):
    """(frequencies (rotary / 2,), cos / sin factor) as published: pair p's
    f_p = theta^(-2p / rotary); a full layer's blended f_p / factor * (1 -
    r_p) + f_p * r_p with r_p = 1 - clip((p - low) / (high - low), 0, 1),
    low and high the correction range for beta_fast and beta_slow rotations
    over the original positions, and cos / sin times ``attention_factor``."""
    s, dim, theta = sizes, sizes[kind + "_rotary"], sizes[kind + "_theta"]
    f = theta ** (-np.arange(0, dim, 2) / dim)
    if kind != "full":
        return f.astype(np.float32), 1.0

    def edge(rotations):
        return dim * math.log(s["yarn_original"] / (rotations * 2 * math.pi)
                              ) / (2 * math.log(theta))

    low = max(math.floor(edge(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(edge(s["yarn_beta_slow"])), dim - 1)
    r = 1 - np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return ((f / s["yarn_factor"] * (1 - r) + f * r).astype(np.float32),
            s["attention_factor"])


def _rope(jnp, x, freq, factor):
    """x (B, T, heads, d) at positions 0..T-1: the leading 2 len(freq)
    dimensions of a head in interleaved pairs, the rest as they are."""
    t, rot = x.shape[1], 2 * len(freq)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    ang = ang.reshape(1, t, 1, -1)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x[..., :rot].reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                       axis=-1).reshape(*x.shape[:-1], rot)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _attention(sizes, low, kind, x, rms_att, w_hgate, w):
    import jax
    import jax.numpy as jnp

    s, eps = sizes, sizes["norm_eps"]
    heads, n_kv, d = s[kind + "_heads"], s["n_kv_heads"], s["head_size"]
    B, T, _ = x.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    freq, factor = rope_table(sizes, kind)
    h = _rmsnorm(jnp, x, rms_att, eps)
    q = _rope(jnp, mm(wf["wq"], h).reshape(B, T, heads, d), freq, factor)
    k = _rope(jnp, mm(wf["wk"], h).reshape(B, T, n_kv, d), freq, factor)
    v = mm(wf["wv"], h).reshape(B, T, n_kv, d)
    qb = T if T <= QUERY_BLOCK else math.gcd(T, QUERY_BLOCK)
    pos = jnp.arange(T)

    def group(block):
        # one KV group's heads and ``qb`` queries at a time: a (B, H, T, T)
        # float32 score plane is 4.4 GB a row at the window's longest
        # request; a head's numbers do not depend on how they are blocked
        qg, kg, vg = block              # (B, T, m, d), (B, T, d), (B, T, d)

        def queries(qpart):
            qq, at = qpart              # (B, qb, m, d), (qb,)
            back = at[:, None] - pos[None, :]
            see = back >= 0
            if kind == "sliding":
                see = see & (back < s["window"])
            sc = ein("btmd,bsd->bmts", qq, kg) / math.sqrt(d)
            sc = jnp.where(see, sc, -jnp.inf)
            return ein("bmts,bsd->btmd", jax.nn.softmax(sc, axis=-1), vg)

        parts = (jnp.moveaxis(qg.reshape(B, T // qb, qb, *qg.shape[2:]),
                              1, 0), pos.reshape(T // qb, qb))
        out = jax.lax.map(queries, parts)         # (T / qb, B, qb, m, d)
        return jnp.moveaxis(out, 0, 1).reshape(B, T, *qg.shape[2:])

    qg = jnp.moveaxis(q.reshape(B, T, n_kv, heads // n_kv, d), 2, 0)
    ao = jax.lax.map(group, (qg, jnp.moveaxis(k, 2, 0),
                             jnp.moveaxis(v, 2, 0)))
    ao = jnp.moveaxis(ao, 0, 2).reshape(B, T, heads, d)
    if s["gate"]:
        g = jax.nn.sigmoid(ein("hn,btn->bth", w_hgate, h))
        ao = ao * g[..., None]
    return x + mm(wf["wo"], ao.reshape(B, T, -1))


def _scores(low, h, gate):
    """Every expert's router score sigmoid(W_r h): (B, T, E)."""
    import jax

    return jax.nn.sigmoid(_ein(low, "ed,btd->bte", gate, h))


def route(sizes, scores, flip, live):
    """The router's choice from ``scores`` (B, T, E), on the host in numpy:
    the chosen experts' ids and weights (B, T, k; weights scale s / sum(s),
    0 at a position that is not ``live``) and the margin (B, T) of the k-th
    chosen score over the best one left out. Equal scores go to the lower
    index, as ``jax.lax.top_k`` has it. ``flip`` (B, T) bool: where set,
    that decision is REVERSED (the best one left out takes the k-th
    chosen's place): the other of the two choices float32 cannot tell apart
    where the margin is a few ulps (``with_reversals`` says when the check
    asks for it). ``live`` (B, T) bool: a position past its row's own
    length (padding, which nothing compared reads: every layer is causal)
    weighs no expert, so that no expert's pass spends rows on it."""
    k = sizes["n_active_experts"]
    order = np.argsort(-scores, axis=-1, kind="stable")[..., :k + 1]
    top = np.take_along_axis(scores, order, axis=-1)
    margin = top[..., k - 1] - top[..., k]
    ids = order[..., :k].copy()
    ids[..., k - 1] = np.where(flip, order[..., k], order[..., k - 1])
    w = np.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + np.float32(1e-20)) * np.float32(
        sizes["route_scale"])
    return ids, np.where(live[..., None], w, np.float32(0.0)), margin


def expert_blocks(ids, w, live, n_experts: int, rows: int = BLOCK_ROWS):
    """The (position, expert) pairs of the live positions as ``_experts``
    takes them: in blocks of ``rows`` pairs of ONE expert each, experts in
    order and an expert's positions in order, so that a position adds its
    experts in ascending order, as a sum over the experts has them. A count
    of blocks that follows from the shape alone (pairs // rows + experts:
    an expert's last block may be part full), so one lot shape is one
    program: how many blocks are ``used``, ``expert`` (blocks,), ``at``
    (blocks, rows) the position of each row, ``we`` (blocks, rows) its
    weight. A row that holds no pair points at a row of zeros of its own
    past the last position (``n + its place in the block``: no two rows of
    a block name the same position) and weighs 0; a block past the last
    one used is all such rows, and ``_experts`` stops before it."""
    n, k = ids.shape[0] * ids.shape[1], ids.shape[-1]
    ids, w = ids.reshape(-1), w.reshape(-1)
    pairs = np.nonzero(np.repeat(live.reshape(-1), k))[0]
    pairs = pairs[np.argsort(ids[pairs], kind="stable")]
    of = ids[pairs]
    counts = np.bincount(of, minlength=n_experts)
    blocks = -(-counts // rows)
    first = np.concatenate([[0], np.cumsum(blocks)])
    rank = np.arange(len(pairs)) - (np.cumsum(counts) - counts)[of]
    blk, row = first[of] + rank // rows, rank % rows
    total = n * k // rows + n_experts
    expert = np.zeros(total, np.int32)
    expert[:first[-1]] = np.repeat(np.arange(n_experts), blocks)
    at = np.tile(n + np.arange(rows, dtype=np.int32), (total, 1))
    we = np.zeros((total, rows), np.float32)
    at[blk, row], we[blk, row] = pairs // k, w[pairs]
    return np.int32(first[-1]), expert, at, we


def _experts(low, x, h, used, expert, at, we, w1, w2, w3):
    """x + sum_e w_e E_e(h) over a layer's chosen (position, expert) pairs,
    a block of ``expert_blocks`` at a time inside the program (a loop over
    the blocks ``used``; the codec stacks (E, d, nb, 16) / (E, d, nb) lie
    on the device as the file holds them, 0.45 GB a layer, and ONE expert's
    12.6 MB of float32 lives at a time, never the stack's 3.2 GB): the
    block's expert on the block's positions and on no others. Which pairs there are was settled on the
    host (``route``), so the program has no sort and no shape that follows
    from the data. The first form ran every expert on EVERY position (32
    times the products the sum needs, 8 of 256 being chosen) and the check
    took 200 to 310 s of a run that may last 360; the second took each
    expert's rows by a sort on the device, at a capacity counted from the
    data, and two runs of one seed hung in it: PERF.md section 6."""
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
        out = mm(b, jax.nn.silu(mm(a, hr)) * mm(c, hr))[0]
        return acc.at[to].add(weight[:, None] * out, unique_indices=True)

    acc = jax.lax.fori_loop(0, used, body, jnp.concatenate(
        [x.reshape(-1, dim), zeros]))
    return acc[:-rows].reshape(x.shape)


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool):
    """The jitted pieces of one configuration at one precision."""
    import jax

    sizes = dict(sizes)
    return {
        **{kind: jax.jit(functools.partial(_attention, sizes, low, kind),
                         donate_argnums=0) for kind in KINDS},
        "normed": jax.jit(functools.partial(_normed, sizes)),
        "block": jax.jit(functools.partial(_swiglu_block, low),
                         donate_argnums=0),
        "scores": jax.jit(functools.partial(_scores, low)),
        "experts": jax.jit(functools.partial(_experts, low)),
        "head": jax.jit(functools.partial(_head, low))}


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           dense_blocks: int = 4, settle: int | None = None, flips=(),
           lengths=None):
    """Float32 logits of the full forward pass over ``tokens`` (B, T), every
    position reading those before it: ``{precision: (B, K, vocab)}`` at the
    positions ``keep`` ((B, K), each row's own; default all) and the router
    margins (B, T, expert layers) of the "highest" pass. "bfloat16" is the
    control one precision down. ``flips`` ((row, position, expert layer),
    ...) are router decisions taken the other way (``_route``). ``settle``
    (a seed) draws an expert layer's router rows again
    (``settle_shared_positions``) until every row's margin is over
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
    seen = {k: 0 for k in KINDS}
    n_experts = sizes["n_experts"]
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
        hgate = put(mix["w_hgate"][at]) if sizes["gate"] else None
        for p in precisions:
            xs[p] = progs[p][kind](xs[p], g_att, hgate, w)
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
            attempt = 0
            while True:
                gate = put(stack["moe_gate"][i])
                routed = {p: route(sizes, np.asarray(progs[p]["scores"](
                    hs[p], gate)), flip_of[i], live) for p in precisions}
                if settle is None or float(routed["highest"][2].min()) \
                        >= SHARED_MARGIN:
                    break
                attempt += 1
                stack["moe_gate"][i] = np.float32(
                    1.0 / np.sqrt(sizes["dim"])) * np.random.default_rng(
                        [settle, 240, i, attempt]).standard_normal(
                            (n_experts, sizes["dim"]), dtype=np.float32)
            if "highest" in routed:
                margins.append(routed["highest"][2])
            shared = tuple(tuple(put(a) for a in _pair(stack[k], i))
                           for k in ("sh_w1", "sh_w2", "sh_w3"))
            held = tuple(tuple(put(a) for a in _pair(stack[k], i))
                         for k in ("moe_w1", "moe_w2", "moe_w3"))
            for p in precisions:
                ids, w, _ = routed[p]
                xs[p] = progs[p]["block"](xs[p], hs[p], None, *shared)
                xs[p] = progs[p]["experts"](xs[p], hs[p], *(
                    put(a) for a in expert_blocks(ids, w, live, n_experts)),
                    *held)
            del held
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


MAX_REVERSALS = 6     # passes of one lot in which reversals are tried


def decisions_to_reverse(margins_row: np.ndarray, upto: int, taken=()):
    """The router decision of one request most likely to explain its first
    disagreement at position ``upto``: (position, expert layer, margin) of
    the LATEST position <= ``upto`` that has a margin under
    ``REVERSAL_EPSILON`` not in ``taken`` (its smallest there); None where
    there is none. The latest, not the smallest of the row: a decision
    taken the other way moves its own position's logits most and a position
    thousands back hardly at all (full attention gives it one part in its
    depth, a window layer nothing), and a 4,144-position row has some
    twenty margins under the epsilon, most of them far from where the
    streams part (the smallest-first rule of ``harness/hyper.py`` reversed
    two such decisions 1,400 and 2,900 positions back in one run of this
    cell and cured nothing: PERF.md section 6)."""
    m = np.array(margins_row[:upto + 1], np.float64)
    for t, layer in taken:
        if t <= upto:
            m[t, layer] = np.inf
    near = np.nonzero(m.min(axis=-1) < REVERSAL_EPSILON)[0]
    if not near.size:
        return None
    t = int(near[-1])
    layer = int(m[t].argmin())
    return t, layer, float(m[t, layer])


def with_reversals(tree: dict, sizes: dict, tokens, keep, want, margins,
                   first_bad, lengths=None):
    """``want`` (B, K, vocab) and ``margins`` (B, T, expert layers) of
    ``logits(tokens, keep=keep)``'s "highest" pass, rewritten IN PLACE for
    the rows that the program's output disagrees with and reversed router
    decisions explain (``harness/hyper.with_reversals``'s rule, on this
    reference). ``first_bad(b, want_b)`` gives the position in row b's
    sequence of its first disagreement with ``want_b`` (K, vocab), or None.
    Such a row's reference is run again with the decision reversed that is
    most likely to have gone the other way in the program
    (``decisions_to_reverse``); the reversal stands only where it cures the
    row or moves its first disagreement later, and the next is then looked
    for on the NEW pass's margins. Both choices are the model's, to
    float32; a fault of a kernel, a ring or a page table is not cured by
    reversing a decision. Returns the reversals that stood: [(row,
    position, expert layer, margin), ...]."""
    flips, tried, stood = [], {}, []
    for _ in range(MAX_REVERSALS):
        state = {b: first_bad(b, want[b]) for b in range(len(want))}
        trial = {}
        for b, at in state.items():
            found = None if at is None else decisions_to_reverse(
                margins[b], at, tried.get(b, ()))
            if found is not None:
                trial[b] = found
                tried.setdefault(b, []).append(found[:2])
        if not trial:
            break
        got, margins2 = logits(
            tree, sizes, tokens, keep=keep, lengths=lengths, flips=flips + [
                (b, t, layer) for b, (t, layer, _) in trial.items()])
        for b, (t, layer, m) in trial.items():
            after = first_bad(b, got["highest"][b])    # rows are independent
            if after is None or after > state[b]:
                flips.append((b, t, layer))
                want[b], margins[b] = got["highest"][b], margins2[b]
                stood.append((b, t, layer, m))
    return stood


def settle_shared_positions(tree: dict, sizes: dict, shared_tokens,
                            seed: int) -> None:
    """Every prompt opens with the same tokens (BOS and the tokenizer's
    leading space): a near-tie of the router THERE would be every request's
    (ROADMAP B1). Part of the seeded tree's recipe, then: an expert layer's
    router rows are drawn again (from the attempt's number, so the seed
    still fixes the tree) until those positions choose with a margin over
    ``latent.SHARED_MARGIN``, layer by layer through the reference."""
    logits(tree, sizes, np.asarray([list(shared_tokens)]), settle=seed)


# -- bytes a step must move, from shapes ---------------------------------------

def _q40_bytes(shapes) -> int:
    return sum(d * n for _, (d, n) in shapes) // costs.Q40_BLOCK \
        * costs.Q40_BLOCK_BYTES


def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE routed expert's three leaves (1,769,472)."""
    return _q40_bytes(ffn_shapes(sizes["dim"], sizes["hidden_dim"]))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: each layer's four attention leaves (its kind's), the leading
    layers' dense FFN, the expert layers' shared expert, the classifier."""
    s = sizes
    kinds = kinds_of(s)
    n_exp = s["n_layers"] - s["dense_layers"]
    return (sum(_q40_bytes(attn_shapes(s, k)) for k in kinds)
            + s["dense_layers"] * _q40_bytes(
                ffn_shapes(s["dim"], s["dense_hidden"]))
            + n_exp * _q40_bytes(ffn_shapes(
                s["dim"], s["shared"] * s["hidden_dim"]))
            + _q40_bytes([("wcls", (s["vocab_size"], s["dim"]))]))


def kv_position_bytes(sizes: dict) -> int:
    """K and V of one position in ONE layer, float32 (8,192 B)."""
    return 2 * sizes["n_kv_heads"] * sizes["head_size"] * 4


def ring_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of window ring a decode step must read ONCE: ``positions``
    (min(pos + 1, window) summed over the rows) of K and V, in every
    sliding layer."""
    return positions * kv_position_bytes(sizes) * kinds_of(sizes).count(
        "sliding")


def full_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of the full layers' pages a decode step must read ONCE:
    ``positions`` (pos + 1 summed over the rows) of K and V, in every full
    layer."""
    return positions * kv_position_bytes(sizes) * kinds_of(sizes).count(
        "full")


# -- what a device trace shows ---------------------------------------------------
# The reducer's ops carry the instruction's name and opcode only. Kernels are
# found by name. A layer's sub-blocks by POSITION among a program run's dense
# Q40 calls, which come in a fixed order, four a layer: wqkv, [the attention
# kernel or a chunk's XLA attention, the gate,] wo, then the FFN's two
# (dense: w13, w2; expert: after the expert kernel's calls, the shared
# expert's sh_w13, sh_w2), and the classifier's one at the end of a decode
# step (an admission chunk has none).

def _is(op, prefix: str) -> bool:
    return op.label == "custom-call" and op.name.lower().startswith(prefix)


def step_kernel_seconds(trace) -> list[dict]:
    """Per decode step of the traced window that ran the ring kernel
    (``reduce_trace.steps``): seconds in the ring kernel, in the paged
    kernel, in the slot kernel and in the dense Q40 calls."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        acc = {"ring": 0.0, "paged": 0.0, "slots": 0.0, "dense": 0.0}
        for o, s in zip(ops, rt.self_times(ops)):
            if _is(o, RING_KERNEL):
                acc["ring"] += s / 1e9
            elif _is(o, PAGED_KERNEL):
                acc["paged"] += s / 1e9
            elif _is(o, SLOT_KERNEL):
                acc["slots"] += s / 1e9
            elif rt.classify(o) == "q40" and not _is(o, MOE_KERNEL_PREFIX):
                acc["dense"] += s / 1e9
        if acc["ring"] > 0:
            out.append(acc)
    return out


def block_seconds(trace, sizes: dict, device: str | None = None) -> dict:
    """Self seconds, over every program run of the traced window on
    ``device`` (default: the first) that is a forward of this model (4 L
    dense Q40 calls, and the classifier's where it is a decode step), of
    the sliding layers' mixers ("sliding") and the full layers' ("full"),
    each from a layer's first dense call (``wqkv``) to its second (``wo``),
    both included, and of the expert sub-blocks ("moe": from the op after
    ``wo`` to the next layer's first call, or to the run's last op, where
    the layer ran an expert kernel: FFN norm, router, slot building, routed
    and shared experts, combine, residual)."""
    from . import reduce_trace as rt

    out = {"sliding": 0.0, "full": 0.0, "moe": 0.0}
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
        if not moe or len(dense) not in (4 * len(kinds), 4 * len(kinds) + 1):
            continue
        for layer, kind in enumerate(kinds):
            lo, hi = dense[4 * layer], dense[4 * layer + 1]
            nxt = (dense[4 * layer + 4] if 4 * layer + 4 < len(dense)
                   else len(inside))
            out[kind] += sum(selfs[i] for i in work if lo <= i <= hi)
            if any(hi < m < nxt for m in moe):
                out["moe"] += sum(selfs[i] for i in work if hi < i < nxt)
    return {k: v / 1e9 for k, v in out.items()}
