"""A latent-attention expert configuration whose residual path is n parallel
streams (Xing4.0-29B-A4B: DeepSeek-V3's block, 64 routed experts held whole,
``hc_mult`` 4 streams mixed by per-token matrices, manifold-constrained
hyper-connections, arXiv:2512.24880) for the drivers: its sizes and
``TransformerSpec``, its seeded codec tree, the benchmark's own copy of the
plain float32 reference, the bytes and operations a step's residual path
must move, and where a device trace shows that path. ``harness/latent.py``
runs ``model_type`` deepseek_v3 only; what it has that applies (sizes, the
tree's latent and expert leaves, the reference's sub-layers, YaRN, the
router, the byte counts of experts, dense leaves and plane) is imported.

The residual path, per token, X in R^(n x C), for each sub-layer F of a
layer (attention; feed-forward), all float32
(``distributed_llama_tpu/models/reference_hyper.py`` states it in full):

  xhat  = vec(X) / sqrt(mean(vec(X)^2) + eps)
  z     = xhat @ Phi^T                       Phi (2 n + n^2, n C)
  H_pre = sigmoid(a_pre z[:n] + b_pre);  H_post = 2 sigmoid(a_post z[n:2n] + b_post)
  H_res = SK(clip(a_res mat(z[2n:]) + B_res, lo, hi)): exp, then ``iters``
          times each column over (its sum + hc_eps), each row likewise
  h = sum_i H_pre[i] X[i];  y = F(h);  X'[i] = sum_j H_res[i,j] X[j] + H_post[i] y

Entry: every stream the embedding. Exit: the streams' sum.
"""

from __future__ import annotations

import functools
import math
import re

import numpy as np

from . import latent, weights
from .latent import _dequant, _ein, _rmsnorm, _rope

SUBLAYERS = ("att", "ffn")
MARGIN_EPSILON = latent.MARGIN_EPSILON
strict_positions = latent.strict_positions
PRECISIONS = ("highest", "bfloat16", "projection_bfloat16")
""""highest": float32 products; "bfloat16": EVERY product's operands rounded
to bfloat16 (the control that must fail); "projection_bfloat16": only the
coefficient projection's (what the check does or does not guard of that one
product's precision: its reading is reported, PERF.md section 7)."""


def sizes_of(config: dict) -> dict:
    s = latent.sizes_of(config)
    s.update(streams=int(config["hc_mult"]),
             sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
             hc_eps=float(config["hc_eps"]),
             clamp_min=float(config["mhc_h_res_clamp_min"]),
             clamp_max=float(config["mhc_h_res_clamp_max"]))
    return s


def check_runnable(config: dict) -> None:
    if config.get("model_type") != "xing4_0":
        raise ValueError("harness/hyper.py runs model_type xing4_0")
    latent.check_runnable(dict(config, model_type="deepseek_v3"))
    if config.get("n_group") != 1 or config.get("topk_group") != 1:
        raise ValueError("the configuration's choice is over one group")
    if config.get("hc_mult", 0) < 2:
        raise ValueError("hc_mult: at least two streams")


def program_spec(sizes: dict):
    """The program's spec. A program without the residual path's record
    stops HERE (an ``ImportError``), before any device is touched."""
    import dataclasses

    from distributed_llama_tpu.models.spec import HyperConnections

    s = sizes
    return dataclasses.replace(
        latent.program_spec(sizes), hyper=HyperConnections(
            s["streams"], s["sinkhorn_iters"], s["hc_eps"], s["clamp_min"],
            s["clamp_max"]))


def coefficients(sizes: dict) -> int:
    n = sizes["streams"]
    return n * (2 + n)


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """``latent.codec_tree`` and the residual path's float32 leaves, a
    layer and sub-layer each from (seed, index, layer): ``hc_<sub>_phi``
    (2 n + n^2, n dim) ~ N(0, 1/sqrt(n dim)), so that a unit-RMS xhat
    projects to N(0, 1); gates 0.5; b_pre, b_post N(0, 1); B_res 4 x
    identity + N(0, 1)."""
    tree = latent.codec_tree(sizes, seed, threads)
    n, k, wide = sizes["streams"], coefficients(sizes), \
        sizes["streams"] * sizes["dim"]
    eye = 4.0 * np.eye(n, dtype=np.float32).reshape(-1)
    for base, dst, depth in ((300, tree["dense"], sizes["dense_layers"]),
                             (400, tree, sizes["n_layers"]
                              - sizes["dense_layers"])):
        for j, sub in enumerate(SUBLAYERS):
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
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# As ``latent.logits``: a layer at a time on one device, inside a layer one
# tensor group at a time. ``low`` = (every product, the projection alone).

def sinkhorn(jnp, logits, iters: int, eps: float):
    m = jnp.exp(logits)
    for _ in range(iters):        # a Python loop: columns, then rows
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def _coef(sizes, low, x, phi, gate, bias):
    """x (B, T, n, C) -> (H_pre (B, T, n), H_post, H_res (B, T, n, n))."""
    import jax
    import jax.numpy as jnp

    s, n = sizes, sizes["streams"]
    B, T = x.shape[:2]
    flat = x.reshape(B, T, -1)
    xhat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + s["norm_eps"])
    z = _ein(any(low), "ok,btk->bto", phi, xhat)
    pre = jax.nn.sigmoid(gate[0] * z[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(gate[1] * z[..., n:2 * n] + bias[n:2 * n])
    res = (gate[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(B, T, n, n)
    return pre, post, sinkhorn(
        jnp, jnp.clip(res, s["clamp_min"], s["clamp_max"]),
        s["sinkhorn_iters"], s["hc_eps"])


def _mix_in(low, x, pre):
    return _ein(low[0], "bti,btic->btc", pre, x)


def _mix_out(low, x, res, post, y):
    return (_ein(low[0], "btij,btjc->btic", res, x)
            + _ein(low[0], "bti,btc->btic", post, y))


def _attention(sizes, low, h_in, rms_att, rms_q_a, rms_kv_a, w):
    """``latent._attention`` without its residual: the sub-layer's output
    of input ``h_in`` (B, T, C), which it norms."""
    import jax
    import jax.numpy as jnp

    s, nh, eps = sizes, sizes["n_heads"], sizes["norm_eps"]
    B, T, _ = h_in.shape
    ein = functools.partial(_ein, low)
    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    freq, scale = latent.yarn(sizes)
    h = _rmsnorm(jnp, h_in, rms_att, eps)
    c_q = _rmsnorm(jnp, mm(wf["wq_a"], h), rms_q_a, eps)
    q = mm(wf["wq_b"], c_q).reshape(B, T, nh, -1)
    q_nope = q[..., :s["nope_dim"]]
    q_rope = _rope(jnp, q[..., s["nope_dim"]:], freq)
    kv = mm(wf["wkv_a"], h)
    c_kv = _rmsnorm(jnp, kv[..., :s["kv_rank"]], rms_kv_a, eps)
    k_rope = _rope(jnp, kv[..., s["kv_rank"]:], freq)
    kvb = mm(wf["wkv_b"], c_kv).reshape(B, T, nh, -1)
    k_nope, v = kvb[..., :s["nope_dim"]], kvb[..., s["nope_dim"]:]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]

    def heads(block):     # a block of heads at a time (latent._attention)
        qn, qr, kn, vv = block
        scores = (ein("bthd,bshd->bhts", qn, kn)
                  + ein("bthd,bsd->bhts", qr, k_rope)) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return ein("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), vv)

    hb = math.gcd(nh, latent.HEAD_BLOCK)
    split = lambda a: jnp.moveaxis(          # noqa: E731
        a.reshape(B, T, nh // hb, hb, a.shape[-1]), 2, 0)
    ao = jnp.moveaxis(jax.lax.map(heads, tuple(
        split(a) for a in (q_nope, q_rope, k_nope, v))), 0, 2)
    return mm(wf["wo"], ao.reshape(B, T, -1))


def _route(sizes, low, h, gate, bias, flip):
    """Each expert's weight (B, T, E; 0 where not chosen) and the margin (B,
    T) between the last expert chosen and the first not, on c = s + b over
    ONE group (``latent._route`` at n_group 1). ``flip`` (B, T) bool: where
    set, that decision is REVERSED (the first not chosen takes the last
    chosen's place): the other of the two choices float32 cannot tell apart
    where the margin is under ``REVERSAL_EPSILON`` (``decisions_to_reverse``
    says when the check asks for it)."""
    import jax
    import jax.numpy as jnp

    s, k = sizes, sizes["n_active_experts"]
    sc = jax.nn.sigmoid(_ein(low, "ed,btd->bte", gate, h))
    top, ids = jax.lax.top_k(sc + bias, k + 1)
    margin = top[..., k - 1] - top[..., k]
    last = jnp.where(flip, ids[..., k], ids[..., k - 1])
    ids = jnp.concatenate([ids[..., :k - 1], last[..., None]], axis=-1)
    chosen = (ids[..., None] == jnp.arange(s["n_experts"])).any(axis=-2)
    w = jnp.where(chosen, sc, 0.0)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * s["route_scale"], margin


def _fan_out(sizes, emb):
    import jax.numpy as jnp

    return jnp.repeat(emb[:, :, None, :], sizes["streams"], axis=2)


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: tuple):
    """The jitted pieces of one configuration at one precision: latent's
    (normed, block, route, head) and the residual path's."""
    import jax
    import jax.numpy as jnp

    out = dict(latent._programs(sizes, low[0]))
    sizes = dict(sizes)
    out.update(
        attention=jax.jit(functools.partial(_attention, sizes, low[0])),
        route=jax.jit(functools.partial(_route, sizes, low[0])),
        coef=jax.jit(functools.partial(_coef, sizes, low)),
        mix_in=jax.jit(functools.partial(_mix_in, low)),
        mix_out=jax.jit(functools.partial(_mix_out, low), donate_argnums=0),
        fan_out=jax.jit(functools.partial(_fan_out, sizes)),
        zeros=jax.jit(jnp.zeros_like),
        fold=jax.jit(lambda x: x.sum(axis=2)))
    return out


def _low(precision: str) -> tuple:
    return (precision == "bfloat16", precision == "projection_bfloat16")


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precisions=("highest",), keep=None, vocab_blocks: int = 4,
           dense_blocks: int = 4, settle: int | None = None, flips=()):
    """``latent.logits`` for this configuration (same arguments, same
    result): float32 logits of the full forward over ``tokens`` (B, T) at
    the positions ``keep``, ``{precision: (B, K, vocab)}`` for each of
    ``PRECISIONS`` asked for, and the "highest" pass's router margins (B, T,
    expert layers). ``flips`` ((row, position, expert layer), ...) are
    router decisions to REVERSE (``_route``)."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    key = tuple(sorted(sizes.items()))
    progs = {p: _programs(key, _low(p)) for p in precisions}
    emb = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    xs = {p: progs[p]["fan_out"](emb) for p in precisions}
    del emb
    margins: list = []
    held, off = sizes["held"], sizes["offset"]
    flip_of = np.zeros((sizes["n_layers"] - sizes["dense_layers"],
                        *tokens.shape), bool)
    for b, t, layer in flips:
        flip_of[layer, b, t] = True

    def around(stack, i, sub, fn):
        """xs <- X' around sub-layer ``fn`` (precision, h) -> y."""
        hc = [put(stack[f"hc_{sub}_{leaf}"][i])
              for leaf in ("phi", "gate", "bias")]
        coefs = {p: progs[p]["coef"](xs[p], *hc) for p in precisions}
        hs = {p: progs[p]["mix_in"](xs[p], coefs[p][0]) for p in precisions}
        ys = fn(hs)
        for p in precisions:
            pre, post, res = coefs[p]
            xs[p] = progs[p]["mix_out"](xs[p], res, post, ys[p])

    for layer in range(sizes["n_layers"]):
        dense = layer < sizes["dense_layers"]
        stack = tree["dense"] if dense else tree
        i = layer if dense else layer - sizes["dense_layers"]

        def attention(hs, stack=stack, i=i):
            w = {k: tuple(put(a) for a in latent._pair(stack[k], i))
                 for k in latent.ATTN_KEYS}
            gains = [put(stack[k][i])
                     for k in ("rms_att", "rms_q_a", "rms_kv_a")]
            return {p: progs[p]["attention"](hs[p], *gains, w)
                    for p in precisions}

        def dense_ffn(hs, stack=stack, i=i, dense_blocks=dense_blocks):
            g = put(stack["rms_ffn"][i])
            hn = {p: progs[p]["normed"](hs[p], g) for p in precisions}
            ys = {p: progs[p]["zeros"](hs[p]) for p in precisions}
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
                    ys[p] = progs[p]["block"](ys[p], hn[p], None, *blk)
            return ys

        def expert_ffn(hs, stack=stack, i=i):
            g = put(stack["rms_ffn"][i])
            hn = {p: progs[p]["normed"](hs[p], g) for p in precisions}
            gate, flip = put(stack["moe_gate"][i]), put(flip_of[i])
            attempt = 0
            while True:
                bias = put(stack["moe_bias"][i])
                routed = {p: progs[p]["route"](hn[p], gate, bias, flip)
                          for p in precisions}
                if settle is None or float(routed["highest"][1].min()) \
                        >= latent.SHARED_MARGIN:
                    break
                attempt += 1
                stack["moe_bias"][i] = np.float32(0.05) * np.random.default_rng(
                    [settle, 241, i, attempt]).standard_normal(
                        sizes["n_experts"], dtype=np.float32)
            if "highest" in routed:
                margins.append(routed["highest"][1])
            used = {p: np.asarray(routed[p][0][..., off:off + held].sum(
                axis=(0, 1)) != 0) for p in precisions}
            ys = {p: progs[p]["zeros"](hs[p]) for p in precisions}
            shared = tuple(tuple(put(a) for a in latent._pair(stack[k], i))
                           for k in ("sh_w1", "sh_w2", "sh_w3"))
            for p in precisions:
                ys[p] = progs[p]["block"](ys[p], hn[p], None, *shared)
            for e in range(held):
                if not any(used[p][e] for p in precisions):
                    continue
                blk = tuple(tuple(put(a)
                                  for a in latent._pair(stack[k], (i, e)))
                            for k in ("moe_w1", "moe_w2", "moe_w3"))
                for p in precisions:
                    if used[p][e]:
                        ys[p] = progs[p]["block"](
                            ys[p], hn[p], routed[p][0][..., off + e], *blk)
            return ys

        around(stack, i, "att", attention)
        around(stack, i, "ffn", dense_ffn if dense else expert_ffn)
        # a layer at a time ON THE DEVICE too (latent.logits)
        jax.block_until_ready(list(xs.values()))
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
    return out, (np.stack([np.asarray(m) for m in margins], axis=-1)
                 if margins else None)


REVERSAL_EPSILON = 2e-6
"""A router decision may be taken as reversed only at a margin under this:
a few float32 ulps of a score of order 1 (an ulp there is 1.2e-7), where the
program's float32 and the reference's cannot be told apart. The
reversals that stood in PR 39's chip runs lay at 6.0e-8 to 9.5e-7 (PERF.md
section 6): twice the largest, and a fifth of ``MARGIN_EPSILON``, under
which a position is merely not compared STRICTLY."""


def decisions_to_reverse(margins_row: np.ndarray, upto: int, taken=()):
    """The router decision of one request most likely to have gone the
    other way in the program: (position, expert layer, margin) of the
    smallest margin under ``REVERSAL_EPSILON`` among positions <= ``upto``
    (the first served position that fell short) not in ``taken``; None
    where there is none."""
    m = np.array(margins_row[:upto + 1], np.float64)
    for t, layer in taken:
        if t <= upto:
            m[t, layer] = np.inf
    t, layer = np.unravel_index(int(m.argmin()), m.shape)
    if m[t, layer] >= REVERSAL_EPSILON:
        return None
    return int(t), int(layer), float(m[t, layer])


MAX_REVERSALS = 3    # rows of one batch given reversals, and passes
MAX_REVERSALS_A_RUN = 4
"""Reversals that may stand in one run's check (``serve_hyper.check_streams``
fails a run with more): 13 seeds on the chip showed six, at most two in a
run (PERF.md section 6); more than twice that is drift, not rounding."""


def with_reversals(tree: dict, sizes: dict, tokens, keep, want, margins,
                   first_bad):
    """``want`` (B, K, vocab) and ``margins`` (B, T, expert layers) of
    ``logits(tokens, keep=keep)``'s "highest" pass, rewritten IN PLACE for
    the rows that the program's output disagrees with and ONE reversed
    router decision explains. ``first_bad(b, want_b)`` gives the position in
    row b's sequence of its first disagreement with ``want_b`` (K, vocab),
    or None. Such a row's reference is run again with the decision reversed
    that is most likely to have gone the other way in the program
    (``decisions_to_reverse``: the smallest margin under
    ``REVERSAL_EPSILON`` at or before that position); the reversal stands
    only where it cures the row or moves its first disagreement later, and
    the next is then looked for on the NEW pass's margins. Both choices are
    the model's, to float32; a fault of a kernel or of a page table is not
    cured by reversing a decision. At most ``MAX_REVERSALS`` rows are tried, in as
    many passes. Returns the reversals that stood: [(row, position, expert
    layer, margin), ...]."""
    flips, tried, stood = [], {}, []
    for _ in range(MAX_REVERSALS + 1):
        state = {b: first_bad(b, want[b]) for b in range(len(want))}
        trial = {}
        for b, at in state.items():
            found = None if at is None else decisions_to_reverse(
                margins[b], at, tried.get(b, ()))
            if found is not None:
                trial[b] = found
                tried.setdefault(b, []).append(found[:2])
        if not trial or len(tried) > MAX_REVERSALS:
            break
        got, margins2 = logits(tree, sizes, tokens, keep=keep, flips=flips + [
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
    """``latent.settle_shared_positions`` through this reference."""
    logits(tree, sizes, np.asarray([list(shared_tokens)]), settle=seed)


# -- bytes and operations a step's residual path must move -------------------

def hc_step_bytes(sizes: dict, rows: int) -> int:
    """Bytes a decode step of ``rows`` rows must move ONCE for its residual
    path, whatever implements it: a sub-layer reads X and writes X' (rows x
    n x dim float32 each), writes its input h and reads its output y (rows
    x dim each), and reads ``phi`` ((2 n + n^2) x n dim float32); two
    sub-layers a layer. (The gates and biases, 27 values, and the 2 n + n^2
    coefficients a row are left out: under a thousandth.)"""
    n, dim = sizes["streams"], sizes["dim"]
    per = (2 * rows * n * dim + 2 * rows * dim
           + coefficients(sizes) * n * dim) * 4
    return 2 * sizes["n_layers"] * per


def hc_step_flops(sizes: dict, rows: int) -> int:
    """Operations of a decode step's residual path, a multiply-add counted
    as two: the flat norm (2 n dim), the projection (2 (2 n + n^2) n dim),
    the two mixes (2 n dim and 2 n^2 dim + 2 n dim) and Sinkhorn (exp, then
    4 n^2 an iteration) a row and sub-layer."""
    n, dim = sizes["streams"], sizes["dim"]
    per = (2 * n * dim + 2 * coefficients(sizes) * n * dim + 2 * n * dim
           + 2 * n * n * dim + 2 * n * dim
           + n * n * (1 + 4 * sizes["sinkhorn_iters"]))
    return 2 * sizes["n_layers"] * rows * per


# -- what a device trace shows -------------------------------------------------
# The residual path is XLA fusions. A capture names a device op by its
# instruction in the compiled step (``fusion.412``) and carries no scope;
# the compiled text does: an instruction's ``op_name`` holds the
# ``jax.named_scope``s it was traced under, and the program opens ``hc.coef``
# and ``hc.mix`` (``obs/spans.py``) around the path and around nothing else.
# So the path's ops are told BY IDENTITY: the driver reads the step's
# compiled text in set-up (``ContinuousEngine.decode_program_text``), keeps
# the names of the instructions under either scope (``path_instructions``),
# and a decode step's ops with those names are the path's. A fusion takes
# its root's ``op_name``: one that the compiler made of a mix AND the
# RMSNorm behind it counts whole on the side its root lies, which is the one
# imprecision left, and goes both ways.
#
# Without the names (a program that cannot give its text, a capture looked
# at by hand) the path is found BY POSITION among a decode step's dense Q40
# calls (six a layer: wq_a, wq_b, wkv_a, wo, then the FFN's two;
# ``latent.block_seconds``), and reads HIGH by what shares its gaps:
#   * between a layer's last Q40 / expert call and the next layer's first
#     attention leaf (the feed-forward's mix-out, the attention's
#     coefficients and input; the attention's RMSNorm and the routed
#     experts' combine ride along);
#   * after ``wo``, the first K ops, K the count between ``wo`` and the
#     FFN's first Q40 call in a DENSE layer (an expert layer runs the same
#     ops there, then its router and slot building, which are not counted);
#   * between the last layer's last call and the classifier.
# Layer 0's first part holds the embedding's ops too and is left out. The
# halves of an asynchronous copy or slice are left out wherever they stand:
# the compiler parks there the wait for the next attention's float32
# ``w_uk`` / ``w_uv`` slices (my chip run, PR 39, PERF.md section 6).
# ``hc_step_ops`` says under ``"rule"`` which of the two it used.

PATH_SCOPES = ("hc.coef/", "hc.mix/")
ASYNC_LABELS = ("-start", "-done")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?(?P<name>[^\s=]+) = .*? (?P<kind>[a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NO_OP = ("parameter", "get-tuple-element", "bitcast", "tuple", "constant")


def path_instructions(hlo_text: str) -> dict:
    """{instruction name: opcode} of a compiled module's instructions,
    outside fused computations and reducers' regions, whose ``op_name`` lies
    under a scope of the residual path; what costs no device op (parameters,
    tuples, bitcasts, constants) left out. The layer scans' bodies appear
    once each: at two stacks of layers, FOUR sub-layers and the exit's sum."""
    out: dict = {}
    fused = False
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():      # a computation's header
            fused = "fused_computation" in line or line.lstrip("%").startswith(
                ("fused", "region_"))
            continue
        scope = _OP_NAME.search(line)
        if fused or scope is None or not any(
                s in scope.group(1) for s in PATH_SCOPES):
            continue
        m = _INSTRUCTION.match(line)
        if m and m.group("kind") not in _NO_OP:
            out[m.group("name")] = m.group("kind")
    return out


def hc_step_ops(trace, names=None) -> list[dict]:
    """Per decode step of the traced window that ran the latent kernel
    (``reduce_trace.steps``): ``{"seconds", "ops", "sublayers", "rule"}`` of
    the residual path and the step's ``"busy"`` seconds (the union of its
    op intervals). With ``names`` (``path_instructions`` of the step's
    compiled text) the path's ops are those named there, rule "identity";
    without, those found by position, rule "position". Empty where no step
    has the layer's period, or the program has no streams."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        found = _hc_ops(st["ops"], rt, names)
        if found is not None:
            out.append(found)
    return out


def _hc_ops(inside, rt, names=None):
    selfs = rt.self_times(inside)
    work = [i for i, o in enumerate(inside) if rt.classify(o) != "control"]
    calls = [i for i in work if inside[i].label == "custom-call"]
    moe = [i for i in calls if latent._is(inside[i], latent.MOE_KERNEL_PREFIX)]
    dense = [i for i in calls if rt.classify(inside[i]) == "q40"
             and i not in set(moe)]
    if not moe or len(dense) % 6 != 1 or not any(
            latent._is(inside[i], latent.DECODE_KERNEL) for i in calls):
        return None
    layers = (len(dense) - 1) // 6
    if names:
        picked = [i for i in work if inside[i].name in names]
        if not picked:
            return None
    else:
        picked = _by_position(inside, [
            i for i in work if not inside[i].label.endswith(ASYNC_LABELS)],
            dense, moe, layers)
        if picked is None:
            return None
    busy = rt.total(rt.union((o.start, o.end) for o in inside)) / 1e9
    return {"seconds": sum(selfs[i] for i in picked) / 1e9, "busy": busy,
            "ops": len(picked), "sublayers": 2 * layers,
            "rule": "identity" if names else "position"}


def _by_position(inside, work, dense, moe, layers):
    heavy = sorted(dense + moe)
    picked: list = []
    k_after_wo = None
    for k in range(layers):
        first, wo = dense[6 * k], dense[6 * k + 3]
        nxt = min(i for i in heavy if i > wo)
        between = [i for i in work if wo < i < nxt]
        if not any(wo < m < dense[6 * k + 4] for m in moe):    # a dense layer
            k_after_wo = len(between) if k_after_wo is None else k_after_wo
        if k_after_wo is None:
            return None     # expert layers before any dense one: no yardstick
        picked += between[:k_after_wo]
        if k:
            last = max(i for i in heavy if i < first)
            picked += [i for i in work if last < i < first]
    last = max(i for i in heavy if i < dense[-1])
    return picked + [i for i in work if last < i < dense[-1]]


def path_names(run):
    """The names a run's driver read from its step's compiled text, or
    None (a run of another driver, a program that gave none)."""
    return getattr(run, "path_ops", None)


def step_rows(run) -> float | None:
    """Rows a decode step of the window carried, on average."""
    steps = run.delta("steps")
    return run.delta("sum_active") / steps if steps else None
