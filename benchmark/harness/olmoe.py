"""An expert configuration (OLMoE-1B-7B: 64 routed experts of width 1024,
8 a token kept as they are, q/k-norm) for the drivers: its sizes and
``TransformerSpec`` from the configuration file, its seeded codec tree, the
benchmark's own copy of the plain float32 reference, and the bytes a step
must move. ``harness/model.py``, ``weights.py``, ``reference.py`` and
``costs.py`` know the dense Llama block's seven tensors only; what they have
that applies (value recipe, dequantizer, RMSNorm, RoPE, tokenizer, KV bytes)
is imported, not copied.

The layer (``distributed_llama_tpu/models/reference_olmoe.py`` states it in
full): q = RMSNorm_q(wq h), k = RMSNorm_k(wk h) with gains over the whole
projection, before interleaved-pair RoPE; causal attention; router logits
W_g h, softmax over all E in float32, the k largest kept without
renormalising; y = sum_e p_e w2_e(silu(w1_e h) * w3_e h).
"""

from __future__ import annotations

import concurrent.futures
import functools
import os

import numpy as np

from . import costs, model, weights
from .reference import _dequant, _head, _rmsnorm, _rope

ATTN_KEYS = ("wq", "wk", "wv", "wo")
EXPERT_KEYS = ("moe_w1", "moe_w2", "moe_w3")


def sizes_of(config: dict) -> dict:
    """The header's sizes: the seven of the dense block (``hidden_dim`` is
    ONE expert's width, read from ``intermediate_size``: the catalog's
    noted inference) and the three of the extension."""
    return dict(model.sizes_of(config), n_experts=config["num_experts"],
                n_active_experts=config["num_experts_per_tok"], qk_norm=True)


def check_runnable(config: dict) -> None:
    model.check_runnable(config)
    if config.get("model_type") != "olmoe":
        raise ValueError("harness/olmoe.py runs model_type olmoe")
    if config.get("norm_topk_prob") or config.get("clip_qkv") is not None:
        raise ValueError("the program keeps the top-k probabilities as they "
                         "are and has no clip_qkv")
    if config["num_key_value_heads"] * (config["hidden_size"]
                                        // config["num_attention_heads"]) \
            > config["hidden_size"]:
        raise ValueError("more kv width than hidden width")


def program_spec(sizes: dict):
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.ops.quants import FloatType

    return TransformerSpec(**sizes, weights_float_type=FloatType.Q40,
                           buffer_float_type=FloatType.F32)


def expert_shapes(sizes: dict) -> list[tuple[str, tuple[int, int]]]:
    d, h = sizes["dim"], sizes["hidden_dim"]
    return [("moe_w1", (h, d)), ("moe_w2", (d, h)), ("moe_w3", (h, d))]


def _dense_sizes(sizes: dict) -> dict:
    return {k: sizes[k] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                                  "n_kv_heads", "vocab_size", "seq_len")}


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of an expert spec. Embedding, norms,
    attention and classifier are ``weights.build_codec_tree``'s own (same
    seed keys); the dense FFN it also builds (hidden 1024: 0.1 GB) is
    dropped, and the q/k-norm gains, the router (rows ~N(0, 1/sqrt(dim)):
    unit-variance logits on unit-RMS input) and the experts are filled per
    (tensor, layer, expert), so the seed alone fixes the tree."""
    from distributed_llama_tpu.io.loader import Q40Weight

    tree = weights.build_codec_tree(_dense_sizes(sizes), seed, Q40Weight,
                                    threads)
    for name in ("w1", "w2", "w3"):
        del tree[name]
    L, E, dim = sizes["n_layers"], sizes["n_experts"], sizes["dim"]
    kv = dim * sizes["n_kv_heads"] // sizes["n_heads"]
    tasks = []
    for idx, (name, width) in enumerate((("rms_q", dim), ("rms_k", kv))):
        tree[name] = np.empty((L, width), np.float32)
        tasks.append((weights._fill_dense, tree[name], 1.0,
                      [seed, 30 + idx, 0]))
    gate = tree["moe_gate"] = np.empty((L, E, dim), np.float32)
    for layer in range(L):
        tasks.append((weights._fill_dense, gate[layer], 0.0,
                      [seed, 32, layer]))
    for idx, (name, (d, n)) in enumerate(expert_shapes(sizes)):
        nb = n // weights.QK
        qs = np.empty((L, E, d, nb, 16), np.uint8)
        d16 = np.empty((L, E, d, nb), np.float16)
        tree[name] = Q40Weight(qs, d16)
        for layer in range(L):
            for e in range(E):
                tasks.append((weights._fill_q40, qs[layer, e], d16[layer, e],
                              n, [seed, 40 + idx, layer, e]))
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
    gate *= np.float32(1.0 / np.sqrt(dim))
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device like harness/reference.py, and inside a
# layer ONE expert at a time (25 MB of float32 each at the published widths,
# never the 1.6 GB stack): every row through every expert, weighted by the
# router's kept probability, 0 where the expert was not kept.

def _attention(sizes, rope_base, prec, x, rms_att, rms_q, rms_k, w):
    import jax
    import jax.numpy as jnp

    n_heads, n_kv = sizes["n_heads"], sizes["n_kv_heads"]
    hs = sizes["dim"] // n_heads
    B, T, _ = x.shape
    mm = functools.partial(jnp.einsum, "dn,btn->btd", precision=prec)
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    pos = jnp.arange(T)
    xb = _rmsnorm(jnp, x, rms_att)
    q = _rope(jnp, _rmsnorm(jnp, mm(wf["wq"], xb), rms_q), pos, hs, rope_base)
    k = _rope(jnp, _rmsnorm(jnp, mm(wf["wk"], xb), rms_k), pos, hs, rope_base)
    v = mm(wf["wv"], xb)
    q = q.reshape(B, T, n_kv, n_heads // n_kv, hs)
    k = k.reshape(B, T, n_kv, hs)
    v = v.reshape(B, T, n_kv, hs)
    scores = jnp.einsum("btgmd,bsgd->bgmts", q, k,
                        precision=prec) / np.sqrt(hs)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    ao = jnp.einsum("bgmts,bsgd->btgmd", jax.nn.softmax(scores, axis=-1), v,
                    precision=prec)
    return x + mm(wf["wo"], ao.reshape(B, T, n_heads * hs))


def _route(k, prec, x, rms_ffn, gate):
    """Normalised rows, each expert's kept weight (B, T, E), and the margin
    (B, T) between the last expert kept and the first dropped, in the
    router's logits: r_(k) - r_(k+1) = log p_(k) - log p_(k+1)."""
    import jax
    import jax.numpy as jnp

    h = _rmsnorm(jnp, x, rms_ffn)
    p = jax.nn.softmax(jnp.einsum("ed,btd->bte", gate, h, precision=prec),
                       -1)
    n_exp = p.shape[-1]
    top, ids = jax.lax.top_k(p, min(k + 1, n_exp))
    margin = (jnp.log(top[..., k - 1]) - jnp.log(top[..., k])
              if k < n_exp else jnp.full(top.shape[:-1], jnp.inf))
    kept = (ids[..., :k, None] == jnp.arange(n_exp)).any(axis=-2)
    return h, jnp.where(kept, p, 0.0), margin


def _expert(prec, acc, h, weight, w1, w2, w3):
    import jax
    import jax.numpy as jnp

    mm = functools.partial(jnp.einsum, "dn,btn->btd", precision=prec)
    w1, w2, w3 = (_dequant(jnp, *w) for w in (w1, w2, w3))
    return acc + weight[..., None] * mm(
        w2, jax.nn.silu(mm(w1, h)) * mm(w3, h))


def logits(tree: dict, sizes: dict, tokens: np.ndarray,
           rope_base: float = 10000.0, device=None, precision="highest"):
    """Float32 logits (B, T, vocab) of the full forward pass over ``tokens``
    (B, T), and the smallest router margin over the layers at each position
    (B, T): a comparison with another implementation holds only up to the
    first position where that margin is under the two's rounding.
    ``precision`` "default" runs the layers' matmuls in the precision below
    float32 (one bf16 pass on a TPU): the reading that must FAIL the
    configuration's tolerance (``tools/olmoe_logits.py --low-precision``)."""
    import jax

    prec = jax.lax.Precision(precision)

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    attention = jax.jit(functools.partial(_attention, _dense_sizes(sizes),
                                          float(rope_base), prec))
    route = jax.jit(functools.partial(_route, sizes["n_active_experts"],
                                      prec))
    expert = jax.jit(functools.partial(_expert, prec), donate_argnums=0)
    x = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    margin = None
    for i in range(sizes["n_layers"]):
        w = {k: (put(tree[k].qs[i]), put(tree[k].d16[i])) for k in ATTN_KEYS}
        x = attention(x, put(tree["rms_att"][i]), put(tree["rms_q"][i]),
                      put(tree["rms_k"][i]), w)
        h, weight, m = route(x, put(tree["rms_ffn"][i]),
                             put(tree["moe_gate"][i]))
        margin = m if margin is None else jax.numpy.minimum(margin, m)
        for e in range(sizes["n_experts"]):
            x = expert(x, h, weight[..., e], *(
                (put(tree[k].qs[i, e]), put(tree[k].d16[i, e]))
                for k in EXPERT_KEYS))
        # a layer at a time ON THE DEVICE too: the loop otherwise runs ahead
        # and parks every layer's experts there (15 GiB read in PR 26)
        jax.block_until_ready(x)
    out = jax.jit(_head)(x, put(tree["rms_final"]), put(tree["wcls"].qs),
                         put(tree["wcls"].d16))
    return np.asarray(out), np.asarray(margin)


MARGIN_EPSILON = 5e-5
"""Positions are compared up to the first whose smallest router margin (in
the router's logits) is under this. Two float32 routers keep different
experts only where the margin is under twice what their logits differ by;
the program's float32 paths differ from a reference by at most 1e-5 on the
final logits (PERF.md section 6), and the router's logits have that scale at
less depth. At the published widths (64 experts, 16 layers) about one
position in a hundred is under it, so a check uses MANY SHORT sequences."""


def compared_positions(margins_row: np.ndarray) -> int:
    low = np.nonzero(np.asarray(margins_row) < MARGIN_EPSILON)[0]
    return int(low[0]) if low.size else int(len(margins_row))


# -- bytes a step must move ----------------------------------------------------

def expert_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of ONE expert's three tensors."""
    return sum(d * n for _, (d, n) in expert_shapes(sizes)) \
        // costs.Q40_BLOCK * costs.Q40_BLOCK_BYTES


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes of the leaves every step reads whole whatever it
    routes: wq, wk, wv, wo of every layer and the classifier."""
    d = sizes["dim"]
    kv = d * sizes["n_kv_heads"] // sizes["n_heads"]
    n = sizes["n_layers"] * (2 * d * d + 2 * kv * d) + sizes["vocab_size"] * d
    return n // costs.Q40_BLOCK * costs.Q40_BLOCK_BYTES


def step_bytes(sizes: dict, active_experts: float, rows: int = 1,
               context: int = 0) -> float:
    """HBM bytes one decode step must move: each DISTINCT routed expert
    once (``active_experts`` summed over the layers), the dense leaves, and
    each row's keys and values up to ``context``."""
    return (active_experts * expert_bytes(sizes) + dense_q40_bytes(sizes)
            + rows * context * costs.kv_bytes_per_position(sizes))


# -- what a device trace shows of the expert layer ----------------------------
# The reducer's ops carry the instruction's name and opcode only: the
# program's ``moe.router`` / ``moe.experts`` scopes are not in a capture's op
# text. The grouped kernels are found by name (``moe_q40_slots``, the decode
# kernel; ``moe_q40_mxu``, the every-expert kernel of prefill chunks); the
# XLA ops of the sub-block (FFN norm, router, top-k, slot building, gathers,
# SiLU, combine, residual add) by POSITION: whatever runs between the end of
# the layer's last dense Q40 call (``wo``) and the start of the next one.

MOE_KERNEL_PREFIX = "moe_q40"
MOE_DECODE_KERNEL = "moe_q40_slots"


def _is_moe_kernel(op) -> bool:
    return (op.label == "custom-call"
            and op.name.lower().startswith(MOE_KERNEL_PREFIX))


def moe_block_seconds(ops: list) -> float:
    """Self seconds of the routed-expert sub-blocks among one device's
    ``ops`` (sorted by start): each run of expert kernels with the ops
    around it, from the preceding dense Q40 call to the following one."""
    from . import reduce_trace as rt

    selfs = rt.self_times(ops)
    q40 = [i for i, o in enumerate(ops) if rt.classify(o) == "q40"]
    total, k = 0.0, 0
    while k < len(q40):
        if not _is_moe_kernel(ops[q40[k]]):
            k += 1
            continue
        j = k
        while j + 1 < len(q40) and _is_moe_kernel(ops[q40[j + 1]]):
            j += 1
        lo = q40[k - 1] + 1 if k else q40[k]
        hi = q40[j + 1] if j + 1 < len(q40) else q40[j] + 1
        total += sum(selfs[i] for i in range(lo, hi)
                     if rt.classify(ops[i]) != "control")
        k = j + 1
    return total / 1e9


def decode_step_kernel_seconds(trace) -> list[tuple[float, float]]:
    """Per decode step of the traced window (``reduce_trace.steps``; a step
    whose span held an admission shows the prefill chunk and is left out):
    (seconds in the decode expert kernel, seconds in the dense Q40 calls)."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        slots = dense = 0.0
        for o, s in zip(ops, rt.self_times(ops)):
            if rt.classify(o) != "q40":
                continue
            if o.name.lower().startswith(MOE_DECODE_KERNEL):
                slots += s / 1e9
            elif not _is_moe_kernel(o):
                dense += s / 1e9
        if slots > 0:
            out.append((slots, dense))
    return out
