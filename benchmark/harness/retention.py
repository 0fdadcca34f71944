"""A power-retention configuration (Brumby-14B: Qwen3-14B's block with the
softmax attention replaced by power retention of degree 2) for the drivers:
its sizes and ``TransformerSpec`` from the configuration file, its seeded
codec tree, the benchmark's own copy of the plain float32 reference (the
ATTENTION form: no state, no chunks), the bytes and operations the two new
kernels must do, and where a device trace shows them. ``harness/model.py``,
``weights.py``, ``reference.py`` and ``costs.py`` know the dense Llama block
at RMSNorm eps 1e-5; what they have that applies (value recipe,
dequantizer, RoPE, tokenizer, the dense block's Q40 bytes) is imported, not
copied.

The layer (``distributed_llama_tpu/models/reference_retention.py`` states
it in full, with every departure from the publication), KV head j, query
head i of its group, head size d:

  q_i = RoPE(RMSNorm_d((wq h)_i)), k_j = RoPE(RMSNorm_d((wk h)_j)), one gain
  of d shared by all heads; v_j = (wv h)_j; g_j = sigmoid((w_gate h)_j)
  a[t, s] = (prod_{r=s+1..t} g_j[r]) (q_i[t] . k_j[s] / sqrt d)^2,  s <= t
  y_i[t] = sum_s a[t, s] v_j[s] / (sum_s a[t, s] + 1e-6)

The program computes the same function through a state of D = d(d+1)/2 =
8256 rows a KV head (it stores 8320: ``ops/retention.py``'s offset layout);
the counts below use D = 8256 whatever is stored, so a layout that stores
more cannot read as a better share of the roofline.
"""

from __future__ import annotations

import bisect
import concurrent.futures
import functools
import os

import numpy as np

from . import model, weights
from .reference import _dequant, _rope

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")
NORMALISER_EPS = 1e-6
DECODE_KERNEL = "retention_decode_step"
CHUNK_KERNEL = "retention_prefill_chunk"


def sizes_of(config: dict) -> dict:
    """The header's seven sizes and what the extension carries."""
    return dict(model.sizes_of(config), rope_theta=float(config["rope_theta"]),
                norm_eps=float(config["rms_norm_eps"]))


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    if config.get("model_type") != "brumby":
        raise ValueError("harness/retention.py runs model_type brumby")
    if (config.get("weights"), config.get("buffers"),
            config.get("state")) != ("q40", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers "
                         "and a float32 state")
    if config.get("attention_bias") or config.get("use_sliding_window") \
            or config.get("rope_scaling") is not None \
            or config.get("tie_word_embeddings"):
        raise ValueError("the program has no attention bias, window, RoPE "
                         "scaling or tied embedding")
    if config.get("hidden_act") != "silu":
        raise ValueError("the program's FFN is SwiGLU")
    model.sizes_of(config)   # head_dim == hidden / heads, or it raises


def _dense_sizes(sizes: dict) -> dict:
    return {k: sizes[k] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                                  "n_kv_heads", "vocab_size", "seq_len")}


def program_spec(sizes: dict):
    """The program's spec. A program without the fields stops HERE (a
    ``TypeError`` on the unknown keyword), before any device is touched."""
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.ops.quants import FloatType

    return TransformerSpec(
        **_dense_sizes(sizes), weights_float_type=FloatType.Q40,
        buffer_float_type=FloatType.F32, qk_norm=True, qk_norm_per_head=True,
        attn_kind="retention",
        rope_theta=sizes["rope_theta"], norm_eps=sizes["norm_eps"])


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of a retention spec. Embedding, norms, the
    seven matmul leaves and the classifier are ``weights.build_codec_tree``'s
    own (same seed keys); the per-head q/k-norm gains (1 +- 0.05) and the
    gate (rows ~N(0, 1/sqrt(dim)), no bias: unit-variance logits, g near
    0.5) are filled per (tensor, layer), so the seed alone fixes the tree."""
    from distributed_llama_tpu.io.loader import Q40Weight

    tree = weights.build_codec_tree(_dense_sizes(sizes), seed, Q40Weight,
                                    threads)
    L, dim = sizes["n_layers"], sizes["dim"]
    hs = dim // sizes["n_heads"]
    tasks = []
    for idx, name in enumerate(("rms_q", "rms_k")):
        tree[name] = np.empty((L, hs), np.float32)
        tasks.append((weights._fill_dense, tree[name], 1.0,
                      [seed, 50 + idx, 0]))
    gate = tree["w_gate"] = np.empty((L, sizes["n_kv_heads"], dim),
                                     np.float32)
    for layer in range(L):
        tasks.append((weights._fill_dense, gate[layer], 0.0,
                      [seed, 52, layer]))
    with concurrent.futures.ThreadPoolExecutor(
            threads or min(16, os.cpu_count() or 1)) as pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
    gate *= np.float32(1.0 / np.sqrt(dim))
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device like harness/reference.py (a layer is
# 1.3 GB of float32 at the published widths), the classifier in blocks of
# rows of the vocabulary (whole it is 3.1 GB beside a served model).

def _rmsnorm(jnp, x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * w


def _layer(sizes, low, x, rms_att, rms_ffn, rms_q, rms_k, w_gate, w):
    """One block over x (B, T, dim); ``w`` maps name -> (qs, d16). ``low``:
    every product's operands rounded to bfloat16 first (float32 sums)."""
    import jax
    import jax.numpy as jnp

    n_heads, n_kv = sizes["n_heads"], sizes["n_kv_heads"]
    hs, eps = sizes["dim"] // n_heads, sizes["norm_eps"]
    B, T, _ = x.shape

    def ein(subscripts, a, b):
        if low:
            a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        return jnp.einsum(subscripts, a, b,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    mm = functools.partial(ein, "dn,btn->btd")
    wf = {k: _dequant(jnp, *v) for k, v in w.items()}
    pos = jnp.arange(T)
    h = _rmsnorm(jnp, x, rms_att, eps)

    def heads(a, gain):          # one gain of hs, each head normed alone
        return _rmsnorm(jnp, a.reshape(B, T, -1, hs), gain, eps).reshape(
            a.shape)

    q = _rope(jnp, heads(mm(wf["wq"], h), rms_q), pos, hs,
              sizes["rope_theta"]).reshape(B, T, n_kv, n_heads // n_kv, hs)
    k = _rope(jnp, heads(mm(wf["wk"], h), rms_k), pos, hs,
              sizes["rope_theta"]).reshape(B, T, n_kv, hs)
    v = mm(wf["wv"], h).reshape(B, T, n_kv, hs)
    log_g = jax.nn.log_sigmoid(ein("kn,btn->btk", w_gate, h))
    c = jnp.transpose(jnp.cumsum(log_g, axis=1), (0, 2, 1))     # (B, n_kv, T)
    causal = pos[None, :] <= pos[:, None]                       # [t, s]
    decay = jnp.where(causal, jnp.exp(jnp.where(
        causal, c[..., :, None] - c[..., None, :], 0.0)), 0.0)
    scores = ein("btgmd,bsgd->bgmts", q, k) / np.sqrt(hs)
    a = scores * scores * decay[:, :, None]
    y = ein("bgmts,bsgd->btgmd", a, v)
    total = jnp.transpose(jnp.sum(a, axis=-1), (0, 3, 1, 2))   # (B,T,g,m)
    y = y / (total[..., None] + NORMALISER_EPS)
    x = x + mm(wf["wo"], y.reshape(B, T, n_heads * hs))
    h = _rmsnorm(jnp, x, rms_ffn, eps)
    return x + mm(wf["w2"], jax.nn.silu(mm(wf["w1"], h)) * mm(wf["w3"], h))


def _head(x, qs, d16):
    import jax
    import jax.numpy as jnp

    return jnp.einsum("vn,btn->btv", _dequant(jnp, qs, d16), x,
                      precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, low: bool):
    """The jitted layer, final norm and classifier block of one
    configuration at one precision, made once: a call of ``logits`` at a
    shape it has seen compiles nothing."""
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes)
    return (jax.jit(functools.partial(_layer, sizes, low), donate_argnums=0),
            jax.jit(lambda x, g: _rmsnorm(jnp, x, g, sizes["norm_eps"])),
            jax.jit(_head))


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precision: str = "highest", vocab_blocks: int = 8,
           keep=None) -> np.ndarray:
    """Float32 logits (B, T, vocab) of the full forward pass over ``tokens``
    (B, T), every position reading those before it (the attention form);
    with ``keep`` only those positions' logits: positions (K,) kept of every
    row, or (B, K) each row's own, -> (B, K, vocab). ``precision``
    "bfloat16" runs the layers in the precision below float32, every
    product's operands rounded to bfloat16 first (what one bf16 pass on a
    TPU computes; written out so that a CPU gives the same): the control
    that must FAIL the configuration's tolerance (the cell's check reads it
    beside the served streams, ``drivers/serve_retention.check_streams``;
    ``tools/retention_logits.py --low-precision``)."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    if precision not in ("highest", "bfloat16"):
        raise ValueError(f"precision {precision!r}: highest or bfloat16")
    layer, final_norm, head = _programs(tuple(sorted(sizes.items())),
                                        precision == "bfloat16")
    x = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    for i in range(sizes["n_layers"]):
        w = {k: (put(tree[k].qs[i]), put(tree[k].d16[i])) for k in LAYER_KEYS}
        x = layer(x, *(put(tree[k][i]) for k in (
            "rms_att", "rms_ffn", "rms_q", "rms_k", "w_gate")), w)
        jax.block_until_ready(x)     # a layer at a time ON THE DEVICE too
    if keep is not None:
        keep = np.asarray(keep)
        x = x[:, keep] if keep.ndim == 1 else jnp.take_along_axis(
            x, put(keep)[..., None], axis=1)
    x = final_norm(x, put(tree["rms_final"]))
    qs, d16 = tree["wcls"].qs, tree["wcls"].d16
    edges = np.linspace(0, qs.shape[0], vocab_blocks + 1).astype(int)
    out = np.empty(tuple(x.shape[:2]) + (qs.shape[0],), np.float32)
    for lo, hi in zip(edges[:-1], edges[1:]):
        out[..., lo:hi] = np.asarray(head(x, put(qs[lo:hi]), put(d16[lo:hi])))
    return out


# -- bytes and operations, from shapes ----------------------------------------

def feature_rows(sizes: dict) -> int:
    """D = d (d + 1) / 2: the rows of a KV head's state the mathematics
    needs (8256 at d = 128), whatever layout stores them."""
    d = sizes["dim"] // sizes["n_heads"]
    return d * (d + 1) // 2


def state_step_bytes(sizes: dict, rows: int) -> int:
    """HBM bytes the decode kernel must move in ONE step: every row's state
    (S: D x d, and z: D) of every layer and KV head, read once and written
    once, float32."""
    d = sizes["dim"] // sizes["n_heads"]
    D = feature_rows(sizes)
    return (rows * sizes["n_layers"] * 2 * sizes["n_kv_heads"]
            * (D * d + D) * 4)


def chunk_kernel_flops(sizes: dict, t_len: int) -> int:
    """Operations (a multiply-add is two) ONE call of the chunk kernel must
    do: one layer, ``t_len`` positions, every KV head. Reading the earlier
    state (phi(q)^T S and phi(q).z for each of the group's query heads),
    advancing it (phi(k) v^T and phi(k) summed over the chunk), and the
    chunk's own positions in the attention form (scores, weighted sum)."""
    d = sizes["dim"] // sizes["n_heads"]
    m = sizes["n_heads"] // sizes["n_kv_heads"]
    D = feature_rows(sizes)
    read = 2 * m * t_len * D * (d + 1)
    advance = 2 * t_len * D * (d + 1)
    own = 2 * 2 * m * t_len * t_len * d
    return sizes["n_kv_heads"] * (read + advance + own)


# -- what a device trace shows of the retention sub-block ---------------------
# The reducer's ops carry the instruction's name and opcode only, and
# ``reduce_trace.classify`` (not to be edited) knows neither kernel, so they
# are found HERE by name: a ``pallas_call``'s instruction is named after
# it. The XLA ops of the sub-block (per-head norms, RoPE, the gate, phi,
# the division by the normaliser) by POSITION: what runs between the layer's
# first dense Q40 call (``wqkv``) and the next one (``wo``).

def _is_kernel(op, name: str) -> bool:
    return op.label == "custom-call" and op.name.lower().startswith(name)


def is_retention_kernel(op) -> bool:
    return _is_kernel(op, DECODE_KERNEL) or _is_kernel(op, CHUNK_KERNEL)


def kernel_calls(ops: list, name: str) -> list[float]:
    """Self seconds of each call of the kernel ``name`` among ``ops``."""
    from . import reduce_trace as rt

    return [s / 1e9 for o, s in zip(ops, rt.self_times(ops))
            if _is_kernel(o, name)]


def decode_step_kernel_seconds(trace) -> list[float]:
    """Per decode step of the traced window (``reduce_trace.steps``; a step
    whose span held an admission shows the chunk program and is left out):
    the seconds in the decode kernel's calls (one a layer)."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        if any(_is_kernel(o, CHUNK_KERNEL) for o in st["ops"]):
            continue
        secs = sum(kernel_calls(st["ops"], DECODE_KERNEL))
        if secs > 0:
            out.append(secs)
    return out


def retention_block_seconds(ops: list) -> float:
    """Self seconds of the retention sub-blocks among one device's ``ops``
    (sorted by start): each retention kernel with the ops around it, from
    the end of the dense Q40 call before it to the start of the one after."""
    from . import reduce_trace as rt

    selfs = rt.self_times(ops)
    q40 = [i for i, o in enumerate(ops) if rt.classify(o) == "q40"]
    total, seen = 0.0, -1
    for i, o in enumerate(ops):
        if i <= seen or not is_retention_kernel(o):
            continue
        at = bisect.bisect_left(q40, i)
        lo = q40[at - 1] + 1 if at else i
        hi = q40[at] if at < len(q40) else i + 1
        total += sum(selfs[j] for j in range(max(lo, seen + 1), hi)
                     if rt.classify(ops[j]) != "control")
        seen = hi - 1
    return total / 1e9
