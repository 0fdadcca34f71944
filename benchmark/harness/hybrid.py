"""A SambaY configuration (Phi-4-mini-flash-reasoning: Mamba, window and one
full differential-attention layer, then Gated Memory Units and
cross-attention over the full layer's K / V) for the drivers: its sizes and
``TransformerSpec`` from the configuration file, its seeded codec tree, the
benchmark's own copy of the plain float32 reference (the whole forward at
every position: no cache, no state carried, no kernels), the bytes and
operations the new kernels must do, and where a device trace shows each kind
of layer. ``harness/model.py``, ``weights.py``, ``reference.py`` and
``costs.py`` know the dense Llama block; what they have that applies (the
Q40 value recipe and its dequantizer, the tokenizer) is imported, not copied.

The layers (``distributed_llama_tpu/models/reference_sambay.py`` states them
in full, with every departure from the publication), LN = LayerNorm with
gain and bias, no positional encoding:

  h = x + mix_i(LN1(x));  out = h + fc2(silu(g) * u),  [g | u] = fc1(LN2(h))
  mamba: [xs | z] = in_proj(u); xs = silu(conv4(xs) + b); [dt | B | C] =
         x_proj(xs); delta = softplus(dt_proj(dt) + b_dt); s_t = exp(delta_t
         A) s_{t-1} + B_t (delta_t xs_t)^T; y_t = s_t^T C_t + D xs_t; out =
         out_proj(y_t * silu(z_t)); the memory layer hands on m_t = y_t
  swa / full / xattn: a = softmax(q1 k1^T / 8) v - lambda softmax(q2 k2^T /
         8) v over pairs of heads, o = (1 - lambda_init) RMSNorm_128(a) g,
         out_proj(o) + b; swa sees the last 512 positions, xattn the full
         layer's k, v with its own queries
  gmu:   out_proj(silu(in_proj(u)) * m_t)
"""

from __future__ import annotations

import bisect
import concurrent.futures
import functools
import math
import os

import numpy as np

from . import model, weights
from .reference import _dequant

KINDS = ("mamba", "swa", "full", "gmu", "xattn")
MAMBA_DECODE = "mamba_decode_step"
MAMBA_CHUNK = "mamba_prefill_chunk"
WINDOW_KERNEL = "hm_attn_rows_decode"
PAGED_KERNEL = "hm_attn_paged_decode"


def kinds_of(n_layers: int) -> tuple:
    """The published pattern, h = L / 2: Mamba at even i <= h, window
    attention at odd i < h, the full layer at h + 1, then GMUs at even and
    cross-attention at odd i."""
    h = n_layers // 2
    return tuple("mamba" if i <= h and i % 2 == 0 else "swa" if i < h
                 else "full" if i == h + 1 else "gmu" if i % 2 == 0
                 else "xattn" for i in range(n_layers))


def sizes_of(config: dict) -> dict:
    """The header's seven sizes and what extension 5 carries."""
    ss = config["state_space"]
    sizes = dict(model.sizes_of(config),
                 norm_eps=float(config["layer_norm_eps"]),
                 window=int(config["sliding_window"]),
                 d_inner=int(ss["expand"]) * config["hidden_size"],
                 d_state=int(ss["d_state"]), d_conv=int(ss["d_conv"]),
                 dt_rank=int(ss["dt_rank"]))
    return sizes


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    if config.get("model_type") != "phi4flash":
        raise ValueError("harness/hybrid.py runs model_type phi4flash")
    if (config.get("weights"), config.get("buffers"), config.get("state"),
            config.get("kv_cache")) != ("q40", "f32", "f32", "f32"):
        raise ValueError("the driver runs Q40 weights with float32 buffers, "
                         "state, window rings and K / V pages")
    if config.get("mb_per_layer") != 2 or config["num_hidden_layers"] % 2:
        raise ValueError("the list of kinds is mb_per_layer 2's, at an even "
                         "depth")
    if not config.get("tie_word_embeddings") or config.get("mlp_bias") \
            or config.get("lm_head_bias") or config.get("hidden_act") != "silu":
        raise ValueError("the program ties the classifier to the embedding "
                         "and has a bias-free SwiGLU FFN and classifier")
    model.sizes_of(config)


def _dense_sizes(sizes: dict) -> dict:
    return {k: sizes[k] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                                  "n_kv_heads", "vocab_size", "seq_len")}


def program_spec(sizes: dict):
    """The program's spec. A program without the fields stops HERE (an
    ``ImportError`` on the unknown name), before any device is touched."""
    from distributed_llama_tpu.models.spec import (HybridLayers,
                                                   TransformerSpec)
    from distributed_llama_tpu.ops.quants import FloatType

    return TransformerSpec(
        **_dense_sizes(sizes), weights_float_type=FloatType.Q40,
        buffer_float_type=FloatType.F32, norm_eps=sizes["norm_eps"],
        hybrid=HybridLayers(kinds_of(sizes["n_layers"]), sizes["window"],
                            sizes["d_inner"], sizes["d_state"],
                            sizes["d_conv"], sizes["dt_rank"]))


def layer_leaves(sizes: dict, kind: str) -> list:
    """(name, "mm" | "f32", shape) of one layer of ``kind``, in file order
    (``TransformerSpec.layer_plans`` has the same list)."""
    d, h = sizes["dim"], sizes["hidden_dim"]
    kv = d * sizes["n_kv_heads"] // sizes["n_heads"]
    hs = d // sizes["n_heads"]
    di, ds, dr = sizes["d_inner"], sizes["d_state"], sizes["dt_rank"]
    f, m = (lambda n, *s: (n, "f32", s)), (lambda n, *s: (n, "mm", s))
    norms = [f("ln1_g", d), f("ln1_b", d), f("ln2_g", d), f("ln2_b", d)]
    diff = [f("lam", 4, hs), f("subln", 2 * hs)]
    out = [m("wo", d, d), f("bo", d)]
    mixer = {
        "mamba": [m("in_proj", 2 * di, d), f("conv_w", sizes["d_conv"], di),
                  f("conv_b", di), f("x_proj", dr + 2 * ds, di),
                  f("dt_proj", di, dr), f("dt_b", di), f("a_log", ds, di),
                  f("d_skip", di), m("out_proj", d, di)],
        "swa": [m("wqkv", d + 2 * kv, d), f("bqkv", d + 2 * kv)] + diff + out,
        "gmu": [m("in_proj", di, d), m("out_proj", d, di)],
        "xattn": [m("wq", d, d), f("bq", d)] + diff + out,
    }
    mixer["full"] = mixer["swa"]
    return norms + mixer[kind] + [m("w13", 2 * h, d), m("w2", d, h)]


def _small_leaf(sizes: dict, name: str, shape: tuple, seed_key) -> np.ndarray:
    """A float32 leaf as the family initialises it (``assumed`` in the
    configuration file): ``a_log`` = log(1..16), ``dt_b`` such that softplus
    gives 1e-3 to 1e-1 spread log-evenly over the channels, ``d_skip`` = 1,
    lambdas N(0, 0.1), ``x_proj`` ~N(0, 1/sqrt(d_inner)), ``dt_proj``
    ~N(0, 1/sqrt(dt_rank)), conv taps ~N(0, 1/2), gains 1 +- 0.05, biases
    +- 0.05."""
    if name == "a_log":
        a = np.log(np.arange(1, sizes["d_state"] + 1, dtype=np.float32))
        return np.broadcast_to(a[:, None], shape).copy()
    if name == "dt_b":
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), shape[-1]))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    if name == "d_skip":
        return np.ones(shape, np.float32)
    x = np.random.default_rng(seed_key).standard_normal(
        shape, dtype=np.float32)
    x *= np.float32({"lam": 0.1, "x_proj": sizes["d_inner"] ** -0.5,
                     "dt_proj": sizes["dt_rank"] ** -0.5,
                     "conv_w": 0.5}.get(name, 0.05))
    if name in ("ln1_g", "ln2_g", "subln"):
        x += np.float32(1)
    return x


def codec_tree(sizes: dict, seed: int, threads: int = 0):
    """The loader's param tree of a hybrid spec: a stack of layers a kind
    (``tree[kind][name]``, leading axis the layers of that kind). Q40 leaves
    by ``weights._fill_q40`` (the accepted value recipe), one task per
    (kind, tensor, layer), so the seed alone fixes the tree whatever the
    thread count. The classifier is drawn as a Q40 leaf (BOS row zeroed) and
    the EMBEDDING is its float32 copy: tied, exactly."""
    from distributed_llama_tpu.io.loader import Q40Weight

    kinds = kinds_of(sizes["n_layers"])
    tree: dict = {}
    tasks = []
    for ki, kind in enumerate(KINDS):
        depth = kinds.count(kind)
        stack = tree[kind] = {}
        for li, (name, what, shape) in enumerate(layer_leaves(sizes, kind)):
            key = [seed, 100 + ki, li]
            if what == "mm":
                d, n = shape
                qs = np.empty((depth, d, n // 32, 16), np.uint8)
                d16 = np.empty((depth, d, n // 32), np.float16)
                stack[name] = Q40Weight(qs, d16)
                for i in range(depth):
                    tasks.append((weights._fill_q40, qs[i], d16[i], n,
                                  key + [i]))
            else:
                stack[name] = np.stack([_small_leaf(sizes, name, shape,
                                                    key + [i])
                                        for i in range(depth)])
    vocab, dim = sizes["vocab_size"], sizes["dim"]
    qs = np.empty((vocab, dim // 32, 16), np.uint8)
    d16 = np.empty((vocab, dim // 32), np.float16)
    step = 8192
    for lo in range(0, vocab, step):
        tasks.append((weights._fill_q40, qs[lo:lo + step], d16[lo:lo + step],
                      dim, [seed, 20, lo]))
    tree["wcls"] = Q40Weight(qs, d16)
    tree["rms_final"] = _small_leaf(sizes, "ln1_g", (dim,), [seed, 3, 0])
    tree["rms_final_b"] = _small_leaf(sizes, "ln1_b", (dim,), [seed, 4, 0])
    pool = concurrent.futures.ThreadPoolExecutor(
        threads or min(16, os.cpu_count() or 1))
    with pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
        d16[weights.BOS] = 0     # logit exactly 0: never the argmax
        emb = tree["tok_embedding"] = np.empty((vocab, dim), np.float32)

        def tie(lo):
            emb[lo:lo + step] = weights.dequantize(qs[lo:lo + step],
                                                   d16[lo:lo + step])

        for f in [pool.submit(tie, lo) for lo in range(0, vocab, step)]:
            f.result()
    return tree


# -- the benchmark's copy of the reference -----------------------------------
# A layer at a time on one device (a layer is up to 0.5 GB of float32 at the
# published widths), attention a row at a time (a row of 2,600 positions has
# 40 score maps of 27 MB), the classifier in blocks of rows of the vocabulary
# (whole it is 2 GB beside a served model).

def _layernorm(jnp, x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def lambda_init(layer):
    import jax.numpy as jnp

    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def _layer(sizes, kind, x, shared, lw, layer, low):
    """One block of ``kind`` over x (B, T, dim), the model's layer ``layer``
    (a traced index: one program a kind serves every layer of it); ``lw``
    maps name -> array or (qs, d16); ``shared`` = (m, k, v) as the earlier
    layers left them. ``low`` (traced): every product's operands rounded to
    bfloat16 first (the products of two bfloat16 values are exact in
    float32, so this IS the bfloat16 product with float32 sums). Returns
    (x, shared)."""
    import jax
    import jax.numpy as jnp

    eps, hs = sizes["norm_eps"], sizes["dim"] // sizes["n_heads"]
    n_h, n_kv = sizes["n_heads"] // 2, sizes["n_kv_heads"] // 2
    di, ds, dr = sizes["d_inner"], sizes["d_state"], sizes["dt_rank"]
    B, T, d = x.shape
    silu = lambda a: a / (1.0 + jnp.exp(-a))  # noqa: E731

    def rounded(a):
        # ``reduce_precision`` and not a cast to bfloat16 and back: the
        # chip's compiler folds such a pair of casts away (the control then
        # reads the reference itself: 0.0, my chip run, PR 37)
        return jnp.where(low, jax.lax.reduce_precision(a, 8, 7), a)

    def ein(subscripts, a, b):
        return jnp.einsum(subscripts, rounded(a), rounded(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def mm(name, a):
        w = lw[name]
        w = _dequant(jnp, *w) if isinstance(w, tuple) else w
        return ein("dn,btn->btd", w, a)

    m, k_sh, v_sh = shared
    u = _layernorm(jnp, x, lw["ln1_g"], lw["ln1_b"], eps)
    if kind == "mamba":
        xz = mm("in_proj", u)
        xs, z = xz[..., :di], xz[..., di:]
        dc = lw["conv_w"].shape[0]
        padded = jnp.concatenate([jnp.zeros((B, dc - 1, di)), xs], axis=1)
        xs = silu(sum(padded[:, j:j + T] * lw["conv_w"][j]
                      for j in range(dc)) + lw["conv_b"])
        dbc = mm("x_proj", xs)
        delta = jax.nn.softplus(mm("dt_proj", dbc[..., :dr]) + lw["dt_b"])
        b_t, c_t = dbc[..., dr:dr + ds], dbc[..., dr + ds:]
        a = -jnp.exp(lw["a_log"])

        def step(s, row):       # s (B, d_state, d_inner): float32 throughout
            d_t, x_t, bb, cc = row
            s = jnp.exp(d_t[:, None] * a) * s + bb[:, :, None] * (
                d_t * x_t)[:, None]
            return s, jnp.sum(s * cc[:, :, None], axis=1)

        _, y = jax.lax.scan(step, jnp.zeros((B, ds, di)), tuple(
            jnp.swapaxes(t, 0, 1) for t in (delta, xs, b_t, c_t)))
        y = jnp.swapaxes(y, 0, 1) + lw["d_skip"] * xs
        m = jnp.where(layer == sizes["memory_layer"], y, m)
        mix = mm("out_proj", y * silu(z))
    elif kind == "gmu":
        mix = mm("out_proj", silu(mm("in_proj", u)) * m)
    else:
        kv = n_kv * 2 * hs
        if kind == "xattn":
            q, k, v = mm("wq", u) + lw["bq"], k_sh, v_sh
        else:
            qkv = mm("wqkv", u) + lw["bqkv"]
            q, k, v = qkv[..., :d], qkv[..., d:d + kv], qkv[..., d + kv:]
            if kind == "full":
                k_sh, v_sh = k, v
        pos = jnp.arange(T)
        mask = pos[None, :] <= pos[:, None]
        if kind == "swa":
            mask &= pos[:, None] - pos[None, :] < sizes["window"]
        li = lambda_init(layer)
        lam = lw["lam"]
        lam_full = (jnp.exp(jnp.sum(lam[0] * lam[1]))
                    - jnp.exp(jnp.sum(lam[2] * lam[3])) + li)

        def one(row):
            qr, kr, vr = row
            qp = qr.reshape(T, n_h, 2, hs)
            kp = jnp.repeat(kr.reshape(T, n_kv, 2, hs), n_h // n_kv, axis=1)
            vp = jnp.repeat(vr.reshape(T, n_kv, 2 * hs), n_h // n_kv, axis=1)
            sc = ein("tjsd,ujsd->jstu", qp, kp) / math.sqrt(hs)
            att = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            o = ein("jstu,ujd->tjsd", att, vp)
            o = o[:, :, 0] - lam_full * o[:, :, 1]
            o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            return ((1.0 - li) * o * lw["subln"]).reshape(T, d)

        mix = mm("wo", jax.lax.map(one, (q, k, v))) + lw["bo"]
    h = x + mix
    gu = mm("w13", _layernorm(jnp, h, lw["ln2_g"], lw["ln2_b"], eps))
    hid = gu.shape[-1] // 2
    return h + mm("w2", silu(gu[..., :hid]) * gu[..., hid:]), (m, k_sh, v_sh)


def _head(x, qs, d16):
    import jax
    import jax.numpy as jnp

    return jnp.einsum("vn,btn->btv", _dequant(jnp, qs, d16), x,
                      precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple):
    """The jitted layers (one a kind: the layer's index and the precision
    are traced), final norm and classifier block of one configuration,
    made once."""
    import jax
    import jax.numpy as jnp

    s = dict(sizes)
    layers = {kind: jax.jit(functools.partial(_layer, s, kind),
                            donate_argnums=0) for kind in KINDS}
    return (layers,
            jax.jit(lambda x, g, b: _layernorm(jnp, x, g, b, s["norm_eps"])),
            jax.jit(_head))


def logits(tree: dict, sizes: dict, tokens: np.ndarray, device=None,
           precision: str = "highest", vocab_blocks: int = 16,
           keep=None) -> np.ndarray:
    """Float32 logits (B, T, vocab) of the full forward pass over ``tokens``
    (B, T) at every position; with ``keep`` only those positions' logits:
    (K,) kept of every row, or (B, K) each row's own, -> (B, K, vocab).
    ``precision`` "bfloat16" runs the layers one precision down, every
    product's operands rounded to bfloat16 first (the state-space scan stays
    float32, as the configuration states it): the control that must FAIL
    the configuration's tolerance."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens)
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    if precision not in ("highest", "bfloat16"):
        raise ValueError(f"precision {precision!r}: highest or bfloat16")
    kinds = kinds_of(sizes["n_layers"])
    mem = max(i for i, k in enumerate(kinds)
              if k == "mamba" and i < kinds.index("gmu"))
    layers, final_norm, head = _programs(
        tuple(sorted(dict(sizes, memory_layer=mem).items())))
    low = np.bool_(precision == "bfloat16")
    x = put(np.ascontiguousarray(tree["tok_embedding"][tokens]))
    B, T = tokens.shape
    kv = sizes["dim"] * sizes["n_kv_heads"] // sizes["n_heads"]
    shared = (put(np.zeros((B, T, sizes["d_inner"]), np.float32)),
              put(np.zeros((B, T, kv), np.float32)),
              put(np.zeros((B, T, kv), np.float32)))
    seen: dict = {}
    for i, kind in enumerate(kinds):
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        lw = {k: ((put(v.qs[at]), put(v.d16[at])) if hasattr(v, "qs")
                  else put(v[at])) for k, v in tree[kind].items()}
        x, shared = layers[kind](x, shared, lw, np.int32(i), low)
        jax.block_until_ready(x)     # a layer at a time ON THE DEVICE too
    if keep is not None:
        keep = np.asarray(keep)
        x = x[:, keep] if keep.ndim == 1 else jnp.take_along_axis(
            x, put(keep)[..., None], axis=1)
    x = final_norm(x, put(tree["rms_final"]), put(tree["rms_final_b"]))
    qs, d16 = tree["wcls"].qs, tree["wcls"].d16
    edges = np.linspace(0, qs.shape[0], vocab_blocks + 1).astype(int)
    out = np.empty(tuple(x.shape[:2]) + (qs.shape[0],), np.float32)
    for lo, hi in zip(edges[:-1], edges[1:]):
        out[..., lo:hi] = np.asarray(head(x, put(qs[lo:hi]), put(d16[lo:hi])))
    return out


# -- bytes and operations, from shapes ----------------------------------------

def _count(sizes: dict, kind: str) -> int:
    return kinds_of(sizes["n_layers"]).count(kind)


def kv_position_bytes(sizes: dict) -> int:
    """K and V of one position in ONE layer, float32 (10,240 B)."""
    return 2 * sizes["dim"] * sizes["n_kv_heads"] // sizes["n_heads"] * 4


def ssm_step_bytes(sizes: dict, rows: int) -> int:
    """HBM bytes the Mamba decode kernel must move in ONE step: every row's
    (d_state, d_inner) state of every Mamba layer read once and written
    once, float32 (the conv inputs, 3 rows of d_inner, are XLA's)."""
    return (rows * _count(sizes, "mamba") * 2 * sizes["d_state"]
            * sizes["d_inner"] * 4)


def window_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of window ring a decode step must read ONCE: ``positions``
    (min(pos + 1, window) summed over the rows) of K and V, in every window
    layer."""
    return positions * kv_position_bytes(sizes) * _count(sizes, "swa")


def shared_kv_step_bytes(sizes: dict, positions: float) -> float:
    """Bytes of the full layer's K / V a decode step must read: ``positions``
    (pos + 1 summed over the rows) of K and V, once in the full layer and
    once in every cross-attention layer."""
    return positions * kv_position_bytes(sizes) * (1 + _count(sizes,
                                                              "xattn"))


def dense_q40_bytes(sizes: dict) -> int:
    """Packed Q40 bytes (18 B a block of 32, as ``costs.py`` counts a dense
    model's) of every matmul leaf a decode step reads whole: each layer's
    mixer and FFN leaves and the classifier."""
    kinds = kinds_of(sizes["n_layers"])
    values = sizes["vocab_size"] * sizes["dim"] + sum(
        shape[0] * shape[1] for kind in kinds
        for _, what, shape in layer_leaves(sizes, kind) if what == "mm")
    return values // 32 * 18


# -- what a device trace shows ---------------------------------------------------
# The reducer's ops carry the instruction's name and opcode only. Kernels are
# found by name: a ``pallas_call`` under a jitted wrapper is named after the
# wrapper. A layer's mixer by POSITION among the step's dense Q40 calls,
# which come in a fixed order: a Mamba layer's in_proj, [conv, projections,
# the scan,] out_proj, w13, w2; an attention layer's wqkv (or wq), [the
# attention kernel,] wo, w13, w2; a GMU's in_proj, out_proj, w13, w2: four a
# layer, and the classifier's one at the end. The mixer is everything from a
# layer's first call to its second, both included.

def _is(op, prefix: str) -> bool:
    return op.label == "custom-call" and op.name.lower().startswith(prefix)


def step_kernel_seconds(trace) -> list[dict]:
    """Per decode step of the traced window that ran the Mamba decode
    kernel (``reduce_trace.steps``; a step whose span held an admission
    shows the chunk program and is left out): seconds in that kernel, in
    the window layers' attention kernel, in the paged kernel (the full and
    the cross layers) and in the dense Q40 calls."""
    from . import reduce_trace as rt

    out = []
    for st in rt.steps(trace):
        ops = st["ops"]
        if any(_is(o, MAMBA_CHUNK) for o in ops):
            continue
        acc = {"ssm": 0.0, "window": 0.0, "paged": 0.0, "dense": 0.0}
        for o, s in zip(ops, rt.self_times(ops)):
            if _is(o, MAMBA_DECODE):
                acc["ssm"] += s / 1e9
            elif _is(o, WINDOW_KERNEL):
                acc["window"] += s / 1e9
            elif _is(o, PAGED_KERNEL):
                acc["paged"] += s / 1e9
            elif rt.classify(o) == "q40":
                acc["dense"] += s / 1e9
        if acc["ssm"] > 0:
            out.append(acc)
    return out


def kernel_calls(ops: list, name: str) -> list[float]:
    """Self seconds of each call of the kernel ``name`` among ``ops``."""
    from . import reduce_trace as rt

    return [s / 1e9 for o, s in zip(ops, rt.self_times(ops))
            if _is(o, name)]


def mixer_seconds(trace, sizes: dict, device: str | None = None) -> dict:
    """Self seconds, over every program run of the traced window on
    ``device`` (default: the first) that is a forward of this model (decode
    steps: 4 L + 1 dense Q40 calls; admission chunks: 4 a layer up to the
    full layer and its one ``wqkv``), of the mixers of the Mamba layers
    ("ssm"), of the window layers ("swa") and of the full layer and the
    cross-decoder after it ("xdec": layers 17 to 31's), each from the
    layer's first dense call to its second, both included."""
    from . import reduce_trace as rt

    out = {"ssm": 0.0, "swa": 0.0, "xdec": 0.0}
    if not trace.devices:
        return out
    kinds = kinds_of(sizes["n_layers"])
    full = kinds.index("full")
    group = {k: ("ssm" if k == "mamba" else "swa" if k == "swa" else "xdec")
             for k in KINDS}
    device = device or sorted(trace.devices)[0]
    ops = trace.devices[device]
    starts = [o.start for o in ops]
    for run in trace.modules.get(device, []):
        inside = ops[bisect.bisect_left(starts, run.start):
                     bisect.bisect_right(starts, run.end)]
        selfs = rt.self_times(inside)
        work = [i for i, o in enumerate(inside)
                if rt.classify(o) != "control"]
        dense = [i for i in work if rt.classify(inside[i]) == "q40"]
        if len(dense) == 4 * len(kinds) + 1:
            n_layers = len(kinds)
        elif len(dense) == 4 * full + 1:
            n_layers = full           # a chunk: the full layer's wqkv ends it
        else:
            continue
        for layer in range(n_layers):
            lo, hi = dense[4 * layer], dense[4 * layer + 1]
            out[group[kinds[layer]]] += sum(
                selfs[i] for i in work if lo <= i <= hi)
    return {k: v / 1e9 for k, v in out.items()}
