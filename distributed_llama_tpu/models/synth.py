"""Seeded synthetic parameter trees for tests, dryruns, and benches.

Mirrors the shape/layout contract of io.loader.load_model: per-layer matmul
weights stacked along a leading layer axis, Q40 weights as codec-layout
Q40Weight pairs (kernel re-tiling happens downstream in params_to_device /
shard_params, like for file-loaded weights).
"""

from __future__ import annotations

import functools as _functools

import numpy as np

from ..io.loader import Q40Weight
from ..ops.quants import quantize_q40
from .spec import TransformerSpec


def _build_tree(spec: TransformerSpec, t, mm, tie=None) -> dict:
    """Assemble the param tree from a dense builder ``t`` and a matmul-weight
    builder ``mm`` — the one place that knows the tree's key set. ``tie``
    (a hybrid spec's tied classifier) turns the embedding into a matmul
    weight; without it the classifier is drawn like any other."""
    if spec.planned:
        return _build_planned_tree(spec, t, mm, tie)
    # draw order is part of the seed's meaning: a dense spec's tree is the
    # one it always was (rms_att, rms_ffn before wcls)
    p = {"tok_embedding": t(spec.vocab_size, spec.dim),
         "rms_final": 1 + t(spec.dim)}
    for name, n in spec.layer_norm_shapes():
        p[name] = 1 + t(spec.n_layers, n)
    p["wcls"] = mm(spec.vocab_size, spec.dim)
    for name, shape in spec.layer_matmul_shapes():
        p[name] = mm(spec.n_layers, *shape)
    for name, shape in spec.expert_matmul_shapes():
        p[name] = mm(spec.n_layers, spec.n_experts, *shape)
    if spec.retention:
        # gate rows ~N(0, 1/sqrt(dim)), no bias: g = sigmoid(.) sits near
        # 0.5, so a seeded model remembers a few tokens (a step costs the
        # same whatever g is; tests that compare a long memory hand the
        # kernels their gates)
        p["w_gate"] = (t(spec.n_layers, *spec.gate_shape)
                       * np.float32(20.0 / np.sqrt(spec.dim)))
    if spec.n_experts:
        # router rows ~N(0, 1/sqrt(dim)): t() draws at std 0.05
        p["moe_gate"] = (t(spec.n_layers, spec.n_experts, spec.dim)
                         * np.float32(20.0 / np.sqrt(spec.dim)))
    return p


def hybrid_leaf(spec: TransformerSpec, name: str, shape, unit) -> np.ndarray:
    """A hybrid spec's float32 leaf ``name`` (any leading layer axes in
    ``shape``) from ``unit(*shape)`` ~ N(0, 1), as the family initialises
    it, so that a seeded state neither dies in a token nor blows up:
    ``a_log`` = log(1..d_state); ``dt_b`` such that softplus gives 1e-3 to
    1e-1, spread log-evenly over the channels; ``d_skip`` = 1; lambdas
    N(0, 0.1); ``x_proj`` rows ~N(0, 1/sqrt(d_inner)), ``dt_proj``
    ~N(0, 1/sqrt(dt_rank)), conv taps ~N(0, 1/2); gains 1 +- 0.05, biases
    +- 0.05."""
    hy = spec.hybrid
    if name == "a_log":
        a = np.log(np.arange(1, hy.d_state + 1, dtype=np.float32))
        return np.broadcast_to(a[:, None], shape).copy()
    if name == "dt_b":
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), shape[-1]))
        return np.broadcast_to((dt + np.log(-np.expm1(-dt))).astype(
            np.float32), shape).copy()
    if name == "d_skip":
        return np.ones(shape, np.float32)
    x = unit(*shape)
    scale = {"lam": 0.1, "x_proj": hy.d_inner ** -0.5,
             "dt_proj": hy.dt_rank ** -0.5, "conv_w": 0.5}.get(name, 0.05)
    x = x * np.float32(scale)
    return x + np.float32(1) if name in ("ln1_g", "ln2_g", "subln") else x


SSD_LEAVES = ("in_dt", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
              "norm_g")


def ssd_leaf(spec: TransformerSpec, name: str, shape, unit) -> np.ndarray:
    """A Mamba-2 layer's float32 leaf ``name`` of an ssd spec (any leading
    layer axes in ``shape``) from ``unit(*shape)`` ~ N(0, 1), as the family
    initialises it: ``a_log`` = log of 1 .. 16 spread evenly over the heads
    (a head's scalar decay); ``dt_bias`` such that softplus gives 1e-3 to
    1e-1, spread log-evenly over the heads; ``d_skip`` = 1; ``in_dt`` rows
    ~N(0, 1/sqrt(dim)); conv taps ~N(0, 1/2), their bias +- 0.05; the
    gated norm's gain 1 +- 0.05."""
    if name == "a_log":
        a = np.log(np.linspace(1.0, 16.0, shape[-1], dtype=np.float32))
        return np.broadcast_to(a, shape).copy()
    if name == "dt_bias":
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), shape[-1]))
        return np.broadcast_to((dt + np.log(-np.expm1(-dt))).astype(
            np.float32), shape).copy()
    if name == "d_skip":
        return np.ones(shape, np.float32)
    x = unit(*shape) * np.float32(
        {"in_dt": spec.dim ** -0.5, "conv_w": 0.5}.get(name, 0.05))
    return x + np.float32(1) if name == "norm_g" else x


KDA_LEAVES = ("conv_w", "a_log", "dt_bias", "w_beta", "ffn_limit")


def kda_leaf(spec: TransformerSpec, name: str, shape, unit) -> np.ndarray:
    """A float32 leaf of a kda spec (any leading layer axes in ``shape``)
    from ``unit(*shape)`` ~ N(0, 1): ``a_log`` = log of 0.5 .. 2 spread
    evenly over the heads and ``dt_bias`` -3 .. 3 spread evenly over the
    heads' channels, so that the decay's exponent ``lower_bound *
    sigmoid(exp(a_log) (W_a h + dt_bias))`` covers [lower_bound, 0) over
    the heads (the first forget slowly, the last at once); conv taps ~N(0,
    1/2); ``w_beta`` rows ~N(0, 1/sqrt(dim)) (the write strength spreads
    around 0.5); ``ffn_limit``: 0 (no clamp) in the first two thirds of the
    expert layers, then (0.5, 0.75) for the routed and the shared experts,
    where seeded projections ~N(0, 1) meet them (ONE layer's, where the
    caller has no stack: no clamp)."""
    if name == "a_log":
        a = np.log(np.linspace(0.5, 2.0, shape[-1], dtype=np.float32))
        return np.broadcast_to(a, shape).copy()
    if name == "dt_bias":
        return np.broadcast_to(np.linspace(-3.0, 3.0, shape[-1],
                                           dtype=np.float32), shape).copy()
    if name == "ffn_limit":
        out = np.zeros(shape, np.float32)
        if len(shape) > 1:
            out[-(-2 * shape[0] // 3):] = (0.5, 0.75)
        return out
    return unit(*shape) * np.float32(
        {"w_beta": spec.dim ** -0.5, "conv_w": 0.5}[name])


def hyper_leaf(spec: TransformerSpec, name: str, shape, unit) -> np.ndarray:
    """A float32 leaf ``hc_<sub>_<phi|gate|bias>`` of a spec with several
    residual streams (any leading layer axes in ``shape``) from
    ``unit(*shape)`` ~ N(0, 1): ``phi`` rows N(0, 1/sqrt(n dim)), so that a
    unit-RMS ``xhat`` projects to N(0, 1); gates 0.5, so that the per-token
    part moves the coefficients and is no constant in disguise; ``b_pre``
    and ``b_post`` N(0, 1); ``B_res`` 4 x identity + N(0, 1) (a mix that
    keeps most of a stream and some of the others)."""
    n = spec.hyper.streams
    if name.endswith("_gate"):
        return np.full(shape, 0.5, np.float32)
    x = unit(*shape).astype(np.float32)
    if name.endswith("_phi"):
        return x * np.float32((n * spec.dim) ** -0.5)
    x[..., 2 * n:] += np.float32(4.0) * np.eye(n, dtype=np.float32).reshape(-1)
    return x


def polynorm_leaf(shape, unit) -> np.ndarray:
    """A layer's ``pn_w`` (..., 4) from ``unit(*shape)`` ~ N(0, 1): the three
    weights 1/3 + N(0, 0.1), the bias N(0, 0.3), so that a clamp at 0.5
    bites in some layers and not in others."""
    x = unit(*shape).astype(np.float32)
    x[..., :3] = np.float32(1 / 3) + np.float32(0.1) * x[..., :3]
    x[..., 3] *= np.float32(0.3)
    return x


def _build_planned_tree(spec: TransformerSpec, t, mm, tie=None) -> dict:
    """``_build_tree`` for a spec with several stacks of layers (an expert
    spec's leading dense ones under ``p["dense"]``, a hybrid spec's kinds
    each under its name): the keys and shapes are ``spec.layer_plans``'s.
    Router rows ~N(0, 1/sqrt(dim)); its bias ~N(0, 0.05), so that the choice
    (on s + b) and the weights (on s) differ."""
    from ..io.loader import stack_of

    p = {"tok_embedding": t(spec.vocab_size, spec.dim),
         "rms_final": 1 + t(spec.dim)}
    if spec.hybrid:
        p["rms_final_b"] = t(spec.dim)
    p["wcls"] = (tie(p["tok_embedding"]) if spec.hybrid and tie
                 else mm(spec.vocab_size, spec.dim))
    for stack, name, kind, shape in spec.stack_leaves():
        dst = stack_of(p, stack)
        if kind == "mm":
            dst[name] = mm(*shape)
        elif spec.hybrid:
            dst[name] = hybrid_leaf(spec, name, shape,
                                    lambda *s: t(*s) * np.float32(20.0))
        elif spec.ssd and name in SSD_LEAVES:
            dst[name] = ssd_leaf(spec, name, shape,
                                 lambda *s: t(*s) * np.float32(20.0))
        elif spec.kda and name in KDA_LEAVES:
            dst[name] = kda_leaf(spec, name, shape,
                                 lambda *s: t(*s) * np.float32(20.0))
        elif name.startswith("hc_"):
            dst[name] = hyper_leaf(spec, name, shape,
                                   lambda *s: t(*s) * np.float32(20.0))
        elif name == "pn_w":
            dst[name] = polynorm_leaf(shape,
                                      lambda *s: t(*s) * np.float32(20.0))
        elif name in ("moe_gate", "w_hgate", "w_lambda"):
            # rows ~N(0, 1/sqrt(dim)): a head's gate sigmoid(.) (a signal
            # head's lambda) then spreads around 0.5 and no seeded head is
            # shut or idle
            dst[name] = t(*shape) * np.float32(20.0 / np.sqrt(spec.dim))
        elif name == "moe_bias":
            dst[name] = t(*shape)
        else:
            dst[name] = 1 + t(*shape)
    return p


def synth_q40_fast(spec: TransformerSpec, seed: int = 0) -> dict:
    """Random Q40 params built directly as packed bytes — for benchmarks.

    Skips the float-generate + quantize pass (minutes for 7B in numpy):
    decode TIMING is value-independent, so random nibble codes + small
    positive f16 deltas give the exact memory layout and dataflow of real
    weights at negligible synthesis cost. Not for numerics tests.
    """
    rng = np.random.default_rng(seed)

    def t(*shape):
        return (rng.standard_normal(shape) * 0.05).astype(np.float32)

    def mm(*shape):
        *lead, d, n = shape
        qs = rng.integers(0, 256, (*lead, d, n // 32, 16), dtype=np.uint8)
        d16 = (rng.random((*lead, d, n // 32), dtype=np.float32)
               * 0.01 + 1e-4).astype(np.float16)
        return Q40Weight(qs, d16)

    return _build_tree(spec, t, mm)


def device_params_like(tree, seed: int = 0):
    """Rebuild ``tree`` as ON-DEVICE arrays of the same shapes/dtypes with
    synthetic values — no host->device transfer of the actual bytes.

    Why this exists: a synthetic bench needs the tree's shapes, layouts and
    dataflow, not its values (module docstring), so generating them on
    device skips both the GB-scale host synthesis and the host->device
    upload. Real --model runs pay the honest upload (their bytes exist only
    on the host).

    ONE jitted program generates the whole tree (module-level cache per
    distinct shape/dtype signature — repeat calls in one process reuse the
    trace): a cold process pays a single generator compile instead of one
    per leaf.
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sig = tuple(
        (tuple(leaf.shape),
         str(np.asarray(leaf).dtype if not hasattr(leaf, "dtype")
             else leaf.dtype))
        for leaf in leaves)
    out = _gen_all(sig)(np.uint32(seed))
    return jax.tree_util.tree_unflatten(treedef, out)


def _gen_leaf(shape, dt, s):
    import jax
    import jax.numpy as jnp

    key = jax.random.key(s)
    if dt == jnp.dtype(jnp.uint8):
        return jax.random.bits(key, shape, jnp.uint8)
    if jnp.issubdtype(dt, jnp.floating):
        # small positive values: safe for every leaf role (Q40 scales
        # must be positive; norm gains near small values are fine;
        # magnitudes never reach inf/nan paths)
        return (jax.random.uniform(key, shape, jnp.float32)
                * 0.01 + 1e-4).astype(dt)
    return jnp.zeros(shape, dt)


@_functools.lru_cache(maxsize=None)
def _gen_all(sig):
    """jit'd whole-tree generator for one (shape, dtype) signature."""
    import jax
    import jax.numpy as jnp

    def gen(s0):
        return [_gen_leaf(shape, jnp.dtype(dtype), s0 + i)
                for i, (shape, dtype) in enumerate(sig)]

    return jax.jit(gen)


def synth_params(spec: TransformerSpec, q40: bool, seed: int = 0,
                 scale: float = 0.05) -> dict:
    rng = np.random.default_rng(seed)

    def t(*shape):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    def mm(*shape):
        x = t(*shape)
        if not q40:
            return x
        qs, d16 = quantize_q40(x)
        return Q40Weight(qs, d16)

    def tie(x):
        return Q40Weight(*quantize_q40(x)) if q40 else x

    return _build_tree(spec, t, mm, tie)


def llama2_7b_spec(**overrides) -> TransformerSpec:
    """The Llama-2-7B shape (converter header values) at Q40 — THE benchmark
    config, shared by bench.py and the tools so a shape correction happens
    in exactly one place."""
    from ..ops.quants import FloatType

    kw = dict(dim=4096, hidden_dim=11008, n_layers=32, n_heads=32,
              n_kv_heads=32, vocab_size=32000, seq_len=2048,
              weights_float_type=FloatType.Q40)
    kw.update(overrides)
    return TransformerSpec(**kw)


def llama2_13b_spec(**overrides) -> TransformerSpec:
    """Llama-2-13B shape (params.json: dim 5120, 40 layers/heads, MHA).
    Q40 kernel-layout ~8.0 GB — fits a 16 GB v5e chip whole, so this rounds
    out the measured ladder against the reference's 13B rows
    (README.md:47, best 848.19 ms/token)."""
    from ..ops.quants import FloatType

    kw = dict(dim=5120, hidden_dim=13824, n_layers=40, n_heads=40,
              n_kv_heads=40, vocab_size=32000, seq_len=2048,
              weights_float_type=FloatType.Q40)
    kw.update(overrides)
    return TransformerSpec(**kw)


def llama2_70b_spec(**overrides) -> TransformerSpec:
    """Llama-2-70B shape (dim 8192, 80 layers, GQA 64q/8kv, hidden 28672) —
    the north-star config (BASELINE.json). Whole-model Q40 is ~38.7 GB: runs
    only sharded; one tp=8 rank's bands (~5 GB) fit one chip
    (parallel/shard_sim.py)."""
    from ..ops.quants import FloatType

    kw = dict(dim=8192, hidden_dim=28672, n_layers=80, n_heads=64,
              n_kv_heads=8, vocab_size=32000, seq_len=2048,
              weights_float_type=FloatType.Q40)
    kw.update(overrides)
    return TransformerSpec(**kw)


def small_bench_spec(**overrides) -> TransformerSpec:
    """Tiny Q40 config for CI/CPU smoke runs of the benchmarks."""
    from ..ops.quants import FloatType

    kw = dict(dim=256, hidden_dim=704, n_layers=4, n_heads=4, n_kv_heads=4,
              vocab_size=1024, seq_len=256,
              weights_float_type=FloatType.Q40)
    kw.update(overrides)
    return TransformerSpec(**kw)


def write_synth_q40_model(path: str, spec: TransformerSpec,
                          seed: int = 0) -> int:
    """Stream a seeded random Q40 ``.bin`` of ``spec`` to ``path`` as packed
    wire bytes, one tensor at a time; returns the byte count (asserted ==
    ``spec.file_size()``).

    ``io.loader.write_model`` quantizes an f32 tree (~26 GB of host memory
    at 7B); this writes the 18-byte blocks directly — random nibble codes
    plus an f16 delta sized so a (d, n) tensor has value std ~ 1/sqrt(n) —
    and never holds more than one tensor. Values are chosen to keep a real
    forward pass sane, not just its timing: unit-RMS activations through
    every matmul, scores ~N(0, 1) into the softmax, logits ~N(0, 1). The
    classifier's BOS row gets zero deltas (logit exactly 0, never the
    argmax), so a greedy stream cannot end early on a sampled BOS.
    """
    import os

    from ..io.tokenizer import BOS

    if spec.weights_float_type.name != "Q40":
        raise ValueError("write_synth_q40_model writes Q40 matmul weights")
    rng = np.random.default_rng(seed)

    def f32(*shape, base=0.0, scale=1.0):
        x = rng.standard_normal(shape, dtype=np.float32)
        if (base, scale) != (0.0, 1.0):
            x = (base + scale * x).astype(np.float32)
        return memoryview(x).cast("B")

    def q40(d, n, zero_row=None):
        nb = n // 32
        # whole 18-byte blocks of random bytes, then the 2 delta bytes on
        # top (one small strided write instead of a 16-column one)
        blocks = rng.integers(0, 256, (d * nb, 18), dtype=np.uint8)
        # nibble 0 (value -8) becomes 8 (value 0): values are symmetric on
        # -7..7. A uniform 0..15 code has mean -0.5, a rank-1 all-ones
        # component in every matrix that swamps the signal within a few
        # layers — the argmax then ignores the input, and a greedy stream
        # would agree across kernels whatever they computed
        blocks |= ((blocks & 0x0F) == 0).astype(np.uint8) << 3
        blocks |= ((blocks & 0xF0) == 0).astype(np.uint8) << 7
        # std of that value distribution is 4.18 per unit delta
        delta = ((0.5 + rng.random(d * nb, dtype=np.float32))
                 / (4.18 * np.sqrt(n))).astype(np.float16)
        if zero_row is not None:
            delta[zero_row * nb:(zero_row + 1) * nb] = 0
        blocks[:, :2] = delta.view(np.uint8).reshape(-1, 2)
        return memoryview(blocks).cast("B")

    with open(path, "wb") as f:
        f.write(spec.header())
        f.write(f32(spec.vocab_size, spec.dim))
        for _, _, entries in (spec.layer_plans() if spec.planned else ()):
            for kind, name, shape, *_ in entries:
                if kind == "mm":
                    f.write(q40(*shape))
                elif spec.hybrid:
                    f.write(memoryview(np.ascontiguousarray(hybrid_leaf(
                        spec, name, shape, lambda *s: rng.standard_normal(
                            s, dtype=np.float32)))).cast("B"))
                elif spec.ssd and name in SSD_LEAVES:
                    f.write(memoryview(np.ascontiguousarray(ssd_leaf(
                        spec, name, shape, lambda *s: rng.standard_normal(
                            s, dtype=np.float32)))).cast("B"))
                elif spec.kda and name in KDA_LEAVES:
                    f.write(memoryview(np.ascontiguousarray(kda_leaf(
                        spec, name, shape, lambda *s: rng.standard_normal(
                            s, dtype=np.float32)))).cast("B"))
                elif name.startswith("hc_"):
                    f.write(memoryview(np.ascontiguousarray(hyper_leaf(
                        spec, name, shape, lambda *s: rng.standard_normal(
                            s, dtype=np.float32)))).cast("B"))
                elif name == "pn_w":
                    f.write(memoryview(polynorm_leaf(
                        shape, lambda *s: rng.standard_normal(
                            s, dtype=np.float32))).cast("B"))
                elif name in ("moe_gate", "w_hgate", "w_lambda"):
                    f.write(f32(*shape, scale=1.0 / np.sqrt(spec.dim)))
                elif name == "moe_bias":
                    f.write(f32(*shape, scale=0.05))
                else:
                    f.write(f32(*shape, base=1.0, scale=0.05))
        for _ in range(0 if spec.planned else spec.n_layers):
            for _, n in spec.layer_norm_shapes():   # rms_att, rms_ffn, ...
                f.write(f32(n, base=1.0, scale=0.05))
            for name, (d, n) in spec.layer_matmul_shapes():
                f.write(q40(d, n))
                if spec.retention and name == "wo":     # gate rows, F32
                    f.write(f32(*spec.gate_shape,
                                scale=1.0 / np.sqrt(spec.dim)))
            if spec.n_experts:                      # router rows, F32
                f.write(f32(spec.n_experts, spec.dim,
                            scale=1.0 / np.sqrt(spec.dim)))
            for _ in range(spec.n_experts):
                for _, (d, n) in spec.expert_matmul_shapes():
                    f.write(q40(d, n))
        f.write(f32(spec.dim, base=1.0, scale=0.05))       # rms_final
        if spec.hybrid:
            f.write(f32(spec.dim, scale=0.05))             # its bias
        f.write(b"\x00" * spec.rope_gap_bytes)
        f.write(q40(spec.vocab_size, spec.dim, zero_row=BOS))
    size = os.path.getsize(path)
    assert size == spec.file_size(), (size, spec.file_size())
    return size


def write_synth_tokenizer(path: str, vocab_size: int) -> None:
    """A llama2.c-format tokenizer of ``vocab_size`` entries to go with a
    synthetic model: the three specials, the 256 byte-fallback tokens, the
    dummy-prefix space, printable ASCII as single-character pieces, then
    unique filler. Flat scores — no merges — so a prompt encodes to BOS,
    the space, and one token per character."""
    from ..io.tokenizer import write_tokenizer

    pieces = [b"<unk>", b"<s>", b"</s>"]
    pieces += [f"<0x{i:02X}>".encode() for i in range(256)]
    pieces += [bytes([c]) for c in range(32, 127)]
    if vocab_size < len(pieces):
        raise ValueError(f"vocab_size {vocab_size} < the {len(pieces)} "
                         f"reserved pieces")
    pieces += [f"<filler{i}>".encode() for i in range(len(pieces),
                                                      vocab_size)]
    write_tokenizer(path, pieces, [0.0] * vocab_size)
