"""The main path's kernels, compiled by the chip's own compiler at
Llama-2-7B width — without a chip.

libtpu compiles for a TPU that is described and not attached
(``jax.experimental.topologies``), so these cases raise here exactly what the
chip's compiler would raise: a slice not aligned to the HBM tiling, a shape
cast the layout pass refuses, an argument dtype the chip does not take, a
scoped-VMEM overflow. Interpret-mode tests (the rest of the kernel suites) can
see none of that — the bf16-cache prefill kernel and the q8 paged kernel
passed every one of them and were both refused by this compiler.

Nothing runs and nothing is timed: a compile that passes is not a chip run.
``chip_smoke.py`` is the chip run.

Each case is one kernel entry at ``interpret=False`` on ShapeDtypeStructs
pinned to one described v5e device, and asserts the Mosaic custom call is in
the compiled program. Cases stay near a second each (tier-1 budget; the
page-size-128 x t_len-4 paged case took ~50 s on the vector-unit fold and was
left out until PR 47: seconds on the MXU fold). The two whole programs at the
end (the tp=4 step and prefill chunk at Yi-34B's shard-local widths, about
5 s each) guard what no single kernel shows: a weight-sized layout copy in
the entry computation.
"""

import functools
import os
import re

import pytest

# the described-chip compiler logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

DIM, HIDDEN, VOCAB, SEQ = 4096, 11008, 32000, 2048
N_KV, HS = 32, 128
# (d, n) of the 7B matmul leaves as the single-chip tree holds them
# (ops/linear.fuse_q40_layer_matmuls: wq stands for the d=4096 leaves)
LEAVES = {"wq": (DIM, DIM), "w13": (2 * HIDDEN, DIM), "w2": (DIM, HIDDEN),
          "wcls": (VOCAB, DIM),
          # Mistral-7B's FFN (hidden 14336: w2 has 448 blocks a row), the
          # leaves the two 8-slot serving cells stream
          "m-w13": (2 * 14336, DIM), "m-w2": (DIM, 14336),
          # DeepSeek-V3's new leaf shapes (in 7168: 224 blocks a row): the
          # latent row's projection padded from 576 to 640 outputs, q_b
          # (48 blocks), wo (512) and the dense layer's w2 (576 blocks)
          "ds-wkv_a": (640, 7168), "ds-wq_b": (24576, 1536),
          "ds-wo": (7168, 16384), "ds-w2": (7168, 18432),
          # Phi-4-mini-flash's (dim 2560: 80 blocks a row; d_inner 5120:
          # 160; FFN 10240: 320): a Mamba layer's in_proj and out_proj (a
          # GMU's are the halves), wqkv, wo / wq, fc1 and fc2
          "ph-in_proj": (10240, 2560), "ph-out_proj": (2560, 5120),
          "ph-wqkv": (5120, 2560), "ph-wo": (2560, 2560),
          "ph-w13": (20480, 2560), "ph-w2": (2560, 10240),
          # Xing4.0-29B-A4B's (dim 3584: 112 blocks a row; q rank 768: 24;
          # dense FFN 9216: 288; shared expert 1024: 32; wo's 4096: 128)
          "x4-wq_a": (768, 3584), "x4-wq_b": (6144, 768),
          "x4-wkv_a": (640, 3584), "x4-wo": (3584, 4096),
          "x4-w13": (18432, 3584), "x4-w2": (3584, 9216),
          "x4-sh_w13": (2048, 3584), "x4-sh_w2": (3584, 1024),
          "x4-wcls": (131072, 3584),
          # Mistral-7B's fused wqkv; Yi-34B's tp-4 shards (dim 7168: wo's
          # shard has 56 blocks a row, w2's 160; a KV projection 256 rows);
          # Brumby-14B's classifier (151,936 rows, whose only row tile is
          # 128: 1187 is prime)
          "m-wqkv": (6144, DIM),
          "yi-wq": (1792, 7168), "yi-wk": (256, 7168), "yi-wo": (7168, 1792),
          "yi-w1": (5120, 7168), "yi-w2": (7168, 5120),
          "yi-wcls": (16000, 7168),
          # ... and what shard_params fuses a rank since PR 56: [q_r | k_r |
          # v_r] (three tiles of 768 at one row) and [w1_r | w3_r] (ten of
          # 1,024)
          "yi-wqkv": (2304, 7168), "yi-w13": (10240, 7168),
          "br-wcls": (151936, 5120),
          # ... and its ``w2`` (FFN 17408: 544 blocks a row, 17 turns of 32)
          "br-w2": (5120, 17408),
          # Laguna-XS.2's (dim 2048: 64 blocks a row; a full layer's 48
          # heads and a sliding layer's 64 over 8 KV heads of 128: wqkv of
          # 8192 and 10240 rows, wo of 192 and 256 blocks; the dense FFN
          # 8192; the shared expert 512: 16 blocks)
          "lg-f-wqkv": (8192, 2048), "lg-s-wqkv": (10240, 2048),
          "lg-f-wo": (2048, 6144), "lg-s-wo": (2048, 8192),
          "lg-w13": (16384, 2048), "lg-w2": (2048, 8192),
          "lg-sh_w13": (1024, 2048), "lg-sh_w2": (2048, 512),
          "lg-wcls": (100352, 2048)}


def _sd(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off: an
    executable compiled for a described chip is written to the cache but
    cannot be read back without a chip (it warns and recompiles)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / no topology support
        pytest.skip(f"cannot describe a v5e:2x2 topology here "
                    f"({type(e).__name__}: {e})")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One device of the described v5e:2x2."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _q40(layout: str, leaf: str, t: int):
    """q40_matmul on the stacked (scalar-prefetch) form the layer scan runs;
    wcls is the one unstacked leaf. ``i4`` converts inside the program, as
    the decode chain does (ops/pallas_q40.chain_weight_prep)."""
    from distributed_llama_tpu.io.loader import Q40Kernel, Q40KernelNb
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul, to_i4_planes

    d, n = LEAVES[leaf]
    nb = n // 32
    lead = () if leaf.endswith("wcls") else (2,)
    if layout == "d":
        w = Q40Kernel(_sd((*lead, 16, d, nb), jnp.uint8),
                      _sd((*lead, d, nb), jnp.float32))
    else:
        w = Q40KernelNb(_sd((*lead, 16, nb, d), jnp.uint8),
                        _sd((*lead, nb, d), jnp.float32))

    def fn(w, x, layer):
        if layout == "i4":
            w = to_i4_planes(w)
        return q40_matmul(w, x, interpret=False,
                          layer=layer if lead else None)

    return fn, (w, _sd((t, n), jnp.float32), _sd((), jnp.int32))


def _decode(dtype, n_kv: int = N_KV, kv_mul: int = 1, seq: int = SEQ):
    from distributed_llama_tpu.ops.pallas_attention import decode_attention

    cache = _sd((2, seq, n_kv, HS), dtype)
    return (functools.partial(decode_attention, kv_mul=kv_mul,
                              interpret=False),
            (_sd((n_kv * kv_mul, HS), jnp.float32), cache, cache,
             _sd((), jnp.int32), _sd((), jnp.int32)))


def _decode_batch():
    from distributed_llama_tpu.ops.pallas_attention import (
        decode_attention_batch)

    b = 8
    cache = _sd((2 * b, SEQ, N_KV, HS), jnp.float32)
    return (functools.partial(decode_attention_batch, kv_mul=1,
                              interpret=False),
            (_sd((b, N_KV, HS), jnp.float32), cache, cache,
             _sd((), jnp.int32), _sd((b,), jnp.int32)))


def _prefill(dtype):
    from distributed_llama_tpu.ops.pallas_attention import prefill_attention

    cache = _sd((SEQ, N_KV, HS), dtype)
    return (functools.partial(prefill_attention, kv_mul=1, interpret=False),
            (_sd((128, N_KV, HS), jnp.float32), cache, cache,
             _sd((), jnp.int32)))


def _paged(dtype, t_len: int, ps: int = 16, n_kv: int = N_KV,
           kv_mul: int = 1, b: int = 8):
    from distributed_llama_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_kernel)

    pages = 64
    pool = _sd((2 * pages, ps, n_kv, HS), dtype)
    return (functools.partial(paged_decode_attention_kernel, page_size=ps,
                              n_pages=pages, kv_mul=kv_mul, t_len=t_len,
                              interpret=False),
            (_sd((b, t_len, n_kv * kv_mul * HS), jnp.float32), pool, pool,
             _sd((), jnp.int32), _sd((b,), jnp.int32),
             _sd((b, SEQ // ps), jnp.int32)))


def _paged_q8(ps: int = 16, t_len: int = 1, n_kv: int = N_KV,
              kv_mul: int = 1):
    from distributed_llama_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_kernel_q8)

    b, pages = 8, 64
    codes = _sd((2 * pages, ps, n_kv, HS), jnp.int8)
    deltas = _sd((2 * pages, ps, n_kv * HS // 32), jnp.float16)
    return (functools.partial(paged_decode_attention_kernel_q8, page_size=ps,
                              n_pages=pages, kv_mul=kv_mul, t_len=t_len,
                              interpret=False),
            (_sd((b, t_len, n_kv * kv_mul * HS), jnp.float32), codes, deltas,
             codes, deltas, _sd((), jnp.int32), _sd((b,), jnp.int32),
             _sd((b, SEQ // ps), jnp.int32)))


def _latent_paged(ps: int = 16):
    """The latent decode kernel (ops/pallas_latent_attention) at
    DeepSeek-V3's widths: 32 rows of 128 heads over pages of 16 positions
    of ONE plane, 576 values in 640 lanes, two layers' pool."""
    from distributed_llama_tpu.ops.pallas_latent_attention import (
        latent_paged_decode)

    b, pages, heads, plane = 32, 64, 128, 640
    return (functools.partial(latent_paged_decode, page_size=ps,
                              n_pages=pages, kv_rank=512, interpret=False),
            (_sd((b, heads, plane), jnp.float32),
             _sd((2 * pages, ps, plane), jnp.float32), _sd((), jnp.int32),
             _sd((b,), jnp.int32), _sd((b, SEQ // ps), jnp.int32)))


def _latent_ring():
    """The latent ring kernel at Motif-3-Beta's shape: 32 rows of 80 heads
    over nine sliding layers' rings of 128 slots of 640 lanes (the fold's
    stacked pieces are 240, 160 and 80 rows)."""
    from distributed_llama_tpu.ops.pallas_latent_attention import (
        latent_ring_decode)

    b, heads, plane = 32, 80, 640
    return (functools.partial(latent_ring_decode, kv_rank=512,
                              interpret=False),
            (_sd((b, heads, plane), jnp.float32),
             _sd((9 * b, 128, plane), jnp.float32), _sd((), jnp.int32),
             _sd((b,), jnp.int32)))


def _moe(leaf: str, rows: int, model: str = "olmoe"):
    """The routed-expert kernels (ops/pallas_moe) at OLMoE-1B-7B's widths:
    64 experts a layer, ``w13`` (2 x 1024, 2048) and ``w2`` (2048, 1024),
    nb-major (``model`` "ds": DeepSeek-V3's, 32 held experts of width 2048
    on dim 7168): the slot call of a dispatch of ``rows`` rows (8 experts
    a row) at the capacity its shape gives: a decode step's, one row's, a
    128-row prefill chunk's (``w13``: the dispatch's rows and each slot's
    row list; ``w2``: each slot's own rows)."""
    from distributed_llama_tpu.ops import pallas_moe as pm

    n_exp, k = {"olmoe": (64, 8), "ds": (32, 8), "x4": (64, 4),
                "lg": (256, 8)}[model]
    d, n = {"olmoe": {"w13": (2048, 2048), "w2": (2048, 1024)},
            "ds": {"w13": (4096, 7168), "w2": (7168, 2048)},
            # Xing4.0's: 64 experts of width 1024 on dim 3584, 4 a row
            "x4": {"w13": (2048, 3584), "w2": (3584, 1024)},
            # Laguna-XS.2's: 256 experts of width 512 on dim 2048, 8 a row
            "lg": {"w13": (1024, 2048), "w2": (2048, 512)}}[model][leaf]
    nb = n // 32
    qs_t = _sd((2, n_exp, 16, nb, d), jnp.uint8)
    scale = _sd((2, n_exp, nb, d), jnp.float32)
    cap = pm.slot_cap(rows, k, n_exp)
    a = pm.max_slots(rows, k, n_exp, cap)
    fn = functools.partial(pm.moe_q40_slots, interpret=False,
                           block_rows=pm._slot_block_rows(d, nb))
    xs = ((_sd((rows, n), jnp.float32), _sd((a, cap), jnp.int32))
          if leaf == "w13" and rows > 32 else
          (_sd((a, cap, n), jnp.float32),))
    return fn, (_sd((1,), jnp.int32), _sd((a,), jnp.int32),
                _sd((), jnp.int32), _sd((a,), jnp.int32), qs_t, scale, *xs)


def _hc_coefficients(rows: int):
    """The residual path's coefficient stage (ops/hyper: plain XLA, no
    kernel) at Xing4.0's widths: 4 streams of 3584, a decode step's 32
    rows, one row, a chunk's 128."""
    from distributed_llama_tpu.models.spec import HyperConnections
    from distributed_llama_tpu.ops.hyper import coefficients

    fn = functools.partial(coefficients, HyperConnections(4), 1e-6)
    return fn, (_sd((24, 4 * 3584), jnp.float32), _sd((3,), jnp.float32),
                _sd((24,), jnp.float32), _sd((4, rows, 3584), jnp.float32))


def _q40_wide_w2():
    """The stacked nb-major matvec at hidden 17408 -> dim 5120."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    d, nb = 5120, 17408 // 32
    fn = functools.partial(pq._q40_matvec_nb_stacked, interpret=False,
                           block_rows=pq._pick_rows_t1(d, nb))
    return fn, (_sd((1,), jnp.int32), _sd((2, 16, nb, d), jnp.uint8),
                _sd((2, nb, d), jnp.float32), _sd((1, nb * 32), jnp.float32))


def _retention(kind: str):
    """The power-retention kernels (ops/retention) at Brumby-14B's widths:
    head size 128 (65 offsets a KV head), 8 KV heads, 5 query heads a
    state, two layers stacked. ``decode``: a step of 16 rows; ``chunk``: a
    128-token chunk of one sequence. The state is aliased in both."""
    from distributed_llama_tpu.ops import retention as rt

    d, m, n_kv, rows, t, layers = 128, 5, 8, 16, 128, 2
    n_off = rt.n_offsets(d)
    f32 = functools.partial(_sd, dtype=jnp.float32)
    layer = _sd((1,), jnp.int32)
    if kind == "decode":
        r = rows * n_kv
        fn = functools.partial(rt.retention_decode_step, interpret=False)
        return fn, (layer, f32((layers * r, n_off, d, d)),
                    f32((layers * r, n_off, d)), f32((r, m, n_off, d)),
                    f32((r, n_off, d)), f32((r, 8, d)))
    mt = m * t
    fn = functools.partial(rt.retention_prefill_chunk, interpret=False)
    return fn, (layer, f32((layers * n_kv, n_off, d, d)),
                f32((layers * n_kv, n_off, d)), f32((n_kv, n_off, mt, d)),
                f32((n_kv, n_off, t, d)), f32((n_kv, mt, d)),
                f32((n_kv, t, d)), f32((n_kv, t, d)), f32((n_kv, d, t)),
                f32((n_kv, 8, t)), f32((n_kv, mt, t)), f32((n_kv, mt, d)),
                f32((n_kv, 8, d)))


def _mamba(kind: str):
    """The state-space kernels (ops/mamba) at Phi-4-mini-flash's widths:
    d_inner 5120, d_state 16, nine layers' state stacked. ``decode``: a
    step of 32 rows; ``chunk``: a 128-token chunk of one sequence. The
    state is aliased in both."""
    from distributed_llama_tpu.ops import mamba

    di, ds, rows, t, layers = 5120, 16, 32, 128, 9
    f32 = functools.partial(_sd, dtype=jnp.float32)
    if kind == "decode":
        fn = functools.partial(mamba.mamba_decode_step, interpret=False)
        return fn, (_sd((1,), jnp.int32), f32((layers * rows, ds, di)),
                    f32((ds, di)), f32((rows, 8, di)),
                    f32((rows, 2 * ds, 128)))
    fn = functools.partial(mamba.mamba_prefill_chunk, interpret=False)
    return fn, (_sd((2,), jnp.int32), f32((layers, ds, di)), f32((ds, di)),
                f32((t, di)), f32((t, di)), f32((t, ds, 128)),
                f32((t, ds, 128)))


def _diff_attention(kind: str):
    """Differential attention through the head-major softmax kernels
    (ops/pallas_head_major_attention) at Phi-4-mini-flash's widths: 40
    padded query heads over 10 KV pairs of 128, 32 rows, the fold's two
    contractions on the MXU in float32 pieces (PR 40). One case a chunk the
    kernels pick from the shape: ``window``: eight layers' rings of 512
    slots (four chunks of 128); ``rows``: one sequence's contiguous K / V of
    8,704 positions (``inference``: chunks of 512); ``paged``: ONE layer's
    pool, 544 pages of 16 a row (eight a turn); ``paged32``: the same pool
    in pages of 32 (four a turn; no cell runs it)."""
    from distributed_llama_tpu.ops import pallas_head_major_attention as hm

    b, n_q, n_kv, hs = 32, 40, 10, 128
    if kind in ("window", "rows"):
        b, s = (b, 512) if kind == "window" else (1, 8704)
        ring = _sd((8 * b, n_kv, s, hs), jnp.float32)
        return (functools.partial(hm.rows_decode_attention, kv_mul=4,
                                  interpret=False),
                (_sd((b, n_q, hs), jnp.float32), ring, ring,
                 _sd((), jnp.int32), _sd((b,), jnp.int32)))
    ps = 32 if kind == "paged32" else 16
    pool = _sd((17152 * 16 // ps + 1, n_kv, ps, hs), jnp.float32)
    return (functools.partial(hm.paged_decode_attention, kv_mul=4,
                              interpret=False),
            (_sd((b, n_q, hs), jnp.float32), pool, pool,
             _sd((b,), jnp.int32), _sd((b, 8704 // ps), jnp.int32)))


def _gqa_attention(kind: str, heads: int):
    """Grouped-query attention through the head-major kernels at
    Laguna-XS.2's widths: 8 KV heads of 128 under 64 query heads (a sliding
    layer: groups of 8) or 48 (a full layer: groups of 6, padded to 8 rows),
    32 rows. ``window``: twelve layers' rings of 512 slots; ``rows``: one
    sequence's contiguous planes of 5,120 positions, four full layers;
    ``paged``: four layers' pools of 5,121 pages of 16 merged, the table
    offset a layer."""
    from distributed_llama_tpu.ops import pallas_head_major_attention as hm

    b, n_kv, hs = 32, 8, 128
    kv_mul = heads // n_kv
    if kind in ("window", "rows"):
        layers, b, s = (12, b, 512) if kind == "window" else (4, 1, 5120)
        ring = _sd((layers * b, n_kv, s, hs), jnp.float32)
        return (functools.partial(hm.rows_decode_attention, kv_mul=kv_mul,
                                  interpret=False),
                (_sd((b, heads, hs), jnp.float32), ring, ring,
                 _sd((), jnp.int32), _sd((b,), jnp.int32)))
    pool = _sd((4 * 5121, n_kv, 16, hs), jnp.float32)
    return (functools.partial(hm.paged_decode_attention, kv_mul=kv_mul,
                              interpret=False),
            (_sd((b, heads, hs), jnp.float32), pool, pool,
             _sd((b,), jnp.int32), _sd((b, 5120 // 16), jnp.int32)))


def _kv_attention(kind: str, sink: bool):
    """The head-major kernels at MiMo-V2-Flash's widths: 64 query heads, K
    heads of 192 held in 256 lanes beside V heads of 128, 32 rows.
    ``window``: nine sliding layers' rings of 128 slots, 8 KV heads (groups
    of 8), a sink a head; ``rows``: one sequence's contiguous planes of
    14,336 positions, three full layers, 4 KV heads (groups of 16);
    ``paged``: three layers' pools of 16,385 pages of 16 merged."""
    from distributed_llama_tpu.ops import pallas_head_major_attention as hm

    b, heads, hk, hv = 32, 64, 256, 128
    sinks = [_sd((heads,), jnp.float32)] if sink else [None]
    q = _sd((b, heads, 192), jnp.float32)
    if kind in ("window", "rows"):
        layers, rows, n_kv, s = ((9, b, 8, 128) if kind == "window"
                                 else (3, 1, 4, 14336))
        q = _sd((rows, heads, 192), jnp.float32)
        return (functools.partial(hm.rows_decode_attention,
                                  kv_mul=heads // n_kv, interpret=False),
                (q, _sd((layers * rows, n_kv, s, hk), jnp.float32),
                 _sd((layers * rows, n_kv, s, hv), jnp.float32),
                 _sd((), jnp.int32), _sd((rows,), jnp.int32), *sinks))
    return (functools.partial(hm.paged_decode_attention, kv_mul=16,
                              interpret=False),
            (q, _sd((3 * 16385, 4, 16, hk), jnp.float32),
             _sd((3 * 16385, 4, 16, hv), jnp.float32),
             _sd((b,), jnp.int32), _sd((b, 14336 // 16), jnp.int32), *sinks))


# kernel=False: the dispatch documents an XLA dequantize-then-dot route for
# that shape (the int4 planes serve T == 1 only) — the case pins the routing
# as well as the compile. An nb-major leaf has a kernel at every T: from 2
# rows to 8 (``serve``'s default 8 slots) it is the MXU body at ``block_t``
# 8, whose small-t-tile row rule (256 rows) keeps it inside scoped VMEM
_Q40_LEAVES = ("wq", "w13", "w2", "wcls")
CASES = {
    **{f"q40-{layout}-{leaf}-T{t}":
       (functools.partial(_q40, layout, leaf, t),
        layout != "i4" or t == 1)
       for layout in ("d", "nb", "i4") for leaf in _Q40_LEAVES
       for t in (1, 8)},
    "q40-nb-wq-T4": (functools.partial(_q40, "nb", "wq", 4), True),
    "q40-nb-m-w13-T8": (functools.partial(_q40, "nb", "m-w13", 8), True),
    "q40-nb-m-w2-T8": (functools.partial(_q40, "nb", "m-w2", 8), True),
    # a 5-row dispatch pads to the same 8-row tile
    "q40-nb-m-w2-T5": (functools.partial(_q40, "nb", "m-w2", 5), True),
    "decode-f32": (functools.partial(_decode, jnp.float32), True),
    "decode-bf16": (functools.partial(_decode, jnp.bfloat16), True),
    "decode-batch-f32": (_decode_batch, True),
    # PR 59: a landed chunk (chunk, n_kv, hs) is read head-major into the MXU
    # fold: a strided read a head at Mistral's 8 KV heads x 4, one relayout
    # of the slot at a Yi-34B tp-4 rank's 2 x 7 and a 13B rank's 10 x 1 (a
    # ``ref.reshape`` of such a slot compiles and reads wrong rows: ROADMAP
    # S4 e), at the decode cells' 4,096 positions; a bf16 slot is widened
    **{f"decode-{name}-kv{n}x{m}":
       (functools.partial(_decode, dt, n, m, 4096), True)
       for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16))
       for n, m in ((8, 4), (2, 7), (10, 1))
       if (name, n) != ("bf16", 10)},
    "prefill-f32": (functools.partial(_prefill, jnp.float32), True),
    # was: "Slice shape along dimension 1 must be aligned to tiling (8),
    # but is 1" on memref<2048x32x128xbf16> -> 512x1x128
    "prefill-bf16": (functools.partial(_prefill, jnp.bfloat16), True),
    **{f"paged-{name}-ps16-t{t}":
       (functools.partial(_paged, dt, t), True)
       for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16))
       for t in (1, 4)},
    # was: "infer-vector-layout: unsupported shape cast ...
    # vector<1x16x32x128xi8> -> vector<16x128x32xi8>", then "Invalid vector
    # type for load ... xf16" and "Only arguments with ... bfloat16 or 32-bit
    # element types are supported"
    "paged-q8-ps16-t1": (_paged_q8, True),
    # PR 47: a turn lands the pages that make 128 positions in one slot and
    # folds it on the MXU, a KV head a strided read of the slot: at OLMoE's
    # shape (16 KV heads x 1, 16 rows) and Mistral's (8 x 4, 8 rows), decode
    # and a verify window of 4 and of 8; a bf16 pool (widened a slot); pages
    # of 128 (one a turn); a tp-4 rank's two KV heads (Mistral's or Yi's)
    # and 13B's ten; the q8 twin's window and its pages of 128
    **{f"paged-f32-ps16-kv{n}x{m}-b{b}-t{t}":
       (functools.partial(_paged, jnp.float32, t, n_kv=n, kv_mul=m, b=b),
        True)
       for n, m, b in ((16, 1, 16), (8, 4, 8)) for t in (1, 4)},
    "paged-f32-ps16-kv8x4-t8":
        (functools.partial(_paged, jnp.float32, 8, n_kv=8, kv_mul=4), True),
    "paged-bf16-ps16-kv8x4-t1":
        (functools.partial(_paged, jnp.bfloat16, 1, n_kv=8, kv_mul=4), True),
    "paged-f32-ps128-t4": (functools.partial(_paged, jnp.float32, 4, 128),
                           True),
    "paged-f32-ps128-kv8x4-t4":
        (functools.partial(_paged, jnp.float32, 4, 128, n_kv=8, kv_mul=4),
         True),
    "paged-f32-ps16-kv2x4-t1":
        (functools.partial(_paged, jnp.float32, 1, n_kv=2, kv_mul=4), True),
    "paged-f32-ps16-kv2x4-t8":
        (functools.partial(_paged, jnp.float32, 8, n_kv=2, kv_mul=4), True),
    "paged-f32-ps16-kv10x1-t1":
        (functools.partial(_paged, jnp.float32, 1, n_kv=10), True),
    "paged-q8-ps16-t4": (functools.partial(_paged_q8, t_len=4), True),
    "paged-q8-ps128-t1": (functools.partial(_paged_q8, 128), True),
    # was (first written with ``ref.at[0]`` views of blocks 1 to 4 lanes
    # wide): "Slice shape along dimension 3 must be aligned to tiling (128),
    # but is 4"
    # (``wide``: a 128-row chunk's slots, at the rows a slot its shape
    # gives: it was the every-expert call ``mxu`` until PR 36)
    **{f"moe-{kind}-{leaf}-T{rows}":
       (functools.partial(_moe, leaf, rows), True)
       for kind, rows in (("slots", 16), ("slots", 32), ("slots", 1),
                          ("wide", 128))
       for leaf in ("w13", "w2")},
    # the state read and rewritten in place (whole-head 4.3 MB blocks under
    # a raised scoped-VMEM limit), and the chunk's float32 MXU matmuls
    "retention-decode-B16": (functools.partial(_retention, "decode"), True),
    "retention-chunk-T128": (functools.partial(_retention, "chunk"), True),
    # the T=1 nb-major matvec at Brumby's w2 (544 blocks a row): the vector
    # body's x planes were "RESOURCE_EXHAUSTED: Ran out of memory in memory
    # space vmem" under the default scoped limit. Since PR 49 the MXU
    # matvec (ops/pallas_q40._matvec_body_nb_mxu: the groups walked with
    # fori_loop, the row's block-diagonal planes in scratch), here and at
    # the block counts and row counts of the two decode cells that no case
    # below holds at one row: Mistral's w13 (1024-row tiles) and w2 (448
    # blocks), Yi's tp-4 wo (56 blocks: a chunk of 32 and a tail of 24), wk
    # (256 rows: one tile) and classifier (16,000 rows: 25 tiles of 640)
    "q40-nb-w2-nb544-T1": (_q40_wide_w2, True),
    **{f"q40-nb-{leaf}-T1": (functools.partial(_q40, "nb", leaf, 1), True)
       for leaf in ("m-w13", "m-w2", "yi-wo", "yi-wk", "yi-wcls",
                    "yi-wqkv", "yi-w13")},
    # DeepSeek-V3 (PR 33). The latent plane: was "Slice shape along
    # dimension 2 must be aligned to tiling (128), but is 576" on
    # memref<36873x16x640xf32> (the chip stores 576 values in 640 lanes
    # whatever it is told: models/latent.plane_width pads the plane itself)
    "latent-paged-ps16-B32": (_latent_paged, True),
    # Motif-3-Beta's ring (PR 52) under the fold of exact pieces (PR 61)
    "latent-ring-w128-H80-B32": (_latent_ring, True),
    # its new leaves at the cell's 32 rows, one row and the 128-row chunk;
    # nb 576 runs under the raised scoped-VMEM limit at T = 1
    **{f"q40-nb-{leaf}-T{t}": (functools.partial(_q40, "nb", leaf, t), True)
       for leaf in ("ds-wkv_a", "ds-wq_b", "ds-wo", "ds-w2")
       for t in (1, 32)},
    "q40-nb-ds-w2-T128": (functools.partial(_q40, "nb", "ds-w2", 128), True),
    # Phi-4-mini-flash (PR 37): the state read and rewritten in place a
    # row, the chunk's recurrence a lane tile at a time, the head-major
    # softmax kernels at 10 KV pairs of 128 (head-minor, the pool and the
    # rings were stored 16 heads wide and copied around every call), and
    # its leaves at 1, 32 and 128 rows
    "mamba-decode-B32": (functools.partial(_mamba, "decode"), True),
    "mamba-chunk-T128": (functools.partial(_mamba, "chunk"), True),
    "diff-window-W512-B32": (functools.partial(_diff_attention, "window"),
                             True),
    "diff-rows-S8704-B1": (functools.partial(_diff_attention, "rows"),
                           True),
    "diff-paged-ps16-B32": (functools.partial(_diff_attention, "paged"),
                            True),
    "diff-paged-ps32-B32": (functools.partial(_diff_attention, "paged32"),
                            True),
    **{f"q40-nb-{leaf}-T{t}": (functools.partial(_q40, "nb", leaf, t), True)
       for leaf in ("ph-in_proj", "ph-out_proj", "ph-wqkv", "ph-wo",
                    "ph-w13", "ph-w2")
       for t in (1, 32, 128)},
    # the T > 1 tile's five-pass dot (PR 38) at every (leaf, rows) pair a
    # cell runs that no case above holds: Mistral's leaves at a step's 8
    # rows and a chunk's 128 (the rows' pieces stacked in bfloat16 planes,
    # 32 and 96 rows a t-tile: ops/pallas_q40._mxu_nb_planes), Yi's tp-4
    # shards at its chunk's 128, Brumby's classifier at its 16 (the expert
    # slots' merged tile: the moe-* cases, 8 / 16 / 32 rows a slot)
    **{f"q40-nb-{leaf}-T{t}": (functools.partial(_q40, "nb", leaf, t), True)
       for leaf, ts in (("m-wqkv", (8, 128)), ("wq", (128,)),
                        ("m-w13", (128,)), ("m-w2", (128,)),
                        ("wcls", (128,)), ("yi-wq", (128,)),
                        ("yi-wk", (128,)), ("yi-wo", (128,)),
                        ("yi-w1", (128,)), ("yi-w2", (128,)),
                        ("yi-wcls", (128,)), ("yi-wqkv", (128,)),
                        ("yi-w13", (128,)), ("br-wcls", (16,)))
       for t in ts},
    **{f"moe-ds-{kind}-{leaf}-T{rows}":
       (functools.partial(_moe, leaf, rows, "ds"), True)
       for kind, rows in (("slots", 32), ("slots", 16), ("slots", 1),
                          ("wide", 128))
       for leaf in ("w13", "w2")},
    # Xing4.0-29B-A4B (PR 39): the residual path's coefficient stage (flat
    # norm, HIGHEST projection (24, 14336) x (14336, rows), sigmoids and 20
    # unrolled Sinkhorn rounds on (4, rows) tiles; XLA, no custom call) at a
    # step's rows, one row and a chunk's; its leaves (input widths 768, 3584, 9216 and 1024 that
    # no cell compiled before) at the cell's 32 rows, and the rest where a
    # shape is new at that count; its experts (2.0 rows an expert, 4 a row)
    **{f"hc-coefficients-T{t}": (functools.partial(_hc_coefficients, t), False)
       for t in (1, 32, 128)},
    **{f"q40-nb-{leaf}-T{t}": (functools.partial(_q40, "nb", leaf, t), True)
       for leaf, ts in (("x4-wq_a", (1, 32, 128)), ("x4-wq_b", (1, 32, 128)),
                        ("x4-wkv_a", (32,)), ("x4-wo", (32,)),
                        ("x4-w13", (32,)), ("x4-w2", (1, 32, 128)),
                        ("x4-sh_w13", (32,)), ("x4-sh_w2", (1, 32, 128)),
                        ("x4-wcls", (32,)))
       for t in ts},
    **{f"moe-x4-{kind}-{leaf}-T{rows}":
       (functools.partial(_moe, leaf, rows, "x4"), True)
       for kind, rows in (("slots", 32), ("slots", 1), ("wide", 128))
       for leaf in ("w13", "w2")},
    # Laguna-XS.2 (PR 44): the head-major kernels at 8 KV heads of 128 and
    # TWO group sizes (8 heads a group in a sliding layer, 6 padded to 8 in
    # a full one), its leaves at the cell's 32 rows, one row and a chunk's
    # 512, and its experts (256 held, 8 a row: 1.6 rows an expert at 32
    # rows, ``w2`` at 16 blocks a row)
    "gqa-window-W512-B32-H64": (functools.partial(_gqa_attention, "window",
                                                  64), True),
    "gqa-rows-S5120-B1-H48": (functools.partial(_gqa_attention, "rows", 48),
                              True),
    "gqa-rows-S5120-B1-H64": (functools.partial(_gqa_attention, "rows", 64),
                              True),
    "gqa-paged-ps16-B32-H48": (functools.partial(_gqa_attention, "paged",
                                                 48), True),
    # MiMo-V2-Flash (PR 48): K 192 in 256 lanes / V 128, 8 and 4 KV heads,
    # groups of 8 and 16, a sink in the first carry (a copy 192 lanes wide
    # was refused: "Slice shape along dimension 3 must be aligned to tiling
    # (128), but is 192")
    "kv-window-W128-B32-K192-sink": (functools.partial(
        _kv_attention, "window", True), True),
    "kv-window-W128-B32-K192": (functools.partial(
        _kv_attention, "window", False), True),
    "kv-rows-S14336-B1-K192": (functools.partial(
        _kv_attention, "rows", False), True),
    "kv-paged-ps16-B32-K192": (functools.partial(
        _kv_attention, "paged", False), True),
    "kv-paged-ps16-B32-K192-sink": (functools.partial(
        _kv_attention, "paged", True), True),
    **{f"q40-nb-{leaf}-T{t}": (functools.partial(_q40, "nb", leaf, t), True)
       for leaf, ts in (("lg-f-wqkv", (1, 32, 512)), ("lg-s-wqkv", (32,)),
                        ("lg-f-wo", (1, 32, 512)), ("lg-s-wo", (1, 32, 512)),
                        ("lg-w13", (32,)), ("lg-w2", (1, 32, 512)),
                        ("lg-sh_w13", (32,)), ("lg-sh_w2", (1, 32, 512)),
                        ("lg-wcls", (32,)))
       for t in ts},
    **{f"moe-lg-{kind}-{leaf}-T{rows}":
       (functools.partial(_moe, leaf, rows, "lg"), True)
       for kind, rows in (("slots", 32), ("slots", 1), ("wide", 512))
       for leaf in ("w13", "w2")},
}


def _q40_tile(d: int, nb: int, t: int):
    """One stacked nb-major leaf of ``nb`` blocks a row at a ``t``-row
    dispatch, through ``q40_matmul``: the tile as the rule lays it."""
    from distributed_llama_tpu.io.loader import Q40KernelNb
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul

    w = Q40KernelNb(_sd((2, 16, nb, d), jnp.uint8),
                    _sd((2, nb, d), jnp.float32))
    return (lambda w, x, layer: q40_matmul(w, x, interpret=False,
                                           layer=layer),
            (w, _sd((t, nb * 32), jnp.float32), _sd((), jnp.int32)))


# PR 51: the T > 1 tile at every (blocks a row, rows, planes a dot) triple
# ``ops/pallas_q40._pick_planes`` returns over the dense leaves of the nine
# benchmark configurations and the widths their cells dispatch (shapes from
# the configuration files, tests/q40_cell_leaves.py; the smallest leaf of a
# triple), so that the chip's compiler has taken every tile a cell runs
# before a cell runs it: PR 36 and PR 38 each met a scoped-VMEM refusal late
from q40_cell_leaves import rule_triples  # noqa: E402

CASES.update({f"q40-tile-nb{nb}-T{t}-G{g}":
              (functools.partial(_q40_tile, d, nb, t), True)
              for (nb, t, g), d in rule_triples().items()})


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    build, kernel = CASES[case]
    fn, shapes = build()
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel, case


@pytest.mark.parametrize("leaf", ["m-wqkv", "wq", "m-w13", "m-w2", "wcls",
                                  "br-w2"])
def test_part_filled_dispatch_compiles_both_bodies_for_v5e(chip, leaf):
    """PR 63: an 8-row decode dispatch that is told its live rows
    (``ops/linear.live_rows``) holds the tile and the stacked
    block-diagonal body under one conditional, each under a name a capture
    classes as Q40: Mistral-7B's five leaves (``wq`` has ``wo``'s shape) and
    Brumby's ``w2``, whose 544 blocks a row are 17 turns of 32."""
    from distributed_llama_tpu.ops.linear import live_rows

    fn, (w, x, layer) = _q40("nb", leaf, 8)

    def told(w, x, layer, mask):
        with live_rows(mask):
            return fn(w, x, layer)

    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        (w, x, layer, _sd((8,), jnp.int32)))
    text = jax.jit(told).lower(*args).compile().as_text()
    kind = "2d" if leaf.endswith("wcls") else "stacked"
    for name in (f"_q40_mxu_nb_{kind}", f"_q40_live_nb_{kind}"):
        assert re.search(rf"%{name}[.\d]* = [^\n]*custom-call\(", text), name
    assert " conditional(" in text


@pytest.mark.parametrize("n_kv", [1, 3, 6, 10, 20])
def test_bf16_cache_head_counts_the_chip_cannot_land_are_gated(chip, n_kv):
    """A bf16 cache is tiled (8, 128)(2, 1) in HBM (16 heads a tile, or 2 or
    4), and the chip's compiler refuses the decode kernel's copy of any
    other head count ("Slice shape along dimension 2 must be aligned to
    tiling (8), but is 10": a 13B tp-4 rank). ``supports`` sends those to
    the XLA path; the float32 cache of the same heads has a kernel."""
    from distributed_llama_tpu.ops import pallas_attention as pa

    assert not pa.supports(4096, HS, 1, n_kv, 2)
    assert pa.supports(4096, HS, 1, n_kv, 4)
    fn, shapes = _decode(jnp.bfloat16, n_kv, 1, 4096)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        shapes)
    with pytest.raises(ValueError, match="no cache chunking"):
        jax.jit(fn).lower(*args)


@pytest.mark.parametrize("n_kv,kv_mul,t_len", [(8, 4, 1), (8, 4, 4),
                                               (16, 1, 1), (16, 1, 4)])
def test_q8_pages_under_128_blocks_a_position_are_refused(chip, n_kv, kv_mul,
                                                          t_len):
    """What the q8 twin cannot do yet, held still so that a repair shows: at
    Mistral's and OLMoE's head counts a position's Q80 deltas are 32 and 64
    values, the chip stores a plane's minor dim in 128 lanes, and Mosaic
    refuses the copy of a page's delta plane ("Slice shape along dimension
    2 must be aligned to tiling (128)"). The kernel before PR 47 was refused
    the same way (the plane is the same whatever a turn lands); a 32-head
    pool (128 deltas a position) compiles, above. ``supports_paged`` does
    not know (ROADMAP S4): ``--kv-quant q8`` has no cell."""
    fn, shapes = _paged_q8(t_len=t_len, n_kv=n_kv, kv_mul=kv_mul)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        shapes)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("rows,name", [(32, "moe_q40_slots"),
                                       (128, "moe_q40_grouped")])
def test_expert_call_is_named_by_its_width(chip, rows, name):
    """A capture names a Pallas call by its ``name=``. The benchmark's
    expert roofline shares find the DECODE kernel by ``moe_q40_slots`` and
    every expert kernel by ``moe_q40``: a chunk's wide call keeps the
    second prefix and not the first, or a step whose span held an admission
    would count the chunk's calls as a decode step's."""
    fn, shapes = _moe("w2", rows)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.split("=")[0].split("%")[-1].strip()
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and calls[0].startswith(name), calls
    assert calls[0].startswith("moe_q40")
    assert (rows > 32) != calls[0].startswith("moe_q40_slots")


# ---- whole sharded programs: no weight-sized copy in a step ---------------

YI = dict(dim=7168, hidden_dim=20480, n_heads=56, n_kv_heads=8,
          vocab_size=64000)   # benchmark/configs/yi-34b-q40-tp4.json
YI_TP, YI_LAYERS, YI_SEQ = 4, 2, 512    # a cut depth and context


@pytest.fixture
def chip_branch(monkeypatch):
    """Code that asks for the backend takes its chip branch (Pallas kernels,
    not interpret mode; the ``auto`` kernel modes), as
    benchmark/tools/rehearse_compile.py arranges. Traces made so are dropped
    afterwards: a jitted dispatch that resolved ``interpret=None`` to the
    chip would otherwise be reused by a CPU test of the same shapes."""
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL", "DLLAMA_TP_SCHEME"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


@pytest.mark.parametrize("t,kernels", [(1, 9), (128, 8)])
def test_sharded_step_copies_no_weights(topo, chip_branch, t, kernels):
    """The tp=4 decode step and T=128 chunk at Yi-34B's widths: every Q40
    leaf reaches its Pallas call in the layout it is stored in. A d-major
    shard whose block count is off the 128 grid is stored d-minor and
    copied row-major (padded) at the top of every step: 6.6 GiB of
    temporaries at 60 layers, 22.9 ms of a 37.2 ms step on the chip
    (PERF.md, PR 25). ``kernels``: seven layer matmuls and the classifier,
    plus decode attention at T=1 — one fewer means a matmul fell to
    dequantize-then-dot."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_llama_tpu.io.loader import Q40Weight
    from distributed_llama_tpu.models.llama import KVCache
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.ops.linear import pack_q40_params
    from distributed_llama_tpu.ops.quants import FloatType
    from distributed_llama_tpu.parallel import tp

    spec = TransformerSpec(**YI, n_layers=YI_LAYERS, seq_len=YI_SEQ,
                           weights_float_type=FloatType.Q40,
                           buffer_float_type=FloatType.F32)
    mesh = Mesh(np.array(topo.devices[:YI_TP]).reshape(1, 1, YI_TP),
                ("dp", "sp", "tp"))
    L, dim = spec.n_layers, spec.dim

    def q40(lead, d, n):
        return Q40Weight(_sd((*lead, d, n // 32, 16), jnp.uint8),
                         _sd((*lead, d, n // 32), jnp.float16))

    tree = {"tok_embedding": _sd((spec.vocab_size, dim), jnp.float32),
            "rms_att": _sd((L, dim), jnp.float32),
            "rms_ffn": _sd((L, dim), jnp.float32),
            "rms_final": _sd((dim,), jnp.float32),
            "wcls": q40((), spec.vocab_size, dim),
            **{k: q40((L,), *shape)
               for k, shape in spec.layer_matmul_shapes()}}
    packed = jax.eval_shape(
        lambda w: pack_q40_params(w, tp=YI_TP,
                                  input_sharded=tp.FUSED_INPUT_SHARDED), tree)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        packed, tp.param_specs(packed, "fused"))
    cache_sh = NamedSharding(mesh, tp.CACHE_SPEC.k)
    cache = KVCache(*(jax.ShapeDtypeStruct(
        (L, YI_SEQ, spec.n_kv_heads, spec.head_size), jnp.float32,
        sharding=cache_sh) for _ in range(2)))
    rep = NamedSharding(mesh, P())
    compiled = tp.make_sharded_forward(spec, mesh, scheme="fused").lower(
        params, cache, jax.ShapeDtypeStruct((t,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    weights = sum(a.size * a.dtype.itemsize // YI_TP
                  for k, v in packed.items() if k != "tok_embedding"
                  for a in jax.tree_util.tree_leaves(v))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.05 * weights, (temp, weights)
    assert compiled.as_text().count("tpu_custom_call") == kernels


def test_laguna_step_holds_what_the_memory_model_counts(chip):
    """The compiled ``serve`` decode step of ``laguna-xs2-q40`` (32 rows,
    the configuration's pool, the rings and pools aliased) takes as
    arguments what ``analysis/memory_model`` counts for the spec on one chip
    (weights in the kernels' layout, the float32 leaves, rings for the
    sliding layers, pages over the full layers only, every expert held), to
    1 %, and copies neither an expert stack, a ring nor a pool around a
    kernel call: what it needs beside its arguments stays under 0.25 GiB."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark.harness import laguna as harness
    from benchmark.tools.rehearse_laguna import shape_params
    from distributed_llama_tpu.analysis import memory_model as mm
    from distributed_llama_tpu.models import laguna

    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-xs2-q40.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    flags = config["entries"]["serve"]
    sizes = harness.sizes_of(config)
    spec = harness.program_spec(sizes)
    b, ps, pages = (int(flags[k]) for k in ("slots", "kv_page_size",
                                            "kv_pages"))
    saved, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        params, _, kinds = shape_params(sizes, spec, b, chip)
        assert all(v != "Q40Weight" for v in kinds.values())   # all packed
        sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
        pool = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), jax.eval_shape(
                lambda: laguna.init_cache_paged(spec, b, pages + 1, ps)))
        compiled = jax.jit(functools.partial(
            laguna.forward_batch, spec, page_size=ps, health=True,
            moe_counts=True), donate_argnums=1).lower(
                params, pool, sds((b,), jnp.int32), sds((b,), jnp.int32),
                sds((b, spec.seq_len // ps), jnp.int32),
                sds((b,), jnp.int32)).compile()
    finally:
        jax.default_backend = saved
    m = compiled.memory_analysis()
    rep = mm.device_footprint(spec, 1, "ref", batch=b, kv_page_size=ps,
                              kv_pages=pages)
    counted = rep.weights_bytes + rep.replicated_bytes + rep.kv_cache_bytes
    assert abs(m.argument_size_in_bytes - counted) < 0.01 * counted, (
        m.argument_size_in_bytes, counted)
    assert rep.kv_cache_bytes == (pages + 1) * 524288 + b * 12 * 512 * 8192
    assert m.temp_size_in_bytes < 0.25 * 2 ** 30
    assert compiled.as_text().count("tpu_custom_call") >= 20
