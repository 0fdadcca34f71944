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
the compiled program. Cases stay near a second each (tier-1 budget): no
page-size-128 x t_len-4 paged case (~50 s).
"""

import functools
import os

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
          "wcls": (VOCAB, DIM)}


def _sd(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off: an executable compiled for a described chip is written to the cache
    but cannot be read back without a chip (it warns and recompiles)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / no topology support
        pytest.skip(f"cannot describe a v5e:2x2 topology here "
                    f"({type(e).__name__}: {e})")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _q40(layout: str, leaf: str, t: int):
    """q40_matmul on the stacked (scalar-prefetch) form the layer scan runs;
    wcls is the one unstacked leaf. ``i4`` converts inside the program, as
    the decode chain does (ops/pallas_q40.chain_weight_prep)."""
    from distributed_llama_tpu.io.loader import Q40Kernel, Q40KernelNb
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul, to_i4_planes

    d, n = LEAVES[leaf]
    nb = n // 32
    lead = () if leaf == "wcls" else (2,)
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


def _decode(dtype):
    from distributed_llama_tpu.ops.pallas_attention import decode_attention

    cache = _sd((2, SEQ, N_KV, HS), dtype)
    return (functools.partial(decode_attention, kv_mul=1, interpret=False),
            (_sd((N_KV, HS), jnp.float32), cache, cache, _sd((), jnp.int32),
             _sd((), jnp.int32)))


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


def _paged(dtype, t_len: int, ps: int = 16):
    from distributed_llama_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_kernel)

    b, pages = 8, 64
    pool = _sd((2 * pages, ps, N_KV, HS), dtype)
    return (functools.partial(paged_decode_attention_kernel, page_size=ps,
                              n_pages=pages, kv_mul=1, t_len=t_len,
                              interpret=False),
            (_sd((b, t_len, N_KV * HS), jnp.float32), pool, pool,
             _sd((), jnp.int32), _sd((b,), jnp.int32),
             _sd((b, SEQ // ps), jnp.int32)))


def _paged_q8(ps: int = 16):
    from distributed_llama_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_kernel_q8)

    b, pages = 8, 64
    codes = _sd((2 * pages, ps, N_KV, HS), jnp.int8)
    deltas = _sd((2 * pages, ps, N_KV * HS // 32), jnp.float16)
    return (functools.partial(paged_decode_attention_kernel_q8, page_size=ps,
                              n_pages=pages, kv_mul=1, t_len=1,
                              interpret=False),
            (_sd((b, 1, N_KV * HS), jnp.float32), codes, deltas, codes,
             deltas, _sd((), jnp.int32), _sd((b,), jnp.int32),
             _sd((b, SEQ // ps), jnp.int32)))


# kernel=False: the dispatch documents an XLA dequantize-then-dot route for
# that shape (nb-major serves T <= 4, the int4 planes T == 1) — the case pins
# the routing as well as the compile
CASES = {
    **{f"q40-{layout}-{leaf}-T{t}":
       (functools.partial(_q40, layout, leaf, t),
        layout == "d" or t == 1)
       for layout in ("d", "nb", "i4") for leaf in LEAVES for t in (1, 8)},
    "q40-nb-wq-T4": (functools.partial(_q40, "nb", "wq", 4), True),
    "decode-f32": (functools.partial(_decode, jnp.float32), True),
    "decode-bf16": (functools.partial(_decode, jnp.bfloat16), True),
    "decode-batch-f32": (_decode_batch, True),
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
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    build, kernel = CASES[case]
    fn, shapes = build()
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel, case
