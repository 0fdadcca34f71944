"""C++ host library vs the pure-Python/numpy reference implementations.

The native layer (csrc/host.cpp via ctypes) must be bit-identical to the
numpy codecs and the Python tokenizer/rng — it is an accelerated twin, not a
second implementation of the spec. Skips cleanly when no toolchain exists.
"""

import numpy as np
import pytest

from distributed_llama_tpu.utils import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def test_xorshift_stream_parity():
    from distributed_llama_tpu.utils.rng import Xorshift64

    state, arr = native.xorshift_fill(800000010, 64, divisor=120.0)
    rng = Xorshift64(800000010)
    want = (rng.f32_array(64).astype(np.float64) / 120.0).astype(np.float32)
    np.testing.assert_array_equal(arr, want)
    assert state == rng.state


def test_q40_codec_roundtrip_parity():
    from distributed_llama_tpu.ops.quants import (pack_q40_bytes,
                                                  quantize_q40,
                                                  unpack_q40_bytes)

    x = (np.random.default_rng(3).standard_normal(4096) * 0.5).astype(
        np.float32)
    qs, d16 = quantize_q40(x)
    wire = np.frombuffer(pack_q40_bytes(qs, d16), dtype=np.uint8)

    dec = native.q40_decode_wire(wire, nb=4096 // 32)
    from distributed_llama_tpu.ops.quants import dequantize_q40

    np.testing.assert_array_equal(dec, dequantize_q40(qs, d16))


def test_native_q40_encode_matches_numpy():
    import ctypes

    lib = native._load()
    x = (np.random.default_rng(5).standard_normal(2048) * 0.7).astype(
        np.float32)
    out = np.empty((2048 // 32) * 18, dtype=np.uint8)
    lib.q40_encode(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                   out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                   2048 // 32)
    from distributed_llama_tpu.ops.quants import pack_q40_bytes, quantize_q40

    qs, d16 = quantize_q40(x)
    np.testing.assert_array_equal(
        out, np.frombuffer(pack_q40_bytes(qs, d16), dtype=np.uint8))


def test_native_q80_codec_matches_numpy():
    import ctypes

    lib = native._load()
    x = (np.random.default_rng(7).standard_normal(1024) * 2.0).astype(
        np.float32)
    out = np.empty((1024 // 32) * 34, dtype=np.uint8)
    lib.q80_encode(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                   out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                   1024 // 32)
    from distributed_llama_tpu.ops.quants import pack_q80_bytes, quantize_q80

    qs, d = quantize_q80(x)
    np.testing.assert_array_equal(
        out, np.frombuffer(pack_q80_bytes(qs, d), dtype=np.uint8))

    dec = np.empty(1024, dtype=np.float32)
    lib.q80_decode(out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                   dec.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                   1024 // 32)
    from distributed_llama_tpu.ops.quants import dequantize_q80

    np.testing.assert_array_equal(dec, dequantize_q80(qs, d))


def test_native_bpe_matches_python(tmp_path):
    from distributed_llama_tpu.io.tokenizer import Tokenizer, write_tokenizer

    pieces = [b"<unk>", b"<s>", b"</s>"]
    pieces += [f"<0x{i:02X}>".encode() for i in range(256)]
    pieces += [b" ", b"h", b"i", b"s", b"t", b"hi", b" hi", b"is", b"this",
               b" this", b"hist"]
    scores = [0.0] * len(pieces)
    for p, s in [(b"hi", -1.0), (b" hi", -0.5), (b"is", -1.2), (b"this", -0.3),
                 (b" this", -0.2), (b"hist", -0.9)]:
        scores[pieces.index(p)] = s
    path = str(tmp_path / "tok.bin")
    write_tokenizer(path, pieces, scores)

    tok = Tokenizer(path, len(pieces))
    assert tok._native.available

    class _Off:
        available = False

    for text in ["hi", "this is history", "héllo ✨", "", "x" * 300]:
        native_ids = tok.encode(text)
        saved = tok._native
        tok._native = _Off()  # force the Python merge loop
        try:
            py_ids = tok.encode(text)
        finally:
            tok._native = saved
        assert native_ids == py_ids, text


def test_native_tile_kernel_layout_matches_numpy():
    from distributed_llama_tpu.utils import native

    if not native.available():
        import pytest

        pytest.skip("native library unavailable")
    rng = np.random.default_rng(6)
    qs = rng.integers(0, 256, (3, 40, 5, 16), dtype=np.uint8)
    d16 = (rng.random((3, 40, 5)) * 0.1).astype(np.float16)
    got = native.q40_tile_kernel_layout(qs, d16)
    assert got is not None
    qs_t, scale = got
    want_qs = np.ascontiguousarray(qs.transpose(0, 3, 1, 2))
    np.testing.assert_array_equal(qs_t, want_qs)
    np.testing.assert_array_equal(scale, d16.astype(np.float32))
    # unstacked rank-3 too
    qs_t2, scale2 = native.q40_tile_kernel_layout(qs[0], d16[0])
    np.testing.assert_array_equal(qs_t2, np.ascontiguousarray(
        qs[0].transpose(2, 0, 1)))
    np.testing.assert_array_equal(scale2, d16[0].astype(np.float32))


@pytest.mark.parametrize("shape", [(3, 40, 5), (2, 300, 11), (1, 128, 8),
                                   (256, 3)])
def test_native_tile_kernel_layout_nb_matches_numpy(shape, monkeypatch):
    """The threaded nb-major tiler against the numpy transpose it replaced
    in ``to_kernel_layout_nb``: ragged row bands (d 40, 300), ragged block
    tiles (nb 5, 11, 3), stacked and not; f16 subnormal deltas (the native
    upconvert halved them) through both tilers."""
    from distributed_llama_tpu.io.loader import (Q40Weight, to_kernel_layout,
                                                 to_kernel_layout_nb)
    from distributed_llama_tpu.utils import native

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(7)
    qs = rng.integers(0, 256, (*shape, 16), dtype=np.uint8)
    d16 = (rng.random(shape) * 0.1).astype(np.float16)
    d16.reshape(-1)[:4] = [6e-8, 3e-5, -6.09e-5, 6.2e-5]  # around 2**-14
    got = to_kernel_layout_nb(Q40Weight(qs, d16))
    got_d = to_kernel_layout(Q40Weight(qs, d16))
    monkeypatch.setattr(native, "q40_tile_kernel_layout",
                        lambda *a, **k: None)      # the numpy fallback
    want = to_kernel_layout_nb(Q40Weight(qs, d16))
    np.testing.assert_array_equal(got_d.scale,
                                  to_kernel_layout(Q40Weight(qs, d16)).scale)
    assert got.qs_t.shape == (*shape[:-2], 16, shape[-1], shape[-2])
    np.testing.assert_array_equal(got.qs_t, want.qs_t)
    np.testing.assert_array_equal(got.scale, want.scale)
    assert got.scale.dtype == np.float32 and got.qs_t.flags.c_contiguous


def test_native_sampler_matches_numpy():
    """csrc sample_logits vs the numpy Sampler path on identical
    logits/coins, across strategies (argmax is numpy-only; multinomial and
    nucleus exercise the native select)."""
    from distributed_llama_tpu.runtime.sampling import (sample_mult,
                                                        sample_topp,
                                                        softmax_f32)

    rng = np.random.default_rng(123)
    for case in range(200):
        n = int(rng.integers(4, 500))
        logits = (rng.standard_normal(n) * rng.uniform(0.5, 6)).astype(
            np.float32)
        temperature = float(rng.uniform(0.2, 1.5))
        coin = float(rng.uniform(0, 1))
        # nucleus (topp in (0,1)) and multinomial (topp outside)
        for topp in (float(rng.uniform(0.05, 0.99)), 1.0):
            got = native.sample_logits(logits, temperature, topp, coin)
            assert got is not None
            probs = softmax_f32(logits / np.float32(temperature))
            if topp <= 0 or topp >= 1:
                want = sample_mult(probs, coin)
            else:
                want = sample_topp(probs, topp, coin)
            assert got == want, (case, n, temperature, topp, coin)


def test_sampler_class_uses_native_consistently():
    """Sampler(use_native=True/False) must emit the same stream."""
    from distributed_llama_tpu.runtime.sampling import Sampler

    rng = np.random.default_rng(7)
    logits_seq = [rng.standard_normal(300).astype(np.float32) * 4
                  for _ in range(50)]
    a = Sampler(300, temperature=0.9, topp=0.9, seed=42, use_native=True)
    b = Sampler(300, temperature=0.9, topp=0.9, seed=42, use_native=False)
    for lg in logits_seq:
        assert a.sample(lg) == b.sample(lg)


def test_native_sampler_degenerate_nucleus():
    """topp < 1/n with near-uniform probs empties the cutoff pre-filter:
    both implementations must return the argmax, not crash/UB."""
    from distributed_llama_tpu.runtime.sampling import (sample_topp,
                                                        softmax_f32)

    n = 64
    logits = np.zeros(n, dtype=np.float32)
    logits[17] = 1e-4  # barely-top token
    for topp in (1e-6, 0.01):
        got = native.sample_logits(logits, 1.0, topp, 0.7)
        probs = softmax_f32(logits)
        want = sample_topp(probs, topp, 0.7)
        assert got == want == 17
    # n == 1: no (n-1) division
    one = np.zeros(1, dtype=np.float32)
    assert native.sample_logits(one, 1.0, 0.9, 0.3) == 0
    assert sample_topp(softmax_f32(one), 0.9, 0.3) == 0
