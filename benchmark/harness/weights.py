"""Seeded random weights, built in memory as the codec tree the program's
loader contract defines, and handed to the program's own placement
(``params_to_device`` / ``shard_params``): no kernel layout is known here.

The value recipe is a copy of ``models/synth.write_synth_q40_model`` (PR 21;
the original is listed in PERF.md's open questions): nibble codes symmetric
on -7..7 (a uniform 0..15 code has mean -0.5, a rank-one component that
swamps the signal within a few layers), an f16 delta sized so a (d, n)
matrix has value std ~ 1/sqrt(n) (unit-RMS activations through every
matmul, logits ~N(0, 1)), norm gains 1 +- 0.05, and the classifier's BOS
row zeroed so a greedy stream never ends early on a sampled BOS.

Codec layout (``io/loader.Q40Weight``): ``qs`` uint8 (..., d, n/32, 16), low
nibble = value j, high nibble = value j + 16 of the block; ``d16`` float16
(..., d, n/32); value = (nibble - 8) * delta. Per-layer matrices are stacked
on a leading layer axis.

Built on the host because the program packs on the host (PERF.md lists
packing on the device as the program change that would let this move): one
task per (tensor, layer) on a few threads, whole 64-bit words of random
bits at a time. The same seed gives the same tree whatever the thread count.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

BOS = 1            # io/tokenizer.BOS
QK = 32            # values per Q40 block
_CODE_STD = 4.18   # std of the symmetric -7..7 code distribution, per delta
_LOW_BITS = np.uint64(0x1111111111111111)


def layer_matmul_shapes(sizes: dict) -> list[tuple[str, tuple[int, int]]]:
    """(name, (d, n)) of one layer's matmul weights, in file order; a row
    is an OUTPUT: out[i] = sum_j w[i, j] * x[j]."""
    d, h = sizes["dim"], sizes["hidden_dim"]
    kv = d * sizes["n_kv_heads"] // sizes["n_heads"]
    return [("wq", (d, d)), ("wk", (kv, d)), ("wv", (kv, d)), ("wo", (d, d)),
            ("w1", (h, d)), ("w2", (d, h)), ("w3", (h, d))]


def _fill_q40(qs: np.ndarray, d16: np.ndarray, n: int, seed_key) -> None:
    """Fill one (d, n/32, 16) code block and its (d, n/32) deltas in place."""
    rng = np.random.default_rng(seed_key)
    words = rng.bit_generator.random_raw(qs.size // 8)
    # a nibble of 0 (value -8) becomes 8 (value 0): OR the four bits of each
    # nibble into its lowest, and set bit 3 where that came out 0
    any_bit = words >> np.uint64(1)
    any_bit |= words
    any_bit |= any_bit >> np.uint64(2)
    any_bit &= _LOW_BITS
    any_bit ^= _LOW_BITS
    any_bit <<= np.uint64(3)
    words |= any_bit
    qs.reshape(-1).view(np.uint64)[:] = words
    delta = rng.random(d16.size, dtype=np.float32)
    delta += np.float32(0.5)
    delta /= np.float32(_CODE_STD * np.sqrt(n))
    d16.reshape(-1)[:] = delta


def build_codec_tree(sizes: dict, seed: int, q40_type, threads: int = 0):
    """The param tree of ``io/loader.load_model``'s contract for the seven
    header ``sizes``; ``q40_type`` is the program's ``Q40Weight``."""
    L, dim, vocab = sizes["n_layers"], sizes["dim"], sizes["vocab_size"]
    threads = threads or min(16, os.cpu_count() or 1)
    tree: dict = {}
    tasks = []

    def q40(name, idx, lead, d, n):
        nb = n // QK
        qs = np.empty((*lead, d, nb, 16), np.uint8)
        d16 = np.empty((*lead, d, nb), np.float16)
        tree[name] = q40_type(qs, d16)
        for layer in range(lead[0] if lead else 1):
            sl = (layer,) if lead else ()
            tasks.append((_fill_q40, qs[sl], d16[sl], n,
                          [seed, idx, layer]))

    def dense(name, idx, shape, base):
        out = np.empty(shape, np.float32)
        tree[name] = out
        rows = out.reshape(-1, shape[-1])
        step = max(1, (1 << 22) // shape[-1])
        for lo in range(0, rows.shape[0], step):
            tasks.append((_fill_dense, rows[lo:lo + step], base,
                          [seed, idx, lo]))

    dense("tok_embedding", 0, (vocab, dim), 0.0)
    dense("rms_att", 1, (L, dim), 1.0)
    dense("rms_ffn", 2, (L, dim), 1.0)
    dense("rms_final", 3, (dim,), 1.0)
    for i, (name, (d, n)) in enumerate(layer_matmul_shapes(sizes)):
        q40(name, 10 + i, (L,), d, n)
    q40("wcls", 20, (), vocab, dim)
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        for f in [pool.submit(fn, *args) for fn, *args in tasks]:
            f.result()
    tree["wcls"].d16[BOS] = 0     # logit exactly 0: never the argmax
    return tree


def _fill_dense(out: np.ndarray, base: float, seed_key) -> None:
    rng = np.random.default_rng(seed_key)
    x = rng.standard_normal(out.shape, dtype=np.float32)
    if base:
        x *= np.float32(0.05)
        x += np.float32(base)
    out[:] = x


def dequantize(qs: np.ndarray, d16: np.ndarray) -> np.ndarray:
    """Codec blocks to float32 (..., d, n) on the host: the codec's own
    definition, for tests and for the reference's inputs."""
    lo = (qs & 0x0F).astype(np.int8) - 8
    hi = (qs >> 4).astype(np.int8) - 8
    vals = np.concatenate([lo, hi], axis=-1).astype(np.float32)
    vals *= d16.astype(np.float32)[..., None]
    return vals.reshape(*qs.shape[:-2], qs.shape[-2] * QK)
