"""Microbenchmark: decompose single-token decode time on the real chip.

A single dispatched op is dominated by dispatch and sync cost, so every
measurement here chains N dependent iterations of the op INSIDE one jitted
program (lax.scan) and reads the output back, so per-iteration time =
(chain_ms - sync_overhead) / N.

Times the fused Q40 matmul at each 7B weight shape (achieved HBM GB/s vs the
packed byte size), the attention core over a full 2048-position cache, and a
whole forward step, so kernel work can be told apart from everything else.

Usage: python tools/microbench.py [--layers N] [--iters N]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_SYNC_MS = [0.0]  # measured per-chain dispatch+transfer constant, subtracted


def chain_ms(make_step, init_x, n_iters, trials=3):
    """ms per iteration of x -> step(x) chained n_iters times on device,
    with the per-chain sync constant subtracted."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x0):
        def body(x, _):
            return make_step(x), None

        x, _ = jax.lax.scan(body, x0, None, length=n_iters)
        return jnp.sum(x)

    np.asarray(run(init_x))  # compile + warm
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        np.asarray(run(init_x))
        best = min(best, time.perf_counter() - t0)
    return max(best * 1000 - _SYNC_MS[0], 0.0) / n_iters


def sync_overhead_ms(trials=5):
    """Round-trip cost of dispatch + tiny transfer (the per-chain constant)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.float32(0)
    np.asarray(f(x))
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        np.asarray(f(x))
        best = min(best, time.perf_counter() - t0)
    return best * 1000


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--iters", type=int, default=300)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.io.loader import Q40Kernel
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul

    from distributed_llama_tpu.utils.chip import require_tpu

    print(f"backend: {require_tpu()}", file=sys.stderr)
    ov = sync_overhead_ms()
    _SYNC_MS[0] = ov
    print(f"sync overhead: {ov:.2f} ms/chain (subtracted)", file=sys.stderr)
    rng = np.random.default_rng(0)
    N = args.iters

    shapes = [("wq/wk/wv/wo", 4096, 4096), ("w1/w3", 11008, 4096),
              ("wqkv fused", 12288, 4096), ("w13 fused", 22016, 4096),
              ("w2", 4096, 11008), ("wcls", 32000, 4096)]
    for name, d, n in shapes:
        nb = n // 32
        qs_t = jnp.asarray(rng.integers(0, 256, (16, d, nb), dtype=np.uint8))
        scale = jnp.asarray(rng.normal(size=(d, nb)).astype(np.float32)) * 0.01
        w = Q40Kernel(qs_t, scale)

        def step(x, w=w, d=d, n=n):
            out = q40_matmul(w, x.reshape(1, -1))  # (1, d)
            # feed output back as next input (resize d -> n cheaply)
            flat = out.reshape(-1)
            reps = -(-n // d)
            return jnp.tile(flat, reps)[:n] * 1e-3

        ms = chain_ms(step, jnp.ones((n,), jnp.float32), N)
        mb = (qs_t.size + scale.size * 4) / 1e6
        gbs = f"{mb / ms:7.1f}" if ms > 0 else "    inf"
        print(f"{name:12s} d={d:6d} n={n:6d}  {ms:7.3f} ms  "
              f"{mb:8.1f} MB  {gbs} GB/s")

    # attention core over the full static cache (one layer, pos=2047)
    from distributed_llama_tpu.models.llama import (attention_core,
                                                    causal_cache_mask)

    S, H, HS = 2048, 32, 128
    k_c = jnp.asarray(rng.normal(size=(S, H, HS)).astype(np.float32))
    v_c = jnp.asarray(rng.normal(size=(S, H, HS)).astype(np.float32))
    mask = causal_cache_mask(S, jnp.int32(S - 1), 1)

    def att_step(q):
        out = attention_core(HS, 1, q.reshape(1, H, HS), k_c, v_c, mask)
        return out.reshape(-1) * 1e-3

    ms = chain_ms(att_step, jnp.ones((H * HS,), jnp.float32), N)
    mb = (k_c.size + v_c.size) * 4 / 1e6
    print(f"{'attention':12s} S={S:6d}        {ms:7.3f} ms  "
          f"{mb:8.1f} MB  {mb / ms:7.1f} GB/s   (x{args.layers} layers = "
          f"{ms * args.layers:.1f} ms)")

    # full single-token forward at 7B: chain via the sampled-token feedback
    import functools

    from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                    params_to_device)
    from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                    synth_q40_fast)

    spec = llama2_7b_spec(n_layers=args.layers)
    params = params_to_device(synth_q40_fast(spec))
    step = functools.partial(forward, spec)

    n_fwd = 64

    @jax.jit
    def fwd_chain(params, cache, tok):
        def body(carry, i):
            tok, cache = carry
            logits, cache = step(params, cache, tok, i)
            tok = jnp.argmax(logits[-1:], axis=-1).astype(jnp.int32)
            return (tok, cache), None

        (tok, cache), _ = jax.lax.scan(
            body, (tok, cache), jnp.arange(n_fwd, dtype=jnp.int32))
        return tok

    cache = init_cache(spec)  # fwd_chain doesn't donate it: reusable
    tok0 = jnp.asarray([7], dtype=jnp.int32)
    np.asarray(fwd_chain(params, cache, tok0))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(fwd_chain(params, cache, tok0))
        best = min(best, time.perf_counter() - t0)
    print(f"{'full forward':12s} L={args.layers:5d}        "
          f"{max(best * 1000 - ov, 0) / n_fwd:7.3f} ms/token  "
          f"({n_fwd} chained)")


if __name__ == "__main__":
    main()
