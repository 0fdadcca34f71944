"""``ops/kda.py`` alone on the chip, at Ling-3.0-flash's published sizes (32
heads of 128, an admission chunk of 512, 20 KDA layers of 32 rows): the chunk
form and its two pieces (the pair sums, the unit-lower inverse) in
milliseconds, the chunk form against the recurrence ON the chip, and the
decode kernel's bytes a second over its 20 calls of a decode step. Under a
minute; run from the root of the repo:

  chiprun --chips 1 -- python3 benchmark/tools/kda_probe.py

A time from a CPU run of this file says nothing about the chip.
"""

from __future__ import annotations

import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HEADS, DIM, CHUNK_T = 32, 128, 512
LAYERS, ROWS = 20, 32


def bench(name: str, fn, *args, runs: int = 10):
    """The mean wall time of ``runs`` calls after one that compiles."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name}: {(time.perf_counter() - t0) / runs * 1e3:.3f} ms",
          flush=True)
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import peaks
    from distributed_llama_tpu.ops import kda

    rng = np.random.default_rng(0)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    q, k, v = (draw(CHUNK_T, HEADS, DIM) for _ in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DIM ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5 * jax.nn.sigmoid(draw(CHUNK_T, HEADS, DIM))
    b = jax.nn.sigmoid(draw(CHUNK_T, HEADS))
    s0 = draw(HEADS, DIM, DIM)

    o, s1 = bench(f"kda_chunk T={CHUNK_T}", jax.jit(kda.kda_chunk), s0, q, k,
                  v, g, b)

    def cut(x):
        return x.reshape(-1, kda.CHUNK, *x.shape[1:])

    m, _ = bench("pair sums (8 chunks of 64)",
                 jax.jit(jax.vmap(kda._pair_sums)), cut(q), cut(k),
                 jnp.cumsum(cut(g), axis=1))
    bench("unit-lower inverse (8 x 32 of 64 x 64)",
          jax.jit(kda._unit_lower_inverse), m)

    def recurrence(s, *xs):
        def step(s, x):
            o, s = kda.recur_step(s, *x)
            return s, o

        s, o = jax.lax.scan(step, s, xs)
        return o, s

    o2, s2 = bench(f"recurrence T={CHUNK_T}", jax.jit(recurrence), s0, q, k,
                   v, g, b, runs=2)
    print(f"chunk form against the recurrence on this device: o "
          f"{float(jnp.abs(o - o2).max()):.3g}, state "
          f"{float(jnp.abs(s1 - s2).max()):.3g}", flush=True)

    rows = (q[:ROWS], k[:ROWS], v[:ROWS], g[:ROWS], b[:ROWS])
    fresh, live = jnp.zeros((ROWS,), bool), jnp.ones((ROWS,), bool)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(s_all):
        def layer(i, carry):
            s_all, acc = carry
            o, s_all = kda.scan_decode(i, s_all, *rows, fresh, live,
                                       kernel=True)
            return s_all, acc + o

        return jax.lax.fori_loop(0, LAYERS, layer, (
            s_all, jnp.zeros((ROWS, HEADS, DIM), jnp.float32)))

    s_all, acc = step(jnp.full((LAYERS * ROWS, HEADS, DIM, DIM), 0.01,
                               jnp.float32))
    jax.block_until_ready(acc)
    t0 = time.perf_counter()
    for _ in range(10):
        s_all, acc = step(s_all)
    jax.block_until_ready(acc)
    dt = (time.perf_counter() - t0) / 10
    from benchmark.harness import ling

    nbytes = LAYERS * ling.state_call_bytes(
        {"n_heads": HEADS, "head_dim": DIM}, ROWS)
    peak = peaks.peak(jax.devices()[0].device_kind, "hbm_bytes_per_s")
    print(f"decode kernel, {LAYERS} layers x {ROWS} rows: {dt * 1e3:.3f} ms, "
          f"{nbytes / dt / 1e9:.1f} GB/s ({100 * nbytes / dt / peak:.1f} % of "
          f"the HBM roofline)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
