"""Prefill MXU floor: attribute the fast-prefill op rate (VERDICT r3 #7).

The r3 ladder pinned prefill WALL time to ~100 ms/launch dispatch + op
time, but took the op rate itself (5,854 tok/s ~= 79 TFLOP/s ~= 40% of
v5e bf16 peak) as given. This tool separates the op time into:

  dense arm    the exact per-layer matmul sequence (wqkv/wo/w13/w2 shapes,
               bf16, f32 accumulation) on PRE-dequantized HBM-resident
               weights — the MXU+HBM ceiling of the dot sequence itself,
               no quantization anywhere.
  dequant arm  the same dots through the production dequant-then-dot path
               (packed Q40 stacks, per-layer unpack to a bf16 HBM temp —
               DLLAMA_PREFILL_MATMUL=dequant, ops.pallas_q40._dequant_*).
               dequant_arm - dense_arm = the quantization temp tax.
  (engine)     the full Engine.prefill op time from the r3 ladder adds
               attention + RoPE/glue + layout on top.

Both arms scan PASSES=4 dependent passes of L layers inside ONE jit, so
the ~92 ms per-chain dispatch amortizes to ~1% and the timing needs no
differencing. L=16 of 32 layers keeps the dense arm's bf16 weights at
~6.4 GB on a 16 GB chip; rates are per-layer, so MFU is unaffected.

Run on the chip (through the chip tool, from the repo root): python tools/prefill_floor.py
     [--chunk 1920] [--layers 16] [--passes 4]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk", type=int, default=1920)
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--passes", type=int, default=4)
    args = ap.parse_args()
    T, L, P = args.chunk, args.layers, args.passes

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.io.loader import Q40Weight
    from distributed_llama_tpu.models.synth import llama2_7b_spec
    from distributed_llama_tpu.ops.linear import (matmul_precision,
                                                  pack_q40_params)
    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul
    from distributed_llama_tpu.utils.compile_cache import (
        enable_persistent_cache)

    enable_persistent_cache()
    spec = llama2_7b_spec()
    dim, hid, kvd = spec.dim, spec.hidden_dim, spec.kv_dim
    from distributed_llama_tpu.utils.chip import peak, require_tpu

    dev = require_tpu()
    # the share of peak is of THIS device's published peak (utils/chip.PEAKS,
    # keyed by device_kind; an unknown kind raises)
    peak_tflops = peak(dev["kind"], "bf16_flops_per_s") / 1e12
    print(f"backend: {dev}  chunk={T} layers={L} passes={P}",
          file=sys.stderr)

    shapes = {"wqkv": (dim + 2 * kvd, dim), "wo": (dim, dim),
              "w13": (2 * hid, dim), "w2": (dim, hid)}
    flop_layer = 2 * T * sum(d * n for d, n in shapes.values())

    rng = np.random.default_rng(0)

    def packed(d, n):
        qs = rng.integers(0, 256, (L, d, n // 32, 16), dtype=np.uint8)
        sc = (rng.random((L, d, n // 32), dtype=np.float32) * 0.01
              + 1e-4).astype(np.float16)
        return Q40Weight(qs, sc)

    host = {k: packed(d, n) for k, (d, n) in shapes.items()}
    kern = pack_q40_params(host, enable=True)
    dev_q = jax.device_put(jax.tree_util.tree_map(jnp.asarray, kern))

    def layer_flow(x, mm):
        """The per-layer matmul sequence at prefill shapes; mm(name, x)
        runs one (d, n) @ x.T matmul."""
        y = mm("wqkv", x)                       # (T, dim+2kvd)
        a = y[:, :dim]
        b = mm("wo", a)                         # (T, dim)
        h = mm("w13", b)                        # (T, 2*hid)
        g = h[:, :hid] * jax.nn.sigmoid(h[:, hid:])
        return mm("w2", g)                      # (T, dim)

    def run_arm(mm_builder, label):
        @jax.jit
        def run(x0, weights):
            def one_pass(x, _):
                def body(x, lw):
                    return layer_flow(x, mm_builder(lw)), None

                x, _ = jax.lax.scan(body, x, weights)
                return x * 1e-3, None           # keep magnitudes bounded

            x, _ = jax.lax.scan(one_pass, x0, None, length=P)
            return jnp.sum(x)

        return run

    x0 = jnp.ones((T, dim), jnp.float32) * 0.01

    results = {}
    # dense arm: pre-dequantized bf16 weights (built ON device from the
    # packed stacks so no 13 GB host upload rides the measurement)
    from distributed_llama_tpu.ops.quants import dequantize_q40_jax

    @jax.jit
    def densify(w):
        qs = jnp.transpose(w.qs_t, (0, 2, 3, 1)) if w.qs_t.ndim == 4 \
            else jnp.transpose(w.qs_t, (1, 2, 0))
        return dequantize_q40_jax(qs, w.scale).astype(jnp.bfloat16)

    dense_w = {k: densify(w) for k, w in dev_q.items()}
    jax.block_until_ready(dense_w)

    def mm_dense(lw):
        def mm(name, x):
            return jnp.einsum("dn,tn->td", lw[name].astype(jnp.bfloat16),
                              x.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)

        return mm

    def mm_dequant(lw):
        def mm(name, x):
            return q40_matmul(lw[name], x)

        return mm

    for label, runner, weights, ctx in (
            ("dense", run_arm(mm_dense, "dense"), dense_w, None),
            ("dequant", run_arm(mm_dequant, "dequant"), dev_q, "bf16")):
        os.environ["DLLAMA_PREFILL_MATMUL"] = "dequant"
        if ctx:
            cm = matmul_precision(ctx)
            cm.__enter__()
        try:
            np.asarray(runner(x0, weights))  # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(runner(x0, weights))
                best = min(best, time.perf_counter() - t0)
        finally:
            if ctx:
                cm.__exit__(None, None, None)
        per_layer_ms = best * 1000 / (P * L)
        tflops = flop_layer / (per_layer_ms / 1e3) / 1e12
        mfu = tflops / peak_tflops
        results[label] = (per_layer_ms, tflops, mfu)
        print(f"{label:8s}: {best * 1000:8.1f} ms total -> "
              f"{per_layer_ms:6.2f} ms/layer @ T={T} = "
              f"{tflops:6.1f} TFLOP/s ({mfu * 100:4.1f}% of "
              f"{dev['kind']}'s {peak_tflops:.0f} TFLOP/s bf16 peak)")

    d_ms, _, _ = results["dense"]
    q_ms, _, _ = results["dequant"]
    eq_tok_s = T / (q_ms * 32 / 1000)  # scaled to the full 32-layer model
    print(f"dequant temp tax: {q_ms - d_ms:+.2f} ms/layer "
          f"({(q_ms - d_ms) / q_ms * 100:.0f}% of the dequant arm)")
    print(f"32-layer matmul-only equivalent: {eq_tok_s:.0f} tok/s "
          f"(engine op rate w/ attention+glue: ~5850 tok/s, r3 ladder)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
