"""Standalone timing of the fused layer kernels at 7B shapes (VERDICT r2 #2).

Runs the head+tail fused kernels back to back over all 32 layers (no
attention, no sampling) as one on-device fori_loop chain — the pure
fused-matvec cost per token. Compare against BASELINE's attribution of the
unfused path (~6.6 ms Q40 kernels + ~1.0 ms glue + ~2 ms launch bubbles):
the fused chain should land near the weight-streaming floor (~6.6-7 ms)
because the glue rides inside the kernels and the per-layer launch count
drops from ~10 to 2.

Run on the chip (through the chip tool, from the repo root): python tools/layer_kernel_bench.py
     [--iters 32] [--config 7b]
"""

import argparse
import sys
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--config", default="7b", choices=("7b", "small"))
    ap.add_argument("--profile", default=None,
                    help="write a profiler trace here and print the op-time "
                         "attribution (utils/it_split)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                    small_bench_spec,
                                                    synth_q40_fast)
    from distributed_llama_tpu.ops.linear import (fuse_q40_layer_matmuls,
                                                  pack_q40_params)
    from distributed_llama_tpu.ops.pallas_layer import (q40_head_fused,
                                                        q40_tail_fused,
                                                        rope_freq_cols,
                                                        supports)
    from distributed_llama_tpu.utils.compile_cache import (
        enable_persistent_cache)

    enable_persistent_cache()
    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    dev = device_triple() if args.config == "small" else require_tpu()
    print(f"backend: {dev}", file=sys.stderr)
    spec = llama2_7b_spec() if args.config == "7b" else small_bench_spec()

    t0 = time.perf_counter()
    params = synth_q40_fast(spec)
    params = fuse_q40_layer_matmuls(
        pack_q40_params(params, enable=True, allow_nb_major=False))
    assert supports(spec, params), "fused path unsupported for this spec"
    keep = {k: params[k] for k in ("wqkv", "wo", "w13", "w2", "rms_att",
                                   "rms_ffn")}
    keep = jax.tree_util.tree_map(lambda a: jax.device_put(jnp.asarray(a)),
                                  keep)
    jax.block_until_ready(keep)
    print(f"weights packed+placed: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    freq_np, even_np = rope_freq_cols(spec)
    freq, even = jnp.asarray(freq_np), jnp.asarray(even_np)

    def token(w, x_col, pos):
        def body(carry, idx):
            x_col = carry
            qkv = q40_head_fused(spec, w["wqkv"],
                                 w["rms_att"][idx][:, None], freq, even,
                                 x_col, idx, pos)
            # attention stand-in: feed q straight through as the att output
            x_col = q40_tail_fused(spec, w["wo"], w["w13"],
                                   w["w2"], w["rms_ffn"][idx][:, None],
                                   qkv[:spec.dim], x_col, idx)
            return x_col, None
        x_col, _ = jax.lax.scan(body, x_col,
                                jnp.arange(spec.n_layers, dtype=jnp.int32))
        # renormalize so a long chain can't overflow (timing-neutral)
        return x_col * jax.lax.rsqrt(jnp.mean(x_col * x_col) + 1e-6)

    # weights ride as ARGUMENTS: a closure would bake the 4 GB tree into
    # the executable as captured constants (memory quirk; round-2 trap)
    @jax.jit
    def chain(w, x_col, n):
        return jax.lax.fori_loop(
            0, n, lambda i, x: token(w, x, jnp.int32(5) + i), x_col)

    x0 = jnp.zeros((spec.dim, 1), jnp.float32).at[0, 0].set(1.0)
    t0 = time.perf_counter()
    np.asarray(chain(keep, x0, jnp.int32(1)))
    print(f"compile+first run: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(chain(keep, x0, jnp.int32(args.iters)))
        times.append((time.perf_counter() - t0) * 1000 / args.iters)
    print(f"fused head+tail chain: {min(times):.3f} ms/token "
          f"(trials {[round(t, 3) for t in times]}, {args.iters} "
          f"iters/chain, {spec.n_layers} layers)")

    if args.profile:
        with jax.profiler.trace(args.profile):
            np.asarray(chain(keep, x0, jnp.int32(args.iters)))
        from distributed_llama_tpu.utils.it_split import (parse_trace,
                                                          summarize)

        summarize(parse_trace(args.profile), tokens=args.iters, top=14)


if __name__ == "__main__":
    main()
