"""Batched lockstep decode bench (runtime/decode.make_batch_decode_loop).

Measures ms/step and ms/token for B rows decoding in lockstep — the
throughput capability the reference lacks (batch=1 only, README.md:21).
Weights are synthetic and generated ON DEVICE (models/synth.
device_params_like): no multi-GB host tree, no upload; the KV cache is bf16 (the memory-bound configuration both 13B
rows require on a 16 GB chip).

Measured (v5e, r3): 7B B=4 5.0 ms/token; 13B B=2 16.5-16.6 ms/token —
the T<=8 VPU multi body's per-row accumulate work is the bottleneck at
13B's wide-nb shapes (tile-cap ladder 300k/600k/1200k words measured flat
32.9-33.2 ms/step via DLLAMA_MULTI_CAP, so tile granularity is NOT the
limiter; the kernel is VPU-bound at T>1 by design — the unpack is shared,
the multiply-accumulate scales with T).

Run on the chip (through the chip tool, from the repo root): python tools/batch_bench.py
     [--config 7b|13b] [--batch 4] [--steps 64]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="13b", choices=("7b", "13b", "small"))
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=64)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import init_cache_batch
    from distributed_llama_tpu.models.synth import (device_params_like,
                                                    llama2_7b_spec,
                                                    llama2_13b_spec,
                                                    small_bench_spec,
                                                    synth_q40_fast)
    from distributed_llama_tpu.ops.linear import (fuse_q40_layer_matmuls,
                                                  pack_q40_params)
    from distributed_llama_tpu.runtime.decode import make_batch_decode_loop
    from distributed_llama_tpu.utils.compile_cache import (
        enable_persistent_cache)

    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    enable_persistent_cache()
    dev = device_triple() if args.config == "small" else require_tpu()
    print(f"backend: {dev}", file=sys.stderr)
    spec = {"7b": llama2_7b_spec, "13b": llama2_13b_spec,
            "small": small_bench_spec}[args.config]()
    t0 = time.perf_counter()
    params = device_params_like(fuse_q40_layer_matmuls(
        pack_q40_params(synth_q40_fast(spec), enable=True,
                        allow_nb_major=(args.config == "13b"))))
    jax.block_until_ready(params)
    print(f"weights: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    B, steps = args.batch, args.steps
    padded = np.full((B, steps + 1), 7, dtype=np.int32)  # forced stream
    coins = np.zeros((B, steps), dtype=np.float32)
    run = make_batch_decode_loop(spec, steps, 0.0, 0.9)
    mk = lambda: (params, init_cache_batch(spec, B, jnp.bfloat16),
                  jnp.asarray(padded), jnp.asarray([7] * B, jnp.int32),
                  jnp.asarray(coins))
    t0 = time.perf_counter()
    np.asarray(run(*mk())[0])  # the token buffer: the whole chain has run
    print(f"compile+first: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(run(*mk())[0])
        times.append((time.perf_counter() - t0) * 1000 / steps)
    ms_step = float(np.median(times))
    print(json.dumps({
        "metric": f"llama2-{args.config} q40 batched decode",
        "batch": B, "steps": steps, "kv_cache": "bf16",
        "ms_per_step": round(ms_step, 2),
        "ms_per_token": round(ms_step / B, 2),
        "tok_s": round(B * 1000 / ms_step, 1),
        "trials_ms_per_step": [round(t, 2) for t in times],
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
