"""Prefill floor ladder (VERDICT r2 #6): where does a prefill chunk's time go?

Decomposes per-chunk prefill wall time at 7B into
  * device op time, split by op family from a profiler trace:
      - the Q40 matmul kernels (unpack + MXU; Pallas custom calls)
      - the flash-attention kernel
      - XLA fusions (activation plane transposes / layout / glue)
      - everything else
  * dispatch = wall - device-op total (the per-launch cost; decode's phase
    ladder showed ~390-410 GB/s program streaming against ~670 GB/s op-time
    streaming for the same reason)

across chunk sizes x matmul strategies (DLLAMA_PREFILL_MATMUL):
  * legacy  — the round-2 Pallas MXU body. Its grid is (t/bt, d/rows) with
    bt capped at 128 by VMEM, so a 1920-token chunk re-DMAs AND re-unpacks
    every packed weight tile t/bt = 15x per chunk.
  * scratch — d-outer grid + unpack-once-to-VMEM-scratch MXU body
    (_matmul_body_scratch): weight bytes move and unpack exactly once.
  * dequant — unpack once per chunk into an HBM bf16 temp, plain XLA dot:
    trades the re-reads for 2x dense-byte traffic (write+read of the temp).

Modes run under --fast-prefill (bf16 MXU) and parity f32 anchors. MXU
ceiling for scale: 7B prefill is ~13.4 GFLOP/token; v5e bf16 peak
~197 TFLOP/s -> ~0.068 ms/token ~ 14.7k tok/s.

Run on the chip (through the chip tool, from the repo root): python tools/prefill_ladder.py
     [--chunks 480,960,1920] [--modes ...] [--out ladder.json]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

_MODES = {
    # name -> (fast_prefill, DLLAMA_PREFILL_MATMUL)
    "legacy_bf16": (True, "legacy"),
    "scratch_bf16": (True, "scratch"),
    "dequant_bf16": (True, "dequant"),
    "legacy_f32": (False, "legacy"),
    "scratch_f32": (False, "scratch"),
    "dequant_f32": (False, "dequant"),
}


def _profile_chunk(engine, toks, chunk, trace_dir):
    """Op-time split of ONE chunk at positions 0..chunk (a first warm run
    compiles; the traced run starts from a reset cache so every position
    stays inside seq_len — a window at pos0=chunk would run past the cache
    for chunk > seq_len/2 and silently clamp its writes)."""
    import jax

    from distributed_llama_tpu.utils.it_split import bucket_ops

    engine.reset()
    engine.prefill(toks[:chunk], 0, chunk)  # warm/compile outside the trace
    engine.reset()
    with jax.profiler.trace(trace_dir):
        engine.prefill(toks[:chunk], 0, chunk)
        np.asarray(engine.cache.k[-1, chunk - 1, 0, :8])
    return bucket_ops(trace_dir)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", default="480,960,1920")
    ap.add_argument("--modes", default="legacy_bf16,scratch_bf16,dequant_bf16,legacy_f32")
    ap.add_argument("--config", default="7b",
                    choices=("7b", "13b", "small"))
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args()
    chunks = [int(c) for c in args.chunks.split(",")]
    modes = args.modes.split(",")

    import jax

    from distributed_llama_tpu.models.synth import (llama2_13b_spec,
                                                    llama2_7b_spec,
                                                    small_bench_spec,
                                                    synth_q40_fast)
    from distributed_llama_tpu.utils.compile_cache import (
        enable_persistent_cache)

    enable_persistent_cache()
    spec = {"7b": llama2_7b_spec, "13b": llama2_13b_spec,
            "small": small_bench_spec}[args.config]()
    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    dev = device_triple() if args.config == "small" else require_tpu()
    print(f"backend {dev}  config {args.config}", flush=True,
          file=sys.stderr)
    t0 = time.perf_counter()
    # pack once on host for the tree structure, then regenerate the values
    # ON DEVICE (no upload of a multi-GB host tree per engine)
    from distributed_llama_tpu.models.synth import device_params_like
    from distributed_llama_tpu.ops.linear import (fuse_q40_layer_matmuls,
                                                  pack_q40_params)

    # 13b picks the nb-major layout (its nb=160 pads 1.6x d-major)
    params = device_params_like(fuse_q40_layer_matmuls(
        pack_q40_params(synth_q40_fast(spec), enable=True,
                        allow_nb_major=(args.config == "13b"))))
    jax.block_until_ready(params)
    print(f"synth+pack+devgen: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    results = []
    for mode in modes:
        fast, strategy = _MODES[mode]
        os.environ["DLLAMA_PREFILL_MATMUL"] = strategy
        from distributed_llama_tpu.runtime.generate import Engine

        cache_dtype = None
        if args.config == "13b":
            import jax.numpy as jnp

            cache_dtype = jnp.bfloat16  # 13B f32 cache exceeds one chip
        engine = Engine(spec, params, fast_prefill=fast,
                        cache_dtype=cache_dtype)
        for chunk in chunks:
            n = min(4 * chunk, spec.seq_len - 8)
            n -= n % chunk  # whole windows only: per-chunk math stays exact
            if n == 0:
                row = {"mode": mode, "chunk": chunk,
                       "skipped": f"chunk {chunk} exceeds "
                                  f"seq_len-8={spec.seq_len - 8}"}
                results.append(row)
                print(json.dumps(row), flush=True)
                continue
            windows = n // chunk
            toks = [7] * n
            rates, walls = [], []
            try:
                for trial in range(args.trials + 1):  # first = compile+warm
                    engine.reset()
                    t0 = time.perf_counter()
                    engine.prefill(toks, 0, chunk)
                    np.asarray(engine.cache.k[-1, n - 1, 0, :8])
                    dt = time.perf_counter() - t0
                    if trial:
                        rates.append(n / dt)
                        walls.append(dt * 1000)
                # >=2 full windows run as ONE device program (Engine's
                # fused window loop), so dispatch is per PREFILL CALL, not
                # per chunk — report it that way
                wall = float(np.median(walls))
                row = {"mode": mode, "chunk": chunk, "windows": windows,
                       "launches_per_prefill": 1 if windows >= 2 else windows,
                       "tok_s": round(float(np.median(rates)), 1),
                       "wall_ms_per_prefill": round(wall, 2)}
                trace = f"/tmp/prefill_ladder_{mode}_{chunk}"
                try:
                    ops = _profile_chunk(engine, toks, chunk, trace)
                    op_total = round(sum(ops.values()), 2)
                    row["op_ms_per_chunk"] = ops
                    row["op_total_ms"] = op_total
                    row["dispatch_ms_per_prefill"] = round(
                        wall - op_total * windows, 2)
                except Exception as e:  # profile is best-effort
                    row["profile_error"] = f"{type(e).__name__}: {e}"
            except Exception as e:
                row = {"mode": mode, "chunk": chunk,
                       "error": f"{type(e).__name__}: {e}"}
            results.append(row)
            print(json.dumps(row), flush=True)
        del engine
        gc.collect()

    out = {"metric": "prefill ladder", "config": args.config, "rows": results}
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
