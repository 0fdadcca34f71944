"""Prefill throughput: parity (f32 HIGHEST) vs --fast-prefill (bf16 MXU).

Measures Engine.prefill tokens/s at 7B Q40 for both precision modes
(VERDICT r1 #7: the fast mode's gate is >= 3x). Long prompt, big chunks, so
the per-dispatch cost is spread over a handful of chunk launches and the
number reflects the chunk compute.

Run on the chip (through the chip tool, from the repo root): python tools/prefill_bench.py
  [--config 7b|small] [--prompt-len N] [--chunk N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _measure(engine, tokens, chunk: int, trials: int = 3) -> float:
    """tokens/s of a full prefill of ``tokens`` (median of trials).

    Syncs by reading back a cache slice the prefill wrote, inside the
    timed region."""
    rates = []
    for _ in range(trials + 1):  # first = compile + warm
        engine.reset()
        t0 = time.perf_counter()
        engine.prefill(tokens, 0, chunk)
        np.asarray(engine.cache.k[-1, len(tokens) - 1, 0, :8])
        rates.append(len(tokens) / (time.perf_counter() - t0))
    return float(np.median(rates[1:]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="7b", choices=("7b", "small"))
    ap.add_argument("--prompt-len", type=int, default=1920)
    ap.add_argument("--chunk", type=int, default=480)
    args = ap.parse_args()

    import jax

    from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                    small_bench_spec,
                                                    synth_q40_fast)
    from distributed_llama_tpu.runtime.generate import Engine
    from distributed_llama_tpu.utils.compile_cache import (
        enable_persistent_cache)

    enable_persistent_cache()
    spec = (llama2_7b_spec() if args.config == "7b"
            else small_bench_spec())
    n = min(args.prompt_len, spec.seq_len - 8)
    toks = [7] * n
    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    dev = device_triple() if args.config == "small" else require_tpu()
    print(f"backend {dev}  {args.config}  "
          f"prompt {n}  chunk {args.chunk}", file=sys.stderr)
    t0 = time.perf_counter()
    params = synth_q40_fast(spec)
    print(f"synth: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    results = {}
    for mode, fast in (("parity_f32", False), ("fast_bf16", True)):
        eng = Engine(spec, params, fast_prefill=fast)
        t0 = time.perf_counter()
        rate = _measure(eng, toks, args.chunk)
        results[mode] = round(rate, 1)
        print(f"{mode:>10}: {rate:8.1f} prefill tok/s "
              f"({time.perf_counter() - t0:.1f}s incl. compile)",
              file=sys.stderr)
        del eng  # free the 7B tree before building the next engine (OOM)
        import gc

        gc.collect()
    results["speedup"] = round(results["fast_bf16"]
                               / max(results["parity_f32"], 1e-9), 2)
    print(json.dumps({"metric": "prefill tok/s", "config": args.config,
                      "prompt_len": n, "chunk": args.chunk, **results}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
