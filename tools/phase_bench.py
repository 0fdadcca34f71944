"""Per-phase decomposition of the single-chip decode step (VERDICT r1 #3).

Round 1 measured 10.08 ms/token at 7B against a ~5.0 ms HBM floor and could
not account for ~2 ms of the difference. This tool measures, on the real
chip, a ladder of progressively fuller per-step programs — each a K-iteration
on-device scan (one dispatch, its fixed cost spread over K) — so consecutive
deltas attribute the time:

  matmuls      the 7 per-layer Q40 matmuls alone (fused wqkv/w13 layout,
               scanned over all layers) — the pure weight-streaming cost
  +glue        + rmsnorm, RoPE, residuals, SwiGLU glue (no attention/cache)
  +attention   + KV-cache update and flash decode = the full layer body
  full step    + final rmsnorm + wcls logits matmul (= forward())
  chain step   + argmax/sampling + while_loop bookkeeping
               (= the flagship fused-loop path, runtime/decode.py)

Run on the chip (through the chip tool, from the repo root): python tools/phase_bench.py
  [--config 7b|13b|small] [--iters K] [--pos P]

``--pos`` sets the cache fill position the attention phases read at (decode
cost grows with pos; default seq_len/2 = the average position of a full-
sequence generation, which is what a whole-chain ms/token averages over).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _timed(fn, *args, trials: int = 3) -> float:
    """Median wall ms of fn(*args) with full materialization."""
    fn(*args)  # compile + warm
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        np.asarray(jax.tree_util.tree_leaves(fn(*args))[0])
        times.append((time.perf_counter() - t0) * 1000)
    return float(np.median(times))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="7b", choices=("7b", "13b", "small"))
    ap.add_argument("--iters", type=int, default=32,
                    help="steps per on-device chain")
    ap.add_argument("--pos", type=int, default=-1,
                    help="cache position for the attention reads "
                         "(-1 = seq_len/2)")
    ap.add_argument("--kv-bf16", action="store_true",
                    help="bf16 KV cache for the attention/full/chain phases "
                         "(required at 13b: the f32 cache + weights exceed "
                         "one 16 GB chip)")
    args = ap.parse_args()

    global jax
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                    llama2_13b_spec,
                                                    small_bench_spec,
                                                    synth_q40_fast)
    from distributed_llama_tpu.runtime.decode import make_decode_loop
    from distributed_llama_tpu.utils.compile_cache import (
        enable_persistent_cache)

    enable_persistent_cache()
    spec = {"7b": llama2_7b_spec, "13b": llama2_13b_spec,
            "small": small_bench_spec}[args.config]()
    pos0 = spec.seq_len // 2 if args.pos < 0 else args.pos
    K = args.iters
    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    dev = device_triple() if args.config == "small" else require_tpu()
    print(f"backend {dev}  config {args.config}  "
          f"iters {K}  pos {pos0}", file=sys.stderr)

    cache_dtype = jnp.bfloat16 if args.kv_bf16 else jnp.float32

    def mk_cache():
        return llama.init_cache(spec, cache_dtype)

    t0 = time.perf_counter()
    params = llama.params_to_device(synth_q40_fast(spec))
    jax.block_until_ready(params)
    print(f"weights ready: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    from distributed_llama_tpu.ops.linear import matmul, rmsnorm, silu

    idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)

    # every phase fn takes ``params`` as an ARGUMENT (closing over the
    # device tree would bake 4+ GB of weights into each executable as
    # captured constants)
    def layer_scan(body, params, x0):
        """Scan ``body(x, lw, idx) -> x`` over the layers, K times."""
        stacked, scanned = llama.split_layer_weights(params)

        def one_iter(x, _):
            def per_layer(x, per):
                idx, lw_slice = per
                return body(x, llama.layer_view(stacked, lw_slice, idx),
                            idx), None
            x, _ = jax.lax.scan(per_layer, x, (idxs, scanned))
            return x, None

        x, _ = jax.lax.scan(one_iter, x0, None, length=K)
        return x

    x0 = jnp.ones((1, spec.dim), jnp.float32) * 0.01

    # -- phase 0: pure weight streaming (the HBM/DMA ceiling) -----------
    # reduce-sum every packed byte of every layer's weights: XLA reads the
    # same HBM bytes as the matmul phase but does no unpack/MXU work. If
    # this time ~= the matmul phase, the kernels are DMA-bound and further
    # compute-side optimization (e.g. an int8-MXU Q40xQ80 formulation,
    # reference funcs.cpp:185-260) has no headroom — the proof-of-floor
    # experiment VERDICT r1 #3 asks for.
    def stream_body(acc, lw, idx):
        # XOR with a CARRY-dependent byte: without it XLA's loop-invariant
        # code motion hoists the (iteration-independent) sums out of the
        # K-loop and the phase reads K-times too fast (observed on CPU)
        m = (acc & 1).astype(jnp.uint8)

        def bsum(a):
            if a.dtype != jnp.uint8:
                a = jax.lax.bitcast_convert_type(a, jnp.uint8)
            return jnp.sum(a ^ m, dtype=jnp.int32)

        for k, w in lw.items():
            if hasattr(w, "w"):          # StackedQ40 view (kernel layout)
                acc += bsum(w.w.qs_t[w.layer]) + bsum(w.w.scale[w.layer])
            elif hasattr(w, "qs_t"):     # per-layer Q40Kernel
                acc += bsum(w.qs_t) + bsum(w.scale)
            elif hasattr(w, "qs"):       # codec-layout Q40Weight (no pack)
                acc += bsum(w.qs) + bsum(w.d16)
            else:                        # dense f32/bf16 weight or norm vec
                acc += bsum(w)
        return acc

    p_stream = jax.jit(
        lambda params, x: layer_scan(stream_body, params, x))

    # -- phase 1: matmuls only ------------------------------------------
    def mm_body(x, lw, idx):
        if "wqkv" in lw:
            qkv = matmul(lw["wqkv"], x)
        else:
            qkv = jnp.concatenate([matmul(lw["wq"], x),
                                   matmul(lw["wk"], x),
                                   matmul(lw["wv"], x)], axis=-1)
        ao = qkv[..., :spec.dim]
        xb2 = matmul(lw["wo"], ao)
        x = x + 1e-6 * xb2
        if "w13" in lw:
            h13 = matmul(lw["w13"], x)
            hb = h13[..., :spec.hidden_dim] * h13[..., spec.hidden_dim:]
        else:
            hb = matmul(lw["w1"], x) * matmul(lw["w3"], x)
        return x + 1e-6 * matmul(lw["w2"], hb)

    p_mm = jax.jit(lambda params, x: layer_scan(mm_body, params, x))

    # -- phase 2: + glue (norms, rope, swiglu activation, q80) ----------
    positions0 = jnp.asarray([pos0])

    def glue_body(x, lw, idx):
        q, k, v = llama._qkv_proj(spec, lw, x, positions0)
        ao = q  # skip attention: feed q straight to wo
        return llama._post_attention(spec, lw, x * 1e-6, ao)

    p_glue = jax.jit(lambda params, x: layer_scan(glue_body, params, x))

    # -- phase 3: + attention/cache = the real layer body ---------------
    def full_layers(params, x, k_all, v_all):
        stacked, scanned = llama.split_layer_weights(params)

        def one_iter(carry, _):
            x, k_all, v_all = carry
            def per_layer(c, per):
                x, k_all, v_all = c
                idx, lw_slice = per
                lw = llama.layer_view(stacked, lw_slice, idx)
                x, k_all, v_all = llama._layer(
                    spec, x, lw, k_all, v_all, idx, jnp.int32(pos0),
                    positions0)
                return (x, k_all, v_all), None
            (x, k_all, v_all), _ = jax.lax.scan(per_layer, (x, k_all, v_all),
                                                (idxs, scanned))
            return (x * 1e-6, k_all, v_all), None

        (x, _, _), _ = jax.lax.scan(one_iter, (x, k_all, v_all), None,
                                    length=K)
        return x

    p_att = jax.jit(full_layers, donate_argnums=(2, 3))

    # -- phase 4: full step (forward incl. wcls) ------------------------
    def full_steps(params, cache, tok):
        def one_iter(carry, _):
            cache, tok = carry
            logits, cache = llama.forward(spec, params, cache, tok,
                                          jnp.int32(pos0))
            return (cache, tok), logits[0, 0]

        (cache, _), ls = jax.lax.scan(one_iter, (cache, tok), None, length=K)
        return ls, cache

    p_step = jax.jit(full_steps, donate_argnums=1)

    # -- phase 5: the real fused chain (decode loop) --------------------
    import functools

    run = make_decode_loop(functools.partial(llama.forward, spec),
                           spec.seq_len, temperature=0.0, topp=0.9)
    padded = np.full((spec.seq_len + 1,), 7, dtype=np.int32)
    coins = jnp.zeros((spec.seq_len,), jnp.float32)

    def p_chain():
        # start the chain at pos0 so its attention reads match the other
        # phases' (decode cost grows with position; deltas must compare
        # like with like)
        return run(params, mk_cache(), jnp.asarray(padded),
                   jnp.int32(7), coins, jnp.int32(pos0), jnp.int32(K))

    results = {}
    tok0 = jnp.asarray([7], jnp.int32)
    for name, fn, fargs in (
            ("stream", p_stream, (params, jnp.int32(0))),
            ("matmuls", p_mm, (params, x0)),
            ("glue", p_glue, (params, x0)),
            ("attention",
             lambda params, x: p_att(params, x, *mk_cache()),
             (params, x0)),
            ("full_step", lambda: p_step(params, mk_cache(), tok0), ()),
            ("chain_step", p_chain, ())):
        t0 = time.perf_counter()
        try:
            ms = _timed(fn, *fargs) / K
        except Exception as e:
            # a phase that cannot compile (e.g. the attention phase's
            # duplicated cache carries exceed HBM at 13B) must not abort
            # the ladder:
            # later phases and the JSON still carry the attribution
            results[name] = None
            print(f"{name:>10}: FAILED ({type(e).__name__}; see stderr "
                  f"above)", file=sys.stderr)
            continue
        results[name] = round(ms, 3)
        print(f"{name:>10}: {ms:7.3f} ms/step   "
              f"(compile+3 trials {time.perf_counter() - t0:.1f}s)",
              file=sys.stderr)

    def delta(a, b):
        return (round(results[a] - results[b], 3)
                if results.get(a) is not None and results.get(b) is not None
                else None)

    deltas = {
        "weight_stream_floor": results.get("stream"),
        "matmuls": results.get("matmuls"),
        "glue_delta": delta("glue", "matmuls"),
        "attention_delta": delta("attention", "glue"),
        "wcls_final_delta": delta("full_step", "attention"),
        "loop_sampling_delta": delta("chain_step", "full_step"),
    }
    print(json.dumps({"config": args.config, "iters": K, "pos": pos0,
                      "phases_ms_per_step": results, "deltas_ms": deltas}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
