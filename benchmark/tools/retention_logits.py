"""Logits of a retention configuration at its published widths, what the
TIMED programs produce against the benchmark's float32 attention-form
reference (``harness/retention.logits``: no state, no chunks), on the chip.

``--entry serve`` (the cell's programs, from a ``ContinuousEngine`` at the
configuration's slots): every row prefills a prompt of two chunks through
``jit_serve_admit_prefill_chunk`` (the second chunk reads the state the
first left) and is inserted into its slot; then 64 positions through
``jit_serve_decode_step`` at all the slots, teacher-forced on the step's own
greedy picks. Compared: the second chunk's logits of two rows, and every
row's 64 decoded positions. Then, on the same rows (stale: position 0 finds
a state empty whatever it holds), 8 positions decoded
FROM POSITION 0 with no prefill: the reading of a decode step on a sequence
of under three positions, where the normaliser phi(q).z is a single term
read by cancellation (reported apart, and inside the same tolerance).
``--entry inference``: ``Engine.prefill`` of 128 tokens, then 8 positions
through ``Engine.infer``.

  python3 benchmark/tools/retention_logits.py [--entry serve|inference]
      [--seed N] [--low-precision 1]

Prints one JSON line; under ``--low-precision 1`` also max |d| over the same
positions against the reference with its matmuls one precision down (bf16
passes on a TPU), the reading the configuration's tolerance has to refuse.
Exit 1 if over the tolerance, 3 off a TPU (``--rehearse 1`` lets a CPU run
through at a toy size, for the tests). Outside any window: a check, not a
measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

DECODE_POSITIONS = 64
FROM_ZERO = 8
PREFILL_ROWS = 2


def _tokens(rng, vocab, n):
    return [1] + [int(t) for t in rng.integers(3, vocab, n - 1)]


def check_serve(spec, tree, sizes, config, seed: int, low: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.harness import retention
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    flags = config["entries"]["serve"]
    B, chunk = int(flags["slots"]), int(flags["prefill_chunk"])
    n_pre = 2 * chunk
    eng = ContinuousEngine(spec, tree, slots=B, temperature=0.0, topp=0.9,
                           seed=seed, prefill_chunk=chunk)
    rng = np.random.default_rng([seed, 0xC4EC])
    rows = [_tokens(rng, sizes["vocab_size"], n_pre + 1) for _ in range(B)]
    chunk_logits = []
    for b, toks in enumerate(rows):
        scratch = eng._scratch_cache()
        for lo in range(0, n_pre, chunk):
            lg, scratch = eng._prefill_fwd(
                eng.params, scratch, jnp.asarray(toks[lo:lo + chunk],
                                                 jnp.int32),
                jnp.int32(lo), jnp.int32(chunk))
        if b < PREFILL_ROWS:
            chunk_logits.append(np.asarray(lg))
        eng.cache = eng._insert(eng.cache, scratch, jnp.int32(b))

    def decode(first, pos0, steps):
        """``steps`` positions for every row, greedy; -> (B, steps, V)."""
        out, tok = [], np.asarray(first, np.int32)
        picked = jnp.zeros((B,), jnp.int32)
        fed = []
        for i in range(steps):
            blk = np.stack([tok, np.full(B, pos0 + i, np.int32),
                            np.ones(B, np.int32)], axis=1)
            lg, picked, eng.cache, low_n = eng._decode(
                eng.params, eng.cache, picked, jnp.asarray(blk))
            fed.append(tok.copy())
            out.append(np.asarray(lg))
            tok = np.asarray(picked)
        return np.stack(out, 1), np.stack(fed, 1), float(np.min(low_n))

    got, fed, low_n = decode([r[n_pre] for r in rows], n_pre,
                             DECODE_POSITIONS)
    full = np.asarray([r[:n_pre] for r in rows])
    full = np.concatenate([full, fed], axis=1)        # (B, n_pre + 64)
    keep = np.arange(chunk, n_pre + DECODE_POSITIONS)

    def readings(precision):
        want = retention.logits(tree, sizes, full, precision=precision,
                                keep=keep)
        d_chunk = max(float(np.abs(chunk_logits[b] - want[b, :chunk]).max())
                      for b in range(PREFILL_ROWS))
        d_dec = float(np.abs(got - want[:, chunk:]).max())
        return d_chunk, d_dec

    d_chunk, d_dec = readings("highest")
    # decode from position 0 with no prefill, on the rows as they stand
    # (a row's first position finds its state empty whatever it holds)
    got0, fed0, low0 = decode([1] * B, 0, FROM_ZERO)
    want0 = retention.logits(tree, sizes, fed0)
    per_pos = np.abs(got0 - want0).max(axis=(0, 2))
    jax.block_until_ready(eng.cache)
    out = {"entry": "serve", "rows": B, "prefill_tokens": n_pre,
           "decoded_positions": DECODE_POSITIONS,
           "max_abs_diff_second_chunk": d_chunk,
           "max_abs_diff_decode": d_dec,
           "max_abs_diff_from_zero_by_position": [float(x) for x in per_pos],
           "min_normaliser_decode": low_n, "min_normaliser_from_zero": low0}
    worst = max(d_chunk, d_dec, float(per_pos.max()))
    if low:
        l_chunk, l_dec = readings("bfloat16")
        out.update(low_precision_max_abs_diff=max(l_chunk, l_dec))
    return out, worst


def check_inference(spec, tree, sizes, config, seed: int, low: bool):
    from benchmark.harness import retention
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy
    from distributed_llama_tpu.runtime.generate import Engine

    chunk = int(config["entries"]["serve"]["prefill_chunk"])
    apply_q40_body_policy(spec, rows=1)
    engine = Engine(spec, tree)
    rng = np.random.default_rng([seed, 0xC4ED])
    tokens = _tokens(rng, sizes["vocab_size"], chunk + 40)
    n = len(tokens)
    engine.prefill(tokens[:n - 1], 0, chunk)   # a full chunk and a padded one
    got, tok = [], tokens[-1]
    for pos in range(n - 1, n - 1 + FROM_ZERO):
        got.append(np.array(engine.infer(tok, pos), np.float32))
        tok = int(np.argmax(got[-1]))
        tokens.append(tok)
    keep = np.arange(n - 1, n - 1 + FROM_ZERO)
    full = np.asarray([tokens[:-1]])
    want = retention.logits(tree, sizes, full, keep=keep)[0]
    worst = float(np.abs(np.stack(got) - want).max())
    out = {"entry": "inference", "prefill_tokens": n - 1,
           "decoded_positions": FROM_ZERO, "max_abs_diff_decode": worst,
           "min_normaliser": engine.min_normaliser}
    if low:
        ref_low = retention.logits(tree, sizes, full, precision="bfloat16",
                                   keep=keep)[0]
        out.update(low_precision_max_abs_diff=float(
            np.abs(np.stack(got) - ref_low).max()))
    return out, worst


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="brumby-14b-q40")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--entry", default="serve",
                    choices=("serve", "inference"))
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--low-precision", type=int, choices=(0, 1), default=0,
                    help="1: also compare with the reference run one "
                         "precision down (bf16 passes), which must fail")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmark.harness import cells, retention, runtime

    config = cells.load_json(args.config_file or os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    retention.check_runnable(config)
    sizes = retention.sizes_of(config)
    spec = retention.program_spec(sizes)
    runtime.enable_compile_cache()
    try:
        device = runtime.require_devices(1, args.rehearse)
    except runtime.NoAccelerator as e:
        print(f"retention_logits: {e}", file=sys.stderr)
        return 3
    tree = retention.codec_tree(sizes, args.seed)
    check = check_serve if args.entry == "serve" else check_inference
    out, worst = check(spec, tree, sizes, config, args.seed,
                       bool(args.low_precision))
    tol = float(config["check"]["logit_tolerance"])
    ok = bool(worst <= tol)
    if "low_precision_max_abs_diff" in out:
        out["low_precision_ok"] = bool(
            out["low_precision_max_abs_diff"] <= tol)
    print(json.dumps(dict(out, tolerance=tol, ok=ok, device=dict(
        device, memory_peak_bytes=runtime.memory_peak_bytes()),
        seed=args.seed)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
