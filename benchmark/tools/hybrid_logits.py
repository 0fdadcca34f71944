"""Logits of a hybrid configuration at its published widths, what the TIMED
programs produce against the benchmark's float32 full-forward reference
(``harness/hybrid.logits``: no cache, no state carried), on the chip.

``--entry serve`` (the cell's programs, from a ``ContinuousEngine`` at the
configuration's slots and pages): every row prefills a prompt of five chunks
(640 positions: past the 512 window, over 40 pages) through
``jit_serve_admit_prefill_chunk`` (the self-decoder alone: no logits) and is
inserted, state, rings and pages, into its slot through
``jit_serve_admit_state_insert``; then 32 positions through
``jit_serve_decode_step`` at all the slots, teacher-forced on the step's own
greedy picks: prefill then decode against the full forward. Then, on the
same rows (stale: position 0 finds state and ring empty whatever they
hold), 8 positions decoded FROM POSITION 0 with no prefill.
``--entry inference``: ``Engine.prefill`` of 680 tokens (five chunks and a
padded one), then 8 positions through ``Engine.infer``.

  python3 benchmark/tools/hybrid_logits.py [--entry serve|inference]
      [--seed N] [--low-precision 1]

Prints one JSON line; under ``--low-precision 1`` also max |d| over the same
positions against the reference with every product's operands rounded to
bfloat16, the reading the tolerance has to refuse. Exit 1 if over the
tolerance, 3 off a TPU (``--rehearse 1`` lets a CPU run through at a toy
size, for the tests). Outside any window: a check, not a measurement.
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

DECODE_POSITIONS = 32
FROM_ZERO = 8
PREFILL_CHUNKS = 5
REFERENCE_ROWS = 8


def _tokens(rng, vocab, n):
    return [1] + [int(t) for t in rng.integers(3, vocab, n - 1)]


def _reference(tree, sizes, full, keep, precision):
    from benchmark.harness import hybrid

    return np.concatenate([
        hybrid.logits(tree, sizes, full[lo:lo + REFERENCE_ROWS], keep=keep,
                      precision=precision)
        for lo in range(0, len(full), REFERENCE_ROWS)])


def check_serve(spec, tree, sizes, config, seed: int, low: bool):
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    flags = config["entries"]["serve"]
    B, chunk = int(flags["slots"]), int(flags["prefill_chunk"])
    ps = int(flags["kv_page_size"])
    n_pre = PREFILL_CHUNKS * chunk
    eng = ContinuousEngine(spec, tree, slots=B, temperature=0.0, topp=0.9,
                           seed=seed, prefill_chunk=chunk, page_size=ps,
                           kv_pages=int(flags["kv_pages"]))
    max_pages = spec.seq_len // ps
    per_row = -(-(n_pre + DECODE_POSITIONS) // ps)
    table = np.zeros((B, max_pages), np.int32)          # 0: the scrap page
    table[:, :per_row] = 1 + np.arange(B * per_row).reshape(B, per_row)
    rng = np.random.default_rng([seed, 0xC4EC])
    rows = [_tokens(rng, sizes["vocab_size"], n_pre + 1) for _ in range(B)]
    for b, toks in enumerate(rows):
        scratch = eng._scratch_cache()
        for lo in range(0, n_pre, chunk):
            lg, scratch = eng._prefill_fwd(
                eng.params, scratch, jnp.asarray(toks[lo:lo + chunk],
                                                 jnp.int32),
                jnp.int32(lo), jnp.int32(chunk))
        assert lg.shape[0] == 0     # the self-decoder alone: no logits
        eng.cache = eng._insert(eng.cache, scratch, jnp.int32(b),
                                jnp.asarray(table[b]))

    def decode(first, pos0, steps):
        """``steps`` positions for every row, greedy; -> (B, steps, V)."""
        out, tok, fed = [], np.asarray(first, np.int32), []
        picked = jnp.zeros((B,), jnp.int32)
        low_d = 1.0
        for i in range(steps):
            blk = np.concatenate(
                [tok[:, None], np.full((B, 1), pos0 + i, np.int32), table,
                 np.ones((B, 1), np.int32)], axis=1)
            lg, picked, eng.cache, decay = eng._decode(
                eng.params, eng.cache, picked, jnp.asarray(blk))
            fed.append(tok.copy())
            out.append(np.asarray(lg))
            tok = np.asarray(picked)
            low_d = min(low_d, float(np.min(decay)))
        return np.stack(out, 1), np.stack(fed, 1), low_d

    got, fed, decay = decode([r[n_pre] for r in rows], n_pre,
                             DECODE_POSITIONS)
    full = np.concatenate([np.asarray([r[:n_pre] for r in rows]), fed],
                          axis=1)                      # (B, n_pre + 32)
    keep = np.arange(n_pre, n_pre + DECODE_POSITIONS)
    d_dec = float(np.abs(got - _reference(tree, sizes, full, keep,
                                          "highest")).max())
    got0, fed0, decay0 = decode([1] * B, 0, FROM_ZERO)
    want0 = _reference(tree, sizes, fed0, np.arange(FROM_ZERO), "highest")
    per_pos = np.abs(got0 - want0).max(axis=(0, 2))
    jax.block_until_ready(eng.cache)
    out = {"entry": "serve", "rows": B, "prefill_tokens": n_pre,
           "decoded_positions": DECODE_POSITIONS,
           "max_abs_diff_decode": d_dec,
           "max_abs_diff_from_zero_by_position": [float(x) for x in per_pos],
           "ssm_min_decay_decode": decay, "ssm_min_decay_from_zero": decay0,
           "logits_std": float(got.std())}
    if low:
        out.update(low_precision_max_abs_diff=float(np.abs(
            got - _reference(tree, sizes, full, keep, "bfloat16")).max()))
    return out, max(d_dec, float(per_pos.max()))


def check_inference(spec, tree, sizes, config, seed: int, low: bool):
    from benchmark.harness import hybrid
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy
    from distributed_llama_tpu.runtime.generate import Engine

    chunk = int(config["entries"]["serve"]["prefill_chunk"])
    apply_q40_body_policy(spec, rows=1)
    engine = Engine(spec, tree)
    rng = np.random.default_rng([seed, 0xC4ED])
    tokens = _tokens(rng, sizes["vocab_size"], PREFILL_CHUNKS * chunk + 41)
    n = len(tokens)
    engine.prefill(tokens[:n - 1], 0, chunk)   # full chunks and a padded one
    got, tok = [], tokens[-1]
    for pos in range(n - 1, n - 1 + FROM_ZERO):
        got.append(np.array(engine.infer(tok, pos), np.float32))
        tok = int(np.argmax(got[-1]))
        tokens.append(tok)
    keep = np.arange(n - 1, n - 1 + FROM_ZERO)
    full = np.asarray([tokens[:-1]])
    want = hybrid.logits(tree, sizes, full, keep=keep)[0]
    worst = float(np.abs(np.stack(got) - want).max())
    out = {"entry": "inference", "prefill_tokens": n - 1,
           "decoded_positions": FROM_ZERO, "max_abs_diff_decode": worst,
           "ssm_min_decay": engine.ssm_min_decay}
    if low:
        ref_low = hybrid.logits(tree, sizes, full, precision="bfloat16",
                                keep=keep)[0]
        out.update(low_precision_max_abs_diff=float(
            np.abs(np.stack(got) - ref_low).max()))
    return out, worst


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="phi4-mini-flash-q40")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--entry", default="serve",
                    choices=("serve", "inference"))
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--low-precision", type=int, choices=(0, 1), default=0,
                    help="1: also compare with the reference run one "
                         "precision down (bf16 products), which must fail")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmark.harness import cells, hybrid, runtime

    config = cells.load_json(args.config_file or os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    hybrid.check_runnable(config)
    sizes = hybrid.sizes_of(config)
    spec = hybrid.program_spec(sizes)
    runtime.enable_compile_cache()
    try:
        device = runtime.require_devices(1, args.rehearse)
    except runtime.NoAccelerator as e:
        print(f"hybrid_logits: {e}", file=sys.stderr)
        return 3
    tree = hybrid.codec_tree(sizes, args.seed)
    check = check_serve if args.entry == "serve" else check_inference
    out, worst = check(spec, tree, sizes, config, args.seed,
                       bool(args.low_precision))
    tol = float(config["check"]["logit_abs_tolerance"])
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
