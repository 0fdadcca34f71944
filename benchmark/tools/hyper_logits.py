"""``latent_logits.py`` for a configuration whose residual path is several
streams (``harness/hyper.py``; default ``xing4-29b-a4b-q40``): the logits the
TIMED programs produce at the published widths against the benchmark's
float32 reference (``hyper.logits``), on the chip. Same protocol (its
docstring has it: ``--entry serve``: a prefill of two chunks a row through
the engine's own admission programs, 64 positions through
``jit_serve_decode_step`` at all the slots, then 8 positions from position 0
on another row's stale pages; ``--entry inference``: ``Engine.prefill`` of a
full and a padded chunk, then 8 positions through ``Engine.infer``), with
what differs: an admission chunk hands out its routed-rows counts since PR
36 and they are unpacked here; a row that ONE reversed router decision
explains is read against the reference that takes the decision the same way
(``hyper.with_reversals``: every expert is held here, so a decision taken
the other way at a margin of a few float32 ulps moves the rest of its row;
the readings up to each row's first near-tie are taken BEFORE any reversal);
and ``--low-precision 1`` reads TWO controls,
every product in bfloat16 (must fail the tolerance) and the coefficient
projection ALONE in bfloat16 (reported beside the tolerance: PERF.md
section 7 says what the check guards of that product).

  python3 benchmark/tools/hyper_logits.py [--entry serve|inference]
      [--seed N] [--low-precision 1]
      [--config-file benchmark/tests/tiny-hyper.json --rehearse 1]

Prints one JSON line; exit 1 if over the tolerance, 3 off a TPU.
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
CHECK_ROWS = 8


def _tokens(rng, vocab, n):
    return [1] + [int(t) for t in rng.integers(3, vocab, n - 1)]


def _worst(got, want, margins, first: int):
    """max |d| over rows, each up to its first near-tie (``margins`` is
    over the row's whole history, ``got`` starts at its position
    ``first``); and the positions compared."""
    from benchmark.harness import hyper as latent

    worst, n = 0.0, 0
    for b in range(got.shape[0]):
        limit = latent.strict_positions(margins[b]) - first
        k = max(0, min(got.shape[1], limit))
        if k:
            worst = max(worst, float(np.abs(got[b, :k] - want[b, :k]).max()))
            n += k
    return worst, n


def _beyond(got, want, tol: float) -> dict:
    """Every position, near-ties or not: a near-tie only MAY flip an
    expert, so most rows agree past theirs too."""
    d = np.abs(got - want).max(axis=-1)
    return {"max_abs_diff_every_position": float(d.max()),
            "positions_over_tolerance": int((d > tol).sum()),
            "positions": int(d.size)}


def check_serve(spec, tree, sizes, config, seed: int, low: bool):
    import jax
    import jax.numpy as jnp

    from benchmark.harness import hyper as latent
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    flags = config["entries"]["serve"]
    B, chunk = int(flags["slots"]), int(flags["prefill_chunk"])
    ps = int(flags["kv_page_size"])
    n_pre = 2 * chunk
    eng = ContinuousEngine(spec, tree, slots=B, temperature=0.0, topp=0.9,
                           seed=seed, prefill_chunk=chunk, page_size=ps,
                           kv_pages=int(flags["kv_pages"]))
    rows_n = min(CHECK_ROWS, B)
    rng = np.random.default_rng([seed, 0xC4EC])
    rows = [_tokens(rng, sizes["vocab_size"], n_pre + 1) for _ in range(B)]
    max_pages = sizes["seq_len"] // ps
    per_row = -(-(n_pre + DECODE_POSITIONS) // ps)
    tables = np.zeros((B, max_pages), np.int32)      # 0: the scrap page
    for b in range(B):
        tables[b, :per_row] = 1 + b * per_row + np.arange(per_row)
    chunk_logits = []
    for b, toks in enumerate(rows):
        tbl = jnp.asarray(tables[b])
        seq = eng._gather_pages(eng.cache, tbl)
        for lo in range(0, n_pre, chunk):
            lg, seq, *_ = eng._prefill_fwd(      # ..., routed-rows counts
                eng.params, seq, jnp.asarray(toks[lo:lo + chunk], jnp.int32),
                jnp.int32(lo))
        if b < rows_n:
            chunk_logits.append(np.asarray(lg))
        eng.cache = eng._scatter_pages(eng.cache, seq, tbl)

    def decode(first, pos0, steps, tbls):
        """``steps`` positions for every row, greedy; (B, steps, V), fed."""
        out, fed, tok = [], [], np.asarray(first, np.int32)
        picked = jnp.zeros((B,), jnp.int32)
        for i in range(steps):
            blk = np.concatenate([tok[:, None], np.full((B, 1), pos0 + i,
                                                        np.int32), tbls], 1)
            lg, picked, eng.cache, counts = eng._decode(
                eng.params, eng.cache, picked, jnp.asarray(blk))
            fed.append(tok.copy())
            out.append(np.asarray(lg)[:rows_n])
            tok = np.asarray(picked)
        return np.stack(out, 1), np.stack(fed, 1), np.asarray(counts)

    got, fed, counts = decode([r[n_pre] for r in rows], n_pre,
                              DECODE_POSITIONS, tables)
    full = np.concatenate([np.asarray([r[:n_pre] for r in rows]), fed],
                          axis=1)[:rows_n]
    keep = np.tile(np.arange(chunk, n_pre + DECODE_POSITIONS), (rows_n, 1))
    precisions = latent.PRECISIONS if low else ("highest",)
    want, margins = latent.logits(tree, sizes, full, keep=keep,
                                  precisions=precisions)

    def readings(w):
        d_chunk, n1 = _worst(np.stack(chunk_logits), w[:, :chunk], margins,
                             chunk)
        d_dec, n2 = _worst(got, w[:, chunk:], margins, n_pre)
        return d_chunk, d_dec, n1, n2

    d_chunk, d_dec, n_chunk, n_dec = readings(want["highest"])
    tol = float(config["check"]["logit_tolerance"])
    limits = [latent.strict_positions(m) for m in margins]
    served = np.concatenate([np.stack(chunk_logits), got], axis=1)

    def first_bad(b, want_b):
        over = np.nonzero(np.abs(served[b] - want_b).max(axis=-1) > tol)[0]
        return chunk + int(over[0]) if over.size else None

    # every expert is held, so ONE router decision the program took the
    # other way at a margin of a few float32 ulps moves the rest of its row:
    # such a row is compared with the reference that takes it the same way
    reversed_ = latent.with_reversals(tree, sizes, full, keep,
                                      want["highest"], margins, first_bad)
    every = _beyond(served, want["highest"], tol)
    # from position 0 with no prefill, each row on the pages the NEXT row
    # just filled: what a reused page holds must not reach the new sequence
    got0, fed0, _ = decode([1] * B, 0, FROM_ZERO, np.roll(tables, 1, axis=0))
    want0, margins0 = latent.logits(tree, sizes, fed0[:rows_n])
    d_zero, n_zero = _worst(got0, want0["highest"], margins0, 0)
    jax.block_until_ready(eng.cache)
    held = spec.held_columns
    out = {"entry": "serve", "rows": B, "rows_compared": rows_n,
           "prefill_tokens": n_pre, "decoded_positions": DECODE_POSITIONS,
           "max_abs_diff_second_chunk": d_chunk,
           "max_abs_diff_decode": d_dec,
           "max_abs_diff_from_zero_on_reused_pages": d_zero,
           "positions_compared": [n_chunk, n_dec, n_zero],
           "first_near_tie_by_row": limits,
           "decisions_reversed": [list(r) for r in reversed_], **every,
           "smallest_margin": float(min(margins.min(), margins0.min())),
           "last_step_pairs": int(counts.sum()),
           "last_step_pairs_here": int(counts[:, held].sum())}
    if low:
        out.update(low_precision=_beyond(served, want["bfloat16"], tol),
                   low_projection=_beyond(served,
                                          want["projection_bfloat16"], tol))
    return out, max(d_chunk, d_dec, d_zero)


def check_inference(spec, tree, sizes, config, seed: int, low: bool):
    from benchmark.harness import hyper as latent
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
    keep = np.arange(n - 1, n - 1 + FROM_ZERO)[None]
    full = np.asarray([tokens[:-1]])
    precisions = latent.PRECISIONS if low else ("highest",)
    want, margins = latent.logits(tree, sizes, full, keep=keep,
                                  precisions=precisions)
    worst, compared = _worst(np.stack(got)[None], want["highest"], margins,
                             n - 1)
    out = {"entry": "inference", "prefill_tokens": n - 1,
           "decoded_positions": FROM_ZERO, "positions_compared": compared,
           "max_abs_diff_decode": worst,
           "smallest_margin": float(margins.min())}
    tol = float(config["check"]["logit_tolerance"])
    out.update(_beyond(np.stack(got)[None], want["highest"], tol))
    if low:
        out.update(low_precision=_beyond(np.stack(got)[None],
                                         want["bfloat16"], tol),
                   low_projection=_beyond(np.stack(got)[None],
                                          want["projection_bfloat16"], tol))
    return out, worst


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="xing4-29b-a4b-q40")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--entry", default="serve",
                    choices=("serve", "inference"))
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--low-precision", type=int, choices=(0, 1), default=0,
                    help="1: also compare with the reference run one "
                         "precision down (bfloat16), which must fail, and "
                         "with only its coefficient projection so")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmark.harness import cells, runtime
    from benchmark.harness import hyper as latent

    config = cells.load_json(args.config_file or os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    latent.check_runnable(config)
    sizes = latent.sizes_of(config)
    spec = latent.program_spec(sizes)
    runtime.enable_compile_cache()
    try:
        device = runtime.require_devices(1, args.rehearse)
    except runtime.NoAccelerator as e:
        print(f"hyper_logits: {e}", file=sys.stderr)
        return 3
    tree = latent.codec_tree(sizes, args.seed)
    check = check_serve if args.entry == "serve" else check_inference
    out, worst = check(spec, tree, sizes, config, args.seed,
                       bool(args.low_precision))
    tol = float(config["check"]["logit_tolerance"])
    # strictly up to each row's first near-tie; past it an expert MAY flip,
    # in a row or two, not everywhere
    ok = bool(worst <= tol
              and 50 * out["positions_over_tolerance"] <= out["positions"])
    if "low_precision" in out:
        out["low_precision_ok"] = bool(
            out["low_precision"]["max_abs_diff_every_position"] <= tol)
        out["low_projection_ok"] = bool(
            out["low_projection"]["max_abs_diff_every_position"] <= tol)
    print(json.dumps(dict(out, tolerance=tol, ok=ok, device=dict(
        device, memory_peak_bytes=runtime.memory_peak_bytes()),
        seed=args.seed)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
