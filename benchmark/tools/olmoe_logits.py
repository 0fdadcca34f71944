"""Logits of an expert configuration at its published widths through the
program's normal ``inference`` path against the benchmark's float32
reference, as ``drivers/inference.check_logits`` does for the dense
configurations: ``Engine.prefill`` of a 32-token prompt (one padded chunk of
``prefill_chunk``: the every-expert MXU kernel), then 8 positions through
``Engine.infer`` (the slot kernel at one row), teacher-forced on the
engine's own greedy tokens; the reference runs the same 40 tokens in one
full forward pass. ``--sequences`` such sequences (about one position in a
hundred has a router margin under ``olmoe.MARGIN_EPSILON`` and ends its
sequence's comparison). Outside any window: a check, not a measurement.

  python3 benchmark/tools/olmoe_logits.py [--config olmoe-1b-7b-q40] [--seed N]

Prints one JSON line: max |d| over the compared positions, how many were
compared, the smallest margin met, the routed-expert counters; under
``--low-precision 1`` also max |d| over the same positions against the
reference with its matmuls one precision down (bf16 passes on a TPU), the
reading the configuration's tolerance has to refuse. Exit 1 if over the
configuration's tolerance, 3 off a TPU (``--rehearse 1`` lets a CPU run
through at a toy size, for the tests).
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

PROMPT_TOKENS = 32
DECODE_POSITIONS = 8


def check(engine, tree, sizes, config, seed: int, chunk: int,
          sequences: int, low_precision: bool = False) -> dict:
    from benchmark.harness import olmoe

    rng = np.random.default_rng([seed, 0xC4EC])
    rows, got = [], []
    for _ in range(sequences):     # each refills the cache from position 0
        tokens = [1] + [int(t) for t in rng.integers(
            3, sizes["vocab_size"], PROMPT_TOKENS - 1)]
        n = len(tokens)
        engine.prefill(tokens[:n - 1], 0, chunk)
        tok = tokens[-1]
        for pos in range(n - 1, n - 1 + DECODE_POSITIONS):
            got.append(np.array(engine.infer(tok, pos), np.float32))
            tok = int(np.argmax(got[-1]))
            tokens.append(tok)
        rows.append(tokens[:-1])
    want, margins = olmoe.logits(tree, sizes, np.asarray(rows),
                                 rope_base=config["rope_theta"])
    got = np.stack(got).reshape(sequences, DECODE_POSITIONS, -1)
    n = PROMPT_TOKENS
    kept = [max(0, olmoe.compared_positions(margins[b]) - (n - 1))
            for b in range(sequences)]
    compared = sum(kept)

    def worst(ref):
        return max((float(np.max(np.abs(got[b, :k] - ref[b, n - 1:n - 1 + k])))
                    for b, k in enumerate(kept) if k), default=float("nan"))

    diff = worst(want)
    tol = float(config["check"]["logit_tolerance"])
    decoded = sequences * DECODE_POSITIONS
    low = {}
    if low_precision:
        # the same positions against the reference one precision down: the
        # reading the tolerance must refuse
        ref_low, _ = olmoe.logits(tree, sizes, np.asarray(rows),
                                  rope_base=config["rope_theta"],
                                  precision="default")
        low_diff = worst(ref_low)
        low = {"low_precision_max_abs_diff": low_diff,
               "low_precision_ok": bool(low_diff <= tol)}
    return {"max_abs_diff": diff, **low,
            "tolerance": tol, "positions_compared": compared,
            "positions_decoded": decoded,
            "margin_epsilon": olmoe.MARGIN_EPSILON,
            "smallest_margin": float(margins.min()),
            "moe_pairs": engine.moe_pairs, "moe_active": engine.moe_active,
            "ok": bool(compared * 2 >= decoded and diff <= tol)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="olmoe-1b-7b-q40")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--sequences", type=int, default=8)
    ap.add_argument("--low-precision", type=int, choices=(0, 1), default=0,
                    help="1: also compare with the reference run one "
                         "precision down (bf16 passes), which must fail")
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmark.harness import cells, olmoe, runtime
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy
    from distributed_llama_tpu.runtime.generate import Engine

    config = cells.load_json(args.config_file or os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    olmoe.check_runnable(config)
    sizes = olmoe.sizes_of(config)
    spec = olmoe.program_spec(sizes)
    runtime.enable_compile_cache()
    try:
        device = runtime.require_devices(1, args.rehearse)
    except runtime.NoAccelerator as e:
        print(f"olmoe_logits: {e}", file=sys.stderr)
        return 3
    tree = olmoe.codec_tree(sizes, args.seed)
    apply_q40_body_policy(spec, rows=1)
    engine = Engine(spec, tree)
    out = check(engine, tree, sizes, config, args.seed,
                int(config["entries"]["serve"]["prefill_chunk"]),
                args.sequences, bool(args.low_precision))
    print(json.dumps(dict(out, device=device, seed=args.seed)), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
