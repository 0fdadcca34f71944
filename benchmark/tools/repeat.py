"""Run one cell several times, each run a new process with another seed, and
print each metric's median and spread (the distance between the quartiles
over the median): how a bound is set, and how two sets of runs are compared.

  python3 benchmark/tools/repeat.py --workload <name> --runs 6 --seed0 100 \
      [--seconds S] [--trace 0|1] [--tag set1]

Every result line, with the end of the run's narration, is appended to
``chiprun_out/<workload>.<tag>.jsonl``. The first run of a cell in a
checkout compiles, so its ``setup_s`` is shown apart and left out of the
spread, as the driver does.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness.runtime import percentile  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default="set")
    ap.add_argument("--first-is-cold", type=int, default=0,
                    help="1: the first run compiles; leave its setup_s out")
    ap.add_argument("--extra", nargs=argparse.REMAINDER, default=[])
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    seconds = args.seconds or bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{args.workload}.{args.tag}.jsonl")
    lines = []
    for i in range(args.runs):
        t0 = time.time()
        proc = subprocess.run(
            [*bench["command"], "--workload", args.workload, "--seed",
             str(args.seed0 + i), "--seconds", str(seconds), "--trace",
             str(args.trace), *args.extra],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        last = proc.stdout.strip().splitlines()[-1:] or [""]
        try:
            line = json.loads(last[0])
        except ValueError:
            line = None
        rec = {"seed": args.seed0 + i, "rc": proc.returncode,
               "wall_s": round(wall, 1), "line": line,
               "stderr_tail": proc.stderr[-2500:]}
        with open(out_path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(rec) + "\n")
        if line is None or proc.returncode != 0:
            print(f"run {i}: rc {proc.returncode}, no result\n"
                  f"{proc.stderr[-3000:]}", flush=True)
            continue
        lines.append(line)
        vals = {k: round(v["value"], 4) for k, v in line["metrics"].items()}
        print(f"run {i} seed {args.seed0 + i} wall {wall:.0f}s correct "
              f"{line['correct']} attempted {line['attempted']} failed "
              f"{line['failed']} peak "
              f"{line['device'].get('memory_peak_bytes', 0) / 1e9:.2f}GB "
              f"{vals}", flush=True)
        if not line["correct"]:
            print(proc.stderr[-1500:], flush=True)
    names = sorted({k for ln in lines for k in ln["metrics"]})
    print(f"\n{args.workload} [{args.tag}] {len(lines)} runs of {seconds}s")
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        if name == "setup_s" and args.first_is_cold and len(vals) > 1:
            print(f"  setup_s first (compiles): {vals[0]:.2f}")
            vals = vals[1:]
        q1, med, q3 = (percentile(vals, q) for q in (25, 50, 75))
        spread = (q3 - q1) / med if med else float("nan")
        print(f"  {name:34s} median {med:12.4f}  spread {100 * spread:6.2f}%"
              f"  min {min(vals):.4f} max {max(vals):.4f}  n {len(vals)}")
    return 0 if len(lines) == args.runs else 1


if __name__ == "__main__":
    sys.exit(main())
