"""Find the highest arrival rate an open-loop serving cell sustains: ONE
process builds the server once and feeds it a short window at each rate.
The knee is the highest rate at which the backlog (requests due whose first
token has not come) at the window's end is no larger than at its middle,
give or take one. Run once, on
the chip; the cell's rate (0.8 x the knee) is then a number in its traffic
file, and the sweep is recorded in PERF.md.

  python3 benchmark/tools/knee_sweep.py --workload mistral7b.serve-chat \
      --rates 1.5 2.0 2.5 3.0 3.5 --seconds 30 [--rehearse 1]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def backlog(records, t: float) -> int:
    """Requests due by ``t`` whose first sampled token had not come by
    ``t``: waiting for a slot or in their prefill. (Requests merely in
    flight would count the slots' own occupancy, which grows with the rate
    below the knee too.)"""
    return sum(1 for r in records if r["due"] <= t
               and (not r["stamps"] or r["stamps"][0] > t))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    from benchmark.drivers import serve
    from benchmark.harness import cells, runtime, traffic

    cell = cells.load_cell(a.workload)
    args = argparse.Namespace(seed=a.seed, seconds=a.seconds, trace=0,
                              rehearse=a.rehearse, keep_trace=None)
    rows = []
    with serve.Served(cell, args) as served:
        for rate in a.rates:
            mix = dict(cell.traffic, arrival=dict(cell.traffic["arrival"],
                                                  rate_per_s=rate))
            plan = traffic.generate(mix, a.seed, a.seconds)
            w = served.window(plan, a.seconds)
            run = runtime.Run(
                cell=dataclasses.replace(cell, traffic=mix), seed=a.seed,
                window_s=a.seconds, setup_s=0.0, records=w["records"],
                device=served.device, counters_before=w["before"],
                counters_after=w["after"])
            recs = w["records"]
            done_in = sum(1 for r in recs
                          if r["done"] is not None and r["done"] <= a.seconds)
            steps = run.delta("steps")
            rows.append({
                "rate_per_s": rate, "sent": len(recs),
                "failed": sum(not r["ok"] for r in recs),
                "completed_in_window_share": round(done_in / len(recs), 3),
                "backlog_mid": backlog(recs, a.seconds / 2),
                "backlog_end": backlog(recs, a.seconds),
                "ttft_ms_p50": runtime.median(run.ttft_ms()),
                "ttft_ms_p95": runtime.percentile(run.ttft_ms(), 95),
                "gap_ms_p50": runtime.median(run.gaps_ms()),
                "gap_ms_p95": runtime.percentile(run.gaps_ms(), 95),
                "rows_per_dispatch": (run.delta("sum_active") / steps
                                      if steps else None),
                "tokens_per_s": sum(
                    1 for r in recs for t in r["stamps"]
                    if 0 <= t <= a.seconds) / a.seconds,
            })
            print(json.dumps(rows[-1]), flush=True)
    sustained = [r["rate_per_s"] for r in rows
                 if r["backlog_end"] <= r["backlog_mid"] + 1
                 and not r["failed"]]
    knee = max(sustained) if sustained else None
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": knee and round(0.8 * knee, 2)}))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"knee_{a.workload}.json"), "w") as fh:
        json.dump({"rows": rows, "knee_per_s": knee}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
