"""One cell, once, in a new process:

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown``; narration goes to standard error. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics. The process exits with another code than 0, and prints no result,
when JAX finds no TPU or fewer chips than the cell asks for, or when the
program is not there to be measured.

This file knows no cell, configuration, traffic mix, metric or entry point
by name: ``harness/cells.py`` finds each by the name ``BENCHMARK.json``
gives it (see ``benchmark/README.md``).
"""

from __future__ import annotations

import time

T_START = time.time()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, reduce_trace, runtime  # noqa: E402

EXIT_NO_ACCELERATOR = 3
EXIT_BAD_BENCHMARK = 4
EXIT_NO_PROGRAM = 5


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for tests and for looking at a trace by hand; the driver passes neither
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0,
                    help="let a run through off the TPU; its result is "
                         "marked not correct")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's files to DIR (traced runs)")
    return ap.parse_args(argv)


def read_metrics(run, entries, kind: str) -> dict:
    """Each metric's own reader over the run. A reader that finds nothing
    to read returns None and the metric is left out of the line."""
    out = {}
    for m in entries:
        value = cells.load_reader(kind, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run, args) -> dict:
    cell = run.cell
    failed = sum(not r["ok"] for r in run.records)
    device = dict(run.device, memory_peak_bytes=runtime.memory_peak_bytes())
    line = {"correct": False, "attempted": len(run.records),
            "failed": failed, "metrics": {}, "device": device}
    compiles = run.delta("compiles")
    if args.trace:
        line["metrics"] = read_metrics(run, cell.per_layer, "layer_metrics")
        b = reduce_trace.busy(run.trace)
        if b["busy_s"]:
            device["busy_s"] = sum(b["busy_s"].values()) / len(b["busy_s"])
        device["window_s"] = b["window_s"]
        line["breakdown"] = {"device_ops": reduce_trace.top_ops(run.trace),
                             "idle_gaps": reduce_trace.idle_gaps(run.trace)}
    else:
        line["metrics"] = read_metrics(run, cell.end_to_end, "end_to_end")
    line["correct"] = bool(
        run.device["platform"] == "tpu" and failed == 0 and run.records
        and compiles == 0 and all(c["ok"] for c in run.checks))
    for c in run.checks:
        runtime.note(f"check {'ok ' if c['ok'] else 'FAILED'}: {c['what']} "
                     f"{c['detail']}")
    for r in [r for r in run.records if not r["ok"]][:5]:
        runtime.note(f"request {r['id']} failed: {r['error']}")
    if compiles:
        runtime.note(f"{compiles} program(s) made INSIDE the window")
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = cells.load_cell(args.workload)
        driver = cells.load_driver(cell.traffic["entry"])
    except cells.BadBenchmark as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_BAD_BENCHMARK
    try:
        import distributed_llama_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e})",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    try:
        run = driver.run(cell, args, T_START)
    except runtime.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    line = result_line(run, args)
    extras = getattr(driver, "narrate", None)
    if extras is not None:
        for text in extras(run):
            runtime.note(text)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
