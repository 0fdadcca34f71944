"""tracecheck: validate and summarize a flight-recorder bundle.

A bundle is what ``obs/flightrec`` dumps on a watchdog trip, a SIGTERM
drain, a crash loop or a watchtower incident (``--flightrec DIR``). Exit 0 =
loadable and schema-clean, 1 = damaged (a postmortem artifact discovered
malformed mid-incident is worse than none), 2 = unreadable or not a bundle.

A profiler capture is NOT read here: ``benchmark/harness/reduce_trace.py``
is the repository's one reader of captures (``benchmark/layer_metrics/``
hold what is computed from it).

Usage:
  python tools/tracecheck.py BUNDLE.json [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _check_bundle(path: str, emit_json: bool = False) -> int:
    """Validate + summarize a flight-recorder bundle (obs/flightrec).
    Exit 0 = loadable and schema-clean, 1 = damaged, 2 = unreadable."""
    from distributed_llama_tpu.obs.flightrec import load_bundle

    try:
        bundle = load_bundle(path)
    except OSError as e:
        print(f"tracecheck: {e}", file=sys.stderr)
        return 2
    except ValueError as e:
        print(f"tracecheck: flight-recorder bundle {path} is invalid: "
              f"{e}", file=sys.stderr)
        return 1
    metric_lines = sum(1 for ln in bundle["metrics"].splitlines()
                       if ln and not ln.startswith("#"))
    summary = {
        "kind": bundle["kind"], "reason": bundle["reason"],
        # the watchtower detector that triggered an incident dump
        # (ISSUE 20) — absent on watchdog/sigterm/crash-loop bundles
        "incident_kind": bundle.get("incident_kind"),
        "ts": bundle["ts"], "pid": bundle.get("pid"),
        "events": len(bundle["events"]), "spans": len(bundle["spans"]),
        "spans_dropped": bundle["spans_dropped"],
        "metric_samples": metric_lines,
        "journal_tail_records": len(bundle["journal_tail"]),
        # scheduler forensics (ISSUE 16) — optional sections, so .get():
        # bundles from older builds simply report 0
        "census_records": len(bundle.get("census_tail", [])),
        "open_ledgers": len(bundle.get("open_ledgers", [])),
        "config_keys": sorted(bundle["config"]),
    }
    if emit_json:
        print(json.dumps(summary))
    else:
        kind = (f" incident_kind={summary['incident_kind']}"
                if summary["incident_kind"] else "")
        print(f"flight-recorder bundle OK: reason={summary['reason']}"
              f"{kind} "
              f"events={summary['events']} spans={summary['spans']} "
              f"(+{summary['spans_dropped']} dropped) "
              f"metrics={summary['metric_samples']} samples "
              f"journal_tail={summary['journal_tail_records']} records "
              f"census={summary['census_records']} "
              f"open_ledgers={summary['open_ledgers']}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tracecheck",
        description="validate + summarize a flight-recorder bundle")
    ap.add_argument("bundle", help="flight-recorder bundle .json")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON object instead "
                         "of the summary line")
    args = ap.parse_args(argv)

    from distributed_llama_tpu.obs.flightrec import is_bundle_file

    if not is_bundle_file(args.bundle):
        print(f"tracecheck: {args.bundle} is not a flight-recorder bundle; "
              f"a profiler capture is read by "
              f"benchmark/harness/reduce_trace.py", file=sys.stderr)
        return 2
    return _check_bundle(args.bundle, emit_json=args.json)


if __name__ == "__main__":
    raise SystemExit(main())
