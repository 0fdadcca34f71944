"""Cut a recorded ``.xplane.pb`` down to a fixture for the reducer's test:
the "XLA Ops" and "XLA Modules" lines of the device planes and the benchmark's host spans,
inside ``--ms`` milliseconds of the ``bench.window`` span (from ``--skip-ms`` after its start), with
only the metadata those events use. Needs the xplane protobuf classes that
TensorFlow ships (this tool only; the benchmark reads traces with JAX).

  python3 benchmark/tools/trim_trace.py <in.xplane.pb> <out.xplane.pb> --skip-ms 300 --ms 40
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--ms", type=float, default=40.0)
    ap.add_argument("--skip-ms", type=float, default=0.0,
                    help="start this long after the window's start")
    args = ap.parse_args()
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark.harness import reduce_trace as rt

    space = xplane_pb2.XSpace()
    with open(args.src, "rb") as fh:
        space.ParseFromString(fh.read())

    def abs_ps(line, ev):
        return line.timestamp_ns * 1000 + ev.offset_ps

    lo = None
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        for line in plane.lines:
            for ev in line.events:
                if names.get(ev.metadata_id) == "bench.window":
                    lo = abs_ps(line, ev)
    if lo is None:
        raise SystemExit("no bench.window span in the trace")
    lo += int(args.skip_ms * 1e9)
    hi = lo + int(args.ms * 1e9)
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = rt.DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        new = out.planes.add()
        new.id, new.name = plane.id, plane.name
        used_events = set()
        for line in plane.lines:
            if device and line.name not in (rt.OPS_LINE, rt.MODULES_LINE):
                continue
            keep = []
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                start = abs_ps(line, ev)
                if device:
                    ok = start >= lo and start + ev.duration_ps <= hi
                elif name == "bench.window":
                    ok = True
                else:
                    ok = (name.startswith(rt.SPAN_PREFIXES)
                          and start >= lo and start + ev.duration_ps <= hi)
                if ok:
                    keep.append(ev)
            if not keep:
                continue
            nl = new.lines.add()
            nl.id, nl.name = line.id, line.name
            nl.display_name = line.display_name
            nl.timestamp_ns = line.timestamp_ns
            for ev in keep:
                ne = nl.events.add()
                ne.CopyFrom(ev)
                if names.get(ev.metadata_id) == "bench.window":
                    ne.offset_ps += lo - abs_ps(line, ev)
                    ne.duration_ps = hi - lo
                # an op's name is its whole HLO text: no stat is needed
                del ne.stats[:]
                used_events.add(ev.metadata_id)
        for k in used_events:
            m = new.event_metadata[k]
            m.id, m.name = k, plane.event_metadata[k].name
    with open(args.dst, "wb") as fh:
        fh.write(out.SerializeToString())
    print(f"{args.dst}: {os.path.getsize(args.dst)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
