"""I/T attribution from a --profile trace (VERDICT r1 #5).

The reference's published benchmark metric is the per-task-type wall-time
split: every task is tagged INFERENCE or TRANSFER and the TaskLoop
accumulates time per tag across the barrier (src/utils.cpp:101-109), printed
per token as "I ... ms T ... ms" (src/tokenizer.cpp:381). Under XLA there is
no task table — the compiler schedules compute and collectives inside one
program — so the equivalent split must come from the profiler: this tool
parses a ``--profile`` xplane trace (jax.profiler.trace output) and buckets
every device-op event into

  I = device compute ns (matmuls, fusions, attention kernels, ...)
  T = collective ns (all-gather / all-reduce / reduce-scatter /
      collective-permute / all-to-all / send / recv — the ICI/DCN ops that
      replaced the reference's socket sync* tasks)

then prints the reference-shaped per-token line. Caveat the reference never
had: XLA can overlap collectives with compute (async start/done pairs), so
I and T measure *op activity*, which may sum to more than wall clock — the
honest TPU analog of barrier-serialized task timing.

Usage:
  python tools/it_split.py TRACE_DIR [--tokens N] [--top K]

TRACE_DIR is the --profile directory (the newest *.xplane.pb under it is
parsed; a direct .pb path also works). --tokens divides totals into
per-token ms for the 🔶-line comparison.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import os
import re
import sys

# HLO-op-shaped event names: lower-case (optionally ONE leading underscore —
# jit-named Pallas custom calls like '_q40_matmul_stacked' carry their
# Python fn name), no spaces/namespacing. Rejects runtime bookkeeping
# ('Rendezvous', 'PjRtCpuExecutable::ExecuteHelper', 'Handle inputs',
# '$profiler.py...') and dunder helpers ('__xla_...').
_OP_RE = re.compile(r"^_?[a-z][\w.\-]*$")
# 'end: X' markers, whole-module events, and control-flow ENVELOPES
# (while/cond/call thunks contain their body ops, which are traced as their
# own events) would double-count their contents
_SKIP_RE = re.compile(r"^(end: |jit_|begin: |(while|conditional|call)"
                      r"(\.\d+)?$)")
_COLLECTIVE_RE = re.compile(
    r"all[_-]gather|all[_-]reduce|reduce[_-]scatter|collective[_-]permute"
    r"|all[_-]to[_-]all|collective[_-]broadcast|\bsend\b|\brecv\b"
    r"|^send|^recv|ragged[_-]all[_-]to[_-]all")
# TPU 'XLA Ops' lines carry the full HLO instruction text
# ('%fusion.3 = f32[...] fusion(...)') — extract the instruction name
_HLO_RE = re.compile(r"^%([\w.\-]+)\s*=")


@dataclasses.dataclass
class DeviceSplit:
    """Per-device (plane/line) op-time totals, in nanoseconds."""
    inference_ns: float = 0.0
    transfer_ns: float = 0.0
    ops: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)  # name -> ns

    @property
    def total_ns(self) -> float:
        return self.inference_ns + self.transfer_ns


def find_xplane(path: str) -> str:
    """Resolve a --profile dir (or direct file) to the newest .xplane.pb."""
    if os.path.isfile(path):
        return path
    hits = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path!r} — was the "
                                f"run started with --profile?")
    return max(hits, key=os.path.getmtime)


def _is_op_line(plane_name: str, line_name: str, has_xla_ops: bool) -> bool:
    """Which trace lines carry per-op events?

    TPU planes ('/device:TPU:N') expose a dedicated 'XLA Ops' line; when one
    exists, use only it (other lines hold module/step envelopes that would
    double-count). The CPU backend ('/host:CPU') instead interleaves thunk
    events on per-executable 'tf_XLAPjRtCpuClient/...' lines.
    """
    if has_xla_ops:
        return line_name == "XLA Ops"
    return line_name.startswith("tf_") or plane_name.startswith("/device:")


def parse_trace(path: str) -> dict[str, DeviceSplit]:
    """Parse an xplane file into per-device I/T splits.

    Keys are 'plane-name[/line]' — one entry per device for TPU traces, one
    per virtual-device executor thread for CPU-mesh traces.
    """
    from jax.profiler import ProfileData

    out: dict[str, DeviceSplit] = {}
    for plane in ProfileData.from_file(find_xplane(path)).planes:
        lines = list(plane.lines)
        has_xla_ops = any(ln.name == "XLA Ops" for ln in lines)
        for line in lines:
            if not _is_op_line(plane.name, line.name, has_xla_ops):
                continue
            split = DeviceSplit()
            for ev in line.events:
                name = ev.name
                hlo = _HLO_RE.match(name)
                if hlo:
                    name = hlo.group(1)
                if _SKIP_RE.search(name) or not _OP_RE.match(name):
                    continue
                ns = float(ev.duration_ns)
                base = name.split(".")[0]
                split.ops[base] += ns
                if _COLLECTIVE_RE.search(name):
                    split.transfer_ns += ns
                else:
                    split.inference_ns += ns
            if split.ops:
                key = (plane.name if has_xla_ops
                       else f"{plane.name}/{line.name}")
                # a plane may emit several op lines (rare); accumulate
                prev = out.setdefault(key, DeviceSplit())
                prev.inference_ns += split.inference_ns
                prev.transfer_ns += split.transfer_ns
                prev.ops.update(split.ops)
    if not out:
        raise ValueError(f"no op events found in {path!r} (empty trace?)")
    return out


def bucket_ops(trace_dir: str, denom: int = 1) -> dict[str, float]:
    """Op time from a trace grouped by kernel family, in ms (divided by
    ``denom``, e.g. steps or tokens) — THE one copy of the family
    classifier used by bench.py and tools/continuous_bench.py (the buckets are a measurement contract
    cited in BASELINE.md).

    Known blind spot: the match is by HLO instruction NAME. Pallas custom
    calls keep their Python fn name ('_q40_matvec...'), but XLA-FALLBACK
    matmuls (the dequant-then-dot path) usually execute inside fused
    instructions literally named 'fusion.N', so on fallback paths their
    compute lands in ``fusion_layout``/``other`` and ``q40_kernels``
    undercounts. Attribution consumers must not read ``fusion_layout`` as
    pure layout overhead when the traced program ran the XLA path."""
    return bucket_ops_from_splits(parse_trace(trace_dir), denom)


def bucket_ops_from_splits(splits: dict[str, DeviceSplit],
                           denom: int = 1) -> dict[str, float]:
    """`bucket_ops` over an already-parsed trace (callers that also need
    the I/T split parse the multi-hundred-MB xplane file ONCE and feed
    both consumers)."""
    buckets: dict[str, float] = {}
    for split in splits.values():
        for name, ns in split.ops.items():
            n = name.lower()
            if "q40" in n or "matmul" in n or "matvec" in n or "mxu" in n:
                b = "q40_kernels"
            elif "attention" in n or "flash" in n:
                b = "attention"
            elif n.startswith(("fusion", "transpose", "copy", "bitcast",
                               "reshape", "convert", "dynamic")):
                b = "fusion_layout"
            else:
                b = "other"
            buckets[b] = buckets.get(b, 0.0) + ns
    return {k: round(v / 1e6 / max(denom, 1), 3)
            for k, v in sorted(buckets.items())}


def summarize(splits: dict[str, DeviceSplit], tokens: int = 0,
              top: int = 8, out=None, note: str = "") -> tuple[float, float]:
    """Print the reference-shaped split; returns (I_ms, T_ms) averaged
    across devices (per token when ``tokens`` > 0). ``note`` extends the
    caveat parenthetical (e.g. the CLI flags that the traced region also
    contains prefill work)."""
    out = out or sys.stdout
    n_dev = len(splits)
    i_ms = sum(s.inference_ns for s in splits.values()) / n_dev / 1e6
    t_ms = sum(s.transfer_ns for s in splits.values()) / n_dev / 1e6
    denom = max(tokens, 1)
    unit = "ms/token" if tokens else "ms"
    print(f"🔶 I {i_ms / denom:10.3f} {unit}  T {t_ms / denom:10.3f} {unit}"
          f"  ({n_dev} device{'s' if n_dev != 1 else ''}, op-time avg;"
          f" I=compute T=collectives{note})", file=out)
    agg: collections.Counter = collections.Counter()
    for s in splits.values():
        agg.update(s.ops)
    width = max((len(k) for k, _ in agg.most_common(top)), default=4)
    for name, ns in agg.most_common(top):
        tag = "T" if _COLLECTIVE_RE.search(name) else "I"
        print(f"   {tag} {name:<{width}} {ns / n_dev / denom / 1e6:10.3f} "
              f"{unit}", file=out)
    return i_ms / denom, t_ms / denom


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="it_split", description="per-token I/T split from a --profile "
                                     "trace (reference utils.cpp:101-109 "
                                     "semantics, profiler-derived)")
    ap.add_argument("trace", help="--profile directory or .xplane.pb file")
    ap.add_argument("--tokens", type=int, default=0,
                    help="tokens generated under the trace (divides totals "
                         "into per-token ms)")
    ap.add_argument("--top", type=int, default=8,
                    help="show the K most expensive ops")
    args = ap.parse_args(argv)
    summarize(parse_trace(args.trace), tokens=args.tokens, top=args.top)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
