"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

``run.py`` knows no cell, configuration, traffic mix or metric by name. A
cell is one entry of ``workloads``; its configuration is
``configs/<config>.json``, its traffic ``traffic/<traffic>.json``, each
metric a reader ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py`` and
its entry point ``drivers/<entry>.py``. A later PR adds files and entries
and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BadBenchmark(ValueError):
    """``BENCHMARK.json`` or a file it names is outside the contract."""


def check_name(name, what: str = "name") -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise BadBenchmark(
            f"{what} {name!r}: a name starts with a letter, a digit or '_' "
            f"and has at most 64 letters, digits, '_', '.' and '-'")
    return name


def check_unit(unit) -> str:
    if not isinstance(unit, str) or not _UNIT.match(unit):
        raise BadBenchmark(
            f"unit {unit!r}: 1 to 16 letters, digits, '_', '/', '%', '.' "
            f"and '-', with no space")
    return unit


def _check_metric(m: dict, per_layer: bool) -> None:
    check_name(m.get("name"), "metric")
    check_unit(m.get("unit"))
    if m.get("better") not in ("lower", "higher"):
        raise BadBenchmark(f"metric {m['name']}: better must be lower|higher")
    allowed = SOURCES if per_layer else ("host_clock", "device_trace")
    if m.get("source") not in allowed:
        raise BadBenchmark(f"metric {m['name']}: source {m.get('source')!r} "
                           f"not in {allowed}")
    for w in m.get("workloads", ()):
        check_name(w, "workload")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries that apply to this cell
    per_layer: tuple
    run_seconds: int


def load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
    except OSError as e:
        raise BadBenchmark(f"{path}: {e}") from None
    if not isinstance(doc, dict):
        raise BadBenchmark(f"{path}: not a JSON object")
    return doc


def load_benchmark(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` with every name and unit checked."""
    doc = load_json(os.path.join(root, "BENCHMARK.json"))
    for c in doc.get("configs", ()):
        check_name(c.get("name"), "config")
        for k in c.get("reduced", ()):
            check_name(k, "reduced key")
    seen = set()
    for w in doc.get("workloads", ()):
        check_name(w.get("name"), "workload")
        check_name(w.get("config"), "config")
        check_name(w.get("traffic"), "traffic")
        if w.get("chips") not in (1, 4):
            raise BadBenchmark(f"workload {w['name']}: chips must be 1 or 4")
        if w["name"] in seen:
            raise BadBenchmark(f"workload {w['name']} appears twice")
        seen.add(w["name"])
    names = set()
    for key, per_layer in (("end_to_end", False), ("per_layer", True)):
        for m in doc.get(key, ()):
            _check_metric(m, per_layer)
            if m["name"] in names:
                raise BadBenchmark(f"metric {m['name']} appears twice")
            names.add(m["name"])
    return doc


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    check_name(workload, "workload")
    doc = load_benchmark(root)
    entry = next((w for w in doc["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BadBenchmark(
            f"no workload {workload!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in doc['workloads']]})")
    cfg_entry = next((c for c in doc["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BadBenchmark(f"workload {workload}: config "
                           f"{entry['config']!r} is not under configs")
    bench = os.path.join(root, doc["paths"][0])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench, "traffic",
                                      entry["traffic"] + ".json"))
    if config.get("chips") != entry["chips"]:
        raise BadBenchmark(
            f"workload {workload}: asks for {entry['chips']} chip(s) but "
            f"config {entry['config']} is laid out for "
            f"{config.get('chips')}")
    return Cell(
        name=workload, chips=entry["chips"], config=config, traffic=traffic,
        end_to_end=tuple(m for m in doc["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m for m in doc["per_layer"]
                        if _applies(m, workload)),
        run_seconds=int(doc["run_seconds"]))


def load_driver(entry: str):
    """``drivers/<entry>.py``: how one entry point is started, warmed and
    fed. A driver is a module of the ``benchmark`` package (it shares the
    harness), so its name is a Python identifier."""
    check_name(entry, "entry")
    if not entry.isidentifier():
        raise BadBenchmark(f"entry {entry!r} is not an identifier")
    if not os.path.exists(os.path.join(BENCH_DIR, "drivers", entry + ".py")):
        raise BadBenchmark(f"entry {entry!r} has no drivers/{entry}.py")
    return importlib.import_module(f"benchmark.drivers.{entry}")


def load_reader(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The reader of one metric, ``<kind>/<name>.py``, as a module. Metric
    names may hold '.' and '-', so this goes by path."""
    check_name(name, "metric")
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise BadBenchmark(f"metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
