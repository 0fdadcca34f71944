"""bench.py driver-protocol smoke: the default `python bench.py` run must
emit ONE final JSON line whose payload carries every config row (the
artifact the driver parses into BENCH_r{N}.json — VERDICT r2 #1).

Runs the aggregation over the tiny config only (DLLAMA_BENCH_CONFIGS=small,
the documented test hook) on the CPU backend; the real 7b/13b/70b-tp8 rows
are exercised on hardware."""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_all_emits_one_json_line_with_rows(tmp_path):
    full_path = tmp_path / "BENCH_FULL.json"
    env = {**os.environ,
           "DLLAMA_BENCH_CONFIGS": "small",
           "DLLAMA_BENCH_FULL_PATH": str(full_path),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--samples", "4"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        timeout=900, cwd=_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    # the VERDICT r4 #1 regression guard: round 4's stdout line outgrew the
    # driver protocol's capture (truncated mid-JSON at 2000 chars ->
    # parsed=null); the compact line must stay WELL inside that budget
    assert len(line) < 1800, f"compact line too long ({len(line)} chars)"
    payload = json.loads(line)
    assert payload["unit"] == "ms/token"
    assert payload["value"] > 0
    assert "small" in payload["rows"]
    row = payload["rows"]["small"]
    assert row["ms"] > 0 and row["x"] > 0
    # the full table (the judge's artifact) carries every detailed field
    full = json.loads(full_path.read_text())
    frow = full["rows"]["small"]
    assert frow["value"] > 0 and frow["executed"] >= 1
    assert "startup_to_first_token_s" in frow
    # drift defense (ISSUE 3): fingerprint + trial count ride every row
    fp = frow["env_fingerprint"]
    assert fp["jax"] and fp["backend"] == "cpu" and fp["clock"]
    assert frow["trials"] == 3  # default median-of-3, recorded


def test_compact_summary_shape_and_size():
    """_compact_summary: headline + per-row ms/x (I/T on the tp rows) +
    [ms, x] scaling pairs; a full 9-row table must serialize far below the
    2000-char driver capture that truncated round 4's record."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_mod2", os.path.join(_ROOT, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    rows = {"7b": {"value": 9.801, "vs_baseline": 50.4,
                   "kv_cache": "f32", "samples": 64, "executed": 64},
            "13b": {"value": 17.9, "vs_baseline": 47.38},
            "70b-tp8": {"value": 18.47, "vs_baseline": 262.2,
                        "shard_ms_measured": 16.05,
                        "ici_bandwidth_ms_modeled": 0.167,
                        "ici_latency_ms_modeled": 2.247,
                        "buffer_modes": {"f32": {"pad": "y" * 500}}}}
    for m in ("7b", "13b"):
        for n in (2, 4, 8):
            rows[f"{m}-tp{n}"] = {
                "value": 6.4, "vs_baseline": 124.0,
                "shard_ms_measured": 6.2,
                "ici_bandwidth_ms_modeled": 0.017,
                "ici_latency_ms_modeled": 0.129,
                "ici_latency_sensitivity_10x": {"f32_total_ms": 7.5}}
    configs = list(rows)
    curve = bench._scaling_curve(rows)
    out = bench._compact_summary(configs, rows, curve)
    line = json.dumps(out)
    assert len(line) < 1500, f"{len(line)} chars: {line[:200]}"
    assert out["value"] == 9.801 and out["vs_baseline"] == 50.4
    assert out["rows"]["7b"] == {"ms": 9.801, "x": 50.4}
    # tp rows: I = measured rank, T = modeled ICI total
    assert out["rows"]["70b-tp8"]["I"] == 16.05
    assert out["rows"]["70b-tp8"]["T"] == 2.414
    assert out["scaling_x_vs_same_n"]["7b"]["2"] == [6.4,
                                                     round(793.69 / 6.4, 2)]
    # failed rows surface as errors, never KeyError
    out2 = bench._compact_summary(
        ["7b", "13b"], {"7b": rows["7b"], "13b": {"error": "rc=1"}}, {})
    assert out2["rows"]["13b"] == {"error": "rc=1"}


def test_scaling_curve_assembly():
    """_scaling_curve (VERDICT r3 #2) mirrors the reference's per-device-
    count table: tp=1 from the measured single-chip row, tp>1 from the
    rank rows, same-n reference baselines, per-point kv_cache basis;
    missing/failed rows are skipped, empty rows give an empty curve."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(_ROOT, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    rows = {"7b": {"value": 9.8, "kv_cache": "f32"},
            "13b": {"value": 17.9, "kv_cache": "bf16"},
            "7b-tp2": {"value": 6.36, "kv_cache": "f32",
                       "shard_ms_measured": 6.22,
                       "ici_bandwidth_ms_modeled": 0.017,
                       "ici_latency_ms_modeled": 0.129},
            "13b-tp8": {"value": 6.6, "kv_cache": "f32",
                        "shard_ms_measured": 5.43,
                        "ici_bandwidth_ms_modeled": 0.047,
                        "ici_latency_ms_modeled": 1.127},
            "13b-tp4": {"error": "rc=1"},  # failed row: skipped
            "70b-tp8": {"value": 18.9}}    # not part of the curve
    curve = bench._scaling_curve(rows)
    assert set(curve) == {"7b", "13b"}
    assert curve["7b"]["1"]["reference_ms"] == 1312.50
    assert curve["7b"]["1"]["vs_reference_same_n"] == round(1312.50 / 9.8, 2)
    assert curve["7b"]["2"]["reference_ms"] == 793.69
    assert curve["7b"]["2"]["shard_ms_measured"] == 6.22
    # 13B has no published 1-device row; the measured point still appears
    assert curve["13b"]["1"]["reference_ms"] is None
    assert curve["13b"]["1"]["kv_cache"] == "bf16"
    assert curve["13b"]["8"]["vs_reference_same_n"] == round(1114.88 / 6.6, 2)
    assert "4" not in curve["13b"]  # failed row skipped
    assert bench._scaling_curve({}) == {}
    # _BASE scaling baselines derive from the same table (one source of
    # truth): spot-check through the public surface
    assert bench._REF_CURVE["13b"][4] == 848.19


def _load_bench(tag):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"bench_mod_{tag}", os.path.join(_ROOT, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_project_tp_reports_both_schemes(monkeypatch):
    """The tp-row projection carries BOTH schemes' modeled ICI (ref = the
    parity anchor), and the fused default's latency term is ~half the ref
    scheme's — the ISSUE 3 acceptance: 13b-tp8 projected total improves
    vs BENCH_r05's ref-scheme 7.419 ms/token record."""
    from distributed_llama_tpu.models.synth import llama2_13b_spec

    bench = _load_bench("proj")
    monkeypatch.delenv("DLLAMA_TP_SCHEME", raising=False)
    # BENCH_r05 13b-tp8: shard 6.245 measured, ref-scheme total 7.419
    out = bench._project_tp(llama2_13b_spec(), 8, 6.245, 848.19)
    assert out["tp_scheme"] == "fused"
    sch = out["schemes_f32"]
    assert set(sch) == {"ref", "fused", "overlap"}
    assert "parity anchor" in sch["ref"]["note"]
    L = llama2_13b_spec().n_layers
    assert sch["ref"]["n_collectives_per_token"] == 4 * L + 1
    assert sch["fused"]["n_collectives_per_token"] == 2 * L + 1
    assert sch["fused"]["ici_latency_ms_modeled"] < \
        sch["ref"]["ici_latency_ms_modeled"] * 0.55
    # the headline (active scheme) total beats the recorded ref total
    assert out["value"] == sch["fused"]["total_ms"] < 7.419
    assert sch["ref"]["total_ms"] == 7.419  # the BENCH_r05 anchor
    # the overlap row (ISSUE 10): 2L(S-1) ppermutes + 2L+1 gathers, with
    # the hidden term carried and subtracted — modeled strictly faster
    # than fused at 13b-tp8 (the acceptance criterion)
    assert sch["overlap"]["n_collectives_per_token"] == \
        2 * L * 7 + 2 * L + 1
    assert sch["overlap"]["ici_hidden_ms_modeled"] > 0
    assert sch["overlap"]["total_ms"] < sch["fused"]["total_ms"]

    # under DLLAMA_TP_SCHEME=ref the headline IS the anchor row
    monkeypatch.setenv("DLLAMA_TP_SCHEME", "ref")
    out_ref = bench._project_tp(llama2_13b_spec(), 8, 6.245, 848.19)
    assert out_ref["tp_scheme"] == "ref"
    assert out_ref["value"] == 7.419


def test_bench_trials_env(monkeypatch):
    bench = _load_bench("trials")
    monkeypatch.delenv("DLLAMA_BENCH_TRIALS", raising=False)
    assert bench._bench_trials() == 3
    monkeypatch.setenv("DLLAMA_BENCH_TRIALS", "7")
    assert bench._bench_trials() == 7
    monkeypatch.setenv("DLLAMA_BENCH_TRIALS", "0")
    import pytest

    with pytest.raises(SystemExit):
        bench._bench_trials()
