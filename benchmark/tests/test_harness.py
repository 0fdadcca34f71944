"""The benchmark's own tests: CPU only, run by hand and by whoever changes
the benchmark (they are no part of the repository's tier-1 run):

  JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Rehearsal 1 of the ``on-chip-measurement`` guide: a toy-width configuration
through ``run.py`` end to end for each driver, in a temporary copy that ADDS
a throw-away cell as files plus entries (which is also the proof that a
cell, a configuration and a traffic mix can be added without editing a file
that is there). Rehearsal 2: the tensor-parallel driver on four virtual
devices. A CPU run must be marked not correct and name its device.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.harness import (cells, costs, peaks, reduce_trace,  # noqa: E402
                               traffic, weights)


# ------------------------------------------------------------ names and units

@pytest.mark.parametrize("name", ["a b", "a,b", "a/b", "", "-a", "x" * 65,
                                  "msµ", None])
def test_a_bad_name_is_refused(name):
    with pytest.raises(cells.BadBenchmark):
        cells.check_name(name)


@pytest.mark.parametrize("name", ["mistral7b.serve-chat", "_x", "9lives",
                                  "x" * 64])
def test_a_good_name_passes(name):
    assert cells.check_name(name) == name


@pytest.mark.parametrize("unit", ["tokens per second", "x" * 17, "", "µs"])
def test_a_bad_unit_is_refused(unit):
    with pytest.raises(cells.BadBenchmark):
        cells.check_unit(unit)


def test_units_in_use_pass():
    for unit in ("ms/token", "tokens/s", "%", "s", "count", "rows"):
        assert cells.check_unit(unit) == unit


# ------------------------------------------------------------ BENCHMARK.json

def test_benchmark_json_meets_the_contract():
    doc = cells.load_benchmark(ROOT)
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmark"]
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
    for w in doc["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = cells.load_cell(w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:     # reported only where what it moves is
            assert m["moves"] in names, (w["name"], m["name"])
    for c in doc["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        cfg = cells.load_json(os.path.join(ROOT, c["file"]))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert any(w["config"] == c["name"] for w in doc["workloads"])


def test_every_metric_has_its_reader_and_they_agree():
    doc = cells.load_benchmark(ROOT)
    for m in doc["end_to_end"]:
        assert callable(cells.load_reader("end_to_end", m["name"]).read)
    for m in doc["per_layer"]:
        mod = cells.load_reader("layer_metrics", m["name"])
        assert callable(mod.read)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]


# ------------------------------------------------------------------- traffic

def _traffic(name):
    return cells.load_json(os.path.join(BENCH, "traffic", name + ".json"))


@pytest.mark.parametrize("mix", ["decode1", "serve-chat", "serve-sat"])
def test_same_seed_same_traffic(mix):
    t = _traffic(mix)
    a, b, c = (traffic.generate(t, s, 20) for s in (7, 7, 8))
    assert a == b
    assert a != c
    for reqs in a["clients"]:
        for r in reqs:
            assert len(r["prompt"]) + 2 == r["prompt_tokens"]
            assert str(r["prompt_tokens"]) in t["prompt_tokens"]
            assert str(r["output_tokens"]) in t["output_tokens"]


def test_open_loop_keeps_its_rate_and_its_window():
    t = _traffic("serve-chat")
    reqs = traffic.generate(t, 3, 400)["clients"][0]
    assert all(0 <= r["due_s"] < 400 for r in reqs)
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    # a Poisson process conditioned on its count: the seed sets when
    assert len(reqs) == round(400 * t["arrival"]["rate_per_s"])
    gaps = [b["due_s"] - a["due_s"] for a, b in zip(reqs, reqs[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert 0.8 < var ** 0.5 / mean < 1.2      # exponential gaps: cv = 1


def test_bursty_arrivals_are_burstier():
    t = dict(_traffic("serve-chat"), arrival={
        "process": "mmpp", "rate_per_s": 1.2, "burst_rate_x": 8,
        "p_enter": 0.08, "p_exit": 0.35})
    due = [r["due_s"] for r in traffic.generate(t, 3, 2000)["clients"][0]]
    gaps = [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert var ** 0.5 / mean > 1.1 and all(0 <= d < 2000 for d in due)
    assert len(due) > 1.1 * 1.2 * 2000      # bursts add arrivals


def test_lengths_are_dealt_in_exact_proportion():
    """The seed sets the order of the work and not its amount."""
    t = _traffic("serve-sat")
    totals = set()
    for seed in (1, 2, 3):
        clients = traffic.generate(t, seed, 40)["clients"]
        first = [r for reqs in clients for r in reqs[:5]]   # 80 = 4 decks
        outs = {}
        for r in first:
            outs[r["output_tokens"]] = outs.get(r["output_tokens"], 0) + 1
        assert outs == {16: 12, 32: 20, 64: 24, 128: 16, 256: 8}
        totals.add((sum(r["prompt_tokens"] for r in first),
                    sum(r["output_tokens"] for r in first)))
    assert len(totals) == 1


def test_shared_prefix_mix():
    t = dict(_traffic("serve-chat"), shared_prefix={
        "share": 0.8, "prefixes": 4, "prefix_tokens": 64})
    reqs = traffic.generate(t, 1, 200)["clients"][0]
    heads = {}
    for r in reqs:
        heads[r["prompt"][:64]] = heads.get(r["prompt"][:64], 0) + 1
    shared = sorted(heads.values())[-4:]
    assert 0.7 < sum(shared) / len(reqs) < 0.9


# ------------------------------------------------------------------- weights

TINY = {"dim": 256, "hidden_dim": 512, "n_layers": 2, "n_heads": 8,
        "n_kv_heads": 2, "vocab_size": 512, "seq_len": 256}


def _q40():
    from distributed_llama_tpu.io.loader import Q40Weight
    return Q40Weight


def test_weights_are_seeded_whatever_the_thread_count():
    a = weights.build_codec_tree(TINY, 5, _q40(), threads=1)
    b = weights.build_codec_tree(TINY, 5, _q40(), threads=7)
    c = weights.build_codec_tree(TINY, 6, _q40())
    def leaves(v):
        return list(v) if isinstance(v, tuple) else [v]

    for k in a:
        for x, y, z in zip(leaves(a[k]), leaves(b[k]), leaves(c[k])):
            assert np.array_equal(x, y)
            assert not np.array_equal(x, z)


def test_weights_follow_the_recipe_and_the_programs_codec():
    from distributed_llama_tpu.ops.quants import dequantize_q40

    tree = weights.build_codec_tree(TINY, 1, _q40())
    assert set(tree) == {"tok_embedding", "rms_att", "rms_ffn", "rms_final",
                         "wcls", "wq", "wk", "wv", "wo", "w1", "w2", "w3"}
    w1 = tree["w1"]
    assert w1.qs.shape == (2, 512, 8, 16) and w1.d16.shape == (2, 512, 8)
    for nib in (w1.qs & 15, w1.qs >> 4):     # symmetric on -7..7
        assert nib.min() == 1
    w = weights.dequantize(w1.qs, w1.d16)
    assert abs(w.std() * np.sqrt(256) - 1) < 0.1 and abs(w.mean()) < 1e-3
    assert np.array_equal(
        w, np.asarray(dequantize_q40(w1.qs, w1.d16)).reshape(w.shape))
    assert not tree["wcls"].d16[weights.BOS].any()
    assert abs(tree["rms_att"].mean() - 1) < 0.01


# ----------------------------------------------------------- costs and peaks

MISTRAL = {"dim": 4096, "hidden_dim": 14336, "n_layers": 32, "n_heads": 32,
           "n_kv_heads": 8, "vocab_size": 32000, "seq_len": 4096}


def test_mistral_weight_bytes_by_hand():
    # per layer: wq, wo 4096x4096; wk, wv 1024x4096; w1, w2, w3 14336x4096
    layer = 2 * 4096 * 4096 + 2 * 1024 * 4096 + 3 * 14336 * 4096
    total = 32 * layer + 32000 * 4096
    assert costs.matmul_params(MISTRAL)["layer"] == layer == 218_103_808
    assert costs.q40_weight_bytes(MISTRAL) == total // 32 * 18
    assert round(costs.q40_weight_bytes(MISTRAL) / 1e9, 2) == 4.00
    assert costs.kv_bytes_per_position(MISTRAL) == 2 * 1024 * 4 * 32
    assert costs.flops_per_token(MISTRAL) == 2 * total
    assert costs.decode_step_bytes(MISTRAL, chips=4) * 4 == \
        costs.q40_weight_bytes(MISTRAL)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")


# ------------------------------------------------------------- trace reducer

def _op(name, lo, hi, kind=None):
    kind = kind or name.split(".")[0]
    return reduce_trace.Op(name, kind, float(lo), float(hi))


def test_hlo_text_to_name_and_kind():
    text = ('%_q40_matvec_nb_stacked.26 = f32[1,28672]{1,0:T(1,128)S(1)} '
            'custom-call(s32[1]{0:T(128)} %dynamic_slice.19, u8[32,16,128,'
            '28672]{3,2,1,0:T(8,128)(4,1)} %get-tuple-element.553), '
            'custom_call_target="tpu_custom_call"')
    assert reduce_trace.short_name(text) == "_q40_matvec_nb_stacked.26"
    assert reduce_trace.op_kind(text) == "custom-call"
    op = reduce_trace.Op(reduce_trace.short_name(text),
                         reduce_trace.op_kind(text), 0.0, 1.0)
    assert reduce_trace.classify(op) == "q40"
    for text, cls in (
            ("%all-reduce-start.3 = f32[7168]{0} all-reduce-start(f32[7168]"
             "{0} %fusion.2), replica_groups={}", "collective"),
            ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), "
             "kind=kLoop", "xla"),
            ("%while.3 = (s32[], f32[4096]{0}) while((s32[], f32[4096]{0}) "
             "%tuple.1), condition=%c, body=%b", "control"),
            ("%decode_attention.6 = f32[32,128]{1,0} custom-call(f32[32,128]"
             "{1,0} %x), custom_call_target=\"tpu_custom_call\"",
             "attention")):
        op = reduce_trace.Op(reduce_trace.short_name(text),
                             reduce_trace.op_kind(text), 0.0, 1.0)
        assert reduce_trace.classify(op) == cls, text


def test_union_subtract_and_self_time():
    assert reduce_trace.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == \
        [(0, 3), (5, 6)]
    assert reduce_trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    ops = [_op("while.1", 0, 10), _op("fusion.1", 1, 4),
           _op("custom-call.2", 5, 9), _op("copy.3", 12, 13)]
    assert reduce_trace.self_times(ops) == [3, 3, 4, 1]


def test_busy_idle_exposure_and_gaps_on_a_made_up_trace():
    dev0 = [_op("while.1", 0, 60),
            _op("_q40_matvec_nb.1", 0, 30, "custom-call"),
            _op("all-reduce.1", 30, 50), _op("fusion.2", 40, 60),
            _op("fusion.3", 80, 90)]
    spans = [_op("inference.step", 0, 70), _op("inference.sample", 62, 68),
             _op("inference.step", 75, 95)]
    # the second program seems to start before the host call that launched
    # it (clock skew): it still belongs to the span its midpoint lies in
    mods = [_op("jit_step", 0, 60, "module"), _op("jit_slice", 61, 62,
                                                   "module"),
            _op("jit_step", 74, 90, "module")]
    tr = reduce_trace.Trace({"/device:TPU:0": dev0}, spans, window=(0, 100),
                            modules={"/device:TPU:0": mods})
    assert reduce_trace.busy(tr)["busy_s"]["/device:TPU:0"] == \
        pytest.approx(70e-9)
    assert reduce_trace.idle_share(tr) == pytest.approx(30.0)
    # the collective runs 30..50, compute covers 40..60: 10 ns exposed
    assert reduce_trace.collective_exposed_s(dev0) == pytest.approx(10e-9)
    by = reduce_trace.time_by_class(tr)["/device:TPU:0"]["by_class"]
    assert by["q40"] == pytest.approx(30e-9)
    assert by["collective"] == pytest.approx(20e-9)
    gaps = dict(reduce_trace.idle_gaps(tr))
    # idle 60..80 and 90..100: 62..68 is the sampler's (the innermost span),
    # 60..62, 68..70, 75..80 and 90..95 the steps', 70..75 and 95..100 nobody's
    assert gaps == {"inference.step": pytest.approx(14e-9),
                    "inference.sample": pytest.approx(6e-9),
                    "(none)": pytest.approx(10e-9)}
    st = reduce_trace.steps(tr)
    assert [round(s["device_s"] * 1e9) for s in st] == [60, 16]
    assert reduce_trace.class_seconds_per_step(tr, "q40") == \
        [pytest.approx(30e-9), 0.0]


@pytest.mark.parametrize("name", ["mistral7b_decode1", "yi34b_tp4_decode1"])
def test_reducer_on_traces_recorded_on_the_chip(name):
    """A few decode steps of ``mistral7b.decode1`` (one chip) and one of
    ``yi34b-tp4.decode1`` (four chips, with collectives), recorded on TPU
    v5e chips in PR 22 and cut down by ``tools/trim_trace.py``. The numbers
    are what the reducer read then: this pins the reduction, not the chip.
    """
    with open(os.path.join(HERE, "fixtures", "expected.json")) as fh:
        want = json.load(fh)[name]
    tr = reduce_trace.load(os.path.join(HERE, "fixtures",
                                        name + ".xplane.pb"))
    assert sorted(tr.devices) == want["devices"]
    assert reduce_trace.idle_share(tr) == pytest.approx(want["idle_share"],
                                                        rel=1e-6)
    by = reduce_trace.time_by_class(tr)[want["devices"][0]]["by_class"]
    assert set(by) == set(want["by_class"])
    for k, v in want["by_class"].items():
        assert by[k] == pytest.approx(v, rel=1e-6)
    st = reduce_trace.steps(tr)
    assert len(st) == want["steps"]
    assert [s["device_s"] * 1e3 for s in st] == pytest.approx(
        want["step_device_ms"], rel=1e-6)
    assert reduce_trace.collective_exposed_ms_per_step(tr) == pytest.approx(
        want["collective_exposed_ms_per_step"], rel=1e-6)
    assert reduce_trace.top_ops(tr)[0][0] == want["top_op"]
    assert reduce_trace.idle_gaps(tr)[0][0] == want["top_gap"]
    assert by["q40"] > 0
    assert ("collective" in by) == (len(tr.devices) > 1)


# ---------------------------------------- rehearsals: run.py in a temp copy

def _temp_root(tmp_path, cells_to_add):
    """A copy of the benchmark plus throw-away cells added as NEW files and
    NEW entries; nothing that is there is edited."""
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = cells.load_benchmark(ROOT)
    for name, config, mix, chips, family in cells_to_add:
        shutil.copy(os.path.join(HERE, config + ".json"),
                    root / "benchmark" / "configs" / (config + ".json"))
        shutil.copy(os.path.join(HERE, mix + ".json"),
                    root / "benchmark" / "traffic" / (mix + ".json"))
        if not any(c["name"] == config for c in doc["configs"]):
            doc["configs"].append({
                "name": config, "source": "none (test)", "reduced": [],
                "file": f"benchmark/configs/{config}.json", "why": "test"})
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": mix, "chips": chips,
                                 "why": "throw-away"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if family in m.get("workloads", ()):
                m["workloads"].append(name)
    with open(root / "BENCHMARK.json", "w") as fh:
        json.dump(doc, fh)
    return str(root)


def _run(root, workload, trace, devices=1, rehearse=1, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", "3", "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse", str(rehearse)],
        env=env, cwd=root, capture_output=True, text=True, timeout=600)


def _last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device"}
    assert line["correct"] is False          # a CPU run is never correct
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] > 0, proc.stderr[-3000:]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert "check FAILED" not in proc.stderr, proc.stderr[-3000:]
    return line


CASES = [("throwaway.decode1", "tiny", "tiny-decode1", 1,
          "mistral7b.decode1"),
         ("throwaway.chat", "tiny", "tiny-chat", 1, "mistral7b.serve-chat"),
         ("throwaway.sat", "tiny", "tiny-sat", 1, "mistral7b.serve-sat")]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_rehearsal_1_each_driver_end_to_end(tmp_path, case):
    root = _temp_root(tmp_path, [case])
    cell = cells.load_cell(case[0], root)
    line = _last_line(_run(root, case[0], trace=0))
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["setup_s"]["value"] > 0
    traced = _last_line(_run(root, case[0], trace=1))
    assert "breakdown" in traced and "window_s" in traced["device"]
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    # what needs a device trace finds nothing to read on the CPU and is
    # left out; the counters and the client's clock are there
    assert set(traced["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert len(traced["metrics"]) >= 2


def test_rehearsal_2_tensor_parallel_on_four_virtual_devices(tmp_path):
    case = ("throwaway.tp4", "tiny-tp4", "tiny-decode1", 4,
            "yi34b-tp4.decode1")
    root = _temp_root(tmp_path, [case])
    line = _last_line(_run(root, case[0], trace=0, devices=4))
    assert line["device"]["count"] == 4
    assert "decode_ms_per_token" in line["metrics"]
    # fewer devices than the cell asks for: no result, another exit code
    proc = _run(root, case[0], trace=0, devices=2)
    assert proc.returncode not in (0, None) and not proc.stdout.strip()


def test_no_tpu_no_result(tmp_path):
    root = _temp_root(tmp_path, [CASES[0]])
    proc = _run(root, CASES[0][0], trace=0, rehearse=0)
    assert proc.returncode == 3 and not proc.stdout.strip()
    assert "not a TPU" in proc.stderr


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark."""
    root = _temp_root(tmp_path, [CASES[0]])
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CASES[0][0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=dict(env, JAX_PLATFORMS="cpu"), cwd=root,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 5 and not proc.stdout.strip()
