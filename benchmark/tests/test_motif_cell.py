"""``motif.deep-sat32``'s part of the benchmark, CPU only (run with the rest
of ``benchmark/tests``): the configuration file against the catalog, the
byte and operation counts of ``harness/motif.py`` against the shapes and
ISSUE 52's table, the seeded tree, the benchmark's copy of the reference
against the program's (and both controls), the trace readers on a made-up
trace, the cell as the issue names it, and the motif serve driver end to end
at a toy width in a temporary copy that adds a throw-away cell (its window
opens after the first wave)."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, laguna, motif, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "motif-3-beta-q40-ep8.json"))
BETA = motif.sizes_of(CONFIG)
TINY_CONFIG = cells.load_json(os.path.join(HERE, "tiny-motif.json"))
TINY = motif.sizes_of(TINY_CONFIG)
CELL = "motif.deep-sat32"
NEW = ("motif_full_attn_roofline", "motif_ring_attn_roofline",
       "motif_expert_roofline", "motif_dense_q40_roofline",
       "motif_full_device_time_share", "motif_sliding_device_time_share",
       "motif_moe_device_time_share", "motif_diff_device_time_share",
       "motif_polynorm_device_time_share", "motif_local_pairs_share",
       "motif_depth_positions_mean")
TRACED = NEW[:9]
ASSUMED = ("heads", "noise_head_place", "differential_form", "gate", "rope",
           "layer_kinds", "residual_path", "hidden_clamp", "polynorm",
           "router", "multi_token_prediction", "tensor_names", "router_rows",
           "seeded_lambda", "seeded_polynorm", "seeded_streams",
           "plane_lanes", "precision")


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Motif-3-Beta":
                return row
    pytest.skip("the catalog has no Motif-3-Beta row")


def test_every_published_key_is_in_the_file_and_no_width_is_cut():
    row = _catalog()
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"} == set(CONFIG["reduced_why"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == row["config"][key]
    assert set(ASSUMED) <= set(CONFIG["assumed"])
    dep = CONFIG["deployment"]
    assert dep["chips_per_layer"] * CONFIG["num_experts"] == 384
    assert CONFIG["vocab_size"] * 8 == 220160 and CONFIG["vocab_size"] % 128 == 0
    motif.check_runnable(CONFIG)


def test_published_sizes_and_bytes_by_hand():
    s = BETA
    assert (s["n_heads"], s["groups"], s["noise_heads"], s["signal_heads"]) \
        == (80, 16, 1, 64)
    assert (s["nope_dim"], s["rope_dim"], s["v_dim"], s["kv_rank"]) == (
        128, 64, 128, 512)
    kinds = motif.kinds_of(s)
    assert kinds == ("sliding", "sliding", "sliding", "full") * 3
    assert dict(motif.attn_shapes(s))["wo"] == (4096, 8192)
    assert dict(motif.attn_shapes(s))["wkv_b"] == (16 * 256, 512)
    assert motif.expert_bytes(s) == 3 * 4096 * 1280 // 32 * 18 == 8847360
    # ISSUE 52's table: attention 89.6 M Q40 weights a layer less wkv_b's
    # 2.1 M (held as float32), a dense FFN of 151.0 M, a shared expert
    attn = (4096 * 1024 + 80 * 192 * 1024 + 576 * 4096 + 2 * 8192 * 4096)
    want = (12 * attn + 2 * 3 * 4096 * 12288 + 10 * 3 * 4096 * 1280
            + 27520 * 4096) // 32 * 18
    assert motif.dense_q40_bytes(s) == want
    nbytes, flops = motif.attn_step_cost(s, "full", 32 * 4900)
    assert nbytes == 32 * 4900 * 576 * 4 * 3
    assert flops == 2 * 32 * 4900 * 80 * (576 + 512) * 3      # 82 GFLOP
    assert flops / nbytes == pytest.approx(75.6, abs=0.1)
    # the operations bound: 6 bf16 passes a float32 product at HIGHEST
    assert motif.roofline_seconds("TPU v5 lite", nbytes, flops) \
        == pytest.approx(flops * 6 / 197e12)
    rb, rf = motif.attn_step_cost(s, "sliding", 32 * 128)
    assert rb == 32 * 128 * 2304 * 9 and rf == rb / 4 * 2 * 80 * 1088 / 576
    spec = motif.program_spec(s)
    assert spec.header_version == 9 and spec.slotted
    assert spec.latent_kinds == kinds and spec.latent.count("full") == 3
    from distributed_llama_tpu.analysis import memory_model as mm

    assert mm.kv_position_bytes(spec, 1) == 3 * 640 * 4 == 7680
    assert mm.state_slot_bytes(spec) == 9 * 128 * 640 * 4     # 2.9 MB a row


def _leaves(v):
    return (v.qs, v.d16) if hasattr(v, "qs") else (v,)


def test_tree_is_seeded_whatever_the_thread_count():
    a, b = motif.codec_tree(TINY, 5, threads=1), motif.codec_tree(TINY, 5, 4)
    for stack in (a, a["dense"]):
        other = b if stack is a else b["dense"]
        for k, v in stack.items():
            if isinstance(v, dict):
                continue
            for x, y in zip(_leaves(v), _leaves(other[k])):
                assert np.array_equal(x, y), k
    assert not np.array_equal(a["w_lambda"], motif.codec_tree(TINY, 6)[
        "w_lambda"])
    assert a["pn_w"].shape == (6, 4) and a["dense"]["pn_w"].shape == (2, 4)
    assert np.abs(a["pn_w"][:, :3] - 1 / 3).max() < 0.5
    assert a["w_lambda"].shape == (6, 8, 128)
    assert a["moe_w1"].qs.shape[:2] == (6, 4)       # the HELD experts


def test_the_two_references_agree_and_both_controls_do_not():
    """The benchmark's layer-at-a-time copy and the program's
    ``models/reference_motif.py`` are written apart and give the same
    logits and margins (groups, the noise head, lambda, the gate, windows,
    PolyNorm, streams, the held share); one precision down, or with
    lambda = 0, they do not."""
    from distributed_llama_tpu.models import reference_motif

    tree = motif.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 40))
    stats: dict = {}
    got, margins = motif.logits(tree, TINY, tokens, vocab_blocks=3,
                                precisions=("highest", "bfloat16"),
                                stats=stats)
    bare, _ = motif.logits(tree, TINY, tokens, lambda_on=False)
    spec = motif.program_spec(TINY)
    for b in range(2):
        want, m, _ = reference_motif.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 1e-4
        assert np.abs(margins[b] - m).max() < 1e-5
        assert np.abs(got["bfloat16"][b] - want).max() > 1e-2
        assert np.abs(bare["highest"][b] - want).max() > 1e-2
        zero = reference_motif.forward(tree, spec, tokens[b],
                                       lambda_zero=True)[0]
        assert np.abs(bare["highest"][b] - zero).max() < 1e-4
    lo, hi = motif.LAMBDA_SHARES
    assert lo < stats["lambda_mean"] < hi
    keep = np.asarray([[3, 39], [0, 17]])
    part, _ = motif.logits(tree, TINY, tokens, keep=keep)
    assert np.abs(part["highest"][1, 1] - got["highest"][1, 17]).max() < 1e-5


def _op(name, lo, hi, kind="custom-call"):
    return th._op(name, lo, hi, kind)


def _made_up_trace(chunk: bool = False):
    """One forward of the cell's depth: per layer wq_a, wq_b, wkv_a (with
    the gate behind it), the kind's attention kernel (or a chunk's fusion),
    the fold's and the gate's fusions, wo, then the FFN (the dense layers:
    w13, PolyNorm's fusion, w2; the others: two expert kernel calls with
    PolyNorm between them, then the shared expert's two), and the
    classifier's call at the end of a decode step."""
    ops, t, names = [], 0, {}

    def add(name, dur, kind="custom-call", scope=None):
        nonlocal t
        ops.append(_op(name, t, t + dur, kind))
        if scope:
            names[name] = scope
        t += dur

    for layer, kind in enumerate(motif.kinds_of(BETA)):
        for i in range(3):
            add(f"_q40_mxu_nb_stacked.{i}", 4)
        if chunk:
            add("fusion.7", 4, "fusion")
        else:
            add(("mla_ring_attn_decode" if kind == "sliding"
                 else "mla_paged_attn_decode") + ".2", 20 if kind == "sliding"
                else 300)
        add(f"fusion.fold{layer}", 2, "fusion", "diff")
        add(f"fusion.gate{layer}", 1, "fusion", "diff")
        add("_q40_mxu_nb_stacked.3", 5)
        if layer >= 2:
            add("fusion.9", 1, "fusion")
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".4", 40)
            add(f"fusion.pn{layer}", 3, "fusion", "polynorm")
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".5", 20)
        add("_q40_mxu_nb_stacked.6", 3)
        add(f"fusion.pnd{layer}", 2, "fusion", "polynorm")
        add("_q40_mxu_nb_stacked.7", 2)
    if not chunk:
        add("_q40_mxu_nb_2d.8", 7)
    return ops, t, names


def test_trace_readers_on_a_made_up_trace():
    from benchmark.drivers import serve_motif

    dev = "/device:TPU:0"
    ops, end, names = _made_up_trace()
    tr = reduce_trace.Trace(
        {dev: ops}, [_op("serve.step", 0, end + 10, "host")],
        window=(0, end + 10),
        modules={dev: [_op("jit_serve_decode_step", 0, end, "module")]})
    (step,) = motif.step_kernel_seconds(tr)
    assert step["ring"] == pytest.approx(9 * 20e-9)
    assert step["full"] == pytest.approx(3 * 300e-9)
    assert step["slots"] == pytest.approx(10 * 60e-9)
    assert step["dense"] == pytest.approx((12 * 22 + 7) * 1e-9)
    blocks = motif.block_seconds(tr, BETA, names)
    assert blocks["sliding"] == pytest.approx(9 * 40e-9)
    assert blocks["full"] == pytest.approx(3 * 320e-9)
    assert blocks["moe"] == pytest.approx(10 * 71e-9)
    assert blocks["diff"] == pytest.approx(12 * 3e-9)
    assert blocks["polynorm"] == pytest.approx((10 * 5 + 2 * 2) * 1e-9)
    before = dict.fromkeys(
        ("steps", "trace_steps", "trace_shared_kv_positions",
         "trace_window_kv_positions", "trace_moe_active", "moe_pairs",
         "moe_local_pairs", "sum_active", "shared_kv_positions"), 0)
    after = {"steps": 99, "trace_steps": 10,
             "trace_shared_kv_positions": 10 * 32 * 5500,
             "trace_window_kv_positions": 10 * 32 * 128,
             "trace_moe_active": 10 * 224, "moe_pairs": 8000,
             "moe_local_pairs": 1000, "sum_active": 99 * 32,
             "shared_kv_positions": 99 * 32 * 5400}
    run = serve_motif.Run(
        cell=cells.load_cell(CELL, ROOT), seed=1, window_s=1.0, setup_s=1.0,
        records=[], device={"kind": "TPU v5 lite"}, counters_before=before,
        counters_after=after, trace=tr, scoped_ops=names)
    read = lambda n: cells.load_reader("layer_metrics", n).read(run)  # noqa
    full_flops = 2 * 32 * 5500 * 80 * 1088 * 3
    assert read(NEW[0]) == pytest.approx(
        100 * full_flops * 6 / 197e12 / 900e-9)
    ring_flops = 2 * 32 * 128 * 80 * 1088 * 9
    assert read(NEW[1]) == pytest.approx(
        100 * ring_flops * 6 / 197e12 / 180e-9)
    assert read(NEW[2]) == pytest.approx(
        100 * 224 * 8847360 / 600e-9 / 819e9)
    assert read(NEW[3]) == pytest.approx(
        100 * motif.dense_q40_bytes(BETA) / 271e-9 / 819e9)
    busy = reduce_trace.busy(tr)["busy_s"][dev]
    assert read(NEW[4]) == pytest.approx(100 * 3 * 320e-9 / busy)
    assert read(NEW[5]) == pytest.approx(100 * 9 * 40e-9 / busy)
    assert read(NEW[6]) == pytest.approx(100 * 10 * 71e-9 / busy)
    assert read(NEW[7]) == pytest.approx(100 * 36e-9 / busy)
    assert read(NEW[8]) == pytest.approx(100 * 54e-9 / busy)
    assert read(NEW[9]) == pytest.approx(12.5)
    assert read(NEW[10]) == pytest.approx(5400)
    cops, cend, _ = _made_up_trace(chunk=True)
    tr2 = reduce_trace.Trace(
        {dev: cops}, [], window=(0, cend),
        modules={dev: [_op("jit_serve_admit_prefill_chunk", 0, cend,
                           "module")]})
    assert motif.step_kernel_seconds(tr2) == []      # no decode step
    assert motif.block_seconds(tr2, BETA)["moe"] == pytest.approx(
        10 * 71e-9)


def test_readers_return_nothing_without_the_programs_kernels():
    """On a program without the kernel, the scopes or the counters (the
    parent commit, an untraced run): every new reader returns None and none
    raises."""
    from benchmark.harness import runtime

    cell = cells.load_cell(CELL, ROOT)
    dev = "/device:TPU:0"
    ops = [_op("_q40_mxu_nb_stacked.1", 0, 10), _op("fusion.1", 10, 12,
                                                    "fusion")]
    tr = reduce_trace.Trace(
        {dev: ops}, [], window=(0, 20),
        modules={dev: [_op("jit_serve_decode_step", 0, 12, "module")]})
    for trace in (None, tr):
        run = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                          records=[], device={"kind": "TPU v5 lite"},
                          counters_before={"steps": 0},
                          counters_after={"steps": 5}, trace=trace)
        for name in NEW:
            assert cells.load_reader("layer_metrics", name).read(run) is None


def test_scoped_instructions_of_a_compiled_text():
    text = "\n".join([
        "HloModule jit_serve_decode_step",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %x = f32[4] multiply(p, p), metadata={op_name="jit(f)/attn/attn.diff/mul"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        '  %fusion.1 = f32[4] fusion(a), kind=kLoop, metadata={op_name="jit(f)/attn/attn.diff/sub"}',
        '  %fusion.2 = f32[4] fusion(a), kind=kLoop, metadata={op_name="jit(f)/attn/attn.gate/mul"}',
        '  %fusion.3 = f32[4] fusion(a), kind=kLoop, metadata={op_name="jit(f)/ffn/ffn.polynorm/add"}',
        '  %fusion.4 = f32[4] fusion(a), kind=kLoop, metadata={op_name="jit(f)/ffn/mul"}',
        '  %bitcast.5 = f32[4] bitcast(a), metadata={op_name="jit(f)/attn/attn.diff/reshape"}',
        "}"])
    assert motif.scoped_instructions(text) == {
        "fusion.1": "diff", "fusion.2": "diff", "fusion.3": "polynorm"}


def test_the_cell_is_what_the_issue_names():
    cell = cells.load_cell(CELL, ROOT)
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"],
            t["max_requests_per_client_per_s"]) == ("serve_motif", "closed",
                                                    64, 1.0)
    assert t["prompt_tokens"] == {"1024": 0.25, "2048": 0.25, "4096": 0.25,
                                  "8064": 0.25}
    assert t["output_tokens"] == {"3100": 0.25, "4100": 0.25, "5100": 0.25,
                                  "6000": 0.25}
    assert sum(int(k) * v for k, v in t["prompt_tokens"].items()) == 3808
    assert (t["first_wave"], t["window_opens"], t["window_end"]) == (
        "whole_mix", "after_first_wave", "cut_by_client")
    assert (t["shapes_seed"], t["open_limit_s"], t["trace_seconds"]) == (
        1377002918, 180, 4)
    assert (t["temperature"], t["stream"]) == (0, True)
    # the SHAPES of swa-deep-sat32, on purpose
    mimo = cells.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                        "swa-deep-sat32.json"))
    for k in ("loop", "clients", "prompt_tokens", "output_tokens",
              "shapes_seed", "first_wave", "window_opens", "window_end",
              "max_requests_per_client_per_s", "temperature", "stream"):
        assert t[k] == mimo[k], k
    assert sum(8 * -(-(int(k) - 1) // 512) for k in t["prompt_tokens"]) == 240
    flags = cell.config["entries"]["serve"]
    assert flags == {"slots": 32, "kv_page_size": 16, "kv_pages": 16384,
                     "prefill_chunk": 512}
    longest = max(map(int, t["prompt_tokens"])) + max(
        map(int, t["output_tokens"]))
    assert longest <= cell.config["max_position_embeddings"] == 14336
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"compiles_in_window", "sat_decode_step_ms_p50",
                       "sat_rows_per_dispatch", "pages_used_share",
                       "moe_rows_per_active_expert", "moe_load_max_over_mean",
                       "hc_device_time_share", "hc_ops_per_sublayer",
                       "setup_program_make_s"} <= names
    doc = cells.load_benchmark(ROOT)
    assert len(doc["workloads"]) >= 12 and len(doc["configs"]) >= 10
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == \
                "out_tokens_per_s"
    check = cell.config["check"]
    assert [p for p, _ in check["long_requests"]] == [
        8064, 4096, 4096, 2048, 2048, 1024, 1024, 1024]


def test_the_driver_stops_at_once_on_a_program_without_the_fields(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "Activation")
    with pytest.raises(ImportError, match="no KV groups"):
        motif.program_spec(BETA)


def test_the_check_runs_lagunas_rules_on_this_reference():
    from benchmark.drivers import serve_motif

    before = laguna.logits
    with serve_motif._reference():
        assert laguna.logits is motif.logits
        assert laguna.with_reversals.__globals__["logits"] is motif.logits
    assert laguna.logits is before
    with pytest.raises(RuntimeError):
        with serve_motif._reference():
            raise RuntimeError("a check that fails")
    assert laguna.logits is before


CASE = ("throwaway.gdla-deep", "tiny-motif", "tiny-gdla-deep-sat", 1, CELL)


def test_rehearsal_1_the_motif_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0, seconds=3)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    err = proc.stderr
    assert "every served position" in err
    assert "window opened" in err and "have streamed a token" in err
    assert "check ok : no admission chunk ran inside the window" in err
    assert "'max_logit_shortfall': 0.0" in err
    assert "check ok : the reference with lambda = 0" in err
    assert "check ok : rings are resident at their exact size" in err
    assert "cut by their clients" in err and "0 prefill chunks" in err
    traced = th._last_line(th._run(root, CASE[0], trace=1, seconds=3))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    assert 0 < got["motif_local_pairs_share"]["value"] < 100
    assert got["motif_depth_positions_mean"]["value"] > 24
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(TRACED) & set(got)
