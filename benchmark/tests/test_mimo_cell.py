"""``mimo.deep-sat32``'s part of the benchmark, CPU only (run with the rest
of ``benchmark/tests``): the configuration file against the catalog, the
byte counts of ``harness/mimo.py`` against the shapes and ISSUE 48's table,
the seeded tree and its sinks, the benchmark's copy of the reference against
the program's (and both controls), the held share's blocks, the trace readers
on a made-up trace, the cell as the issue names it, the late cutter, and the
mimo serve driver end to end at a toy width in a temporary copy that adds a
throw-away cell (its window opens after the first wave)."""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, laguna, mimo, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "mimo-v2-flash-q40-ep8.json"))
FLASH = mimo.sizes_of(CONFIG)
TINY_CONFIG = cells.load_json(os.path.join(HERE, "tiny-mimo.json"))
TINY = mimo.sizes_of(TINY_CONFIG)
CELL = "mimo.deep-sat32"
NEW = ("mimo_ring_attn_roofline", "mimo_paged_attn_roofline",
       "mimo_expert_roofline", "mimo_dense_q40_roofline",
       "mimo_sliding_device_time_share", "mimo_full_device_time_share",
       "mimo_moe_device_time_share", "mimo_local_pairs_share",
       "mimo_depth_positions_mean")
TRACED = NEW[:7]


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "MiMo-V2-Flash":
                return row
    pytest.skip("the catalog has no MiMo-V2-Flash row")


def test_every_published_key_is_in_the_file_and_no_width_is_cut():
    row = _catalog()
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "max_position_embeddings"}
    assert set(CONFIG["reduced"]) == set(CONFIG["reduced_why"])
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert CONFIG[key] == row["config"][key][:12], key
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == row["config"][key], key
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["vocab_size"] % 128 == 0
    assert set(CONFIG["assumed"]) >= {"rotary_form", "window", "sink",
                                      "multi_token_prediction",
                                      "tensor_names", "router_rows",
                                      "router_bias"}
    bench = cells.load_benchmark(ROOT)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mimo-v2-flash-q40-ep8")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == row["source_url"] and len(entry["why"]) <= 200


def test_published_sizes_and_bytes_by_hand():
    s = FLASH
    assert mimo.kinds_of(s) == ("full", "sliding", "sliding", "sliding",
                                "sliding", "full", "sliding", "sliding",
                                "sliding", "sliding", "sliding", "full")
    assert (s["heads"], s["full_kv_heads"], s["sliding_kv_heads"]) == (
        64, 4, 8)
    assert (s["head_size"], s["v_head_size"], s["rotary"]) == (192, 128, 64)
    assert (s["n_experts"], s["held"], s["offset"], s["dense_layers"]) == (
        256, 32, 0, 1)
    assert mimo.expert_bytes(s) == 14155776            # ISSUE 48: 14.16 MB
    assert mimo.kv_position_bytes(s, "full") == 5120
    assert mimo.kv_position_bytes(s, "sliding") == 10240
    assert mimo.kv_held_bytes(s, "full") == 6144
    assert mimo.kv_held_bytes(s, "sliding") == 12288
    # a ring as published: 128 x 8 x 320 x 4 = 1.31 MB
    assert 128 * mimo.kv_position_bytes(s, "sliding") == 1310720
    assert mimo.ring_step_bytes(s, 32 * 128) == 9 * 32 * 1310720
    assert mimo.full_step_bytes(s, 32 * 5500) == 32 * 5500 * 15360
    attn = {k: sum(d * n for _, (d, n) in mimo.attn_shapes(s, k))
            for k in ("full", "sliding")}
    assert attn == {"full": 89128960, "sliding": 94371840}
    assert mimo.dense_q40_bytes(s) == pytest.approx(0.79e9, rel=0.02)
    spec = mimo.program_spec(s)
    assert spec.kv_shape("full") == (4, 192, 128)
    assert spec.kv_shape("sliding") == (8, 192, 128)
    assert spec.kv_cached("full") * 4 == mimo.kv_held_bytes(s, "full")
    assert spec.n_experts_held == 32 and spec.header_version == 8
    assert spec.file_size() == pytest.approx(6.13e9, rel=0.02)


def _leaves(v):
    return [v.qs, v.d16] if hasattr(v, "qs") else [v]


def test_tree_is_seeded_whatever_the_thread_count_and_its_sinks_hold_mass():
    a, b = mimo.codec_tree(TINY, 7, threads=1), mimo.codec_tree(TINY, 7,
                                                                threads=5)
    flat = lambda t: [x for k in sorted(t) for x in (  # noqa: E731
        flat(t[k]) if isinstance(t[k], dict) else _leaves(t[k]))]
    for x, y in zip(flat(a), flat(b)):
        assert np.array_equal(x, y)
    assert set(a["sliding"]) == {"rms_att", "wq", "wk", "wv", "wo", "sink"}
    assert "sink" not in a["full"] and a["sliding"]["sink"].shape == (6, 4)
    lo, hi = mimo.SINK_SHARES
    share = np.exp(a["sliding"]["sink"]) / (
        np.exp(a["sliding"]["sink"]) + TINY["window"] * np.exp(0.5))
    assert lo <= share.min() and share.max() <= hi
    assert a["moe_w1"].qs.shape[:2] == (7, 4)           # the held share
    assert a["moe_gate"].shape == (7, 16, 128) and a["moe_bias"].shape == (
        7, 16)
    assert not np.array_equal(mimo.codec_tree(TINY, 8)["sliding"]["sink"],
                              a["sliding"]["sink"])


def test_the_two_references_agree_and_both_controls_do_not():
    """The benchmark's layer-at-a-time copy and the program's
    ``models/reference_laguna.py`` are written apart and give the same
    logits and margins (K 192 / V 128, KV heads a kind, the sink, the value
    scale, the choice bias, the held share); one precision down, or without
    the sink, they do not. The sink holds a share of a head's mass between
    a tenth and a half."""
    from distributed_llama_tpu.models import reference_laguna

    tree = mimo.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 40))
    stats: dict = {}
    got, margins = mimo.logits(tree, TINY, tokens, vocab_blocks=3,
                               precisions=("highest", "bfloat16"),
                               stats=stats)
    bare, _ = mimo.logits(tree, TINY, tokens, sink=False)
    spec = mimo.program_spec(TINY)
    for b in range(2):
        want, m, _ = reference_laguna.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 1e-4
        assert np.abs(margins[b] - m).max() < 1e-5
        assert np.abs(got["bfloat16"][b] - want).max() > 1e-2
        assert np.abs(bare["highest"][b] - want).max() > 1e-2
        no_sink = reference_laguna.forward(tree, spec, tokens[b],
                                           drop=("sink",))[0]
        assert np.abs(bare["highest"][b] - no_sink).max() < 1e-4
    assert 0.1 < stats["sink_mass_share"] < 0.5
    keep = np.asarray([[3, 39], [0, 17]])
    part, _ = mimo.logits(tree, TINY, tokens, keep=keep)
    assert np.abs(part["highest"][1, 1] - got["highest"][1, 17]).max() < 1e-5


def test_the_choice_is_on_the_biased_scores_and_the_share_keeps_its_pairs():
    rng = np.random.default_rng(2)
    scores = rng.uniform(0.1, 0.9, (2, 5, 16)).astype(np.float32)
    bias = rng.normal(0, 0.3, 16).astype(np.float32)
    live = np.ones((2, 5), bool)
    live[1, 3:] = False
    flip = np.zeros((2, 5), bool)
    ids, w, margin = mimo.route(TINY, scores, bias, flip, live)
    order = np.argsort(-(scores + bias), axis=-1)
    assert np.array_equal(np.sort(ids, -1), np.sort(order[..., :2], -1))
    assert np.allclose(w[live].sum(-1), 1.0) and not w[~live].any()
    picked = np.take_along_axis(scores, ids, -1)
    assert np.allclose(w[0], picked[0] / picked[0].sum(-1, keepdims=True))
    top = np.take_along_axis(scores + bias, order, -1)
    assert np.allclose(margin, top[..., 1] - top[..., 2])
    flip[0, 2] = True
    ids2, _, _ = mimo.route(TINY, scores, bias, flip, live)
    assert ids2[0, 2, 1] == order[0, 2, 2] and ids2[0, 2, 0] == ids[0, 2, 0]
    # the blocks hold the pairs on experts 4 .. 7 (the held share) alone
    used, expert, at, we = mimo.held_blocks(TINY, ids, w, live)
    here = (ids >= 4) & (ids < 8) & live[..., None]
    assert int((we != 0).sum()) == int(here.sum())
    n_pos = 10
    for blk in range(int(used)):
        for row in np.nonzero(we[blk])[0]:
            b, t = divmod(int(at[blk, row]), 5)
            j = list(ids[b, t]).index(expert[blk] + 4)
            assert we[blk, row] == w[b, t, j]
    assert (at[we == 0] >= n_pos).all()         # rows of zeros of their own


def _op(name, lo, hi, kind="custom-call"):
    return th._op(name, lo, hi, kind)


def _made_up_trace(chunk: bool = False):
    """One forward of the cell's depth: per layer wqkv, an attention kernel
    (or a chunk's fusion), the scale's fusion, wo, then the FFN (layer 0:
    w13, w2; the others: a fusion and two expert kernel calls, no shared
    expert), and the classifier's call at the end of a decode step."""
    ops, t = [], 0

    def add(name, dur, kind="custom-call"):
        nonlocal t
        ops.append(_op(name, t, t + dur, kind))
        t += dur

    for layer, kind in enumerate(mimo.kinds_of(FLASH)):
        add("_q40_mxu_nb_stacked.1", 10)
        if chunk:
            add("fusion.7", 4, "fusion")
        else:
            add(("hm_attn_rows_decode" if kind == "sliding"
                 else "hm_attn_paged_decode") + ".2", 20 if kind == "sliding"
                else 30)
        add("fusion.8", 1, "fusion")
        add("_q40_mxu_nb_stacked.3", 5)
        if layer:
            add("fusion.9", 1, "fusion")
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".4", 40)
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".5", 20)
        else:
            add("_q40_mxu_nb_stacked.6", 3)
            add("_q40_mxu_nb_stacked.7", 2)
    if not chunk:
        add("_q40_mxu_nb_2d.8", 7)
    return ops, t


def test_trace_readers_on_a_made_up_trace():
    from benchmark.harness import runtime

    dev = "/device:TPU:0"
    ops, end = _made_up_trace()
    tr = reduce_trace.Trace(
        {dev: ops}, [_op("serve.step", 0, end + 10, "host")],
        window=(0, end + 10),
        modules={dev: [_op("jit_serve_decode_step", 0, end, "module")]})
    (step,) = mimo.step_kernel_seconds(tr)
    assert step["ring"] == pytest.approx(9 * 20e-9)
    assert step["paged"] == pytest.approx(3 * 30e-9)
    assert step["slots"] == pytest.approx(11 * 60e-9)
    assert step["dense"] == pytest.approx((12 * 15 + 5 + 7) * 1e-9)
    blocks = mimo.block_seconds(tr, FLASH)
    assert blocks["sliding"] == pytest.approx(9 * 36e-9)
    assert blocks["full"] == pytest.approx(3 * 46e-9)
    assert blocks["moe"] == pytest.approx(11 * 61e-9)
    before = {"steps": 0, "trace_steps": 0, "trace_shared_kv_positions": 0,
              "trace_window_kv_positions": 0, "trace_moe_active": 0,
              "moe_pairs": 0, "moe_local_pairs": 0, "sum_active": 0,
              "shared_kv_positions": 0}
    after = {"steps": 99, "trace_steps": 10,
             "trace_shared_kv_positions": 10 * 32 * 5500,
             "trace_window_kv_positions": 10 * 32 * 128,
             "trace_moe_active": 10 * 224, "moe_pairs": 8000,
             "moe_local_pairs": 1000, "sum_active": 99 * 32,
             "shared_kv_positions": 99 * 32 * 5400}
    run = runtime.Run(cell=cells.load_cell(CELL, ROOT), seed=1, window_s=1.0,
                      setup_s=1.0, records=[],
                      device={"kind": "TPU v5 lite"}, counters_before=before,
                      counters_after=after, trace=tr)
    read = lambda n: cells.load_reader("layer_metrics", n).read(run)  # noqa
    assert read(NEW[0]) == pytest.approx(
        100 * 9 * 32 * 1310720 / 180e-9 / 819e9)
    assert read(NEW[1]) == pytest.approx(
        100 * 32 * 5500 * 15360 / 90e-9 / 819e9)
    assert read(NEW[2]) == pytest.approx(
        100 * 224 * 14155776 / 660e-9 / 819e9)
    assert read(NEW[3]) == pytest.approx(
        100 * mimo.dense_q40_bytes(FLASH) / 192e-9 / 819e9)
    busy = reduce_trace.busy(tr)["busy_s"][dev]
    assert read(NEW[4]) == pytest.approx(100 * 9 * 36e-9 / busy)
    assert read(NEW[5]) == pytest.approx(100 * 3 * 46e-9 / busy)
    assert read(NEW[6]) == pytest.approx(100 * 11 * 61e-9 / busy)
    assert read(NEW[7]) == pytest.approx(12.5)
    assert read(NEW[8]) == pytest.approx(5400)
    cops, cend = _made_up_trace(chunk=True)
    tr2 = reduce_trace.Trace(
        {dev: cops}, [], window=(0, cend),
        modules={dev: [_op("jit_serve_admit_prefill_chunk", 0, cend,
                           "module")]})
    assert mimo.step_kernel_seconds(tr2) == []      # no decode step
    chunk = mimo.block_seconds(tr2, FLASH)
    assert chunk["sliding"] == pytest.approx(9 * 20e-9)
    assert chunk["moe"] == pytest.approx(11 * 61e-9)


def test_readers_return_nothing_without_the_programs_kernels():
    """On a program without the kernels or the counters (and in an untraced
    run): every new reader returns None and none raises."""
    from benchmark.harness import runtime

    cell = cells.load_cell(CELL, ROOT)
    for trace in (None, reduce_trace.Trace({}, [], window=(0, 1)),
                  reduce_trace.Trace({"/device:TPU:0": [
                      _op("_q40_mxu_nb_2d.1", 0, 1)]}, [], window=(0, 1))):
        run = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                          records=[], device={"kind": "TPU v5 lite"},
                          counters_before={"steps": 0},
                          counters_after={"steps": 5}, trace=trace)
        for name in NEW:
            assert cells.load_reader("layer_metrics", name).read(run) is None


def test_the_cell_is_what_the_issue_names():
    cell = cells.load_cell(CELL, ROOT)
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"],
            t["max_requests_per_client_per_s"]) == ("serve_mimo", "closed",
                                                    64, 1.0)
    assert t["prompt_tokens"] == {"1024": 0.25, "2048": 0.25, "4096": 0.25,
                                  "8064": 0.25}
    assert t["output_tokens"] == {"3100": 0.25, "4100": 0.25, "5100": 0.25,
                                  "6000": 0.25}
    assert sum(int(k) * v for k, v in t["prompt_tokens"].items()) == 3808
    assert (t["first_wave"], t["window_opens"], t["window_end"]) == (
        "whole_mix", "after_first_wave", "cut_by_client")
    assert t["shapes_seed"] == cells.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "swa-mix-sat32.json"))["shapes_seed"]
    assert (t["temperature"], t["stream"]) == (0, True)
    # the fill: 8 prompts of each length on the 32 rows; a prompt's last
    # token takes the decode step, the others 240 chunks of 512
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
                       "moe_rows_per_active_expert",
                       "moe_load_max_over_mean"} <= names
    doc = cells.load_benchmark(ROOT)
    assert len(doc["workloads"]) >= 11 and len(doc["configs"]) >= 9
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        if m["moves"] == "out_tokens_per_s":
            assert "workloads" in m, m["name"]
    check = cell.config["check"]
    assert [p for p, _ in check["long_requests"]][:6] == [
        8064, 4096, 4096, 2048, 2048, 1024]
    assert check["sink_mass_floor"] == 0.1


def test_the_driver_stops_at_once_on_a_program_without_the_fields(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    import dataclasses

    from distributed_llama_tpu.models import spec as spec_mod

    @dataclasses.dataclass(frozen=True)
    class Old:
        heads: int
        rope_theta: float = 10000.0
        rotary_dim: int = 0
        rope_scaling: object = None

    monkeypatch.setattr(spec_mod, "MixerKind", Old)
    with pytest.raises(ImportError, match="no KV head count a kind"):
        mimo.program_spec(FLASH)


def test_the_check_runs_lagunas_rules_on_this_reference():
    """``serve_mimo._reference`` rebinds the name the check and the reversal
    rule call, for the time of the check alone."""
    from benchmark.drivers import serve_mimo

    before = laguna.logits
    with serve_mimo._reference():
        assert laguna.logits is mimo.logits
        assert laguna.with_reversals.__globals__["logits"] is mimo.logits
    assert laguna.logits is before
    with pytest.raises(RuntimeError):
        with serve_mimo._reference():
            raise RuntimeError("a check that fails")
    assert laguna.logits is before


def test_a_late_cutter_cuts_when_it_is_told():
    import socket

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "harness"))
    from wave_client import LateCutter

    cutter = LateCutter()
    a, b = socket.socketpair()
    assert cutter.watch(a)
    time.sleep(0.05)
    assert not cutter.done                  # armed by nobody: nothing is cut
    cutter.arm(time.monotonic() + 0.05)
    time.sleep(0.3)
    assert cutter.done and not cutter.watch(b)
    assert b.recv(1) == b""                 # a's end was shut down
    a.close()
    b.close()


def test_the_window_must_be_named_in_the_traffic_file():
    from benchmark.drivers import serve_mimo

    served = serve_mimo.Served.__new__(serve_mimo.Served)
    served.cell = cells.load_cell("laguna.mix-sat32", ROOT)   # no window_opens
    served.server = served.compiles = served.args = None
    with pytest.raises(ValueError, match="window_opens"):
        served.window({"clients": []}, 1.0)


CASE = ("throwaway.swa-deep", "tiny-mimo", "tiny-swa-deep-sat", 1, CELL)


def test_rehearsal_1_the_mimo_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0, seconds=3)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    err = proc.stderr
    assert "every served position" in err
    assert "window opened" in err and "have streamed a token" in err
    # window_opens honoured: no admission chunk inside the window
    assert "check ok : no admission chunk ran inside the window" in err
    assert "'max_logit_shortfall': 0.0" in err
    assert "check ok : the reference WITHOUT the sink column" in err
    assert "check ok : rings are resident at their exact size" in err
    assert "cut by their clients" in err and "0 prefill chunks" in err
    # set-up holds the fill: the window opened after the check
    assert line["metrics"]["setup_s"]["value"] > 5
    traced = th._last_line(th._run(root, CASE[0], trace=1, seconds=3))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    assert 0 < got["mimo_local_pairs_share"]["value"] < 100
    assert got["mimo_depth_positions_mean"]["value"] > 24
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(TRACED) & set(got)
