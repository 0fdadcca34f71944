"""``nemotron3.reason-sat32``'s part of the benchmark, CPU only (run with the
rest of ``benchmark/tests``): the configuration file against the catalog, the
byte counts of ``harness/nemotron.py``'s four roofline readers against
hand-counted shapes, the seeded tree, the benchmark's copy of the reference
against the program's (and the control), the trace readers on a made-up
trace, the cell as the issue names it, and the nemotron serve driver end to
end at a toy width in a temporary copy that adds a throw-away cell."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, nemotron, reduce_trace  # noqa: E402
from benchmark.harness import runtime  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "nemotron-3-nano-q40-ep2.json"))
NANO = nemotron.sizes_of(CONFIG)
TINY_CONFIG = cells.load_json(os.path.join(HERE, "tiny-nemotron.json"))
TINY = nemotron.sizes_of(TINY_CONFIG)
CELL = "nemotron3.reason-sat32"
NEW = ("nemo_ssd_state_roofline", "nemo_expert_roofline",
       "nemo_paged_attn_roofline", "nemo_dense_q40_roofline",
       "nemo_ssd_device_time_share", "nemo_attn_device_time_share",
       "nemo_moe_device_time_share", "nemo_local_pairs_share",
       "nemo_depth_positions_mean")
TRACED = NEW[:7]
ASSUMED = ("d_inner", "no_rotary", "groups", "intermediate_size",
           "initialisation_only", "residual", "state", "in_proj_split",
           "expert_padding", "gate_then_norm", "router", "seeded_leaves",
           "tensor_names", "precision")
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
                return row
    pytest.skip("the catalog has no Nemotron-3-Nano row")


def test_every_published_key_is_in_the_file_and_no_width_is_cut():
    row = _catalog()
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert set(CONFIG["reduced"]) == {
        "n_routed_experts", "vocab_size",
        "max_position_embeddings"} == set(CONFIG["reduced_why"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == row["config"][key]
    assert set(ASSUMED) <= set(CONFIG["assumed"])
    # all 52 layers, the published list letter for letter
    assert CONFIG["hybrid_override_pattern"] == PUBLISHED
    assert CONFIG["num_hidden_layers"] == 52
    dep = CONFIG["deployment"]
    assert dep["chips_per_layer"] * CONFIG["n_routed_experts"] == 128
    assert CONFIG["vocab_size"] * 2 == 131072
    nemotron.check_runnable(CONFIG)


def test_published_sizes_and_bytes_by_hand():
    s = NANO
    kinds = nemotron.kinds_of(s)
    assert [kinds.count(k) for k in nemotron.KINDS] == [23, 6, 23]
    assert (nemotron.d_inner(s), nemotron.conv_dim(s)) == (4096, 6144)
    assert dict(nemotron.mamba_shapes(s))["in_zx"] == (10240, 2688)
    assert dict(nemotron.attn_shapes(s))["wq"] == (4096, 2688)
    assert dict(nemotron.attn_shapes(s))["wk"] == (256, 2688)
    # a sequence keeps, a Mamba-2 layer: (64, 64, 128) of state and 3 rows
    # of 6,144 conv inputs, float32
    assert nemotron.state_row_bytes(s) == 2170880
    # the state kernel, a call at 32 rows: the state read and written, x,
    # decay and y (64, 128) each, B and C (8, 128) each
    assert nemotron.state_call_bytes(s, 32) == 32 * 4 * (
        2 * 64 * 64 * 128 + 3 * 64 * 128 + 2 * 8 * 128) == 137625600
    assert nemotron.state_step_bytes(s, 32) == 23 * 137625600
    # an expert AS READ: 1,856 packed as 2,048 rows of up over 88 blocks (84
    # and four of zeros) and 64 blocks a row of down
    assert nemotron.padded_hidden(s) == 2048
    assert nemotron.expert_bytes(s) == (2048 * 88 + 2688 * 64) * 18 \
        == 6340608
    assert nemotron.published_expert_bytes(s) == 2 * 1856 * 2688 // 32 * 18
    # K and V of a position in one attention layer: 2 heads of 128, float32
    assert nemotron.kv_position_bytes(s) == 2048
    assert nemotron.full_step_bytes(s, 32 * 2000) == 32 * 2000 * 2048 * 6
    want = (23 * (10240 + 4096) * 2688 + 6 * (4096 + 512 + 4096) * 2688
            + 23 * 2 * 3712 * 2688 + 65536 * 2688) // 32 * 18
    assert nemotron.dense_q40_bytes(s) == want
    spec = nemotron.program_spec(s)
    assert spec.header_version == 10 and spec.slotted
    assert spec.n_experts_held == 64 and spec.n_expert_layers == 23
    from distributed_llama_tpu.analysis import memory_model as mm

    assert mm.kv_position_bytes(spec, 1) == 6 * 2048
    assert mm.state_slot_bytes(spec) == 23 * 2170880


def _leaves(v):
    return (v.qs, v.d16) if hasattr(v, "qs") else (v,)


def test_tree_is_seeded_whatever_the_thread_count():
    a = nemotron.codec_tree(TINY, 5, threads=1)
    b = nemotron.codec_tree(TINY, 5, 4)
    for kind in nemotron.KINDS:
        for k, v in a[kind].items():
            for x, y in zip(_leaves(v), _leaves(b[kind][k])):
                assert np.array_equal(x, y), k
    assert not np.array_equal(a["experts"]["moe_gate"], nemotron.codec_tree(
        TINY, 6)["experts"]["moe_gate"])
    assert a["experts"]["moe_w1"].qs.shape[:3] == (3, 4, 96)   # the HELD
    assert a["experts"]["moe_gate"].shape == (3, 8, 128)
    assert a["mamba2"]["in_dt"].shape == (4, 4, 128)
    assert np.allclose(np.exp(a["mamba2"]["a_log"][0]), [1, 6, 11, 16])
    assert "moe_w3" not in a["experts"] and "sh_w3" not in a["experts"]


def test_the_two_references_agree_and_the_control_does_not():
    """The benchmark's layer-at-a-time copy (the recurrence under a scan,
    the router on the host, only routed pairs multiplied) and the program's
    ``models/reference_nemotron.py`` are written apart and give the same
    logits and margins, the held share included; one precision down they
    do not."""
    from distributed_llama_tpu.models import reference_nemotron

    tree = nemotron.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 40))
    got, margins = nemotron.logits(tree, TINY, tokens, vocab_blocks=3,
                                   precisions=("highest", "bfloat16"))
    spec = nemotron.program_spec(TINY)
    assert spec.layout.held == 4 and spec.layout.offset == 4
    for b in range(2):
        want, m, _ = reference_nemotron.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 1e-4
        assert np.abs(margins[b] - m).max() < 1e-5
        assert np.abs(got["bfloat16"][b] - want).max() > 1e-2
    keep = np.asarray([[3, 39], [0, 17]])
    part, _ = nemotron.logits(tree, TINY, tokens, keep=keep)
    assert np.abs(part["highest"][1, 1] - got["highest"][1, 17]).max() < 1e-5
    # padding past a row's length weighs no expert and reaches nothing
    # before it
    cut, _ = nemotron.logits(tree, TINY, tokens, lengths=[40, 20])
    assert np.abs(cut["highest"][1, :20] - got["highest"][1, :20]
                  ).max() < 1e-5


def _op(name, lo, hi, kind="custom-call"):
    return th._op(name, lo, hi, kind)


def _made_up_trace(chunk: bool = False):
    """One forward of the cell's depth: a Mamba-2 layer's in_zx, fusions,
    the state kernel (or a chunk's fusions), out_proj; an attention layer's
    wqkv, the paged kernel (or a chunk's fusion), wo; an expert layer's
    router fusion, two expert kernel calls, the shared expert's two; and
    the classifier's call at the end of a decode step."""
    ops, t = [], 0

    def add(name, dur, kind="custom-call"):
        nonlocal t
        ops.append(_op(name, t, t + dur, kind))
        t += dur

    for kind in nemotron.kinds_of(NANO):
        add("fusion.norm", 1, "fusion")
        if kind == "mamba2":
            add("_q40_mxu_nb_stacked.1", 6)
            add("fusion.conv", 2, "fusion")
            add("fusion.ssd" if chunk else "mamba2_decode_step.3",
                30 if chunk else 200, "fusion" if chunk else "custom-call")
            add("fusion.gate", 2, "fusion")
            add("_q40_mxu_nb_stacked.2", 4)
        elif kind == "full":
            add("_q40_mxu_nb_stacked.4", 3)
            add("fusion.attn" if chunk else "hm_attn_paged_decode.5", 50,
                "fusion" if chunk else "custom-call")
            add("_q40_mxu_nb_stacked.6", 3)
        else:       # the shared expert's calls BEFORE the routed experts'
            add("fusion.route", 3, "fusion")
            add("_q40_mxu_nb_stacked.9", 3)
            add("_q40_mxu_nb_stacked.10", 2)
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".7", 40)
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".8", 35)
    if not chunk:
        add("_q40_mxu_nb_2d.11", 7)
    return ops, t


def test_trace_readers_on_a_made_up_trace():
    dev = "/device:TPU:0"
    ops, end = _made_up_trace()
    tr = reduce_trace.Trace(
        {dev: ops}, [_op("serve.step", 0, end + 10, "host")],
        window=(0, end + 10),
        modules={dev: [_op("jit_serve_decode_step", 0, end, "module")]})
    (step,) = nemotron.step_kernel_seconds(tr)
    assert step["state"] == pytest.approx(23 * 200e-9)
    assert step["paged"] == pytest.approx(6 * 50e-9)
    assert step["slots"] == pytest.approx(23 * 75e-9)
    assert step["dense"] == pytest.approx((23 * 10 + 6 * 6 + 23 * 5 + 7)
                                          * 1e-9)
    blocks = nemotron.block_seconds(tr, NANO)
    # an expert layer runs on to the next layer's first call, so the
    # pre-norm (1 ns) of a layer that follows one falls to it
    # (the list ends in an expert layer, which runs on to the classifier)
    after_e = PUBLISHED.count("EM")
    assert PUBLISHED.count("E*") == 0 and after_e == 22
    assert blocks["mamba2"] == pytest.approx((23 * 215 - after_e) * 1e-9)
    assert blocks["full"] == pytest.approx(6 * 57e-9)
    assert blocks["experts"] == pytest.approx((23 * 84 + after_e) * 1e-9)
    before = dict.fromkeys(
        ("steps", "trace_steps", "trace_shared_kv_positions",
         "trace_moe_active", "trace_sum_active", "moe_pairs",
         "moe_local_pairs"), 0)
    after = {"steps": 99, "trace_steps": 10,
             "trace_shared_kv_positions": 10 * 32 * 2100,
             "trace_moe_active": 10 * 700, "trace_sum_active": 10 * 32,
             "moe_pairs": 8000, "moe_local_pairs": 4000}
    run = runtime.Run(
        cell=cells.load_cell(CELL, ROOT), seed=1, window_s=1.0, setup_s=1.0,
        records=[], device={"kind": "TPU v5 lite"}, counters_before=before,
        counters_after=after, trace=tr)
    read = lambda n: cells.load_reader("layer_metrics", n).read(run)  # noqa
    assert read(NEW[0]) == pytest.approx(
        100 * 23 * 137625600 / 4600e-9 / 819e9)
    assert read(NEW[1]) == pytest.approx(
        100 * 700 * 6340608 / 1725e-9 / 819e9)
    assert read(NEW[2]) == pytest.approx(
        100 * 32 * 2100 * 2048 * 6 / 300e-9 / 819e9)
    assert read(NEW[3]) == pytest.approx(
        100 * nemotron.dense_q40_bytes(NANO) / 388e-9 / 819e9)
    busy = reduce_trace.busy(tr)["busy_s"][dev]
    assert read(NEW[4]) == pytest.approx(100 * (23 * 215 - 22) * 1e-9 / busy)
    assert read(NEW[5]) == pytest.approx(100 * 6 * 57e-9 / busy)
    assert read(NEW[6]) == pytest.approx(100 * (23 * 84 + 22) * 1e-9 / busy)
    assert read(NEW[7]) == pytest.approx(50.0)
    assert read(NEW[8]) == pytest.approx(2100)
    cops, cend = _made_up_trace(chunk=True)
    tr2 = reduce_trace.Trace(
        {dev: cops}, [], window=(0, cend),
        modules={dev: [_op("jit_serve_admit_prefill_chunk", 0, cend,
                           "module")]})
    assert nemotron.step_kernel_seconds(tr2) == []      # no decode step
    assert nemotron.block_seconds(tr2, NANO)["mamba2"] == pytest.approx(
        (23 * 45 - after_e) * 1e-9)


def test_readers_return_nothing_without_the_programs_kernels():
    """On a program without the kernel or the counters (the parent commit,
    an untraced run): every new reader returns None and none raises."""
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


def test_the_cell_is_what_the_issue_names():
    cell = cells.load_cell(CELL, ROOT)
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"],
            t["max_requests_per_client_per_s"]) == ("serve_nemotron",
                                                    "closed", 64, 1.0)
    assert t["prompt_tokens"] == {"128": 0.2, "384": 0.3, "1024": 0.3,
                                  "2560": 0.2}
    assert t["output_tokens"] == {"1100": 0.2, "2300": 0.3, "3700": 0.3,
                                  "6000": 0.2}
    assert (t["temperature"], t["stream"], t["trace_seconds"]) == (0, True,
                                                                   4)
    assert (t["first_wave"], t["window_end"]) == ("whole_mix",
                                                  "cut_by_client")
    # the SHAPES of reason-sat32, number for number
    other = cells.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                         "reason-sat32.json"))
    for k in ("loop", "clients", "prompt_tokens", "output_tokens",
              "max_requests_per_client_per_s", "temperature", "stream",
              "trace_seconds"):
        assert t[k] == other[k], k
    flags = cell.config["entries"]["serve"]
    assert flags == {"slots": 32, "kv_page_size": 16, "kv_pages": 5632,
                     "prefill_chunk": 512}
    assert flags["prefill_chunk"] % cell.config["chunk_size"] == 0
    longest = max(map(int, t["prompt_tokens"])) + max(
        map(int, t["output_tokens"]))
    assert longest <= cell.config["max_position_embeddings"] == 8704
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "compiles_in_window", "sat_decode_step_ms_p50",
        "sat_rows_per_dispatch", "pages_used_share",
        "moe_rows_per_active_expert", "moe_load_max_over_mean",
        "sat_admit_stall_ms_per_chunk", "sat_admission_window_share",
        "sat_ttft_ms_p50", "sat_gap_ms_p95", "sat_client_late_ms_p99",
        "sat_plain_land_interval_ms_p50", "setup_program_make_s",
        "setup_weights_s", "setup_engine_s"} <= names
    doc = cells.load_benchmark(ROOT)
    assert len(doc["workloads"]) >= 13 and len(doc["configs"]) >= 11
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == \
                "out_tokens_per_s"
    check = cell.config["check"]
    assert [p for p, _ in check["long_requests"]] == [2560, 2560, 1024, 1024,
                                                      384, 128]


def test_the_driver_stops_at_once_on_a_program_without_the_record(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "SsdLayers")
    with pytest.raises(ImportError, match="SsdLayers"):
        nemotron.program_spec(NANO)


class _Tok:
    def encode(self, text, bos=True, eos=False):
        return [1] + [ord(c) for c in text]


def _made_up_check(monkeypatch, wrong_after=None, wrong_strict=False,
                   control_wrong=True, near_tie_at=6):
    """``check_streams`` over eight made-up short requests whose reference
    is a fake: the "right" token at a position is (position mod 7) + 3; the
    served streams pick it except at the positions ``wrong_after`` says."""
    from benchmark.drivers import serve_nemotron

    vocab, n_prompt, k = 16, 5, 40
    plan = {"clients": [[{"id": i, "prompt": "abcd", "prompt_tokens": 5}]
                        for i in range(8)]}
    right = lambda t: t % 7 + 3                                  # noqa: E731
    records = []
    for i in range(8):
        served = [right(n_prompt - 1 + j) for j in range(k)]
        if wrong_strict and i == 0:
            served[0] = 0
        for j in (wrong_after or {}).get(i, ()):
            served[j] = 0
        records.append({"id": i, "ok": True, "tokens": [ord(c) for c in
                                                        "abcd"] + served})

    def fake(tree, sizes, tokens, keep=None, lengths=None,
             precisions=("highest",)):
        b, span = keep.shape
        out = {}
        for p in precisions:
            lg = np.zeros((b, span, vocab), np.float32)
            for r in range(b):
                for j in range(span):
                    t = int(keep[r, j])
                    lg[r, j, right(t)] = 1.0
                    if p == "bfloat16" and control_wrong and j % 2:
                        lg[r, j, 1] = 2.0
            out[p] = lg
        margins = np.full((b, tokens.shape[1], 2), 1.0, np.float32)
        margins[:, near_tie_at] = 1e-7
        return out, margins

    monkeypatch.setattr(nemotron, "logits", fake)
    config = {"check": {"logit_tolerance": 0.002, "pooled_share_limit": 0.25,
                        "long_share_limit": 0.4}}
    return serve_nemotron.check_streams(records, plan, _Tok(), None, TINY,
                                        config)


def test_the_check_judges_strictly_then_by_the_pooled_share(monkeypatch):
    good = _made_up_check(monkeypatch)
    d = good["detail"]
    assert good["ok"] and d["positions_strict"] == 8 * 2
    assert d["positions_pooled_short"] == 8 * 38 and d["pooled_share_short"] == 0
    assert d["control_pooled_share_short"] == 0.5
    # one request that went another way after its near-tie: inside the limit
    one = _made_up_check(monkeypatch, wrong_after={3: range(5, 40)})
    assert one["ok"] and one["detail"]["max_request_share"] > 0.9
    assert one["detail"]["pooled_share_short"] == pytest.approx(35 / 304)
    assert one["detail"]["requests_with_a_position_over"] == 1
    # every request wrong at a third of its positions: a fault
    many = _made_up_check(monkeypatch, wrong_after={
        i: range(4, 40, 3) for i in range(8)})
    assert not many["ok"]
    # a wrong pick BEFORE the first near-tie fails whatever the shares say
    assert not _made_up_check(monkeypatch, wrong_strict=True)["ok"]
    # a control that passes fails the check
    assert not _made_up_check(monkeypatch, control_wrong=False)["ok"]


CASE = ("throwaway.ssd-reason", "tiny-nemotron", "tiny-ssd-reason-sat", 1,
        CELL)


def test_rehearsal_1_the_nemotron_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0, seconds=3)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    err = proc.stderr
    assert "every served position" in err
    assert "'max_logit_shortfall': 0.0" in err
    assert "check ok : the Mamba-2 states and conv rows are resident" in err
    assert "cut by their clients" in err and "smallest state decay" in err
    traced = th._last_line(th._run(root, CASE[0], trace=1, seconds=3))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    assert 0 < got["nemo_local_pairs_share"]["value"] < 100
    assert got["nemo_depth_positions_mean"]["value"] > 24
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(TRACED) & set(got)
