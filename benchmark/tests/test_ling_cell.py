"""``ling3flash.reason-sat32``'s part of the benchmark, CPU only (run with the
rest of ``benchmark/tests``): the configuration file against the catalog, the
byte counts of ``harness/ling.py``'s roofline readers against hand-counted
shapes, the seeded tree, the router's grouped choice on the host against the
program's, the benchmark's copy of the reference against the program's (and
the control), the trace readers on a made-up trace, the cell as the issue
names it, the check's rules on made-up streams, and the ling serve driver
end to end at a toy width in a temporary copy that adds a throw-away cell."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, ling, nemotron, reduce_trace  # noqa: E402
from benchmark.harness import runtime  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "ling-3-flash-q40-ep8.json"))
FLASH = ling.sizes_of(CONFIG)
TINY_CONFIG = cells.load_json(os.path.join(HERE, "tiny-ling.json"))
TINY = ling.sizes_of(TINY_CONFIG)
CELL = "ling3flash.reason-sat32"
NEW = ("ling_kda_state_roofline", "ling_expert_roofline",
       "ling_latent_attn_roofline", "ling_dense_q40_roofline",
       "ling_kda_device_time_share", "ling_latent_device_time_share",
       "ling_moe_device_time_share", "ling_kda_chunk_ms_per_chunk",
       "ling_local_pairs_share", "ling_depth_positions_mean")
TRACED = NEW[:8]
ASSUMED = ("layer_order", "safe_gate", "kda_projections", "group_norm_size",
           "use_qk_norm", "swiglu_limit", "nothing_to_compute",
           "multi_token_prediction", "tensor_names", "seeded_leaves",
           "precision")
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size", "max_position_embeddings",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Ling-3.0-flash":
                return row
    pytest.skip("the catalog has no Ling-3.0-flash row")


def test_every_published_key_is_in_the_file_and_no_width_is_cut():
    row = _catalog()
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert set(CONFIG["reduced"]) == REDUCED == set(CONFIG["reduced_why"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CONFIG["reduced"])
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == row["config"][key]
    assert set(ASSUMED) <= set(CONFIG["assumed"])
    # four whole periods of the published order, one leading dense layer
    assert CONFIG["num_hidden_layers"] == 24 == 4 * CONFIG["layer_group_size"]
    kinds = ling.kinds_of(FLASH)
    assert kinds[:6] == ("kda",) * 5 + ("full",) and kinds == kinds[:6] * 4
    assert CONFIG["first_k_dense_replace"] == 1
    dep = CONFIG["deployment"]
    assert dep["chips_per_layer"] * CONFIG["num_experts"] == 512
    assert CONFIG["num_experts"] == 512 // CONFIG["n_group"]   # ONE group
    assert CONFIG["vocab_size"] == 154 * 128 >= 157184 / 8
    # the limits are the published lists' first 24 entries, all 0 there
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert CONFIG[key] == row["config"][key][:24] == [0] * 24
        assert max(row["config"][key]) > 0
    ling.check_runnable(CONFIG)
    for key in ("entries_why", "stands_for"):
        assert "TO BE WRITTEN" not in CONFIG[key]
    assert "TO BE WRITTEN" not in CONFIG["check"]["why"]


def test_published_sizes_and_bytes_by_hand():
    s = FLASH
    kinds = ling.kinds_of(s)
    assert (kinds.count("kda"), kinds.count("full")) == (20, 4)
    assert ling.width(s) == 4096
    assert dict(ling.kda_shapes(s)) == {"in_qkvag": (20480, 2560),
                                        "wo": (2560, 4096)}
    assert dict(ling.latent_shapes(s)) == {
        "wq": (6144, 2560), "wkv_a": (576, 2560), "wkv_b": (8192, 512),
        "wo": (2560, 4096)}
    # a sequence keeps, a KDA layer: (32, 128, 128) of state and 3 rows of
    # 3 x 4,096 conv inputs, float32
    assert ling.state_row_bytes(s) == 2244608
    # the state kernel, a call at 32 rows: the state read and written, the
    # (128, 128) block of columns, v, b (k . q) and o (32, 128) each
    assert ling.state_call_bytes(s, 32) == 32 * 4 * (
        2 * 32 * 128 * 128 + 128 * 128 + 3 * 32 * 128) == 137887744
    assert ling.state_step_bytes(s, 32) == 20 * 137887744
    assert ling.expert_bytes(s) == 3 * 768 * 2560 // 32 * 18 == 3317760
    # a cached position in one latent layer: 576 values in 640 lanes
    assert ling.plane_position_bytes(s) == 2560
    assert ling.latent_step_bytes(s, 32 * 2000) == 32 * 2000 * 2560 * 4
    want = (20 * (20480 + 4096) * 2560 + 4 * (6144 + 640 + 4096) * 2560
            + 3 * 6144 * 2560 + 23 * 3 * 768 * 2560 + 19712 * 2560
            ) // 32 * 18
    assert ling.dense_q40_bytes(s) == want
    spec = ling.program_spec(s)
    assert spec.header_version == 11 and spec.slotted
    assert spec.n_experts_held == 64 and spec.n_expert_layers == 23
    assert spec.latent.q_rank == 0 and spec.latent.head_gate
    from distributed_llama_tpu.analysis import memory_model as mm
    from distributed_llama_tpu.runtime.continuous import sequence_caches

    assert mm.kv_position_bytes(spec, 1) == 4 * 2560
    assert mm.state_slot_bytes(spec) == 20 * 2244608
    assert sequence_caches(spec) == frozenset({"state", "plane"})


def _leaves(v):
    return (v.qs, v.d16) if hasattr(v, "qs") else (v,)


def test_tree_is_seeded_whatever_the_thread_count():
    a = ling.codec_tree(TINY, 5, threads=1)
    b = ling.codec_tree(TINY, 5, 4)
    for stack in ("kda", "full", "dense"):
        for k, v in a[stack].items():
            for x, y in zip(_leaves(v), _leaves(b[stack][k])):
                assert np.array_equal(x, y), k
    assert not np.array_equal(a["moe_gate"],
                              ling.codec_tree(TINY, 6)["moe_gate"])
    assert a["moe_w1"].qs.shape[:3] == (5, 4, 128)      # the HELD
    assert a["moe_gate"].shape == (5, 16, 128)
    assert a["kda"]["in_qkvag"].qs.shape[:2] == (4, 5 * 64)
    assert a["kda"]["conv_w"].shape == (4, 4, 3 * 64)
    assert a["full"]["w_hgate"].shape == (2, 4, 128)
    assert np.allclose(np.exp(a["kda"]["a_log"][0]), [0.5, 1.0, 1.5, 2.0])
    assert a["ffn_limit"].tolist() == [[0, 0]] * 3 + [[0.5, 0.75]] * 2
    spec = ling.program_spec(TINY)
    for stack, name, _, shape in spec.stack_leaves():
        leaf = (a[stack] if stack else a)[name]
        got = (*leaf.qs.shape[:-2], leaf.qs.shape[-2] * 32) \
            if hasattr(leaf, "qs") else leaf.shape
        assert tuple(got) == tuple(shape), (stack, name)


def test_the_host_router_chooses_as_the_program_does():
    """``ling.route`` (numpy, on the host) against ``ops/pallas_moe.route``
    and ``models/reference_latent.route`` on the same scores: the same
    experts, weights and margins under 8 groups of which 4 are kept."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models import reference_latent
    from distributed_llama_tpu.ops.pallas_moe import route

    sizes = dict(FLASH, n_experts=64, held=8, n_active_experts=8)
    spec = ling.program_spec(dict(sizes, dim=64, vocab_size=256, seq_len=64,
                                  n_layers=6))
    rng = np.random.default_rng(2)
    gate = rng.standard_normal((64, 64)).astype(np.float32) / 8
    bias = (0.05 * rng.standard_normal(64)).astype(np.float32)
    h = rng.standard_normal((2, 9, 64)).astype(np.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "ed,btd->bte", gate, h, precision=jax.lax.Precision.HIGHEST)))
    live = np.ones((2, 9), bool)
    live[1, 6:] = False
    ids, w, margin = ling.route(sizes, scores, bias, live)
    topw, topi = route(jnp.asarray(gate), jnp.asarray(h.reshape(-1, 64)), 8,
                       spec.router, jnp.asarray(bias))
    assert np.array_equal(np.sort(ids.reshape(-1, 8)),
                          np.sort(np.asarray(topi)))
    got = np.take_along_axis(w.reshape(-1, 8), np.argsort(
        ids.reshape(-1, 8)), 1)
    want = np.take_along_axis(np.asarray(topw), np.argsort(
        np.asarray(topi)), 1)
    assert np.abs(got - want)[live.reshape(-1)].max() < 1e-6
    assert (w[1, 6:] == 0).all()
    _, _, m = reference_latent.route(spec, gate, bias,
                                     jnp.asarray(h.reshape(-1, 64)))
    assert np.abs(margin.reshape(-1) - np.asarray(m)).max() < 1e-6


def test_the_two_references_agree_and_the_control_does_not():
    """The benchmark's layer-at-a-time copy (the recurrence under a scan,
    expanded latent attention, the router on the host, only routed pairs
    multiplied, the clamp) and the program's ``models/reference_kda.py``
    are written apart and give the same logits and margins, the held share
    included; one precision down they do not."""
    from distributed_llama_tpu.models import reference_kda

    tree = ling.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 40))
    got, margins = ling.logits(tree, TINY, tokens, vocab_blocks=3,
                               precisions=("highest", "bfloat16"))
    spec = ling.program_spec(TINY)
    assert spec.layout.held == 4 and spec.layout.offset == 4
    for b in range(2):
        want, m, _ = reference_kda.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 1e-4
        assert np.abs(margins[b] - m).max() < 1e-5
        assert np.abs(got["bfloat16"][b] - want).max() > 1e-2
    keep = np.asarray([[3, 39], [0, 17]])
    part, _ = ling.logits(tree, TINY, tokens, keep=keep)
    assert np.abs(part["highest"][1, 1] - got["highest"][1, 17]).max() < 1e-5
    # padding past a row's length weighs no expert and reaches nothing
    # before it
    cut, _ = ling.logits(tree, TINY, tokens, lengths=[40, 20])
    assert np.abs(cut["highest"][1, :20] - got["highest"][1, :20]
                  ).max() < 1e-5
    # the clamp of the last layers bites in this copy too
    free = dict(tree, ffn_limit=tree["ffn_limit"] * 0)
    loose, _ = ling.logits(free, TINY, tokens)
    assert np.abs(loose["highest"] - got["highest"]).max() > 1e-3


def _op(name, lo, hi, kind="custom-call"):
    return th._op(name, lo, hi, kind)


def _made_up_trace(chunk: bool = False):
    """One forward of the cell's depth: a KDA mixer's in_qkvag, fusions, the
    state kernel (or a chunk's fusions), wo; a latent mixer's wq and wkv_a,
    the paged kernel (or a chunk's fusion), wo; an FFN's router fusion, the
    shared expert's two calls and the two expert kernel calls (layer 0: the
    dense FFN's two); the classifier's call at the end of a decode step. A
    chunk returns no logits: its last layer's FFN is dropped by the
    compiler."""
    ops, t = [], 0

    def add(name, dur, kind="custom-call"):
        nonlocal t
        ops.append(_op(name, t, t + dur, kind))
        t += dur

    kinds = ling.kinds_of(FLASH)
    for layer, kind in enumerate(kinds):
        add("fusion.norm", 1, "fusion")
        if kind == "kda":
            add("_q40_mxu_nb_stacked.1", 12)
            add("fusion.conv", 2, "fusion")
            add("fusion.chunk" if chunk else "kda_decode_step.3",
                60 if chunk else 200, "fusion" if chunk else "custom-call")
            add("fusion.out_norm", 2, "fusion")
            add("_q40_mxu_nb_stacked.2", 4)
        else:
            add("_q40_mxu_nb_stacked.4", 3)
            add("_q40_mxu_nb_stacked.5", 1)
            add("fusion.attn" if chunk else "mla_paged_attn_decode.5", 50,
                "fusion" if chunk else "custom-call")
            add("_q40_mxu_nb_stacked.6", 3)
        if chunk and layer + 1 == len(kinds):
            break
        add("fusion.route", 3, "fusion")
        add("_q40_mxu_nb_stacked.9", 3)
        add("_q40_mxu_nb_stacked.10", 2)
        if layer:
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
    (step,) = ling.step_kernel_seconds(tr)
    assert step["state"] == pytest.approx(20 * 200e-9)
    assert step["latent"] == pytest.approx(4 * 50e-9)
    assert step["slots"] == pytest.approx(23 * 75e-9)
    assert step["dense"] == pytest.approx((20 * 16 + 4 * 7 + 24 * 5 + 7)
                                          * 1e-9)
    blocks = ling.block_seconds(tr, FLASH)
    # an FFN runs on to the next layer's first call, so every layer's
    # pre-norm (1 ns) but the first's falls to the FFN before it
    assert blocks["kda"] == pytest.approx((20 * 220 + 1) * 1e-9)
    assert blocks["full"] == pytest.approx(4 * 57e-9)
    assert blocks["moe"] == pytest.approx((24 * 8 + 23 * 75 + 23) * 1e-9)
    assert blocks["chunks"] == 0 and blocks["chunk_mid"] == 0
    before = dict.fromkeys(
        ("steps", "trace_steps", "trace_shared_kv_positions",
         "trace_moe_active", "trace_sum_active", "moe_pairs",
         "moe_local_pairs"), 0)
    after = {"steps": 99, "trace_steps": 10,
             "trace_shared_kv_positions": 10 * 32 * 2100,
             "trace_moe_active": 10 * 500, "trace_sum_active": 10 * 32,
             "moe_pairs": 8000, "moe_local_pairs": 1000}
    run = runtime.Run(
        cell=cells.load_cell(CELL, ROOT), seed=1, window_s=1.0, setup_s=1.0,
        records=[], device={"kind": "TPU v5 lite"}, counters_before=before,
        counters_after=after, trace=tr)
    read = lambda n: cells.load_reader("layer_metrics", n).read(run)  # noqa
    assert read(NEW[0]) == pytest.approx(
        100 * 20 * 137887744 / 4000e-9 / 819e9)
    assert read(NEW[1]) == pytest.approx(
        100 * 500 * 3317760 / 1725e-9 / 819e9)
    assert read(NEW[2]) == pytest.approx(
        100 * 32 * 2100 * 2560 * 4 / 200e-9 / 819e9)
    assert read(NEW[3]) == pytest.approx(
        100 * ling.dense_q40_bytes(FLASH) / 475e-9 / 819e9)
    busy = reduce_trace.busy(tr)["busy_s"][dev]
    assert read(NEW[4]) == pytest.approx(100 * 4401e-9 / busy)
    assert read(NEW[5]) == pytest.approx(100 * 4 * 57e-9 / busy)
    assert read(NEW[6]) == pytest.approx(100 * 1940e-9 / busy)
    assert read(NEW[7]) is None         # no admission chunk in this trace
    assert read(NEW[8]) == pytest.approx(12.5)
    assert read(NEW[9]) == pytest.approx(2100)
    cops, cend = _made_up_trace(chunk=True)
    tr2 = reduce_trace.Trace(
        {dev: cops}, [], window=(0, cend),
        modules={dev: [_op("jit_serve_admit_prefill_chunk", 0, cend,
                           "module")]})
    assert ling.step_kernel_seconds(tr2) == []      # no decode step
    blocks = ling.block_seconds(tr2, FLASH)
    assert blocks["chunks"] == 1
    assert blocks["chunk_mid"] == pytest.approx(20 * 64e-9)
    run2 = runtime.Run(
        cell=run.cell, seed=1, window_s=1.0, setup_s=1.0, records=[],
        device={"kind": "TPU v5 lite"}, counters_before=before,
        counters_after=after, trace=tr2)
    assert cells.load_reader("layer_metrics", NEW[7]).read(
        run2) == pytest.approx(20 * 64e-6)


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
            t["max_requests_per_client_per_s"]) == ("serve_ling", "closed",
                                                    64, 1.0)
    assert (t["first_wave"], t["window_end"]) == ("whole_mix",
                                                  "cut_by_client")
    # the SHAPES of reason-sat32, number for number
    other = cells.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                         "reason-sat32.json"))
    for k in ("loop", "clients", "prompt_tokens", "output_tokens",
              "max_requests_per_client_per_s", "temperature", "stream",
              "trace_seconds"):
        assert t[k] == other[k], k
    assert "TO BE WRITTEN" not in t["bypasses"]
    flags = cell.config["entries"]["serve"]
    assert (flags["slots"], flags["kv_page_size"],
            flags["prefill_chunk"]) == (32, 16, 512)
    assert flags["prefill_chunk"] % 64 == 0         # whole KDA chunks
    longest = max(map(int, t["prompt_tokens"])) + max(
        map(int, t["output_tokens"]))
    assert longest <= cell.config["max_position_embeddings"] == 8704
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    doc = cells.load_benchmark(ROOT)
    beside = {m["name"] for m in doc["per_layer"]
              if "nemotron3.reason-sat32" in m.get("workloads", ())
              and not m["name"].startswith("nemo_")}
    assert set(NEW) | beside | {"compiles_in_window"} <= names
    assert {"sat_decode_step_ms_p50", "pages_used_share",
            "moe_rows_per_active_expert", "sat_admit_stall_ms_per_chunk",
            "sat_admission_window_share", "setup_engine_s"} <= beside
    assert len(doc["workloads"]) >= 14 and len(doc["configs"]) >= 12
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == \
                "out_tokens_per_s"
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    check = cell.config["check"]
    assert [p for p, _ in check["long_requests"]] == [2560, 2560, 1024, 1024,
                                                      384, 128]


def test_the_driver_stops_at_once_on_a_program_without_the_record(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "KdaLayers")
    with pytest.raises(ImportError, match="KdaLayers"):
        ling.program_spec(FLASH)


class _Tok:
    def encode(self, text, bos=True, eos=False):
        return [1] + [ord(c) for c in text]


def _made_up_check(monkeypatch, wrong_after=None, wrong_strict=False,
                   control_wrong=True, near_tie_at=6):
    """``serve_ling.check_streams`` over eight made-up short requests whose
    reference (``ling.logits``, which the driver binds under the name the
    shared rules call) is a fake: the "right" token at a position is
    (position mod 7) + 3; the served streams pick it except at the positions
    ``wrong_after`` says."""
    from benchmark.drivers import serve_ling

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

    monkeypatch.setattr(ling, "logits", fake)
    config = {"check": {"logit_tolerance": 0.002, "pooled_share_limit": 0.25,
                        "long_share_limit": 0.4}}
    before = nemotron.logits
    out = serve_ling.check_streams(records, plan, _Tok(), None, TINY, config)
    assert nemotron.logits is before        # the name is handed back
    return out


def test_the_check_judges_strictly_then_by_the_pooled_share(monkeypatch):
    good = _made_up_check(monkeypatch)
    d = good["detail"]
    assert good["ok"] and d["positions_strict"] == 8 * 2
    assert "delta-rule reference" in good["what"]
    assert d["positions_pooled_short"] == 8 * 38 and d["pooled_share_short"] == 0
    assert d["control_pooled_share_short"] == 0.5
    # one request that went another way after its near-tie: inside the limit
    one = _made_up_check(monkeypatch, wrong_after={3: range(5, 40)})
    assert one["ok"] and one["detail"]["max_request_share"] > 0.9
    # every request wrong at a third of its positions: a fault
    assert not _made_up_check(monkeypatch, wrong_after={
        i: range(4, 40, 3) for i in range(8)})["ok"]
    # a wrong pick BEFORE the first near-tie fails whatever the shares say
    assert not _made_up_check(monkeypatch, wrong_strict=True)["ok"]
    # a control that passes fails the check
    assert not _made_up_check(monkeypatch, control_wrong=False)["ok"]


CASE = ("throwaway.kda-reason", "tiny-ling", "tiny-kda-reason-sat", 1, CELL)


def test_rehearsal_1_the_ling_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0, seconds=3)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0
    err = proc.stderr
    assert "every served position" in err
    assert "'max_logit_shortfall': 0.0" in err
    assert "check ok : the delta-rule states and conv rows are resident" in err
    assert "cut by their clients" in err and "smallest mean decay" in err
    traced = th._last_line(th._run(root, CASE[0], trace=1, seconds=3))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    assert 0 < got["ling_local_pairs_share"]["value"] < 100
    assert got["ling_depth_positions_mean"]["value"] > 24
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(TRACED) & set(got)
