"""The latent-attention expert configuration's part of the benchmark, CPU
only (run with the rest of ``benchmark/tests``): the byte and operation
counts of ``harness/latent.py`` against the shapes, the seeded tree, the
benchmark's copy of the reference against the program's, both sides of the
check's tolerance, the trace readers on a made-up trace, and the latent
serve driver end to end at a toy width in a temporary copy that adds a
throw-away cell."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, latent, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CELL = "deepseekv3.gen-sat32"
CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "deepseek-v3-q40-ep8.json"))
DSV3 = latent.sizes_of(CONFIG)
TINY_CONFIG = cells.load_json(os.path.join(HERE, "tiny-latent.json"))
TINY = latent.sizes_of(TINY_CONFIG)
NEW = ("mla_device_time_share", "mla_attn_flops_share",
       "mla_latent_hbm_share", "mla_dense_q40_hbm_share",
       "dsmoe_expert_hbm_share", "dsmoe_device_time_share",
       "dsmoe_local_pairs_share")


def test_published_sizes_bytes_and_operations_by_hand():
    latent.check_runnable(CONFIG)
    spec = latent.program_spec(DSV3)
    assert (spec.latent.width, spec.head_size, spec.n_experts_held,
            spec.n_dense_layers, spec.n_expert_layers) == (576, 192, 32, 1, 8)
    # one expert: 3 x 2048 x 7168 weights at 18 bytes a block of 32
    assert latent.expert_bytes(DSV3) == 3 * 2048 * 7168 // 32 * 18 == 24772608
    attn = (1536 * 7168 + 24576 * 1536 + 576 * 7168 + 7168 * 16384)
    want = (9 * attn + 3 * 18432 * 7168 + 8 * 3 * 2048 * 7168
            + 16256 * 7168) // 32 * 18
    assert latent.dense_q40_bytes(DSV3) == want
    assert round(want / 1e9, 3) == 1.349
    # the program's own accounting of the same file
    own = sum(c * spec.matmul_bytes(s) for s, c in spec.matmul_shape_counts()
              if s != (32768, 512))             # wkv_b: held as float32
    one_expert_layer = (attn + 3 * 2048 * 7168 * 33) // 32 * 18
    assert own == one_expert_layer
    # a cached position: 576 float32 values a layer, read by 128 heads
    assert latent.latent_step_bytes(DSV3, 1) == 576 * 4 * 9 == 20736
    assert latent.latent_step_flops(DSV3, 1) == 2 * 128 * (576 + 512) * 9
    # a page of the pool, as the issue states it
    flags = CONFIG["entries"]["serve"]
    assert flags["kv_page_size"] * latent.latent_step_bytes(DSV3, 1) == 331776
    freq, scale = latent.yarn(DSV3)
    assert freq.shape == (32,) and freq[0] == 1.0
    assert scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2, rel=1e-4)
    # pairs under the correction range keep f; those over it take f / 40
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert np.allclose(freq[:10], plain[:10])
    assert np.allclose(freq[-8:], plain[-8:] / 40)


def test_tree_is_seeded_whatever_the_thread_count_and_loads():
    a = latent.codec_tree(TINY, 5, threads=1)
    b = latent.codec_tree(TINY, 5, threads=7)
    c = latent.codec_tree(TINY, 6)
    import jax

    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not any(np.array_equal(x, z) for x, z in zip(la, lc))
    assert a["moe_w1"].qs.shape[:2] == (2, 8) and a["moe_gate"].shape == (
        2, 16, 256)
    assert abs(a["moe_gate"].std() * np.sqrt(256) - 1) < 0.1
    assert abs(a["moe_bias"].std() / 0.05 - 1) < 0.3
    # the program's loader contract: its own seeded tree has these leaves
    from distributed_llama_tpu.models.synth import synth_params

    own = synth_params(latent.program_spec(TINY), q40=True, seed=1)
    assert jax.tree.structure(own) == jax.tree.structure(a)
    for x, y in zip(jax.tree.leaves(own), la):
        assert x.shape == y.shape and x.dtype == y.dtype


def test_the_two_references_agree():
    """The benchmark's layer-and-expert-at-a-time copy and the program's
    ``models/reference_latent.py`` are written apart and give the same
    logits and margins, of the share of the experts the tree holds; one
    precision down they do not."""
    from distributed_llama_tpu.models import reference_latent

    tree = latent.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 24))
    got, margins = latent.logits(tree, TINY, tokens, vocab_blocks=3,
                                 precisions=("highest", "bfloat16"))
    spec = latent.program_spec(TINY)
    for b in range(2):
        want, m, _ = reference_latent.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 5e-5
        assert np.allclose(margins[b], m.min(axis=1), atol=1e-5)
        assert np.abs(got["bfloat16"][b] - want).max() > 1e-2
    part, _ = latent.logits(tree, TINY, tokens, keep=[[3, 5], [7, 23]])
    assert np.allclose(part["highest"][1], got["highest"][1, [7, 23]],
                       atol=1e-5)


def test_settling_widens_the_margins_at_the_shared_positions():
    tree = latent.codec_tree(TINY, 3)
    tokens = np.asarray([[1, 260]])
    old = latent.SHARED_MARGIN
    try:
        latent.SHARED_MARGIN = 0.02       # so that some layer is re-drawn
        before = tree["moe_bias"].copy()
        _, m0 = latent.logits(tree, TINY, tokens)
        latent.settle_shared_positions(tree, TINY, tokens[0], seed=9)
        _, m1 = latent.logits(tree, TINY, tokens)
    finally:
        latent.SHARED_MARGIN = old
    assert m0.min() < 0.02 <= m1.min()
    assert not np.array_equal(before, tree["moe_bias"])
    again = latent.codec_tree(TINY, 3)
    try:
        latent.SHARED_MARGIN = 0.02
        latent.settle_shared_positions(again, TINY, tokens[0], seed=9)
    finally:
        latent.SHARED_MARGIN = old
    assert np.array_equal(again["moe_bias"], tree["moe_bias"])  # the seed's


def test_the_check_is_made_at_the_windows_load():
    from benchmark.drivers import serve_latent as drv

    plan = drv.check_requests(7, 32)
    reqs = [r for c in plan["clients"] for r in c]
    assert (plan["loop"], len(plan["clients"]), len(reqs)) == (
        "closed", 64, 64)
    shapes = {r["id"]: (r["prompt_tokens"], r["output_tokens"])
              for r in reqs}
    assert tuple(shapes[i] for i in range(8)) == drv.CHECK_PROMPTS
    assert max(n for n, _ in shapes.values()) > 128     # two chunks
    assert min(n for n, _ in shapes.values()) == 3
    assert len({r["prompt"][0] for r in reqs}) == 64    # distinct openings
    assert all(len(r["prompt"]) == r["prompt_tokens"] - 2 for r in reqs)
    assert drv.check_requests(7, 32) == plan != drv.check_requests(8, 32)
    # the configuration's long requests come first, flagged, and reach the
    # window's own lengths: its longest prompt (four chunks) and more, and
    # its longest sequence (1,536 positions, the mix's 512 + 1,024)
    longs = [tuple(x) for x in CONFIG["check"]["long_requests"]]
    flags = CONFIG["entries"]["serve"]
    plan = drv.check_requests(7, int(flags["slots"]), longs)
    reqs = [r for c in plan["clients"] for r in c]
    assert len(reqs) == 64 and len({r["prompt"][0] for r in reqs}) == 64
    assert [(r["prompt_tokens"], r["output_tokens"])
            for r in reqs if r["long"]] == longs
    assert [r["long"] for r in reqs] == [True] * len(longs) + [False] * (
        64 - len(longs))
    mix = cells.load_json(os.path.join(
        cells.BENCH_DIR, "traffic", "mla-gen-sat32.json"))
    longest = (max(map(int, mix["prompt_tokens"]))
               + max(map(int, mix["output_tokens"])))
    assert max(n + out for n, out in longs) == longest == 1536
    assert sum(n >= 4 * int(flags["prefill_chunk"]) for n, _ in longs) >= 4
    assert sum(n + out > longest - 256 for n, out in longs) >= 3
    assert max(n + out for n, out in longs) <= CONFIG[
        "max_position_embeddings"]


def _greedy_records(tree, tok, plan, precision):
    """What a server that computed the reference at ``precision`` would
    stream for ``plan``, greedy: records as the load client writes them."""
    reqs = [r for c in plan["clients"] for r in c]
    prompts = [tok.encode(r["prompt"], bos=True, eos=False) for r in reqs]
    width = max(len(p) + r["output_tokens"] for p, r in zip(prompts, reqs))
    rows = np.zeros((len(reqs), width), np.int64)
    for b, p in enumerate(prompts):
        rows[b, :len(p)] = p
    ends = [len(p) + r["output_tokens"] for p, r in zip(prompts, reqs)]
    for t in range(min(map(len, prompts)) - 1, width - 1):
        got, _ = latent.logits(tree, TINY, rows, precisions=(precision,),
                               keep=[[t]] * len(reqs), vocab_blocks=1)
        nxt = got[precision][:, 0].argmax(-1)
        for b, p in enumerate(prompts):
            if len(p) - 1 <= t < ends[b] - 1:
                rows[b, t + 1] = nxt[b]
    return [{"id": r["id"], "ok": True,
             "tokens": [int(x) for x in rows[b, 1:ends[b]]]}
            for b, r in enumerate(reqs)]


@pytest.mark.parametrize("precision,ok", [("highest", True),
                                          ("bfloat16", False)])
def test_the_check_passes_float32_streams_and_fails_bfloat16_ones(
        precision, ok):
    """The comparison that decides ``correct``, on streams of its own
    making: the float32 reference's greedy streams pass with a shortfall of
    0, and the streams of the same reference one precision down come out
    NOT correct by the configuration's tolerance."""
    from benchmark.drivers import serve_latent as drv
    from benchmark.harness import model

    assert TINY_CONFIG["check"]["logit_tolerance"] == \
        CONFIG["check"]["logit_tolerance"]
    tree = latent.codec_tree(TINY, 11)
    tok = model.tokenizer(TINY["vocab_size"])
    latent.settle_shared_positions(
        tree, TINY, tok.encode("", bos=True, eos=False), 11)
    plan = drv.check_requests(11, 3)
    for reqs in plan["clients"]:               # short, for the CPU
        for r in reqs:
            r["prompt"] = r["prompt"][:r["id"] + 1]
            r["prompt_tokens"] = len(tok.encode(r["prompt"], bos=True,
                                                eos=False))
            r["output_tokens"] = 24
    records = _greedy_records(tree, tok, plan, precision)
    got = drv.check_streams(records, plan, tok, tree, TINY, TINY_CONFIG,
                            group=6)
    d = got["detail"]
    assert got["ok"] is ok, d
    assert d["requests"] == 6
    if ok:
        assert d["max_logit_shortfall"] == 0.0
        assert d["positions_strict"] > 100
        assert d["control_bfloat16_max_shortfall"] > 2 * d["tolerance"]
        assert d["control_positions_over_tolerance"] >= 1
    else:
        assert d["max_logit_shortfall"] > 2 * d["tolerance"]


def test_a_near_tie_excuses_its_own_request_only(monkeypatch):
    """One request with a margin under epsilon at its third served position
    and a wrong token after it passes; the same wrong token before it, or
    in a request without a near-tie, fails."""
    from benchmark.drivers import serve_latent as drv
    from benchmark.harness import model

    tok = model.tokenizer(TINY["vocab_size"])
    plan = drv.check_requests(5, 2)
    reqs = [r for c in plan["clients"] for r in c]
    for r in reqs:
        r["prompt"] = r["prompt"][:4]
        r["prompt_tokens"], r["output_tokens"] = 6, 8
    vocab, n_req = TINY["vocab_size"], len(reqs)

    def fake_logits(tree, sizes, tokens, keep=None, precisions=("highest",),
                    **kw):
        B, T = tokens.shape
        out = np.zeros((B, keep.shape[1], vocab), np.float32)
        out[..., 7] = 1.0                          # the reference picks 7
        m = np.full((B, T), 0.5)
        m[0, 7] = 1e-7                             # request 0, served #2
        low = out.copy()
        low[:, 0, 9] = 2.0              # the control picks 9, and so fails
        return {"highest": out, "bfloat16": low}, m

    monkeypatch.setattr(latent, "logits", fake_logits)

    def records(wrong):
        out = []
        for r in reqs:
            toks = tok.encode(r["prompt"], bos=True, eos=False)[1:] + [7] * 8
            out.append({"id": r["id"], "ok": True, "tokens": toks})
        for rid, at in wrong:
            out[rid]["tokens"][5 + at] = 9
        return out

    def run(wrong):
        return drv.check_streams(records(wrong), plan, tok, None, TINY,
                                 TINY_CONFIG, group=n_req)

    clean = run([])
    assert clean["ok"] and clean["detail"]["positions_strict"] == 2 + 8 * (
        n_req - 1)
    after = run([(0, 5)])
    assert after["ok"]
    assert after["detail"]["requests_with_an_excused_shortfall"] == 1
    assert not run([(0, 1)])["ok"]                 # before the near-tie
    assert not run([(1, 5)])["ok"]                 # another request


@pytest.mark.parametrize("wrong,control_wrong,ok", [
    (0, 30, True),      # nothing falls short, the control does
    (1, 30, True),      # a flipped expert: few positions behind it
    (12, 30, False),    # a fault: over the limit's share of them
    (0, 1, False),      # a control that passes fails the check
])
def test_a_long_request_decides_by_the_share_that_falls_short(
        wrong, control_wrong, ok, monkeypatch):
    """A long request has a near-tie inside its prompt, so none of its
    served positions is strict: of its 60 excused positions the share over
    the tolerance decides, against ``check.excused_share_limit``, and the
    bfloat16 control has to read over that limit on the same positions."""
    from benchmark.drivers import serve_latent as drv
    from benchmark.harness import model

    tok = model.tokenizer(TINY["vocab_size"])
    plan = drv.check_requests(5, 2, [(12, 60)])
    reqs = [r for c in plan["clients"] for r in c]
    for r in reqs[1:]:
        r["prompt"] = r["prompt"][:4]
        r["prompt_tokens"], r["output_tokens"] = 6, 8
    assert [r["long"] for r in reqs] == [True, False, False, False]
    vocab = TINY["vocab_size"]

    def fake_logits(tree, sizes, tokens, keep=None, precisions=("highest",),
                    **kw):
        B, T = tokens.shape
        out = np.zeros((B, keep.shape[1], vocab), np.float32)
        out[..., 7] = 1.0                          # the reference picks 7
        low = out.copy()
        m = np.full((B, T), 0.5)
        if T > 40:                                 # the long group
            m[:, 3] = 1e-7                         # inside the prompt
            low[:, :control_wrong, 9] = 2.0        # the control picks 9
        else:
            low[:, 0, 9] = 2.0
        return {"highest": out, "bfloat16": low}, m

    monkeypatch.setattr(latent, "logits", fake_logits)
    records = []
    for r in reqs:
        toks = tok.encode(r["prompt"], bos=True, eos=False)[1:] + [7] * r[
            "output_tokens"]
        records.append({"id": r["id"], "ok": True, "tokens": toks})
    for at in range(wrong):
        records[0]["tokens"][11 + 2 * at] = 9
    got = drv.check_streams(records, plan, tok, None, TINY, TINY_CONFIG,
                            group=3)
    d = got["detail"]
    assert got["ok"] is ok, d
    assert d["positions_strict"] == 3 * 8 and d["requests_comparable"] == 3
    assert d["requests_with_an_excused_share"] == 1
    assert d["max_excused_share"] == pytest.approx(wrong / 60)
    assert d["control_bfloat16_excused_share"] == pytest.approx(
        control_wrong / 60)
    assert d["excused_share_limit"] == CONFIG["check"][
        "excused_share_limit"] == 0.03


def _op(name, lo, hi, kind="custom-call"):
    return reduce_trace.Op(name, kind, float(lo), float(hi))


def _made_up_step():
    """One dense and one expert layer and the classifier."""
    names = ["_q40_mxu_nb_stacked.1", "_q40_mxu_nb_stacked.2",
             "_q40_mxu_nb_stacked.3", "fusion.1",
             "mla_paged_attn_decode.1", "fusion.2", "_q40_mxu_nb_stacked.4",
             "_q40_mxu_nb_stacked.5", "_q40_mxu_nb_stacked.6",   # dense FFN
             "_q40_mxu_nb_stacked.7", "_q40_mxu_nb_stacked.8",
             "_q40_mxu_nb_stacked.9", "mla_paged_attn_decode.2",
             "_q40_mxu_nb_stacked.10",
             "fusion.3", "moe_q40_slots.1", "moe_q40_slots.2",   # experts
             "_q40_mxu_nb_stacked.11", "_q40_mxu_nb_stacked.12",  # shared
             "fusion.4", "_q40_mxu_nb_2d.1"]
    ops = [_op("while.1", 0, 10 * len(names), "while")]
    for i, n in enumerate(names):
        ops.append(_op(n, 10 * i, 10 * i + 10,
                       "fusion" if n.startswith("fusion") else "custom-call"))
    return ops, 10 * len(names)


def test_trace_readers_on_a_made_up_step():
    ops, end = _made_up_step()
    assert reduce_trace.classify(ops[5]) == "attention"
    spans = [_op("serve.step", 0, end + 10, "host")]
    mods = [_op("jit_serve_decode_step", 0, end, "module")]
    tr = reduce_trace.Trace({"/device:TPU:0": ops}, spans,
                            window=(0, end + 10),
                            modules={"/device:TPU:0": mods})
    assert latent.step_kernel_seconds(tr) == [
        {"latent": pytest.approx(20e-9), "slots": pytest.approx(20e-9),
         "dense": pytest.approx(130e-9)}]
    got = latent.block_seconds(tr)
    # two attention blocks of 7 and 5 ops; one expert block: fusion.3 to
    # fusion.4 (6 ops) after the second ``wo``
    assert got["mla"] == pytest.approx(120e-9)
    assert got["moe"] == pytest.approx(60e-9)
    # a dense model's trace has neither kernel: nothing to read
    dense = [o for o in ops if "mla_" not in o.name and "moe_" not in o.name]
    tr2 = reduce_trace.Trace({"/device:TPU:0": dense}, spans,
                             window=(0, end + 10),
                             modules={"/device:TPU:0": mods})
    assert latent.step_kernel_seconds(tr2) == []
    assert latent.block_seconds(tr2) == {"mla": 0.0, "moe": 0.0}


def test_the_readers_on_a_made_up_run_and_on_the_parents():
    from benchmark.harness import runtime

    ops, end = _made_up_step()
    tr = reduce_trace.Trace(
        {"/device:TPU:0": ops}, [_op("serve.step", 0, end + 10, "host")],
        window=(0, end + 10),
        modules={"/device:TPU:0": [_op("jit_serve_decode_step", 0, end,
                                       "module")]})
    cell = cells.load_cell(CELL, ROOT)
    before = {"steps": 0, "moe_pairs": 0, "moe_local_pairs": 0,
              "moe_active": 0, "latent_positions": 0}
    after = {"steps": 10, "moe_pairs": 20480, "moe_local_pairs": 2560,
             "moe_active": 1600, "latent_positions": 100000}
    run = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                      records=[], device={"kind": "TPU v5 lite"},
                      counters_before=before, counters_after=after, trace=tr)
    got = {n: cells.load_reader("layer_metrics", n).read(run) for n in NEW}
    assert got["dsmoe_local_pairs_share"] == pytest.approx(12.5)
    assert got["mla_latent_hbm_share"] == pytest.approx(
        100 * 10000 * 20736 / 20e-9 / 819e9)
    assert got["mla_attn_flops_share"] == pytest.approx(
        100 * 10000 * 2 * 128 * 1088 * 9 / 20e-9 / 197e12)
    assert got["dsmoe_expert_hbm_share"] == pytest.approx(
        100 * 160 * 24772608 / 20e-9 / 819e9)
    assert got["mla_dense_q40_hbm_share"] == pytest.approx(
        100 * latent.dense_q40_bytes(DSV3) / 130e-9 / 819e9)
    busy = (len(ops) - 1) * 10e-9
    assert got["mla_device_time_share"] == pytest.approx(
        100 * 120e-9 / busy)
    assert got["dsmoe_device_time_share"] == pytest.approx(100 * 60e-9 / busy)
    # the parent's program counts none of it and its trace holds no such
    # kernel: every new reader returns None
    old = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                      records=[], device={"kind": "TPU v5 lite"},
                      counters_before={"steps": 0},
                      counters_after={"steps": 5},
                      trace=reduce_trace.Trace({}, [], window=(0, 1)))
    for name in NEW:
        assert cells.load_reader("layer_metrics", name).read(old) is None


def test_the_cell_is_what_the_issue_names():
    cell = cells.load_cell(CELL, ROOT)
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"], cell.chips) == (
        "serve_latent", "closed", 64, 1)
    assert t["prompt_tokens"] == {"32": .2, "64": .25, "128": .25,
                                  "256": .2, "512": .1}
    assert t["output_tokens"] == {"128": .2, "256": .4, "512": .3,
                                  "1024": .1}
    assert sum(int(k) * v for k, v in t["output_tokens"].items()) == \
        pytest.approx(384)
    assert (t["temperature"], t["stream"], t["trace_seconds"],
            t["trace_start_s"]) == (0, True, 4, 10)
    assert cell.config["entries"]["serve"] == {
        "slots": 32, "kv_page_size": 16, "kv_pages": 4096,
        "prefill_chunk": 128}
    assert cell.config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings"]
    assert set(cell.config["reduced_why"]) == set(cell.config["reduced"])
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert "sat_q40_hbm_share" not in names
    assert set(NEW) | {"compiles_in_window", "sat_decode_step_ms_p50",
                       "pages_used_share"} <= names
    doc = cells.load_benchmark(ROOT)
    # (no claim that the cell is the LAST entry: the next cell added would
    # break it, as this one breaks test_retention_cell's)
    assert set(NEW) <= {m["name"] for m in doc["per_layer"]}
    assert all(len(x["why"]) <= 200 for x in doc["configs"]
               + doc["workloads"])


def test_the_driver_stops_at_once_on_a_program_without_the_records(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "LatentAttn")
    with pytest.raises(ImportError, match="LatentAttn"):
        latent.program_spec(DSV3)


CASE = ("throwaway.mla-gen-sat", "tiny-latent", "tiny-mla-gen-sat", 1, CELL)


def test_rehearsal_1_the_latent_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "every served position" in proc.stderr
    traced = th._last_line(th._run(root, CASE[0], trace=1))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    # the program's counters reach the reader: this toy chip holds the
    # second half of 16 experts; what needs a device trace finds no kernel
    # on the CPU and is left out
    assert 20.0 <= got["dsmoe_local_pairs_share"]["value"] <= 80.0
    assert not (set(NEW) - {"dsmoe_local_pairs_share"}) & set(got)
