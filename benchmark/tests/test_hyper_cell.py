"""The part of the benchmark that belongs to the configuration whose
residual path is several streams (``xing4-29b-a4b-q40``, the cell
``xing4.gen-sat32``), CPU only (run with the rest of ``benchmark/tests``):
the byte and operation counts of ``harness/hyper.py`` by hand, the seeded
tree, the benchmark's copy of the reference against the program's, both
controls, the trace readers on a made-up step, and the hyper serve driver
end to end at a toy width in a temporary copy that adds a throw-away cell."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, hyper, latent, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CELL = "xing4.gen-sat32"
CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "xing4-29b-a4b-q40.json"))
XING = hyper.sizes_of(CONFIG)
TINY_CONFIG = cells.load_json(os.path.join(HERE, "tiny-hyper.json"))
TINY = hyper.sizes_of(TINY_CONFIG)
NEW = ("hc_device_time_share", "hc_stream_hbm_share", "hc_ops_per_sublayer")


def test_published_sizes_bytes_and_operations_by_hand():
    hyper.check_runnable(CONFIG)
    assert (XING["dim"], XING["streams"], XING["n_layers"],
            XING["dense_layers"], XING["held"], XING["n_experts"]) == (
        3584, 4, 18, 2, 64, 64)
    assert hyper.coefficients(XING) == 24
    # a sub-layer at 32 rows: X and X' (32 x 4 x 3584 float32 each), h and y
    # (32 x 3584 each), phi (24 x 14336 float32); 36 sub-layers
    per = (2 * 32 * 4 * 3584 + 2 * 32 * 3584 + 24 * 14336) * 4
    assert per == 5963776
    assert hyper.hc_step_bytes(XING, 32) == 36 * per == 214695936
    # operations a row and sub-layer: norm 2 x 14336, projection 2 x 24 x
    # 14336, mixes 2 x 14336 + 2 x 16 x 3584 + 2 x 14336, Sinkhorn 16 x 81
    row = (2 * 14336 + 2 * 24 * 14336 + 2 * 14336 + 2 * 16 * 3584
           + 2 * 14336 + 16 * 81)
    assert hyper.hc_step_flops(XING, 32) == 36 * 32 * row
    assert latent.expert_bytes(XING) == 3 * 1024 * 3584 // 32 * 18
    with pytest.raises(ValueError, match="xing4_0"):
        hyper.check_runnable(dict(CONFIG, model_type="deepseek_v3"))
    with pytest.raises(ValueError, match="one group"):
        hyper.check_runnable(dict(CONFIG, n_group=8))


def test_every_catalog_number_is_in_the_file_under_its_key():
    """The catalog's ``config`` (model-configs guide), key for key; what
    differs is under ``reduced`` and nothing else is."""
    import json

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    want = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert CONFIG["source"] == want["source_url"]
    differs = {k for k, v in want["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {"num_hidden_layers",
                                                 "max_position_embeddings"}
    assert set(CONFIG["reduced_why"]) == differs
    for key in ("sinkhorn_order", "hc_eps", "flat_norm", "entry_and_exit",
                "seeded_values", "tensor_names", "multi_token_prediction"):
        assert key in CONFIG["assumed"]


def test_tree_is_seeded_and_holds_the_paths_leaves():
    a, b = hyper.codec_tree(TINY, 7, threads=1), hyper.codec_tree(TINY, 7)
    c = hyper.codec_tree(TINY, 8)
    for stack_a, stack_b, stack_c, depth in (
            (a["dense"], b["dense"], c["dense"], 2), (a, b, c, 3)):
        for sub in hyper.SUBLAYERS:
            phi = stack_a[f"hc_{sub}_phi"]
            assert phi.shape == (depth, 24, 256) and phi.dtype == np.float32
            assert np.array_equal(phi, stack_b[f"hc_{sub}_phi"])
            assert not np.array_equal(phi, stack_c[f"hc_{sub}_phi"])
            assert phi.std() == pytest.approx(256 ** -0.5, rel=0.05)
            assert (stack_a[f"hc_{sub}_gate"] == 0.5).all()
            b_res = stack_a[f"hc_{sub}_bias"][:, 8:].reshape(depth, 4, 4)
            assert np.diagonal(b_res, axis1=1, axis2=2).mean() > 3.0
    # the program loads it: the spec's stacks, leaf for leaf
    spec = hyper.program_spec(TINY)
    names = {(s, n) for s, n, _, _ in spec.stack_leaves()}
    assert {("dense", "hc_att_phi"), ("", "hc_ffn_bias")} <= names
    for stack, name, _, shape in spec.stack_leaves():
        leaf = (a[stack] if stack else a)[name]
        got = leaf.qs.shape[:-2] + (leaf.qs.shape[-2] * 32,) \
            if hasattr(leaf, "qs") else leaf.shape
        assert tuple(got) == tuple(shape), (stack, name)


def test_the_two_references_agree_and_both_controls_read():
    """The benchmark's copy against the program's own reference, on logits;
    the all-products control reads far over the tolerance; the
    projection-only control moves the logits less."""
    from distributed_llama_tpu.models import reference_hyper

    tree = hyper.codec_tree(TINY, 11)
    tokens = np.random.default_rng(2).integers(3, TINY["vocab_size"],
                                               (2, 24))
    got, margins = hyper.logits(tree, TINY, tokens,
                                precisions=hyper.PRECISIONS)
    spec = hyper.program_spec(TINY)
    for b in range(2):
        want, m, _ = reference_hyper.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 2e-5
        assert np.allclose(margins[b], m, atol=1e-5)
    tol = TINY_CONFIG["check"]["logit_tolerance"]
    low = np.abs(got["bfloat16"] - got["highest"]).max()
    proj = np.abs(got["projection_bfloat16"] - got["highest"]).max()
    assert low > 10 * tol and 0 < proj < low


def _greedy_records(tree, tok, plan, precision, flips=()):
    """What a server that computed the reference at ``precision`` (with the
    router decisions ``flips`` reversed) would stream for ``plan``."""
    reqs = [r for c in plan["clients"] for r in c]
    prompts = [tok.encode(r["prompt"], bos=True, eos=False) for r in reqs]
    width = max(len(p) + r["output_tokens"] for p, r in zip(prompts, reqs))
    rows = np.zeros((len(reqs), width), np.int64)
    for b, p in enumerate(prompts):
        rows[b, :len(p)] = p
    ends = [len(p) + r["output_tokens"] for p, r in zip(prompts, reqs)]
    for t in range(min(map(len, prompts)) - 1, width - 1):
        got, _ = hyper.logits(tree, TINY, rows, precisions=(precision,),
                              keep=[[t]] * len(reqs), vocab_blocks=1,
                              flips=flips)
        nxt = got[precision][:, 0].argmax(-1)
        for b, p in enumerate(prompts):
            if len(p) - 1 <= t < ends[b] - 1:
                rows[b, t + 1] = nxt[b]
    return [{"id": r["id"], "ok": True,
             "tokens": [int(x) for x in rows[b, 1:ends[b]]]}
            for b, r in enumerate(reqs)]


def _short_plan(tok, seed, n_clients, outputs):
    from benchmark.drivers import serve_hyper as drv

    plan = drv.check_requests(seed, n_clients)
    for reqs in plan["clients"]:               # short, for the CPU
        for r in reqs:
            r["prompt"] = r["prompt"][:r["id"] + 1]
            r["prompt_tokens"] = len(tok.encode(r["prompt"], bos=True,
                                                eos=False))
            r["output_tokens"] = outputs
    return plan


@pytest.fixture(scope="module")
def settled():
    from benchmark.harness import model

    tree = hyper.codec_tree(TINY, 11)
    tok = model.tokenizer(TINY["vocab_size"])
    hyper.settle_shared_positions(
        tree, TINY, tok.encode("", bos=True, eos=False), 11)
    return tree, tok


@pytest.mark.parametrize("precision,ok", [("highest", True),
                                          ("bfloat16", False)])
def test_the_check_passes_float32_streams_and_fails_bfloat16_ones(
        precision, ok, settled):
    """The comparison that decides ``correct``, on streams of its own
    making: the float32 reference's greedy streams pass with a shortfall of
    0, and the streams of the same reference one precision down come out
    NOT correct by the configuration's tolerance."""
    from benchmark.drivers import serve_hyper as drv

    assert TINY_CONFIG["check"]["logit_tolerance"] == \
        CONFIG["check"]["logit_tolerance"]
    tree, tok = settled
    plan = _short_plan(tok, 11, 3, 24)
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
        assert d["control_projection_bfloat16_max_shortfall"] is not None
        assert d["decisions_reversed"] == 0 == d["control_decisions_reversed"]
        assert d["decisions_reversed_at"] == []
    else:
        assert d["max_logit_shortfall"] > 2 * d["tolerance"]


def test_a_decision_the_server_took_the_other_way_is_found_and_reversed(
        settled, monkeypatch):
    """A server that took ONE router decision the other way (request 0, at a
    prompt position, where the check is told the margin is a float32 ulp)
    streams what the reference gives with that decision reversed: the
    check finds the decision, reverses it and passes; told the same of a
    decision the server did NOT take the other way, it does not pass."""
    from benchmark.drivers import serve_hyper as drv

    tree, tok = settled
    plan = _short_plan(tok, 11, 2, 40)
    flip = (0, 2, 0)            # request 0, position 2, expert layer 0
    records = _greedy_records(tree, tok, plan, "highest", flips=[flip])
    honest = _greedy_records(tree, tok, plan, "highest")
    assert records[0]["tokens"] != honest[0]["tokens"]
    assert records[1:] == honest[1:]
    real = hyper.logits

    def doubtful_at(position, margin=1e-7):
        def logits(*a, **kw):
            got, margins = real(*a, **kw)
            if margins is not None:
                margins = margins.copy()
                margins[0, position, flip[2]] = margin
            return got, margins
        return logits

    monkeypatch.setattr(hyper, "logits", doubtful_at(flip[1]))
    got = drv.check_streams(records, plan, tok, tree, TINY, TINY_CONFIG,
                            group=4)
    d = got["detail"]
    assert got["ok"], d
    assert [(r["request"], r["position"], r["expert_layer"])
            for r in d["decisions_reversed_at"]] == [flip]
    assert d["decisions_reversed"] == 1 <= d["decisions_reversed_limit"]
    assert d["max_excused_share"] == 0.0
    # the control went through the same rule and still reads over: what a
    # reversal can explain of the served streams it may explain of the
    # control's too, and explains nothing there
    assert d["control_bfloat16_max_shortfall"] > 2 * d["tolerance"]
    assert d["control_decisions_reversed"] == 0
    # more reversals standing than a run may show: drift, not rounding
    monkeypatch.setattr(hyper, "MAX_REVERSALS_A_RUN", 0)
    over = drv.check_streams(records, plan, tok, tree, TINY, TINY_CONFIG,
                             group=4)
    assert not over["ok"] and over["detail"]["decisions_reversed"] == 1
    monkeypatch.undo()
    # the honest server's streams under the same doubt: nothing to reverse
    monkeypatch.setattr(hyper, "logits", doubtful_at(flip[1]))
    assert drv.check_streams(honest, plan, tok, tree, TINY, TINY_CONFIG,
                             group=4)["detail"]["decisions_reversed"] == 0
    # a margin that is merely small (under MARGIN_EPSILON, over a few
    # float32 ulps) is no licence to reverse
    assert hyper.REVERSAL_EPSILON < 5e-6 < hyper.MARGIN_EPSILON
    monkeypatch.setattr(hyper, "logits", doubtful_at(flip[1], 5e-6))
    wide = drv.check_streams(records, plan, tok, tree, TINY, TINY_CONFIG,
                             group=4)
    assert not wide["ok"] and wide["detail"]["decisions_reversed"] == 0
    # a doubt at ANOTHER decision does not explain the streams
    monkeypatch.setattr(hyper, "logits", doubtful_at(flip[1] + 1))
    bad = drv.check_streams(records, plan, tok, tree, TINY, TINY_CONFIG,
                            group=4)
    assert not bad["ok"] and bad["detail"]["max_excused_share"] > 0.03


def test_positions_judged_by_share_count_against_a_void_check(
        settled, monkeypatch):
    """Three of four requests meet a near-tie three positions into their
    answers and a server that took no decision the other way: 9 + 48 of 192
    served positions are compared strictly, under half, and the other 135
    by their requests' shares (0 of them fall short), so the check is not
    void and passes; with answers too short for a share (under
    ``EXCUSED_MIN`` positions after the near-tie) the same doubt voids it."""
    from benchmark.drivers import serve_hyper as drv

    tree, tok = settled
    real = hyper.logits
    for outputs, ok in ((48, True), (24, False)):
        plan = _short_plan(tok, 11, 2, outputs)
        records = _greedy_records(tree, tok, plan, "highest")
        lens = [r["prompt_tokens"] for c in plan["clients"] for r in c]

        def logits(*a, **kw):
            got, margins = real(*a, **kw)
            if margins is not None:
                margins = margins.copy()
                for b in range(3):
                    margins[b, lens[b] + 2, 0] = 1e-7
            return got, margins

        monkeypatch.setattr(hyper, "logits", logits)
        got = drv.check_streams(records, plan, tok, tree, TINY, TINY_CONFIG,
                                group=4)
        d = got["detail"]
        assert got["ok"] is ok, d
        assert d["positions_strict"] == 9 + outputs
        assert d["positions_judged_by_share"] == (3 * 45 if ok else 0)
        assert d["max_logit_shortfall"] == 0.0
        assert d["max_excused_share"] == 0.0


def test_the_reference_reverses_one_decision_and_no_other():
    tree = hyper.codec_tree(TINY, 5)
    tokens = np.random.default_rng(4).integers(3, TINY["vocab_size"], (2, 12))
    a, m = hyper.logits(tree, TINY, tokens)
    b, _ = hyper.logits(tree, TINY, tokens, flips=[(1, 5, 0)])
    d = np.abs(a["highest"] - b["highest"]).max(axis=-1)
    assert d[0].max() == 0 and d[1, :5].max() == 0 and d[1, 5:].min() > 0
    assert m.shape == (2, 12, 3)
    assert hyper.decisions_to_reverse(np.full((9, 3), 0.5), 8) is None
    doubt = np.full((9, 3), 0.5)
    doubt[3, 1], doubt[4, 2], doubt[6, 0], doubt[8, 1] = 5e-6, 1e-6, 3e-7, 1e-7
    assert hyper.decisions_to_reverse(doubt, 7) == (6, 0, 3e-7)
    assert hyper.decisions_to_reverse(doubt, 7, [(6, 0)]) == (4, 2, 1e-6)
    # under MARGIN_EPSILON and over REVERSAL_EPSILON: not a candidate
    assert hyper.decisions_to_reverse(doubt, 7, [(6, 0), (4, 2)]) is None
    assert hyper.decisions_to_reverse(doubt, 3) is None


HLO = """HloModule jit_serve_decode_step

%fused_computation.7 (param_0.1: f32[4,32]) -> f32[4,32] {
  %param_0.1 = f32[4,32]{1,0} parameter(0)
  ROOT %divide.3 = f32[4,32]{1,0} divide(%param_0.1, %param_0.1), metadata={op_name="jit(serve_decode_step)/while/body/hc.coef/div"}
}

%region_2.2 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %reduce_sum.3 = f32[] add(%a.1, %b.1), metadata={op_name="hc.coef/reduce_sum"}
}

%body.5 (arg.1: (f32[4,32], f32[4,32,64])) -> (f32[4,32], f32[4,32,64]) {
  %arg.1 = (f32[4,32], f32[4,32,64]) parameter(0)
  %get-tuple-element.9 = f32[4,32]{1,0} get-tuple-element(%arg.1), index=0, metadata={op_name="jit(serve_decode_step)/while/body/hc.coef/slice"}
  %fusion.20 = f32[4,32]{1,0} fusion(%get-tuple-element.9), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(serve_decode_step)/while/body/hc.coef/div"}
  %fusion.21 = f32[32,64]{1,0} fusion(%fusion.20), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(serve_decode_step)/while/body/hc.mix/add"}
  %copy.4 = f32[32,64]{0,1} copy(%fusion.21), metadata={op_name="jit(serve_decode_step)/while/body/hc.mix/add"}
  %fusion.22 = f32[32,64]{1,0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(serve_decode_step)/while/body/rms_norm/mul"}
  ROOT %tuple.3 = (f32[4,32], f32[4,32,64]) tuple(%fusion.20, %fusion.22), metadata={op_name="jit(serve_decode_step)/while/body/hc.mix/add"}
}

ENTRY %main.9 (p.1: f32[4,32,64]) -> f32[32,64] {
  %p.1 = f32[4,32,64]{2,1,0} parameter(0)
  ROOT %fusion.901 = f32[32,64]{1,0} fusion(%p.1), kind=kLoop, calls=%fused_computation.10, metadata={op_name="jit(serve_decode_step)/hc.mix/add"}
}
"""


def test_the_paths_instructions_are_read_from_the_compiled_text():
    """Under a scope of the path, outside fused computations and reducers'
    regions, and a device op: two fusions and a copy of the scan's body and
    the exit's sum; not the RMSNorm beside them."""
    assert hyper.path_instructions(HLO) == {
        "fusion.20": "fusion", "fusion.21": "fusion", "copy.4": "copy",
        "fusion.901": "fusion"}
    assert hyper.path_instructions("") == {}


def _made_up_step():
    """Two dense layers and one expert layer of a program whose residual
    path is XLA fusions. Of a layer's fusions, 20 k + 0 / 1 are the
    attention's coefficients and input, + 3 / 4 / 5 the attention's
    mix-out and the FFN's coefficients and input, + 9 the FFN's mix-out;
    + 2 and + 6 are the two RMSNorms, + 7 / 8 an expert layer's router."""
    def layer(k, expert):
        f = [f"fusion.{20 * k + i}" for i in range(10)]
        out = [f"copy-done.{k}", f[0], f[1], f[2],
               *(f"_q40_mxu_nb_stacked.{10 * k + i}" for i in (1, 2, 3)),
               f"mla_paged_attn_decode.{k}",
               f"_q40_mxu_nb_stacked.{10 * k + 4}", f[3], f[4], f[5], f[6]]
        if expert:
            out += [f[7], f[8], f"moe_q40_slots.{2 * k}",
                    f"moe_q40_slots.{2 * k + 1}"]
        return out + [f"_q40_mxu_nb_stacked.{10 * k + 5}",
                      f"_q40_mxu_nb_stacked.{10 * k + 6}", f[9]]

    names = ["fusion.900"] + layer(0, False) + layer(1, False) \
        + layer(2, True) + ["fusion.901", "fusion.902", "_q40_mxu_nb_2d.1"]
    ops = [th_op("while.1", 0, 10 * len(names), "while")]
    for i, n in enumerate(names):
        kind = ("fusion" if n.startswith("fusion") else "copy-done"
                if n.startswith("copy-done") else "custom-call")
        ops.append(th_op(n, 10 * i, 10 * i + 10, kind))
    end = 10 * len(names)
    tr = reduce_trace.Trace(
        {"/device:TPU:0": ops}, [th_op("serve.step", 0, end + 10, "host")],
        window=(0, end + 10),
        modules={"/device:TPU:0": [th_op("jit_serve_decode_step", 0, end,
                                         "module")]})
    path = frozenset(f"fusion.{20 * k + i}" for k in range(3)
                     for i in (0, 1, 3, 4, 5, 9)) | {"fusion.901"}
    return tr, names, path


def test_trace_readers_on_a_made_up_step():
    """By identity the path is the 6 fusions a layer that the compiled
    text names and the exit's sum; by position (no names) it reads HIGH:
    the two RMSNorms a layer and the final one ride along, and layer 0's
    first part is left out with the embedding."""
    from benchmark.drivers import serve_hyper as drv

    tr, names, path = _made_up_step()
    busy = len(names) * 10e-9
    (got,) = hyper.hc_step_ops(tr, path)
    assert got == {"seconds": pytest.approx(19 * 10e-9),
                   "busy": pytest.approx(busy), "ops": 19, "sublayers": 6,
                   "rule": "identity"}
    (by_pos,) = hyper.hc_step_ops(tr)
    # after every wo 4 (the dense layer's count: the norm is one of them);
    # before layers 1 and 2: the last FFN's mix-out, coefficients, input,
    # norm (the wait for an asynchronous copy is not the path's); the tail:
    # the last mix-out, the streams' sum and the final norm
    assert by_pos == {"seconds": pytest.approx((3 * 4 + 2 * 4 + 3) * 10e-9),
                      "busy": pytest.approx(busy), "ops": 23,
                      "sublayers": 6, "rule": "position"}
    # names of another program (none of them in the step): nothing to read
    assert hyper.hc_step_ops(tr, frozenset({"fusion.77777"})) == []
    cell = cells.load_cell(CELL, ROOT)
    kw = dict(cell=cell, seed=1, window_s=1.0, setup_s=1.0, records=[],
              device={"kind": "TPU v5 lite"},
              counters_before={"steps": 0, "sum_active": 0, "hc_streams": 4},
              counters_after={"steps": 10, "sum_active": 320,
                              "hc_streams": 4}, trace=tr)
    for run, n_ops in ((drv.Run(path_ops=path, **kw), 19),
                       (drv.Run(**kw), 23)):
        read = {n: cells.load_reader("layer_metrics", n).read(run)
                for n in NEW}
        assert read["hc_ops_per_sublayer"] == pytest.approx(n_ops / 6)
        assert read["hc_device_time_share"] == pytest.approx(
            100 * n_ops * 10e-9 / busy)
        assert read["hc_stream_hbm_share"] == pytest.approx(
            100 * hyper.hc_step_bytes(XING, 32) / (n_ops * 10e-9) / 819e9)
    # a program without the streams (the parent's, any other cell's) and a
    # run without a trace: nothing to read
    from benchmark.harness import runtime

    for trace, after in (
            (None, kw["counters_after"]),
            (reduce_trace.Trace({}, [], window=(0, 1)), {"steps": 5})):
        old = runtime.Run(**dict(kw, counters_before={"steps": 0},
                                 counters_after=after, trace=trace))
        for name in NEW:
            assert cells.load_reader("layer_metrics", name).read(old) is None


def th_op(name, lo, hi, kind="custom-call"):
    return reduce_trace.Op(name, kind, float(lo), float(hi))


def test_the_cell_is_what_the_issue_names():
    cell = cells.load_cell(CELL, ROOT)
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"], cell.chips) == (
        "serve_hyper", "closed", 64, 1)
    assert t["prompt_tokens"] == {"32": .2, "64": .25, "128": .25,
                                  "256": .2, "512": .1}
    assert t["output_tokens"] == {"112": .2, "240": .3, "400": .25,
                                  "656": .15, "1008": .1}
    assert sum(int(k) * v for k, v in t["output_tokens"].items()) == \
        pytest.approx(393.6)
    assert (t["temperature"], t["stream"], t["trace_seconds"],
            t["trace_start_s"]) == (0, True, 4, 10)
    assert cell.config["entries"]["serve"] == {
        "slots": 32, "kv_page_size": 16, "kv_pages": 4096,
        "prefill_chunk": 128}
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"compiles_in_window", "sat_decode_step_ms_p50",
                       "pages_used_share", "mla_device_time_share",
                       "dsmoe_expert_hbm_share"} <= names
    assert not {"sat_q40_hbm_share", "dsmoe_local_pairs_share"} & names
    doc = cells.load_benchmark(ROOT)
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert (m["workloads"], m["moves"], m["layer"]) == (
                [CELL], "out_tokens_per_s", "residual path")
    assert all(len(x["why"]) <= 200 for x in doc["configs"]
               + doc["workloads"])
    longest = max(p + o for p, o in cell.config["check"]["long_requests"])
    assert longest == 512 + 1008 <= cell.config["max_position_embeddings"]


def test_the_driver_stops_at_once_on_a_program_without_the_record(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "HyperConnections")
    with pytest.raises(ImportError, match="HyperConnections"):
        hyper.program_spec(XING)


CASE = ("throwaway.hc-gen-sat", "tiny-hyper", "tiny-hc-gen-sat", 1, CELL)


def test_rehearsal_1_the_hyper_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "check ok : served tokens vs the float32 hyper-connection" in \
        proc.stderr
    assert "check ok : every routed pair landed on a held expert" in \
        proc.stderr
    assert "control_projection_bfloat16_max_shortfall" in proc.stderr
    assert "4 residual streams mixed around 10 sub-layers a step" in \
        proc.stderr
    assert "memory peak of serve alone" in proc.stderr
    assert "'decisions_reversed': 0" in proc.stderr
    proc = th._run(root, CASE[0], trace=1)
    # the step's compiled text gave the path's instructions (on the chip
    # the capture's ops are matched against them)
    assert "its ops are told by identity" in proc.stderr
    got = th._last_line(proc)["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    # the routed-rows counters the expert cells share read here unchanged
    assert got["moe_rows_per_active_expert"]["value"] >= 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(NEW) & set(got)


def test_rehearsal_2_the_logits_tool_reads_both_controls():
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for entry in ("serve", "inference"):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmark", "tools",
                                          "hyper_logits.py"),
             "--entry", entry, "--rehearse", "1", "--low-precision", "1",
             "--seed", "2147483999", "--config-file",
             os.path.join(HERE, "tiny-hyper.json")],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        assert out["ok"] and out["max_abs_diff_decode"] < 5e-5
        assert out["low_precision_ok"] is False
        assert out["low_projection"]["max_abs_diff_every_position"] > 0
