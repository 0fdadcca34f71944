"""The retention configuration's part of the benchmark, CPU only (run with
the rest of ``benchmark/tests``): the byte and operation counts of
``harness/retention.py`` against the shapes, the seeded tree, the
benchmark's copy of the reference against the program's, the trace readers
on a made-up trace, the cell as the issue names it, and the retention serve
driver end to end at a toy width in a temporary copy that adds a throw-away
cell."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, reduce_trace, retention  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "brumby-14b-q40.json"))
BRUMBY = retention.sizes_of(CONFIG)
TINY = retention.sizes_of(cells.load_json(os.path.join(
    HERE, "tiny-retention.json")))
CELL = "brumby14b.gen-sat16"
NEW = ("ret_state_hbm_share", "ret_prefill_flops_share",
       "ret_device_time_share")


def test_published_sizes_bytes_and_operations_by_hand():
    assert BRUMBY == {"dim": 5120, "hidden_dim": 17408, "n_layers": 10,
                      "n_heads": 40, "n_kv_heads": 8, "vocab_size": 151936,
                      "seq_len": 32768, "rope_theta": 1e6, "norm_eps": 1e-6}
    retention.check_runnable(CONFIG)
    assert retention.feature_rows(BRUMBY) == 8256 == 128 * 129 // 2
    # one sequence, one layer: 8 heads x (8256 x 128 + 8256) floats
    one = 8 * (8256 * 128 + 8256) * 4
    assert one == 34_080_768
    assert retention.state_step_bytes(BRUMBY, 16) == 16 * 10 * 2 * one
    assert round(retention.state_step_bytes(BRUMBY, 16) / 1e9, 1) == 10.9
    # the program stores 8320 rows a head: 0.8 % more than is counted
    spec = retention.program_spec(BRUMBY)
    from distributed_llama_tpu.ops.retention import state_bytes

    stored = state_bytes(spec.n_kv_heads, spec.head_size)
    assert stored == 8 * 65 * 128 * 129 * 4 and 1.007 < stored / one < 1.008
    # a 128-token chunk of one layer: about 13 GFLOP
    flops = retention.chunk_kernel_flops(BRUMBY, 128)
    read = 2 * 5 * 128 * 8256 * 129
    advance = 2 * 128 * 8256 * 129
    own = 4 * 5 * 128 * 128 * 128
    assert flops == 8 * (read + advance + own)
    assert 13.0e9 < flops < 13.5e9
    # the dense block's seven tensors are costs.py's, at this model's sizes
    from benchmark.harness import costs

    assert round(costs.q40_weight_bytes(BRUMBY) / 1e9, 2) == 2.30


def test_tree_is_seeded_whatever_the_thread_count_and_loads():
    a = retention.codec_tree(TINY, 5, threads=1)
    b = retention.codec_tree(TINY, 5, threads=7)
    c = retention.codec_tree(TINY, 6)

    def leaves(v):
        return list(v) if isinstance(v, tuple) else [v]

    assert set(a) == {"tok_embedding", "rms_att", "rms_ffn", "rms_final",
                      "rms_q", "rms_k", "wcls", "wq", "wk", "wv", "wo",
                      "w1", "w2", "w3", "w_gate"}
    for k in a:
        for x, y, z in zip(leaves(a[k]), leaves(b[k]), leaves(c[k])):
            assert np.array_equal(x, y) and not np.array_equal(x, z)
    assert a["rms_q"].shape == a["rms_k"].shape == (2, 16)
    assert a["w_gate"].shape == (2, 2, 128)
    assert abs(a["w_gate"].std() * np.sqrt(128) - 1) < 0.1
    # the program's loader contract: its own seeded tree has these leaves
    from distributed_llama_tpu.models.synth import synth_params

    own = synth_params(retention.program_spec(TINY), q40=True, seed=1)
    assert set(own) == set(a)
    for k in a:
        for x, y in zip(leaves(a[k]), leaves(own[k])):
            assert x.shape == y.shape and x.dtype == y.dtype, k


def test_the_two_references_agree():
    """The benchmark's layer-at-a-time copy and the program's
    ``models/reference_retention.py`` are written apart and give the same
    logits; one precision down they do not."""
    from distributed_llama_tpu.models import reference_retention

    tree = retention.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 24))
    got = retention.logits(tree, TINY, tokens, vocab_blocks=3)
    spec = retention.program_spec(TINY)
    for b in range(2):
        want = reference_retention.forward(tree, spec, tokens[b])
        assert np.abs(got[b] - want).max() < 5e-5


def test_the_check_is_made_at_the_windows_load():
    """Three requests a slot from two clients a slot, arriving at once: the
    rows fill, a queue stands and every later admission reuses a row."""
    from benchmark.drivers import serve_retention as drv

    plan = drv.check_requests(7, 16)
    reqs = [r for c in plan["clients"] for r in c]
    assert (plan["loop"], len(plan["clients"]), len(reqs)) == (
        "closed", 32, 48)
    assert [len(c) for c in plan["clients"]] == [2] * 16 + [1] * 16
    assert sorted(r["id"] for r in reqs) == list(range(48))
    shapes = {r["id"]: (r["prompt_tokens"], r["output_tokens"])
              for r in reqs}
    assert tuple(shapes[i] for i in range(8)) == drv.CHECK_PROMPTS
    assert all(3 <= n <= 72 and 12 <= out <= 48
               for i, (n, out) in shapes.items() if i >= 8)
    # a prompt of two chunks, and the shortest a prompt can be
    assert max(n for n, _ in shapes.values()) > 128
    assert min(n for n, _ in shapes.values()) == 3
    heads = [r["prompt"][:4] for r in reqs if r["prompt_tokens"] >= 6]
    assert len(set(heads)) == len(heads)            # no shared prefix
    assert drv.check_requests(7, 16) == plan != drv.check_requests(8, 16)
    small = drv.check_requests(7, 4)
    assert [len(c) for c in small["clients"]] == [2] * 4 + [1] * 4


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
        # one shape, so one program: the layer is causal, so the zeros
        # after position t do not reach it
        nxt = retention.logits(tree, TINY, rows, precision=precision,
                               keep=[t], vocab_blocks=1)[:, 0].argmax(-1)
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
    making: the float32 reference's greedy streams pass with a shortfall
    of 0, and the streams of the same reference one precision down (the
    control) come out NOT correct by the configuration's tolerance. The
    passing run also reads the control's teacher-forced picks, over it."""
    from benchmark.drivers import serve_retention as drv
    from benchmark.harness import model

    config = cells.load_json(os.path.join(HERE, "tiny-retention.json"))
    assert config["check"]["logit_tolerance"] == \
        CONFIG["check"]["logit_tolerance"]
    tree = retention.codec_tree(TINY, 11)
    tok = model.tokenizer(TINY["vocab_size"])
    plan = drv.check_requests(11, 2)
    for reqs in plan["clients"]:               # short, for the CPU
        for r in reqs:
            r["prompt"] = r["prompt"][:r["id"] + 1]
            r["prompt_tokens"] = len(tok.encode(r["prompt"], bos=True,
                                                eos=False))
            r["output_tokens"] = 40
    records = _greedy_records(tree, tok, plan, precision)
    got = drv.check_streams(records, plan, tok, tree, TINY, config, group=6)
    d = got["detail"]
    assert got["ok"] is ok, d
    assert d["positions_compared"] == 6 * 40
    if ok:
        assert d["max_logit_shortfall"] == 0.0
        # (4 tolerances at this toy width; the published widths' readings
        # on the chip are PERF.md's)
        assert d["control_bfloat16_max_shortfall"] > 2 * d["tolerance"]
        assert d["control_positions_over_tolerance"] >= 1
    else:
        assert d["max_logit_shortfall"] > 2 * d["tolerance"]


def test_the_check_is_void_on_a_failed_or_altered_request():
    from benchmark.drivers import serve_retention as drv
    from benchmark.harness import model

    tok = model.tokenizer(TINY["vocab_size"])
    plan = drv.check_requests(3, 1)
    reqs = [r for c in plan["clients"] for r in c]
    good = [{"id": r["id"], "ok": True, "tokens": tok.encode(
        r["prompt"], bos=True, eos=False)[1:] + [5] * r["output_tokens"]}
        for r in reqs]
    rows, error = drv.served_rows(good, plan, tok)
    assert error is None and [n for _, n, _ in rows] == [
        r["prompt_tokens"] for r in reqs]
    assert all(len(row) == n + len(served) - 1 for row, n, served in rows)
    _, error = drv.served_rows(good[:-1], plan, tok)
    assert error == "no record"
    bad = [dict(good[0], ok=False, error="reset")] + good[1:]
    assert drv.served_rows(bad, plan, tok)[1] == "reset"
    bad = [dict(good[0], tokens=[9] + good[0]["tokens"][1:])] + good[1:]
    assert "echo" in drv.served_rows(bad, plan, tok)[1]


def test_shortfalls_by_hand():
    from benchmark.drivers import serve_retention as drv

    want = np.asarray([[0.0, 2.0, 1.5], [3.0, -1.0, 3.0]], np.float32)
    assert drv.shortfalls(want, [1, 2]).tolist() == [0.0, 0.0]
    assert drv.shortfalls(want, [2, 1]).tolist() == [0.5, 4.0]


def _op(name, lo, hi, kind="custom-call"):
    return reduce_trace.Op(name, kind, float(lo), float(hi))


def test_trace_readers_on_a_made_up_step():
    """One layer and the classifier: wqkv, [norm, rope, phi, the kernel,
    the division], wo, w13, w2, wcls."""
    ops = [_op("while.1", 0, 100, "while"),
           _op("fusion.0", 0, 2, "fusion"),            # rms_att: before wqkv
           _op("_q40_mxu_nb_stacked.1", 2, 10),
           _op("fusion.1", 10, 14, "fusion"),          # norms, rope, phi
           _op("retention_decode_step.1", 14, 60),
           _op("fusion.2", 60, 62, "fusion"),          # / normaliser
           _op("_q40_mxu_nb_stacked.2", 62, 70),
           _op("_q40_mxu_nb_stacked.3", 70, 80),
           _op("_q40_mxu_nb_stacked.4", 80, 90),
           _op("_q40_mxu_nb_2d.1", 90, 100)]
    assert reduce_trace.classify(ops[4]) == "custom"
    assert retention.retention_block_seconds(ops) == pytest.approx(52e-9)
    assert retention.kernel_calls(ops, retention.DECODE_KERNEL) == [
        pytest.approx(46e-9)]
    spans = [_op("serve.step", 0, 110, "host")]
    mods = [_op("jit_serve_decode_step", 0, 100, "module")]
    dev = "/device:TPU:0"
    tr = reduce_trace.Trace({dev: ops}, spans, window=(0, 110),
                            modules={dev: mods})
    assert retention.decode_step_kernel_seconds(tr) == [pytest.approx(46e-9)]
    # a step whose program held an admission chunk is left out
    mixed = ops[:5] + [_op("retention_prefill_chunk.1", 60, 61)] + ops[6:]
    tr2 = reduce_trace.Trace({dev: mixed}, spans, window=(0, 110),
                             modules={dev: mods})
    assert retention.decode_step_kernel_seconds(tr2) == []
    assert retention.kernel_calls(mixed, retention.CHUNK_KERNEL) == [
        pytest.approx(1e-9)]
    # a trace without either kernel: nothing to read
    dense = [o for o in ops if not o.name.startswith("retention")]
    assert retention.retention_block_seconds(dense) == 0
    # the readers, on the made-up trace
    from benchmark.harness import runtime

    cell = cells.load_cell(CELL, ROOT)
    run = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                      records=[], device={"kind": "TPU v5 lite"},
                      counters_before={"steps": 0},
                      counters_after={"steps": 5}, trace=tr)
    share = cells.load_reader("layer_metrics", NEW[0]).read(run)
    assert share == pytest.approx(
        100 * retention.state_step_bytes(BRUMBY, 16) / 46e-9 / 819e9)
    assert cells.load_reader("layer_metrics", NEW[1]).read(run) is None
    assert cells.load_reader("layer_metrics", NEW[2]).read(run) == \
        pytest.approx(52.0)
    run2 = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                       records=[], device={"kind": "TPU v5 lite"},
                       counters_before={}, counters_after={}, trace=tr2)
    assert cells.load_reader("layer_metrics", NEW[1]).read(run2) == \
        pytest.approx(100 * retention.chunk_kernel_flops(BRUMBY, 128)
                      / 1e-9 / 197e12)


def test_readers_return_nothing_without_the_programs_kernels():
    """On the parent commit the trace holds no retention kernel (and an
    untraced run has no trace): every new reader returns None."""
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
    assert (t["entry"], t["loop"], t["clients"]) == ("serve_retention",
                                                     "closed", 32)
    assert sum(int(k) * v for k, v in t["output_tokens"].items()) == \
        pytest.approx(384)
    gen = cells.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                       "gen-sat16.json"))
    assert (t["prompt_tokens"], t["output_tokens"]) == (
        gen["prompt_tokens"], gen["output_tokens"])
    assert (t["trace_seconds"], t["trace_start_s"]) == (4, 10)
    flags = cell.config["entries"]["serve"]
    assert flags == {"slots": 16, "prefill_chunk": 128}
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert "pages_used_share" not in names
    assert set(NEW) | {"sat_q40_hbm_share", "compiles_in_window",
                       "sat_decode_step_ms_p50",
                       "sat_admission_device_share"} <= names
    doc = cells.load_benchmark(ROOT)
    entry = next(c for c in doc["configs"] if c["name"] == "brumby-14b-q40")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cell.name == doc["workloads"][-1]["name"]


def test_the_driver_stops_at_once_on_a_program_without_the_fields(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    class Old:
        def __init__(self, dim, hidden_dim, n_layers, n_heads, n_kv_heads,
                     vocab_size, seq_len, weights_float_type=0,
                     buffer_float_type=0, n_experts=0, n_active_experts=0,
                     qk_norm=False):
            pass

    monkeypatch.setattr(spec_mod, "TransformerSpec", Old)
    with pytest.raises(TypeError, match="unexpected keyword"):
        retention.program_spec(BRUMBY)


CASE = ("throwaway.ret-gen-sat", "tiny-retention", "tiny-ret-gen-sat", 1,
        CELL)


def test_rehearsal_1_the_retention_driver_end_to_end(tmp_path):
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
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(NEW) & set(got)
