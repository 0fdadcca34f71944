"""The expert configuration's part of the benchmark, CPU only (run with the
rest of ``benchmark/tests``): the byte counts of ``harness/olmoe.py``
against the shapes, the seeded tree, the benchmark's copy of the reference
against the program's, the trace readers on a made-up trace, and the expert
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

from benchmark.harness import cells, olmoe, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

OLMOE = olmoe.sizes_of(cells.load_json(os.path.join(
    ROOT, "benchmark", "configs", "olmoe-1b-7b-q40.json")))
TINY = olmoe.sizes_of(cells.load_json(os.path.join(HERE, "tiny-olmoe.json")))


def test_published_sizes_and_bytes_by_hand():
    assert OLMOE == {"dim": 2048, "hidden_dim": 1024, "n_layers": 16,
                     "n_heads": 16, "n_kv_heads": 16, "vocab_size": 50304,
                     "seq_len": 4096, "n_experts": 64, "n_active_experts": 8,
                     "qk_norm": True}
    one = 3 * 2048 * 1024 // 32 * 18            # w1, w2, w3 of one expert
    assert olmoe.expert_bytes(OLMOE) == one == 3_538_944
    dense = (16 * 4 * 2048 * 2048 + 50304 * 2048) // 32 * 18
    assert olmoe.dense_q40_bytes(OLMOE) == dense
    assert round(64 * 16 * one / 1e9, 2) == 3.62       # the experts
    assert round((64 * 16 * one + dense) / 1e9, 2) == 3.83
    # the program's own accounting agrees (file bytes of the matmul leaves)
    spec = olmoe.program_spec(OLMOE)
    leaves = spec.n_layers * sum(
        c * spec.matmul_bytes(s) for s, c in spec.matmul_shape_counts()
    ) + spec.matmul_bytes((spec.vocab_size, spec.dim))
    assert leaves == 64 * 16 * one + dense
    # a 16-row step at the expected 56 distinct experts a layer
    step = olmoe.step_bytes(OLMOE, 56 * 16, rows=16, context=340)
    assert round(56 * 16 * one / 1e9, 2) == 3.17
    assert 4.5e9 < step < 5.2e9


def test_tree_is_seeded_whatever_the_thread_count_and_loads():
    a = olmoe.codec_tree(TINY, 5, threads=1)
    b = olmoe.codec_tree(TINY, 5, threads=7)
    c = olmoe.codec_tree(TINY, 6)

    def leaves(v):
        return list(v) if isinstance(v, tuple) else [v]

    assert set(a) == {"tok_embedding", "rms_att", "rms_ffn", "rms_final",
                      "rms_q", "rms_k", "wcls", "wq", "wk", "wv", "wo",
                      "moe_gate", "moe_w1", "moe_w2", "moe_w3"}
    for k in a:
        for x, y, z in zip(leaves(a[k]), leaves(b[k]), leaves(c[k])):
            assert np.array_equal(x, y) and not np.array_equal(x, z)
    assert a["moe_w1"].qs.shape == (2, 8, 128, 8, 16)
    assert a["moe_w2"].d16.shape == (2, 8, 256, 4)
    assert not np.array_equal(a["moe_w1"].qs[0, 0], a["moe_w1"].qs[0, 1])
    assert abs(a["moe_gate"].std() * np.sqrt(256) - 1) < 0.05
    assert abs(a["rms_q"].mean() - 1) < 0.01


def test_the_two_references_agree():
    """The benchmark's layer-and-expert-at-a-time copy and the program's
    ``models/reference_olmoe.py`` are written apart and give the same
    logits and the same margins."""
    from distributed_llama_tpu.models import reference_olmoe

    tree = olmoe.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 24))
    got, margins = olmoe.logits(tree, TINY, tokens)
    spec = olmoe.program_spec(TINY)
    for b in range(2):
        want, m, _ = reference_olmoe.forward(tree, spec, tokens[b])
        n = reference_olmoe.compared_positions(m, olmoe.MARGIN_EPSILON)
        assert n == olmoe.compared_positions(margins[b]) >= 16
        assert np.abs(got[b, :n] - want[:n]).max() < 5e-5
        assert np.allclose(margins[b], m.min(axis=1), atol=1e-6)


def _op(name, lo, hi, kind="custom-call"):
    return reduce_trace.Op(name, kind, float(lo), float(hi))


def test_trace_readers_on_a_made_up_step():
    """One layer and the classifier: wqkv, attention, wo, [norm, router,
    slots, experts, combine], wcls."""
    ops = [_op("while.1", 0, 100, "while"),
           _op("_q40_mxu_nb_stacked.1", 0, 10),
           _op("paged_decode_attention_kernel.1", 10, 20),
           _op("_q40_mxu_nb_stacked.2", 20, 30),
           _op("fusion.1", 30, 35, "fusion"),          # norm, router
           _op("moe_q40_slots.1", 35, 60),
           _op("fusion.2", 60, 62, "fusion"),          # silu
           _op("moe_q40_slots.2", 62, 80),
           _op("fusion.3", 80, 85, "fusion"),          # combine
           _op("_q40_mxu_nb_2d.1", 90, 100)]
    assert reduce_trace.classify(ops[5]) == "q40"
    assert olmoe.moe_block_seconds(ops) == pytest.approx(55e-9)
    spans = [_op("serve.step", 0, 110, "host")]
    mods = [_op("jit_serve_decode_step", 0, 100, "module")]
    tr = reduce_trace.Trace({"/device:TPU:0": ops}, spans, window=(0, 110),
                            modules={"/device:TPU:0": mods})
    assert olmoe.decode_step_kernel_seconds(tr) == [
        (pytest.approx(43e-9), pytest.approx(30e-9))]
    # a dense model's trace has no expert kernel: nothing to read
    dense = [o for o in ops if not o.name.startswith("moe_")]
    assert olmoe.moe_block_seconds(dense) == 0
    tr2 = reduce_trace.Trace({"/device:TPU:0": dense}, spans,
                             window=(0, 110),
                             modules={"/device:TPU:0": mods})
    assert olmoe.decode_step_kernel_seconds(tr2) == []


def test_readers_return_nothing_without_the_programs_counters():
    """On the parent commit the program counts no routed experts and its
    trace holds no expert kernel: every new reader returns None."""
    from benchmark.harness import runtime

    cell = cells.load_cell("olmoe7b.gen-sat16", ROOT)
    run = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                      records=[], device={"kind": "TPU v5 lite"},
                      counters_before={"steps": 0}, counters_after={
                          "steps": 5},
                      trace=reduce_trace.Trace({}, [], window=(0, 1)))
    for name in ("moe_expert_hbm_share", "moe_device_time_share",
                 "moe_rows_per_active_expert", "moe_load_max_over_mean",
                 "moe_dense_q40_hbm_share"):
        assert cells.load_reader("layer_metrics", name).read(run) is None


def test_the_cell_is_what_the_issue_names():
    cell = cells.load_cell("olmoe7b.gen-sat16", ROOT)
    t = cell.traffic
    assert (t["entry"], t["loop"], t["clients"]) == ("serve_olmoe",
                                                     "closed", 32)
    assert sum(int(k) * v for k, v in t["output_tokens"].items()) == \
        pytest.approx(384)
    assert cell.config["entries"]["serve"]["slots"] == 16
    assert cell.config["reduced"] == []
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert "sat_q40_hbm_share" not in names
    assert {"moe_expert_hbm_share", "moe_device_time_share",
            "moe_rows_per_active_expert", "moe_load_max_over_mean",
            "moe_dense_q40_hbm_share", "compiles_in_window",
            "sat_decode_step_ms_p50"} <= names


CASE = ("throwaway.gen-sat", "tiny-olmoe", "tiny-gen-sat", 1,
        "olmoe7b.gen-sat16")


def test_rehearsal_1_the_expert_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    line = th._last_line(th._run(root, CASE[0], trace=0))
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    traced = th._last_line(th._run(root, CASE[0], trace=1))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    # the program's counters reach the readers; what needs a device trace
    # finds no expert kernel on the CPU and is left out
    assert 1.0 <= got["moe_rows_per_active_expert"]["value"] <= 4.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert "moe_expert_hbm_share" not in got
