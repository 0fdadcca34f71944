"""The mixer-kinds configuration's part of the benchmark, CPU only (run with
the rest of ``benchmark/tests``): the byte counts of ``harness/laguna.py``
against the shapes and ISSUE 44's table, the seeded tree, the benchmark's
copy of the reference against the program's, the trace readers on a made-up
trace, the cell as the issue names it, and the laguna serve driver end to end
at a toy width in a temporary copy that adds a throw-away cell."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, laguna, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "laguna-xs2-q40.json"))
XS2 = laguna.sizes_of(CONFIG)
TINY = laguna.sizes_of(cells.load_json(os.path.join(HERE,
                                                    "tiny-laguna.json")))
CELL = "laguna.mix-sat32"
NEW = ("lag_ring_attn_roofline", "lag_paged_attn_roofline",
       "lag_expert_roofline", "lag_dense_q40_roofline",
       "lag_sliding_device_time_share", "lag_full_device_time_share",
       "lag_moe_device_time_share")


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            row = json.loads(line)
            if row["name"] == "Laguna-XS.2":
                return row
    pytest.skip("the catalog has no Laguna-XS.2 row")


def test_every_published_key_is_in_the_file_and_no_width_is_cut():
    row = _catalog()
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == value, key
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "max_position_embeddings"}
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert CONFIG[key] == row["config"][key][:16], key
    assert set(CONFIG["assumed"]) >= {"gate_form", "router_scoring",
                                      "norm_topk_prob", "qk_norm", "window",
                                      "rotary_form", "tensor_names"}


def test_published_sizes_and_bytes_by_hand():
    laguna.check_runnable(CONFIG)
    kinds = laguna.kinds_of(XS2)
    assert (kinds.count("full"), kinds.count("sliding")) == (4, 12)
    assert [i for i, k in enumerate(kinds) if k == "full"] == [0, 4, 8, 12]
    assert (XS2["full_heads"], XS2["sliding_heads"], XS2["full_rotary"],
            XS2["sliding_rotary"], XS2["window"]) == (48, 64, 64, 128, 512)
    assert XS2["seq_len"] == 5120 >= 4032 + 1008
    # ISSUE 44's table: one expert's three leaves, a position's K and V in
    # one layer, a page over the four full layers, the rings of 32 slots
    assert laguna.expert_bytes(XS2) == 1769472
    assert laguna.kv_position_bytes(XS2) == 8192
    assert 16 * laguna.kv_position_bytes(XS2) * 4 == 524288
    assert laguna.ring_step_bytes(XS2, 32 * 512) == 12 * 32 * 512 * 8192
    assert round(laguna.ring_step_bytes(XS2, 32 * 512) / 1e9, 3) == 1.611
    assert round(laguna.full_step_bytes(XS2, 32 * 1500) / 1e9, 2) == 1.57
    # attention 0.321 GB, the dense FFN 0.028, shared experts 0.027, the
    # classifier 0.116
    assert round(laguna.dense_q40_bytes(XS2) / 1e9, 3) == 0.491
    spec = laguna.program_spec(XS2)
    assert spec.header_version == 7 and spec.mixers.kinds == kinds
    assert round(spec.file_size() / 1e9, 2) == 8.15
    from distributed_llama_tpu.analysis import memory_model as mm

    assert mm.kv_page_bytes(spec, 1, 16) == 524288
    assert mm.state_slot_bytes(spec) * 32 == 12 * 32 * 512 * 8192


def _leaves(v):
    return list(v) if isinstance(v, tuple) else [v]


def _stacks(tree):
    return [("", {k: v for k, v in tree.items() if not isinstance(v, dict)}),
            *sorted((k, v) for k, v in tree.items() if isinstance(v, dict))]


def test_tree_is_seeded_whatever_the_thread_count_and_loads():
    a = laguna.codec_tree(TINY, 5, threads=1)
    b = laguna.codec_tree(TINY, 5, threads=7)
    c = laguna.codec_tree(TINY, 6)
    from distributed_llama_tpu.models.synth import synth_params

    own = synth_params(laguna.program_spec(TINY), q40=True, seed=1)
    assert set(own) == set(a)
    for (name, sa), (_, sb), (_, sc), (_, so) in zip(
            _stacks(a), _stacks(b), _stacks(c), _stacks(own)):
        assert set(sa) == set(so), name
        for k in sa:
            for x, y, z, o in zip(_leaves(sa[k]), _leaves(sb[k]),
                                  _leaves(sc[k]), _leaves(so[k])):
                assert np.array_equal(x, y), (name, k)
                assert not np.array_equal(x, z), (name, k)
                assert x.shape == o.shape and x.dtype == o.dtype, (name, k)
    from benchmark.harness import weights

    assert not a["wcls"].d16[weights.BOS].any()


def test_the_two_references_agree():
    """The benchmark's layer-at-a-time copy and the program's
    ``models/reference_laguna.py`` are written apart and give the same
    logits and margins; one precision down they do not."""
    from distributed_llama_tpu.models import reference_laguna

    tree = laguna.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 40))
    got, margins = laguna.logits(tree, TINY, tokens, vocab_blocks=3,
                                 precisions=("highest", "bfloat16"))
    spec = laguna.program_spec(TINY)
    for b in range(2):
        want, m, _ = reference_laguna.forward(tree, spec, tokens[b])
        assert np.abs(got["highest"][b] - want).max() < 1e-4
        assert np.abs(margins[b] - m).max() < 1e-5
        assert np.abs(got["bfloat16"][b] - want).max() > 1e-2
    keep = np.asarray([[3, 39], [0, 17]])
    part, _ = laguna.logits(tree, TINY, tokens, keep=keep)
    assert np.abs(part["highest"][1, 1] - got["highest"][1, 17]).max() < 1e-5


def _every_expert_on_every_position(tree, sizes, h, ids, w):
    """sum_e w_e E_e(h) the long way, float64, from the codec leaves."""
    from benchmark.harness.reference import _dequant

    out = np.zeros(h.shape, np.float64)
    dense = np.zeros(h.shape[:2] + (sizes["n_experts"],), np.float64)
    np.put_along_axis(dense, ids, w, axis=-1)
    for e in range(sizes["n_experts"]):
        a, b, c = (np.asarray(_dequant(np, tree[k].qs[0, e],
                                       tree[k].d16[0, e]), np.float64)
                   for k in ("moe_w1", "moe_w2", "moe_w3"))
        g = h @ a.T
        out += dense[..., e:e + 1] * ((g / (1 + np.exp(-g)) * (h @ c.T))
                                      @ b.T)
    return out


def test_an_expert_runs_on_the_positions_that_chose_it():
    """``route`` chooses as ``jax.lax.top_k`` does (ties to the lower
    index), reverses the decision it is told to and weighs padding nothing;
    ``expert_blocks`` holds every live pair once, an expert's pairs in
    blocks of its own, experts and positions in order, in a count of blocks
    that follows from the shape; ``_experts`` on those blocks gives what
    every expert on every position gives; and a row padded past its length
    reads as it does alone."""
    import jax

    rng = np.random.default_rng(4)
    n_exp, k = TINY["n_experts"], TINY["n_active_experts"]
    scores = rng.random((2, 48, n_exp), dtype=np.float32)
    scores[0, 7] = scores[0, 7, 0]                  # every score a tie
    live = np.arange(48)[None] < np.asarray([48, 20])[:, None]
    flip = np.zeros((2, 48), bool)
    flip[0, 3] = True
    ids, w, margin = laguna.route(TINY, scores, flip, live)
    top, want = jax.lax.top_k(scores, k + 1)
    want = np.asarray(want)
    assert np.array_equal(ids[~flip], want[~flip][:, :k])
    assert list(ids[0, 7]) == list(range(k)) and margin[0, 7] == 0
    assert ids[0, 3, k - 1] == want[0, 3, k] and (
        ids[0, 3, :k - 1] == want[0, 3, :k - 1]).all()
    assert np.allclose(margin, np.asarray(top)[..., k - 1]
                       - np.asarray(top)[..., k])
    assert np.allclose(w[live].sum(-1), TINY["route_scale"], rtol=1e-6)
    assert not w[~live].any() and w.dtype == np.float32
    for rows in (4, 64):
        used, expert, at, we = laguna.expert_blocks(ids, w, live, n_exp,
                                                    rows)
        assert len(expert) == 2 * 48 * k // rows + n_exp
        real = at < 2 * 48
        assert real[:used].any(-1).all() and not real[used:].any()
        assert real.sum() == live.sum() * k and not we[~real].any()
        assert (np.diff(expert[real.any(-1)]) >= 0).all()
        assert all(len(set(r)) == rows for r in at.tolist())
        assert (at[~real] == (2 * 48 + np.nonzero(~real)[1])).all()
        got = sorted(zip(at[real].tolist(), np.broadcast_to(
            expert[:, None], at.shape)[real].tolist(), we[real].tolist()))
        pos, slot = np.nonzero(live.reshape(-1))[0], np.arange(k)
        assert got == sorted(
            (int(p), int(ids.reshape(-1, k)[p, j]),
             float(w.reshape(-1, k)[p, j])) for p in pos for j in slot)
    tree = laguna.codec_tree(TINY, 5)
    h = rng.standard_normal((2, 48, TINY["dim"]), dtype=np.float32)
    x = rng.standard_normal((2, 48, TINY["dim"]), dtype=np.float32)
    held = [(jax.numpy.asarray(tree[k_].qs[0]),
             jax.numpy.asarray(tree[k_].d16[0]))
            for k_ in ("moe_w1", "moe_w2", "moe_w3")]
    out = np.asarray(jax.jit(laguna._experts, static_argnums=0)(
        False, x, h, *laguna.expert_blocks(ids, w, live, n_exp, 4), *held))
    assert np.abs(out - x - _every_expert_on_every_position(
        tree, TINY, h, ids, w)).max() < 1e-5
    tokens = np.random.default_rng(2).integers(3, 512, (2, 96))
    whole, _ = laguna.logits(tree, TINY, tokens)
    padded = tokens.copy()
    padded[1, 40:] = 0
    cut, _ = laguna.logits(tree, TINY, padded, lengths=[96, 40])
    assert np.abs(cut["highest"][0] - whole["highest"][0]).max() < 1e-5
    assert np.abs(cut["highest"][1, :40] - whole["highest"][1, :40]
                  ).max() < 1e-5


def test_settling_gives_the_shared_positions_a_wide_margin():
    tree = laguna.codec_tree(TINY, 11)
    shared = [1, 5]
    laguna.settle_shared_positions(tree, TINY, shared, 11)
    _, margins = laguna.logits(tree, TINY, np.asarray([shared]))
    assert margins.min() >= laguna.SHARED_MARGIN


def _op(name, lo, hi, kind="custom-call"):
    return th._op(name, lo, hi, kind)


def _made_up_trace(chunk: bool = False):
    """One forward of the published depth: per layer wqkv, an attention
    kernel (or a chunk's fusion), wo, then the FFN (layer 0: w13, w2; the
    others: a fusion, two expert kernel calls, sh_w13, sh_w2), and the
    classifier's call at the end of a decode step."""
    ops, t = [], 0

    def add(name, dur, kind="custom-call"):
        nonlocal t
        ops.append(_op(name, t, t + dur, kind))
        t += dur

    for layer, kind in enumerate(laguna.kinds_of(XS2)):
        add("_q40_mxu_nb_stacked.1", 10)
        if chunk:
            add("fusion.7", 4, "fusion")
        else:
            add(("hm_attn_rows_decode" if kind == "sliding"
                 else "hm_attn_paged_decode") + ".2", 20 if kind == "sliding"
                else 30)
        add("_q40_mxu_nb_stacked.3", 5)
        if layer:
            add("fusion.9", 1, "fusion")
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".4", 40)
            add(("moe_q40_grouped" if chunk else "moe_q40_slots") + ".5", 20)
        add("_q40_mxu_nb_stacked.6", 3)
        add("_q40_mxu_nb_stacked.7", 2)
    if not chunk:
        add("_q40_mxu_nb_2d.8", 7)
    return ops, t


def test_trace_readers_on_a_made_up_trace():
    dev = "/device:TPU:0"
    ops, end = _made_up_trace()
    tr = reduce_trace.Trace(
        {dev: ops}, [_op("serve.step", 0, end + 10, "host")],
        window=(0, end + 10),
        modules={dev: [_op("jit_serve_decode_step", 0, end, "module")]})
    (step,) = laguna.step_kernel_seconds(tr)
    assert step["ring"] == pytest.approx(12 * 20e-9)
    assert step["paged"] == pytest.approx(4 * 30e-9)
    assert step["slots"] == pytest.approx(15 * 60e-9)
    assert step["dense"] == pytest.approx((16 * 20 + 7) * 1e-9)
    blocks = laguna.block_seconds(tr, XS2)
    assert blocks["sliding"] == pytest.approx(12 * 35e-9)
    assert blocks["full"] == pytest.approx(4 * 45e-9)
    assert blocks["moe"] == pytest.approx(15 * 66e-9)
    from benchmark.harness import runtime

    before = {"steps": 0, "trace_steps": 0, "trace_shared_kv_positions": 0,
              "trace_window_kv_positions": 0, "trace_moe_active": 0}
    after = {"steps": 99, "trace_steps": 10,
             "trace_shared_kv_positions": 10 * 32 * 1500,
             "trace_window_kv_positions": 10 * 32 * 512,
             "trace_moe_active": 10 * 2445}
    run = runtime.Run(cell=cells.load_cell(CELL, ROOT), seed=1, window_s=1.0,
                      setup_s=1.0, records=[],
                      device={"kind": "TPU v5 lite"}, counters_before=before,
                      counters_after=after, trace=tr)
    read = lambda n: cells.load_reader("layer_metrics", n).read(run)  # noqa
    assert read(NEW[0]) == pytest.approx(
        100 * laguna.ring_step_bytes(XS2, 32 * 512) / 240e-9 / 819e9)
    assert read(NEW[1]) == pytest.approx(
        100 * laguna.full_step_bytes(XS2, 32 * 1500) / 120e-9 / 819e9)
    assert read(NEW[2]) == pytest.approx(
        100 * 2445 * 1769472 / 900e-9 / 819e9)
    assert read(NEW[3]) == pytest.approx(
        100 * laguna.dense_q40_bytes(XS2) / 327e-9 / 819e9)
    busy = reduce_trace.busy(tr)["busy_s"][dev]
    assert read(NEW[4]) == pytest.approx(100 * 12 * 35e-9 / busy)
    assert read(NEW[5]) == pytest.approx(100 * 4 * 45e-9 / busy)
    assert read(NEW[6]) == pytest.approx(100 * 15 * 66e-9 / busy)
    cops, cend = _made_up_trace(chunk=True)
    tr2 = reduce_trace.Trace(
        {dev: cops}, [], window=(0, cend),
        modules={dev: [_op("jit_serve_admit_prefill_chunk", 0, cend,
                           "module")]})
    assert laguna.step_kernel_seconds(tr2) == []    # no decode step
    chunk = laguna.block_seconds(tr2, XS2)
    assert chunk["sliding"] == pytest.approx(12 * 19e-9)
    assert chunk["moe"] == pytest.approx(15 * 66e-9)


def test_readers_return_nothing_without_the_programs_kernels():
    """On a program without the kernels or the counters (and in an untraced
    run): every new reader returns None."""
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
            t["max_requests_per_client_per_s"]) == ("serve_laguna", "closed",
                                                    64, 1.0)
    assert t["prompt_tokens"] == {"64": 0.2, "192": 0.25, "448": 0.25,
                                  "2048": 0.2, "4032": 0.1}
    assert t["output_tokens"] == {"112": 0.2, "240": 0.3, "400": 0.25,
                                  "656": 0.15, "1008": 0.1}
    assert sum(int(k) * v for k, v in t["prompt_tokens"].items()) == \
        pytest.approx(986, abs=1)
    assert sum(int(k) * v for k, v in t["output_tokens"].items()) == \
        pytest.approx(394, abs=1)
    assert (t["trace_seconds"], t["trace_start_s"], t["temperature"],
            t["stream"]) == (4, 15, 0, True)
    flags = cell.config["entries"]["serve"]
    assert (flags["slots"], flags["kv_page_size"]) == (32, 16)
    assert flags["kv_pages"] >= 4096
    assert flags["prefill_chunk"] in (128, 256, 512)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"compiles_in_window", "sat_decode_step_ms_p50",
                       "sat_rows_per_dispatch", "pages_used_share",
                       "moe_rows_per_active_expert",
                       "moe_load_max_over_mean",
                       "sat_admission_device_share"} <= names
    doc = cells.load_benchmark(ROOT)
    assert len(doc["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]


def test_the_driver_stops_at_once_on_a_program_without_the_record(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "MixerKinds")
    with pytest.raises(ImportError, match="MixerKinds"):
        laguna.program_spec(XS2)


def test_a_reversed_decision_is_found_and_cured():
    """A stream picked from the reference with ONE near-tied decision taken
    the other way fails the rules against the plain reference, and
    ``with_reversals`` finds that decision and cures the row; a margin over
    ``REVERSAL_EPSILON`` is never reversed."""
    import functools

    from benchmark.drivers import serve_laguna

    tree = laguna.codec_tree(TINY, 3)
    tokens = np.random.default_rng(4).integers(3, 512, (1, 60))
    keep = np.arange(20, 60)[None]
    got, margins = laguna.logits(tree, TINY, tokens, keep=keep)
    plain = got["highest"][0].argmax(-1)
    for t, layer in ((t, layer) for t in range(20, 30) for layer in (0, 3)):
        flipped, _ = laguna.logits(tree, TINY, tokens, keep=keep,
                                   flips=[(0, t, layer)])
        served = flipped["highest"][0].argmax(-1)
        if (plain != served).any():     # this decision moves a pick
            break
    else:
        pytest.fail("no single decision of the toy moves a pick")
    lot = [(list(tokens[0]), 21, list(served))]
    bad = functools.partial(serve_laguna._first_bad, lot, [0], 1e-6, 0.01,
                            [served])
    assert bad(0, got["highest"][0]) is not None
    want, m = got["highest"].copy(), margins.copy()
    assert laguna.with_reversals(tree, TINY, tokens, keep, want, m,
                                 bad) == []          # the margin is wide
    m[0, t, layer] = 1e-7                            # ... and now a near-tie
    stood = laguna.with_reversals(tree, TINY, tokens, keep, want, m, bad)
    assert [(b, tt, ll) for b, tt, ll, _ in stood] == [(0, t, layer)]
    assert bad(0, want[0]) is None
    assert laguna.decisions_to_reverse(margins[0], 59) is None


def test_a_planted_fault_is_not_cured_by_reversals():
    """A stream served over a STALE cache entry (position 10 holds another
    token's K / V: what a ring slot or a page id gone wrong gives every
    later position) fails the rules, and stays failed through
    ``with_reversals`` even with EVERY decision of the row declared a
    near-tie: reversing decisions moves its first disagreement at most
    past a position or two, never past the share rule."""
    import functools

    from benchmark.drivers import serve_laguna

    tree = laguna.codec_tree(TINY, 3)
    tokens = np.random.default_rng(4).integers(3, 512, (1, 60))
    keep = np.arange(20, 60)[None]
    stale = tokens.copy()
    stale[0, 10] = (stale[0, 10] + 7) % 512
    served = laguna.logits(tree, TINY, stale, keep=keep)[0]["highest"][
        0].argmax(-1)
    got, margins = laguna.logits(tree, TINY, tokens, keep=keep)
    assert (got["highest"][0].argmax(-1) != served).mean() > 0.15
    lot = [(list(tokens[0]), 21, list(served))]
    for n_strict in (40, 0):       # strictly compared, and judged by share
        bad = functools.partial(serve_laguna._first_bad, lot, [n_strict],
                                2e-3, 0.15, [served])
        want, m = got["highest"].copy(), np.full_like(margins, 1e-7)
        stood = laguna.with_reversals(tree, TINY, tokens, keep, want, m, bad)
        assert bad(0, want[0]) is not None
        assert len(stood) <= laguna.MAX_REVERSALS


def test_the_window_plan_keeps_one_seeds_shapes_and_draws_the_texts():
    """``shapes_seed`` in the traffic file: every run's window holds the
    same shapes in the same order (that seed's), the run's seed draws the
    texts."""
    from benchmark.drivers import serve_laguna
    from benchmark.harness import traffic

    mix = cells.load_cell(CELL).traffic
    assert mix["shapes_seed"] == 1377002918
    assert (mix["first_wave"], mix["window_end"]) == ("whole_mix",
                                                      "cut_by_client")
    a, b = (serve_laguna.window_plan(mix, seed, 40.0)
            for seed in (11, 2**31 + 77))

    def shapes(plan):
        return [[(r["id"], r["prompt_tokens"], r["output_tokens"])
                 for r in c] for c in plan["clients"]]

    assert shapes(a) == shapes(b) == shapes(
        traffic.generate(mix, mix["shapes_seed"], 40.0))
    reqs_a, reqs_b = ([r for c in p["clients"] for r in c] for p in (a, b))
    assert all(len(r["prompt"]) + traffic.PROMPT_OVERHEAD
               == r["prompt_tokens"] for r in reqs_a + reqs_b)
    assert sum(x["prompt"] == y["prompt"]
               for x, y in zip(reqs_a, reqs_b)) == 0
    assert serve_laguna.window_plan(mix, 11, 40.0) == a


def test_a_request_that_ended_as_the_window_was_cut_did_not_fail():
    """Every token and the done line delivered, then the cutter shut the
    socket under the client's last read: ok. Anything less stays failed."""
    from benchmark.drivers import serve_laguna

    rec = {"id": 7, "ok": False, "cut": False, "done": 40.1,
           "stamps": [1.0, 2.0, 3.0], "output_tokens": 3,
           "error": "IncompleteRead: IncompleteRead(0 bytes read)"}
    assert serve_laguna.ended_at_the_cut(dict(rec)) == dict(
        rec, ok=True, error=None)
    for change in ({"done": None}, {"stamps": [1.0, 2.0]},
                   {"error": "stream ended without a done line"},
                   {"error": "3 sampled tokens, not 4 (ended early)",
                    "output_tokens": 4}):
        other = dict(rec, **change)
        assert serve_laguna.ended_at_the_cut(dict(other)) == other
    fine = dict(rec, ok=True, error=None)
    assert serve_laguna.ended_at_the_cut(dict(fine)) == fine


def test_check_requests_and_their_lots():
    from benchmark.drivers import serve_laguna

    plan = serve_laguna.check_requests(
        7, 32, CONFIG["check"]["long_requests"],
        {"64": 0.2, "192": 0.25, "448": 0.25, "2048": 0.2, "4032": 0.1})
    reqs = [r for c in plan["clients"] for r in c]
    assert len(reqs) == 64 > 32 and len({r["prompt"][0] for r in reqs}) == 64
    assert [r["prompt_tokens"] for r in reqs[:3]] == [4032, 2048, 2048]
    assert min(r["prompt_tokens"] for r in reqs) == 64
    rows = [([0] * (r["prompt_tokens"] + r["output_tokens"] - 1),
             r["prompt_tokens"], [0] * r["output_tokens"]) for r in reqs]
    lots = serve_laguna._lots(rows)
    assert [w for _, w in lots[:3]] == [5120, 5120, 5120]
    assert {w for _, w in lots[3:]} == {512, 1024}
    assert all(len(p) == 1 for p, w in lots if w > serve_laguna.LONG)
    assert sorted(i for p, _ in lots for i in p) == list(range(64))


CASE = ("throwaway.swa-mix", "tiny-laguna", "tiny-swa-mix-sat", 1, CELL)


def test_rehearsal_1_the_laguna_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "every served position" in proc.stderr
    assert "per-head gate over the window" in proc.stderr
    assert "cut by their clients" in proc.stderr
    traced = th._last_line(th._run(root, CASE[0], trace=1))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    assert got["moe_rows_per_active_expert"]["value"] >= 1.0
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(NEW) & set(got)
