"""The hybrid configuration's part of the benchmark, CPU only (run with the
rest of ``benchmark/tests``): the byte and operation counts of
``harness/hybrid.py`` against the shapes, the seeded tree, the benchmark's
copy of the reference against the program's, the check on streams of its own
making, the trace readers on a made-up trace, the cell as the issue names
it, and the hybrid serve driver end to end at a toy width in a temporary
copy that adds a throw-away cell."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, hybrid, reduce_trace  # noqa: E402
from benchmark.tests import test_harness as th  # noqa: E402

CONFIG = cells.load_json(os.path.join(ROOT, "benchmark", "configs",
                                      "phi4-mini-flash-q40.json"))
PHI = hybrid.sizes_of(CONFIG)
TINY = hybrid.sizes_of(cells.load_json(os.path.join(HERE,
                                                    "tiny-hybrid.json")))
CELL = "phi4flash.reason-sat32"
NEW = ("ssm_state_hbm_share", "swa_kv_hbm_share",
       "xkv_attn_hbm_share", "hyb_dense_q40_hbm_share",
       "ssm_device_time_share", "swa_device_time_share",
       "xdec_device_time_share", "xdec_positions_per_admission")
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}


def test_published_sizes_bytes_and_operations_by_hand():
    hybrid.check_runnable(CONFIG)
    # every key of the catalog's config at its published value, but the one
    # that is reduced
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["max_position_embeddings"]
    assert PHI["seq_len"] == 8704 >= 2560 + 6000
    assert (PHI["d_inner"], PHI["d_state"], PHI["dt_rank"],
            PHI["window"]) == (5120, 16, 160, 512)
    kinds = hybrid.kinds_of(32)
    assert [kinds.count(k) for k in hybrid.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    # the published "3.8B"
    count = 200064 * 2560 + sum(
        int(np.prod(shape)) for kind in kinds
        for _, _, shape in hybrid.layer_leaves(PHI, kind))
    assert 3.80e9 < count < 3.90e9
    assert hybrid.kv_position_bytes(PHI) == 10240
    assert 16 * hybrid.kv_position_bytes(PHI) == 163840      # a page
    assert hybrid.ssm_step_bytes(PHI, 32) == 32 * 9 * 2 * 16 * 5120 * 4
    # 32 rows at a mean depth of 1,400: 3.7 GB of the shared K / V a step
    assert round(hybrid.shared_kv_step_bytes(PHI, 32 * 1400) / 1e9, 1) == 3.7
    # 32 rows with full rings: 1.34 GB
    assert round(hybrid.window_step_bytes(PHI, 32 * 512) / 1e9, 2) == 1.34
    assert round(hybrid.dense_q40_bytes(PHI) / 1e9, 2) == 2.16
    spec = hybrid.program_spec(PHI)
    assert spec.header_version == 5 and spec.hybrid.kinds == kinds
    from distributed_llama_tpu.analysis import memory_model as mm

    assert mm.kv_page_bytes(spec, 1, 16) == 163840
    assert mm.state_slot_bytes(spec) * 32 == (
        hybrid.ssm_step_bytes(PHI, 32) // 2 + 32 * 9 * 3 * 5120 * 4
        + 32 * 8 * 512 * 10240)


def _leaves(v):
    return list(v) if isinstance(v, tuple) else [v]


def test_tree_is_seeded_whatever_the_thread_count_and_loads():
    a = hybrid.codec_tree(TINY, 5, threads=1)
    b = hybrid.codec_tree(TINY, 5, threads=7)
    c = hybrid.codec_tree(TINY, 6)
    assert set(a) == {"tok_embedding", "rms_final", "rms_final_b", "wcls",
                      *hybrid.KINDS}
    for kind in hybrid.KINDS:
        for k in a[kind]:
            for x, y, z in zip(_leaves(a[kind][k]), _leaves(b[kind][k]),
                               _leaves(c[kind][k])):
                assert np.array_equal(x, y), (kind, k)
                if k not in ("a_log", "dt_b", "d_skip"):
                    assert not np.array_equal(x, z), (kind, k)
    # tied, exactly, and the BOS row is zero
    from benchmark.harness import weights

    assert np.array_equal(a["tok_embedding"], weights.dequantize(
        a["wcls"].qs, a["wcls"].d16))
    assert not a["tok_embedding"][weights.BOS].any()
    # the program's loader contract: its own seeded tree has these leaves
    from distributed_llama_tpu.models.synth import synth_params

    own = synth_params(hybrid.program_spec(TINY), q40=True, seed=1)
    assert set(own) == set(a)
    for kind in hybrid.KINDS:
        assert set(own[kind]) == set(a[kind])
        for k in a[kind]:
            for x, y in zip(_leaves(a[kind][k]), _leaves(own[kind][k])):
                assert x.shape == y.shape and x.dtype == y.dtype, (kind, k)
    assert np.allclose(np.exp(a["mamba"]["a_log"][0, :, 0]),
                       np.arange(1, 17))
    dt = np.log1p(np.exp(a["mamba"]["dt_b"][0]))
    assert 0.9e-3 < dt.min() < 1.1e-3 and 0.09 < dt.max() < 0.11


def test_the_two_references_agree():
    """The benchmark's layer-at-a-time copy and the program's
    ``models/reference_sambay.py`` are written apart and give the same
    logits; one precision down they do not."""
    from distributed_llama_tpu.models import reference_sambay

    tree = hybrid.codec_tree(TINY, 3)
    tokens = np.random.default_rng(1).integers(3, 512, (2, 40))
    got = hybrid.logits(tree, TINY, tokens, vocab_blocks=3)
    spec = hybrid.program_spec(TINY)
    for b in range(2):
        want = reference_sambay.forward(tree, spec, tokens[b])
        assert np.abs(got[b] - want).max() < 1e-4
    low = hybrid.logits(tree, TINY, tokens, vocab_blocks=3,
                        precision="bfloat16")
    assert np.abs(low - got).max() > 5e-3
    keep = np.asarray([[3, 39], [0, 17]])
    part = hybrid.logits(tree, TINY, tokens, keep=keep)
    assert np.abs(part[1, 1] - got[1, 17]).max() < 1e-5


def test_the_check_is_made_at_the_windows_lengths():
    """More requests than slots, all arriving at once; the first eight at
    the window's own prompt lengths."""
    from benchmark.drivers import serve_hybrid as drv

    plan = drv.check_requests(7, 32)
    reqs = [r for c in plan["clients"] for r in c]
    assert (plan["loop"], len(plan["clients"]), len(reqs)) == (
        "closed", 40, 40)
    shapes = {r["id"]: (r["prompt_tokens"], r["output_tokens"])
              for r in reqs}
    assert tuple(shapes[i] for i in range(8)) == drv.CHECK_PROMPTS
    window = {int(k) for k in cells.load_cell(CELL, ROOT).traffic[
        "prompt_tokens"]}
    assert window <= {n for n, _ in drv.CHECK_PROMPTS}
    assert sum(n >= 128 for n, _ in drv.CHECK_PROMPTS) >= 6
    assert all(3 <= n <= 72 and 12 <= out <= 48
               for i, (n, out) in shapes.items() if i >= 8)
    heads = [r["prompt"][:4] for r in reqs if r["prompt_tokens"] >= 6]
    assert len(set(heads)) == len(heads)            # no shared prefix
    assert drv.check_requests(7, 32) == plan != drv.check_requests(8, 32)


def test_the_first_wave_is_the_mix_in_its_proportions():
    """Whatever the seed, the 32 clients that send first hold 6, 10, 10 and
    6 prompts of the four lengths: 236 chunks, 30,208 positions."""
    from benchmark.drivers import serve_hybrid as drv
    from benchmark.harness import traffic

    mix = cells.load_cell(CELL, ROOT).traffic
    seen = set()
    for seed in (1, 2, 3, 2147483900):
        plan = traffic.generate(mix, seed, 40)
        got = drv.whole_mix_first(plan, mix["prompt_tokens"], 32)
        firsts = [c[0]["prompt_tokens"] for c in got["clients"][:32]]
        assert sorted(firsts) == [128] * 6 + [384] * 10 + [1024] * 10 + [
            2560] * 6
        assert sum(-(-(n - 1) // 128) for n in firsts) == 236
        assert sorted(r["id"] for c in got["clients"] for r in c) == sorted(
            r["id"] for c in plan["clients"] for r in c)
        seen.add(tuple(firsts))
    assert len(seen) == 4                       # the seed sets the order
    few = {"loop": "closed", "clients": plan["clients"][:3]}
    assert drv.whole_mix_first(few, mix["prompt_tokens"], 32) is few


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
        nxt = hybrid.logits(tree, TINY, rows, precision=precision,
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
    control) come out NOT correct by the configuration's tolerance."""
    from benchmark.drivers import serve_hybrid as drv
    from benchmark.harness import model

    config = cells.load_json(os.path.join(HERE, "tiny-hybrid.json"))
    assert config["check"]["logit_tolerance"] == \
        CONFIG["check"]["logit_tolerance"]
    tree = hybrid.codec_tree(TINY, 11)
    tok = model.tokenizer(TINY["vocab_size"])
    plan = drv.check_requests(11, 0)
    plan["clients"] = plan["clients"][:6]
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
        assert d["control_bfloat16_max_shortfall"] > 2 * d["tolerance"]
        assert d["control_positions_over_tolerance"] >= 1
    else:
        assert d["max_logit_shortfall"] > 2 * d["tolerance"]


def _op(name, lo, hi, kind="custom-call"):
    return reduce_trace.Op(name, kind, float(lo), float(hi))


def _made_up_step(sizes, chunk=False):
    """A forward of ``sizes``'s model as the device shows it, 10 ns a dense
    call: a Mamba layer's in_proj, [fusion, the scan, fusion,] out_proj,
    w13, w2; an attention layer's first leaf, [fusion, the kernel,
    fusion,] wo, w13, w2; a GMU's in_proj, [fusion,] out_proj, w13, w2;
    the classifier. A chunk ends at the full layer's wqkv."""
    kinds = hybrid.kinds_of(sizes["n_layers"])
    ops, t = [], 0

    def add(name, dur, kind="custom-call"):
        nonlocal t
        ops.append(_op(name, t, t + dur, kind))
        t += dur

    for i, kind in enumerate(kinds):
        add("_q40_mxu_nb_stacked.1", 10)
        if chunk and kind == "full":
            break
        add("fusion.1", 1, "fusion")
        if kind == "mamba":
            add("mamba_prefill_chunk.3" if chunk else "mamba_decode_step.2",
                7)
        elif kind == "swa" and not chunk:
            add("hm_attn_rows_decode.4", 5)
        elif kind in ("full", "xattn"):
            add("hm_attn_paged_decode.5", 20)
        add("fusion.2", 1, "fusion")
        for _ in range(3):
            add("_q40_mxu_nb_stacked.2", 10)
    if not chunk:
        add("_q40_mxu_nb_2d.1", 10)
    return [_op("while.1", 0, t, "while")] + ops, t


def test_trace_readers_on_a_made_up_step():
    from benchmark.harness import runtime

    cell = cells.load_cell(CELL, ROOT)
    ops, end = _made_up_step(PHI)
    spans = [_op("serve.step", 0, end + 10, "host")]
    mods = [_op("jit_serve_decode_step", 0, end, "module")]
    dev = "/device:TPU:0"
    tr = reduce_trace.Trace({dev: ops}, spans, window=(0, end + 10),
                            modules={dev: mods})
    (step,) = hybrid.step_kernel_seconds(tr)
    assert step == {"ssm": pytest.approx(9 * 7e-9),
                    "window": pytest.approx(8 * 5e-9),
                    "paged": pytest.approx(8 * 20e-9),
                    "dense": pytest.approx(129 * 10e-9)}
    mix = hybrid.mixer_seconds(tr, PHI)
    assert mix == {"ssm": pytest.approx(9 * 29e-9),
                   "swa": pytest.approx(8 * 27e-9),
                   "xdec": pytest.approx(8 * 42e-9 + 7 * 22e-9)}
    before = {"steps": 0, "shared_kv_positions": 0, "window_kv_positions": 0,
              "xdec_positions": 0, "admit_prefills": 0}
    after = {"steps": 10, "shared_kv_positions": 10 * 32 * 1400,
             "window_kv_positions": 10 * 32 * 512, "xdec_positions": 32,
             "admit_prefills": 32}
    run = runtime.Run(cell=cell, seed=1, window_s=1.0, setup_s=1.0,
                      records=[], device={"kind": "TPU v5 lite"},
                      counters_before=before, counters_after=after, trace=tr)
    read = lambda n: cells.load_reader("layer_metrics", n).read(run)  # noqa
    assert read(NEW[0]) == pytest.approx(
        100 * hybrid.ssm_step_bytes(PHI, 32) / 63e-9 / 819e9)
    assert read(NEW[1]) == pytest.approx(
        100 * hybrid.window_step_bytes(PHI, 32 * 512) / 40e-9 / 819e9)
    assert read(NEW[2]) == pytest.approx(
        100 * hybrid.shared_kv_step_bytes(PHI, 32 * 1400) / 160e-9 / 819e9)
    assert read(NEW[3]) == pytest.approx(
        100 * hybrid.dense_q40_bytes(PHI) / 1290e-9 / 819e9)
    busy = reduce_trace.busy(tr)["busy_s"][dev]
    assert read(NEW[4]) == pytest.approx(100 * 9 * 29e-9 / busy)
    assert read(NEW[5]) == pytest.approx(100 * 8 * 27e-9 / busy)
    assert read(NEW[6]) == pytest.approx(100 * (8 * 42 + 7 * 22) * 1e-9
                                         / busy)
    assert read(NEW[7]) == 1.0
    # an admission chunk: the self-decoder alone, the scan's chunk kernel
    cops, cend = _made_up_step(PHI, chunk=True)
    tr2 = reduce_trace.Trace(
        {dev: cops}, [_op("serve.step", 0, cend + 10, "host")],
        window=(0, cend + 10),
        modules={dev: [_op("jit_serve_admit_prefill_chunk", 0, cend,
                           "module")]})
    assert hybrid.step_kernel_seconds(tr2) == []    # no decode step
    mix = hybrid.mixer_seconds(tr2, PHI)
    assert mix["xdec"] == 0 and mix["ssm"] == pytest.approx(9 * 29e-9)
    assert hybrid.kernel_calls(cops, hybrid.MAMBA_CHUNK) == [
        pytest.approx(7e-9)] * 9


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
    assert (t["entry"], t["loop"], t["clients"]) == ("serve_hybrid",
                                                     "closed", 64)
    assert t["prompt_tokens"] == {"128": 0.2, "384": 0.3, "1024": 0.3,
                                  "2560": 0.2}
    assert t["output_tokens"] == {"1100": 0.2, "2300": 0.3, "3700": 0.3,
                                  "6000": 0.2}
    assert sum(int(k) * v for k, v in t["prompt_tokens"].items()) == \
        pytest.approx(960, abs=1)
    assert sum(int(k) * v for k, v in t["output_tokens"].items()) == \
        pytest.approx(3220)
    assert (t["trace_seconds"], t["trace_start_s"], t["temperature"],
            t["stream"]) == (4, 20, 0, True)
    assert cell.config["entries"]["serve"] == {
        "slots": 32, "prefill_chunk": 128, "kv_page_size": 16,
        "kv_pages": 17152}
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"out_tokens_per_s",
                                                    "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"compiles_in_window", "sat_decode_step_ms_p50",
                       "sat_rows_per_dispatch", "pages_used_share",
                       "sat_admission_device_share"} <= names
    doc = cells.load_benchmark(ROOT)
    assert cell.name in [w["name"] for w in doc["workloads"]]
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    entry = next(c for c in doc["configs"]
                 if c["name"] == "phi4-mini-flash-q40")
    assert entry["reduced"] == ["max_position_embeddings"]
    for m in doc["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]


def test_the_driver_stops_at_once_on_a_program_without_the_fields(
        monkeypatch):
    """What the parent commit does with this cell: ``program_spec`` raises
    before any device is asked for."""
    from distributed_llama_tpu.models import spec as spec_mod

    monkeypatch.delattr(spec_mod, "HybridLayers")
    with pytest.raises(ImportError, match="HybridLayers"):
        hybrid.program_spec(PHI)


def test_the_client_cuts_what_outlasts_the_window():
    """``harness/cut_client.send`` against a stream that never ends: the
    record is cut at the window's end, ok, with the stamps it has."""
    import http.server
    import threading
    import time

    from benchmark.harness import cut_client

    class Endless(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            self.send_response(200)
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            try:
                while True:
                    body = b'{"token": 5}\n'
                    self.wfile.write(f"{len(body):x}\r\n".encode() + body
                                     + b"\r\n")
                    self.wfile.flush()
                    time.sleep(0.01)
            except OSError:
                pass

        def log_message(self, *a):
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Endless)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        t0 = time.monotonic()
        cutter = cut_client.Cutter(t0 + 0.3 + cut_client.CUT_GRACE_S)
        req = {"id": 0, "prompt": "ab", "prompt_tokens": 4,
               "output_tokens": 1000}
        rec = cut_client.send("127.0.0.1", httpd.server_address[1], req,
                              {"t0": t0, "seconds": 0.3}, t0, cutter)
        late = cut_client.send("127.0.0.1", httpd.server_address[1], req,
                               {"t0": t0, "seconds": 0.3}, t0, cutter)
    finally:
        httpd.shutdown()
    assert rec["ok"] and rec["cut"] and rec["error"] is None
    assert 5 < len(rec["stamps"]) < 1000 and rec["done"] is None
    assert max(rec["stamps"]) < 0.3 + cut_client.CUT_GRACE_S + 0.2
    assert late["ok"] and late["cut"] and not late["stamps"]


CASE = ("throwaway.reason-sat", "tiny-hybrid", "tiny-reason-sat", 1, CELL)


def test_rehearsal_1_the_hybrid_driver_end_to_end(tmp_path):
    root = th._temp_root(tmp_path, [CASE])
    cell = cells.load_cell(CASE[0], root)
    proc = th._run(root, CASE[0], trace=0)
    line = th._last_line(proc)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "every served position" in proc.stderr
    assert "cut by their clients" in proc.stderr
    traced = th._last_line(th._run(root, CASE[0], trace=1))
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert set(got) <= {m["name"] for m in cell.per_layer}
    assert got["sat_rows_per_dispatch"]["value"] >= 1.0
    assert got["xdec_positions_per_admission"]["value"] == 1.0
    # what needs a device trace finds no kernel on the CPU and is left out
    assert not set(NEW[:-1]) & set(got)
