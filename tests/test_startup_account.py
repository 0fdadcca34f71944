"""The start-up account (``obs/spans.startup_phase`` and the programs JAX
makes, by name), where an operator reads it (the ``startup:`` line, the
``startup.summary`` record, ``/metrics``), the first token's spans in
``generate`` (``inference.encode``, ``inference.echo``,
``GenStats.first_token_ms``), and the benchmark's readers of both
(``benchmark/layer_metrics/setup_*.py``, ``ttft_*.py`` over
``benchmark/harness/first_token.py``) on traces made by hand.

Toy engines on the CPU. Counts, names and identities are looked at, never
times as such: nothing measured here is a device metric.
"""

import json
import os
import sys
import time
import types

import pytest

from distributed_llama_tpu.models.spec import FloatType, TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, first_token  # noqa: E402
from benchmark.harness.reduce_trace import Op, Trace  # noqa: E402

# widths no other test file uses: ``_shared_program`` and JAX's own caches
# then hold none of these programs, and the first step makes one
DENSE = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=136, seq_len=32)
EXPERT = TransformerSpec(dim=128, hidden_dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=4, vocab_size=264, seq_len=32,
                         weights_float_type=FloatType.Q40, n_experts=4,
                         n_active_experts=2, qk_norm=True)
STATE = TransformerSpec(dim=128, hidden_dim=256, n_layers=2, n_heads=8,
                        n_kv_heads=2, vocab_size=520, seq_len=64,
                        weights_float_type=FloatType.Q40, qk_norm=True,
                        qk_norm_per_head=True, attn_kind="retention",
                        rope_theta=1e6, norm_eps=1e-6)
KINDS = ("dense", "expert", "state", "tp2")
ENGINE_PHASES = {"pack", "place", "cache", "engine"}


class _IdTokenizer:
    def encode(self, text, bos=True, eos=False):
        return [1] + [3 + b for b in text.encode()]

    def decode_piece(self, prev, tok):
        return b"<%d>" % tok


@pytest.fixture
def account(monkeypatch):
    """An empty account of this test's own; the ONE pair of listeners (the
    module's functions) then files into it. JAX's persistent compile cache
    is off in every test (``conftest.persistent_cache_off``), so a program
    made here is ``compiled``, never read from the checkout's
    ``.jax_cache/``."""
    spans._listen()
    fresh = spans._Account()
    fresh.listening = True
    monkeypatch.setattr(spans, "_account", fresh)
    return fresh


def _made(program):
    """(how, record) of a program this process made in ONE way: ``compiled``
    with the persistent cache off or cold, ``cache`` where a process keeps
    it on all the same. Either is a program filed by its name."""
    (how, rec), = program.items()
    assert how in ("compiled", "cache"), how
    return how, rec


@pytest.fixture(scope="module")
def trees():
    return {"dense": (DENSE, synth_params(DENSE, q40=False, seed=4,
                                          scale=0.3)),
            "expert": (EXPERT, synth_params(EXPERT, q40=True, seed=11)),
            "state": (STATE, synth_params(STATE, q40=True, seed=11))}


def _spec_tree_mesh(trees, kind):
    if kind != "tp2":
        return (*trees[kind], None)
    from distributed_llama_tpu.parallel import make_mesh

    return (*trees["dense"], make_mesh(tp=2))


# ------------------------------------------------------------- the account


def test_phases_nest_and_add_up(account):
    t0 = time.perf_counter()
    with spans.startup_phase("engine"):
        time.sleep(0.02)
        with spans.startup_phase("pack"):
            time.sleep(0.03)
            with spans.startup_phase("pack"):      # a nested stack of layers
                time.sleep(0.01)
        with spans.startup_phase("cache"):
            time.sleep(0.01)
    wall = time.perf_counter() - t0
    phases = spans.startup_account()["phases"]
    assert list(phases) == ["engine", "pack", "cache"]   # as first opened
    # a phase's OWN seconds: the four add up to the wall time they cover
    assert sum(phases.values()) == pytest.approx(wall, abs=5e-3)
    assert phases["pack"] >= 0.04 and phases["cache"] >= 0.01
    assert 0.02 <= phases["engine"] < wall - 0.05 + 5e-3


def test_a_phase_that_raises_still_books_its_time(account):
    with pytest.raises(RuntimeError):
        with spans.startup_phase("engine"):
            with spans.startup_phase("place"):
                time.sleep(0.02)
                raise RuntimeError("no device")
    phases = spans.startup_account()["phases"]
    assert phases["place"] >= 0.02 and phases["engine"] >= 0.0
    assert not account.local.stack          # and the stack is unwound


def test_startup_phase_is_a_decorator_too(account):
    @spans.startup_phase("load")
    def load(x):
        return x + 1

    assert load(1) == 2 and load(2) == 3
    assert list(spans.startup_account()["phases"]) == ["load"]


def test_startup_placed_counts_bytes(account):
    import numpy as np

    spans.startup_placed({"a": np.zeros((1 << 18,), np.float32),
                          "b": [np.zeros((3,), np.uint8)]})
    spans.startup_placed(np.zeros((5,), np.uint8))
    assert spans.startup_account()["bytes_placed"] == (1 << 20) + 3 + 5


def _fire(name, trace_s, lower_s, backend_s, hit=False):
    """What JAX 0.9.0 sends for one program, in its order."""
    spans._on_duration(spans.JAX_TRACE_EVENT, trace_s, fun_name=name)
    spans._on_duration(spans.JAX_LOWER_EVENT, lower_s,
                       fun_name=f"jit({name})")
    spans._on_event("/jax/compilation_cache/compile_requests_use_cache")
    if hit:
        spans._on_event(spans.JAX_CACHE_HIT_EVENT)
        spans._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                           backend_s * 0.9)
    spans._on_duration(spans.JAX_COMPILE_EVENT, backend_s,
                       fun_name=f"jit({name})")


def test_listener_files_by_name_and_splits_compiled_from_cache(account):
    # a jitted helper traced INSIDE the step's tracing: no program of its own
    spans._on_duration(spans.JAX_TRACE_EVENT, 0.5, fun_name="_where")
    _fire("serve_decode_step", 1.0, 0.25, 8.0)
    _fire("serve_decode_step", 1.0, 0.25, 0.5, hit=True)
    _fire("serve_admit_gather", 0.1, 0.05, 0.2, hit=True)
    progs = spans.startup_account()["programs"]
    assert set(progs) == {"serve_decode_step", "serve_admit_gather"}
    step = progs["serve_decode_step"]
    assert step["compiled"] == {"makes": 1, "trace_s": 1.0, "lower_s": 0.25,
                                "backend_s": 8.0}
    assert step["cache"] == {"makes": 1, "trace_s": 1.0, "lower_s": 0.25,
                             "backend_s": 0.5}
    assert set(progs["serve_admit_gather"]) == {"cache"}
    # the hit was the program's whose compile event followed it, no other's
    _fire("serve_admit_scatter", 0.1, 0.05, 0.2)
    assert set(spans.startup_account()["programs"][
        "serve_admit_scatter"]) == {"compiled"}


def test_listener_keeps_threads_apart(account):
    import threading

    spans._on_event(spans.JAX_CACHE_HIT_EVENT)      # this thread's, pending
    t = threading.Thread(target=_fire, args=("other_thread", 0.1, 0.1, 1.0))
    t.start()
    t.join()
    assert set(spans.startup_account()["programs"]["other_thread"]) == {
        "compiled"}


def test_account_is_a_plain_dict_and_line_and_record_agree(account, capsys,
                                                            monkeypatch):
    with spans.startup_phase("engine"):
        with spans.startup_phase("place"):
            pass
    account.bytes_placed = 3 << 30
    _fire("serve_decode_step", 1.0, 0.25, 0.65, hit=True)
    _fire("serve_admit_prefill_chunk", 0.5, 0.25, 2.25)
    acc = spans.startup_account()
    assert json.loads(json.dumps(acc)) == acc
    acc["phases"]["engine"] = -1.0      # a copy: the account is not touched
    assert spans.startup_account()["phases"]["engine"] >= 0.0

    spans.log_startup()
    line = capsys.readouterr().err.strip()
    assert line.startswith("startup: place 0.0 (3.00 GiB) engine 0.0 | "
                           "programs 2 made, 4.9 s (cache 1, compiled 1): "
                           "serve_admit_prefill_chunk 3.0 (compiled), "
                           "serve_decode_step 1.9")
    monkeypatch.setenv("DLLAMA_LOG_JSON", "1")
    printed = spans.log_startup()
    rec = json.loads(capsys.readouterr().err.strip())
    assert rec["event"] == "startup.summary"
    for key in ("phases", "bytes_placed", "programs"):
        assert rec[key] == printed[key] == spans.startup_account()[key]
    assert spans.startup_line(printed) == line


def test_a_program_made_after_startup_is_logged_and_fed(account, capsys):
    seen = []
    snapshot = spans.on_program_made(lambda *a: seen.append(a))
    assert snapshot["programs"] == {}
    _fire("inference_step", 0.5, 0.25, 1.25)            # start-up: no line
    assert capsys.readouterr().err == ""
    spans.log_startup()
    capsys.readouterr()
    _fire("inference_prefill_chunk", 0.5, 0.25, 0.25, hit=True)
    assert capsys.readouterr().err.strip() == (
        "program made: inference_prefill_chunk 1.00 s (cache)")
    assert seen == [("inference_step", "compiled", 2.0),
                    ("inference_prefill_chunk", "cache", 1.0)]


# ------------------------------------------------------ engines open them


@pytest.mark.parametrize("kind", KINDS)
def test_engine_opens_the_phases_and_its_step_is_filed(account, trees, kind):
    from distributed_llama_tpu.runtime.generate import Engine

    spec, tree, mesh = _spec_tree_mesh(trees, kind)
    eng = Engine(spec, tree, mesh=mesh)
    acc = spans.startup_account()
    assert ENGINE_PHASES <= set(acc["phases"]), acc["phases"]
    assert acc["bytes_placed"] > 0
    assert "inference_step" not in acc["programs"]
    eng.infer(1, 0)
    _, step = _made(spans.startup_account()["programs"]["inference_step"])
    assert step["makes"] == 1
    assert step["trace_s"] > 0 and step["backend_s"] > 0


@pytest.mark.parametrize("kind", KINDS)
def test_continuous_engine_opens_the_phases_and_its_step_is_filed(
        account, trees, kind):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    spec, tree, mesh = _spec_tree_mesh(trees, kind)
    kw = {} if kind == "state" else {"page_size": 4}
    # a vocabulary of its own a kind: the step is no other engine's program
    eng = ContinuousEngine(spec, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=5, mesh=mesh, **kw)
    acc = spans.startup_account()
    assert ENGINE_PHASES <= set(acc["phases"]), acc["phases"]
    assert acc["bytes_placed"] > 0
    made_before = sum(r["makes"] for hows in acc["programs"].values()
                      for r in hows.values())
    eng.run([[1, 5]], steps=4)
    progs = spans.startup_account()["programs"]
    assert sum(r["makes"] for hows in progs.values()
               for r in hows.values()) > made_before
    if mesh is None or "serve_decode_step" in progs:
        assert _made(progs["serve_decode_step"])[1]["makes"] >= 1


# ------------------------------------------------------------------ /metrics


def test_server_start_prints_the_line_and_metrics_carry_the_account(
        account, trees, capsys):
    import urllib.request

    from distributed_llama_tpu.runtime.server import InferenceServer

    spec = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                           n_kv_heads=2, vocab_size=144, seq_len=32)
    srv = InferenceServer(spec, synth_params(spec, q40=False, seed=4,
                                             scale=0.3), _IdTokenizer(),
                          "127.0.0.1", 0, slots=2, steps=8, temperature=0.0,
                          topp=0.9, seed=5, quiet=True)
    reg = srv.registry
    srv.start()
    try:
        err = capsys.readouterr().err
        line = [ln for ln in err.splitlines() if ln.startswith("startup: ")]
        assert len(line) == 1 and " engine " in line[0]
        assert "program made" not in err
        acc = spans.startup_account()
        for phase, seconds in acc["phases"].items():
            assert reg.get(f'dllama_startup_seconds{{phase="{phase}"}}'
                           ).value == seconds
        assert reg.get("dllama_engine_compile_events_total").value == 0

        # a new shape while serving: one line by name, and the samples
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=json.dumps({"prompt": "xy", "steps": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
        made = [ln for ln in capsys.readouterr().err.splitlines()
                if ln.startswith("program made: serve_decode_step ")]
        how, _ = _made(spans.startup_account()["programs"][
            "serve_decode_step"])
        assert len(made) == 1 and made[0].endswith(f"({how})")
        key = f'{{how="{how}",program="serve_decode_step"}}'
        assert reg.get("dllama_program_makes_total" + key).value == 1
        assert reg.get("dllama_program_make_seconds_total" + key).value > 0
        events = reg.get("dllama_engine_compile_events_total").value
        progs = spans.startup_account()["programs"]
        assert 1 <= events <= sum(r["makes"] for hows in progs.values()
                                  for r in hows.values())
        text = reg.expose()
        assert "# TYPE dllama_program_makes_total counter" in text
        assert "# TYPE dllama_startup_seconds gauge" in text
    finally:
        srv.stop()
    # stopped: the feed is off, the registry stands still
    _fire("serve_decode_step", 0.1, 0.1, 0.1)
    assert reg.get("dllama_engine_compile_events_total").value == events
    assert not account.sinks


def test_new_families_are_in_the_census():
    from distributed_llama_tpu.analysis import wiremodel as wm

    assert wm.METRIC_FAMILIES["dllama_startup_seconds"].labels == ("phase",)
    for fam in ("dllama_program_makes_total",
                "dllama_program_make_seconds_total"):
        assert wm.METRIC_FAMILIES[fam].labels == ("program", "how")


# --------------------------------------------------- the first token's spans


def test_generate_books_the_first_sampled_token(trees, capsys):
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    spec, tree = trees["dense"]
    eng = Engine(spec, tree)
    stamps = []
    t0 = time.perf_counter()
    _, stats = generate(eng, _IdTokenizer(),
                        Sampler(spec.vocab_size, 0.0, 0.9, seed=3),
                        "abcdefghijkl", 18,
                        emit=lambda piece: stamps.append(time.perf_counter()),
                        quiet=False, prefill_chunk=12)
    # 12 echoed tokens, then the first sampled one: booked after its emit
    # (from the call's entry, a moment after ``t0``), before the next token's
    assert len(stamps) == 18
    assert (stamps[12] - t0) * 1e3 - 5.0 <= stats.first_token_ms
    assert stats.first_token_ms <= (stamps[13] - t0) * 1e3
    out = capsys.readouterr().out
    assert f"First sampled token: {stats.first_token_ms:.2f} ms" in out

    # a budget the prompt fills: nothing sampled, nothing booked
    eng.reset()
    _, stats = generate(eng, _IdTokenizer(),
                        Sampler(spec.vocab_size, 0.0, 0.9, seed=3),
                        "abcdefghijkl", 6, quiet=True)
    assert stats.first_token_ms is None


def test_run_summary_record_carries_first_token_ms(trees, capsys,
                                                   monkeypatch):
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    monkeypatch.setenv("DLLAMA_LOG_JSON", "1")
    spec, tree = trees["dense"]
    _, stats = generate(Engine(spec, tree), _IdTokenizer(),
                        Sampler(spec.vocab_size, 0.0, 0.9, seed=3),
                        "abcd", 8, quiet=True)
    recs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    summary = [r for r in recs if r["event"] == "run.summary"]
    assert len(summary) == 1
    assert summary[0]["first_token_ms"] == round(stats.first_token_ms, 3)
    assert "ahead_used" in summary[0]


def test_encode_and_echo_are_spans_of_a_capture(trees, tmp_path):
    """Both on the profiler's clock, at the top of their thread, in order:
    encode, the chunk's launch, the echo, then the first dispatch."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from distributed_llama_tpu.obs import profiler
    from distributed_llama_tpu.runtime.generate import Engine, generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    spec, tree = trees["dense"]
    eng = Engine(spec, tree)

    def run():
        generate(eng, _IdTokenizer(),
                 Sampler(spec.vocab_size, 0.0, 0.9, seed=3), "abcdefghijkl",
                 18, emit=lambda piece: None, quiet=True, prefill_chunk=12)

    run()
    eng.reset()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=profiler.capture_options())
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    got = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("inference."))
    names = [n for _, _, n in got]
    assert names[:4] == ["inference.encode", "inference.prefill_chunk",
                         "inference.echo", "inference.dispatch"]
    assert names.count("inference.encode") == 1
    assert names.count("inference.echo") == 1
    for (_, end, _), (start, _, _) in zip(got, got[1:]):
        assert end <= start            # none inside another
    assert not [n for n in names if n.endswith(".step")]


# ------------------------------------------------- the benchmark's readers

MS = 1e6        # ns


def _op(name, start_ms, end_ms, label="main"):
    return Op(name, label, start_ms * MS, end_ms * MS)


def _generation(t0, chunk_ms=58.0, step_ms=7.5, devices=("/device:TPU:0",),
                chunks=1, skew=None):
    """One generation from ``t0`` ms: (host spans, {device: module runs}).
    Encode 0.5, the chunk's launch 1.0, the echo 2.0, the dispatch 1.0; the
    device starts the chunk 1.2 ms after the call, runs the step behind it
    and the step enqueued ahead behind that; the fetch ends 0.3 ms after the
    first step does. ``skew`` {device: ms} makes a device's chunk longer."""
    spans_ = [_op("inference.encode", t0, t0 + 0.5),
              _op("inference.prefill_chunk", t0 + 0.5, t0 + 1.5),
              _op("inference.echo", t0 + 1.5, t0 + 3.5),
              _op("inference.dispatch", t0 + 3.5, t0 + 4.5)]
    mods = {}
    end = 0.0
    for dev in devices:
        c = chunk_ms + (skew or {}).get(dev, 0.0)
        t = t0 + 1.2
        runs = []
        for _ in range(chunks):
            runs.append(_op("jit_inference_prefill_chunk", t, t + c, "module"))
            t += c
        runs.append(_op("jit_inference_step", t, t + step_ms, "module"))
        t += step_ms
        end = max(end, t)
        runs.append(_op("jit_inference_step", t, t + step_ms, "module"))
        mods[dev] = runs
    spans_.append(_op("inference.fetch", t0 + 4.5, end + 0.3))
    spans_.append(_op("inference.emit", end + 0.3, end + 0.4))
    # the second step's pair, so a later fetch is not the first
    spans_.append(_op("inference.dispatch", end + 0.4, end + 0.9))
    spans_.append(_op("inference.fetch", end + 0.9, end + step_ms + 0.3))
    return spans_, mods


def _trace(parts, window=None):
    spans_, mods = [], {}
    for s, m in parts:
        spans_ += s
        for dev, runs in m.items():
            mods.setdefault(dev, []).extend(runs)
    spans_.sort(key=lambda o: (o.start, -o.end))
    devices = {d: [Op("fusion.1", "fusion", r.start, r.end) for r in runs]
               for d, runs in mods.items()}
    return Trace(devices, spans_, window=window, modules=mods)


def _run(trace):
    return types.SimpleNamespace(trace=trace)


def _reader(name):
    return cells.load_reader("layer_metrics", name)


def test_ttft_readers_one_generation():
    run = _run(_trace([_generation(10.0)]))
    gens = first_token.first_tokens(run.trace)
    assert len(gens) == 1
    g = gens[0]
    # encode's start to the first fetch's end: 1.2 + 58 + 7.5 + 0.3
    assert g["interval_ms"] == pytest.approx(67.0)
    assert g["prefill_device_ms"] == pytest.approx(58.0)
    # the step enqueued ahead starts inside the interval and is left out
    assert g["busy_ms"] == pytest.approx(65.5)
    assert g["host_ms"] == pytest.approx(1.5)
    assert _reader("ttft_prefill_device_ms").read(run) == pytest.approx(58.0)
    assert _reader("ttft_host_ms").read(run) == pytest.approx(1.5)


def test_ttft_readers_two_generations_take_the_median():
    run = _run(_trace([_generation(10.0),
                       _generation(1100.0, chunk_ms=60.0)]))
    assert len(first_token.first_tokens(run.trace)) == 2
    assert _reader("ttft_prefill_device_ms").read(run) == pytest.approx(59.0)
    assert _reader("ttft_host_ms").read(run) == pytest.approx(1.5)


def test_ttft_readers_leave_out_a_generation_the_capture_cuts():
    whole = _generation(10.0)
    cut = _generation(1450.0)
    # the window ends while the second generation's chunk runs: its first
    # fetch never closed inside, and its program runs are not whole
    hi = 1480.0 * MS
    spans_ = [s for s in cut[0] if s.start < hi]
    mods = {d: [m for m in runs if m.end <= hi] for d, runs in cut[1].items()}
    run = _run(_trace([whole, (spans_, mods)], window=(0.0, hi)))
    gens = first_token.first_tokens(run.trace)
    assert len(gens) == 1
    assert gens[0]["prefill_device_ms"] == pytest.approx(58.0)
    # and one that began before the capture did is no generation either
    early = _run(_trace([_generation(10.0)], window=(10.2 * MS, 200.0 * MS)))
    assert first_token.first_tokens(early.trace) == []


def test_ttft_readers_four_devices_read_the_busiest():
    devs = tuple(f"/device:TPU:{i}" for i in range(4))
    run = _run(_trace([_generation(10.0, chunk_ms=105.0, step_ms=11.0,
                                   devices=devs, chunks=1,
                                   skew={"/device:TPU:2": 0.4})]))
    g = first_token.first_tokens(run.trace)[0]
    assert g["prefill_device_ms"] == pytest.approx(105.4)
    assert g["busy_ms"] == pytest.approx(116.4)
    assert g["host_ms"] == pytest.approx(g["interval_ms"] - 116.4)
    assert g["host_ms"] == pytest.approx(1.5)


def test_ttft_readers_sum_two_chunks_and_find_nothing_on_a_parent():
    run = _run(_trace([_generation(10.0, chunk_ms=30.0, chunks=2)]))
    assert _reader("ttft_prefill_device_ms").read(run) == pytest.approx(60.0)
    # a parent commit's capture: no ``inference.encode``
    spans_, mods = _generation(10.0)
    parent = _run(_trace([([s for s in spans_
                            if s.name not in ("inference.encode",
                                              "inference.echo")], mods)]))
    for name in ("ttft_prefill_device_ms", "ttft_host_ms"):
        assert _reader(name).read(parent) is None
        assert _reader(name).read(_run(None)) is None


@pytest.mark.parametrize("name,phases", [
    ("setup_weights_s", ("load", "pack", "place")),
    ("setup_engine_s", ("cache", "engine")),
])
def test_setup_phase_readers(account, name, phases):
    reader = _reader(name)
    assert reader.read(None) == 0.0         # no phase opened: 0.0, not None
    account.phases.update({"load": 1.0, "pack": 2.0, "place": 4.0,
                           "cache": 8.0, "engine": 16.0, "other": 32.0})
    want = {"setup_weights_s": 7.0, "setup_engine_s": 24.0}[name]
    assert reader.read(None) == want
    del account.phases[phases[0]]
    assert reader.read(None) == want - {"load": 1.0, "cache": 8.0}[phases[0]]
    assert (reader.MOVES, reader.SOURCE) == ("setup_s", "program_counter")


def test_setup_program_make_reader(account, monkeypatch):
    reader = _reader("setup_program_make_s")
    assert reader.read(None) == 0.0
    _fire("serve_decode_step", 1.0, 0.25, 0.65, hit=True)
    _fire("serve_decode_step", 1.0, 0.25, 8.0)
    _fire("serve_admit_gather", 0.125, 0.125, 0.25, hit=True)
    assert reader.read(None) == pytest.approx(1.9 + 9.25 + 0.5)
    # a program without the account (a parent commit): nothing, no raise
    monkeypatch.delattr(spans, "startup_account")
    assert reader.read(None) is None
    for name in ("setup_weights_s", "setup_engine_s"):
        assert _reader(name).read(None) is None


def test_the_five_entries_are_the_last_of_per_layer():
    # the last of PR 50's list: a later PR appends its own after them
    doc = cells.load_benchmark()
    at = [m["name"] for m in doc["per_layer"]].index("setup_program_make_s")
    last = doc["per_layer"][at:at + 5]
    assert at >= 84 and [m["name"] for m in last] == [
        "setup_program_make_s", "setup_weights_s", "setup_engine_s",
        "ttft_prefill_device_ms", "ttft_host_ms"]
    every = [w["name"] for w in doc["workloads"]]
    for m in last[:3]:
        assert (m["moves"], m["workloads"]) == ("setup_s", every)
    for m in last[3:]:
        assert m["moves"] == "ttft_ms_p50"
        assert m["workloads"] == ["mistral7b.decode1", "yi34b-tp4.decode1"]
    for m in last:
        reader = _reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
