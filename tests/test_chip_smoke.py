"""chip_smoke.py off the chip, and the rules it stands on.

The smoke is the proof that the system starts on the chip; here it runs at a
tiny size on the CPU to prove its OWN logic: with no chip it must FAIL (the
no-fallback rule as a test), with the platform expectation stubbed its
phases pass, and a failing child fails the run. The same file pins the
chip-expecting helper (utils/chip), where the compile cache goes
(utils/compile_cache) and the seeded model writer the smoke feeds on.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_under_test", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _one_cpu_device(monkeypatch, tmp_path, smoke):
    """The children inherit this process's environment: one CPU device (the
    suite's 8-device flag would make ``inference`` build a tp=8 mesh) and a
    compile cache of the test's own. The smoke's work directory is the
    test's own too: ``main`` removes it at its end, under another worker's
    smoke if they share the checkout's."""
    monkeypatch.setattr(smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def test_smoke_fails_without_a_chip(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero with
    "ok": false and the platform it really saw on its last line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), "--size",
         "tiny"], capture_output=True, text=True, env=env, timeout=300,
        cwd=str(tmp_path))
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # it stopped at the probe: no model was written, no phase ran
    assert '"phase": "inference"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_smoke_phases_pass_with_platform_stubbed(monkeypatch, tmp_path,
                                                 capsys):
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "EXPECT_PLATFORM", "cpu")
    _one_cpu_device(monkeypatch, tmp_path, smoke)
    rc = smoke.main(["--size", "tiny"])
    out = capsys.readouterr().out
    notes = [json.loads(ln) for ln in out.strip().splitlines()]
    assert rc == 0, out[-3000:]
    assert notes[-1] == {"ok": True, "device": {"platform": "cpu",
                                                "kind": "cpu", "count": 1}}
    by_phase = {n["phase"]: n for n in notes[:-1]}
    for phase in ("device", "model", "inference", "warm", "reference",
                  "serve"):
        assert phase in by_phase, (phase, sorted(by_phase))
    assert by_phase["inference"]["tokens"] == smoke.STEPS
    assert by_phase["compile_cache"]["hit"] is True
    assert by_phase["serve"]["prefix_hits"] >= 1
    assert by_phase["serve"]["exit"] == 0
    # the smoke removes what it made
    assert not os.path.exists(smoke.WORK)


def test_smoke_fails_when_a_child_fails(monkeypatch, tmp_path, capsys):
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "EXPECT_PLATFORM", "cpu")
    _one_cpu_device(monkeypatch, tmp_path, smoke)
    missing = str(tmp_path / "no-such-model.bin")
    monkeypatch.setattr(smoke, "write_model",
                        lambda size, seed: (missing, missing))
    rc = smoke.main(["--size", "tiny"])
    notes = [json.loads(ln)
             for ln in capsys.readouterr().out.strip().splitlines()]
    assert rc == 1
    assert notes[-1]["ok"] is False
    failed = [n for n in notes if n.get("phase") == "failed"]
    assert failed and "child exited" in failed[0]["error"]


@pytest.mark.parametrize("placed_outside", [True, False])
def test_compile_cache_is_placed_from_outside(placed_outside, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code (jax's own reading of the variable stands); unset, the cache is
    <checkout>/.jax_cache. A fresh interpreter: jax reads the variable at
    import."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _ROOT
    outside = str(tmp_path / "elsewhere")
    if placed_outside:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    code = ("import jax\n"
            "from distributed_llama_tpu.utils.compile_cache import "
            "enable_persistent_cache\n"
            "print(enable_persistent_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured, min_secs = proc.stdout.split()
    want = outside if placed_outside else os.path.join(_ROOT, ".jax_cache")
    assert returned == configured == want
    assert float(min_secs) == 0


def test_require_tpu_raises_off_chip():
    from distributed_llama_tpu.utils import chip

    dev = chip.device_triple()
    assert dev["platform"] == "cpu" and dev["count"] >= 1 and dev["kind"]
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip.require_tpu()
    assert chip.memory_line("loaded") is None  # the CPU reports no stats


def test_peaks_are_keyed_by_device_kind_and_unknown_raises():
    from distributed_llama_tpu.utils import chip

    assert chip.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert chip.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert all(row["source"] for row in chip.PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        chip.peak("cpu", "bf16_flops_per_s")


def test_bench_refuses_a_device_config_off_chip(tmp_path):
    """bench.py for every config but ``small`` raises instead of printing a
    CPU rate under a device metric's name."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--config", "7b",
         "--samples", "2"], capture_output=True, text=True, env=env,
        timeout=300, cwd=_ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""  # no row


def test_bench_all_exits_nonzero_when_a_row_failed(monkeypatch, tmp_path,
                                                   capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_mod_failed_row", os.path.join(_ROOT, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    good = json.dumps({"value": 9.9, "vs_baseline": 49.9})

    def fake_run(cmd, **kw):
        cfg = cmd[cmd.index("--config") + 1]
        ok = cfg == "7b"
        return subprocess.CompletedProcess(cmd, 0 if ok else 1,
                                           stdout=good if ok else "")

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setenv("DLLAMA_BENCH_CONFIGS", "7b,13b")
    monkeypatch.setenv("DLLAMA_BENCH_FULL_PATH", str(tmp_path / "full.json"))

    class Args:
        samples = 2

    assert bench._run_all(Args()) == 1
    out = capsys.readouterr()
    row = json.loads(out.out.strip().splitlines()[-1])
    assert row["rows"]["13b"] == {"error": "rc=1"}  # the record survives
    assert "FAILED rows: 13b" in out.err


def test_cache_errors_are_reported_once_and_counted(capsys, tmp_path):
    """A cache that cannot be written never kills the run, and is never
    silent either: first error of a store on stderr, every error counted."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.runtime.decode import _load_or_compile
    from distributed_llama_tpu.utils import compile_cache

    before = compile_cache.cache_error_count()
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    lowered = jax.jit(lambda x: x + 1).lower(jnp.zeros((4,), jnp.float32))
    for _ in range(2):  # makedirs under a plain file fails both times
        compiled = _load_or_compile(lowered, str(blocker / "aot"))
        assert float(compiled(jnp.ones((4,), jnp.float32))[0]) == 2.0
    assert compile_cache.cache_error_count() == before + 2
    err = capsys.readouterr().err
    assert err.count("💡 cache error [exe]") <= 1  # once per process
    if before == 0:
        assert "💡 cache error [exe]" in err


def test_unreadable_kcache_sidecar_is_counted(capsys, tmp_path):
    from distributed_llama_tpu.io.kernel_cache import MAGIC, load_packed
    from distributed_llama_tpu.utils import compile_cache

    before = compile_cache.cache_error_count()
    side = tmp_path / "m.bin.kcache"
    side.write_bytes(MAGIC + np.uint32(9).tobytes() + b"not json!")
    assert load_packed(str(side), "any-key") is None
    assert compile_cache.cache_error_count() == before + 1


def test_synth_q40_writer_streams_a_loadable_model(tmp_path):
    """write_synth_q40_model: byte-exact size, loads through the normal
    reader, zero-mean weights (a mean would swamp the signal and make every
    greedy stream agree), and a BOS row that can never win the argmax."""
    from distributed_llama_tpu.io.loader import load_model
    from distributed_llama_tpu.io.tokenizer import BOS, Tokenizer
    from distributed_llama_tpu.models.synth import (
        llama2_7b_spec, write_synth_q40_model, write_synth_tokenizer)
    from distributed_llama_tpu.ops.quants import FloatType, dequantize_q40

    spec = llama2_7b_spec(dim=128, hidden_dim=256, n_layers=2, n_heads=4,
                          n_kv_heads=4, vocab_size=512, seq_len=64)
    path = str(tmp_path / "m.bin")
    assert write_synth_q40_model(path, spec, seed=3) == spec.file_size()
    again = str(tmp_path / "m2.bin")
    write_synth_q40_model(again, spec, seed=3)
    assert open(path, "rb").read() == open(again, "rb").read()  # seeded
    _, params = load_model(path, weights_float_type=FloatType.Q40)
    w1 = dequantize_q40(params["w1"].qs, params["w1"].d16)
    assert abs(float(w1.mean())) < 0.02 * float(w1.std())
    assert 0.5 < float(w1.std()) * np.sqrt(spec.dim) < 2.0
    wcls = dequantize_q40(params["wcls"].qs, params["wcls"].d16)
    assert not wcls[BOS].any() and wcls[BOS + 1].any()

    tok_path = str(tmp_path / "tok.bin")
    write_synth_tokenizer(tok_path, spec.vocab_size)
    tok = Tokenizer(tok_path, spec.vocab_size)
    ids = tok.encode("hello tpu")
    assert len(ids) == 2 + len("hello tpu") and ids[0] == BOS
    assert tok.decode(ids[1:]) == b"hello tpu"
    with pytest.raises(ValueError, match="reserved pieces"):
        write_synth_tokenizer(tok_path, 100)
