"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the main path once through the entry points a user would call, on a
seeded random Llama-2-7B Q40 model at FULL WIDTH (dim 4096, hidden 11008,
32 heads / 32 kv heads, vocab 32000, seq 2048; depth below), and checks what
comes out. Every phase is its own child process, one at a time — a chip
belongs to one process, and this parent never initialises a JAX backend: it
learns the device from what the children print.

  python chip_smoke.py            one chip (what the driver runs):
      device     a probe child: utils/chip.require_tpu()
      model      write the .bin (streamed packed Q40 bytes) and a tokenizer
      inference  python -m distributed_llama_tpu inference ... --steps 32
                 --temperature 0, default kernel policy, cold compile cache
      warm       the same command again: same stream, compile cache hits
      reference  the same command with DLLAMA_Q40_KERNEL=xla
                 DLLAMA_ATTN_KERNEL=xla — dequantize-then-dot and einsum
                 attention ON THE CHIP, a named phase and not a fallback
      serve      python -m distributed_llama_tpu serve --kv-page-size 16
                 --prefill-chunk 128 --slots 8; four /generate requests
                 (two sharing a prompt prefix, one streamed), /metrics,
                 the inference prompt again, SIGTERM -> exit 0
  python chip_smoke.py --chips 4  the tensor-parallel path and what it is
                 compared with, and no other phase: inference --tp 4 in one
                 process driving four chips, against inference --tp 1

The last stdout line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}};
earlier lines are notes, one JSON object each (phase wall times split into
load / compile / steady ms-per-token, compile-cache state, device memory).
They are smoke notes, not benchmark numbers. Exit code 0 only if every phase
passed, every child exited 0 and every child saw the expected platform.

PASS RULE for compared streams (inference vs warm vs reference vs serve, and
tp=4 vs tp=1): the greedy token streams must be IDENTICAL. The kernels are
pinned to the XLA paths at rtol = atol = 1e-5 on logits
(tests/test_pallas_q40.py, tests/test_pallas_attention.py). The synthetic
model's logits are ~N(0, 1) over 32000 entries, so the top-2 gap falls inside
a 2e-5 band with probability ~1e-4 per token: 32 tokens differing anywhere
means a kernel is outside its tolerance (or the rare tie, which the printed
first-difference index lets a reader judge). The CLI emits no logits, so no
logit difference is printed.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
# fixed and git-ignored: the chip tool copies the tree as it stands, and a
# 4 GB model plus its 4.4 GB .kcache sidecar left behind would break the copy
WORK = os.path.join(ROOT, ".chip_smoke")
DEPTH = 32            # all of Llama-2-7B's layers; cut depth only, never width
STEPS = 32
PROMPT = "hello tpu"
# 40 shared characters = 2 full 16-position KV pages of common prefix
SHARED = "the quick brown fox jumps over the lazy d"
DEADLINE_S = 1150     # the contract allows 1200 s, compilation included
CHILD_TIMEOUT_S = 700

# The platform every child must report. tests/test_chip_smoke.py sets this to
# "cpu" to exercise the phases off-chip; nothing else reads the environment.
EXPECT_PLATFORM = "tpu"

_T0 = time.monotonic()


def note(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _left() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


class PhaseFailed(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _cache_dir() -> str:
    """Where the children keep the compile cache (utils/compile_cache.py):
    the standard variable if the machine sets it, else the checkout's."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def _cache_entries() -> int:
    try:
        return sum(1 for n in os.listdir(_cache_dir())
                   if not n.startswith("."))
    except OSError:
        return 0


# -- phase: device ----------------------------------------------------------

def probe_device() -> dict:
    """The first chip owner: print the device triple, then — when the chip
    is expected — require it (utils/chip.require_tpu raises off-TPU, so a
    sandbox run ends here, before any 4 GB file is written)."""
    code = ("import json\n"
            "from distributed_llama_tpu.utils import chip\n"
            "print(json.dumps(chip.device_triple()), flush=True)\n")
    if EXPECT_PLATFORM == "tpu":
        code += "chip.require_tpu()\n"
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "-c", code], env=_env(), cwd=ROOT,
                       capture_output=True, text=True,
                       timeout=min(CHILD_TIMEOUT_S, max(_left(), 1)))
    dev = None
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            dev = json.loads(line)
    note(phase="device", device=dev, exit=p.returncode,
         seconds=round(time.monotonic() - t0, 1))
    if dev is None or p.returncode != 0:
        sys.stderr.write(p.stderr[-2000:])
    return {"device": dev, "exit": p.returncode}


# -- phase: model -----------------------------------------------------------

def write_model(size: str, seed: int) -> tuple[str, str]:
    from distributed_llama_tpu.models.synth import (
        llama2_7b_spec, write_synth_q40_model, write_synth_tokenizer)

    if size == "tiny":   # test-only: NOT a size a user would call real
        spec = llama2_7b_spec(dim=128, hidden_dim=256, n_layers=2, n_heads=4,
                              n_kv_heads=4, vocab_size=512, seq_len=256)
    else:
        spec = llama2_7b_spec(n_layers=DEPTH)
    os.makedirs(WORK, exist_ok=True)
    model = os.path.join(WORK, "model.bin")
    tok = os.path.join(WORK, "tokenizer.bin")
    t0 = time.monotonic()
    nbytes = write_synth_q40_model(model, spec, seed)
    write_synth_tokenizer(tok, spec.vocab_size)
    _check(nbytes == spec.file_size(), "model file size != spec.file_size()")
    note(phase="model", bytes=nbytes, depth=spec.n_layers, dim=spec.dim,
         hidden_dim=spec.hidden_dim, n_heads=spec.n_heads,
         n_kv_heads=spec.n_kv_heads, vocab_size=spec.vocab_size,
         seq_len=spec.seq_len, seed=seed,
         seconds=round(time.monotonic() - t0, 1),
         disk_free_gb=round(shutil.disk_usage(WORK).free / 1e9, 1))
    return model, tok


# -- phase: inference (and warm / reference / tp) ---------------------------

_DEV_LINE = re.compile(r"nSlices: (\d+) .*\((\d+) devices, (\w+)\)")


def run_inference(name: str, model: str, tok: str, seed: int,
                  extra_args=(), extra_env=None,
                  pallas: bool | None = True) -> dict:
    """One ``inference`` child. Returns its greedy stream and notes; raises
    PhaseFailed on any failed check. ``pallas``: which matmul path the
    child's '💡 Q40 body policy' line must name (None: a mesh run, which
    prints no policy line — its kernel layout is fixed by the sharding)."""
    cmd = [sys.executable, "-m", "distributed_llama_tpu", "inference",
           "--model", model, "--tokenizer", tok, "--prompt", PROMPT,
           "--steps", str(STEPS), "--temperature", "0", "--seed", str(seed),
           "--log-json", *extra_args]
    entries0 = _cache_entries()
    t0 = time.monotonic()
    p = subprocess.run(cmd, env=_env(extra_env), cwd=ROOT,
                       capture_output=True, text=True,
                       timeout=min(CHILD_TIMEOUT_S, max(_left(), 1)))
    wall = time.monotonic() - t0
    tokens, gen_ms, fps = [], [], []
    load_s = None
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            if rec.get("event") == "decode.token":
                tokens.append(rec["token"])
                gen_ms.append(rec["gen_ms"])
                fps.append(rec.get("env_fingerprint", {}))
    for line in p.stderr.splitlines():
        # the program's own start-up account (obs/spans.log_startup): load,
        # pack, place, cache and engine, summed
        if line.startswith("{") and '"startup.summary"' in line:
            load_s = round(sum(json.loads(line)["phases"].values()), 1)
    dev = _DEV_LINE.search(p.stdout)
    mem = _memory_lines(p.stderr)
    cache_errors = p.stderr.count("💡 cache error [")
    steady = sorted(gen_ms[1:])[len(gen_ms[1:]) // 2] if len(gen_ms) > 1 \
        else None
    fp = fps[0] if fps else {}
    out = {
        "phase": name, "exit": p.returncode,
        "device": _fp_device(fp),
        "tp": int(dev.group(1)) if dev else None,
        "tokens": len(tokens), "wall_s": round(wall, 1), "load_s": load_s,
        # the first step compiles (or loads from the compile cache) the one
        # T=1 program this path runs; the rest are steady state
        "first_token_ms": gen_ms[0] if gen_ms else None,
        "compile_s": (round((gen_ms[0] - steady) / 1e3, 2)
                      if steady is not None else None),
        "steady_ms_per_token": steady,
        "compile_cache": {"dir": _cache_dir(), "entries_before": entries0,
                          "entries_added": _cache_entries() - entries0},
        "cache_errors": cache_errors,
        "device_memory": mem,
        "q40_body_policy": _policy(p.stderr),
        "stream": tokens,
    }
    note(**out)
    if p.returncode != 0:
        sys.stderr.write(p.stderr[-3000:])
    _check(p.returncode == 0, f"{name}: child exited {p.returncode}")
    _check(len(tokens) == STEPS, f"{name}: {len(tokens)} tokens, not {STEPS}")
    _check(dev is not None and dev.group(3) == EXPECT_PLATFORM
           and fp.get("backend") == EXPECT_PLATFORM,
           f"{name}: the child's device line says "
           f"{dev.group(3) if dev else None!r} / its log stamp "
           f"{fp.get('backend')!r}, not {EXPECT_PLATFORM!r}")
    _check(cache_errors == 0, f"{name}: {cache_errors} cache error(s)")
    for bad in ("retrying", "fallback", "⚠️"):
        _check(bad not in p.stderr, f"{name}: stderr mentions {bad!r}")
    if EXPECT_PLATFORM == "tpu" and pallas is not None:
        policy = out["q40_body_policy"] or ""
        _check(bool(policy), f"{name}: no '💡 Q40 body policy' line")
        _check(("XLA matmul path" in policy) != pallas,
               f"{name}: policy line {policy!r} — expected the "
               f"{'Pallas' if pallas else 'XLA'} path")
    return out


def _fp_device(fp: dict) -> dict:
    """The device triple out of a child's log stamp (utils/fingerprint)."""
    return {"platform": fp.get("backend"), "kind": fp.get("device_kind"),
            "count": fp.get("n_devices")}


def _policy(stderr: str) -> str | None:
    m = re.search(r"💡 Q40 body policy: (.*)", stderr)
    return m.group(1) if m else None


def _memory_lines(stderr: str) -> dict:
    out = {}
    for m in re.finditer(r"💡 device memory \((\w+)\): (\[.*\])", stderr):
        out[m.group(1)] = json.loads(m.group(2))
    return out


def compare(a: dict, b: dict) -> None:
    """The pass rule (module docstring): identical greedy streams."""
    sa, sb = a["stream"], b["stream"]
    diff = next((i for i, (x, y) in enumerate(zip(sa, sb)) if x != y),
                None if len(sa) == len(sb) else min(len(sa), len(sb)))
    note(phase="compare", a=a["phase"], b=b["phase"], equal=diff is None,
         first_difference=diff, rule="identical greedy streams "
         "(kernel tolerance rtol=atol=1e-5 on logits)")
    if diff is not None:
        raise PhaseFailed(
            f"{a['phase']} and {b['phase']} streams first differ at index "
            f"{diff}: {sa[diff:diff + 4]} vs {sb[diff:diff + 4]}")


# -- phase: serve -----------------------------------------------------------

def run_serve(model: str, tok: str, seed: int, want_stream: list) -> None:
    # --kv-pages: the default pool (slots * seq_len / page = 1024 pages) is
    # byte-parity with 8 contiguous f32 caches — 16 GiB at 7B, the whole
    # chip. 128 pages (2 GiB) hold these four requests many times over.
    cmd = [sys.executable, "-m", "distributed_llama_tpu", "serve",
           "--model", model, "--tokenizer", tok, "--kv-page-size", "16",
           "--prefill-chunk", "128", "--slots", "8", "--kv-pages", "128",
           "--port", "0", "--seed", str(seed), "--log-json"]
    t0 = time.monotonic()
    p = subprocess.Popen(cmd, env=_env(), cwd=ROOT, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    out_lines: list[str] = []
    err_lines: list[str] = []
    readers = [threading.Thread(target=lambda s=s, b=b: b.extend(s),
                                daemon=True)
               for s, b in ((p.stdout, out_lines), (p.stderr, err_lines))]
    for r in readers:
        r.start()
    try:
        _serve_session(p, out_lines, err_lines, t0, want_stream)
    finally:
        if p.poll() is None:          # a failed check: stop what we started
            p.kill()
            p.wait()
        for r in readers:
            r.join(timeout=5)


def _http(url: str, payload: dict | None = None, timeout: float = 600):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


def _serve_session(p, out_lines, err_lines, t0, want_stream) -> None:
    base = None
    while base is None:
        _check(p.poll() is None, f"serve: child exited {p.poll()} before "
                                 f"serving:\n" + "".join(err_lines[-30:]))
        _check(_left() > 0, "serve: deadline passed while loading")
        for line in list(out_lines):
            m = re.search(r"serving on (http://[\d.]+:\d+)", line)
            if m:
                base = m.group(1)
        time.sleep(0.2)
    load_s = time.monotonic() - t0
    state = json.loads(_http(base + "/health")).get("state")
    _check(state in ("starting", "serving"), f"serve: /health state {state!r}")

    def generate(prompt, **kw):
        n_prompt = 2 + len(prompt)       # BOS + dummy space + 1 per char
        steps = kw.pop("steps", n_prompt + 16)
        t = time.monotonic()
        body = _http(base + "/generate",
                     {"prompt": prompt, "steps": steps, "temperature": 0,
                      **kw}, timeout=max(_left(), 1))
        return body, steps, time.monotonic() - t

    walls = {}
    total_steps = 0
    # two prompts with a shared 2-page prefix, sent in turn: the second
    # admission must find the first one's pages in the radix tree
    for key, prompt in (("prefix_a", SHARED + "og"),
                        ("prefix_b", SHARED + "ay")):
        body, steps, walls[key] = generate(prompt)
        rep = json.loads(body)
        _check(len(rep["tokens"]) == steps, f"serve {key}: "
               f"{len(rep['tokens'])} tokens for steps={steps}")
        total_steps += steps
    body, steps, walls["stream"] = generate("stream me", stream=True)
    chunks = [json.loads(ln) for ln in body.splitlines() if ln.strip()]
    _check(chunks and chunks[-1].get("done") is True
           and chunks[-1].get("steps") == steps,
           f"serve stream: last chunk {chunks[-1] if chunks else None}")
    total_steps += steps
    body, steps, walls["same_prompt"] = generate(PROMPT, steps=STEPS)
    rep = json.loads(body)
    total_steps += steps

    metrics = _http(base + "/metrics")

    def metric(name):
        m = re.search(rf"^{name}(?:{{[^}}]*}})? ([0-9.e+]+)$", metrics, re.M)
        return float(m.group(1)) if m else None

    health = json.loads(_http(base + "/health"))
    fps = [json.loads(ln).get("env_fingerprint", {})
           for ln in out_lines if ln.startswith("{")]
    fp = fps[-1] if fps else {}
    p.send_signal(signal.SIGTERM)       # by PID, never by pattern
    try:
        rc = p.wait(timeout=60)
    except subprocess.TimeoutExpired:
        rc = None
    stderr = "".join(err_lines)
    cache_errors = stderr.count("💡 cache error [")
    steady = health.get("token_latency_s", {})
    note(phase="serve", exit=rc, device=_fp_device(fp),
         load_s=round(load_s, 1),
         request_wall_s={k: round(v, 2) for k, v in walls.items()},
         # the first request compiles the prefill and decode programs
         token_latency_s=steady, generated_tokens=metric(
             "dllama_generated_tokens_total"),
         prefix_hits=metric("dllama_prefix_hits_total"),
         paged_kv=health.get("paged_kv"), cache_errors=cache_errors,
         device_memory=_memory_lines(stderr),
         q40_body_policy=_policy(stderr), stream=rep["tokens"])
    _check(rc == 0, f"serve: SIGTERM -> exit {rc}, not 0")
    _check(fp.get("backend") == EXPECT_PLATFORM,
           f"serve: the child's log stamp says {fp.get('backend')!r}, not "
           f"{EXPECT_PLATFORM!r}")
    _check(metric("dllama_generated_tokens_total") == total_steps,
           f"serve: dllama_generated_tokens_total "
           f"{metric('dllama_generated_tokens_total')} != {total_steps}")
    _check((metric("dllama_prefix_hits_total") or 0) >= 1,
           "serve: dllama_prefix_hits_total < 1")
    _check(cache_errors == 0, f"serve: {cache_errors} cache error(s)")
    compare({"phase": "inference", "stream": want_stream},
            {"phase": "serve", "stream": rep["tokens"]})


# -- main -------------------------------------------------------------------

def _spread(mem: dict) -> None:
    """tp=4: the weights must be spread, not parked on device 0. Judged on
    the snapshot after the run: right after the load device 0 still holds
    the whole KV cache it staged for the other three (3.5 GiB at 7B,
    freed before the first step) — printed, not failed."""
    rows = mem.get("end")
    _check(bool(rows) and len(rows) == 4,
           f"tp4: no per-device memory line for 4 devices ({rows})")
    used = [r["bytes_in_use"] for r in rows]
    note(phase="tp4_spread", bytes_in_use=used,
         share=[round(u / sum(used), 3) for u in used],
         after_load=[r["bytes_in_use"] for r in mem.get("loaded", [])],
         peak=[r["peak_bytes_in_use"] for r in rows])
    _check(max(used) <= 1.25 * min(used),
           f"tp4: device bytes not spread evenly: {used}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--size", default="7b", choices=("7b", "tiny"),
                    help=argparse.SUPPRESS)   # tiny: tests only
    args = ap.parse_args(argv)
    global _T0
    _T0 = time.monotonic()

    note(phase="start", chips=args.chips, expect_platform=EXPECT_PLATFORM,
         versions=_versions(), note="smoke notes, not benchmark numbers")
    probe = probe_device()
    device = probe["device"] or {"platform": None, "kind": None,
                                 "count": None}
    ok = False
    try:
        _check(probe["exit"] == 0 and probe["device"] is not None,
               f"device probe exited {probe['exit']}")
        _check(device["platform"] == EXPECT_PLATFORM,
               f"platform {device['platform']!r}, not {EXPECT_PLATFORM!r}")
        _check(EXPECT_PLATFORM != "tpu" or device["count"] == args.chips,
               f"{device['count']} devices, not {args.chips}")
        model, tok = write_model(args.size, args.seed)
        seen = []
        if args.chips == 4:
            tp4 = run_inference("tp4", model, tok, args.seed, ("--tp", "4"),
                                pallas=None)
            seen.append(tp4)
            _check(tp4["tp"] == 4, f"tp4: ran with tp={tp4['tp']}")
            if EXPECT_PLATFORM == "tpu":
                _spread(tp4["device_memory"])
            tp1 = run_inference("tp1", model, tok, args.seed, ("--tp", "1"))
            seen.append(tp1)
            compare(tp4, tp1)
        else:
            cold = run_inference("inference", model, tok, args.seed)
            warm = run_inference("warm", model, tok, args.seed)
            compare(cold, warm)
            hit = (warm["compile_cache"]["entries_added"] == 0
                   and warm["compile_cache"]["entries_before"] > 0)
            note(phase="compile_cache", dir=_cache_dir(), hit=hit,
                 cold_compile_s=cold["compile_s"],
                 warm_compile_s=warm["compile_s"],
                 cold_entries_added=cold["compile_cache"]["entries_added"])
            _check(hit, "warm: the second inference run added compile-cache "
                        "entries (no hit)")
            ref = run_inference(
                "reference", model, tok, args.seed, pallas=False,
                extra_env={"DLLAMA_Q40_KERNEL": "xla",
                           "DLLAMA_ATTN_KERNEL": "xla"})
            compare(cold, ref)
            seen += [cold, warm, ref]
            run_serve(model, tok, args.seed, cold["stream"])
        for s in seen:   # every child saw the same device as the probe
            _check(s["device"]["kind"] == device["kind"],
                   f"{s['phase']}: device kind {s['device']['kind']!r} != "
                   f"the probe's {device['kind']!r}")
        ok = True
    except PhaseFailed as e:
        note(phase="failed", error=str(e))
    except subprocess.TimeoutExpired as e:
        note(phase="failed", error=f"timeout: {e}")
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    note(phase="end", seconds=round(time.monotonic() - _T0, 1))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def _versions() -> dict:
    import importlib.metadata as md

    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = None
    return out


if __name__ == "__main__":
    raise SystemExit(main())
