"""Driver of the serving entry point: builds ``runtime/server.InferenceServer``
in the run's process as ``frontend/cli.py`` ``serve`` does, on a port of the
loopback interface, and feeds it over HTTP from a child process that imports
no JAX (``harness/client.py``).

Flags (``entries.serve`` of the configuration): ``slots``, ``kv_page_size``,
``kv_pages``, ``prefill_chunk``; everything else is the default a user gets.
The traffic is an ``open`` loop (requests leave on their due times) or a
``closed`` one (each client sends its next request when its last completes).

``serve`` hands out tokens and no logits, so correctness is teacher-forced:
the float32 reference runs over the SERVED stream of four check requests
(64 positions each, two sharing a two-page prefix), and every served token's
reference logit must be within the configuration's tolerance of the
reference's maximum at its position.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from ..harness import model, reference, runtime, traffic
from ..harness.cells import BENCH_DIR
from ..harness.runtime import note

CHECK_POSITIONS = 64
CHECK_SHARED_TOKENS = 34     # BOS + space + 32 characters: two 16-pages
_METRIC_LINE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? ([-+0-9.eE]+|NaN)$")


def _build_server(spec, tree, tok, flags, seed: int):
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy
    from distributed_llama_tpu.runtime.server import InferenceServer

    # as cmd_serve: the layout policy looks at the dispatch width, and must
    # land before the tree is packed (ContinuousEngine -> params_to_device)
    apply_q40_body_policy(spec, rows=int(flags["slots"]))
    return InferenceServer(
        spec, tree, tok, "127.0.0.1", 0, int(flags["slots"]), 64, 0.8, 0.9,
        seed, prefill_chunk=int(flags["prefill_chunk"]),
        page_size=int(flags["kv_page_size"]),
        kv_pages=int(flags["kv_pages"]), quiet=True)


def run_client(base_url: str, plan: dict, t0: float, seconds: float,
               keep_tokens: bool = False, on_tick=None) -> dict:
    """Run the load client as a child process and return its records file.
    ``on_tick`` is called about every 50 ms while it runs."""
    with tempfile.TemporaryDirectory(prefix="bench_client_") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        out_path = os.path.join(tmp, "records.json")
        with open(spec_path, "w", encoding="utf-8") as fh:
            json.dump({"base_url": base_url, "loop": plan["loop"],
                       "clients": plan["clients"], "t0": t0,
                       "seconds": seconds, "temperature": 0,
                       "keep_tokens": keep_tokens, "timeout_s": 120,
                       "drain_s": 90}, fh)
        proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "harness", "client.py"),
             spec_path, out_path])
        try:
            while proc.poll() is None:
                if on_tick is not None:
                    on_tick()
                time.sleep(0.05)
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        if not os.path.exists(out_path):
            raise RuntimeError(f"load client exited {proc.returncode} "
                               f"without records")
        with open(out_path, encoding="utf-8") as fh:
            return json.load(fh)


def check_requests(seed: int) -> dict:
    """Four requests of 64 positions: two share a two-page prefix."""
    import random

    rng = random.Random(seed ^ 0xC4EC)
    chars = traffic.CHARS
    shared = "".join(rng.choice(chars) for _ in range(
        CHECK_SHARED_TOKENS - traffic.PROMPT_OVERHEAD))
    prompts = [shared + "".join(rng.choice(chars) for _ in range(6)),
               shared + "".join(rng.choice(chars) for _ in range(6)),
               "".join(rng.choice(chars) for _ in range(22)),
               "".join(rng.choice(chars) for _ in range(10))]
    reqs = []
    for i, p in enumerate(prompts):
        n = len(p) + traffic.PROMPT_OVERHEAD
        reqs.append({"id": i, "due_s": None, "prompt": p, "prompt_tokens": n,
                     "output_tokens": CHECK_POSITIONS - n + 1})
    # the two sharing requests in turn (the second must find the first's
    # pages), the other two beside them
    return {"loop": "closed", "clients": [reqs[:2], reqs[2:3], reqs[3:]]}


def check_streams(records, plan, tok, tree, sizes, config) -> dict:
    """Teacher-force the reference on what ``serve`` streamed."""
    by_id = {r["id"]: r for r in records}
    rows, spans = [], []
    for reqs in plan["clients"]:
        for req in reqs:
            rec = by_id.get(req["id"])
            if rec is None or not rec["ok"]:
                return {"what": "served check requests", "ok": False,
                        "detail": {"error": (rec or {}).get("error",
                                                            "no record")}}
            prompt = tok.encode(req["prompt"], bos=True, eos=False)
            n = len(prompt)
            if n != req["prompt_tokens"] or rec["tokens"][:n - 1] != prompt[1:]:
                return {"what": "served check requests", "ok": False,
                        "detail": {"error": "prompt echo differs from the "
                                            "encoded prompt"}}
            seq = prompt + rec["tokens"][n - 1:]
            rows.append(seq[:CHECK_POSITIONS])
            spans.append((n, rec["tokens"][n - 1:]))
    want = reference.logits(tree, sizes, np.asarray(rows),
                            rope_base=config["rope_theta"])
    worst = 0.0
    for b, (n, served) in enumerate(spans):
        for i, t in enumerate(served):
            row = want[b, n - 1 + i]
            worst = max(worst, float(row.max() - row[t]))
    tol = float(config["check"]["logit_tolerance"])
    return {"what": f"served tokens vs the float32 reference's maximum, "
                    f"{len(rows)} requests x {CHECK_POSITIONS} positions, "
                    f"teacher-forced",
            "ok": bool(worst <= tol),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol}}


def parse_metrics(text: str) -> dict:
    """Prometheus text to ``{name: summed value}`` (labels summed over)."""
    out: dict = {}
    for line in text.splitlines():
        m = _METRIC_LINE.match(line)
        if m and m.group(3) != "NaN":
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(3))
    return out


def counters(server, compiles) -> dict:
    """Exact counts made where the work happens: ``ContinuousStats``, the
    allocator, and the ``/metrics`` registry (read in process, the same text
    ``GET /metrics`` serves)."""
    eng = server.engine
    m = parse_metrics(server.registry.expose())
    alloc = eng.allocator
    return {
        "steps": eng.stats.steps, "sum_active": eng.stats.sum_active,
        "prefill_chunks": eng.stats.prefill_chunks,
        "queue_wait_sum_s": m.get("dllama_request_queue_wait_seconds_sum", 0),
        "queue_wait_count": m.get("dllama_request_queue_wait_seconds_count",
                                  0),
        "generated_tokens": m.get("dllama_generated_tokens_total", 0),
        "engine_compile_events": m.get(
            "dllama_engine_compile_events_total", 0),
        "prefix_hits": alloc.prefix_hits if alloc is not None else 0,
        "evictions": alloc.evictions if alloc is not None else 0,
        "compiles": compiles.count,
    }


class Served:
    """The server, built, checked and warm; ``window`` feeds it one window
    of traffic. ``tools/knee_sweep.py`` runs several windows on one."""

    def __init__(self, cell, args):
        import jax

        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        model.check_runnable(config)
        sizes = model.sizes_of(config)
        spec = model.program_spec(sizes)
        note(f"device {self.device}; compile cache {cache}")
        tree = model.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        self.server = _build_server(spec, tree, tok, flags, args.seed)
        jax.block_until_ready(self.server.engine.params)
        note("server built, weights placed")
        if args.trace:
            runtime.wrap_span(self.server.engine, "step_many", "serve.step")
        self.server.start()
        self.base_url = f"http://127.0.0.1:{self.server.port}"
        try:
            # warm-up and check in one: the four check requests run the
            # prefill chunk, the decode step, and the gather and scatter
            # programs (every program this engine has at these flags)
            plan = check_requests(args.seed)
            doc = run_client(self.base_url, plan, time.monotonic(), 600.0,
                             keep_tokens=True)
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            note(f"check: {self.checks[0]['detail']}")
            hits = self.server.engine.allocator.prefix_hits
            self.checks.append({
                "what": "the second check request found the first's prefix "
                        "pages", "ok": bool(hits >= 1),
                "detail": {"prefix_hits": hits}})
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.server.stop()

    def window(self, plan: dict, seconds: float) -> dict:
        """One window of ``plan``. Returns the client's records, the
        counters at the window's start and END (the client goes on until
        the requests in flight have drained), and the trace if one ran."""
        server, compiles, args = self.server, self.compiles, self.args
        alloc = server.engine.allocator
        peak_used = [alloc.n_pages - alloc.n_free]
        at_end: dict = {}
        t0 = time.monotonic() + 0.25     # the client is up by then

        def tick():
            peak_used[0] = max(peak_used[0], alloc.n_pages - alloc.n_free)
            if not at_end and time.monotonic() >= t0 + seconds:
                at_end.update(counters(server, compiles))

        before = counters(server, compiles)
        out = {"trace": None}
        if args.trace:
            tracer = runtime.Tracer(self.cell.traffic.get("trace_seconds", 4),
                                    args.keep_trace)

            # not from the window's first moment: a closed loop opens with
            # every client sending at once, which is no steady state
            t_trace = t0 + min(float(self.cell.traffic.get(
                "trace_start_s", 0.0)), seconds / 4)

            def traced():
                time.sleep(max(0.0, t_trace - time.monotonic()))
                tracer.start()
                time.sleep(tracer.seconds)
                tracer.stop()

            th = threading.Thread(target=traced)
            th.start()
            doc = run_client(self.base_url, plan, t0, seconds, on_tick=tick)
            th.join()
            out["trace"] = tracer.finish()
        else:
            doc = run_client(self.base_url, plan, t0, seconds, on_tick=tick)
        after = at_end or counters(server, compiles)
        before.update(peak_pages_used=0, pool_pages=0)
        after.update(peak_pages_used=peak_used[0], pool_pages=alloc.n_pages)
        if doc.get("stuck_threads"):
            note(f"{doc['stuck_threads']} client thread(s) never finished")
        out.update(records=doc["records"], before=before, after=after)
        return out


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = traffic.generate(cell.traffic, args.seed, args.seconds)
        setup_s = time.time() - t_start + 0.25
        w = served.window(plan, args.seconds)
    note(f"window over: {len(w['records'])} requests")
    return runtime.Run(
        cell=cell, seed=args.seed, window_s=float(args.seconds),
        setup_s=setup_s, records=w["records"], device=served.device,
        counters_before=w["before"], counters_after=w["after"],
        trace=w["trace"], checks=served.checks)


def narrate(run) -> list:
    """Utilisations that are no metric: printed on earlier lines."""
    from ..harness import costs

    steps = run.delta("steps")
    if not steps:
        return []
    qs = (10, 25, 50, 75, 90, 95, 99)
    shape = {name: [round(runtime.percentile(vals, q), 1) for q in qs]
             for name, vals in (("ttft_ms", run.ttft_ms()),
                                ("gap_ms", run.gaps_ms())) if vals}
    gbps = (costs.q40_weight_bytes(model.sizes_of(run.cell.config)) * steps
            / run.window_s / 1e9)
    return [f"percentiles {qs} of {len(run.in_window())} requests: {shape}",
            f"weights_gbps {gbps:.1f} (weight bytes x steps over the "
            f"window: an end-to-end utilisation, not a roofline share); "
            f"prefix_hits {run.delta('prefix_hits')}, evictions "
            f"{run.delta('evictions')}, engine_compile_events "
            f"{run.delta('engine_compile_events')}"]
