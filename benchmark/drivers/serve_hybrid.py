"""Driver of the serving entry point for a SambaY configuration
(``harness/hybrid.py``): ``drivers/serve.py``'s traffic, trace and ``Run`` and
``drivers/serve_retention.py``'s teacher-forced check, imported, around a
model built from the hybrid harness. What is its own: how the server is
built (``serve`` with pages for the ONE full layer and a slot of fixed size
a sequence), the check's requests, the client (``harness/cut_client.py``:
this traffic's requests outlast the window, so the client cuts what is
still streaming when the window closes) and the counters of the new caches.

The check teacher-forces the float32 reference (the whole forward at every
position: no state, no cache) on the SERVED streams and compares EVERY
served position: no top-k, so no margin rule and no position left out. It is
made on the timed weights and programs, outside the window: more requests
than slots, all connecting at once (``check_requests``), so the rows fill, a
queue stands and every later admission inserts into a row that holds another
sequence's state, ring and pages. The first ``len(CHECK_PROMPTS)`` have the
window's own prompt lengths (2,560 tokens = 20 admission chunks, past the
512 window five times over, over 160 pages; 1,024; 384; 128), so the check
is the warm-up of every program the window runs too.

The same positions, histories and comparison also read the CONTROL: what
the reference picks when every product's operands are rounded to bfloat16
first. It has to come out over the tolerance, and is reported beside the
served streams' reading in every run; it does not decide ``correct``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from ..harness import hybrid, model, runtime, traffic
from ..harness.runtime import note
from . import serve as dense
from .serve_retention import served_rows, shortfalls

# (prompt tokens, output tokens) of the first check requests; the others
# are drawn from the seed: prompts of 3 to 72 tokens, outputs of 12 to 48
CHECK_PROMPTS = ((2560, 20), (2560, 14), (1024, 24), (1024, 16), (384, 24),
                 (128, 24), (3, 29), (140, 18))
CHECK_EXTRA = 8      # requests beyond the slots: rows are reused


def _build_server(spec, tree, tok, flags, seed: int):
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy
    from distributed_llama_tpu.runtime.server import InferenceServer

    apply_q40_body_policy(spec, rows=int(flags["slots"]))
    return InferenceServer(
        spec, tree, tok, "127.0.0.1", 0, int(flags["slots"]), 64, 0.8, 0.9,
        seed, prefill_chunk=int(flags["prefill_chunk"]),
        page_size=int(flags["kv_page_size"]), kv_pages=int(flags["kv_pages"]),
        quiet=True)


def check_requests(seed: int, slots: int, positions: int = 8704) -> dict:
    """``slots + CHECK_EXTRA`` requests, one a client, all arriving at once,
    no two sharing a prefix (a toy configuration of fewer ``positions``
    leaves out the fixed shapes that do not fit it)."""
    import random

    rng = random.Random(seed ^ 0x5A3B)
    shapes = [s for s in CHECK_PROMPTS if sum(s) <= positions]
    while len(shapes) < slots + CHECK_EXTRA:
        shapes.append((rng.randint(3, 72), rng.randint(12, 48)))
    reqs = [{"id": i, "due_s": None, "prompt_tokens": n, "output_tokens": out,
             "prompt": "".join(rng.choice(traffic.CHARS) for _ in range(
                 n - traffic.PROMPT_OVERHEAD))}
            for i, (n, out) in enumerate(shapes)]
    return {"loop": "closed", "clients": [[r] for r in reqs]}


def check_streams(records, plan, tok, tree, sizes, config,
                  group: int = 16, long_group: int = 6) -> dict:
    """Teacher-force the reference on what ``serve`` streamed, and read the
    control on the same positions. Rows of over 256 positions run
    ``long_group`` at a time, the others ``group``, each lot padded to one
    shape (two programs a layer and precision)."""
    what = "served check requests"
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": what, "ok": False, "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    worst = control = 0.0
    compared = control_over = 0
    lots = [([r for r in rows if len(r[0]) > 256], long_group),
            ([r for r in rows if len(r[0]) <= 256], group)]
    for lot, size in lots:
        if not lot:
            continue
        width = max(len(r) for r, _, _ in lot)
        span = max(len(served) for _, _, served in lot)
        for lo in range(0, len(lot), size):
            part = lot[lo:lo + size]
            part += [part[-1]] * (size - len(part))       # one shape
            # a short row is padded: every layer is causal, so what follows
            # a position does not reach it
            tokens = np.asarray([r + [0] * (width - len(r))
                                 for r, _, _ in part])
            keep = np.asarray([[min(n - 1 + i, width - 1)
                                for i in range(span)] for _, n, _ in part])
            want = hybrid.logits(tree, sizes, tokens, keep=keep)
            low = hybrid.logits(tree, sizes, tokens, keep=keep,
                                precision="bfloat16")
            for b, (_, n, served) in enumerate(part[:len(lot) - lo]):
                k = len(served)
                short = shortfalls(want[b, :k], served)
                worst = max(worst, float(short.max()))
                compared += k
                ctl = shortfalls(want[b, :k], low[b, :k].argmax(axis=-1))
                control = max(control, float(ctl.max()))
                control_over += int((ctl > tol).sum())
    return {"what": f"served tokens vs the float32 full-forward reference's "
                    f"maximum, {len(rows)} requests of "
                    f"{min(len(r) for r, _, _ in rows) + 1} to "
                    f"{max(len(r) for r, _, _ in rows) + 1} positions, "
                    f"teacher-forced, every served position",
            "ok": bool(worst <= tol),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_compared": compared,
                       "control_bfloat16_max_shortfall": control,
                       "control_positions_over_tolerance": control_over}}


def counters(server, compiles) -> dict:
    """``drivers/serve.counters`` and the new caches'."""
    out = dense.counters(server, compiles)
    st = server.engine.stats
    out.update({k: getattr(st, k, 0) for k in (
        "state_bytes", "window_bytes", "shared_kv_positions",
        "window_kv_positions", "prompt_positions", "xdec_positions",
        "admit_prefills")})
    return out


def whole_mix_first(plan: dict, prompt_mix: dict, slots: int) -> dict:
    """``plan`` with its clients reordered so that the FIRST requests of
    the first ``slots`` clients hold the prompt mix in its exact proportions
    (``slots`` x weight, the largest remainders rounded up: 6, 10, 10 and 6
    of 128, 384, 1,024 and 2,560 tokens at 32 slots), each kind's clients
    in the order the seed dealt them. Those clients send first
    (``cut_client``'s ``first_wave``), so the window's one fill is the same
    amount of work in every run, as the generator's decks make a whole
    window's; the seed sets which prompts and in which order. The requests
    are what the generator made, all of them. A plan that cannot give the
    proportions (fewer clients than slots, a kind too rare) is returned as
    it is."""
    total = sum(float(w) for w in prompt_mix.values())
    share = {int(k): slots * float(w) / total for k, w in prompt_mix.items()}
    want = {k: int(v) for k, v in share.items()}
    for k in sorted(share, key=lambda k: want[k] - share[k])[
            :slots - sum(want.values())]:
        want[k] += 1
    clients = plan["clients"]
    first, rest = [], []
    for c in clients:
        kind = c[0]["prompt_tokens"] if c else None
        if want.get(kind, 0) > 0:
            want[kind] -= 1
            first.append(c)
        else:
            rest.append(c)
    if any(want.values()):
        return plan
    return dict(plan, clients=first + rest)


def run_cut_client(base_url: str, plan: dict, t0: float, seconds: float,
                   first_wave: int, on_tick=None) -> dict:
    """``drivers/serve.run_client`` with ``harness/cut_client.py``."""
    with tempfile.TemporaryDirectory(prefix="bench_client_") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        out_path = os.path.join(tmp, "records.json")
        with open(spec_path, "w", encoding="utf-8") as fh:
            json.dump({"base_url": base_url, "loop": plan["loop"],
                       "clients": plan["clients"], "t0": t0,
                       "seconds": seconds, "temperature": 0,
                       "first_wave": first_wave}, fh)
        proc = subprocess.Popen(
            [sys.executable, os.path.join(dense.BENCH_DIR, "harness",
                                          "cut_client.py"),
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


class Served(dense.Served):
    """``drivers/serve.Served`` over a hybrid model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        hybrid.check_runnable(config)
        sizes = self.sizes = hybrid.sizes_of(config)
        spec = hybrid.program_spec(sizes)   # a program without the fields
        #                            stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = hybrid.codec_tree(sizes, args.seed)
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
            plan = check_requests(args.seed, int(flags["slots"]),
                                  sizes["seq_len"])
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   900.0, keep_tokens=True)
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            note(f"check: {self.checks[0]['detail']}")
            st = self.server.engine.stats
            per = st.xdec_positions / max(st.admit_prefills, 1)
            self.checks.append({
                "what": "state, rings and pages are resident, rows ran "
                        "ahead, and the cross-decoder ran at one position "
                        "a prompt",
                "ok": bool(st.state_bytes > 0 and st.window_bytes > 0
                           and st.steps_ahead > 0 and per <= 1.0 + 1e-9),
                "detail": {"state_bytes": st.state_bytes,
                           "window_bytes": st.window_bytes,
                           "steps_ahead": st.steps_ahead,
                           "xdec_positions": st.xdec_positions,
                           "prompt_positions": st.prompt_positions,
                           "admissions": st.admit_prefills,
                           "ssm_min_decay": st.ssm_min_decay}})
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        """``drivers/serve.Served.window`` with the cutting client."""
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
        th = None
        if args.trace:
            tracer = runtime.Tracer(self.cell.traffic.get("trace_seconds", 4),
                                    args.keep_trace)
            t_trace = t0 + min(float(self.cell.traffic.get(
                "trace_start_s", 0.0)), seconds / 2)

            def traced():
                time.sleep(max(0.0, t_trace - time.monotonic()))
                tracer.start()
                time.sleep(tracer.seconds)
                tracer.stop()

            th = threading.Thread(target=traced)
            th.start()
        slots = int(self.cell.config["entries"]["serve"]["slots"])
        plan = whole_mix_first(plan, self.cell.traffic["prompt_tokens"],
                               slots)
        doc = run_cut_client(self.base_url, plan, t0, seconds, slots,
                             on_tick=tick)
        if th is not None:
            th.join()
            out["trace"] = tracer.finish()
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
        st = served.server.engine.stats
        low = st.ssm_min_decay
    cut = sum(bool(r.get("cut")) for r in w["records"])
    note(f"window over: {len(w['records'])} requests, {cut} of them cut by "
         f"their clients at the window's end; smallest state decay of any "
         f"decode step {low:.3g}")
    return runtime.Run(
        cell=cell, seed=args.seed, window_s=float(args.seconds),
        setup_s=setup_s, records=w["records"], device=served.device,
        counters_before=w["before"], counters_after=w["after"],
        trace=w["trace"], checks=served.checks)


def narrate(run) -> list:
    """Utilisations that are no metric: printed on earlier lines."""
    steps = run.delta("steps")
    if not steps:
        return []
    sizes = hybrid.sizes_of(run.cell.config)
    rows = int(run.cell.config["entries"]["serve"]["slots"])
    shared = hybrid.shared_kv_step_bytes(
        sizes, run.delta("shared_kv_positions") / steps)
    ring = hybrid.window_step_bytes(
        sizes, run.delta("window_kv_positions") / steps)
    state = hybrid.ssm_step_bytes(sizes, rows)
    dense_b = hybrid.dense_q40_bytes(sizes)
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step",
            f"a mean step moves {shared / 1e9:.2f} GB of the shared K / V, "
            f"{ring / 1e9:.2f} GB of window rings, {state / 1e9:.2f} GB of "
            f"state ({rows} rows) and {dense_b / 1e9:.2f} GB of weights: "
            f"step_gbps {(shared + ring + state + dense_b) * steps / run.window_s / 1e9:.1f} "
            f"(an end-to-end utilisation, not a roofline share)"]
