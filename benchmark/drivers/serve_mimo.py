"""Driver of the serving entry point for ``harness/mimo.py``'s configuration
(MiMo-V2-Flash: window layers with a sink beside full layers, KV heads a
kind, K 192 / V 128, a SHARE of the experts): ``drivers/serve_laguna.py``'s
check, counters and plan and ``drivers/serve_hybrid.py``'s first wave,
imported, around a model built from the mimo harness. What is its own: a
window that opens after the first wave's admissions, the sink's control and
the held share's counters.

The check is ``serve_laguna.check_streams`` (40 requests at once on the 32
slots, the configuration's ``check.long_requests`` first: prompts of 8,064,
4,096, 4,096, 2,048, 2,048 and 1,024 tokens, so sixteen-chunk admissions,
rings wrapped 63 times, over 500 pages a row in each full layer's pool; 32
short ones, eight of the 40 on reused rows; every served position compared
on logits with the float32 reference teacher-forced on the served streams;
strictly up to a
request's first router near-tie, by the share of positions over the
tolerance after it; the latest doubtful decision reversed where a request
fails; the bfloat16 control through the same rules) run on THIS
configuration's reference: ``serve_laguna`` and ``laguna.with_reversals``
call the reference as ``laguna.logits``, and for the time of the check that
name is ``mimo.logits`` (``_reference``). The chip holds 32 of 256 experts:
``serve_latent.py``'s rules for a held share are these rules already (a pair
on an expert held elsewhere adds nothing here, in the program and in the
reference alike), and a reversal moves the reference only where one of the
two tied experts is held; elsewhere it changes nothing, cures nothing and
does not stand. One rule is this cell's own (``held_share_verdict``): the
bfloat16 control has to come out over ONE of the two limits, not over each.
With an eighth of the experts held, a decision that bfloat16 takes the other
way is invisible seven times in eight, and the control's worst request moves
4 of its 90 served positions where Laguna's, with every expert held, moves
two thirds: its SHARE reads near any limit that leaves the served streams
room (0.044 on the first seed), while its worst position stays 170 times
over the tolerance.

The check is ``CHECK_REQUESTS`` (40) requests at once on the 32 slots, not
64: the float32 reference of eight rows in a lot 512 wide takes 12.5 s on
the chip (a run with 64 took 300 s warm and 556 cold, with 48 260 to 304 s)
and the driver stops a run at 360 s (ROADMAP S9); the long requests are the
configuration's, all of them.

A SECOND control (``sink_control``): the first short lot read again by the
reference WITHOUT the sink column; its picks must come out over the
tolerance at more than ``SINK_CONTROL_SHARE`` of the positions (a program
that dropped the sink would read so), and the mean share of a sliding
head's mass that lies on the sink (``sink_mass_share``) must be at least
``check.sink_mass_floor``: a sink that holds nothing tests nothing.

The window (``Served.window``; the traffic file must say ``window_opens``
``after_first_wave``, ``first_wave`` ``whole_mix`` and ``window_end``
``cut_by_client``): the clients start, the first 32 hold 8 prompts of each
length, and the driver polls the program's count of requests that have
streamed their first token; when the first wave has, it reads the counters,
stamps the moment and tells the client (``harness/wave_client.py``), which
cuts every stream ``seconds`` later. Records come back stamped from the
client's start and are moved to the window's. Set-up ends where the window
opens. A check fails the run if an admission chunk ran inside the window.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from ..harness import laguna, mimo, model, runtime
from ..harness.runtime import note
from . import serve as dense
from . import serve_laguna
from .serve_hybrid import whole_mix_first
from .serve_laguna import TRACED, ended_at_the_cut, window_plan
from .serve_retention import served_rows, shortfalls

CHECK_REQUESTS = 40
SINK_CONTROL_SHARE = 0.25
"""The share of the sink control's positions that must fall short by more
than the tolerance (the chip's readings and the served streams': the
configuration's ``check.why``)."""
FIRST_TOKENS = "dllama_request_prefill_seconds_count"
_lag_counters = serve_laguna.counters


@contextlib.contextmanager
def _reference():
    """``serve_laguna.check_streams`` and ``laguna.with_reversals`` on this
    configuration's reference (the module docstring says why a name is
    rebound and no code copied)."""
    saved, laguna.logits = laguna.logits, mimo.logits
    try:
        yield
    finally:
        laguna.logits = saved


def held_share_verdict(check: dict) -> dict:
    """``serve_laguna.check_streams``'s result with the control held to ONE
    of its two limits (the module docstring says why): the served streams'
    rules are as they were."""
    d = check["detail"]
    if "error" in d:
        return check
    tol, limit = d["tolerance"], d["excused_share_limit"]
    share = d["control_bfloat16_excused_share"]
    check["ok"] = bool(
        d["max_logit_shortfall"] <= tol
        and 2 * (d["positions_strict"] + d["positions_judged_by_share"])
        >= d["positions_served"] and d["max_excused_share"] < limit
        and d["decisions_reversed"] <= d["decisions_reversed_limit"]
        and (d["control_bfloat16_max_shortfall"] > tol
             or (share is not None and share > limit)))
    check["what"] += (", the control over the tolerance OR over the share "
                      "limit (a share of the experts is held)")
    return check


def sink_control(records, plan, tok, tree, sizes, config) -> dict:
    """The first ``serve_laguna.GROUP`` short rows of the check through the
    reference with and without the sink column."""
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": "sink control", "ok": False,
                "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    part, width = next((p, w) for p, w in serve_laguna._lots(rows)
                       if w <= serve_laguna.LONG)
    lot = [rows[i] for i in part]
    lot += [lot[-1]] * (serve_laguna.GROUP - len(lot))
    span = max(len(served) for _, _, served in lot)
    tokens = np.asarray([r + [0] * (width - len(r)) for r, _, _ in lot])
    keep = np.asarray([[min(n - 1 + i, width - 1) for i in range(span)]
                       for _, n, _ in lot])
    lengths = [len(r) for r, _, _ in lot]
    stats: dict = {}
    want = mimo.logits(tree, sizes, tokens, keep=keep, lengths=lengths,
                       stats=stats)[0]["highest"]
    bare = mimo.logits(tree, sizes, tokens, keep=keep, lengths=lengths,
                       sink=False)[0]["highest"].argmax(-1)
    short = np.concatenate([
        shortfalls(want[b, :len(served)], bare[b, :len(served)])
        for b, (_, _, served) in enumerate(lot[:len(part)])])
    share = float((short > tol).mean())
    mass = stats.get("sink_mass_share", 0.0)
    floor = float(config["check"]["sink_mass_floor"])
    return {"what": f"the reference WITHOUT the sink column, its picks on "
                    f"{len(part)} short requests' served positions, must "
                    f"fall short at more than {SINK_CONTROL_SHARE} of them, "
                    f"and a sliding head's sink must hold at least {floor} "
                    f"of its mass",
            "ok": bool(share > SINK_CONTROL_SHARE and mass >= floor),
            "detail": {"control_no_sink_share_over_tolerance": share,
                       "control_no_sink_max_shortfall": float(short.max()),
                       "control_no_sink_positions": int(short.size),
                       "sink_mass_share": mass, "sink_mass_floor": floor}}


def counters(server, compiles) -> dict:
    """``serve_laguna.counters`` and the pairs that landed on a held
    expert."""
    out = _lag_counters(server, compiles)
    out["moe_local_pairs"] = getattr(server.engine.stats, "moe_local_pairs",
                                     0)
    return out


def first_tokens(server) -> float:
    return dense.parse_metrics(server.registry.expose()).get(FIRST_TOKENS, 0)


def run_wave_client(base_url: str, plan: dict, t0: float, seconds: float,
                    first_wave: int, open_limit_s: float, on_tick) -> dict:
    """``serve_hybrid.run_cut_client`` with ``harness/wave_client.py``.
    ``on_tick(opened_path)`` is called about every 20 ms until the window
    has opened (it writes ``opened_path`` when it finds that it has; each
    call reads the registry, on the server's own interpreter) and every 50
    ms after."""
    with tempfile.TemporaryDirectory(prefix="bench_client_") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        out_path = os.path.join(tmp, "records.json")
        opened_path = os.path.join(tmp, "opened.json")
        with open(spec_path, "w", encoding="utf-8") as fh:
            json.dump({"base_url": base_url, "loop": plan["loop"],
                       "clients": plan["clients"], "t0": t0,
                       "seconds": seconds, "temperature": 0,
                       "first_wave": first_wave, "opened_path": opened_path,
                       "open_limit_s": open_limit_s}, fh)
        proc = subprocess.Popen(
            [sys.executable, os.path.join(dense.BENCH_DIR, "harness",
                                          "wave_client.py"),
             spec_path, out_path])
        try:
            while proc.poll() is None:
                time.sleep(0.05 if on_tick(opened_path) else 0.02)
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        if proc.returncode == 2 or not os.path.exists(out_path):
            raise RuntimeError(
                f"load client exited {proc.returncode}: the window did not "
                f"open within {open_limit_s} s (the first wave's requests "
                f"had not all streamed a token) or no records were written")
        with open(out_path, encoding="utf-8") as fh:
            return json.load(fh)


class Served(dense.Served):
    """``drivers/serve.Served`` over the mimo harness's model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        mimo.check_runnable(config)
        sizes = self.sizes = mimo.sizes_of(config)
        spec = mimo.program_spec(sizes)     # a program without the fields
        #                              stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = mimo.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        mimo.settle_shared_positions(
            tree, sizes, tok.encode("", bos=True, eos=False), args.seed)
        note("router margins at the shared positions settled")
        self.server = dense._build_server(spec, tree, tok, flags, args.seed)
        jax.block_until_ready(self.server.engine.params)
        note("server built, weights placed")
        if args.trace:
            runtime.wrap_span(self.server.engine, "step_many", "serve.step")
        self.server.start()
        self.base_url = f"http://127.0.0.1:{self.server.port}"
        try:
            plan = serve_laguna.check_requests(
                args.seed, min(CHECK_REQUESTS // 2, int(flags["slots"])),
                config["check"].get("long_requests", ()),
                cell.traffic["prompt_tokens"])
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   600.0, keep_tokens=True)
            note(f"check requests served; memory peak of serve alone "
                 f"{runtime.memory_peak_bytes()} B")
            with _reference():
                self.checks = [held_share_verdict(serve_laguna.check_streams(
                    doc["records"], plan, tok, tree, sizes, config))]
            note(f"check: {self.checks[0]['detail']}")
            self.checks.append(sink_control(doc["records"], plan, tok, tree,
                                            sizes, config))
            note(f"check: {self.checks[1]['detail']}")
            st = self.server.engine.stats
            mx = spec.mixers
            n_exp = sizes["n_layers"] - sizes["dense_layers"]
            self.checks.append({
                "what": "rings are resident at their exact size, pages were "
                        "used, some but not all routed pairs landed on a "
                        "held expert, and rows ran ahead",
                "ok": bool(
                    st.window_bytes == int(flags["slots"]) * mx.count(
                        "sliding") * mx.window * mimo.kv_held_bytes(
                            sizes, "sliding")
                    and st.shared_kv_positions > st.window_kv_positions > 0
                    and 0 < st.moe_local_pairs < st.moe_pairs
                    and st.moe_load is not None
                    and st.moe_pairs % (n_exp * sizes["n_active_experts"])
                    == 0 and st.steps_ahead > 0),
                "detail": {"window_bytes": st.window_bytes,
                           "shared_kv_positions": st.shared_kv_positions,
                           "window_kv_positions": st.window_kv_positions,
                           "moe_pairs": st.moe_pairs,
                           "moe_local_pairs": st.moe_local_pairs,
                           "moe_active": st.moe_active,
                           "steps_ahead": st.steps_ahead}})
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        """The clients start now; the window opens when each of the first
        wave's requests has streamed its first token, and closes ``seconds``
        later (the module docstring). Counters are read where the window
        opens and closes and, in a traced run, where the profiler starts
        and stops (``serve_laguna.Served.window`` says why)."""
        server, compiles, args = self.server, self.compiles, self.args
        mix = self.cell.traffic
        if (mix.get("first_wave"), mix.get("window_opens"),
                mix.get("window_end")) != ("whole_mix", "after_first_wave",
                                           "cut_by_client"):
            raise ValueError("serve_mimo's window is the stratified first "
                             "wave, opened after its admissions, and the "
                             "cutting client: the traffic file has to say so "
                             "(first_wave, window_opens, window_end)")
        slots = int(self.cell.config["entries"]["serve"]["slots"])
        alloc = server.engine.allocator
        peak_used = [0]
        state: dict = {}          # opened_at, before, at_end
        traced_: dict = {}
        t0 = time.monotonic() + 0.25     # the client is up by then
        base = first_tokens(server)
        tracer = runtime.Tracer(mix.get("trace_seconds", 4),
                                args.keep_trace) if args.trace else None

        def traced():
            at = state["opened_at"] + min(float(mix.get("trace_start_s",
                                                        0.0)), seconds / 2)
            time.sleep(max(0.0, at - time.monotonic()))
            tracer.start()
            lo = counters(server, compiles)
            time.sleep(tracer.seconds)
            hi = counters(server, compiles)
            tracer.stop()
            traced_.update({"trace_" + k: hi[k] - lo[k] for k in TRACED})

        th = threading.Thread(target=traced) if tracer else None

        def tick(opened_path) -> bool:
            if "opened_at" not in state:
                if first_tokens(server) - base < slots:
                    return False
                state["before"] = counters(server, compiles)
                state["opened_at"] = time.monotonic()
                state["opened_wall"] = time.time()
                with open(opened_path + ".tmp", "w", encoding="utf-8") as fh:
                    json.dump({"opened_at": state["opened_at"]}, fh)
                os.replace(opened_path + ".tmp", opened_path)
                note(f"window opened {state['opened_at'] - t0:.1f} s after "
                     f"the clients started: {slots} requests have streamed "
                     f"a token")
                if th is not None:
                    th.start()
            peak_used[0] = max(peak_used[0], alloc.n_pages - alloc.n_free)
            if "at_end" not in state and time.monotonic() >= (
                    state["opened_at"] + seconds):
                state["at_end"] = counters(server, compiles)
            return True

        plan = whole_mix_first(plan, mix["prompt_tokens"], slots)
        doc = run_wave_client(self.base_url, plan, t0, seconds, slots,
                              float(mix.get("open_limit_s", 120)), tick)
        out = {"trace": None}
        if th is not None:
            th.join()
            out["trace"] = tracer.finish()
        before = state["before"]
        after = state.get("at_end") or counters(server, compiles)
        before.update(peak_pages_used=0, pool_pages=0,
                      **dict.fromkeys(traced_, 0))
        after.update(peak_pages_used=peak_used[0], pool_pages=alloc.n_pages,
                     **traced_)
        if doc.get("stuck_threads"):
            note(f"{doc['stuck_threads']} client thread(s) never finished")
        shift = state["opened_at"] - t0
        records = [ended_at_the_cut(r) for r in doc["records"]]
        for r in records:           # from the client's start to the window's
            r["stamps"] = [t - shift for t in r["stamps"]]
            for k in ("due", "sent", "done"):
                if r[k] is not None:
                    r[k] -= shift
        chunks = after["prefill_chunks"] - before["prefill_chunks"]
        self.checks.append({
            "what": "no admission chunk ran inside the window",
            "ok": chunks == 0, "detail": {"prefill_chunks_in_window": chunks}})
        out.update(records=records, before=before, after=after,
                   setup_wall=state["opened_wall"], fill_s=shift)
        return out


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = window_plan(cell.traffic, args.seed, args.seconds)
        w = served.window(plan, args.seconds)
    cut = sum(bool(r.get("cut")) for r in w["records"])
    note(f"window over: the fill took {w['fill_s']:.1f} s (set-up); "
         f"{len(w['records'])} requests, {cut} of them cut by their clients "
         f"at the window's end")
    return runtime.Run(
        cell=cell, seed=args.seed, window_s=float(args.seconds),
        setup_s=w["setup_wall"] - t_start, records=w["records"],
        device=served.device, counters_before=w["before"],
        counters_after=w["after"], trace=w["trace"], checks=served.checks)


def narrate(run) -> list:
    """Utilisations that are no metric: printed on earlier lines."""
    steps = run.delta("steps")
    if not steps:
        return []
    sizes = mimo.sizes_of(run.cell.config)
    active = run.delta("moe_active") / steps
    ring = mimo.ring_step_bytes(
        sizes, run.delta("window_kv_positions") / steps)
    full = mimo.full_step_bytes(
        sizes, run.delta("shared_kv_positions") / steps)
    experts = active * mimo.expert_bytes(sizes)
    dense_b = mimo.dense_q40_bytes(sizes)
    pairs = max(run.delta("moe_pairs"), 1)
    depth = run.delta("shared_kv_positions") / max(run.delta("sum_active"), 1)
    gbps = (ring + full + experts + dense_b) * steps / run.window_s / 1e9
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step at a mean depth of {depth:.0f} positions a row",
            f"a mean step moves (published bytes) {ring / 1e9:.2f} GB of "
            f"window rings, {full / 1e9:.2f} GB of the full layers' pages, "
            f"{experts / 1e9:.2f} GB of {active:.1f} distinct held experts "
            f"(summed over the expert layers; "
            f"{100 * run.delta('moe_local_pairs') / pairs:.1f} % of the "
            f"pairs landed here) and {dense_b / 1e9:.2f} GB of dense "
            f"leaves: step_gbps {gbps:.1f} (an end-to-end utilisation, not "
            f"a roofline share); pages in use at the end "
            f"{run.counters_after.get('shared_kv_pages')}"]
