"""Driver of the serving entry point for ``harness/nemotron.py``'s
configuration (NVIDIA-Nemotron-3-Nano-30B-A3B: a layer is ONE mixer, Mamba-2,
attention without positional encoding, or non-gated relu2 experts of which a
SHARE is held): ``drivers/serve_laguna.py``'s check requests, lots and
counters and ``drivers/serve_hybrid.py``'s first wave and cutting client,
imported, around a model built from the nemotron harness. What is its own:
how the served streams are judged after a router near-tie (a state carries
it), the counters of the new state, the state's check, and a window whose
fill is INSIDE it.

The check (``check_streams``): ``CHECK_REQUESTS`` requests at once on the 32
slots (``serve_laguna.check_requests``), the configuration's
``check.long_requests`` first: prompts of 2,560, 2,560, 1,024, 1,024, 384 and
128 tokens, so five-chunk admissions whose SSD form hands a state over twenty
chunk boundaries; the others with 128- and 384-token prompts and 64 to 96
outputs; eight of the 40 run on REUSED rows, so a state, conv rows or pages
that a retired request left and a new one found would show. The float32
reference, the RECURRENCE, is teacher-forced on the served streams in
``serve_laguna``'s lots and EVERY served position is compared on logits.

The layer has a top-k over 128 scores in 23 layers, which is discontinuous:
two float32 routers choose differently where a margin is under what their
scores differ by, and here a decision taken the other way does not stay at
its position: the Mamba-2 state carries what that expert added to every later
position of the row, so the row's later picks may differ at many positions
and no single reversed decision explains them (``serve_laguna``'s rules were
run on this cell first, at layers 0-33: seven runs on seven seeds read 0.08
to 0.41 for the worst request's share of positions over the tolerance, 0 to
5 reversals stood, and the bfloat16 control's read 0.53 to 0.65: no limit
lies between
with room; the configuration's ``check.why`` has the readings). So: a request
is compared STRICTLY up to its first position whose smallest router margin is
under ``laguna.MARGIN_EPSILON`` (the served token's logit within the
tolerance of the reference's maximum), and after it the requests are judged
TOGETHER, by the share of their positions that fall short by more than the
tolerance, pooled over the short lots (``check.pooled_share_limit``) and over
the long lots apart (``check.long_share_limit``: four requests' 192
positions, where one request gone another way is a quarter of the pool, so
its limit is wider and catches what only a long prompt's five chunks could
break): a decision taken the other way costs ONE request part of its
positions, a fault costs every request. The chip holds 64 of 128 experts: a
pair on an expert held elsewhere adds nothing here, in the program and in
the reference alike. The same positions and histories also read the CONTROL
(every product's operands rounded to bfloat16) on the first long lot and the
first short one, which has to come out over the tolerance and, in the short
pool (700-odd positions; the long lot's one request is too few to judge by),
over the pooled limit: a control that passes fails the check.

The window (``Served.window``; the traffic file must say ``first_wave``
``whole_mix`` and ``window_end`` ``cut_by_client``) is ``serve_hybrid``'s:
it opens when the server is warm, the 32 clients that send first hold the
prompt mix in its exact proportions, the fill of the 32 rows is inside it,
and a request still streaming when it closes is cut there by its client. The
counters are also read where the profiler starts and stops
(``serve_laguna.Served.window`` says why) and handed on as ``trace_steps``,
``trace_shared_kv_positions``, ``trace_moe_active`` and
``trace_sum_active``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..harness import model, nemotron, runtime, traffic
from ..harness.runtime import note
from . import serve as dense
from . import serve_laguna
from .serve_hybrid import run_cut_client, whole_mix_first
from .serve_laguna import ended_at_the_cut
from .serve_retention import served_rows, shortfalls

CHECK_REQUESTS = 40
# the counters whose change over the TRACED seconds the readers take
TRACED = (*serve_laguna.TRACED, "sum_active")
_lag_counters = serve_laguna.counters


def check_streams(records, plan, tok, tree, sizes, config) -> dict:
    """Teacher-force the nemotron reference on what ``serve`` streamed (the
    module docstring has the rules). The control is read on the first long
    lot and the first short one."""
    what = "served check requests"
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": what, "ok": False, "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    limits = {"short": float(config["check"]["pooled_share_limit"]),
              "long": float(config["check"]["long_share_limit"])}
    pools = {k: {"over": 0, "n": 0, "control_over": 0, "control_n": 0}
             for k in ("long", "short")}
    worst = control = share_max = 0.0
    strict = served_n = flipped = 0
    smallest = float("inf")
    seen = set()
    for part, width in serve_laguna._lots(rows):
        t_lot = time.monotonic()
        pool = "long" if width > serve_laguna.LONG else "short"
        with_control = pool not in seen
        seen.add(pool)
        real = len(part)
        size = 1 if pool == "long" else serve_laguna.GROUP
        lot = [rows[i] for i in part]
        lot += [lot[-1]] * (size - real)                 # one shape
        span = max(len(served) for _, _, served in lot)
        # a short row is padded: every layer is causal, so what follows a
        # position does not reach it
        tokens = np.asarray([r + [0] * (width - len(r)) for r, _, _ in lot])
        keep = np.asarray([[min(n - 1 + i, width - 1) for i in range(span)]
                           for _, n, _ in lot])
        got, margins = nemotron.logits(
            tree, sizes, tokens, keep=keep,
            lengths=[len(r) for r, _, _ in lot], precisions=(
                ("highest", "bfloat16") if with_control else ("highest",)))
        want = got["highest"]
        for b, (row, n, served) in enumerate(lot[:real]):
            k = len(served)
            n_strict = max(0, min(k, nemotron.strict_positions(
                margins[b, :len(row)]) - (n - 1)))
            smallest = min(smallest, float(margins[b, :len(row)].min()))
            short = shortfalls(want[b, :k], served)
            served_n += k
            strict += n_strict
            if n_strict:
                worst = max(worst, float(short[:n_strict].max()))
            over = short[n_strict:] > tol
            pools[pool]["over"] += int(over.sum())
            pools[pool]["n"] += k - n_strict
            flipped += bool(over.any())
            if k - n_strict:
                share_max = max(share_max, float(over.mean()))
            if with_control:
                ctl = shortfalls(want[b, :k], got["bfloat16"][b, :k].argmax(
                    axis=-1))
                control = max(control, float(ctl.max()))
                pools[pool]["control_over"] += int((ctl > tol).sum())
                pools[pool]["control_n"] += k
        note(f"check: {real} request(s) in a lot {width} wide read in "
             f"{time.monotonic() - t_lot:.1f} s")
    shares = {k: p["over"] / max(p["n"], 1) for k, p in pools.items()}
    ctl_shares = {k: p["control_over"] / max(p["control_n"], 1)
                  for k, p in pools.items() if p["control_n"]}
    ok = (worst <= tol and served_n > 0
          and all(shares[k] < limits[k] for k in shares)
          and control > tol
          and ctl_shares.get("short", 0.0) > limits["short"])
    return {"what": f"served tokens vs the float32 nemotron reference's "
                    f"maximum, {len(rows)} requests of "
                    f"{min(len(r) for r, _, _ in rows) + 1} to "
                    f"{max(len(r) for r, _, _ in rows) + 1} positions at "
                    f"once, teacher-forced, every served position: strictly "
                    f"up to a request's first router margin under "
                    f"{nemotron.MARGIN_EPSILON}, and after it by the share "
                    f"of positions over the tolerance, pooled over the short "
                    f"and over the long requests; the control over the "
                    f"tolerance and over the short pool's limit",
            "ok": bool(ok),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_strict": strict,
                       "positions_served": served_n, "requests": len(rows),
                       "pooled_share_long": shares["long"],
                       "pooled_share_short": shares["short"],
                       "pooled_share_limit": limits["short"],
                       "long_share_limit": limits["long"],
                       "positions_pooled_long": pools["long"]["n"],
                       "positions_pooled_short": pools["short"]["n"],
                       "requests_with_a_position_over": flipped,
                       "max_request_share": share_max,
                       "smallest_margin": smallest,
                       "control_bfloat16_max_shortfall": control,
                       "control_pooled_share_long": ctl_shares.get("long"),
                       "control_pooled_share_short": ctl_shares.get("short")}}


def counters(server, compiles) -> dict:
    """``serve_laguna.counters`` and the pairs that landed on a held
    expert."""
    out = _lag_counters(server, compiles)
    out["moe_local_pairs"] = getattr(server.engine.stats, "moe_local_pairs",
                                     0)
    return out


def state_check(st, sizes: dict, slots: int) -> dict:
    """The state is resident at its exact size, pages were used, some but
    not all routed pairs landed on a held expert, rows ran ahead, no state
    was forgotten in one token, and a step ran every layer."""
    want = slots * nemotron.kinds_of(sizes).count(
        "mamba2") * nemotron.state_row_bytes(sizes)
    run = getattr(st, "layers_run", {})
    per = {k: run.get(k, 0) / max(st.steps, 1) for k in nemotron.KINDS}
    kinds = nemotron.kinds_of(sizes)
    return {
        "what": "the Mamba-2 states and conv rows are resident at their "
                "exact size, pages were used, some but not all routed pairs "
                "landed on a held expert, rows ran ahead, no state was "
                "forgotten in one token and a step ran every layer",
        "ok": bool(
            st.state_bytes == want and st.window_bytes == 0
            and st.shared_kv_positions > 0
            and 0 < st.moe_local_pairs < st.moe_pairs
            and st.moe_load is not None
            and st.moe_load.shape == (sizes["n_experts"],)
            and st.steps_ahead > 0 and 1e-3 < st.ssm_min_decay <= 1.0
            and all(per[k] == kinds.count(k) for k in nemotron.KINDS)),
        "detail": {"state_bytes": st.state_bytes, "state_bytes_want": want,
                   "shared_kv_positions": st.shared_kv_positions,
                   "moe_pairs": st.moe_pairs,
                   "moe_local_pairs": st.moe_local_pairs,
                   "moe_active": st.moe_active,
                   "steps_ahead": st.steps_ahead,
                   "ssm_min_decay": st.ssm_min_decay,
                   "layers_a_step": per}}


class Served(dense.Served):
    """``drivers/serve.Served`` over the nemotron harness's model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        nemotron.check_runnable(config)
        sizes = self.sizes = nemotron.sizes_of(config)
        spec = nemotron.program_spec(sizes)  # a program without the record
        #                                stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = nemotron.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        nemotron.settle_shared_positions(
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
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            note(f"check: {self.checks[0]['detail']}")
            self.checks.append(state_check(self.server.engine.stats, sizes,
                                           int(flags["slots"])))
            note(f"check: {self.checks[1]['detail']}")
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        """``serve_laguna.Served.window`` on this driver's counters."""
        server, compiles, args = self.server, self.compiles, self.args
        mix = self.cell.traffic
        if (mix.get("first_wave"), mix.get("window_end")) != (
                "whole_mix", "cut_by_client"):
            raise ValueError("serve_nemotron's window is the stratified "
                             "first wave and the cutting client: the "
                             "traffic file has to say so (first_wave, "
                             "window_end)")
        alloc = server.engine.allocator
        peak_used = [alloc.n_pages - alloc.n_free]
        at_end: dict = {}
        traced_: dict = {}
        t0 = time.monotonic() + 0.25     # the client is up by then

        def tick():
            peak_used[0] = max(peak_used[0], alloc.n_pages - alloc.n_free)
            if not at_end and time.monotonic() >= t0 + seconds:
                at_end.update(counters(server, compiles))

        before = counters(server, compiles)
        out = {"trace": None}
        th = None
        if args.trace:
            tracer = runtime.Tracer(mix.get("trace_seconds", 4),
                                    args.keep_trace)
            t_trace = t0 + min(float(mix.get("trace_start_s", 0.0)),
                               seconds / 2)

            def traced():
                time.sleep(max(0.0, t_trace - time.monotonic()))
                tracer.start()
                lo = counters(server, compiles)
                time.sleep(tracer.seconds)
                hi = counters(server, compiles)
                tracer.stop()
                traced_.update({"trace_" + k: hi[k] - lo[k] for k in TRACED})

            th = threading.Thread(target=traced)
            th.start()
        slots = int(self.cell.config["entries"]["serve"]["slots"])
        plan = whole_mix_first(plan, mix["prompt_tokens"], slots)
        doc = run_cut_client(self.base_url, plan, t0, seconds, slots,
                             on_tick=tick)
        if th is not None:
            th.join()
            out["trace"] = tracer.finish()
        after = at_end or counters(server, compiles)
        before.update(peak_pages_used=0, pool_pages=0,
                      **dict.fromkeys(traced_, 0))
        after.update(peak_pages_used=peak_used[0], pool_pages=alloc.n_pages,
                     **traced_)
        if doc.get("stuck_threads"):
            note(f"{doc['stuck_threads']} client thread(s) never finished")
        out.update(records=[ended_at_the_cut(r) for r in doc["records"]],
                   before=before, after=after)
        return out


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = traffic.generate(cell.traffic, args.seed, args.seconds)
        setup_s = time.time() - t_start + 0.25
        w = served.window(plan, args.seconds)
        low = served.server.engine.stats.ssm_min_decay
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
    sizes = nemotron.sizes_of(run.cell.config)
    rows = int(run.cell.config["entries"]["serve"]["slots"])
    active = run.delta("moe_active") / steps
    state = nemotron.state_step_bytes(sizes, rows)
    full = nemotron.full_step_bytes(
        sizes, run.delta("shared_kv_positions") / steps)
    experts = active * nemotron.expert_bytes(sizes)
    dense_b = nemotron.dense_q40_bytes(sizes)
    pairs = max(run.delta("moe_pairs"), 1)
    depth = run.delta("shared_kv_positions") / max(run.delta("sum_active"), 1)
    gbps = (state + full + experts + dense_b) * steps / run.window_s / 1e9
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step at a mean depth of {depth:.0f} positions a row",
            f"a mean step moves {state / 1e9:.2f} GB of Mamba-2 state "
            f"({rows} rows, read and written), {full / 1e9:.2f} GB of the "
            f"attention layers' pages, {experts / 1e9:.2f} GB of "
            f"{active:.1f} distinct held experts (summed over the expert "
            f"layers; {100 * run.delta('moe_local_pairs') / pairs:.1f} % of "
            f"the pairs landed here) and {dense_b / 1e9:.2f} GB of dense "
            f"leaves: step_gbps {gbps:.1f} (an end-to-end utilisation, not "
            f"a roofline share); pages in use at the end "
            f"{run.counters_after.get('shared_kv_pages')}"]
