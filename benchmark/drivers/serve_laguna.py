"""Driver of the serving entry point for a mixer-kinds expert configuration
(``harness/laguna.py``): ``drivers/serve.py``'s client, window, trace and
``Run``, imported, around a model built from the laguna harness. What is its
own: how the check's requests are chosen and grouped for the reference, and
the counters of the rings, the full layers' pages, the experts and the gate.

The check is made at the window's load, on the timed weights and programs at
the timed sizes: ``2 * slots`` requests from as many clients, all connecting
at once (``check_requests``), so the rows fill, a queue stands, rows, rings
and pages are handed on and every later admission writes a ring and pages
another sequence held. The first are the configuration's
``check.long_requests``: requests at the WINDOW'S OWN lengths, its longest
prompts (4,032 and 2,048 tokens: rings wrapped eight and four times over,
over 250 pages a row in every full layer's pool, every block of the paged
kernel's walk) among them. The others take the mix's short prompt lengths
(64, 192 and 448 tokens: a padded chunk, a whole one, several) with
``SHORT_OUTPUTS`` outputs drawn from the seed (twice ``EXCUSED_MIN`` and
more, so a request on a REUSED row is still judged after an early near-tie),
so the check is the warm-up of every program the window runs too. NO
request is shorter than the mix's shortest prompt: a router decision that
the program and the reference take differently moves later
positions by what attention gives ONE position, a sixty-fourth at most here
(``drivers/serve_hyper.py`` says what it does to a request of a few
tokens). The float32 reference is teacher-forced on the SERVED streams, a
layer, a KV group and an expert at a time, a long row alone and the others
``GROUP`` at a time, and EVERY served position is compared on logits.

The layer has a top-k, which is discontinuous: two float32 routers choose
differently where a margin is under what their scores differ by. The rules
are ``drivers/serve_latent.py``'s (its docstring has them): every request
opens with a character of its own; the two positions all prompts share are
given wide margins when the tree is made (``laguna.settle_shared_positions``);
a request is compared STRICTLY up to its first position whose smallest
margin is under ``laguna.MARGIN_EPSILON``, and by the SHARE of its positions
over the tolerance after it (``check.excused_share_limit``); half of the
comparable requests' served positions must be judged one way or the other.
Every expert is held here, so a decision taken the other way is visible
(``drivers/serve_hyper.py`` met the same): where a request fails either
rule, the reference is run again with the ONE decision reversed that is most
likely to have gone the other way (``laguna.with_reversals``: only a margin
under ``laguna.REVERSAL_EPSILON``, a few float32 ulps, qualifies; both
choices are the model's, to float32, and the served stream must agree with
one of them). More than ``MAX_REVERSALS_A_RUN`` reversals in a run fail it.
The same positions, histories and rules also read the CONTROL (bfloat16
products), reversals included, which has to come out over the tolerance and
over the share limit in every run: a control that passes fails the check.

The window's requests and client are what the traffic file says
(``"shapes_seed"`` -> ``window_plan``: one seed's shapes in every run, the
run's seed drawing the texts; ``"first_wave": "whole_mix"`` ->
``serve_hybrid.whole_mix_first``; ``"window_end": "cut_by_client"`` ->
``harness/cut_client.py``; the driver has no other plan or client, and
refuses a file that does not say all three): the 32 clients
that send first hold the prompt mix in its exact proportions (6 / 8 / 8 / 7
/ 3 of 64 / 192 / 448 / 2,048 / 4,032 tokens), because half of this cell's
window is admission and a 4,032-token prompt is eight chunks: with all 64
clients racing for the 32 slots, WHICH prompts the first fill held spread
``out_tokens_per_s`` by 4.5 % over six seeds (my chip run, PR 44), more than
a new cell may; and a request still streaming when the window closes is cut
there by its client, its tokens inside the window counted, in place of a
drain of up to 90 s.
The window is otherwise ``drivers/serve.py``'s with one addition: the
counters are also read where the profiler starts and stops, and the change between the
two is handed on as ``trace_steps``, ``trace_window_kv_positions``,
``trace_shared_kv_positions`` and ``trace_moe_active``, so that a kernel's
roofline share divides the bytes of the steps THAT WERE TRACED by their time
(the rows' depth drifts over a window of this traffic, and a share read
from the whole window's mean could pass 100 %).
"""

from __future__ import annotations

import functools
import threading
import time

import numpy as np

from ..harness import laguna, model, runtime, traffic
from ..harness.runtime import note
from . import serve as dense
from .serve_hybrid import run_cut_client, whole_mix_first
from .serve_latent import EXCUSED_MIN
from .serve_retention import served_rows, shortfalls

GROUP = 8            # short rows the reference reads at a time
LONG = 1024          # a row past this many positions is read alone ...
LONG_PAD = 1024      # ... padded to a multiple of this (one query block)
SHORT_PAD = 512      # the others' lots to a multiple of this
SHORT_OUTPUTS = (64, 96)   # outputs of a short check request, drawn between
MAX_REVERSALS_A_RUN = 6
"""Reversed router decisions that may stand in one run's check, by
``harness/hyper.py``'s reasoning (twice the most seen): the chip showed 0,
0, 0, 0, 0, 0, 1, 2 and 3 in a run (PERF.md section 6; the 8th and 9th of
256 scores lie within ``laguna.REVERSAL_EPSILON`` at some 2e-5 of the
220,000 decisions a run compares, and few of those fall where a stream
turns on them); more than six is drift, not rounding."""
_dense_counters = dense.counters
# the counters whose change over the TRACED seconds the roofline readers take
TRACED = ("steps", "window_kv_positions", "shared_kv_positions",
          "moe_active")


def check_requests(seed: int, slots: int, long_requests, prompt_mix) -> dict:
    """``2 * slots`` requests, one a client, all at once; request i opens
    with character i of the alphabet, so no two share their first own
    token. ``long_requests`` ((prompt, outputs), ...) come first; the others
    take the mix's prompt lengths of 448 tokens and fewer in turn, with
    ``SHORT_OUTPUTS`` outputs drawn from the seed."""
    import random

    rng = random.Random(seed ^ 0x1A6A)
    n_req = min(2 * slots, len(traffic.CHARS))
    shapes = [tuple(x) for x in long_requests][:n_req]
    short = sorted(int(k) for k in prompt_mix if int(k) <= 448) or [64]
    while len(shapes) < n_req:
        shapes.append((short[len(shapes) % len(short)],
                       rng.randint(*SHORT_OUTPUTS)))
    reqs = [{"id": i, "due_s": None, "prompt_tokens": n, "output_tokens": out,
             "prompt": traffic.CHARS[i] + "".join(
                 rng.choice(traffic.CHARS)
                 for _ in range(n - traffic.PROMPT_OVERHEAD - 1))}
            for i, (n, out) in enumerate(shapes)]
    return {"loop": "closed", "clients": [[r] for r in reqs]}


def _lots(rows):
    """The rows in lots of FEW shapes (three at the cell's lengths: each
    shape is two attention programs of the reference to compile, a quarter
    of a minute each for the chip): a long row alone, every one padded to
    the longest's multiple of ``LONG_PAD``; the others ``GROUP`` at a time,
    longest first, padded to their lot's longest. Padding costs little: it
    is routed to no expert (``laguna.logits``'s ``lengths``)."""
    order = sorted(range(len(rows)), key=lambda i: -len(rows[i][0]))
    long_ = [i for i in order if len(rows[i][0]) > LONG]
    rest = [i for i in order if len(rows[i][0]) <= LONG]
    lots = [([i], -(-len(rows[long_[0]][0]) // LONG_PAD) * LONG_PAD)
            for i in long_]
    for lo in range(0, len(rest), GROUP):
        part = rest[lo:lo + GROUP]
        lots.append((part, -(-len(rows[part[0]][0]) // SHORT_PAD)
                     * SHORT_PAD))
    return lots


def _excused_share(short, n_strict: int, tol: float):
    """The share of a request's served positions after its first near-tie
    that fall short by more than the tolerance; None under ``EXCUSED_MIN``
    of them."""
    if len(short) - n_strict < EXCUSED_MIN:
        return None
    return float((short[n_strict:] > tol).mean())


def _first_bad(lot, n_stricts, tol, limit, picks, b, want_b):
    """The position in row b's sequence of the first token of ``picks``
    that the rules do not pass: over the tolerance among the strictly
    compared positions, or the first one over it after them where the
    excused share reads over the limit; None for a row that passes."""
    if b >= len(n_stricts):
        return None
    _, n, served = lot[b]
    k = len(served)
    short = shortfalls(want_b[:k], picks[b][:k])
    over = np.nonzero(short > tol)[0]
    share = _excused_share(short, n_stricts[b], tol)
    bad = [int(i) for i in over if i < n_stricts[b]
           or (share is not None and share >= limit)]
    return n - 1 + bad[0] if bad else None


def check_streams(records, plan, tok, tree, sizes, config) -> dict:
    """Teacher-force the laguna reference on what ``serve`` streamed."""
    what = "served check requests"
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": what, "ok": False, "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    limit = float(config["check"]["excused_share_limit"])
    worst = excused_worst = control = 0.0
    strict = by_share = served_n = excused = 0
    control_over = control_n = control_reversed = 0
    share_max, share_n, control_share = 0.0, 0, None
    smallest, reversed_ = float("inf"), []
    seen_long = seen_short = False
    for part, width in _lots(rows):
        t_lot, n_rev = time.monotonic(), control_reversed + len(reversed_)
        is_long = width > LONG
        with_control = not (seen_long if is_long else seen_short)
        if is_long:
            seen_long = True
        else:
            seen_short = True
        real = len(part)
        size = 1 if is_long else GROUP
        lot = [rows[i] for i in part]
        lot += [lot[-1]] * (size - real)                 # one shape
        span = max(len(served) for _, _, served in lot)
        # a short row is padded: every layer is causal, so what follows a
        # position does not reach it
        tokens = np.asarray([r + [0] * (width - len(r)) for r, _, _ in lot])
        keep = np.asarray([[min(n - 1 + i, width - 1) for i in range(span)]
                           for _, n, _ in lot])
        lengths = [len(r) for r, _, _ in lot]
        got, margins = laguna.logits(
            tree, sizes, tokens, keep=keep, lengths=lengths, precisions=(
                ("highest", "bfloat16") if with_control else ("highest",)))
        want = got["highest"]
        first = want.copy() if with_control else want
        n_stricts = [max(0, min(len(served), laguna.strict_positions(
            margins[b, :len(row)]) - (n - 1)))
            for b, (row, n, served) in enumerate(lot[:real])]
        bad = functools.partial(_first_bad, lot, n_stricts, tol, limit)
        if with_control:
            # the control through the same rule, on margins of its own:
            # what a reversal can explain, it may explain for it too
            ctl_picks = got["bfloat16"].argmax(-1)
            control_reversed += len(laguna.with_reversals(
                tree, sizes, tokens, keep, first, margins.copy(),
                functools.partial(bad, ctl_picks), lengths))
        reversed_ += [
            {"request": part[b], "position": t, "expert_layer": layer,
             "margin": m}
            for b, t, layer, m in laguna.with_reversals(
                tree, sizes, tokens, keep, want, margins,
                functools.partial(bad, [served for _, _, served in lot]),
                lengths)]
        note(f"check: {real} request(s) in a lot {width} wide read in "
             f"{time.monotonic() - t_lot:.1f} s, "
             f"{control_reversed + len(reversed_) - n_rev} reversal(s) stood")
        for b, (row, n, served) in enumerate(lot[:real]):
            k, n_strict = len(served), n_stricts[b]
            smallest = min(smallest, float(margins[b, :len(row)].min()))
            short = shortfalls(want[b, :k], served)
            ctl = (shortfalls(first[b, :k], ctl_picks[b, :k])
                   if with_control else None)
            judged = k - n_strict >= EXCUSED_MIN
            served_n += k
            strict += n_strict
            if n_strict:
                worst = max(worst, float(short[:n_strict].max()))
            if with_control:
                control = max(control, float(ctl.max()))
                control_over += int((ctl > tol).sum())
                control_n += k
            if n_strict < k:
                excused_worst = max(excused_worst,
                                    float(short[n_strict:].max()))
                excused += int((short[n_strict:] > tol).any())
            if judged:
                share_n += 1
                by_share += k - n_strict
                share_max = max(share_max,
                                float((short[n_strict:] > tol).mean()))
                if with_control:
                    control_share = max(
                        control_share or 0.0,
                        float((ctl[n_strict:] > tol).mean()))
    ok = (worst <= tol and 2 * (strict + by_share) >= served_n
          and share_max < limit and len(reversed_) <= MAX_REVERSALS_A_RUN
          and control > tol
          and (control_share is None or control_share > limit))
    return {"what": f"served tokens vs the float32 laguna reference's "
                    f"maximum, {len(rows)} requests of "
                    f"{min(len(r) for r, _, _ in rows) + 1} to "
                    f"{max(len(r) for r, _, _ in rows) + 1} positions at "
                    f"once, teacher-forced, every served position, strictly "
                    f"up to a request's first router margin under "
                    f"{laguna.MARGIN_EPSILON}, and by the share of its "
                    f"positions that fall short after it, the most doubtful "
                    f"decision (a margin under {laguna.REVERSAL_EPSILON}) "
                    f"reversed where a request fails either, for the "
                    f"control's streams too",
            "ok": bool(ok),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_strict": strict,
                       "positions_judged_by_share": by_share,
                       "positions_served": served_n,
                       "requests": len(rows),
                       "requests_with_an_excused_shortfall": excused,
                       "max_excused_shortfall": excused_worst,
                       "max_excused_share": share_max,
                       "excused_share_limit": limit,
                       "requests_with_an_excused_share": share_n,
                       "decisions_reversed": len(reversed_),
                       "decisions_reversed_limit": MAX_REVERSALS_A_RUN,
                       "decisions_reversed_at": reversed_,
                       "control_decisions_reversed": control_reversed,
                       "smallest_margin": smallest,
                       "control_bfloat16_max_shortfall": control,
                       "control_positions_over_tolerance": control_over,
                       "control_positions": control_n,
                       "control_bfloat16_excused_share": control_share}}


def ended_at_the_cut(rec: dict) -> dict:
    """A record of ``harness/cut_client.send`` for a request that ENDED as
    the window was cut. ``send`` reads on after a request's ``done`` line
    until the server closes the stream; where the cutter shuts the socket
    in that moment (the request ended within ``CUT_GRACE_S`` of the
    window's end) the read raises ``IncompleteRead(0 bytes read)`` and the
    record says failed, with every token and a ``done`` line delivered
    (my chip run, PR 44, call M: one of a window's 127 requests, and the
    run printed ``correct`` false for it). This window ends some 65
    requests in 40 s where ``reason-sat32``'s ends a handful, so it meets
    that moment thirty times as often. Such a record is made what it
    records, a request that ended; any other is left as it is."""
    if (not rec["ok"] and not rec["cut"] and rec["done"] is not None
            and str(rec["error"]).startswith("IncompleteRead")
            and len(rec["stamps"]) == rec["output_tokens"]):
        rec.update(ok=True, error=None)
    return rec


def counters(server, compiles) -> dict:
    """``drivers/serve.counters`` and the counts of the rings, the full
    layers' pages, the experts and the gate."""
    out = _dense_counters(server, compiles)
    st = server.engine.stats
    load = getattr(st, "moe_load", None)
    out.update({k: getattr(st, k, 0) for k in (
        "window_bytes", "shared_kv_pages", "shared_kv_positions",
        "window_kv_positions", "moe_pairs", "moe_active", "admit_prefills",
        "gate_mean_sum", "gate_steps")})
    out.update(gate_min=getattr(st, "gate_min", 1.0),
               moe_load=np.zeros(1, np.int64) if load is None
               else load.copy())
    return out


class Served(dense.Served):
    """``drivers/serve.Served`` over a mixer-kinds expert model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        laguna.check_runnable(config)
        sizes = laguna.sizes_of(config)
        spec = laguna.program_spec(sizes)   # a program without the record
        #                              stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = laguna.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        laguna.settle_shared_positions(
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
            plan = check_requests(
                args.seed, int(flags["slots"]),
                config["check"].get("long_requests", ()),
                cell.traffic["prompt_tokens"])
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   600.0, keep_tokens=True)
            note(f"check requests served; memory peak of serve alone "
                 f"{runtime.memory_peak_bytes()} B")
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            note(f"check: {self.checks[0]['detail']}")
            st = self.server.engine.stats
            mx = spec.mixers
            n_exp = sizes["n_layers"] - sizes["dense_layers"]
            self.checks.append({
                "what": "rings are resident at their exact size, pages were "
                        "used, every routed pair landed on a held expert at "
                        "(expert layers, experts), rows ran ahead and the "
                        "gate was read",
                "ok": bool(
                    st.window_bytes == int(flags["slots"]) * mx.count(
                        "sliding") * mx.window * laguna.kv_position_bytes(
                            sizes)
                    and st.shared_kv_positions > st.window_kv_positions > 0
                    and st.moe_pairs == st.moe_local_pairs > 0
                    and st.moe_load is not None
                    and st.moe_load.shape == (sizes["n_experts"],)
                    and st.moe_pairs % (n_exp * sizes["n_active_experts"])
                    == 0 and st.steps_ahead > 0
                    and 0.0 < st.gate_min < st.gate_mean < 1.0),
                "detail": {"window_bytes": st.window_bytes,
                           "shared_kv_positions": st.shared_kv_positions,
                           "window_kv_positions": st.window_kv_positions,
                           "moe_pairs": st.moe_pairs,
                           "moe_active": st.moe_active,
                           "steps_ahead": st.steps_ahead,
                           "gate_min": st.gate_min,
                           "gate_mean": st.gate_mean}})
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        """``drivers/serve.Served.window`` with the counters read at the
        profiler's start and stop too (the module's docstring says why)."""
        server, compiles, args = self.server, self.compiles, self.args
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
            tracer = runtime.Tracer(self.cell.traffic.get("trace_seconds", 4),
                                    args.keep_trace)
            t_trace = t0 + min(float(self.cell.traffic.get(
                "trace_start_s", 0.0)), seconds / 2)

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
        mix = self.cell.traffic
        if (mix.get("first_wave"), mix.get("window_end")) != (
                "whole_mix", "cut_by_client"):
            raise ValueError("serve_laguna's window is the stratified first "
                             "wave and the cutting client: the traffic file "
                             "has to say so (first_wave, window_end)")
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


def window_plan(mix: dict, seed: int, seconds: float) -> dict:
    """The window's requests: their SHAPES (which client sends a prompt and
    an output of which length, in which order) are the generator's for the
    traffic file's ``shapes_seed`` in every run, and ``seed`` draws every
    prompt's text. Half of this window is admission, a 2,048-token prompt
    more or less inside it is 1.7 % of its tokens and a 4,032-token one
    3.5 %, and with the shapes dealt anew by every seed fourteen runs
    spread by 3.5 % (PERF.md section 6), more than a cell's yardstick
    may."""
    import random

    plan = traffic.generate(mix, int(mix["shapes_seed"]), seconds)
    rng = random.Random(seed ^ 0x7E87)
    for reqs in plan["clients"]:
        for r in reqs:
            r["prompt"] = "".join(
                rng.choice(traffic.CHARS) for _ in range(
                    r["prompt_tokens"] - traffic.PROMPT_OVERHEAD))
    return plan


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = window_plan(cell.traffic, args.seed, args.seconds)
        setup_s = time.time() - t_start + 0.25
        w = served.window(plan, args.seconds)
    cut = sum(bool(r.get("cut")) for r in w["records"])
    note(f"window over: {len(w['records'])} requests, {cut} of them cut by "
         f"their clients at the window's end")
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
    sizes = laguna.sizes_of(run.cell.config)
    active = run.delta("moe_active") / steps
    ring = laguna.ring_step_bytes(
        sizes, run.delta("window_kv_positions") / steps)
    full = laguna.full_step_bytes(
        sizes, run.delta("shared_kv_positions") / steps)
    experts = active * laguna.expert_bytes(sizes)
    dense_b = laguna.dense_q40_bytes(sizes)
    gate_steps = run.delta("gate_steps")
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step",
            f"a mean step moves {ring / 1e9:.2f} GB of window rings, "
            f"{full / 1e9:.2f} GB of the full layers' pages, "
            f"{experts / 1e9:.2f} GB of {active:.0f} distinct experts "
            f"(summed over the expert layers; "
            f"{run.delta('moe_pairs') / max(run.delta('moe_active'), 1):.2f} "
            f"rows an active expert) and {dense_b / 1e9:.2f} GB of dense "
            f"leaves: step_gbps "
            f"{(ring + full + experts + dense_b) * steps / run.window_s / 1e9:.1f} "
            f"(an end-to-end utilisation, not a roofline share)",
            f"per-head gate over the window: smallest "
            f"{run.counters_after.get('gate_min'):.3g}, mean "
            f"{run.delta('gate_mean_sum') / max(gate_steps, 1):.3g}; pages "
            f"in use at the end {run.counters_after.get('shared_kv_pages')}"]
