"""Driver of the serving entry point for a latent-attention expert
configuration whose residual path is several streams (``harness/hyper.py``):
``drivers/serve_latent.py``'s check requests, rules and counters around a
model built from the hyper harness. What is its own: the reference the check
teacher-forces (``hyper.logits``: the residual path around the latent
harness's sub-layers), the reversal of ONE doubtful router decision where a
request's excused share reads over the limit (``check_streams`` says why a
chip that holds every expert needs it, and what bounds it: the margin, the
count a run, the control through the same rule), a SECOND control (only the
coefficient projection's operands rounded to bfloat16, read beside the
tolerance and deciding nothing: PERF.md section 7 says what the check guards
of that product), the counters of the streams, a census check that fits a chip
holding EVERY expert (all pairs land here, where the DeepSeek cell requires
that some do not), in a traced run the names of the step's instructions
under the residual path's scopes (``Run.path_ops``: what the ``hc_*``
readers tell the path's device ops by), and the memory peak of ``serve``
alone, read before the reference is built.

The check (``serve_latent.py``'s docstring has the rules it shares): 64
requests at once on the 32 slots, six of them at the window's own lengths,
the float32 reference teacher-forced on the served streams in blocks, every
served position compared on logits, strictly up to a request's first router
near-tie and by share after it; the bfloat16 control must lie over the
tolerance and over the share limit.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from ..harness import hyper, model, runtime, traffic
from ..harness.runtime import note
from . import serve as dense
from . import serve_latent
from .serve_latent import EXCUSED_MIN, LONG_GROUP, check_requests
from .serve_retention import served_rows, shortfalls

PROJECTION = "projection_bfloat16"


def _excused_share(short, n_strict: int, tol: float):
    """The share of a request's served positions after its first near-tie
    that fall short by more than the tolerance; None under ``EXCUSED_MIN``
    of them."""
    if len(short) - n_strict < EXCUSED_MIN:
        return None
    return float((short[n_strict:] > tol).mean())


def _first_over(short, n_strict: int, tol: float, limit: float):
    """Index of the first served position over the tolerance of a request
    whose excused share reads over the limit; None for another."""
    share = _excused_share(short, n_strict, tol)
    if share is None or share < limit:
        return None
    return n_strict + int(np.nonzero(short[n_strict:] > tol)[0][0])


def check_streams(records, plan, tok, tree, sizes, config,
                  group: int = 32) -> dict:
    """Teacher-force the reference on what ``serve`` streamed:
    ``serve_latent.check_streams``'s rules and readings, with what a chip
    that holds EVERY expert needs beside them.

    In the DeepSeek cell seven of eight routed pairs land on experts held
    elsewhere, so a router decision that the program and the reference take
    differently (a margin of a few float32 ulps) seldom moves anything. Here
    every expert is held: one such decision moves its request's later
    positions by more than the tolerance at a third of them (my chip run,
    PR 39: PERF.md section 6). So where a request's excused share reads
    over the limit, the reference is run again with the ONE decision
    REVERSED that is most likely to have gone the other way
    (``hyper.with_reversals`` has the rule; only a margin under
    ``hyper.REVERSAL_EPSILON``, a few float32 ulps, qualifies). Both
    choices are the model's, to float32; the served stream must agree with
    one of them. What keeps that from excusing a fault: a run in which more
    than ``hyper.MAX_REVERSALS_A_RUN`` reversals stand FAILS (the chip
    showed at most two), and the bfloat16 control's streams are put through
    the SAME rule and must still read over the tolerance and the share
    limit: what a reversal can explain, it may explain for the control too.

    The guard against a check that compares nothing (``serve_latent``'s
    ``2 * strict >= served``) counts here what is JUDGED: a comparable
    request's positions up to its first near-tie, strictly, and those after
    it where they are enough for a share (``EXCUSED_MIN``), by that share.
    This cell's long requests go to 1,008 served positions from prompts of
    64 tokens up, so one of them that meets its first near-tie early in its
    answer would alone put the strict count under half (about one seed in
    thirty by the margins the chip read; PERF.md section 6), though every
    one of its positions was compared and held to a limit.
    ``decisions_reversed`` and ``positions_judged_by_share`` are numbers of
    every run's ``check:`` line, so that drift in either is seen."""
    what = "served check requests"
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": what, "ok": False, "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    limit = float(config["check"]["excused_share_limit"])
    n_long = sum(bool(r.get("long")) for c in plan["clients"] for r in c)
    worst = excused_worst = control = 0.0
    strict = served_n = comparable = excused = control_over = control_n = 0
    share_max, share_n, by_share, control_share = 0.0, 0, 0, None
    projection, projection_n, reversed_, control_reversed = None, 0, [], 0
    smallest = float("inf")
    kinds = ((rows[n_long:], group, False), (rows[:n_long], LONG_GROUP, True))
    for kind, size, is_long in kinds:
        width = max((len(r) for r, _, _ in kind), default=0)
        span = max((len(served) for _, _, served in kind), default=0)
        for lo in range(0, len(kind), size):
            part = kind[lo:lo + size]
            real = len(part)
            part = part + [part[-1]] * (size - real)      # one shape
            tokens = np.asarray([r + [0] * (width - len(r))
                                 for r, _, _ in part])
            keep = np.asarray([[min(n - 1 + i, width - 1)
                                for i in range(span)] for _, n, _ in part])
            with_control = lo == 0
            got, margins = hyper.logits(
                tree, sizes, tokens, keep=keep, precisions=(
                    ("highest",) if not with_control
                    else ("highest", "bfloat16") if is_long
                    else hyper.PRECISIONS))
            want = got["highest"]
            first = want.copy() if with_control else want
            ties = [hyper.strict_positions(margins[b, :len(row)])
                    for b, (row, _, _) in enumerate(part[:real])]
            n_stricts = [max(0, min(len(served), ties[b] - (n - 1)))
                         for b, (_, n, served) in enumerate(part[:real])]

            def first_bad(picks, b, want_b):
                """The position in row b of its first token of ``picks``
                over the tolerance, where its excused share reads over the
                limit."""
                if b >= real:
                    return None
                _, n, served = part[b]
                k = len(served)
                over = _first_over(shortfalls(want_b[:k], picks[b][:k]),
                                   n_stricts[b], tol, limit)
                return None if over is None else n - 1 + over

            if with_control:
                # the control through the same rule, on margins of its own
                ctl_picks = got["bfloat16"].argmax(-1)
                control_reversed += len(hyper.with_reversals(
                    tree, sizes, tokens, keep, first, margins.copy(),
                    functools.partial(first_bad, ctl_picks)))
            reversed_ += [
                {"request": lo + b + (0 if is_long else n_long),
                 "position": t, "expert_layer": layer, "margin": m}
                for b, t, layer, m in hyper.with_reversals(
                    tree, sizes, tokens, keep, want, margins,
                    functools.partial(
                        first_bad, [served for _, _, served in part]))]
            shorts = [shortfalls(want[b, :len(served)], served)
                      for b, (_, _, served) in enumerate(part[:real])]
            for b, (row, n, served) in enumerate(part[:real]):
                k, n_strict, short = len(served), n_stricts[b], shorts[b]
                smallest = min(smallest, float(margins[b, :len(row)].min()))
                ctl = (shortfalls(first[b, :k], ctl_picks[b, :k])
                       if with_control else None)
                if n_strict:
                    comparable += 1
                    served_n += k
                    strict += n_strict
                    worst = max(worst, float(short[:n_strict].max()))
                    if with_control and not is_long:
                        control = max(control, float(ctl[:n_strict].max()))
                        control_over += int((ctl[:n_strict] > tol).sum())
                        control_n += n_strict
                        proj = shortfalls(first[b, :k], got[PROJECTION][
                            b, :k].argmax(-1))[:n_strict]
                        projection = max(projection or 0.0, float(proj.max()))
                        projection_n += n_strict
                if n_strict < k:
                    excused_worst = max(excused_worst,
                                        float(short[n_strict:].max()))
                    excused += int((short[n_strict:] > tol).any())
                share = _excused_share(short, n_strict, tol)
                if share is not None:
                    share_n += 1
                    share_max = max(share_max, share)
                    by_share += (k - n_strict) if n_strict else 0
                    if with_control and is_long:
                        control_share = max(control_share or 0.0, float(
                            (ctl[n_strict:] > tol).mean()))
    ok = (worst <= tol and 4 * comparable >= len(rows)
          and 2 * (strict + by_share) >= served_n and share_max < limit
          and len(reversed_) <= hyper.MAX_REVERSALS_A_RUN
          and control > tol
          and (control_share is None or control_share > limit))
    return {"what": f"served tokens vs the float32 hyper-connection "
                    f"reference's maximum, {len(rows)} requests of "
                    f"{min(len(r) for r, _, _ in rows) + 1} to "
                    f"{max(len(r) for r, _, _ in rows) + 1} "
                    f"positions at once, teacher-forced, every served "
                    f"position, strictly up to a request's first router "
                    f"margin under {hyper.MARGIN_EPSILON}, and by the share "
                    f"of its positions that fall short after it, the most "
                    f"doubtful decision (a margin under "
                    f"{hyper.REVERSAL_EPSILON}) reversed where that share "
                    f"reads over, for the control's streams too",
            "ok": bool(ok),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_strict": strict,
                       "positions_judged_by_share": by_share,
                       "positions_served_of_comparable": served_n,
                       "requests_comparable": comparable,
                       "requests": len(rows),
                       "requests_with_an_excused_shortfall": excused,
                       "max_excused_shortfall": excused_worst,
                       "max_excused_share": share_max,
                       "excused_share_limit": limit,
                       "requests_with_an_excused_share": share_n,
                       "decisions_reversed": len(reversed_),
                       "decisions_reversed_limit": hyper.MAX_REVERSALS_A_RUN,
                       "decisions_reversed_at": reversed_,
                       "control_decisions_reversed": control_reversed,
                       "smallest_margin": smallest,
                       "control_bfloat16_max_shortfall": control,
                       "control_positions_over_tolerance": control_over,
                       "control_positions": control_n,
                       "control_bfloat16_excused_share": control_share,
                       "control_projection_bfloat16_max_shortfall":
                           projection,
                       "control_projection_positions": projection_n}}


def counters(server, compiles) -> dict:
    """``serve_latent.counters`` and the streams' two."""
    out = serve_latent.counters(server, compiles)
    st = server.engine.stats
    out.update(hc_streams=getattr(st, "hc_streams", 0),
               hc_sublayers_a_step=getattr(st, "hc_sublayers_a_step", 0))
    return out


@dataclasses.dataclass
class Run(runtime.Run):
    """``runtime.Run`` and what the residual path's trace readers tell the
    path's device ops by (``hyper.hc_step_ops``)."""
    path_ops: frozenset | None = None


def _path_ops(engine) -> frozenset | None:
    """Names of the decode step's instructions under the residual path's
    scopes, from the step's compiled text; None from a program that cannot
    give it (the readers then go by position, and say so)."""
    try:
        names = frozenset(hyper.path_instructions(
            engine.decode_program_text()))
    except Exception as e:     # noqa: BLE001  a reader's aid, not the run
        names = frozenset()
        note(f"residual path: no compiled text ({type(e).__name__}: {e})")
    note(f"residual path: {len(names)} instructions under its scopes in "
         f"the step's compiled text; its ops are "
         f"{'told by identity' if names else 'found by position'}")
    return names or None


class Served(dense.Served):
    """``drivers/serve.Served`` over a model with several residual streams."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        hyper.check_runnable(config)
        sizes = hyper.sizes_of(config)
        spec = hyper.program_spec(sizes)    # a program without the record
        #                              stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = hyper.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        hyper.settle_shared_positions(
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
            plan = check_requests(args.seed, int(flags["slots"]),
                                  config["check"].get("long_requests", ()))
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   900.0, keep_tokens=True)
            # what ``serve`` alone peaks at, 64 requests through the 32
            # slots: the process's peak from here on holds the check's
            # float32 reference too, which no deployment holds
            serve_peak = runtime.memory_peak_bytes()
            note(f"check requests served; memory peak of serve alone "
                 f"{serve_peak} bytes")
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            self.checks[0]["detail"]["serve_memory_peak_bytes"] = serve_peak
            note(f"check: {self.checks[0]['detail']}")
            st = self.server.engine.stats
            self.checks.append({
                "what": "every routed pair landed on a held expert, the "
                        "streams were counted and rows ran ahead",
                "ok": bool(0 < st.moe_pairs == st.moe_local_pairs
                           and st.hc_streams == sizes["streams"]
                           and st.hc_sublayers_a_step == 2 * sizes["n_layers"]
                           and st.steps_ahead > 0),
                "detail": {"moe_pairs": st.moe_pairs,
                           "moe_local_pairs": st.moe_local_pairs,
                           "hc_streams": st.hc_streams,
                           "hc_sublayers_a_step": st.hc_sublayers_a_step,
                           "steps_ahead": st.steps_ahead}})
            self.path_ops = _path_ops(self.server.engine) if args.trace \
                else None
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        # the window reads its counters through the module's name
        saved, dense.counters = dense.counters, counters
        try:
            return super().window(plan, seconds)
        finally:
            dense.counters = saved


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = traffic.generate(cell.traffic, args.seed, args.seconds)
        setup_s = time.time() - t_start + 0.25
        w = served.window(plan, args.seconds)
    note(f"window over: {len(w['records'])} requests")
    return Run(
        path_ops=served.path_ops, cell=cell, seed=args.seed,
        window_s=float(args.seconds), setup_s=setup_s, records=w["records"],
        device=served.device,
        counters_before=w["before"], counters_after=w["after"],
        trace=w["trace"], checks=served.checks)


def narrate(run) -> list:
    """``serve_latent.narrate`` and the residual path's two utilisations."""
    out = serve_latent.narrate(run)
    rows = hyper.step_rows(run)
    if run.trace is not None:
        rules = {s["rule"] for s in hyper.hc_step_ops(
            run.trace, hyper.path_names(run))}
        out.append(f"residual path's ops told by: "
                   f"{', '.join(sorted(rules)) or 'nothing (no decode step)'}")
    if out and rows:
        sizes = hyper.sizes_of(run.cell.config)
        out.append(f"residual path: {sizes['streams']} streams around "
                   f"{run.counters_after.get('hc_sublayers_a_step')} "
                   f"sub-layers a step, {hyper.hc_step_bytes(sizes, round(rows)) / 1e6:.1f} "
                   f"MB and {hyper.hc_step_flops(sizes, round(rows)) / 1e9:.2f} "
                   f"GFLOP a step of {rows:.1f} rows")
    return out
