"""Driver of the serving entry point for ``harness/motif.py``'s configuration
(Motif-3-Beta: grouped differential attention on a latent plane, rings of
latent rows beside paged full layers, PolyNorm experts of which a SHARE is
held, four residual streams): ``drivers/serve_mimo.py``'s window (the first
wave whole, opened after its admissions, cut by the client), its held-share
verdict and its counters, ``drivers/serve_laguna.py``'s check and plan and
``drivers/serve_hyper.py``'s path names, imported, around a model built from
the motif harness. What is its own: the lambda control, the streams'
counters beside the rings', and the names of the step's instructions under
the differential fold's, the gate's and PolyNorm's scopes.

The check is ``serve_laguna.check_streams`` (``CHECK_REQUESTS`` (24)
requests at once on the 32 slots, the configuration's
``check.long_requests`` first: prompts of 8,064 to 1,024 tokens, so
sixteen-chunk admissions, rings wrapped 63 times, over 500 pages a row; the
rest short; eight of them on the REUSED rows, rings and pages that
``warm_programs``' requests held. ISSUE 52 forecast MiMo's 40: the float32
reference of a long row takes 5 s and of a short lot 11 s here, the two
programs take a minute to trace and lower even where the cache has them, and
at 40 a warm run took 330 s of the driver's 360 (my chip run, PR 52), so
sixteen short requests went, not the traffic; every served position compared on logits with the float32
reference teacher-forced on the served streams; strictly up to a request's
first router near-tie, by the share of positions over the tolerance after
it; the latest doubtful decision reversed where a request fails; the
bfloat16 control through the same rules) run on THIS configuration's
reference: for the time of the check ``laguna.logits`` is ``motif.logits``
(``_reference``, as ``serve_mimo`` has it). The chip holds 48 of 384
experts: ``serve_mimo.held_share_verdict`` holds the bfloat16 control to ONE
of its two limits, for the reason its docstring gives.

A SECOND control (``lambda_control``): the first short lot read again by
the reference with lambda = 0 (the noise heads left out); its picks must
come out over the tolerance at more than ``LAMBDA_CONTROL_SHARE`` of the
positions (a program that dropped the subtraction would read so), and the
mean lambda the reference finds must lie inside ``motif.LAMBDA_SHARES``: a
noise head that takes nothing tests nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from ..harness import hyper, laguna, model, motif, runtime
from ..harness.runtime import note
from . import serve as dense
from . import serve_laguna, serve_mimo
from .serve_laguna import window_plan
from .serve_mimo import held_share_verdict
from .serve_retention import served_rows, shortfalls

CHECK_REQUESTS = 24
WARM_REQUESTS = 8
CONTROL_PAD = 64     # the lambda control's lot is padded to a multiple of this
LAMBDA_CONTROL_SHARE = 0.25
"""The share of the lambda control's positions that must fall short by more
than the tolerance (the chip's readings: the configuration's
``check.why``)."""
WARM_ATTEMPTS = 4
_mimo_counters = serve_mimo.counters


def warm_programs(base_url: str, seed: int) -> None:
    """``WARM_REQUESTS`` short requests before the check's, sent again until
    all come back whole: they make the admission chunk's, the insert's and
    the decode step's programs, and they leave eight rows, their rings and
    their pages USED, so that eight of the check's requests run on reused
    ones though the check's own count is under the slots'. Cold, the chip's
    compiler takes 79 s over the chunk and 55 s over the step (my chip run,
    PR 52; 41 and 18 s to trace and lower them where the cache has them),
    more than the 120 s a client waits for a byte
    (``drivers/serve.run_client``): the check's requests must not be the
    ones that wait for it."""
    plan = serve_laguna.check_requests(seed, WARM_REQUESTS // 2, (),
                                       {"64": 1.0})
    for attempt in range(WARM_ATTEMPTS):
        t0 = time.monotonic()
        doc = dense.run_client(base_url, plan, t0, 600.0)
        if all(r["ok"] for r in doc["records"]):
            note(f"programs warm after {attempt + 1} attempt(s), the last "
                 f"{time.monotonic() - t0:.1f} s")
            return
    raise RuntimeError(f"the warm-up requests failed {WARM_ATTEMPTS} times")


@contextlib.contextmanager
def _reference():
    """``serve_laguna.check_streams`` and ``laguna.with_reversals`` on this
    configuration's reference (``serve_mimo._reference`` says why a name is
    rebound and no code copied)."""
    saved, laguna.logits = laguna.logits, motif.logits
    try:
        yield
    finally:
        laguna.logits = saved


def lambda_control(records, plan, tok, tree, sizes, config) -> dict:
    """The first ``serve_laguna.GROUP`` short rows of the check through the
    reference with lambda as it is and with lambda = 0."""
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": "lambda control", "ok": False,
                "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    part = next(p for p, w in serve_laguna._lots(rows)
                if w <= serve_laguna.LONG)
    lot = [rows[i] for i in part]
    lot += [lot[-1]] * (serve_laguna.GROUP - len(lot))
    # its own width, not the check's 512: a short row is 130 to 160
    # positions, and two passes at 192 cost a third of two at 512
    width = -(-max(len(r) for r, _, _ in lot) // CONTROL_PAD) * CONTROL_PAD
    span = max(len(served) for _, _, served in lot)
    tokens = np.asarray([r + [0] * (width - len(r)) for r, _, _ in lot])
    keep = np.asarray([[min(n - 1 + i, width - 1) for i in range(span)]
                       for _, n, _ in lot])
    lengths = [len(r) for r, _, _ in lot]
    stats: dict = {}
    want = motif.logits(tree, sizes, tokens, keep=keep, lengths=lengths,
                        stats=stats)[0]["highest"]
    bare = motif.logits(tree, sizes, tokens, keep=keep, lengths=lengths,
                        lambda_on=False)[0]["highest"].argmax(-1)
    short = np.concatenate([
        shortfalls(want[b, :len(served)], bare[b, :len(served)])
        for b, (_, _, served) in enumerate(lot[:len(part)])])
    share = float((short > tol).mean())
    lam = stats.get("lambda_mean", 0.0)
    lo, hi = motif.LAMBDA_SHARES
    return {"what": f"the reference with lambda = 0 (no noise head), its "
                    f"picks on {len(part)} short requests' served positions, "
                    f"must fall short at more than {LAMBDA_CONTROL_SHARE} of "
                    f"them, and the mean lambda must lie in {lo} to {hi}",
            "ok": bool(share > LAMBDA_CONTROL_SHARE and lo <= lam <= hi),
            "detail": {"control_lambda0_share_over_tolerance": share,
                       "control_lambda0_max_shortfall": float(short.max()),
                       "control_lambda0_positions": int(short.size),
                       "lambda_mean": lam}}


def counters(server, compiles) -> dict:
    """``serve_mimo.counters`` and the streams' two."""
    out = _mimo_counters(server, compiles)
    st = server.engine.stats
    out.update(hc_streams=getattr(st, "hc_streams", 0),
               hc_sublayers_a_step=getattr(st, "hc_sublayers_a_step", 0))
    return out


@dataclasses.dataclass
class Run(runtime.Run):
    """``runtime.Run``, what the residual path's trace readers tell the
    path's device ops by (``hyper.hc_step_ops``) and what this cell's tell
    the fold's, the gate's and PolyNorm's by (``motif.block_seconds``)."""
    path_ops: frozenset | None = None
    scoped_ops: dict | None = None


def _step_names(engine) -> tuple:
    """(``hyper.path_instructions``, ``motif.scoped_instructions``) of the
    decode step's compiled text; (None, None) from a program that cannot
    give it."""
    try:
        text = engine.decode_program_text()
        path = frozenset(hyper.path_instructions(text))
        scoped = motif.scoped_instructions(text)
    except Exception as e:     # noqa: BLE001  a reader's aid, not the run
        note(f"step text: none ({type(e).__name__}: {e})")
        return None, None
    note(f"step text: {len(path)} instructions under the residual path's "
         f"scopes, {len(scoped)} under the fold's, the gate's and "
         f"PolyNorm's")
    return path or None, scoped or None


class Served(serve_mimo.Served):
    """``serve_mimo.Served`` (its window) over the motif harness's model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        motif.check_runnable(config)
        sizes = self.sizes = motif.sizes_of(config)
        spec = motif.program_spec(sizes)    # a program without the fields
        #                              stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = motif.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        motif.settle_shared_positions(
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
            warm_programs(self.base_url, args.seed)
            plan = serve_laguna.check_requests(
                args.seed, min(CHECK_REQUESTS // 2, int(flags["slots"])),
                config["check"].get("long_requests", ()),
                cell.traffic["prompt_tokens"])
            t_check = time.monotonic()
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   600.0, keep_tokens=True)
            note(f"check requests served in "
                 f"{time.monotonic() - t_check:.1f} s; memory peak of serve "
                 f"alone {runtime.memory_peak_bytes()} B")
            with _reference():
                self.checks = [held_share_verdict(serve_laguna.check_streams(
                    doc["records"], plan, tok, tree, sizes, config))]
            note(f"check: {self.checks[0]['detail']}")
            self.checks.append(lambda_control(doc["records"], plan, tok,
                                              tree, sizes, config))
            note(f"check: {self.checks[1]['detail']}")
            st = self.server.engine.stats
            la = spec.latent
            n_exp = sizes["n_layers"] - sizes["dense_layers"]
            self.checks.append({
                "what": "rings are resident at their exact size, pages were "
                        "used, some but not all routed pairs landed on a "
                        "held expert, the streams ran, and rows ran ahead",
                "ok": bool(
                    st.window_bytes == int(flags["slots"]) * la.count(
                        "sliding") * la.window * 4 * (
                            -(-la.width // 128) * 128)
                    and st.shared_kv_positions > st.window_kv_positions > 0
                    and 0 < st.moe_local_pairs < st.moe_pairs
                    and st.moe_load is not None
                    and st.moe_pairs % (n_exp * sizes["n_active_experts"])
                    == 0 and st.hc_streams == sizes["streams"]
                    and st.steps_ahead > 0),
                "detail": {"window_bytes": st.window_bytes,
                           "shared_kv_positions": st.shared_kv_positions,
                           "window_kv_positions": st.window_kv_positions,
                           "moe_pairs": st.moe_pairs,
                           "moe_local_pairs": st.moe_local_pairs,
                           "moe_active": st.moe_active,
                           "hc_streams": st.hc_streams,
                           "steps_ahead": st.steps_ahead}})
            self.path_ops, self.scoped_ops = _step_names(
                self.server.engine) if args.trace else (None, None)
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        # ``serve_mimo``'s window reads its counters through its module's
        # name (``serve_hyper.Served.window`` does the same to ``serve``'s)
        saved, serve_mimo.counters = serve_mimo.counters, counters
        try:
            return super().window(plan, seconds)
        finally:
            serve_mimo.counters = saved


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = window_plan(cell.traffic, args.seed, args.seconds)
        w = served.window(plan, args.seconds)
        t_stop = time.monotonic()
    cut = sum(bool(r.get("cut")) for r in w["records"])
    note(f"window over: the fill took {w['fill_s']:.1f} s (set-up); "
         f"{len(w['records'])} requests, {cut} of them cut by their clients "
         f"at the window's end; the server stopped in "
         f"{time.monotonic() - t_stop:.1f} s")
    return Run(
        path_ops=served.path_ops, scoped_ops=served.scoped_ops, cell=cell,
        seed=args.seed, window_s=float(args.seconds),
        setup_s=w["setup_wall"] - t_start, records=w["records"],
        device=served.device, counters_before=w["before"],
        counters_after=w["after"], trace=w["trace"], checks=served.checks)


def narrate(run) -> list:
    """Utilisations that are no metric: printed on earlier lines."""
    steps = run.delta("steps")
    if not steps:
        return []
    sizes = motif.sizes_of(run.cell.config)
    active = run.delta("moe_active") / steps
    ring_b, ring_f = motif.attn_step_cost(
        sizes, "sliding", run.delta("window_kv_positions") / steps)
    full_b, full_f = motif.attn_step_cost(
        sizes, "full", run.delta("shared_kv_positions") / steps)
    experts = active * motif.expert_bytes(sizes)
    dense_b = motif.dense_q40_bytes(sizes)
    pairs = max(run.delta("moe_pairs"), 1)
    depth = run.delta("shared_kv_positions") / max(run.delta("sum_active"), 1)
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step at a mean depth of {depth:.0f} positions a row",
            f"a mean step moves (published bytes) {ring_b / 1e9:.3f} GB of "
            f"latent rings ({ring_f / 1e9:.1f} GFLOP), {full_b / 1e9:.3f} GB "
            f"of the full layers' plane ({full_f / 1e9:.1f} GFLOP of float32 "
            f"at HIGHEST), {experts / 1e9:.2f} GB of {active:.1f} distinct "
            f"held experts (summed over the expert layers; "
            f"{100 * run.delta('moe_local_pairs') / pairs:.1f} % of the "
            f"pairs landed here) and {dense_b / 1e9:.2f} GB of dense leaves; "
            f"pages in use at the end "
            f"{run.counters_after.get('shared_kv_pages')}"]
