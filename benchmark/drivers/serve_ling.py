"""Driver of the serving entry point for ``harness/ling.py``'s configuration
(Ling-3.0-flash: Kimi-Delta-Attention layers beside latent attention, ONE
routing group of DeepSeek-V3's router held): ``drivers/serve_nemotron.py``'s
check, window, plan and counters, imported, around a model built from the
ling harness. What is its own: the reference the check teacher-forces (the
delta-rule RECURRENCE and expanded latent attention: ``ling.logits``, under
the name ``serve_nemotron.check_streams`` calls), the state's check and the
narration. The window's requests are ``harness/traffic.generate``'s for the
run's seed, as ``serve_nemotron`` and ``serve_hybrid`` plan theirs: three
state models stand under one mix and differ in the model alone.

The check is ``serve_nemotron.check_streams``'s, rule for rule (its module
docstring has them and why a state forces them): ``CHECK_REQUESTS`` requests
at once on the 32 slots, the configuration's ``check.long_requests`` first
(prompts of 2,560, 2,560, 1,024, 1,024, 384 and 128 tokens: five-chunk
admissions whose chunk form hands a state over 40 boundaries of 64), eight of
the 40 on REUSED rows; every served position compared on logits, STRICTLY up
to a request's first router margin under ``laguna.MARGIN_EPSILON`` and after
it by the share of positions over the tolerance, pooled over the short lots
and over the long lots apart; the bfloat16 control over the tolerance and
over the short pool's limit. The chip holds ONE routing group of eight: a
pair lands here only where group 0 is among a token's four kept groups, and a
pair on an expert held elsewhere adds nothing here, in the program and in
the reference alike.
"""

from __future__ import annotations

import contextlib
import math
import time

from ..harness import ling, model, nemotron, runtime, traffic
from ..harness.runtime import note
from . import serve as dense
from . import serve_laguna, serve_nemotron

CHECK_REQUESTS = serve_nemotron.CHECK_REQUESTS


@contextlib.contextmanager
def _reference():
    """``serve_nemotron.check_streams`` on this configuration's reference:
    it calls the reference as ``nemotron.logits``, and for the time of the
    check that name is ``ling.logits`` (``drivers/serve_mimo._reference``
    says why a name is rebound and no code copied; the function would
    rather TAKE its reference, and ``serve_nemotron.py`` is not this PR's
    to edit: PERF.md section 7)."""
    saved, nemotron.logits = nemotron.logits, ling.logits
    try:
        yield
    finally:
        nemotron.logits = saved


def check_streams(records, plan, tok, tree, sizes, config) -> dict:
    with _reference():
        check = serve_nemotron.check_streams(records, plan, tok, tree, sizes,
                                             config)
    check["what"] = check["what"].replace("nemotron reference",
                                          "delta-rule reference")
    return check


def state_check(st, sizes: dict, slots: int) -> dict:
    """The state is resident at its exact size, pages were used, some but
    not all routed pairs landed on a held expert, rows ran ahead, no state
    was forgotten in one token, no head gate is shut, and a step ran every
    layer."""
    kinds = ling.kinds_of(sizes)
    want = slots * kinds.count("kda") * ling.state_row_bytes(sizes)
    run = getattr(st, "layers_run", {})
    per = {k: run.get(k, 0) / max(st.steps, 1) for k in ("kda", "latent")}
    floor = math.exp(sizes["lower_bound"])
    return {
        "what": "the delta-rule states and conv rows are resident at their "
                "exact size, pages were used, some but not all routed pairs "
                "landed on a held expert, rows ran ahead, no state was "
                "forgotten in one token, no head gate is shut and a step "
                "ran every layer",
        "ok": bool(
            st.state_bytes == want and st.window_bytes == 0
            and st.shared_kv_positions > 0
            and 0 < st.moe_local_pairs < st.moe_pairs
            and st.moe_load is not None
            and st.moe_load.shape == (sizes["n_experts"],)
            and st.steps_ahead > 0 and floor < st.ssm_min_decay <= 1.0
            and 0.0 < st.gate_min < 1.0
            and per == {"kda": kinds.count("kda"),
                        "latent": kinds.count("full")}),
        "detail": {"state_bytes": st.state_bytes, "state_bytes_want": want,
                   "shared_kv_positions": st.shared_kv_positions,
                   "moe_pairs": st.moe_pairs,
                   "moe_local_pairs": st.moe_local_pairs,
                   "moe_active": st.moe_active,
                   "steps_ahead": st.steps_ahead,
                   "min_decay": st.ssm_min_decay, "gate_min": st.gate_min,
                   "layers_a_step": per}}


class Served(serve_nemotron.Served):
    """``drivers/serve_nemotron.Served`` (its window and its counters) over
    the ling harness's model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        ling.check_runnable(config)
        sizes = self.sizes = ling.sizes_of(config)
        spec = ling.program_spec(sizes)  # a program without the record
        #                                  stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = ling.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        ling.settle_shared_positions(
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


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = traffic.generate(cell.traffic, args.seed, args.seconds)
        setup_s = time.time() - t_start + 0.25
        w = served.window(plan, args.seconds)
        low = served.server.engine.stats.ssm_min_decay
    cut = sum(bool(r.get("cut")) for r in w["records"])
    note(f"window over: {len(w['records'])} requests, {cut} of them cut by "
         f"their clients at the window's end; smallest mean decay of any "
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
    sizes = ling.sizes_of(run.cell.config)
    rows = int(run.cell.config["entries"]["serve"]["slots"])
    active = run.delta("moe_active") / steps
    state = ling.state_step_bytes(sizes, rows)
    plane = ling.latent_step_bytes(
        sizes, run.delta("shared_kv_positions") / steps)
    experts = active * ling.expert_bytes(sizes)
    dense_b = ling.dense_q40_bytes(sizes)
    pairs = max(run.delta("moe_pairs"), 1)
    depth = run.delta("shared_kv_positions") / max(run.delta("sum_active"), 1)
    gbps = (state + plane + experts + dense_b) * steps / run.window_s / 1e9
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step at a mean depth of {depth:.0f} positions a row",
            f"a mean step moves {state / 1e9:.2f} GB of delta-rule state "
            f"({rows} rows, read and written), {plane / 1e9:.2f} GB of the "
            f"latent layers' pages, {experts / 1e9:.2f} GB of "
            f"{active:.1f} distinct held experts (summed over the expert "
            f"layers; {100 * run.delta('moe_local_pairs') / pairs:.1f} % of "
            f"the pairs landed here) and {dense_b / 1e9:.2f} GB of dense "
            f"leaves: step_gbps {gbps:.1f} (an end-to-end utilisation, not "
            f"a roofline share); pages in use at the end "
            f"{run.counters_after.get('shared_kv_pages')}"]
