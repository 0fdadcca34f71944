"""Driver of the serving entry point for a latent-attention expert
configuration (``harness/latent.py``): ``drivers/serve.py``'s client,
window, trace and ``Run``, imported, around a model built from the latent
harness. What is its own: the seeded tree's settling step, the check, and
the counters of a share of the experts and of the latent pages.

The check is made at the window's load, on the timed weights and programs at
the timed sizes: ``2 * slots`` requests from as many clients, all connecting
at once (``check_requests``), so the rows fill, a queue stands, rows and
pages are handed on and every later admission writes pages another sequence
held. The first are the configuration's ``check.long_requests``: requests
at the WINDOW'S OWN lengths (prompts of its four chunks and more, sequences
up to its longest, 1,536 positions: every block of the decode kernel's
loop, every entry of a page table the window fills, a chunk that reads a
plane gathered chunks earlier). Then ``CHECK_PROMPTS``, fixed short shapes
chosen to run every program the window will (a padded chunk, gather and
scatter), so the check is the warm-up too; the rest are drawn from the
seed. The float32 reference is teacher-forced on the SERVED streams, a
layer and an expert at a time, the long rows ``LONG_GROUP`` at a time, and
EVERY served position is compared on logits.

The layer has a top-k (twice: groups, then experts), which is discontinuous:
two float32 routers choose differently where a margin is under what their
scores differ by. So (ROADMAP B1, the OLMoE check's fault, not repeated):
every request opens with a character of its own; the two positions all
prompts still share (BOS and the tokenizer's leading space) are given wide
margins when the tree is made (``latent.settle_shared_positions``); a
request is compared STRICTLY up to its first position whose smallest margin
is under ``latent.MARGIN_EPSILON``, and a shortfall after that is excused in
THAT request only; the share rule (half the served positions compared
strictly) is over the requests that were comparable at all, of which there
must be a quarter.

A long request meets a near-tie inside its prompt (one position in a
hundred has one), so none of its served positions is strict. It decides all
the same, by HOW MANY of them fall short: an expert that flipped at one
position moves the later ones by what attention gives that one position,
and few of them change their pick; a fault of the kernel or of a page
table moves every position behind it. Of a request's served positions after
its first near-tie, where there are ``EXCUSED_MIN`` or more, the share over
the tolerance must stay under ``check.excused_share_limit``.

The same positions, histories and comparison also read the CONTROL: what
the reference picks when it is computed one precision down (bfloat16
products): at its worst strictly compared position of the first short
group, and as the share of a long request's excused positions it moves
over the tolerance (the largest among the first long group's requests, as
the rule reads a served stream). The first has to come out over the tolerance,
the second over the limit, in every run: a control that passes fails the
check.
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import latent, model, runtime, traffic
from ..harness.runtime import note
from . import serve as dense
from .serve_retention import served_rows, shortfalls

# (prompt tokens, output tokens) of the first short check requests; the
# others are drawn from the seed: prompts of 3 to 40 tokens, outputs of 12
# to 32
CHECK_PROMPTS = ((140, 20), (70, 20), (33, 24), (22, 30), (12, 32), (3, 29),
                 (50, 14), (6, 26))
LONG_GROUP = 3       # long rows the reference reads at a time
EXCUSED_MIN = 32     # excused positions a request needs for its share to count
_dense_counters = dense.counters


def check_requests(seed: int, slots: int, long_requests=()) -> dict:
    """``2 * slots`` requests, one a client, all at once; request i opens
    with character i of the alphabet, so no two share their first own
    token. ``long_requests`` ((prompt, outputs), ...) come first."""
    import random

    rng = random.Random(seed ^ 0x31A7E)
    n_req = min(2 * slots, len(traffic.CHARS))
    shapes = [tuple(x) for x in long_requests] + list(CHECK_PROMPTS)
    shapes = shapes[:n_req]
    while len(shapes) < n_req:
        shapes.append((rng.randint(3, 40), rng.randint(12, 32)))
    reqs = [{"id": i, "due_s": None, "prompt_tokens": n, "output_tokens": out,
             "long": i < len(long_requests),
             "prompt": traffic.CHARS[i] + "".join(
                 rng.choice(traffic.CHARS)
                 for _ in range(n - traffic.PROMPT_OVERHEAD - 1))}
            for i, (n, out) in enumerate(shapes)]
    return {"loop": "closed", "clients": [[r] for r in reqs]}


def check_streams(records, plan, tok, tree, sizes, config,
                  group: int = 32) -> dict:
    """Teacher-force the latent reference on what ``serve`` streamed."""
    what = "served check requests"
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": what, "ok": False, "detail": {"error": error}}
    tol = float(config["check"]["logit_tolerance"])
    limit = float(config["check"]["excused_share_limit"])
    n_long = sum(bool(r.get("long")) for c in plan["clients"] for r in c)
    worst = excused_worst = control = 0.0
    strict = served_n = comparable = excused = control_over = control_n = 0
    share_max, share_n, control_share = 0.0, 0, None
    smallest = float("inf")
    # the long rows (the plan's first) in groups of their own: one shape a
    # kind, so one set of programs a kind
    kinds = ((rows[n_long:], group, False), (rows[:n_long], LONG_GROUP, True))
    for kind, size, is_long in kinds:
        width = max((len(r) for r, _, _ in kind), default=0)
        span = max((len(served) for _, _, served in kind), default=0)
        for lo in range(0, len(kind), size):
            part = kind[lo:lo + size]
            real = len(part)
            part = part + [part[-1]] * (size - real)      # one shape
            # a short row is padded: the layers are causal, so what follows
            # a position does not reach it
            tokens = np.asarray([r + [0] * (width - len(r))
                                 for r, _, _ in part])
            keep = np.asarray([[min(n - 1 + i, width - 1)
                                for i in range(span)] for _, n, _ in part])
            with_control = lo == 0
            got, margins = latent.logits(
                tree, sizes, tokens, keep=keep, precisions=(
                    ("highest", "bfloat16") if with_control
                    else ("highest",)))
            want = got["highest"]
            for b, (row, n, served) in enumerate(part[:real]):
                k = len(served)
                smallest = min(smallest, float(margins[b, :len(row)].min()))
                first_tie = latent.strict_positions(margins[b, :len(row)])
                n_strict = max(0, min(k, first_tie - (n - 1)))
                short = shortfalls(want[b, :k], served)
                ctl = (shortfalls(want[b, :k], got["bfloat16"][b, :k].argmax(
                    -1)) if with_control else None)
                if n_strict:
                    comparable += 1
                    served_n += k
                    strict += n_strict
                    worst = max(worst, float(short[:n_strict].max()))
                    if with_control and not is_long:
                        control = max(control, float(ctl[:n_strict].max()))
                        control_over += int((ctl[:n_strict] > tol).sum())
                        control_n += n_strict
                if n_strict < k:
                    excused_worst = max(excused_worst,
                                        float(short[n_strict:].max()))
                    excused += int((short[n_strict:] > tol).any())
                if k - n_strict >= EXCUSED_MIN:
                    share_n += 1
                    share_max = max(share_max, float(
                        (short[n_strict:] > tol).mean()))
                    if with_control and is_long:
                        # as the rule reads a served stream: its worst row
                        control_share = max(
                            control_share or 0.0,
                            float((ctl[n_strict:] > tol).mean()))
    ok = (worst <= tol and 4 * comparable >= len(rows)
          and 2 * strict >= served_n and share_max < limit
          and control > tol
          and (control_share is None or control_share > limit))
    return {"what": f"served tokens vs the float32 latent reference's "
                    f"maximum, {len(rows)} requests of "
                    f"{min(len(r) for r, _, _ in rows) + 1} to "
                    f"{max(len(r) for r, _, _ in rows) + 1} "
                    f"positions at once, teacher-forced, every served "
                    f"position, strictly up to a request's first router "
                    f"margin under {latent.MARGIN_EPSILON}, and by the share "
                    f"of its positions that fall short after it",
            "ok": bool(ok),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_strict": strict,
                       "positions_served_of_comparable": served_n,
                       "requests_comparable": comparable,
                       "requests": len(rows),
                       "requests_with_an_excused_shortfall": excused,
                       "max_excused_shortfall": excused_worst,
                       "max_excused_share": share_max,
                       "excused_share_limit": limit,
                       "requests_with_an_excused_share": share_n,
                       "smallest_margin": smallest,
                       "control_bfloat16_max_shortfall": control,
                       "control_positions_over_tolerance": control_over,
                       "control_positions": control_n,
                       "control_bfloat16_excused_share": control_share}}


def counters(server, compiles) -> dict:
    """``drivers/serve.counters`` and the counts of a share of the experts
    and of the latent pages."""
    out = _dense_counters(server, compiles)
    st = server.engine.stats
    load = getattr(st, "moe_load", None)
    out.update(moe_pairs=getattr(st, "moe_pairs", 0),
               moe_local_pairs=getattr(st, "moe_local_pairs", 0),
               moe_active=getattr(st, "moe_active", 0),
               latent_pages=getattr(st, "latent_pages", 0),
               latent_positions=getattr(st, "latent_positions", 0),
               moe_load=np.zeros(1, np.int64) if load is None else load.copy())
    return out


class Served(dense.Served):
    """``drivers/serve.Served`` over a latent-attention expert model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        latent.check_runnable(config)
        sizes = latent.sizes_of(config)
        spec = latent.program_spec(sizes)   # a program without the records
        #                              stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = latent.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        latent.settle_shared_positions(
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
                                   600.0, keep_tokens=True)
            note("check requests served")
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            note(f"check: {self.checks[0]['detail']}")
            st = self.server.engine.stats
            self.checks.append({
                "what": "pairs landed on held experts, pages were reused "
                        "and rows ran ahead",
                "ok": bool(0 < st.moe_local_pairs < st.moe_pairs
                           and st.steps_ahead > 0),
                "detail": {"moe_pairs": st.moe_pairs,
                           "moe_local_pairs": st.moe_local_pairs,
                           "steps_ahead": st.steps_ahead}})
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
    sizes = latent.sizes_of(run.cell.config)
    active = run.delta("moe_active") / steps
    pairs, local = run.delta("moe_pairs"), run.delta("moe_local_pairs")
    gbps = ((active * latent.expert_bytes(sizes)
             + latent.dense_q40_bytes(sizes)) * steps / run.window_s / 1e9)
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step",
            f"experts: {pairs / steps:.0f} pairs routed a step of which "
            f"{local / steps:.0f} landed here ({100 * local / max(pairs, 1):.1f}"
            f" %), {active:.1f} held experts touched a step (summed over "
            f"{sizes['n_layers'] - sizes['dense_layers']} expert layers)",
            f"weights_gbps {gbps:.1f} (the distinct held experts routed to "
            f"and the dense Q40 leaves, packed bytes x steps over the "
            f"window: an end-to-end utilisation, not a roofline share); "
            f"latent pages in use at the end {run.counters_after.get('latent_pages')}"]
