"""Driver of the serving entry point for an expert configuration
(``harness/olmoe.py``): ``drivers/serve.py`` with the model built from the
expert harness and the check teacher-forcing the expert reference. The
client, the check requests, the counters, the window and the trace are
``drivers/serve.py``'s, imported; this file replaces how the model is built,
which reference checks it, and adds the program's routed-expert counters
(``ContinuousStats.moe_pairs`` / ``moe_active`` / ``moe_load``) to the
counters the metric readers see.

The check uses the tests' near-tie rule: top-k is discontinuous, so each
check request is compared up to the first position whose smallest router
margin is under ``olmoe.MARGIN_EPSILON``; ``positions_compared`` is
reported and the check fails if it is under half of what was served. At 64
experts and 16 layers about one position in a hundred has such a margin, so
the check serves MANY SHORT requests beside ``drivers/serve.py``'s two that
share a prefix: 28 of 24 positions each, of which four in five are compared
(with 14 the compared share read 0.64 to 0.99 over 16 runs on the chip, mean
0.84: too near the half for a check that every later PR runs).
Folding the two serve drivers into one that finds its architecture from the
configuration is owed to a ``benchmark`` issue (PERF.md section 7).
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import model, olmoe, runtime, traffic
from ..harness.runtime import note
from . import serve as dense

CHECK_POSITIONS = dense.CHECK_POSITIONS
SHORT_REQUESTS = 28
SHORT_POSITIONS = 24
_dense_counters = dense.counters


def check_requests(seed: int) -> dict:
    """``drivers/serve.check_requests``' two requests that share a two-page
    prefix (64 positions each), and ``SHORT_REQUESTS`` short ones of
    ``SHORT_POSITIONS`` positions (prompts of 6 to 12 tokens), two to a
    client."""
    import random

    shared = dense.check_requests(seed)["clients"][0]
    rng = random.Random(seed ^ 0x5C4EC)
    short = []
    for i in range(SHORT_REQUESTS):
        n = rng.randint(6, 12)
        short.append({
            "id": len(shared) + i, "due_s": None, "prompt_tokens": n,
            "prompt": "".join(rng.choice(traffic.CHARS) for _ in range(
                n - traffic.PROMPT_OVERHEAD)),
            "output_tokens": SHORT_POSITIONS - n + 1})
    return {"loop": "closed",
            "clients": [shared] + [short[i:i + 2]
                                   for i in range(0, len(short), 2)]}


def check_streams(records, plan, tok, tree, sizes, config) -> dict:
    """Teacher-force the expert reference on what ``serve`` streamed."""
    what = "served check requests"
    by_id = {r["id"]: r for r in records}
    rows, spans = [], []
    for reqs in plan["clients"]:
        for req in reqs:
            rec = by_id.get(req["id"])
            if rec is None or not rec["ok"]:
                return {"what": what, "ok": False, "detail": {
                    "error": (rec or {}).get("error", "no record")}}
            prompt = tok.encode(req["prompt"], bos=True, eos=False)
            n = len(prompt)
            if n != req["prompt_tokens"] or rec["tokens"][:n - 1] != prompt[1:]:
                return {"what": what, "ok": False, "detail": {
                    "error": "prompt echo differs from the encoded prompt"}}
            seq = (prompt + rec["tokens"][n - 1:])[:CHECK_POSITIONS]
            spans.append((n, rec["tokens"][n - 1:], len(seq)))
            rows.append(seq + [0] * (CHECK_POSITIONS - len(seq)))
    # a short row is padded: attention is causal, so what follows a
    # position does not reach it
    want, margins = olmoe.logits(tree, sizes, np.asarray(rows),
                                 rope_base=config["rope_theta"])
    worst, compared, served_n, smallest = 0.0, 0, 0, float("inf")
    for b, (n, served, length) in enumerate(spans):
        limit = olmoe.compared_positions(margins[b, :length])
        smallest = min(smallest, float(margins[b, :length].min()))
        served_n += len(served)
        for i, t in enumerate(served):
            if n - 1 + i >= limit:
                break
            row = want[b, n - 1 + i]
            worst = max(worst, float(row.max() - row[t]))
            compared += 1
    tol = float(config["check"]["logit_tolerance"])
    return {"what": f"served tokens vs the float32 expert reference's "
                    f"maximum, {len(rows)} requests of {SHORT_POSITIONS} to "
                    f"{CHECK_POSITIONS} positions, teacher-forced, each up "
                    f"to its first router margin under "
                    f"{olmoe.MARGIN_EPSILON}",
            "ok": bool(worst <= tol and 2 * compared >= served_n),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_compared": compared,
                       "positions_served": served_n,
                       "smallest_margin": smallest}}


def counters(server, compiles) -> dict:
    """``drivers/serve.counters`` and the routed-expert counts."""
    out = _dense_counters(server, compiles)
    st = server.engine.stats
    load = getattr(st, "moe_load", None)
    out.update(moe_pairs=getattr(st, "moe_pairs", 0),
               moe_active=getattr(st, "moe_active", 0),
               moe_load=np.zeros(1, np.int64) if load is None else load.copy())
    return out


class Served(dense.Served):
    """``drivers/serve.Served`` over an expert model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        olmoe.check_runnable(config)
        sizes = olmoe.sizes_of(config)
        spec = olmoe.program_spec(sizes)    # a program without experts
        #                                     stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = olmoe.codec_tree(sizes, args.seed)
        note("codec tree built on the host")
        tok = model.tokenizer(sizes["vocab_size"])
        self.server = dense._build_server(spec, tree, tok, flags, args.seed)
        jax.block_until_ready(self.server.engine.params)
        note("server built, weights placed")
        if args.trace:
            runtime.wrap_span(self.server.engine, "step_many", "serve.step")
        self.server.start()
        self.base_url = f"http://127.0.0.1:{self.server.port}"
        try:
            # warm-up and check in one, as drivers/serve.py: prefill chunk,
            # decode step, gather and scatter all run here; the two sharing
            # requests go in turn (the second must find the first's pages)
            plan = check_requests(args.seed)
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   600.0, keep_tokens=True)
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
    sizes = olmoe.sizes_of(run.cell.config)
    active = run.delta("moe_active") / steps
    load = run.delta("moe_load")
    gbps = (olmoe.step_bytes(sizes, active) * steps / run.window_s / 1e9)
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step",
            f"experts: {active:.1f} active a step (summed over "
            f"{sizes['n_layers']} layers), {run.delta('moe_pairs') / steps:.0f}"
            f" routed pairs a step; busiest expert {load.max()} rows, mean "
            f"{load.mean():.0f}",
            f"weights_gbps {gbps:.1f} (the distinct routed experts' and the "
            f"dense leaves' packed bytes x steps over the window: an "
            f"end-to-end utilisation, not a roofline share); prefix_hits "
            f"{run.delta('prefix_hits')}, evictions {run.delta('evictions')}"]
