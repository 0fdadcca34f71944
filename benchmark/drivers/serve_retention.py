"""Driver of the serving entry point for a power-retention configuration
(``harness/retention.py``): ``drivers/serve.py``'s client, traffic, trace
and ``Run``, imported, around a model built from the retention harness. What
is its own: how the server is built (no pages: ``serve`` with a slot of
fixed size a sequence), the check, the window (``drivers/serve.Served.window``
reads the page allocator, which this engine does not have) and the state's
counters.

The check teacher-forces the float32 reference (the ATTENTION form, no
state) on the SERVED streams and compares EVERY served position: the layer
has no top-k, so there is no margin rule and no position is left out. It is
made at the window's load: three requests a slot from two clients a slot,
all connecting at once (``check_requests``), so the rows fill, a queue
stands, rows are handed over with their predecessor's step in flight and
every later admission inserts into a row that holds another sequence's
state. No two requests share a prefix (sharing is refused for a state). The
first ``len(CHECK_PROMPTS)`` have fixed lengths, chosen to run every
program the window will (a prompt of two chunks: a state carried between
chunks; short ones: a padded chunk; the shortest a prompt can be, 3 tokens:
a decode step on a sequence of under three positions, where the normaliser
is read by cancellation; the state insert), so the check is the warm-up too.

The same positions, histories and comparison also read the CONTROL: what
the reference picks when it is computed one precision down (bfloat16
products). It has to come out over the tolerance, and is reported beside
the served streams' reading in every run; it does not decide ``correct``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..harness import model, retention, runtime, traffic
from ..harness.runtime import note
from . import serve as dense

# (prompt tokens, output tokens) of the first check requests; the others
# are drawn from the seed: prompts of 3 to 72 tokens, outputs of 12 to 48
CHECK_PROMPTS = ((140, 24), (70, 24), (33, 31), (22, 42), (12, 52), (3, 29),
                 (50, 14), (6, 26))


def _build_server(spec, tree, tok, flags, seed: int):
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy
    from distributed_llama_tpu.runtime.server import InferenceServer

    apply_q40_body_policy(spec, rows=int(flags["slots"]))
    return InferenceServer(
        spec, tree, tok, "127.0.0.1", 0, int(flags["slots"]), 64, 0.8, 0.9,
        seed, prefill_chunk=int(flags["prefill_chunk"]), quiet=True)


def check_requests(seed: int, slots: int) -> dict:
    """``3 * slots`` requests from ``2 * slots`` clients in a closed loop,
    no shared prefix: every client's first arrives at once (twice the
    rows), and the first ``slots`` clients send a second when their first
    completes."""
    import random

    rng = random.Random(seed ^ 0x5EC4)
    shapes = list(CHECK_PROMPTS[:3 * slots])
    while len(shapes) < 3 * slots:
        shapes.append((rng.randint(3, 72), rng.randint(12, 48)))
    reqs = [{"id": i, "due_s": None, "prompt_tokens": n, "output_tokens": out,
             "prompt": "".join(rng.choice(traffic.CHARS) for _ in range(
                 n - traffic.PROMPT_OVERHEAD))}
            for i, (n, out) in enumerate(shapes)]
    return {"loop": "closed",
            "clients": [reqs[c:c + 1] + reqs[2 * slots + c:2 * slots + c + 1]
                        * (c < slots) for c in range(2 * slots)]}


def served_rows(records, plan, tok):
    """Per check request (teacher-forcing row, prompt length, served
    tokens), or the error that voids the check."""
    by_id = {r["id"]: r for r in records}
    out = []
    for reqs in plan["clients"]:
        for req in reqs:
            rec = by_id.get(req["id"])
            if rec is None or not rec["ok"]:
                return None, (rec or {}).get("error", "no record")
            prompt = tok.encode(req["prompt"], bos=True, eos=False)
            n = len(prompt)
            if n != req["prompt_tokens"] or rec["tokens"][:n - 1] != prompt[1:]:
                return None, "prompt echo differs from the encoded prompt"
            served = rec["tokens"][n - 1:]
            out.append((prompt + served[:-1], n, served))  # the last feeds
    return out, None                                       # nothing


def shortfalls(want, picks) -> np.ndarray:
    """How far each pick's logit lies under the row's maximum: ``want``
    (..., vocab) float32 reference logits, ``picks`` (...) token ids."""
    return want.max(axis=-1) - np.take_along_axis(
        want, np.asarray(picks)[..., None], axis=-1)[..., 0]


def check_streams(records, plan, tok, tree, sizes, config,
                  group: int = 16) -> dict:
    """Teacher-force the retention reference on what ``serve`` streamed,
    and read the control on the same positions. The reference runs
    ``group`` rows at a time, all padded to one shape (one program)."""
    what = "served check requests"
    rows, error = served_rows(records, plan, tok)
    if error:
        return {"what": what, "ok": False, "detail": {"error": error}}
    width = max(len(r) for r, _, _ in rows)
    span = max(len(served) for _, _, served in rows)
    worst = first = control = 0.0
    compared = control_over = 0
    tol = float(config["check"]["logit_tolerance"])
    for lo in range(0, len(rows), group):
        part = rows[lo:lo + group]
        part += [part[-1]] * (group - len(part))       # one shape
        # a short row is padded: the layer is causal, so what follows a
        # position does not reach it
        tokens = np.asarray([r + [0] * (width - len(r)) for r, _, _ in part])
        keep = np.asarray([[min(n - 1 + i, width - 1) for i in range(span)]
                           for _, n, _ in part])
        want = retention.logits(tree, sizes, tokens, keep=keep)
        low = retention.logits(tree, sizes, tokens, keep=keep,
                               precision="bfloat16")
        for b, (_, n, served) in enumerate(part[:len(rows) - lo]):
            k = len(served)
            short = shortfalls(want[b, :k], served)
            worst = max(worst, float(short.max()))
            if n - 1 < 3:
                first = max(first, float(short[:3 - (n - 1)].max()))
            compared += k
            ctl = shortfalls(want[b, :k], low[b, :k].argmax(axis=-1))
            control = max(control, float(ctl.max()))
            control_over += int((ctl > tol).sum())
    return {"what": f"served tokens vs the float32 attention-form "
                    f"reference's maximum, {len(rows)} requests of "
                    f"{min(len(r) for r, _, _ in rows) + 1} to {width + 1} "
                    f"positions, teacher-forced, every served position",
            "ok": bool(worst <= tol),
            "detail": {"max_logit_shortfall": worst, "tolerance": tol,
                       "positions_compared": compared,
                       "shortfall_under_three_positions": first,
                       "control_bfloat16_max_shortfall": control,
                       "control_positions_over_tolerance": control_over}}


def counters(server, compiles) -> dict:
    """``drivers/serve.counters`` and the state's."""
    out = dense.counters(server, compiles)
    st = server.engine.stats
    out.update(state_bytes=getattr(st, "state_bytes", 0))
    return out


class Served(dense.Served):
    """``drivers/serve.Served`` over a retention model."""

    def __init__(self, cell, args):
        import jax

        self.cell, self.args = cell, args
        config = cell.config
        flags = config["entries"]["serve"]
        retention.check_runnable(config)
        sizes = retention.sizes_of(config)
        spec = retention.program_spec(sizes)   # a program without the
        #                            fields stops here, before the device
        cache = runtime.enable_compile_cache()
        self.device = runtime.require_devices(cell.chips, args.rehearse)
        self.compiles = runtime.CompileCounter()
        note(f"device {self.device}; compile cache {cache}")
        tree = retention.codec_tree(sizes, args.seed)
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
            plan = check_requests(args.seed, int(flags["slots"]))
            doc = dense.run_client(self.base_url, plan, time.monotonic(),
                                   600.0, keep_tokens=True)
            self.checks = [check_streams(doc["records"], plan, tok, tree,
                                         sizes, config)]
            note(f"check: {self.checks[0]['detail']}")
            st = self.server.engine.stats
            self.checks.append({
                "what": "the state is resident and rows ran ahead",
                "ok": bool(st.state_bytes > 0 and st.steps_ahead > 0),
                "detail": {"state_bytes": st.state_bytes,
                           "steps_ahead": st.steps_ahead,
                           "min_normaliser": st.min_normaliser}})
            note(f"warm; {self.compiles.count} programs made in set-up")
        except BaseException:
            self.server.stop()
            raise

    def window(self, plan: dict, seconds: float) -> dict:
        """``drivers/serve.Served.window`` without the page pool's peak."""
        server, compiles, args = self.server, self.compiles, self.args
        at_end: dict = {}
        t0 = time.monotonic() + 0.25     # the client is up by then

        def tick():
            if not at_end and time.monotonic() >= t0 + seconds:
                at_end.update(counters(server, compiles))

        before = counters(server, compiles)
        out = {"trace": None}
        th = None
        if args.trace:
            tracer = runtime.Tracer(self.cell.traffic.get("trace_seconds", 4),
                                    args.keep_trace)
            t_trace = t0 + min(float(self.cell.traffic.get(
                "trace_start_s", 0.0)), seconds / 4)

            def traced():
                time.sleep(max(0.0, t_trace - time.monotonic()))
                tracer.start()
                time.sleep(tracer.seconds)
                tracer.stop()

            th = threading.Thread(target=traced)
            th.start()
        doc = dense.run_client(self.base_url, plan, t0, seconds, on_tick=tick)
        if th is not None:
            th.join()
            out["trace"] = tracer.finish()
        if doc.get("stuck_threads"):
            note(f"{doc['stuck_threads']} client thread(s) never finished")
        out.update(records=doc["records"], before=before,
                   after=at_end or counters(server, compiles))
        return out


def run(cell, args, t_start: float) -> runtime.Run:
    with Served(cell, args) as served:
        plan = traffic.generate(cell.traffic, args.seed, args.seconds)
        setup_s = time.time() - t_start + 0.25
        w = served.window(plan, args.seconds)
        low = served.server.engine.stats.min_normaliser
    note(f"window over: {len(w['records'])} requests; smallest normaliser "
         f"phi(q).z of any decode step {low:.3g}")
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
    sizes = retention.sizes_of(run.cell.config)
    rows = int(run.cell.config["entries"]["serve"]["slots"])
    state = retention.state_step_bytes(sizes, rows)
    dense_b = costs.q40_weight_bytes(sizes)
    return [f"{steps} decode steps and {run.delta('prefill_chunks')} prefill "
            f"chunks in the window: {run.window_s / steps * 1e3:.2f} ms of "
            f"window a step",
            f"a step moves {state / 1e9:.2f} GB of state ({rows} rows) and "
            f"{dense_b / 1e9:.2f} GB of weights: step_gbps "
            f"{(state + dense_b) * steps / run.window_s / 1e9:.1f} (an "
            f"end-to-end utilisation, not a roofline share)"]
