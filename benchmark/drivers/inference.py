"""Driver of the one-shot entry point: what ``frontend/cli.py`` ``inference``
calls (``runtime/generate.Engine`` and ``generate``; with ``tp > 1`` the
mesh of ``--tp``), in the run's own process, one sequence at a time.

Flags (``entries.inference`` of the configuration): ``tp``,
``prefill_chunk``. The traffic is a ``replay`` loop: a generation starts
when the one before it ends, until the window is over.
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import model, reference, runtime, traffic
from ..harness.runtime import note

CHECK_PROMPT_TOKENS = 32
CHECK_DECODE_POSITIONS = 8


def _build_engine(spec, tree, flags):
    from distributed_llama_tpu.runtime.generate import Engine

    tp = int(flags.get("tp", 1))
    if tp > 1:
        # mesh runs keep the codec tree: tp-aware packing and placement
        # happen in parallel/tp.shard_params (as cmd_inference does)
        from distributed_llama_tpu.parallel import make_mesh

        return Engine(spec, tree, mesh=make_mesh(sp=1, tp=tp))
    from distributed_llama_tpu.ops.linear import apply_q40_body_policy

    apply_q40_body_policy(spec, rows=1)    # before packing, as the CLI does
    return Engine(spec, tree)


def check_logits(engine, tree, sizes, config, seed: int, chunk: int) -> dict:
    """Prefill of a 32-token prompt and 8 decoded positions through the
    engine, teacher-forced on the engine's own greedy tokens, against the
    float32 reference's full forward pass over the same 40 tokens."""
    rng = np.random.default_rng([seed, 0xC4EC])
    tokens = [1] + [int(t) for t in rng.integers(
        3, sizes["vocab_size"], CHECK_PROMPT_TOKENS - 1)]
    n = len(tokens)
    engine.prefill(tokens[:n - 1], 0, chunk)
    got = []
    tok = tokens[-1]
    for pos in range(n - 1, n - 1 + CHECK_DECODE_POSITIONS):
        logits = np.array(engine.infer(tok, pos), np.float32)
        got.append(logits[:sizes["vocab_size"]])
        tok = int(np.argmax(got[-1]))
        tokens.append(tok)
    want = reference.logits(tree, sizes, np.asarray([tokens[:-1]]),
                            rope_base=config["rope_theta"])[0, n - 1:]
    diff = float(np.max(np.abs(np.stack(got) - want)))
    tol = float(config["check"]["logit_tolerance"])
    return {"what": f"engine logits vs float32 reference, "
                    f"{CHECK_DECODE_POSITIONS} positions after a "
                    f"{CHECK_PROMPT_TOKENS}-token prefill",
            "ok": bool(diff <= tol), "detail": {"max_abs_diff": diff,
                                                "tolerance": tol}}


def _generate(engine, tok, req, chunk: int, t_zero: float, counters: dict,
              tracer=None):
    """One generation through the program's ``generate``; the record has
    the shape of a client's (harness/client.py)."""
    from distributed_llama_tpu.runtime.generate import generate
    from distributed_llama_tpu.runtime.sampling import Sampler

    n, out = req["prompt_tokens"], req["output_tokens"]
    stamps: list = []

    def emit(_piece):
        stamps.append(time.monotonic() - t_zero)
        if tracer is not None and tracer.due():
            tracer.stop()     # a traced run's end-to-end times are not used

    sampler = Sampler(engine.spec.vocab_size, 0.0, 0.9, seed=req["id"] + 1)
    if tracer is not None:
        runtime.wrap_span(sampler, "sample", "inference.sample")
    due = time.monotonic() - t_zero
    # ``steps`` counts positions, the prompt's included; the last prompt
    # position already samples, so n - 1 + out positions give ``out`` tokens
    _, stats = generate(engine, tok, sampler, req["prompt"], n - 1 + out,
                        emit=emit, quiet=True, prefill_chunk=chunk)
    done = time.monotonic() - t_zero
    sampled = stamps[n - 1:]      # the first n - 1 emits echo the prompt
    for k, v in (("tokens", stats.tokens), ("host_ms", stats.host_ms),
                 ("infer_ms", stats.infer_ms)):
        counters[k] = counters.get(k, 0) + v
    ok = len(sampled) == out
    return {"id": req["id"], "due": due, "sent": due, "stamps": sampled,
            "done": done, "ok": ok, "prompt_tokens": n, "output_tokens": out,
            "error": None if ok else f"{len(sampled)} sampled tokens, not "
                                     f"{out} (ended early)"}


def run(cell, args, t_start: float) -> runtime.Run:
    import jax

    cache = runtime.enable_compile_cache()
    device = runtime.require_devices(cell.chips, args.rehearse)
    compiles = runtime.CompileCounter()
    config = cell.config
    flags = config["entries"]["inference"]
    chunk = int(flags["prefill_chunk"])
    model.check_runnable(config)
    sizes = model.sizes_of(config)
    spec = model.program_spec(sizes)
    note(f"device {device}; compile cache {cache}")
    tree = model.codec_tree(sizes, args.seed)
    note("codec tree built on the host")
    tok = model.tokenizer(sizes["vocab_size"])
    engine = _build_engine(spec, tree, flags)
    jax.block_until_ready(engine.params)
    note("engine built, weights placed")

    checks = [check_logits(engine, tree, sizes, config, args.seed, chunk)]
    note(f"check: {checks[0]['detail']}")
    del tree
    plan = traffic.generate(cell.traffic, args.seed, args.seconds)
    reqs = plan["clients"][0]
    warm = dict(reqs[0], id=-1, output_tokens=4)
    assert len(tok.encode(warm["prompt"], bos=True, eos=False)) \
        == warm["prompt_tokens"], "prompt does not encode one token a char"
    _generate(engine, tok, warm, chunk, time.monotonic(), {})
    note(f"warm; {compiles.count} programs made in set-up")

    if args.trace:
        runtime.wrap_span(engine, "infer", "inference.step")
        runtime.wrap_span(engine, "prefill", "inference.prefill")
    counters: dict = {}
    records = []
    tracer = None
    compiles_before = compiles.count
    if args.trace:
        tracer = runtime.Tracer(cell.traffic.get("trace_seconds", 2),
                                args.keep_trace)
    t_zero = time.monotonic()
    setup_s = time.time() - t_start
    if tracer is not None:
        tracer.start()
    for req in reqs:
        if time.monotonic() - t_zero >= args.seconds:
            break
        records.append(_generate(engine, tok, req, chunk, t_zero, counters,
                                 tracer))
    window_s = time.monotonic() - t_zero
    trace = tracer.finish() if tracer is not None else None
    counters["compiles"] = compiles.count - compiles_before
    note(f"window over: {len(records)} generations in {window_s:.1f}s")
    return runtime.Run(
        cell=cell, seed=args.seed, window_s=window_s, setup_s=setup_s,
        records=records, device=device,
        counters_before={k: 0 for k in counters}, counters_after=counters,
        trace=trace, checks=checks)
