"""Seconds of building the engine less its weights, from the program's own
start-up account (``obs/spans.startup_account``): ``cache`` (the KV cache,
page pool, rings and states) and ``engine`` (the rest of the constructor).
0.0 where a phase never opened; None for a program without the account (a
parent commit)."""

LAYER = "load path and compile cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    from distributed_llama_tpu.obs import spans

    account = getattr(spans, "startup_account", None)
    if account is None:
        return None
    phases = account()["phases"]
    return sum(phases.get(k, 0.0) for k in ("cache", "engine"))
