"""Seconds the weights took on the host, from the program's own start-up
account (``obs/spans.startup_account``): ``pack`` (the host repack of the
codec tree into the kernels' layout) and ``place`` (the host's part of
placing the tree: the transfers are enqueued, not waited for), and ``load``
(reading the file; the benchmark's seeded tree does not pass there, so 0
here). 0.0 where a phase never opened; None for a program without the
account (a parent commit)."""

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
    return sum(phases.get(k, 0.0) for k in ("load", "pack", "place"))
