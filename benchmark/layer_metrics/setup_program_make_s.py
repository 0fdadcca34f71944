"""Seconds of making programs, from the program's own start-up account
(``obs/spans.startup_account``, read in process after the window): tracing,
lowering and the compile or the read from the persistent cache of every
program made, summed. A line with ``compiles_in_window`` above 0 is void,
so what the account holds here was made in set-up. 0.0 where no program was
made; None for a program without the account (a parent commit)."""

LAYER = "load path and compile cache"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    from distributed_llama_tpu.obs import spans

    account = getattr(spans, "startup_account", None)
    if account is None:
        return None
    return sum(spans.program_seconds(row)
               for hows in account()["programs"].values()
               for row in hows.values())
