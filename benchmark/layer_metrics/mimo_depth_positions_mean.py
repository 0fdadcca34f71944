"""The mean depth of a row in a decode step of the window: the positions a
riding row reads in a full layer (itself and all before it: the change of
the program's ``shared_kv_positions``) over the rows that rode (the change of
``sum_active``). It sets the bytes of pages a step must read (5,120 B a
position in each of three full layers) and deepens through the window by
about the steps it ran. None for a program without the counter."""

LAYER = "cache manager"
UNIT = "positions"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "shared_kv_positions" not in run.counters_after:
        return None
    rows = run.delta("sum_active")
    return run.delta("shared_kv_positions") / rows if rows else None
