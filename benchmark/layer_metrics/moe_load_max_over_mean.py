"""How uneven the router is: the rows the busiest expert took over the mean
over the experts, from the change of the program's ``moe_load`` (rows routed
to each expert, summed over layers) across the window. 1.0 is uniform. None
for a program that counts no routed experts."""

LAYER = "scheduler"
UNIT = "ratio"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "moe_load" not in run.counters_after:
        return None
    load = run.delta("moe_load")
    return float(load.max() / load.mean()) if load.sum() > 0 else None
