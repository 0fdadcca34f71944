"""The share of routed (row, expert) pairs that landed on an expert this
chip holds: the change of the program's ``moe_local_pairs`` over the change
of ``moe_pairs`` across the window. The router keeps its full width and
this chip holds one routing group of eight: an eighth (12.5 %), if the
seeded router is even. None for a program that counts no pairs landed
here."""

LAYER = "scheduler"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "moe_local_pairs" not in run.counters_after:
        return None
    pairs = run.delta("moe_pairs")
    return 100.0 * run.delta("moe_local_pairs") / pairs if pairs else None
