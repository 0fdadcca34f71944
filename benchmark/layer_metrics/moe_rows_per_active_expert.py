"""Rows an active expert sees in a decode step: the change of the program's
``moe_pairs`` (routed (row, expert) pairs) over the change of ``moe_active``
(distinct experts, summed over layers and steps) across the window. It is
the small-T regime the expert kernel works in: 16 full rows x 8 over 56
distinct experts of 64 give 2.3. None for a program that counts neither."""

LAYER = "scheduler"
UNIT = "rows"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "moe_active" not in run.counters_after:
        return None
    active = run.delta("moe_active")
    return run.delta("moe_pairs") / active if active else None
