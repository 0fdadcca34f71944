"""Active rows per decode dispatch: the change of ``ContinuousStats``'
``sum_active`` over the change of ``steps`` across the window (both are
running totals, so ``avg_active`` itself would include warm-up)."""

LAYER = "scheduler"
UNIT = "rows"
MOVES = "gap_ms_p50"
SOURCE = "program_counter"


def read(run):
    if "steps" not in run.counters_after:
        return None
    n = run.delta("steps")
    return run.delta("sum_active") / n if n else None
