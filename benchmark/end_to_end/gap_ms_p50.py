"""Median gap between streamed tokens, client side, over all requests of
the window: the pace a chat user reads at, one decode step at the load's
row count plus the scheduler's host time. The gaps' tail (a step that also
held an admission prefill) is recorded per layer: its statistics swing by
more than any bound at the few dozen admissions a window holds. ms, lower
is better."""

from benchmark.harness.runtime import median


def read(run):
    return median(run.gaps_ms())
