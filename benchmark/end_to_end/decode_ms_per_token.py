"""Median gap between sampled tokens of one sequence, host clock at each
token the program hands out (``generate``'s ``emit``): the source paper's
metric. ms/token, lower is better."""

from benchmark.harness.runtime import median


def read(run):
    return median(run.gaps_ms())
