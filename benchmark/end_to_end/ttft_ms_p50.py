"""Median time from when a request was DUE to its first sampled token, as
the client (``serve``) or the caller (``inference``) sees it. ms, lower is
better."""

from benchmark.harness.runtime import median


def read(run):
    return median(run.ttft_ms())
