"""Median time to the first sampled token in the SATURATED cell, from when
the request was due (its client's last completion). Above the knee this is
the queue's and swings with it, so it is a per-layer metric here and not
judged."""

from benchmark.harness.runtime import median

LAYER = "scheduler"
UNIT = "ms"
MOVES = "out_tokens_per_s"
SOURCE = "host_clock"


def read(run):
    return median(run.ttft_ms())
