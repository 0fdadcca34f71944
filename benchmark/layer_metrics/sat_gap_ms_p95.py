"""95th percentile of the gaps between streamed tokens in the SATURATED
cell; not judged there (see ``sat_ttft_ms_p50``)."""

from benchmark.harness.runtime import percentile

LAYER = "scheduler"
UNIT = "ms"
MOVES = "out_tokens_per_s"
SOURCE = "host_clock"


def read(run):
    return percentile(run.gaps_ms(), 95)
