"""How late the load client ran: send time minus due time, 99th percentile
over the window's requests. A starved client must not read as a fast
server."""

from benchmark.harness.runtime import percentile

LAYER = "entry"
UNIT = "ms"
# what it would move is TTFT, which the chat cell records per layer and
# cannot judge (chat_ttft_ms_p25.py); it points at the cell's judged latency
MOVES = "gap_ms_p50"
SOURCE = "host_clock"


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.records
            if r.get("sent") is not None and 0 <= r["due"] < run.window_s]
    return percentile(late, 99)
