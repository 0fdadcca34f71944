"""Mean wait between arrival and admission: the change of
``dllama_request_queue_wait_seconds``' ``_sum`` over the change of its
``_count`` across the window (exact, where bucket quantiles are not)."""

LAYER = "scheduler"
UNIT = "ms"
# what it would move is TTFT, which the chat cell records per layer and
# cannot judge (chat_ttft_ms_p25.py); it points at the cell's judged latency
MOVES = "gap_ms_p50"
SOURCE = "program_counter"


def read(run):
    if "queue_wait_count" not in run.counters_after:
        return None
    n = run.delta("queue_wait_count")
    return 1e3 * run.delta("queue_wait_sum_s") / n if n else None
