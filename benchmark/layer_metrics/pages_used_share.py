"""Peak KV pages in use over the pool's size, from the allocator, sampled
about every 50 ms through the window."""

LAYER = "cache manager"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    pool = run.counters_after.get("pool_pages")
    if not pool:
        return None
    return 100.0 * run.counters_after["peak_pages_used"] / pool
