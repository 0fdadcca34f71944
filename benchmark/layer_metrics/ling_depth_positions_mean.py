"""The mean depth of a row in a decode step of the TRACED seconds: the
positions a riding row reads in a latent layer (itself and all before it:
the change of the program's ``shared_kv_positions`` where the profiler
starts and stops) over the rows that rode those steps (the change of
``sum_active``). It sets the bytes of pages a step must read (2,560 B a
position in each latent layer) and deepens through the window by about the
steps it ran; the state's bytes do not depend on it. None for a program
without the counter or a run without the traced counters."""

LAYER = "cache manager"
UNIT = "positions"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "trace_shared_kv_positions" not in run.counters_after:
        return None
    rows = run.delta("trace_sum_active")
    return run.delta("trace_shared_kv_positions") / rows if rows else None
