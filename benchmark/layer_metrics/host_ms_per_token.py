"""The generation loop's own split of each step into device-blocking and
host time: ``GenStats.host_ms`` (logits transfer, sampling, loop) over
``GenStats.tokens``, summed over the window's generations."""

LAYER = "generation loop"
UNIT = "ms/token"
MOVES = "decode_ms_per_token"
SOURCE = "program_counter"


def read(run):
    if "tokens" not in run.counters_after or not run.delta("tokens"):
        return None
    return run.delta("host_ms") / run.delta("tokens")
