"""Programs made inside the measured window: backend compiles and fetches
from the persistent cache, counted by a ``jax.monitoring`` listener the
benchmark registers (covers both entry points). Must read 0."""

LAYER = "load path and compile cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    return run.delta("compiles")
