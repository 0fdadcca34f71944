"""``chat_admission_window_share`` where the end-to-end metric it should move is
``out_tokens_per_s``: a per-layer metric is reported only where the metric it moves
is, so this cell family has the reader under a name of its own."""

from benchmark.harness.cells import load_reader

LAYER = "scheduler"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_span"

read = load_reader("layer_metrics", "chat_admission_window_share").read
