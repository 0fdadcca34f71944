"""``client_late_ms_p99`` where the end-to-end metric it should move is
``out_tokens_per_s``: a per-layer metric is reported only where the metric it moves
is, so this cell family has the reader under a name of its own."""

from benchmark.harness.cells import load_reader

LAYER = "entry"
UNIT = "ms"
MOVES = "out_tokens_per_s"
SOURCE = "host_clock"

read = load_reader("layer_metrics", "client_late_ms_p99").read
