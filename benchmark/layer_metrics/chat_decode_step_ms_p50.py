"""``decode_step_ms_p50`` where the end-to-end metric it should move is
``gap_ms_p50``: a per-layer metric is reported only where the metric it moves
is, so this cell family has the reader under a name of its own."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "ms"
MOVES = "gap_ms_p50"
SOURCE = "device_trace"

read = load_reader("layer_metrics", "decode_step_ms_p50").read
