"""``dsmoe_local_pairs_share`` under an ssd spec: the share of routed (row,
expert) pairs that landed on an expert this chip holds (the change of the
program's ``moe_local_pairs`` over the change of ``moe_pairs`` across the
window). The router keeps its 128 outputs and this chip holds 64 in order,
with no routing groups: a half (50 %), if the seeded router and its choice
bias are even. None for a program that counts no pairs landed here."""

from benchmark.harness.cells import load_reader

LAYER = "scheduler"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"

read = load_reader("layer_metrics", "dsmoe_local_pairs_share").read
