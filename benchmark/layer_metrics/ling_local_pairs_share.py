"""``dsmoe_local_pairs_share`` under a kda spec: the share of routed (row,
expert) pairs that landed on an expert this chip holds (the change of the
program's ``moe_local_pairs`` over the change of ``moe_pairs`` across the
window). The router keeps its 512 outputs and this chip holds ONE of its 8
routing groups, of which a token keeps 4: an eighth (12.5 %), if the seeded
router and its choice bias are even. None for a program that counts no pairs
landed here."""

from benchmark.harness.cells import load_reader

LAYER = "scheduler"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"

read = load_reader("layer_metrics", "dsmoe_local_pairs_share").read
