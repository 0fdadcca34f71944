"""The mean depth of a row in a decode step of the window: the positions a
riding row reads in a full layer (itself and all before it: the change of
the program's ``shared_kv_positions``) over the rows that rode (the change of
``sum_active``). It sets the plane a step must read and score (2,304 B and
174,080 operations a position in each of three full layers) and deepens
through the window by about the steps it ran. None for a program without
the counter."""

from benchmark.harness.cells import load_reader

LAYER = "cache manager"
UNIT = "positions"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"

read = load_reader("layer_metrics", "mimo_depth_positions_mean").read
