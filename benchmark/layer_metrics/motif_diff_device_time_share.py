"""``motif_sliding_device_time_share`` for what the differential heads and
the gate add to every layer: the ops of a decode step under the program's
``attn.diff`` scope (lambda's projection and sigmoid, the noise head's
subtraction in the latent space, the one ``W_UV`` product after it) and
``attn.gate`` scope (the gate's sigmoid and product; its projection rides in
``wkv_a``'s Q40 call), told BY IDENTITY from the step's compiled text
(``harness/motif.scoped_instructions``, which the driver reads in set-up of
a traced run). None without the text."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_sliding = load_reader("layer_metrics", "motif_sliding_device_time_share")


def read(run):
    return _sliding.part_share(run, "diff")
