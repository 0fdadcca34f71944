"""A generation's time to its first sampled token less what the device ran
in it: ``inference.encode``'s start to the end of the first
``inference.fetch``, less the program runs of the busiest device inside
(``harness/first_token.py``): the tokenizer, the launches, the transfer of
the first token, and any wait of the device for the host. The median over
the capture's generations. None for a program without
``inference.encode``."""

from benchmark.harness import first_token

LAYER = "generation loop"
UNIT = "ms"
MOVES = "ttft_ms_p50"
SOURCE = "program_span"


def read(run):
    return first_token.median_of(run, "host_ms")
