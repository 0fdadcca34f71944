"""Prompt positions at which the cross-decoder (the full layer's query side and
the fourteen layers after it) ran, over admissions, in the window: the
program's ``xdec_positions`` counter over its ``admit_prefills``. 1.0 where
the skip holds: admission chunks run the self-decoder alone and the prompt's
last token takes a decode step. A prompt that crawled through decode steps
would count all its positions. None for a program without the counters, or
a window without an admission."""


LAYER = "scheduler"
UNIT = "positions"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    if "xdec_positions" not in run.counters_before:
        return None
    admits = run.delta("admit_prefills")
    return run.delta("xdec_positions") / admits if admits else None
