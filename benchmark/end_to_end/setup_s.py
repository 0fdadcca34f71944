"""Process start to the first measured request: weights, placement, compile
or cache read, correctness check, warm-up. s, lower is better."""


def read(run):
    return run.setup_s
