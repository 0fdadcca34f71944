"""How much of the device's busy time a decode step's residual path takes
where the path is several streams (``harness/hyper.py``): the self time of
the path's ops, told BY IDENTITY (``harness/hyper.hc_step_ops``: the ops
whose instruction lies under the program's ``hc.coef`` / ``hc.mix`` scopes in
the step's compiled text, which the driver read in set-up; by position among
the step's dense Q40 calls where it has no text), summed over the traced
window's decode steps, over the busy time of those steps (the union of their
op intervals). None where the trace holds no decode step of such a model."""

from benchmark.harness import hyper

LAYER = "residual path"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    steps = hyper.hc_step_ops(run.trace, hyper.path_names(run))
    path = sum(s["seconds"] for s in steps)
    busy = sum(s["busy"] for s in steps)
    if busy <= 0 or path <= 0:
        return None
    return 100.0 * path / busy
