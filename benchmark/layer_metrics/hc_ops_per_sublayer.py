"""Device ops a sub-layer's residual path costs where the path is several
streams: the ops of the path in the median decode step of the traced window
(``harness/hyper.hc_step_ops``: told by their instructions' scopes in the
step's compiled text; by position where the driver has none) over the
sub-layers a step mixes
the streams around (two a layer: 36 at 18 layers). What says whether the
path is latency-bound: an op of a few microseconds costs its launch, not its
bytes. None where the trace holds no decode step of such a model."""

from benchmark.harness import hyper
from benchmark.harness.runtime import median

LAYER = "residual path"
UNIT = "ops"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    steps = hyper.hc_step_ops(run.trace, hyper.path_names(run))
    if not steps:
        return None
    return median([s["ops"] / s["sublayers"] for s in steps])
