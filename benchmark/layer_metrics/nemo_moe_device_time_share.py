"""``nemo_ssd_device_time_share`` for the expert layers (the pre-norm, the
router, its choice bias and top-k, slot building, the held experts' two
kernel calls with relu2 between them, the combine, the shared expert's two
calls, the residual add)."""

from benchmark.harness.cells import load_reader

LAYER = "device step"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"

_ssd = load_reader("layer_metrics", "nemo_ssd_device_time_share")


def read(run):
    return _ssd.part_share(run, "experts")
