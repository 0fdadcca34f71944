"""``harness/phases.py``: the idle time by groups of the program's host
phases and the busy time by program name, on traces made by hand and on
traces recorded on the chip, and the per-layer metrics that read them.

Runs on the CPU: nothing here measures anything.
"""

from __future__ import annotations

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, phases, reduce_trace  # noqa: E402
from benchmark.harness.reduce_trace import Op, Trace  # noqa: E402

FIXTURES = os.path.join(HERE, "fixtures")
NEW_METRICS = (
    "idle_step_ms_per_token", "idle_loop_ms_per_token",
    "chat_idle_prepare_ms_per_step", "chat_idle_fetch_ms_per_step",
    "chat_idle_finish_ms_per_step", "chat_admission_device_share",
    "sat_idle_prepare_ms_per_step", "sat_idle_fetch_ms_per_step",
    "sat_idle_finish_ms_per_step", "sat_admission_device_share")
MS = 1e6       # ns


def _op(name, lo, hi, label="fusion"):
    return Op(name, label, lo * MS, hi * MS)


def _serve_trace():
    """Two iterations of 10 ms on one device, made by hand. The device runs
    the step in [2, 8) and [12, 18) and an admission's three programs in
    [20, 26); everything else is idle."""
    dev = "/device:TPU:0"
    ops = [_op("step.1", 2, 8), _op("step.2", 12, 18),
           _op("gather.1", 20, 21), _op("chunk.1", 21, 25),
           _op("scatter.1", 25, 26)]
    mods = [Op("jit_serve_decode_step", "module", 2 * MS, 8 * MS),
            Op("jit_serve_decode_step", "module", 12 * MS, 18 * MS),
            Op("jit_serve_admit_gather", "module", 20 * MS, 21 * MS),
            Op("jit_serve_admit_prefill_chunk", "module", 21 * MS, 25 * MS),
            Op("jit_serve_admit_scatter", "module", 25 * MS, 26 * MS)]
    spans = []
    for t in (0, 10):
        spans += [_op("serve.step", t, t + 10, "sched"),
                  _op("serve.intake", t, t + 0.5, "sched"),
                  _op("serve.stage", t + 0.5, t + 1, "sched"),
                  _op("serve.decode", t + 1, t + 8.5, "sched"),
                  _op("serve.stage", t + 1, t + 1.5, "sched"),
                  _op("serve.dispatch", t + 1.5, t + 2.5, "sched"),
                  _op("serve.fetch", t + 2.5, t + 8.4, "sched"),
                  _op("serve.census", t + 8.5, t + 9, "sched"),
                  _op("serve.sample", t + 9, t + 9.8, "sched")]
    spans += [_op("serve.admit", 19.8, 20.2, "sched"),
              _op("serve.admit.gather", 19.9, 20.1, "sched"),
              _op("serve.idle", 26, 28, "sched")]
    spans.sort(key=lambda o: (o.start, -o.end))
    return Trace({dev: ops}, spans, window=(0.0, 30 * MS),
                 modules={dev: mods})


def test_groups_partition_the_idle_time_and_the_innermost_span_wins():
    tr = _serve_trace()
    idle_s = reduce_trace.busy(tr)["window_s"] - \
        reduce_trace.busy(tr)["busy_s"]["/device:TPU:0"]
    assert idle_s == pytest.approx(12e-3)
    by_span = phases.idle_by_span(tr)
    assert sum(by_span.values()) == pytest.approx(idle_s)
    # [0, 2) of each iteration: intake .5, stage .5 + .5 (the second one
    # inside serve.decode: the innermost wins), dispatch .5
    assert by_span["serve.intake"] == pytest.approx(1.0e-3)
    assert by_span["serve.stage"] == pytest.approx(2.0e-3)
    assert by_span["serve.dispatch"] == pytest.approx(1.0e-3)
    # [8, 10): fetch .4, decode's own .1, census .5, sample .8, step .2
    assert by_span["serve.fetch"] == pytest.approx(0.8e-3)
    assert by_span["serve.decode"] == pytest.approx(0.2e-3)
    # the second iteration's last .2 lie under serve.admit, of which .1
    # under its child: the driver's span keeps the first iteration's .2
    assert by_span["serve.step"] == pytest.approx(0.2e-3)
    assert by_span["serve.admit"] == pytest.approx(0.1e-3)
    assert by_span["serve.admit.gather"] == pytest.approx(0.1e-3)
    assert by_span["serve.idle"] == pytest.approx(2.0e-3)
    assert by_span[phases.NONE] == pytest.approx(2.0e-3)
    split = phases.idle_split(tr, phases.SERVE_GROUPS, phases.SERVE_REST)
    assert set(split) == {"prepare", "fetch", "idle", "finish"}
    assert sum(split.values()) == pytest.approx(idle_s)
    assert split["prepare"] == pytest.approx(4.2e-3)
    assert split["fetch"] == pytest.approx(0.8e-3)
    assert split["idle"] == pytest.approx(2.0e-3)
    assert split["finish"] == pytest.approx(5.0e-3)


def test_per_step_metrics_of_the_made_up_serve_trace():
    run = types.SimpleNamespace(trace=_serve_trace())
    # two dispatches in the window
    assert phases.serve_idle_ms_per_step(run, "prepare") == pytest.approx(2.1)
    assert phases.serve_idle_ms_per_step(run, "fetch") == pytest.approx(0.4)
    assert phases.serve_idle_ms_per_step(run, "finish") == pytest.approx(2.5)
    # admission: 6 of 18 busy ms
    assert phases.program_share(run.trace, phases.ADMISSION_PROGRAMS) \
        == pytest.approx(100 * 6 / 18)
    by = phases.busy_by_program(run.trace)
    assert by["jit_serve_decode_step"] == pytest.approx(12e-3)


def test_inference_groups_leave_prefill_out():
    dev = "/device:TPU:0"
    ops = [_op("chunk", 1, 5), _op("step.1", 7, 9), _op("step.2", 11, 13)]
    spans = [_op("inference.prefill", 0, 1.5, "main"),
             _op("inference.prefill_chunk", 0.2, 1.4, "main"),
             _op("inference.step", 5.5, 9.6, "main"),
             _op("inference.dispatch", 5.5, 7.2, "main"),
             _op("inference.fetch", 7.2, 9.6, "main"),
             _op("inference.sampler", 9.6, 9.8, "main"),
             _op("inference.sample", 9.65, 9.75, "main"),
             _op("inference.emit", 9.8, 10, "main"),
             _op("inference.step", 10, 13.5, "main"),
             _op("inference.dispatch", 10, 11.3, "main"),
             _op("inference.fetch", 11.3, 13.5, "main")]
    tr = Trace({dev: ops}, spans, window=(0.0, 14 * MS))
    split = phases.idle_split(tr, phases.INFERENCE_GROUPS,
                              phases.INFERENCE_REST)
    assert split["prefill"] == pytest.approx(1.0e-3)     # [0, 1)
    # dispatch 1.5 + 1.0 and fetch .6 + .5, read as one group: a real
    # trace's clocks cannot split them
    assert split["step"] == pytest.approx(3.6e-3)
    # [5, 5.5) under nothing, [9.6, 10) sampler and emit, [13.5, 14)
    assert split["loop"] == pytest.approx(1.4e-3)
    run = types.SimpleNamespace(trace=tr)
    assert phases.inference_idle_ms_per_token(run, "step") \
        == pytest.approx(1.8)
    assert phases.inference_idle_ms_per_token(run, "loop") \
        == pytest.approx(0.7)


def test_a_shift_of_the_device_clock_leaves_the_inference_split_alone():
    """The device's clock reads about 1 ms early in a chip trace: moving
    every device interval by 1 ms moves idle time between dispatch and
    fetch and nothing between ``step`` and ``loop``."""
    dev = "/device:TPU:0"
    spans = []
    for t in (0, 12, 24, 36):
        spans += [_op("inference.step", t, t + 11.8, "main"),
                  _op("inference.dispatch", t, t + 1.2, "main"),
                  _op("inference.fetch", t + 1.2, t + 11.8, "main"),
                  _op("inference.sampler", t + 11.8, t + 11.9, "main"),
                  _op("inference.emit", t + 11.9, t + 12, "main")]
    splits = []
    for early in (0.0, 1.0):
        ops = [_op("step", t + 1.1 - early, t + 10.8 - early)
               for t in (0, 12, 24, 36)]
        tr = Trace({dev: ops}, spans, window=(6 * MS, 42 * MS))
        splits.append((phases.idle_split(tr, phases.INFERENCE_GROUPS,
                                         phases.INFERENCE_REST),
                       phases.idle_by_span(tr)))
    (true, by_true), (read, by_read) = splits
    assert by_true["inference.dispatch"] == pytest.approx(3.3e-3)
    assert by_read["inference.dispatch"] == pytest.approx(0.3e-3)
    for split in (true, read):
        assert split["step"] == pytest.approx(6.3e-3)
        assert split["loop"] == pytest.approx(0.6e-3)


def test_nothing_to_read_gives_none_and_does_not_raise():
    """No trace, an empty trace, and a program without phases or program
    names (a parent commit's trace): every reader returns None."""
    empty = Trace({}, [])
    dev = "/device:TPU:0"
    parent = Trace({dev: [_op("step.1", 2, 8)]},
                   [_op("serve.step", 0, 10, "sched"),
                    _op("inference.step", 0, 10, "main")],
                   window=(0.0, 10 * MS),
                   modules={dev: [Op("jit__unknown", "module", 2 * MS,
                                     8 * MS)]})
    for trace in (None, empty, parent):
        run = types.SimpleNamespace(trace=trace)
        for name in NEW_METRICS:
            assert cells.load_reader("layer_metrics", name).read(run) is None
    assert phases.idle_by_span(empty) == {}
    assert phases.busy_by_program(empty) == {}
    # the parent's idle time is all under the drivers' spans, as the
    # ledger of PR 22 shows it
    assert sum(phases.idle_by_span(parent).values()) == pytest.approx(4e-3)
    assert phases.NONE not in phases.idle_by_span(parent)


def test_an_admission_free_window_reads_zero_not_none():
    tr = _serve_trace()
    dev = "/device:TPU:0"
    tr.modules[dev] = [m for m in tr.modules[dev]
                       if m.name == "jit_serve_decode_step"]
    assert phases.program_share(tr, phases.ADMISSION_PROGRAMS) == 0.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_entries_meet_the_name_unit_and_source_rules(name):
    doc = cells.load_benchmark(ROOT)
    entry = next(m for m in doc["per_layer"] if m["name"] == name)
    cells.check_name(entry["name"], "metric")
    cells.check_unit(entry["unit"])
    assert entry["source"] in ("program_span", "device_trace")
    assert entry["better"] == "lower"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    mod = cells.load_reader("layer_metrics", name)
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for w in entry["workloads"]:
        assert w in e2e[entry["moves"]]["workloads"]


def test_new_entries_come_last_and_the_old_ones_are_as_they_were():
    doc = cells.load_benchmark(ROOT)
    names = [m["name"] for m in doc["per_layer"]]
    assert tuple(names[-len(NEW_METRICS):]) == NEW_METRICS
    assert len(names) == len(set(names)) == 26 + len(NEW_METRICS)


@pytest.mark.parametrize("name", ["mistral7b_decode1", "yi34b_tp4_decode1"])
def test_on_the_parents_chip_traces_there_is_nothing_to_read(name):
    """PR 22's fixtures: a program without phases or program names. The
    idle time lies under the drivers' spans and every new reader finds
    nothing to read."""
    tr = reduce_trace.load(os.path.join(FIXTURES, name + ".xplane.pb"))
    by = phases.idle_by_span(tr)
    assert by and set(by) <= {"inference.step", "inference.sample",
                              "inference.prefill", phases.NONE}
    run = types.SimpleNamespace(trace=tr)
    for metric in NEW_METRICS:
        assert cells.load_reader("layer_metrics", metric).read(run) is None


# ------------------------------------- traces recorded on the chip by PR 24

def _expected():
    return cells.load_json(os.path.join(FIXTURES, "expected_phases.json"))


CHIP = {"mistral7b_decode1_phases": (phases.INFERENCE_GROUPS,
                                     phases.INFERENCE_REST,
                                     phases.INFERENCE_STEP),
        "mistral7b_serve_sat_phases": (phases.SERVE_GROUPS,
                                       phases.SERVE_REST,
                                       phases.SERVE_STEP)}


@pytest.mark.parametrize("name", sorted(CHIP))
def test_phases_on_traces_recorded_on_the_chip(name):
    """Trimmed from this PR's own chip runs (``tools/trim_trace.py``): the
    program's phases and program names as a v5e trace carries them."""
    groups, rest, step = CHIP[name]
    want = _expected()[name]
    tr = reduce_trace.load(os.path.join(FIXTURES, name + ".xplane.pb"))
    assert sorted({s.name for s in tr.spans}) == want["span_names"]
    assert phases.count(tr, step) == want["steps"]
    assert reduce_trace.idle_share(tr) == pytest.approx(want["idle_share"])
    split = phases.idle_split(tr, groups, rest)
    assert split == pytest.approx(want["idle_split_s"], rel=1e-6, abs=1e-12)
    # the groups partition the window's idle time
    idle_s = want["window_ms"] / 1e3 * want["idle_share"] / 100
    assert sum(split.values()) == pytest.approx(idle_s, rel=1e-6)
    by = phases.idle_by_span(tr)
    assert phases.busy_by_program(tr) == pytest.approx(want["programs_s"])
    assert phases.program_share(tr, phases.ADMISSION_PROGRAMS) \
        == pytest.approx(want["admission_device_share"])
    # the drivers' outer span keeps little of the idle time (it kept 96 to
    # 99 % before the program had phases of its own)
    outer = by.get("inference.step", 0.0) + by.get("serve.step", 0.0)
    assert outer < 0.15 * idle_s


def test_every_reader_of_a_cell_reads_the_chip_trace():
    serve = types.SimpleNamespace(trace=reduce_trace.load(os.path.join(
        FIXTURES, "mistral7b_serve_sat_phases.xplane.pb")))
    decode = types.SimpleNamespace(trace=reduce_trace.load(os.path.join(
        FIXTURES, "mistral7b_decode1_phases.xplane.pb")))
    for name in NEW_METRICS:
        run = decode if name.startswith("idle_") else serve
        value = cells.load_reader("layer_metrics", name).read(run)
        assert value is not None and value >= 0.0, name
    # the admission's three programs by hand: 12.0 + 87.2 + 8.1 ms of the
    # window's 179.9 busy ms
    assert cells.load_reader(
        "layer_metrics", "sat_admission_device_share").read(serve) \
        == pytest.approx(59.57, abs=0.01)
    # per dispatch: the three groups add up to the idle time outside
    # ``serve.idle`` over the two steps
    parts = [cells.load_reader("layer_metrics", n).read(serve)
             for n in NEW_METRICS[6:9]]
    assert sum(parts) * 2 == pytest.approx(
        193.31 * 6.925603952201131 / 100, rel=1e-6)
