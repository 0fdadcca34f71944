"""The scheduler's admission account (``ContinuousStats.book_land``): every
landed step is booked by what stood before it on the device queue, on the
host's clock over the whole run; the landings are spans on the profiler's
clock (``serve.land``, ``serve.land.chunk``); ``/metrics`` carries the
totals and the request's side (``dllama_request_prefill_seconds``: admission
to first sampled token); and the benchmark's reader of the landing spans
(``benchmark/harness/landings.py``), on traces made by hand.

Toy engines of three kinds (a paged one, a contiguous one, a state one) on
the CPU. Counts and identities are looked at, never times as such: nothing
measured here is a device metric.
"""

import glob
import json
import math
import os
import sys
import time
import types

import pytest

from distributed_llama_tpu.models.spec import FloatType, TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.obs import profiler
from distributed_llama_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                               Registry)
from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                      ContinuousStats,
                                                      Request)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, landings  # noqa: E402
from benchmark.harness.reduce_trace import Op, Trace  # noqa: E402

SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=128, seq_len=32)
STATE_SPEC = TransformerSpec(
    dim=128, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=2,
    vocab_size=512, seq_len=128, weights_float_type=FloatType.Q40,
    qk_norm=True, qk_norm_per_head=True, attn_kind="retention",
    rope_theta=1e6, norm_eps=1e-6)
KINDS = ("paged", "contiguous", "state")
# two admissions back to back, a third when a slot frees, and a prompt too
# short for a prefill (no device work at its admission)
PROMPTS = ([1, 5, 9, 2, 8, 3, 7, 4, 6, 11], [1, 7, 9, 2, 8, 3, 12],
           [1, 4, 6, 9, 5, 3, 8, 2, 7, 10, 12, 11, 13], [1, 9])
STEPS = 16


@pytest.fixture(scope="module")
def built():
    """kind -> (spec, params, engine keywords, prefill chunk)."""
    dense = synth_params(SPEC, q40=False, seed=4, scale=0.3)
    return {
        "paged": (SPEC, dense, dict(page_size=4, prefill_chunk=4), 4),
        "contiguous": (SPEC, dense, dict(prefill_chunk=4), 4),
        "state": (STATE_SPEC, synth_params(STATE_SPEC, q40=True, seed=11),
                  dict(prefill_chunk=8), 8),
    }


def _engine(built, kind, **kw):
    spec, params, base, _ = built[kind]
    return ContinuousEngine(spec, params, slots=2, temperature=0.0,
                            topp=0.9, seed=5, **{**base, **kw})


def _expected_chunks(built, kind):
    chunk = built[kind][3]
    return sum(math.ceil((len(p) - 1) / chunk) for p in PROMPTS
               if len(p) - 1 >= 2)


def _serve(eng):
    """The four prompts through ``step_once``: two at once, two more three
    iterations in. Returns the requests and, per landed step, what its
    flight had ahead of it and whether it was launched ahead:
    [(chunks, admissions, ahead)]."""
    flights = []
    land = eng._land

    def spy(flight, *a, **kw):
        flights.append((flight.chunks_ahead, flight.admits_ahead,
                        flight.ahead))
        return land(flight, *a, **kw)

    eng._land = spy
    reqs = [eng.submit(Request(tokens=list(p), steps=STEPS))
            for p in PROMPTS[:2]]
    n = 0
    while True:
        live = eng.step_once()
        n += 1
        if n == 3:
            reqs += [eng.submit(Request(tokens=list(p), steps=STEPS))
                     for p in PROMPTS[2:]]
        if not live and n > 3:
            break
    assert all(r.done.is_set() and r.error is None for r in reqs)
    return reqs, flights


def _spy_instruments(monkeypatch):
    calls = []
    for cls, names in ((Counter, ("inc",)), (Gauge, ("set", "inc", "dec")),
                       (Histogram, ("observe",))):
        for name in names:
            orig = getattr(cls, name)

            def spy(self, *a, _orig=orig, _tag=(cls.__name__, name), **kw):
                calls.append(_tag)
                return _orig(self, *a, **kw)

            monkeypatch.setattr(cls, name, spy)
    return calls


def _metric(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise KeyError(name)


# ------------------------------------------------------------- the account


@pytest.mark.parametrize("kind", KINDS)
def test_a_dark_engine_books_every_landing_by_what_was_queued_ahead(
        built, kind, monkeypatch):
    calls = _spy_instruments(monkeypatch)
    eng = _engine(built, kind)
    assert eng._obs is None
    _, flights = _serve(eng)
    st = eng.stats
    want = _expected_chunks(built, kind)
    assert st.prefill_chunks == want
    assert st.admit_prefills == 3          # the two-token prompt enqueues none
    assert sum(c for c, _, _ in flights) == want
    assert sum(a for _, a, _ in flights) == 3
    assert len(flights) == st.steps
    # booked behind an admission: each step that stood behind one, and the
    # landing before it where the step was launched ahead (the admission
    # was enqueued before that landing's fetch, which it may have held up)
    behind = {i for i, (c, a, _) in enumerate(flights) if c or a}
    behind |= {i - 1 for i in behind if i and flights[i][2]}
    assert st.lands_behind_admit == len(behind)
    assert 3 <= st.lands_behind_admit < st.steps
    assert st.admits_back_to_back_max == max(a for _, a, _ in flights) == 2
    assert flights[0][:2] == (want - math.ceil(
        (len(PROMPTS[2]) - 1) / built[kind][3]), 2)   # both before step one
    assert 0 < st.land_behind_admit_s < st.land_s
    assert 0 < st.fetch_wait_behind_admit_s < st.fetch_wait_s < st.land_s
    assert calls == []                     # dark: no registry call


@pytest.mark.parametrize("kind", KINDS)
def test_land_s_is_the_sum_of_the_intervals_record_step_sees(built, kind):
    reg = Registry()
    eng = _engine(built, kind, metrics=reg)
    dts = []
    record = eng._obs.record_step

    def spy(dt, *a, **kw):
        dts.append(dt)
        return record(dt, *a, **kw)

    eng._obs.record_step = spy
    _serve(eng)
    st = eng.stats
    assert len(dts) == st.steps
    assert st.land_s == pytest.approx(sum(dts), rel=1e-9)
    text = reg.expose()
    for name, want in (
            ("dllama_engine_land_seconds_total", st.land_s),
            ("dllama_engine_land_behind_admit_seconds_total",
             st.land_behind_admit_s),
            ("dllama_engine_lands_behind_admit_total",
             st.lands_behind_admit),
            ("dllama_admit_prefills_total", st.admit_prefills),
            ("dllama_admit_prefill_chunks_total", st.prefill_chunks),
            ("dllama_engine_fetch_wait_seconds_total", st.fetch_wait_s),
            ("dllama_engine_fetch_wait_behind_admit_seconds_total",
             st.fetch_wait_behind_admit_s)):
        assert _metric(text, name) == pytest.approx(want, rel=1e-6), name


@pytest.mark.parametrize("kind", KINDS)
def test_request_prefill_seconds_is_admission_to_first_sampled_token(
        built, kind):
    reg = Registry()
    eng = _engine(built, kind, metrics=reg)
    reqs, _ = _serve(eng)
    served = [r for r in reqs if r.t_first_token]
    assert len(served) == len(reqs)
    assert all(r.t_first_token >= r.t_admit > 0 for r in served)
    text = reg.expose()
    assert _metric(text, "dllama_request_prefill_seconds_count") == len(served)
    total = sum(r.t_first_token - r.t_admit for r in served)
    assert _metric(text, "dllama_request_prefill_seconds_sum") \
        == pytest.approx(total, rel=1e-6)
    # TTFT = queue wait + this, request by request
    ttft = sum(r.t_first_token - r.t_enqueue for r in served)
    wait = sum(r.t_admit - r.t_enqueue for r in served)
    assert ttft == pytest.approx(wait + total, rel=1e-9)
    help_line = next(line for line in text.splitlines() if line.startswith(
        "# HELP dllama_request_prefill_seconds"))
    assert "first sampled token" in help_line


@pytest.mark.parametrize("kw, per_landing", [
    (dict(dispatch_tokens=4), 1), (dict(block_steps=3), 3),
    (dict(spec_k=2), 1)], ids=["step_mixed", "chain", "step_spec"])
def test_the_sibling_iterations_book_through_the_same_lines(built, kw,
                                                            per_landing):
    """``--dispatch-tokens``: the prompts ride the step, so every landing
    is plain; a chain books its k steps behind the admissions before it;
    all of them add up ``land_s``."""
    spec, params, _, _ = built["paged"]
    eng = ContinuousEngine(spec, params, slots=2, temperature=0.0, topp=0.9,
                           seed=5, page_size=4, prefill_chunk=4, **kw)
    eng.run([list(p) for p in PROMPTS[:3]], steps=STEPS)
    st = eng.stats
    assert st.land_s > 0 and 0 < st.fetch_wait_s < st.land_s
    if "dispatch_tokens" in kw:
        assert (st.lands_behind_admit, st.land_behind_admit_s,
                st.admit_prefills, st.prefill_chunks,
                st.admits_back_to_back_max) == (0, 0.0, 0, 0, 0)
        assert st.admit_share == 0.0
        assert st.plain_step_ms == pytest.approx(1e3 * st.land_s / st.steps)
    else:
        assert st.admit_prefills == 3 and st.prefill_chunks == 8
        assert st.lands_behind_admit >= 2 * per_landing
        assert st.lands_behind_admit % per_landing == 0
        assert st.admits_back_to_back_max == 2


def test_the_derived_readings_have_no_state_of_their_own():
    st = ContinuousStats(steps=100, prefill_chunks=12)
    for dt, chunks, admits in [(0.020, 0, 0)] * 90 + [(0.100, 1, 1)] * 8 \
            + [(0.180, 2, 2)] * 2:
        st.book_land(dt, 1, chunks, admits, wait_s=dt - 0.004)
    assert st.land_s == pytest.approx(90 * 0.02 + 8 * 0.1 + 2 * 0.18)
    assert st.lands_behind_admit == 10
    assert st.plain_step_ms == pytest.approx(20.0)
    assert st.admit_stall_s == pytest.approx(8 * 0.08 + 2 * 0.16)
    assert st.admit_stall_ms_per_chunk == pytest.approx(80.0)
    assert st.admit_share == pytest.approx(0.96 / 2.96)
    assert st.host_ms_per_step == pytest.approx(4.0)
    assert st.admits_back_to_back_max == 2
    # a landing the enqueue of a burst held up by 60 ms (the host stood in
    # the enqueue, not in the fetch), and the step behind the burst, short
    # by as much: together 3 chunks of 80 ms, and the host's own part of
    # an iteration is still read from the plain landings alone
    st.book_land(0.080, 1, 0, 0, wait_s=0.0, enqueued_since=True)
    st.book_land(0.200, 1, 3, 1, wait_s=0.196)
    for _ in range(4):
        st.book_land(0.020, 1, 0, 0, wait_s=0.016)
    st.steps, st.prefill_chunks = 106, 15
    assert st.lands_behind_admit == 12
    assert st.plain_step_ms == pytest.approx(20.0)
    assert st.admit_stall_ms_per_chunk == pytest.approx(80.0)
    assert st.host_ms_per_step == pytest.approx(4.0)
    clause = st.admission_clause
    assert "80.0 ms a chunk over 15 chunks" in clause
    assert "plain step 20.00 ms" in clause and "host 4.00 ms a step" in clause
    assert "at most 2 admissions back to back" in clause
    empty = ContinuousStats()            # nothing landed: zeros, no error
    assert (empty.plain_step_ms, empty.admit_share,
            empty.admit_stall_ms_per_chunk, empty.host_ms_per_step) \
        == (0.0, 0.0, 0.0, 0.0)


def test_a_step_launched_from_the_host_is_booked_from_the_first_enqueue(
        built):
    """With nothing in flight the admissions are enqueued first, and the
    enqueue can hold the host (here: 50 ms a chunk): the step's interval
    runs from the first enqueue, not from its own launch."""
    eng = _engine(built, "paged")
    fwd = eng._prefill_fwd

    def held(*a, **kw):
        time.sleep(0.05)
        return fwd(*a, **kw)

    eng._prefill_fwd = held
    eng.submit(Request(tokens=list(PROMPTS[0]), steps=STEPS))
    t_start = time.monotonic()
    eng.step_once()
    took = time.monotonic() - t_start
    st = eng.stats
    assert st.steps == 1 and st.prefill_chunks == 3
    assert 0.15 <= st.land_s <= took
    assert (st.lands_behind_admit, st.land_behind_admit_s) == (1, st.land_s)
    eng._prefill_fwd = fwd
    while eng.step_once():
        pass
    assert eng.stats.lands_behind_admit == 1


def test_a_flight_dropped_unlanded_hands_its_count_to_the_next(built):
    """A step launched ahead whose rows all stopped meanwhile is never
    landed; the chunks that stood before it stand before the next one."""
    eng = _engine(built, "paged")
    flights = []
    land = eng._land
    eng._land = lambda f, *a, **kw: (flights.append(
        (f.chunks_ahead, f.admits_ahead)), land(f, *a, **kw))[1]
    a = eng.submit(Request(tokens=[1, 9], steps=STEPS))
    eng.step_once()
    eng.step_once()                      # A decodes, a step ahead of it
    assert eng._flight is not None
    b = eng.submit(Request(tokens=list(PROMPTS[0]), steps=STEPS))
    fetch = eng._fetch

    def cancel_under_the_step(flight):
        out = fetch(flight)
        eng.cancel(a)
        eng.cancel(b)
        return out

    eng._fetch = cancel_under_the_step
    eng.step_once()   # admits B, launches ahead behind its chunks, lands
    eng._fetch = fetch
    assert eng._flight is None           # dropped: no row of it was left
    assert eng._ahead == [3, 1] and flights[-1] == (0, 0)
    eng.submit(Request(tokens=[1, 7], steps=4))
    while eng.step_once():
        pass
    assert (3, 1) in flights
    assert sum(c for c, _ in flights) == eng.stats.prefill_chunks == 3
    # the landing the enqueue may have held up, and the step behind it
    assert eng.stats.lands_behind_admit == 2


# ------------------------------------------------- on the profiler's clock


def _capture(tmp_path, fn):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=profiler.capture_options())
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [(ev.name, line.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]


@pytest.mark.parametrize("kind", KINDS)
def test_a_capture_holds_each_landing_and_the_chunks_it_paid_for(
        built, kind, tmp_path):
    _serve(_engine(built, kind))            # compile outside the capture
    eng = _engine(built, kind)
    spans = _capture(tmp_path, lambda: _serve(eng))
    lands = [s for s in spans if s[0] == "serve.land"]
    chunks = [s for s in spans if s[0] == "serve.land.chunk"]
    assert len(lands) == eng.stats.steps
    assert len(chunks) == eng.stats.prefill_chunks \
        == _expected_chunks(built, kind)

    def parent(s):
        held = [p for p in spans if p is not s and p[1] == s[1]
                and p[2] <= s[2] and s[3] <= p[3]
                and (p[2], p[3]) != (s[2], s[3])]
        return min(held, key=lambda p: p[3] - p[2])[0] if held else None

    assert all(parent(c) == "serve.land" for c in chunks)
    inner = [s for s in spans if s[0] in ("serve.census", "serve.sample")]
    assert len(inner) == 2 * len(lands)
    assert all(parent(s) == "serve.land" for s in inner)
    assert all(parent(s) is None for s in lands)
    assert sum(1 for land in lands
               if any(land[2] <= c[2] and c[3] <= land[3] for c in chunks)
               ) <= eng.stats.lands_behind_admit
    assert not [s for s in spans if s[0].endswith(".step")]


# ------------------------------------------------------- what went (item 6)


GONE = "METRICS" + "_SYNC"       # spelt so that a grep of tests/ finds no hit


@pytest.mark.parametrize("kind", KINDS)
def test_the_sync_switch_is_gone(built, kind, monkeypatch):
    """The environment switch that drained the cache after every step,
    set, changes nothing: the engine reads no such name and has no such
    path."""
    want = [list(r.out) for r in _serve(_engine(built, kind,
                                                metrics=Registry()))[0]]
    monkeypatch.setenv("DLLAMA_" + GONE, "1")
    import jax

    drains = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: drains.append(1) or block(x))
    eng = _engine(built, kind, metrics=Registry())
    assert not hasattr(eng._obs, "sync")
    got = [list(r.out) for r in _serve(eng)[0]]
    assert got == want and drains == []
    hits = []
    for top in ("distributed_llama_tpu", "tests"):
        for path in glob.glob(os.path.join(ROOT, top, "**", "*.py"),
                              recursive=True):
            text = open(path, encoding="utf-8").read()
            if GONE in text or "sync_device" + "_timing" in text:
                hits.append(os.path.relpath(path, ROOT))
    assert hits == []


# --------------------------------------- benchmark/harness/landings.py

MS = 1e6       # ns


def _op(name, lo, hi, label="sched"):
    return Op(name, label, lo * MS, hi * MS)


def _trace(spans, window=(0.0, 400 * MS)):
    spans = sorted(spans, key=lambda o: (o.start, -o.end))
    return Trace({"/device:TPU:0": []}, spans, window=window)


def _landing(t, chunks=0, label="sched"):
    """A ``serve.land`` of 1 ms at ``t`` ms with ``chunks`` empty chunk
    spans and the two children inside it."""
    out = [_op("serve.land", t, t + 1, label)]
    out += [_op("serve.land.chunk", t + 0.01 * (i + 1), t + 0.01 * (i + 1)
                + 0.001, label) for i in range(chunks)]
    return out + [_op("serve.census", t + 0.1, t + 0.3, label),
                  _op("serve.sample", t + 0.3, t + 0.9, label)]


def _hand_made():
    """Plain landings 20 ms apart; one that stood behind one chunk (100 ms
    since the landing before) and one behind two (180 ms)."""
    spans, t = [], 10.0
    for gap, chunks in [(0, 0), (20, 0), (20, 0), (100, 1), (20, 0),
                        (20, 0), (180, 2), (20, 0)]:
        t += gap
        spans += _landing(t, chunks)
    return spans


def test_landings_pair_a_landing_with_its_chunks_by_containment():
    tr = _trace(_hand_made() + [
        # another thread's chunk span inside a landing's interval: not its
        _op("serve.land.chunk", 30.2, 30.3, "other"),
        # and a chunk span outside every landing
        _op("serve.land.chunk", 35.0, 35.1)])
    got = landings.landings(tr)
    assert [x.chunks for x in got] == [0, 0, 0, 1, 0, 0, 2, 0]
    assert got[0].interval_ms is None        # nothing to count back to
    assert [round(x.interval_ms) for x in got[1:]] \
        == [20, 20, 100, 20, 20, 180, 20]
    assert landings.plain_ms_p50(tr) == pytest.approx(20.0)
    # (100 - 20) + (180 - 20) over three chunks
    assert landings.stall_ms_per_chunk(tr) == pytest.approx(80.0)
    run = types.SimpleNamespace(trace=tr, window_s=40.0,
                                counters_before={"prefill_chunks": 7},
                                counters_after={"prefill_chunks": 157},
                                delta=lambda k: 150)
    assert landings.window_share(run) == pytest.approx(30.0)   # 150 x 80 ms


def test_a_landing_the_enqueue_held_up_is_read_with_the_one_after():
    """The host enqueues a burst before it fetches the step in flight and
    the runtime holds it there: that landing comes 300 ms late and the one
    with the 10 chunks inside is short by as much. The pair adds up."""
    spans, t = [], 10.0
    for gap, chunks in [(0, 0), (20, 0), (20, 0), (20 + 300, 0),
                        (20 + 1600 - 300, 10), (20, 0), (20, 0)]:
        t += gap
        spans += _landing(t, chunks)
    tr = _trace(spans, window=(0.0, 2000 * MS))
    assert landings.plain_ms_p50(tr) == pytest.approx(20.0)
    assert landings.stall_ms_per_chunk(tr) == pytest.approx(160.0)
    # two bursts one after the other: the first's step is the second's
    # landing before, and is counted once
    spans, t = [], 10.0
    for gap, chunks in [(0, 0), (20, 0), (20 + 50, 0), (20 + 270, 4),
                        (20 + 160, 2), (20, 0)]:
        t += gap
        spans += _landing(t, chunks)
    tr = _trace(spans, window=(0.0, 2000 * MS))
    assert landings.stall_ms_per_chunk(tr) == pytest.approx(480 / 6)
    # a burst whose landing before has no known interval says nothing:
    # how late that landing came cannot be told
    tr = _trace(_landing(10) + _landing(400, chunks=4) + _landing(420))
    assert landings.stall_ms_per_chunk(tr) is None


def test_a_capture_with_no_chunk_reads_none_not_free():
    tr = _trace([s for t in (10, 30, 50, 70) for s in _landing(t)])
    assert landings.plain_ms_p50(tr) == pytest.approx(20.0)
    assert landings.stall_ms_per_chunk(tr) is None
    run = types.SimpleNamespace(trace=tr, window_s=40.0,
                                counters_after={"prefill_chunks": 9},
                                delta=lambda k: 9)
    assert landings.window_share(run) is None


def _window_run(trace, records, steps, chunks, window_s=40.0):
    counts = {"steps": steps, "prefill_chunks": chunks}
    return types.SimpleNamespace(
        trace=trace, window_s=window_s, records=records,
        counters_before=dict.fromkeys(counts, 0), counters_after=counts,
        delta=counts.__getitem__)


_NO_PAIR = [s for t in (10, 30, 50, 70) for s in _landing(t)]
_CLOSED = [{"sent": 0.0, "done": 25.0}, {"sent": 0.001, "done": None},
           {"sent": 24.0, "done": 41.5}, {"sent": None, "done": None}]
_OPEN = [{"sent": 1.0, "done": 11.0}, {"sent": 5.0, "done": 9.0},
         {"sent": 10.0, "done": 21.0}, {"sent": 30.0, "done": 44.0}]


@pytest.mark.parametrize("records, steps, chunks, want", [
    # a closed loop: the whole 40 s less 1,500 steps of 20 ms, over 125
    (_CLOSED, 1500, 125, 80.0),
    # an open loop slept 10 s: 30 s less 1,000 steps, over 100
    (_OPEN, 1000, 100, 100.0),
    # more steps than the time holds: not under 0
    (_OPEN, 1600, 100, 0.0),
    # a window with no chunk says nothing of what one costs
    (_CLOSED, 2000, 0, None)],
    ids=["closed_loop", "open_loop", "not_under_0", "no_chunk"])
def test_a_capture_with_no_pair_reads_the_windows_own_subtraction(
        records, steps, chunks, want):
    """A burst that the capture's edge cuts leaves landings and no whole
    pair; the line still has to carry the metric (a traced run whose line
    lacks it is refused), so the reader falls back on the window's counts
    at the capture's plain pace."""
    run = _window_run(_trace(_NO_PAIR), records, steps, chunks)
    assert landings.stall_ms_per_chunk(run.trace) is None
    got = landings.stall(run)
    assert got == (None if want is None else pytest.approx(want))
    share = landings.window_share(run)
    assert share == (None if want is None
                     else pytest.approx(100 * chunks * want / 40e3))
    for family in ("chat_", "sat_"):
        mod = cells.load_reader("layer_metrics",
                                family + "admit_stall_ms_per_chunk")
        assert mod.read(run) == got


def test_the_captures_own_reading_comes_before_the_windows():
    run = _window_run(_trace(_hand_made()), _CLOSED, 1500, 125)
    assert landings.window_stall_ms_per_chunk(run) == pytest.approx(80.0)
    run = _window_run(_trace(_hand_made()), _CLOSED, 1000, 125)
    assert landings.window_stall_ms_per_chunk(run) == pytest.approx(160.0)
    assert landings.stall(run) == pytest.approx(80.0)    # the pairs'
    # a parent commit has no plain pace to subtract at
    run.trace = _trace([_op("serve.fetch", 3, 9)])
    assert landings.stall(run) is None
    assert landings.window_share(run) is None


def test_busy_time_is_the_union_of_the_requests_cut_to_the_window():
    run = types.SimpleNamespace(window_s=40.0, records=_OPEN)
    assert landings.busy_ms(run) == pytest.approx(30e3)
    run.records = _CLOSED
    assert landings.busy_ms(run) == pytest.approx(40e3)
    run.records = []
    assert landings.busy_ms(run) == 0.0


def test_a_landing_the_captures_edge_cuts_is_left_out():
    spans = (_landing(-0.5, chunks=1)        # opens before the window
             + _landing(20) + _landing(40) + _landing(140, chunks=1)
             + _landing(399.5, chunks=3))    # closes after it
    got = landings.landings(_trace(spans))
    assert [x.chunks for x in got] == [0, 0, 1]
    # the cut landing still says when the step before the first whole one
    # landed; the cut one at the end is not counted at all
    assert [round(x.interval_ms, 1) for x in got] == [20.5, 20.0, 100.0]
    assert landings.stall_ms_per_chunk(_trace(spans)) == pytest.approx(
        100 - 20.25)


def test_an_interval_that_holds_a_sleep_is_left_out():
    spans = (_landing(10) + _landing(30) + [_op("serve.idle", 35, 85)]
             + _landing(120, chunks=1) + _landing(140) + _landing(240, 1))
    got = landings.landings(_trace(spans))
    assert [x.interval_ms for x in got][2] is None
    assert landings.plain_ms_p50(_trace(spans)) == pytest.approx(20.0)
    assert landings.stall_ms_per_chunk(_trace(spans)) == pytest.approx(80.0)


@pytest.mark.parametrize("trace", [None, _trace([
    _op("serve.census", 1, 2), _op("serve.sample", 2, 3),
    _op("serve.fetch", 3, 9)])], ids=["untraced", "a_parent_commit"])
def test_a_program_without_the_phase_reads_nothing(trace):
    run = types.SimpleNamespace(trace=trace, window_s=40.0,
                                counters_after={"prefill_chunks": 9},
                                delta=lambda k: 9)
    assert landings.landings(trace) == []
    assert landings.plain_ms_p50(trace) is None
    assert landings.stall_ms_per_chunk(trace) is None
    assert landings.window_share(run) is None


NEW_METRICS = {
    "plain_land_interval_ms_p50": ("ms", 20.0),
    "admit_stall_ms_per_chunk": ("ms", 80.0),
    "admission_window_share": ("%", 30.0)}
SAT_CELLS = ["mistral7b.serve-sat", "olmoe7b.gen-sat16",
             "brumby14b.gen-sat16", "deepseekv3.gen-sat32",
             "phi4flash.reason-sat32", "xing4.gen-sat32",
             "laguna.mix-sat32"]


@pytest.mark.parametrize("family", ["chat_", "sat_"])
@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_six_readers_and_their_entries(family, name):
    unit, want = NEW_METRICS[name]
    mod = cells.load_reader("layer_metrics", family + name)
    moves = "gap_ms_p50" if family == "chat_" else "out_tokens_per_s"
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) \
        == ("scheduler", unit, moves, "program_span")
    run = types.SimpleNamespace(trace=_trace(_hand_made()), window_s=40.0,
                                counters_after={"prefill_chunks": 150},
                                delta=lambda k: 150)
    assert mod.read(run) == pytest.approx(want)
    run.trace = None
    assert mod.read(run) is None
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        doc = json.load(fh)
    entry = next(m for m in doc["per_layer"] if m["name"] == family + name)
    listed = ["mistral7b.serve-chat"] if family == "chat_" else SAT_CELLS
    # a later cell whose window holds admissions appends its name
    assert entry["workloads"][:len(listed)] == listed
    if family == "sat_":    # PR 55's and PR 60's cells, in that order
        assert entry["workloads"][len(listed):] == [
            "nemotron3.reason-sat32", "ling3flash.reason-sat32"]
    assert dict(entry, workloads=listed) == {
        "name": family + name, "unit": unit, "better": "lower",
        "source": "program_span", "layer": "scheduler", "moves": moves,
        "workloads": listed}
    assert entry in doc["per_layer"]     # later PRs append after them
