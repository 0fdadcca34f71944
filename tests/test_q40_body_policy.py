"""Q40 decode-body policy (ISSUE 3 satellite): the bench's A/B-winning
i4-plane + nb-major combo must reach plain `inference` runs through ONE
policy function, with DLLAMA_Q40_BODY as the explicit override and loud
reasons either way. Decision logic only — the kernels themselves are
pinned by tests/test_pallas_q40.py."""

from __future__ import annotations

import os

import pytest

from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                llama2_13b_spec)
from distributed_llama_tpu.ops.linear import (apply_q40_body_policy,
                                              q40_body_policy)


_KNOBS = ("DLLAMA_Q40_BODY", "DLLAMA_Q40_I4", "DLLAMA_NB_MAJOR",
          "DLLAMA_Q40_BODY_MAX_GB", "DLLAMA_Q40_KERNEL")


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in _KNOBS:
        monkeypatch.delenv(var, raising=False)
    yield
    # apply_q40_body_policy writes os.environ itself, and delenv of an
    # absent variable registered no undo: drop what a test left behind
    # (monkeypatch, torn down after this, puts back what was set before)
    for var in _KNOBS:
        os.environ.pop(var, None)


def test_auto_picks_i4_nb_for_7b_on_pallas(monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    policy, reason = q40_body_policy(llama2_7b_spec())
    assert policy == "i4-nb"
    assert "auto" in reason


@pytest.mark.parametrize("rows,want", [(1, "i4-nb"), (4, "i4-nb"),
                                       (5, "d-major"), (8, "d-major"),
                                       (16, "i4-nb")])
def test_auto_keeps_5_to_8_row_dispatches_off_nb_major(monkeypatch, rows,
                                                       want):
    """nb-major has a VPU body for T <= 4 and an MXU body for T > 8; a
    5..8-row decode dispatch (serve's default 8 slots) would take the XLA
    dequantize-then-dot route for every matmul — measured 75 vs 37.5
    ms/token on the chip (PERF.md, PR 21)."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    policy, reason = q40_body_policy(llama2_7b_spec(), rows=rows)
    assert policy == want, reason
    if want == "d-major":
        assert f"{rows}-row" in reason


def test_auto_declines_13b_on_memory_headroom(monkeypatch):
    # the 13B i4 conversion OOMed a 16 GB chip (BASELINE.md r5): auto must
    # keep d-major there, and say why
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    policy, reason = q40_body_policy(llama2_13b_spec())
    assert policy == "d-major"
    assert "headroom" in reason
    # ... but a raised gate flips it (the knob the bench's tp2/tp4 rank
    # rows effectively use at their smaller band sizes)
    monkeypatch.setenv("DLLAMA_Q40_BODY_MAX_GB", "12")
    policy, _ = q40_body_policy(llama2_13b_spec())
    assert policy == "i4-nb"


def test_auto_declines_off_pallas():
    # CPU / xla mode: layouts are moot, keep the stock picks
    policy, reason = q40_body_policy(llama2_7b_spec())
    assert policy == "d-major"
    assert "Pallas" in reason or "XLA" in reason


def test_explicit_env_always_wins(monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setenv("DLLAMA_Q40_I4", "off")
    # the label reports what the env actually engages — never a policy
    # nobody chose (a mislabel would defeat the comparability note)
    policy, reason = q40_body_policy(llama2_7b_spec())
    assert policy == "env(i4=off, nb-major=auto)" and "respected" in reason

    # direct env knobs beat DLLAMA_Q40_BODY too (nothing unsets user env)
    monkeypatch.setenv("DLLAMA_Q40_BODY", "i4-nb")
    policy, reason = q40_body_policy(llama2_7b_spec())
    assert policy.startswith("env(") and "respected" in reason

    # the exact winning combo set by hand reports as itself
    monkeypatch.setenv("DLLAMA_Q40_I4", "on")
    monkeypatch.setenv("DLLAMA_NB_MAJOR", "force")
    assert q40_body_policy(llama2_7b_spec())[0] == "i4-nb"

    monkeypatch.delenv("DLLAMA_Q40_I4")
    monkeypatch.delenv("DLLAMA_NB_MAJOR")
    policy, reason = q40_body_policy(llama2_7b_spec())
    assert policy == "i4-nb" and "explicit DLLAMA_Q40_BODY" in reason

    monkeypatch.setenv("DLLAMA_Q40_BODY", "nope")
    with pytest.raises(ValueError, match="DLLAMA_Q40_BODY"):
        q40_body_policy(llama2_7b_spec())


def test_apply_sets_env_knobs_and_notes(monkeypatch, capsys):
    import os

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    assert apply_q40_body_policy(llama2_7b_spec()) == "i4-nb"
    assert os.environ["DLLAMA_NB_MAJOR"] == "force"
    assert os.environ["DLLAMA_Q40_I4"] == "on"
    assert "Q40 body policy: i4-nb" in capsys.readouterr().err


def test_apply_never_overrides_explicit_env(monkeypatch, capsys):
    import os

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setenv("DLLAMA_Q40_BODY", "i4-nb")  # forced policy...
    monkeypatch.setenv("DLLAMA_Q40_I4", "off")      # ...but explicit knob
    apply_q40_body_policy(llama2_7b_spec())
    assert os.environ["DLLAMA_Q40_I4"] == "off"     # user env untouched
    # an env-labeled outcome sets NOTHING (the user's partial config is
    # not silently completed) and the note says what actually engages
    assert "DLLAMA_NB_MAJOR" not in os.environ
    assert "Q40 body policy: env(i4=off" in capsys.readouterr().err
