"""Motif-3-Beta's layout (tests/test_motif.py has the toy spec and says what
it holds) through both ENGINES against ``models/reference_motif.py`` on
logits: ``inference`` (prefill in chunks, then decode) and ``serve`` (chunked
admission on pages, rows, rings and pages handed from one request to the
next), in XLA and with every kernel in interpret mode. A file of its own so
that the run's workers share the compiles."""

import math

import numpy as np
import pytest

from distributed_llama_tpu.models import reference_motif as ref
from distributed_llama_tpu.models.spec import (Activation, ExpertLayout,
                                               HyperConnections, LatentAttn,
                                               Router, TransformerSpec)
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5
MARGIN_EPS = 1e-4
SEQ = 40
# tests/test_motif.py's toy spec
SPEC = TransformerSpec(
    dim=64, hidden_dim=32, n_layers=6, n_heads=10, n_kv_heads=10,
    vocab_size=384, seq_len=64, weights_float_type=FloatType.Q40,
    n_experts=8, n_active_experts=2, norm_eps=1e-5,
    latent=LatentAttn(32, 32, 16, 8, 16, kv_groups=2, noise_heads=1,
                      gate=True, kinds=("sliding", "sliding", "full") * 2,
                      window=8),
    layout=ExpertLayout(dense_layers=2, dense_hidden=96, shared=1),
    router=Router("sigmoid", 1, 1, True, 2.0, False),
    hyper=HyperConnections(4, 20, 1e-6, -math.inf, math.inf, 1e6),
    activation=Activation("polynorm", 0.5, 0.25))


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)


@pytest.fixture(scope="module")
def want(tree, tokens):
    return ref.forward(tree, SPEC, tokens)


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    """XLA everywhere, and every kernel (packed Q40, grouped experts, the
    paged and the ring latent decode) in interpret mode."""
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL"):
        monkeypatch.setenv(var, request.param)
    return request.param


def compared(margins, at_least):
    low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
    n = int(low[0]) if low.size else len(margins)
    assert n >= at_least, f"only {n} positions before a router near-tie"
    return n


def _through_inference(tree, tokens, ref_logits, n):
    """Prefill (chunks of 8: three ring wraps), then decode."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng = Engine(SPEC, tree)
    worst = 0.0
    eng.prefill([int(t) for t in tokens[:24]], chunk=8)
    for pos in range(24, n):
        got = eng.infer(int(tokens[pos]), pos)
        worst = max(worst, float(np.abs(np.asarray(got)
                                        - ref_logits[pos]).max()))
    return worst


def _through_serve(tree, tokens, ref_logits, n):
    """``ContinuousEngine`` on pages: five requests on two rows (chunked
    admission, rows, rings and pages handed over), each the same prompt cut
    at another length; a greedy stream's every pick must be the
    reference's maximum at its position, given the reference's own
    prefix."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=8, prefill_chunk=8)
    prompts = [[1] + [int(t) for t in tokens[:k]] for k in (19, 9, 22, 4, 13)]
    reqs = [eng.submit(Request(tokens=list(p), steps=n)) for p in prompts]
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    st = eng.stats
    assert st.hc_streams == 4 and st.moe_pairs == st.moe_local_pairs > 0
    assert st.prefill_chunks >= 5 and st.window_bytes == 2 * 4 * 8 * 128 * 4
    assert 0 < st.window_kv_positions < st.shared_kv_positions
    assert 0.0 < st.gate_min < st.gate_mean < 1.0
    worst = 0.0
    for r, p in zip(reqs, prompts):
        seq = [p[0]] + list(r.out)
        want, margins, _ = ref.forward(tree, SPEC, seq[:-1])
        low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
        stop = int(low[0]) if low.size else len(seq)
        assert stop > len(p), "a near-tie inside the prompt: pick a seed"
        for pos in range(len(p) - 1, min(stop, len(seq) - 1)):
            worst = max(worst, float(want[pos].max()
                                     - want[pos][seq[pos + 1]]))
    return worst


@pytest.mark.parametrize("entry", ["inference", "serve"])
def test_logits_agree_with_the_reference(kernel_mode, entry, tree, tokens,
                                         want):
    ref_logits, margins, _ = want
    n = compared(margins, SEQ * 3 // 4)
    if kernel_mode == "pallas":     # interpret mode: a second a step
        n = 27
    run = _through_inference if entry == "inference" else _through_serve
    assert run(tree, tokens, ref_logits, n) < TOL
