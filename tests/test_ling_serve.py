"""Ling-3.0-flash's layout (tests/test_kda.py has the toy spec and says what
it holds) through both ENGINES: ``serve`` (chunked admission on pages, two
rows decoding while a third request is admitted, rows, states, conv rows and
pages handed from one request to the next) equal to one-at-a-time generation
through ``inference`` and to ``models/reference_kda.py`` on logits, in XLA
and with every kernel in interpret mode; and every line with which the spec
refuses what a delta-rule state beside a latent plane cannot run. A file of
its own so that the run's workers share the compiles."""

import numpy as np
import pytest

from distributed_llama_tpu.models import reference_kda as ref
from distributed_llama_tpu.models.spec import (Activation, ExpertLayout,
                                               KdaLayers, LatentAttn, Router,
                                               TransformerSpec)
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5
MARGIN_EPS = 1e-4
# tests/test_kda.py's toy spec: 8 of 16 experts held
SPEC = TransformerSpec(
    dim=64, hidden_dim=32, n_layers=6, n_heads=4, n_kv_heads=4,
    vocab_size=256, seq_len=64, weights_float_type=FloatType.Q40,
    n_experts=16, n_active_experts=4, rope_theta=6e6, norm_eps=1e-6,
    latent=LatentAttn(0, 32, 16, 8, 16, kinds=("kda", "kda", "full") * 2,
                      head_gate=True),
    layout=ExpertLayout(1, 96, 1, 8, 0),
    router=Router("sigmoid", 4, 2, True, 2.5, bias=True),
    activation=Activation(limits=True),
    kda=KdaLayers(heads=4, head_dim=16, d_conv=4))
STATE_ROW = 4 * (4 * 16 * 16 + 3 * 3 * 64)      # a KDA layer's, a row


@pytest.fixture(autouse=True)
def toy_chunk(monkeypatch):
    """The forward tiles a prompt's chunk form by ``ops/kda.CHUNK`` (64):
    8 here, so that the toy's prompts cross chunk boundaries."""
    from distributed_llama_tpu.ops import kda as kda_ops

    monkeypatch.setattr(kda_ops, "CHUNK", 8)


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=3)


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL"):
        monkeypatch.setenv(var, request.param)
    return request.param


def _strict(tree, seq, n_prompt):
    """(the reference's logits over ``seq``, how many of its positions
    come before the first router near-tie)."""
    want, margins, _ = ref.forward(tree, SPEC, seq)
    low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
    stop = int(low[0]) if low.size else len(seq)
    assert stop > n_prompt, "a near-tie inside the prompt: pick a seed"
    return want, stop


def test_serve_equals_one_at_a_time_generation(kernel_mode, tree):
    """Five requests on two rows (three with every kernel in interpret mode,
    where a compile is the cost; a third is admitted, in chunks of 8, while
    two decode; rows, states, conv rows and pages are handed over): every
    greedy pick is the reference's maximum at its position given its own
    prefix, and the stream is what ``inference`` generates for that prompt
    alone, up to the first router near-tie."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)
    from distributed_llama_tpu.runtime.generate import Engine

    steps, n_req = (30, 5) if kernel_mode == "xla" else (27, 3)
    rng = np.random.default_rng(5)
    prompts = [[1] + [int(t) for t in rng.integers(3, SPEC.vocab_size, k)]
               for k in (19, 9, 22, 4, 13)][:n_req]
    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=8, prefill_chunk=8)
    reqs = [eng.submit(Request(tokens=list(p), steps=steps))
            for p in prompts]
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    st = eng.stats
    assert st.prefill_chunks >= n_req and st.admit_prefills == n_req
    assert st.state_bytes == 2 * 4 * STATE_ROW
    assert st.window_bytes == 0 and st.window_kv_positions == 0
    assert st.shared_kv_positions > 0
    assert np.exp(-5.0) < st.ssm_min_decay <= 1.0
    assert 0.0 < st.gate_min < 1.0
    assert 0 < st.moe_local_pairs < st.moe_pairs
    assert st.layers_run == {"kda": 4 * st.steps, "latent": 2 * st.steps}
    worst = 0.0
    solo = Engine(SPEC, tree)
    for r, p in zip(reqs, prompts):
        seq = [p[0]] + list(r.out)
        want, stop = _strict(tree, seq[:-1], len(p))
        for pos in range(len(p) - 1, min(stop, len(seq) - 1)):
            worst = max(worst, float(want[pos].max()
                                     - want[pos][seq[pos + 1]]))
        if r is reqs[2]:        # the request admitted beside two rows
            solo.prefill(p[:-1], chunk=8)
            tok, alone = p[-1], []
            for pos in range(len(p) - 1, min(stop, len(seq) - 1)):
                tok = int(np.asarray(solo.infer(tok, pos)).argmax())
                alone.append(tok)
            assert alone == seq[len(p):len(p) + len(alone)]
            assert np.exp(-5.0) < solo.ssm_min_decay <= 1.0
    assert worst < TOL


REFUSED = [
    (dict(tp=2), "--tp 2", "delta-rule state"),
    (dict(prefix_share=True), "prefix sharing", "state cannot be shared"),
    (dict(spec_k=2), "--spec-k 2", "cannot be rolled back"),
    (dict(dispatch_tokens=16), "--dispatch-tokens 16", "mixed window"),
    (dict(kv_quant="q8"), "--kv-quant q8", "state is float32"),
    (dict(kv_host_pages=4), "--kv-host-pages", "spill and promote"),
    (dict(journal=True), "--journal", "state snapshot"),
    (dict(disagg=True), "--disagg-role", "prefilled KV pages"),
    (dict(block_steps=4), "--block-steps 4", "scrap page"),
    (dict(kv_cache_dtype="bf16"), "--kv-cache-dtype bf16", "float32"),
    (dict(page_size=0), "serve without --kv-page-size", "pages only"),
]


@pytest.mark.parametrize("flags,flag,why", REFUSED,
                         ids=[f for _, f, _ in REFUSED])
def test_what_a_state_beside_a_plane_cannot_run_is_refused_by_name(
        flags, flag, why):
    """Before this spec the set {"state", "plane"} was refused NOTHING
    (``_WHY`` had no line for it): prefix sharing over a state included."""
    from distributed_llama_tpu.runtime.continuous import (cache_refusals,
                                                          sequence_caches)

    caches = sequence_caches(SPEC)
    assert caches == frozenset({"state", "plane"})
    assert cache_refusals(caches, page_size=8) == []
    lines = cache_refusals(caches, **{"page_size": 8, **flags})
    assert len(lines) == 1 and lines[0].startswith(flag) and why in lines[0]
    assert "delta-rule model" in lines[0] or flag == "--tp 2"


def test_the_engine_refuses_with_those_lines(tree):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    with pytest.raises(ValueError, match="--spec-k 2.*rolled back"):
        ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                         seed=3, page_size=8, prefill_chunk=8, spec_k=2)
    with pytest.raises(ValueError, match="prefix sharing.*state cannot"):
        ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                         seed=3, page_size=8, prefill_chunk=8,
                         prefix_share=True)
    with pytest.raises(ValueError, match="serve without --kv-page-size"):
        ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                         seed=3, prefill_chunk=8)
