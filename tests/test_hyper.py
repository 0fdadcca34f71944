"""A latent-attention expert model whose residual path is four streams
(manifold-constrained hyper-connections: Xing4.0's block) against its plain
float32 reference (models/reference_hyper.py), at toy widths on the CPU, on
seeded weights. Logits are compared, never sampled tokens; a sequence is
compared up to its first router near-tie. Also: the ONE residual function
is the plain add for every spec without streams."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, read_spec,
                                              tensor_byte_ranges,
                                              write_model)
from distributed_llama_tpu.models import reference_hyper, reference_latent
from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                params_to_device)
from distributed_llama_tpu.models.spec import (EXT6_STRUCT, ExpertLayout,
                                               HybridLayers,
                                               HyperConnections, LatentAttn,
                                               RopeScaling, Router,
                                               TransformerSpec, sambay_kinds)
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import hyper, pallas_moe
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5          # the sibling tests' (tests/test_latent.py)
MARGIN_EPS = 1e-4
SEQ = 40


def toy_spec(**kw):
    """dim 64, 4 streams, 2 dense + 3 expert layers, 8 experts, 2 a token,
    ONE routing group (the grouped choice reduces to a biased top-k)."""
    base = dict(dim=64, hidden_dim=32, n_layers=5, n_heads=4, n_kv_heads=4,
                vocab_size=384, seq_len=64, weights_float_type=FloatType.Q40,
                n_experts=8, n_active_experts=2, norm_eps=1e-6,
                latent=LatentAttn(32, 32, 16, 8, 16),
                layout=ExpertLayout(dense_layers=2, dense_hidden=96,
                                    shared=1),
                router=Router("sigmoid", 1, 1, True, 2.0, True),
                rope_scaling=RopeScaling(64.0, 32, 32.0, 1.0, 1.0, 1.0),
                hyper=HyperConnections(4))
    base.update(kw)
    return TransformerSpec(**base)


SPEC = toy_spec()


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)


@pytest.fixture(scope="module")
def want(tree, tokens):
    return reference_hyper.forward(tree, SPEC, tokens)


def compared(margins, at_least):
    low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
    n = int(low[0]) if low.size else len(margins)
    assert n >= at_least, f"only {n} positions before a router near-tie"
    return n


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    """XLA everywhere, and every kernel (packed Q40, grouped experts,
    latent decode) in interpret mode."""
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL"):
        monkeypatch.setenv(var, request.param)
    return request.param


# -- the program against the reference, through both entries -------------------

def _through_inference(tree, tokens, ref, n):
    """Prefill (a 24-row chunk), then decode through the contiguous cache."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng = Engine(SPEC, tree)
    worst = 0.0
    eng.prefill([int(t) for t in tokens[:24]], chunk=8)
    for pos in range(24, n):
        got = eng.infer(int(tokens[pos]), pos)
        worst = max(worst, float(np.abs(np.asarray(got) - ref[pos]).max()))
    return worst


def _through_serve(tree, tokens, ref, n):
    """``ContinuousEngine`` on pages: five requests on two rows (chunked
    admission, rows handed over), each the same prompt cut at another
    length; a greedy stream's every pick must be the reference's maximum
    at its position, given the reference's own prefix."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=8, prefill_chunk=8)
    prompts = [[1] + [int(t) for t in tokens[:k]] for k in (19, 9, 22, 4, 13)]
    # one length for all, so that the reference compiles once
    reqs = [eng.submit(Request(tokens=list(p), steps=27)) for p in prompts]
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    st = eng.stats
    assert st.hc_streams == 4 and st.hc_sublayers_a_step == 10
    assert st.moe_pairs == st.moe_local_pairs > 0       # every expert held
    assert st.latent_positions > st.steps and st.prefill_chunks >= 5
    worst = 0.0
    for r, p in zip(reqs, prompts):
        seq = [p[0]] + list(r.out)
        want, margins, _ = reference_hyper.forward(tree, SPEC, seq[:-1])
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
    ref, margins, _ = want
    n = compared(margins, SEQ * 3 // 4)
    if kernel_mode == "pallas":     # interpret mode: a second a step
        n = 30
    run = _through_inference if entry == "inference" else _through_serve
    assert run(tree, tokens, ref, n) < TOL


def test_bfloat16_products_fail_the_tolerance(tree, tokens, want):
    """The control: the same forward one precision down must read over."""
    from distributed_llama_tpu.ops.linear import matmul_precision

    with matmul_precision("bf16"):
        got, _ = forward(SPEC, params_to_device(tree, spec=SPEC),
                         init_cache(SPEC), jnp.asarray(tokens[:24]),
                         jnp.int32(0))
    assert np.abs(np.asarray(got) - want[0][:24]).max() > 5 * TOL


def test_a_chunk_of_four_streams_walks_its_live_blocks_only(tree, want,
                                                            monkeypatch):
    """The walk of tests/test_latent.py under the four streams (they are
    mixed outside the attention): 16 rows at 16 of 64 walk two blocks of
    16, agree with the whole plane and with the reference, and read
    nothing of the NaN planted past them."""
    from distributed_llama_tpu.models import latent

    params, toks = params_to_device(tree, spec=SPEC), jnp.asarray(
        np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)[:32])
    assert latent.chunk_walked_positions(SPEC.seq_len, 16, 16) == 32

    def second_chunk(dead):
        # traced anew each call: the whole-plane form is chosen at trace
        step = jax.jit(lambda p, c, t, pos: forward(SPEC, p, c, t, pos))
        _, cache = step(params, init_cache(SPEC), toks[:16], jnp.int32(0))
        return step(params, latent.LatentCache(cache.c.at[:, 32:].set(dead)),
                    toks[16:], jnp.int32(16))

    walk, over_nan = second_chunk(0.0), second_chunk(jnp.nan)
    monkeypatch.setattr(latent, "chunk_attn_block", lambda *_: None)
    whole = second_chunk(0.0)
    assert np.abs(np.asarray(walk[0]) - want[0][16:32]).max() < TOL
    assert 0 < np.abs(np.asarray(walk[0] - whole[0])).max() < TOL / 10
    assert np.array_equal(np.asarray(over_nan[0]), np.asarray(walk[0]))
    assert np.array_equal(np.asarray(over_nan[1].c[:, :32]),
                          np.asarray(walk[1].c[:, :32]))


# -- the residual function ------------------------------------------------------

def _plain_specs():
    latent = toy_spec(hyper=None)
    dense = TransformerSpec(64, 96, 2, 4, 4, 384, 64)
    return {"dense": dense, "latent": latent}


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_a_spec_without_streams_takes_the_plain_add_and_nothing_else(kind):
    """No new op reaches a cell that has no streams: the residual function
    of such a spec is the carry handed through and ONE ``add``."""
    spec = _plain_specs()[kind]
    x = jnp.ones((3, spec.dim), jnp.float32)

    def residual(x, y):
        h, coef = hyper.residual_in(spec, {}, "att", x)
        assert h is x and coef is None
        return hyper.residual_out(coef, hyper.fan_out(spec, x), y)

    eqns = jax.make_jaxpr(residual)(x, x).jaxpr.eqns
    assert [e.primitive.name for e in eqns] == ["add"]
    assert jax.make_jaxpr(lambda x: hyper.fold_in(spec, x))(x).jaxpr.eqns == []


@pytest.mark.parametrize("streams", [True, False])
def test_the_steps_compiled_text_names_the_paths_scopes(streams):
    """A capture names a device op by its instruction and carries no scope;
    the step's compiled text does (``ContinuousEngine.decode_program_text``):
    both scopes of the residual path are in it where the spec has streams,
    and neither where it has none."""
    from distributed_llama_tpu.obs.spans import SCOPE_HC_COEF, SCOPE_HC_MIX
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    spec = SPEC if streams else toy_spec(hyper=None)
    eng = ContinuousEngine(spec, synth_params(spec, q40=True, seed=3),
                           slots=2, temperature=0.0, topp=0.9, seed=3,
                           page_size=8, prefill_chunk=8)
    text = eng.decode_program_text()
    assert "serve_decode_step" in text
    for scope in (SCOPE_HC_COEF, SCOPE_HC_MIX):
        assert (f"/{scope}/" in text) is streams


def test_sinkhorn_is_doubly_stochastic_and_equals_a_numpy_loop():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((7, 4, 4)).astype(np.float32)
    m = np.exp(logits.astype(np.float64))
    for _ in range(20):
        m = m / (m.sum(axis=-2, keepdims=True) + 1e-6)
        m = m / (m.sum(axis=-1, keepdims=True) + 1e-6)
    # the program holds a matrix's row i as m[i] (n, tokens)
    rows = [jnp.exp(jnp.asarray(logits[:, i, :].T)) for i in range(4)]
    got = np.stack([np.asarray(r) for r in hyper._sinkhorn_rows(
        rows, 20, 1e-6)])                                   # (i, j, token)
    got = np.moveaxis(got, -1, 0)
    assert np.abs(got - m).max() < 1e-6
    assert np.abs(got.sum(-1) - 1).max() < 1e-5
    assert np.abs(got.sum(-2) - 1).max() < 1e-5
    ref = np.asarray(reference_hyper.sinkhorn(jnp.asarray(logits), 20, 1e-6))
    assert np.abs(ref - m).max() < 1e-6


@pytest.mark.parametrize("rows", [1, 24, 64])
def test_the_coefficient_stage_equals_the_reference(rows):
    rng = np.random.default_rng(rows)
    hc, n, dim = SPEC.hyper, 4, SPEC.dim
    x = jnp.asarray(rng.standard_normal((n, rows, dim)), jnp.float32)
    lw = {"hc_att_phi": rng.standard_normal((24, n * dim)).astype(
              np.float32) / 16,
          "hc_att_gate": np.asarray([0.5, 0.7, 0.9], np.float32),
          "hc_att_bias": rng.standard_normal(24).astype(np.float32)}
    got = hyper.coefficients(
        hc, SPEC.norm_eps, *(jnp.asarray(lw[f"hc_att_{k}"])
                             for k in ("phi", "gate", "bias")), x)
    with jax.default_matmul_precision("highest"):
        pre, post, res = reference_hyper.coefficients(
            SPEC, lw, "att", jnp.moveaxis(x, 0, 1))
    want = (pre.T, post.T, jnp.moveaxis(res, 0, -1))
    for a, c in zip(got, want):
        assert a.shape == c.shape
        assert np.abs(np.asarray(a) - np.asarray(c)).max() < 2e-6


# -- the router at one group ----------------------------------------------------

def test_route_at_one_group_is_a_plain_biased_top_k():
    rng = np.random.default_rng(2)
    gate = rng.standard_normal((8, 64)).astype(np.float32) / 8
    h = rng.standard_normal((50, 64)).astype(np.float32)
    bias = (0.05 * rng.standard_normal(8)).astype(np.float32)
    w, ids = pallas_moe.route(jnp.asarray(gate), jnp.asarray(h), 2,
                              SPEC.router, jnp.asarray(bias))
    s = 1 / (1 + np.exp(-(h.astype(np.float64) @ gate.T.astype(np.float64))))
    c = s + bias
    order = np.argsort(-c, axis=1)
    sure = (np.take_along_axis(c, order[:, 1:2], 1)
            - np.take_along_axis(c, order[:, 2:3], 1))[:, 0] > 1e-5
    assert sure.sum() > 40
    want_ids = np.sort(order[:, :2], axis=1)
    assert (np.sort(np.asarray(ids), axis=1)[sure] == want_ids[sure]).all()
    picked = np.take_along_axis(s, np.asarray(ids), axis=1)
    assert np.allclose(np.asarray(w)[sure], (2.0 * picked / picked.sum(
        1, keepdims=True))[sure], atol=1e-6)
    with jax.default_matmul_precision("highest"):
        rw, rids, _ = reference_latent.route(SPEC, gate, bias, jnp.asarray(h))
    assert (np.sort(np.asarray(rids), 1)[sure] == want_ids[sure]).all()


# -- header, loader, synth, converter ------------------------------------------

def test_header_version_6_round_trip():
    raw = SPEC.header()
    assert SPEC.header_version == 6 and len(raw) == EXT6_STRUCT.size == 232
    assert TransformerSpec.from_header(raw, FloatType.Q40) == SPEC
    odd = toy_spec(hyper=HyperConnections(2, 7, 1e-5, -3.5, 12.0))
    assert TransformerSpec.from_header(odd.header(), FloatType.Q40) == odd
    assert SPEC.rope_gap_bytes == 0


@pytest.mark.parametrize("kw,version,size", [
    (dict(), 0, 28),
    (dict(n_experts=8, n_active_experts=2, qk_norm=True), 2, 52),
    (dict(qk_norm=True, qk_norm_per_head=True, attn_kind="retention",
          rope_theta=1e6, norm_eps=1e-6), 3, 72),
    (dict(n_experts=8, n_active_experts=2, norm_eps=1e-6,
          latent=LatentAttn(32, 32, 16, 8, 16),
          layout=ExpertLayout(1, 96, 1), router=Router("sigmoid", 2, 1),
          rope_scaling=RopeScaling(40.0, 16)), 4, 192),
    (dict(n_layers=8, hybrid=HybridLayers(sambay_kinds(8), 16, 128, 16, 4,
                                          8)), 5, 352)])
def test_older_headers_read_and_write_byte_for_byte(kw, version, size):
    base = dict(dim=64, hidden_dim=32, n_layers=2, n_heads=4, n_kv_heads=4,
                vocab_size=384, seq_len=64)
    base.update(kw)
    spec = TransformerSpec(**base)
    raw = spec.header()
    assert (spec.header_version, len(raw)) == (version, size)
    again = TransformerSpec.from_header(raw)
    assert again == spec and again.header() == raw and again.hyper is None


@pytest.mark.parametrize("ftype", [FloatType.F32, FloatType.Q40])
def test_write_load_round_trip(tmp_path, ftype):
    spec = dataclasses.replace(SPEC, weights_float_type=ftype)
    dense = synth_params(spec, q40=False, seed=2)
    path = str(tmp_path / "m.bin")
    write_model(path, spec, dense)
    assert read_spec(path, ftype) == spec
    got_spec, got = load_model(path, weights_float_type=ftype)
    assert got_spec == spec
    assert jax.tree.structure(got) == jax.tree.structure(
        synth_params(spec, q40=ftype == FloatType.Q40, seed=2))
    for stack in (got, got["dense"]):       # float32 whatever the type
        for sub in ("att", "ffn"):
            assert stack[f"hc_{sub}_phi"].shape[1:] == (24, 256)
            assert stack[f"hc_{sub}_phi"].dtype == np.float32
    assert np.array_equal(got["hc_ffn_bias"], dense["hc_ffn_bias"])
    assert np.array_equal(got["dense"]["hc_att_phi"],
                          dense["dense"]["hc_att_phi"])
    ranges = tensor_byte_ranges(spec)
    assert sum(r.nbytes for r in ranges) + spec.header_bytes == \
        spec.file_size()
    names = [r.name for r in ranges if r.layer == 0]
    assert names[:10] == ["rms_att", "rms_ffn", "rms_q_a", "rms_kv_a",
                          "hc_att_phi", "hc_att_gate", "hc_att_bias",
                          "hc_ffn_phi", "hc_ffn_gate", "hc_ffn_bias"]


def test_seeded_leaves_are_what_the_configuration_says(tree):
    for stack in (tree, tree["dense"]):
        assert (stack["hc_att_gate"] == 0.5).all()
        phi = stack["hc_ffn_phi"]
        assert phi.std() == pytest.approx(256 ** -0.5, rel=0.1)
        b_res = stack["hc_att_bias"][:, 8:].reshape(-1, 4, 4)
        assert np.diagonal(b_res, axis1=1, axis2=2).mean() > 3.0
        assert abs(b_res[:, 0, 1:].mean()) < 1.0


def test_synth_model_file_runs_through_the_cli(tmp_path, capsys):
    from distributed_llama_tpu.frontend.cli import main
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, SPEC, seed=4)
    write_synth_tokenizer(tok, SPEC.vocab_size)
    rc = main(["inference", "--model", model, "--tokenizer", tok,
               "--prompt", "ab", "--steps", "6", "--temperature", "0",
               "--weights-float-type", "q40", "--tp", "1"])
    out = capsys.readouterr()
    assert rc == 0 and "4 residual streams" in out.out


def test_converter_maps_its_guess_of_the_names_and_says_so(tmp_path):
    import types

    from distributed_llama_tpu import convert

    spec = dataclasses.replace(SPEC, weights_float_type=FloatType.F32)
    dense = synth_params(spec, q40=False, seed=6)

    class _Tensor:
        def __init__(self, a):
            self.a = np.asarray(a, np.float32)

        def to(self, _):
            return self

        def numpy(self):
            return self.a

    state = {"model.embed_tokens.weight": _Tensor(dense["tok_embedding"]),
             "model.norm.weight": _Tensor(dense["rms_final"]),
             "lm_head.weight": _Tensor(dense["wcls"])}
    names = dict(convert.LATENT_TENSORS, **convert.HYPER_TENSORS)
    for name, key in names.items():
        for layer in range(5):
            stack, at = ((dense["dense"], layer) if layer < 2
                         else (dense, layer - 2))
            if name not in stack:
                continue
            if name.startswith("moe_w"):
                for e in range(8):
                    state[key.format(layer=layer, expert=e)] = _Tensor(
                        stack[name][at, e])
            else:
                state[key.format(layer=layer)] = _Tensor(stack[name][at])
    config = types.SimpleNamespace(
        model_type="xing4_0", hidden_size=64, moe_intermediate_size=32,
        intermediate_size=96, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=4, vocab_size=384, n_routed_experts=8,
        num_experts_per_tok=2, rope_theta=10000.0, rms_norm_eps=1e-6,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=2,
        n_shared_experts=1, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.0, scoring_func="sigmoid",
        topk_method="noaux_tc", hidden_act="silu", moe_layer_freq=1,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
        rope_scaling={"type": "yarn", "factor": 64,
                      "original_max_position_embeddings": 32,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                      "mscale_all_dim": 1.0})

    class Stub(convert.HFCheckpoint):
        def __init__(self):
            self.torch = types.SimpleNamespace(float32=None)
            self.config, self._state = config, state

    out = str(tmp_path / "m.bin")
    convert.convert_hf("toy", "float32", out, seq_len=64, ckpt=Stub())
    got_spec, got = load_model(out)
    assert got_spec == spec
    assert np.array_equal(got["hc_att_phi"], dense["hc_att_phi"])
    assert np.array_equal(got["dense"]["hc_ffn_bias"],
                          dense["dense"]["hc_ffn_bias"])
    assert np.array_equal(got["moe_w2"], dense["moe_w2"])
    assert "guess" in convert.HYPER_TENSORS_NOTE


def test_spec_rejects_streams_without_latent_attention():
    with pytest.raises(ValueError, match="several streams"):
        TransformerSpec(64, 96, 2, 4, 4, 384, 64,
                        hyper=HyperConnections(4))
    with pytest.raises(ValueError, match="several streams"):
        toy_spec(hyper=HyperConnections(1))
    with pytest.raises(ValueError, match="several streams"):
        toy_spec(hyper=HyperConnections(4, clamp_min=5.0, clamp_max=5.0))


# -- what it refuses, one parametrised test -------------------------------------

def _engine(**kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    base = dict(slots=2, temperature=0.0, topp=0.9, seed=1, page_size=8,
                prefill_chunk=8)
    base.update(kw)
    return ContinuousEngine(SPEC, synth_params(SPEC, q40=True, seed=1),
                            **base)


@pytest.mark.parametrize("kw,flag", [
    (dict(page_size=0), "serve without --kv-page-size"),
    (dict(kv_quant="q8"), "--kv-quant q8"),
    (dict(kv_host_pages=4), "--kv-host-pages"),
    (dict(remote_pages=True), "--disagg-role"),
    (dict(spec_k=2), "--spec-k 2"),
    (dict(dispatch_tokens=8), "--dispatch-tokens 8"),
    (dict(block_steps=2), "--block-steps 2"),
    (dict(cache_dtype=jnp.bfloat16), "--kv-cache-dtype"),
])
def test_the_engine_refuses_by_flag(kw, flag):
    with pytest.raises(ValueError, match="several residual streams") as e:
        _engine(**kw)
    assert flag in str(e.value)


def test_the_refusal_list_names_the_streams_under_tp():
    from distributed_llama_tpu.runtime.continuous import (cache_refusals,
                                                          sequence_caches)

    caches = sequence_caches(SPEC)
    assert caches == {"plane", "streams"}
    assert sequence_caches(toy_spec(hyper=None)) == {"plane"}
    lines = cache_refusals(caches, tp=4, page_size=16)
    assert len(lines) == 1 and "--tp 4" in lines[0]
    assert "streams' carry" in lines[0]
    assert cache_refusals(caches, page_size=16) == []
    assert cache_refusals(caches, serve=False) == []


# -- the analysis tools ---------------------------------------------------------

def _published_cut():
    return TransformerSpec(
        3584, 1024, 18, 32, 32, 131072, 2048, FloatType.Q40, n_experts=64,
        n_active_experts=4, norm_eps=1e-6,
        latent=LatentAttn(768, 512, 128, 64, 128),
        layout=ExpertLayout(2, 9216, 1),
        router=Router("sigmoid", 1, 1, True, 2.0, True),
        rope_scaling=RopeScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0),
        hyper=HyperConnections(4))


def test_memory_model_counts_the_streams_projections():
    from distributed_llama_tpu.analysis import memory_model as mm

    spec = _published_cut()
    # by hand: two projections of (24, 4 x 3584) float32, gates and biases
    assert mm.hyper_bytes(spec) == 18 * 2 * (24 * 14336 + 3 + 24) * 4
    attn = 768 * 3584 + 6144 * 768 + 576 * 3584 + 3584 * 4096
    expert = 3 * 1024 * 3584
    values = (18 * attn + 2 * 3 * 9216 * 3584 + 16 * 65 * expert
              + 131072 * 3584)
    assert mm.weights_device_bytes(spec, 1) == values // 32 * 20
    resident = (mm.weights_device_bytes(spec, 1)
                + mm.replicated_device_bytes(spec)
                + mm.kv_page_pool_bytes(spec, 1, 4096, 16))
    assert round(resident / 2**30, 2) == 12.21     # the rehearsal's 12.215
    plain = dataclasses.replace(spec, hyper=None)
    assert mm.activation_bytes_analytic(spec, 1) - \
        mm.activation_bytes_analytic(plain, 1) == 4 * 6 * 3584


def test_body_policy_packs_every_new_leaf_nb_major(monkeypatch):
    from distributed_llama_tpu.ops.linear import q40_body_policy

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    # nb 112 (in 3584) is off the 128 grid: the stock picks would leave
    # wq_a d-major and the chip would copy it in every step
    layout = q40_body_policy(_published_cut(), rows=32)
    assert layout.label == "nb-major" and layout.force_nb_major
    assert "(768, 3584)" in layout.reason and "nb 112" in layout.reason
