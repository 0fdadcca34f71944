"""A latent spec with layer kinds (Motif-3-Beta's layout: grouped
differential attention on a latent plane, 10 query heads over 2 latent KV
groups with one noise head a group, a per-token lambda, an elementwise gate,
rings of 8 latent rows in the sliding layers beside the full layers' plane,
PolyNorm in every FFN, four residual streams with no clamp) against
``models/reference_motif.py`` on LOGITS, at a toy size: 6 layers (sliding,
sliding, full, twice), 2 dense then 4 expert layers, 8 experts of which 2 a
token. The rings wrap several times in 40 positions.

TOL as ``tests/test_hyper.py``: float32 against float32 at highest precision
differs by op order alone (the largest reading here is 3e-6).
"""

import dataclasses
import functools
import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, read_spec,
                                             tensor_byte_ranges, write_model)
from distributed_llama_tpu.models import latent, reference_latent
from distributed_llama_tpu.models import reference_motif as ref
from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                params_to_device)
from distributed_llama_tpu.models.spec import (EXT9_STRUCT, Activation,
                                               ExpertLayout,
                                               HyperConnections, LatentAttn,
                                               Router, TransformerSpec)
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import pallas_latent_attention as pla
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5
MARGIN_EPS = 1e-4
SEQ = 40
KINDS = ("sliding", "sliding", "full") * 2
LATENT = LatentAttn(32, 32, 16, 8, 16, kv_groups=2, noise_heads=1, gate=True,
                    kinds=KINDS, window=8)


def toy(**kw):
    base = dict(dim=64, hidden_dim=32, n_layers=6, n_heads=10, n_kv_heads=10,
                vocab_size=384, seq_len=64, weights_float_type=FloatType.Q40,
                n_experts=8, n_active_experts=2, norm_eps=1e-5, latent=LATENT,
                layout=ExpertLayout(dense_layers=2, dense_hidden=96,
                                    shared=1),
                router=Router("sigmoid", 1, 1, True, 2.0, False),
                hyper=HyperConnections(4, 20, 1e-6, -math.inf, math.inf, 1e6),
                activation=Activation("polynorm", 0.5, 0.25))
    base.update(kw)
    return TransformerSpec(**base)


SPEC = toy()


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)


@pytest.fixture(scope="module")
def want(tree, tokens):
    return ref.forward(tree, SPEC, tokens)


# jitted once a (spec, shapes): an eager forward traces its scans each call
fwd = jax.jit(forward, static_argnums=0)
chunk_fwd = jax.jit(latent.forward_chunk, static_argnums=0)


def compared(margins, at_least):
    low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
    n = int(low[0]) if low.size else len(margins)
    assert n >= at_least, f"only {n} positions before a router near-tie"
    return n


# -- the spec, its header and its file -----------------------------------------

def test_the_spec_says_groups_signal_heads_and_kinds():
    assert SPEC.header_version == 9 and SPEC.slotted and SPEC.stateful
    assert (SPEC.latent_groups, SPEC.latent_signal_heads) == (2, 8)
    assert SPEC.latent_kinds == KINDS and LATENT.count("full") == 2
    shapes = dict(SPEC.attn_matmul_shapes())
    assert shapes["wkv_b"] == (2 * 32, 32) and shapes["wq_b"] == (240, 32)
    assert shapes["wg"] == (128, 64) and shapes["wo"] == (64, 128)
    names = [e[1] for e in SPEC.layer_plans()[0][2]]
    assert names.index("w_lambda") < names.index("pn_w") < names.index("wq_a")
    assert not SPEC.hyper.clamped and SPEC.hyper.stream_clamp == 1e6


@pytest.mark.parametrize("change", [
    dict(),
    dict(hyper=None),
    dict(activation=Activation()),
    dict(latent=dataclasses.replace(LATENT, kinds=(), window=0)),
    dict(latent=dataclasses.replace(LATENT, noise_heads=0, gate=False)),
    dict(latent=dataclasses.replace(LATENT, kv_groups=0, noise_heads=0)),
], ids=["all", "one-stream", "silu", "every-layer-full", "no-noise",
        "a-head-its-own-group"])
def test_header_round_trip(change):
    spec = toy(**change)
    raw = spec.header()
    assert spec.header_version == 9 and len(raw) == EXT9_STRUCT.size
    assert TransformerSpec.from_header(raw, FloatType.Q40) == spec


@pytest.mark.parametrize("hyper,version", [(None, 4), (HyperConnections(4), 6)])
def test_a_spec_that_states_none_of_it_writes_the_version_it_wrote(hyper,
                                                                   version):
    """DeepSeek-V3's and Xing4.0's files read and write byte for byte."""
    spec = toy(latent=LatentAttn(32, 32, 16, 8, 16), hyper=hyper,
               activation=Activation())
    assert spec.header_version == version and not spec.slotted
    assert spec.latent_groups == spec.n_heads == spec.latent_signal_heads
    assert TransformerSpec.from_header(spec.header(), FloatType.Q40) == spec


@pytest.mark.parametrize("change,match", [
    (dict(latent=dataclasses.replace(LATENT, kv_groups=3)), "kv_groups=3"),
    (dict(latent=dataclasses.replace(LATENT, noise_heads=5)), "noise"),
    (dict(latent=dataclasses.replace(LATENT, window=0)), "a window where"),
    (dict(latent=dataclasses.replace(LATENT, kinds=KINDS[:5])),
     "latent.kinds"),
    (dict(activation=Activation("gelu")), "activation"),
    (dict(activation=Activation("silu", 0.5)), "activation"),
    (dict(latent=None, hyper=None, layout=ExpertLayout(), router=Router(),
          n_experts=0, n_active_experts=0), "an activation other than SiLU"),
])
def test_the_spec_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        toy(**change)


def test_file_round_trip_and_byte_ranges(tmp_path):
    spec = toy(weights_float_type=FloatType.F32)
    tree = synth_params(spec, q40=False, seed=3)
    path = str(tmp_path / "m.bin")
    write_model(path, spec, tree)
    assert read_spec(path) == spec
    _, back = load_model(path)
    for k in ("w_lambda", "pn_w", "hc_att_phi", "wg", "wkv_b"):
        assert np.array_equal(back[k], tree[k])
        assert np.array_equal(back["dense"][k], tree["dense"][k])
    ranges = tensor_byte_ranges(spec)
    assert ranges[-1].offset + ranges[-1].nbytes == spec.file_size()
    assert [r.layer for r in ranges if r.name == "pn_w"] == list(range(6))


def test_synth_q40_file_is_byte_exact(tmp_path):
    path = str(tmp_path / "q.bin")
    assert write_synth_q40_model(path, SPEC, seed=1) == SPEC.file_size()
    assert read_spec(path, FloatType.Q40) == SPEC


def test_convert_reads_the_published_config():
    """``motif_spec`` on the catalog's keys."""
    from distributed_llama_tpu.convert import motif_spec

    c = types.SimpleNamespace(
        model_type="Motif", attention_cls="gdla", diff_v2=True,
        elementwise_attn_output_gate=True, experts_top_k=8, head_dim=192,
        headwise_attn_output_gate=False, hidden_act="poly_norm",
        hidden_size=4096, interleave_moe_layer_step=1,
        intermediate_size=12288, kv_lora_rank=512, max_window_layers=9,
        mhc_enabled=True, mhc_expansion_rate=4, mhc_sinkhorn_iters=20,
        moe_intermediate_size=1280, n_dense_first_layers=2,
        num_attention_heads=80, num_experts=384, num_hidden_layers=53,
        num_key_value_heads=16, num_noise_heads=16, num_shared_experts=1,
        q_lora_rank=1024, qk_rope_head_dim=64, rms_norm_eps=1e-5,
        rope_theta=10000, route_norm=True, route_scale=2,
        score_before_experts=False, score_func="sigmoid", sliding_window=128,
        sliding_window_pattern="interleave", sliding_window_period=4,
        swa_rope_theta=10000, use_sliding_window=True, v_head_dim=128,
        vocab_size=220160, polynorm_output_scale=0.5,
        polynorm_bias_clamp=0.5, hidden_clamp=1000000,
        rope_scaling={"rope_type": "yarn", "factor": 64,
                      "apply_yarn_scaling": False})
    spec = motif_spec(c, FloatType.Q40, 4096)
    la = spec.latent
    assert (la.q_rank, la.kv_rank, la.nope_dim, la.rope_dim, la.v_dim) == (
        1024, 512, 128, 64, 128)
    assert (la.kv_groups, la.noise_heads, la.gate, la.window) == (
        16, 1, True, 128)
    assert la.kinds[:8] == ("sliding",) * 3 + ("full",) + ("sliding",) * 3 + (
        "full",) and la.count("full") == 13
    assert spec.latent_signal_heads == 64 and spec.rope_scaling is None
    assert dict(spec.attn_matmul_shapes())["wo"] == (4096, 8192)
    assert spec.layout == ExpertLayout(2, 12288, 1)
    assert spec.router == Router("sigmoid", 1, 1, True, 2.0, False)
    assert spec.activation == Activation("polynorm", 0.5, 0.5)
    assert spec.hyper.streams == 4 and not spec.hyper.clamped
    assert spec.header_version == 9
    assert TransformerSpec.from_header(spec.header(), FloatType.Q40) == spec
    c.num_noise_heads = 8
    with pytest.raises(ValueError, match="one noise head a KV group"):
        motif_spec(c, FloatType.Q40, 4096)


# -- what the engines refuse ------------------------------------------------------

REFUSED = {
    "tp": (dict(tp=2, page_size=16), "--tp 2"),
    "prefix sharing": (dict(page_size=16, prefix_share=True),
                       "prefix sharing"),
    "no pages": (dict(), "serve without --kv-page-size"),
    "spec_k": (dict(page_size=16, spec_k=4), "--spec-k 4"),
    "dispatch_tokens": (dict(page_size=16, dispatch_tokens=64),
                        "--dispatch-tokens 64"),
    "kv_quant": (dict(page_size=16, kv_quant="q8"), "--kv-quant q8"),
    "journal": (dict(page_size=16, journal=True), "--journal"),
    "disagg": (dict(page_size=16, disagg=True), "--disagg-role"),
}


@pytest.mark.parametrize("streams", [True, False])
@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_each_refusal_by_name(flag, streams):
    """Plane + rings (+ streams): what rings refuse and what a plane
    refuses, by the lines that were there."""
    from distributed_llama_tpu.runtime.continuous import (cache_refusals,
                                                          sequence_caches)

    spec = SPEC if streams else toy(hyper=None)
    caches = sequence_caches(spec)
    assert caches == {"state", "plane", "rings"} | (
        {"streams"} if streams else set())
    kw, names = REFUSED[flag]
    lines = cache_refusals(caches, **kw)
    assert len(lines) == 1 and lines[0].startswith(names)
    assert "ring of latent rows" in lines[0]
    assert ("several residual streams" in lines[0]) == streams
    assert cache_refusals(caches, page_size=16) == []


def test_a_uniform_latent_spec_caches_a_plane_as_before():
    from distributed_llama_tpu.runtime.continuous import sequence_caches

    flat = toy(latent=dataclasses.replace(LATENT, kinds=(), window=0))
    assert sequence_caches(flat) == {"plane", "streams"}
    assert sequence_caches(toy(latent=flat.latent, hyper=None)) == {"plane"}
    assert isinstance(init_cache(flat), latent.LatentCache)
    assert init_cache(flat).c.shape[0] == 6
    both = init_cache(SPEC)
    assert both.c.shape == (2, 64, 128) and both.w.shape == (4, 8, 128)


# -- chunks and steps against the reference (both engines: tests/test_motif_serve.py)

@pytest.mark.parametrize("t_len", [8, 16])
def test_a_chunk_then_steps_through_wrapped_rings(tree, tokens, want, t_len):
    """One chunk of ``t_len`` (one and two turns of the ring), a padded
    chunk of 8 after it (``n_valid`` 5), then steps."""
    params = params_to_device(tree, spec=SPEC)
    toks = jnp.asarray(tokens)
    n = compared(want[1], 30)
    lg, cache = fwd(SPEC, params, init_cache(SPEC), toks[:t_len],
                    jnp.int32(0))
    assert np.abs(np.asarray(lg) - want[0][:t_len]).max() < TOL
    pad = jnp.concatenate([toks[t_len:t_len + 5], jnp.zeros(3, toks.dtype)])
    lg, cache = chunk_fwd(SPEC, params, cache, pad, jnp.int32(t_len),
                          jnp.int32(5))
    assert np.abs(np.asarray(lg[:5]) - want[0][t_len:t_len + 5]).max() < TOL
    for pos in range(t_len + 5, min(t_len + 9, n)):
        lg, cache = fwd(SPEC, params, cache, toks[pos:pos + 1],
                        jnp.int32(pos))
        assert np.abs(np.asarray(lg[0]) - want[0][pos]).max() < TOL


def test_bfloat16_products_fail_the_tolerance(tree, tokens, want):
    from distributed_llama_tpu.ops.linear import matmul_precision

    with matmul_precision("bf16"):
        got, _ = jax.jit(forward, static_argnums=0)(
            SPEC, params_to_device(tree, spec=SPEC), init_cache(SPEC),
            jnp.asarray(tokens[:24]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[0][:24]).max() > 5 * TOL


@pytest.mark.parametrize("ablation", ["lambda_zero", "every_layer_full",
                                      "no_gate", "silu", "clamped_bias"])
def test_each_mechanism_matters(tree, tokens, want, ablation):
    """Each mechanism left out of the REFERENCE moves the logits a hundred
    times the tolerance: a test that passes does not pass by their being
    idle at these weights."""
    spec, kw = SPEC, {}
    if ablation == "lambda_zero":
        kw = dict(lambda_zero=True)
    elif ablation == "every_layer_full":
        spec = toy(latent=dataclasses.replace(LATENT, kinds=("full",) * 6,
                                              window=0))
    elif ablation == "no_gate":
        spec = toy(latent=dataclasses.replace(LATENT, gate=False))
    elif ablation == "silu":
        spec = toy(activation=Activation())
    else:       # the clamp bites in some layer of these weights
        spec = toy(activation=Activation("polynorm", 0.5, 0.0))
        assert np.abs(tree["pn_w"][:, 3]).max() > SPEC.activation.clamp
    got = ref.forward(tree, spec, tokens, **kw)[0]
    assert np.abs(got - want[0]).max() > 100 * TOL


def test_a_noise_head_takes_a_fifth_to_four_fifths_of_a_signal_heads_mass(
        tree, tokens):
    """lambda lies around 0.5 and moves with the token."""
    lw = reference_latent._layer_of(tree, 0)
    x = jnp.asarray(tree["tok_embedding"], jnp.float32)[np.asarray(tokens)]
    h = reference_latent._rmsnorm(x, lw["rms_att"], SPEC.norm_eps)
    lam = np.asarray(latent.signal_lambda(lw, h))
    assert lam.shape == (SEQ, 8)
    assert 0.2 < np.quantile(lam, 0.1) and np.quantile(lam, 0.9) < 0.8
    assert lam.std(axis=0).min() > 0.02


# -- lambda = 0 and every layer full reduce to the latent reference ----------------

def test_without_noise_windows_gate_and_polynorm_it_is_the_latent_reference(
        tokens):
    """With lambda = 0 the noise heads drop out; with every layer full, no
    gate, SiLU and one stream what is left is ``reference_latent.py``'s
    block on a tree whose ``wkv_b`` repeats a group's rows for each of its
    signal heads and whose ``wq_b`` holds the signal heads alone."""
    la = dataclasses.replace(LATENT, gate=False, kinds=(), window=0)
    spec = toy(latent=la, hyper=None, activation=Activation(),
               weights_float_type=FloatType.F32)
    tree = synth_params(spec, q40=False, seed=7)
    got = ref.forward(tree, spec, tokens, lambda_zero=True)[0]

    flat = toy(latent=LatentAttn(32, 32, 16, 8, 16), n_heads=8, n_kv_heads=8,
               hyper=None, activation=Activation(),
               weights_float_type=FloatType.F32)

    def expand(stack):
        out = {k: v for k, v in stack.items() if k != "w_lambda"}
        n = stack["wq_b"].shape[0]
        out["wq_b"] = stack["wq_b"].reshape(n, 2, 5, 24, 32)[:, :, :4].reshape(
            n, 8 * 24, 32)
        out["wkv_b"] = np.repeat(stack["wkv_b"].reshape(n, 2, 1, 32, 32), 4,
                                 axis=2).reshape(n, 8 * 32, 32)
        return out

    same = {k: v for k, v in tree.items() if not isinstance(v, dict)}
    same = dict(expand(same), dense=expand(tree["dense"]))
    want = reference_latent.forward(same, flat, tokens)[0]
    assert np.abs(got - want).max() < 1e-5
    with_noise = ref.forward(tree, spec, tokens)[0]
    assert np.abs(with_noise - want).max() > 100 * TOL


# -- the ring call against the XLA path -----------------------------------------

@pytest.mark.parametrize("window,heads", [(8, 10), (128, 80)],
                         ids=["toy", "published"])
def test_the_ring_kernel_against_the_masked_einsum(window, heads,
                                                   monkeypatch):
    """Rows whose rings have not wrapped (slots past ``pos`` unseen), one
    that just did, and one far past it, in the second of three layers'
    rings."""
    la = dataclasses.replace(LATENT, window=window)
    spec = toy(latent=la, n_heads=heads, n_kv_heads=heads)
    rng = np.random.default_rng(11)
    B, width, rank = 4, latent.plane_width(spec), la.kv_rank
    q = jnp.asarray(rng.standard_normal((B, heads, width)), jnp.float32)
    row = jnp.asarray(rng.standard_normal((B, width)), jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((3 * B, window, width)), jnp.float32)
    pos = jnp.asarray([0, window // 2, window, 5 * window + 3], jnp.int32)
    outs = {}
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("DLLAMA_ATTN_KERNEL", mode)
        outs[mode] = latent.ring_decode_attention(spec, q, row, w3, 1, pos)
    (o_x, w_x), (o_p, w_p) = outs["xla"], outs["pallas"]
    assert o_p.shape == (B, heads, rank)
    assert np.array_equal(np.asarray(w_x), np.asarray(w_p))
    assert np.abs(np.asarray(o_x) - np.asarray(o_p)).max() < 2e-5
    # the written row lies at slot pos mod window of ring 1 * B + b
    assert np.array_equal(np.asarray(w_x[B + 3, 3]), np.asarray(row[3]))
    # row 0 sees its own slot alone: the softmax is one weight of 1
    assert np.abs(np.asarray(o_p[0]) - np.asarray(row[0, :rank])).max() < 1e-5
    direct = pla.latent_ring_decode(q, w_x, 1, pos, kv_rank=rank,
                                    interpret=True)
    assert np.array_equal(np.asarray(direct), np.asarray(o_p))


def test_the_paged_kernel_still_agrees_after_sharing_its_fold(monkeypatch):
    spec = toy()
    rng = np.random.default_rng(12)
    B, ps, P, width = 3, 8, 9, latent.plane_width(spec)
    q = jnp.asarray(rng.standard_normal((B, 10, width)), jnp.float32)
    row = jnp.asarray(rng.standard_normal((B, width)), jnp.float32)
    c3 = jnp.asarray(rng.standard_normal((2 * P, ps, width)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, P))[:B * 2].reshape(B, 2),
                        jnp.int32)
    pos = jnp.asarray([0, 7, 13], jnp.int32)
    outs = []
    for mode in ("xla", "pallas"):
        monkeypatch.setenv("DLLAMA_ATTN_KERNEL", mode)
        outs.append(latent.paged_decode_attention(spec, ps, P, q, row, c3, 1,
                                                  pos, table)[0])
    assert np.abs(np.asarray(outs[0]) - np.asarray(outs[1])).max() < 2e-5


# -- the fold's six exact piece products (PR 61) --------------------------------

def _softmax64(q, rows):
    """softmax(q . rows^T) rows[:, :rank] in float64; rank from LATENT."""
    s = q.astype(np.float64) @ rows.astype(np.float64).T
    p = np.exp(s - s.max(axis=1, keepdims=True))
    return (p / p.sum(axis=1, keepdims=True)) @ rows[:, :LATENT.kv_rank]


def _six_products(heads):
    """``_dot6`` in both of the fold's contractions (the scores' over the
    block's last dim, the values' over its first), operands with full
    24-bit mantissas: level with a float32 product at HIGHEST, far from
    one bf16 pass, and farther with a product left out."""
    from distributed_llama_tpu.ops.pallas_head_major_attention import (
        _dot6, _stack3)
    from distributed_llama_tpu.ops.pallas_q40 import _mask_pieces

    @functools.partial(jax.jit, static_argnums=2)
    def products(x, w, contract):
        x3, pieces = _stack3(x), _mask_pieces(w, 3)
        dn = (((1,), (contract,)), ((), ()))
        bf16 = [p.astype(jnp.bfloat16) for p in pieces]
        return (_dot6(x3, pieces, contract),
                # without hi . lo
                _dot6(x3, (*pieces[:2], jnp.zeros_like(w)), contract),
                jax.lax.dot_general(x, w, dn,
                                    precision=jax.lax.Precision.HIGHEST),
                jax.lax.dot_general(x.astype(jnp.bfloat16),
                                    w.astype(jnp.bfloat16), dn,
                                    preferred_element_type=jnp.float32),
                jnp.stack(pieces), jnp.stack(bf16).astype(jnp.float32))

    rng = np.random.default_rng(heads)
    for contract, k, n in ((1, 40, 24), (0, 24, 32)):
        x = rng.standard_normal((heads, k)).astype(np.float32)
        w = rng.standard_normal((n, k) if contract else (k, n)).astype(
            np.float32)
        assert (x.view(np.uint32) & 0xFF).any() and (
            w.view(np.uint32) & 0xFF).any()
        exact = x.astype(np.float64) @ (w.T if contract else w).astype(
            np.float64)
        six, five, highest, one_pass, pieces, as_bf16 = (
            np.asarray(a) for a in products(x, w, contract))
        assert np.array_equal(pieces, as_bf16)      # each IS a bf16 number
        assert np.array_equal(pieces.sum(axis=0), w)
        far = lambda got: np.abs(got - exact).max()         # noqa: E731
        assert six.shape == exact.shape
        # the CPU's HIGHEST is one float32 product (all nine piece products)
        assert far(six) <= 2.5 * far(highest), (far(six), far(highest))
        assert far(one_pass) > 100 * far(six)
        assert far(five) > 10 * far(six)            # 2^-16 of a product off


def _paged_blocks():
    """Rows that end a block's last position, begin the next, lie in a
    part-filled third block, and in the first alone: pages of 8 positions,
    32 a block."""
    rng = np.random.default_rng(61)
    B, ps, width, rank = 4, 8, latent.plane_width(SPEC), LATENT.kv_rank
    pages = 80
    n_pages = B * pages + 1
    q = rng.standard_normal((B, 10, width)).astype(np.float32)
    c3 = rng.standard_normal((2 * n_pages, ps, width)).astype(np.float32)
    table = rng.permutation(np.arange(1, n_pages)).reshape(B, pages).astype(
        np.int32)
    blk = pla.BLOCK_POSITIONS
    pos = np.asarray([blk - 1, blk, 2 * blk + 88, 5], np.int32)
    got = np.asarray(pla.latent_paged_decode(
        q, c3, 1, pos, table, page_size=ps, n_pages=n_pages, kv_rank=rank,
        interpret=True))
    for b in range(B):
        plane = c3[n_pages + table[b]].reshape(-1, width)[:pos[b] + 1]
        assert np.abs(got[b] - _softmax64(q[b], plane)).max() < 1e-5, b


def _ring_wraps():
    """Rings of 8 slots before they wrap (slots past ``pos`` unseen), at
    the wrap and far past it."""
    rng = np.random.default_rng(62)
    B, window, width = 4, LATENT.window, latent.plane_width(SPEC)
    q = rng.standard_normal((B, 10, width)).astype(np.float32)
    w3 = rng.standard_normal((3 * B, window, width)).astype(np.float32)
    pos = np.asarray([0, 3, window, 5 * window + 3], np.int32)
    got = np.asarray(pla.latent_ring_decode(q, w3, 1, pos,
                                            kv_rank=LATENT.kv_rank,
                                            interpret=True))
    for b in range(B):
        seen = w3[B + b, :min(pos[b], window - 1) + 1]
        assert np.abs(got[b] - _softmax64(q[b], seen)).max() < 1e-5, b


@pytest.mark.parametrize("check", [
    *(functools.partial(_six_products, h) for h in (32, 80, 128)),
    _paged_blocks, _ring_wraps],
    ids=["six-H32", "six-H80", "six-H128", "paged-blocks", "ring-wraps"])
def test_the_fold_keeps_highests_six_products(check):
    check()


# -- the shares add up -------------------------------------------------------------

def _share_of(tree, spec, held, offset):
    cut = dataclasses.replace(spec, layout=dataclasses.replace(
        spec.layout, held=held, offset=offset))
    part = dict(tree)
    for k in ("moe_w1", "moe_w2", "moe_w3"):
        part[k] = tree[k][:, offset:offset + held]
    return cut, part


@pytest.mark.parametrize("shares", [8, 4, 2])
def test_the_shares_partial_sums_add_up_to_the_uncut_layer(tokens, shares):
    """An expert layer of the uncut model against the ``shares`` chips of an
    expert-parallel group, each holding 8 / shares experts in order: the
    partial expert sums add up to the uncut layer's, the shared expert
    counted ONCE; the choice and the weights are the whole router's on
    every chip; a (token, expert) pair's PolyNorm needs nothing of another
    expert."""
    whole = toy(weights_float_type=FloatType.F32)
    tree = synth_params(whole, q40=False, seed=3)
    lw = {k: v[0] for k, v in tree.items() if not isinstance(v, dict)
          and k.startswith(("moe_", "sh_", "rms_ffn", "pn_w"))}
    x = jnp.asarray(tree["tok_embedding"], jnp.float32)[np.asarray(tokens)]
    act = ref.activation(whole, lw)
    with jax.default_matmul_precision("highest"):
        full, margin, ids = reference_latent.experts_out(whole, lw, x,
                                                         act=act)
        shared_alone = reference_latent._swiglu(
            reference_latent._rmsnorm(x, lw["rms_ffn"], whole.norm_eps),
            lw["sh_w1"], lw["sh_w2"], lw["sh_w3"], act)
        total = shared_alone
        held = 8 // shares
        for s in range(shares):
            cut, part = _share_of(tree, whole, held, s * held)
            lw_s = dict(lw, **{k: part[k][0] for k in ("moe_w1", "moe_w2",
                                                       "moe_w3")})
            y, m_s, ids_s = reference_latent.experts_out(
                cut, lw_s, x, shared=False, act=act)
            assert np.array_equal(np.asarray(ids_s), np.asarray(ids))
            assert np.array_equal(np.asarray(m_s), np.asarray(margin))
            total = total + y
    assert float(jnp.abs(total - full).max()) < 1e-5
    assert float(jnp.abs(full - shared_alone).max()) > 1e-2


def test_the_program_runs_a_share_as_the_reference_does(tokens):
    """The same stream through the program on two different shares: each is
    its share's reference, and the two differ."""
    whole = toy(weights_float_type=FloatType.F32)
    tree = synth_params(whole, q40=False, seed=3)
    outs = []
    for offset in (0, 4):
        cut, part = _share_of(tree, whole, 4, offset)
        want = ref.forward(part, cut, tokens[:32])[0]
        got, _ = fwd(cut, params_to_device(part, spec=cut), init_cache(cut),
                     jnp.asarray(tokens[:32]), jnp.int32(0))
        assert np.abs(np.asarray(got) - want).max() < TOL
        outs.append(want)
    assert np.abs(outs[0] - outs[1]).max() > 100 * TOL


# -- a uniform spec lowers to what it lowered to -------------------------------------

def _ops_of(spec, tree, t_len):
    """The optimized HLO's op names, counted, of one forward at ``t_len``."""
    import collections
    import re

    params = params_to_device(tree, spec=spec)
    text = jax.jit(lambda p, c, t, pos: forward(spec, p, c, t, pos)).lower(
        params, init_cache(spec), jnp.zeros((t_len,), jnp.int32),
        jnp.int32(0)).compile().as_text()
    return collections.Counter(re.findall(r"= \S+ (\w[\w-]*)\(", text))


@pytest.mark.parametrize("t_len", [1, 16], ids=["step", "chunk"])
@pytest.mark.parametrize("hyper", [None, HyperConnections(4)],
                         ids=["deepseek-v3", "xing4"])
def test_a_uniform_list_is_two_scans_and_no_new_op(hyper, t_len):
    """A spec whose layers are all full, a head its own group, no noise
    head, no gate, SiLU: two scans of layers (the dense stack's and the
    expert stack's, which holds the XLA path's scan over its experts), no
    ring, no lambda, no gate, no PolyNorm, and the same ops whether
    ``kinds`` is empty or says "full" six times."""
    flat = toy(latent=LatentAttn(32, 32, 16, 8, 16), hyper=hyper,
               activation=Activation())
    tree = synth_params(flat, q40=True, seed=3)
    ops = _ops_of(flat, tree, t_len)
    listed = toy(latent=dataclasses.replace(flat.latent, kinds=("full",) * 6),
                 hyper=hyper, activation=Activation())
    assert _ops_of(listed, tree, t_len) == ops
    jaxpr = str(jax.make_jaxpr(lambda p, c, t: forward(flat, p, c, t, 0))(
        params_to_device(tree, spec=flat), init_cache(flat),
        jnp.zeros((t_len,), jnp.int32)))
    assert jaxpr.count("scan[") == 3
    for scope in ("attn.diff", "attn.gate", "ring.write", "ffn.polynorm"):
        assert scope not in jaxpr
