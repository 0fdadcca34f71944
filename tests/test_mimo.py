"""A mixer-kinds spec whose kinds differ in more than a head count
(MiMo-V2-Flash's layout: sliding layers with a learned softmax sink a query
head and their own KV head count, K heads wider than V heads, RoPE on a
head's leading part, the attention output scaled, a choice bias in the
router and a SHARE of the experts held) against
``models/reference_laguna.py`` on LOGITS, at a toy size: L = 8 in the
published opening (full, sliding x 4, full, sliding x 2), 16 query heads
over 1 KV head in a full layer (groups of 16) and 2 in a sliding one, K 24 /
V 16, window 8 (so the rings wrap several times in 64 positions), layer 0 a
dense SwiGLU, then 8 experts of which 2 a token and 4 held from offset 2.

TOL as ``tests/test_laguna.py``: float32 against float32 at highest
precision differs by op order alone (the largest reading here is 1e-6); each
ablation of ``test_each_mechanism_matters`` reads a hundred times over it.
"""

import dataclasses
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, read_spec,
                                             tensor_byte_ranges, write_model)
from distributed_llama_tpu.models import laguna
from distributed_llama_tpu.models import reference_laguna as ref
from distributed_llama_tpu.models.llama import (attention_core, forward,
                                                init_cache, params_to_device)
from distributed_llama_tpu.models.spec import (ExpertLayout, MixerKind,
                                               MixerKinds, Router,
                                               TransformerSpec, cache_lanes)
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import pallas_head_major_attention as hm
from distributed_llama_tpu.ops.quants import FloatType

TOL = 2e-4
SEQ = 64
KINDS = ("full", "sliding", "sliding", "sliding", "sliding", "full",
         "sliding", "sliding")


def tiny(wft=FloatType.F32, head=24, v_head=16, held=4, offset=2, **kw):
    mixers = MixerKinds(
        KINDS, 8, head, MixerKind(16, 5e6, 8),
        MixerKind(16, 1e4, 8, None, 2, True), False, v_head, 0.707)
    return TransformerSpec(
        dim=64, hidden_dim=32, n_layers=len(KINDS), n_heads=16, n_kv_heads=1,
        vocab_size=128, seq_len=SEQ, weights_float_type=wft, norm_eps=1e-5,
        n_experts=8, n_active_experts=2,
        layout=ExpertLayout(1, 96, 0, held, offset),
        router=Router("sigmoid", 1, 1, True, 1.0, True), mixers=mixers, **kw)


SPEC = tiny()


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in np.random.default_rng(0).integers(3, 128, SEQ)]


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=False, seed=3)


@pytest.fixture(scope="module")
def want(tree, tokens):
    logits, margins, _ = ref.forward(tree, SPEC, tokens)
    assert margins.min() > 5e-6     # no near-tie in the seeded stream
    return logits


# -- (e) the spec, its header and its file ------------------------------------

def test_the_spec_says_a_kinds_kv_heads_and_both_head_sizes():
    assert SPEC.header_version == 8 and SPEC.planned and SPEC.slotted
    assert SPEC.kv_shape("full") == (1, 24, 16)
    assert SPEC.kv_shape("sliding") == (2, 24, 16)
    assert SPEC.kv_cached("sliding") == 2 * (24 + 16)
    wide = tiny(head=192, v_head=128)
    assert wide.kv_cached("full") == 256 + 128     # K of 192 in 256 lanes
    assert [cache_lanes(h) for h in (16, 128, 129, 192, 256)] == [
        16, 128, 256, 256, 256]
    leaves = {(s, n): shape for s, n, _, shape in SPEC.stack_leaves()}
    assert leaves[("full", "wk")] == (2, 24, 64)
    assert leaves[("sliding", "wk")] == (6, 48, 64)
    assert leaves[("sliding", "wv")] == (6, 32, 64)
    assert leaves[("sliding", "wo")] == (6, 64, 256)
    assert leaves[("sliding", "sink")] == (6, 16)
    assert ("full", "sink") not in leaves
    assert leaves[("", "moe_w1")] == (7, 4, 32, 64)     # the held share


@pytest.mark.parametrize("change", [
    dict(),
    dict(v_head_size=0),
    dict(value_scale=1.0),
    dict(full=MixerKind(16, 5e6, 8, None, 0, True)),
    dict(sliding=MixerKind(16, 1e4, 8, None, 0, True)),
    dict(sliding=MixerKind(16, 1e4, 8, None, 2, False)),
])
def test_header_round_trip(change):
    spec = dataclasses.replace(SPEC, mixers=dataclasses.replace(
        SPEC.mixers, **change))
    raw = spec.header()
    assert spec.header_version == 8 and len(raw) == spec.header_bytes == 496
    assert TransformerSpec.from_header(raw, spec.weights_float_type) == spec


def test_a_spec_that_states_none_of_it_writes_version_7():
    """A Laguna file reads and writes byte for byte as it did."""
    plain = dataclasses.replace(SPEC, mixers=MixerKinds(
        KINDS, 8, 24, MixerKind(16, 5e6, 8), MixerKind(16, 1e4, 8)))
    assert plain.header_version == 7 and len(plain.header()) == 468
    assert TransformerSpec.from_header(plain.header()) == plain
    assert not plain.mixers.widened and SPEC.mixers.widened


@pytest.mark.parametrize("change,match", [
    (dict(sliding=MixerKind(16, 1e4, 8, None, 3)), "multiple of n_kv_heads"),
    (dict(full=MixerKind(16, 5e6, 8, None, 2)), "n_heads the full kind's"),
    (dict(v_head_size=-1), "n_heads the full kind's"),
])
def test_the_spec_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(SPEC, mixers=dataclasses.replace(
            SPEC.mixers, **change))


def test_file_round_trip_and_byte_ranges(tmp_path, tree):
    path = str(tmp_path / "m.bin")
    write_model(path, SPEC, tree)
    assert read_spec(path) == SPEC
    _, back = load_model(path)
    for stack in ("full", "sliding", "dense"):
        assert set(back[stack]) == set(tree[stack])
        for k, v in tree[stack].items():
            assert np.array_equal(back[stack][k], v), (stack, k)
    assert np.array_equal(back["moe_bias"], tree["moe_bias"])
    ranges = tensor_byte_ranges(SPEC)
    assert ranges[-1].offset + ranges[-1].nbytes == SPEC.file_size()
    assert [r.layer for r in ranges if r.name == "sink"] == [1, 2, 3, 4, 6, 7]


def test_synth_q40_file_is_byte_exact(tmp_path):
    spec = tiny(FloatType.Q40, head=32, v_head=16)
    path = str(tmp_path / "q.bin")
    assert write_synth_q40_model(path, spec, seed=1) == spec.file_size()
    assert read_spec(path, FloatType.Q40) == spec


REFUSED = {
    "tp": (dict(tp=2, page_size=16), "--tp 2"),
    "prefix sharing": (dict(page_size=16, prefix_share=True),
                       "prefix sharing"),
    "no pages": (dict(), "serve without --kv-page-size"),
    "spec_k": (dict(page_size=16, spec_k=4), "--spec-k 4"),
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_each_refusal_by_name(flag):
    """``cache_refusals`` is unchanged in what it refuses: rings and pages."""
    from distributed_llama_tpu.runtime.continuous import (cache_refusals,
                                                          sequence_caches)

    caches = sequence_caches(SPEC)
    assert caches == {"state", "pages", "rings"}
    kw, names = REFUSED[flag]
    lines = cache_refusals(caches, **kw)
    assert len(lines) == 1 and lines[0].startswith(names)
    assert "window ring" in lines[0]
    assert cache_refusals(caches, page_size=16) == []


def test_tp_refuses_the_spec(tree):
    from distributed_llama_tpu.analysis import memory_model as mm
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.tp import validate_sharding

    for raises in (lambda: validate_sharding(SPEC, make_mesh(tp=2)),
                   lambda: mm.weight_values_per_device(SPEC, 2),
                   lambda: mm.kv_position_bytes(SPEC, 2)):
        with pytest.raises(ValueError, match="one chip only"):
            raises()


def test_convert_reads_the_published_config():
    """``mimo_spec`` on the catalog's keys."""
    from distributed_llama_tpu.convert import mimo_spec

    pattern = [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]
    c = types.SimpleNamespace(
        model_type="mimo_v2_flash", attention_value_scale=0.707,
        hidden_size=4096, intermediate_size=16384, num_attention_heads=64,
        head_dim=192, num_hidden_layers=12, num_key_value_heads=4,
        layernorm_epsilon=1e-5, rope_theta=5000000, vocab_size=152576,
        partial_rotary_factor=0.334, sliding_window=128, swa_rope_theta=10000,
        attention_bias=False, v_head_dim=128, hybrid_layer_pattern=pattern,
        add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
        moe_layer_freq=[0] + [1] * 11, moe_intermediate_size=2048,
        n_routed_experts=256, n_shared_experts=None, num_experts_per_tok=8,
        norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
        topk_method="noaux_tc", routed_scaling_factor=None,
        swa_num_attention_heads=64, swa_num_key_value_heads=8,
        swa_head_dim=192, swa_v_head_dim=128)
    spec = mimo_spec(c, FloatType.Q40, 4096)
    mx = spec.mixers
    assert mx.kinds[:6] == KINDS[:6] and mx.count("full") == 3
    assert (mx.window, mx.head_size, mx.v_head_size, mx.value_scale) == (
        128, 192, 128, 0.707)
    assert mx.full == MixerKind(64, 5e6, 64, None, 0, False)
    assert mx.sliding == MixerKind(64, 1e4, 64, None, 8, True)
    assert spec.kv_shape("full") == (4, 192, 128)
    assert spec.kv_shape("sliding") == (8, 192, 128)
    assert spec.layout == ExpertLayout(1, 16384, 0)
    assert spec.router == Router("sigmoid", 1, 1, True, 1.0, True)
    assert spec.header_version == 8
    c.swa_head_dim = 128
    with pytest.raises(ValueError, match="one head count and one head size"):
        mimo_spec(c, FloatType.Q40, 4096)


# -- (a) the forward against the reference ------------------------------------

@pytest.mark.parametrize("head,v_head", [(24, 16), (192, 128)],
                         ids=["k24v16", "k192v128"])
def test_chunked_prefill_then_decode(tokens, head, v_head):
    """``inference``: chunks of 8 with a ragged last one (21 = 2 x 8 + 5)
    through the caches, then decode to position 56: the rings wrap seven
    times, groups of 16 and 8 heads run, and at K 192 the caches hold K in
    256 lanes."""
    spec = tiny(head=head, v_head=v_head)
    tree = synth_params(spec, q40=False, seed=3)
    want = ref.forward(tree, spec, tokens)[0]
    params = params_to_device(tree)
    pre = jax.jit(lambda p, c, t, pos, n: laguna.forward_chunk(
        spec, p, c, t, pos, n, xdec=False))
    step = jax.jit(lambda p, c, t, pos: forward(spec, p, c, t, pos))
    cache = init_cache(spec)
    assert cache.wk.shape == (6, 2, 8, cache_lanes(head))
    assert cache.v.shape == (2, 1, SEQ, v_head)
    for lo in range(0, 21, 8):
        part = tokens[lo:min(lo + 8, 21)]
        _, cache = pre(params, cache,
                       jnp.asarray(part + [0] * (8 - len(part))),
                       jnp.int32(lo), jnp.int32(len(part)))
    worst = 0.0
    for pos in range(21, 56):
        logits, cache = step(params, cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos))
        worst = max(worst, float(np.abs(np.asarray(logits)[0]
                                        - want[pos]).max()))
    assert worst < TOL


@pytest.mark.parametrize("t_len", [16, 24])
def test_a_chunk_walks_its_live_prefix(tree, tokens, want, t_len):
    """Chunks of 16 take the walk over the blocks up to pos + T (the sink
    in its first carry); a chunk of 24 does not divide the 64 positions and
    takes the whole masked plane (the sink as a column)."""
    params = params_to_device(tree)
    cache, worst = init_cache(SPEC), 0.0
    for lo in range(0, 48, t_len):
        logits, cache = forward(SPEC, params, cache,
                                jnp.asarray(tokens[lo:lo + t_len]),
                                jnp.int32(lo))
        worst = max(worst, float(np.abs(
            np.asarray(logits) - want[lo:lo + t_len]).max()))
    assert worst < TOL


def test_q40_tree_matches_the_reference(tokens):
    spec = tiny(FloatType.Q40, head=32, v_head=16)
    tree = synth_params(spec, q40=True, seed=5)
    want = ref.forward(tree, spec, tokens[:24])[0]
    got, _ = forward(spec, params_to_device(tree), init_cache(spec),
                     jnp.asarray(tokens[:24]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want).max() < TOL


def test_decode_through_the_kernels(tokens, monkeypatch):
    """The decode step with the head-major kernels on (interpret mode): K
    192 in 256 lanes / V 128, groups of 16 (full) and 8 (sliding), the sink
    in the ring kernel's first carry."""
    spec = tiny(head=192, v_head=128)
    tree = synth_params(spec, q40=False, seed=3)
    want = ref.forward(tree, spec, tokens[:20])[0]
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "pallas")
    params = params_to_device(tree)
    step = jax.jit(lambda c, t, pos: forward(spec, params, c, t, pos))
    cache, worst = init_cache(spec), 0.0
    for pos in range(20):
        logits, cache = step(cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos))
        worst = max(worst, float(np.abs(np.asarray(logits)[0]
                                        - want[pos]).max()))
    assert worst < TOL


def _engine(tree, spec=SPEC, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    kw = dict(dict(slots=2, temperature=0.0, topp=0.9, seed=3,
                   prefill_chunk=8, page_size=4, kv_pages=40), **kw)
    return ContinuousEngine(spec, tree, **kw)


def test_serve_more_requests_than_slots(tree, tokens):
    """``serve``: five requests on two slots through rings and pages, a slot
    reused over another sequence's rings and pages, prompts longer than
    three windows among them: every served position's logit lies at the
    reference's maximum, and an eighth-like share of the pairs lands here."""
    from distributed_llama_tpu.analysis import memory_model as mm
    from distributed_llama_tpu.runtime.continuous import Request

    prompts = [tokens[:9], tokens[5:30], tokens[20:22], tokens[10:37],
               tokens[40:52]]
    budgets = [24, 40, 20, 44, 30]
    eng = _engine(tree)
    mx = SPEC.mixers
    assert eng.stats.window_bytes == 2 * 6 * mx.window * 2 * (24 + 16) * 4
    assert eng.stats.window_bytes == 2 * mm.state_slot_bytes(SPEC)
    # a page covers the full layers only: 2 pools of 40 + 1 pages, ONE KV
    # head; the rings have the sliding kind's two
    assert eng.cache.k.shape == (2, 41, 1, 4, 24)
    assert eng.cache.v.shape == (2, 41, 1, 4, 16)
    assert eng.cache.wk.shape == (6, 2, 2, 8, 24)
    rep = mm.device_footprint(SPEC, 1, "ref", batch=2, kv_page_size=4,
                              kv_pages=40)
    assert rep.kv_cache_bytes == sum(int(a.nbytes) for a in eng.cache)
    reqs = [eng.submit(Request(tokens=list(p), steps=b))
            for p, b in zip(prompts, budgets)]
    while eng.step_once():
        pass
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.error is None and len(r.out) == b
        served = r.out[len(p) - 1:]
        logits = ref.forward(tree, SPEC, list(p) + served[:-1])[0][
            len(p) - 1:]
        short = logits.max(-1) - logits[np.arange(len(served)), served]
        assert short.max() < TOL
    st = eng.stats
    assert st.steps_ahead > 0 and st.admit_prefills == 4
    assert 0 < st.moe_local_pairs < st.moe_pairs
    assert st.shared_kv_positions > st.window_kv_positions > 0


def test_memory_model_counts_a_wide_head_in_whole_lane_tiles():
    from distributed_llama_tpu.analysis import memory_model as mm

    spec = tiny(FloatType.Q40, head=192, v_head=128)
    assert mm.state_slot_bytes(spec) == 6 * 8 * 2 * (256 + 128) * 4
    assert mm.kv_position_bytes(spec, 1) == 2 * 1 * (256 + 128) * 4
    eng = _engine(synth_params(spec, q40=True, seed=3), spec)
    rep = mm.device_footprint(spec, 1, "ref", batch=2, kv_page_size=4,
                              kv_pages=40)
    assert rep.kv_cache_bytes == sum(int(a.nbytes) for a in eng.cache)


def test_synth_model_file_runs_through_the_cli(tmp_path, capsys):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    spec = dataclasses.replace(tiny(FloatType.Q40, head=32, v_head=16),
                               vocab_size=512)
    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    assert write_synth_q40_model(model, spec, seed=1) == spec.file_size()
    write_synth_tokenizer(tok, spec.vocab_size)
    rc = cli.main(["inference", "--model", model, "--tokenizer", tok,
                   "--weights-float-type", "q40", "--prompt", "hello there",
                   "--steps", "12", "--temperature", "0", "--tp", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "1 and 2 KV heads (K 32, V 16)" in out
    assert "a softmax sink a sliding head" in out
    assert "output scaled by 0.707" in out and "4 experts held" in out


# -- (b) the kernels against a masked einsum ----------------------------------

def _rows_case(rng, sink):
    B, n_kv, kv_mul, hs, hk, hv, S = 3, 2, 16, 192, 256, 128, 128
    q = jnp.asarray(rng.standard_normal((B, n_kv * kv_mul, hs)), jnp.float32)
    k = jnp.pad(jnp.asarray(rng.standard_normal((2 * B, n_kv, S, hs)),
                            jnp.float32), ((0, 0),) * 3 + ((0, hk - hs),))
    v = jnp.asarray(rng.standard_normal((2 * B, n_kv, S, hv)), jnp.float32)
    last = jnp.asarray([5, 127, 64])
    got = hm.rows_decode_attention(q.reshape(B, -1), k, v, 1, last, sink,
                                   kv_mul=kv_mul, interpret=True)
    mask = jnp.arange(S)[None, None, :] <= last[:, None, None]
    want = attention_core(
        hs, kv_mul, q.reshape(B, 1, -1, hs),
        jnp.swapaxes(k[B:], 1, 2)[..., :hs], jnp.swapaxes(v[B:], 1, 2), mask,
        sink).reshape(B, -1)
    return got, want


def _paged_case(rng, sink):
    B, n_kv, kv_mul, hs, hk, hv = 3, 1, 16, 192, 256, 128
    ps, P, maxp = 16, 40, 8
    q = jnp.asarray(rng.standard_normal((B, n_kv * kv_mul, hs)), jnp.float32)
    kp = jnp.pad(jnp.asarray(rng.standard_normal((P, n_kv, ps, hs)),
                             jnp.float32), ((0, 0),) * 3 + ((0, hk - hs),))
    vp = jnp.asarray(rng.standard_normal((P, n_kv, ps, hv)), jnp.float32)
    table = jnp.asarray(rng.permutation(P - 1)[:B * maxp].reshape(B, maxp)
                        + 1, jnp.int32)
    pos = jnp.asarray([3, 127, 70])
    got = hm.paged_decode_attention(q.reshape(B, -1), kp, vp, pos, table,
                                    sink, kv_mul=kv_mul, interpret=True)

    def plane(pool):
        pages = jnp.take(pool, table.reshape(-1), axis=0).reshape(
            B, maxp, n_kv, ps, pool.shape[-1])
        return jnp.swapaxes(pages, 2, 3).reshape(B, maxp * ps, n_kv,
                                                 pool.shape[-1])

    mask = jnp.arange(maxp * ps)[None, None, :] <= pos[:, None, None]
    want = attention_core(hs, kv_mul, q.reshape(B, 1, -1, hs),
                          plane(kp)[..., :hs], plane(vp), mask,
                          sink).reshape(B, -1)
    return got, want


@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("case", [_rows_case, _paged_case],
                         ids=["rows", "paged"])
def test_a_kernel_against_the_masked_einsum(case, with_sink):
    """Both kernels in interpret mode, K 192 held in 256 lanes beside V 128,
    groups of 16, with and without a sink (sinks of either sign, one far
    over every score: that head's output is nearly nothing)."""
    rng = np.random.default_rng(7)
    n_q = 32 if case is _rows_case else 16
    sink = None
    if with_sink:
        sink = jnp.asarray(rng.standard_normal(n_q) * 2, jnp.float32)
        sink = sink.at[3].set(30.0)
    got, want = case(rng, sink)
    assert got.shape == want.shape == (3, n_q * 128)
    assert float(jnp.abs(got - want).max()) < 2e-6
    if with_sink:
        bare = case(np.random.default_rng(7), None)[0]
        assert float(jnp.abs(got - bare).max()) > 1e-2
        assert float(jnp.abs(got.reshape(3, n_q, 128)[:, 3]).max()) < 1e-6


@pytest.mark.parametrize("head,v_head,ok", [
    (128, 0, True), (256, 128, True), (128, 256, True), (192, 128, False),
    (64, 64, False), (256, 192, False)])
def test_the_kernels_take_whole_lane_tiles_only(head, v_head, ok):
    assert hm.supports(512, 8, head, 4, v_head) is ok
    assert hm.supports_paged(16, 4, head, 4, v_head) is ok


def test_a_ring_of_128_slots_is_cut_in_two():
    """A plane of 128 positions, one tile of the fold, is cut in two (the
    chip read four chunks of 32 at 137 us a layer, two of 64 at 116: PERF.md
    section 6, PR 48); every other plane is cut as before."""
    assert hm._chunk(128, 8, 256, 4, 128) == 64
    assert hm._chunk(512, 8, 128, 4) == 128      # a Laguna or hybrid ring
    assert hm._chunk(8704, 10, 128, 4) == 512    # a hybrid sequence's plane
    assert hm._chunk(5120, 8, 128, 4) == 512     # a Laguna sequence's plane
    assert hm._chunk(256, 8, 128, 4) == 64       # four chunks
    assert hm._chunk(64, 8, 128, 4) == 16 and hm._chunk(48, 8, 128, 4) == 8


# -- (c) each mechanism matters -----------------------------------------------

@pytest.mark.parametrize("ablation", [
    dict(drop=("sink",)), dict(drop=("value_scale",)),
    dict(drop=("kv_heads",)), dict(rope=False)],
    ids=["sink", "value_scale", "kv_heads", "rope_over_the_whole_head"])
def test_each_mechanism_matters(tree, tokens, want, ablation):
    """The reference with the sink dropped, the value scale dropped, the
    full layers' KV head count used in the sliding layers, or RoPE over all
    of a head at one base is NOT what the program computes: a forward that
    dropped either would fail the tests above by the tolerance."""
    other = ref.forward(tree, SPEC, tokens[:45], **ablation)[0]
    assert np.abs(other - want[:45]).max() > 100 * TOL
    got, _ = forward(SPEC, params_to_device(tree), init_cache(SPEC),
                     jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[:45]).max() < TOL
    assert np.abs(np.asarray(got) - other).max() > 100 * TOL


def test_a_rows_weights_sum_to_less_than_one(tree, tokens):
    """The sink takes mass and gives no value: with V all ones a sliding
    head's output is the mass left on the keys."""
    lw = {k: v[0] for k, v in tree["sliding"].items()}
    x = jnp.asarray(tree["tok_embedding"], jnp.float32)[np.asarray(tokens)]
    with jax.default_matmul_precision("highest"):
        bare = ref.attention(SPEC, lw, "sliding", x, drop=("sink",)) - x
        sunk = ref.attention(SPEC, lw, "sliding", x) - x
    assert float(jnp.abs(bare - sunk).max()) > 1e-3


def test_bfloat16_fails_the_tolerance(tree, tokens, want):
    from distributed_llama_tpu.ops.linear import matmul_precision

    with matmul_precision("bf16"):
        got, _ = forward(SPEC, params_to_device(tree), init_cache(SPEC),
                         jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[:45]).max() > 5 * TOL


# -- (d) the shares add up ----------------------------------------------------

def _share_of(tree, spec, held, offset):
    cut = dataclasses.replace(spec, layout=dataclasses.replace(
        spec.layout, held=held, offset=offset))
    part = dict(tree)
    for k in ("moe_w1", "moe_w2", "moe_w3"):
        part[k] = tree[k][:, offset:offset + held]
    return cut, part


@pytest.mark.parametrize("shares", [8, 4, 2])
def test_the_shares_partial_sums_add_up_to_the_uncut_layer(tokens, shares):
    """An expert layer of the uncut model (all 8 experts held) against the
    ``shares`` chips of an expert-parallel group, each holding 8 / shares in
    order: the partial expert sums add up to the uncut layer's, with the
    residual (and so the attention and dense parts before it) counted once;
    the choice and the weights are the whole router's on every chip."""
    whole = tiny(held=0, offset=0)
    tree = synth_params(whole, q40=False, seed=3)
    lw = {k: v[0] for k, v in tree.items()
          if not isinstance(v, dict) and k.startswith(("moe_", "rms_ffn"))}
    x = jnp.asarray(tree["tok_embedding"], jnp.float32)[np.asarray(tokens)]
    with jax.default_matmul_precision("highest"):
        full, margin, ids = ref.experts(whole, lw, x)
        total = jnp.zeros_like(x)
        held = 8 // shares
        for s in range(shares):
            cut, part = _share_of(tree, whole, held, s * held)
            lw_s = dict(lw, **{k: part[k][0] for k in ("moe_w1", "moe_w2",
                                                       "moe_w3")})
            y, m_s, ids_s = ref.experts(cut, lw_s, x)
            assert np.array_equal(np.asarray(ids_s), np.asarray(ids))
            assert np.array_equal(np.asarray(m_s), np.asarray(margin))
            total = total + (y - x)
    assert float(jnp.abs(total + x - full).max()) < 1e-5
    assert float(jnp.abs(full - x).max()) > 1e-2


def test_the_program_runs_a_share_as_the_reference_does(tokens):
    """The same stream through the program on two different shares: each is
    its share's reference, and the two differ."""
    whole = tiny(held=0, offset=0)
    tree = synth_params(whole, q40=False, seed=3)
    outs = []
    for offset in (0, 4):
        cut, part = _share_of(tree, whole, 4, offset)
        want = ref.forward(part, cut, tokens[:32])[0]
        got, _ = forward(cut, params_to_device(part), init_cache(cut),
                         jnp.asarray(tokens[:32]), jnp.int32(0))
        assert np.abs(np.asarray(got) - want).max() < TOL
        outs.append(want)
    assert np.abs(outs[0] - outs[1]).max() > 100 * TOL
