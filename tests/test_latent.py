"""A latent-attention expert model (DeepSeek-V3's block: low-rank q, ONE
cached plane [c_kv | k_rope], a dense layer before expert layers, sigmoid
group-limited routing with a shared expert, a SHARE of the routed experts)
against its plain float32 reference (models/reference_latent.py), at toy
widths on the CPU, on seeded weights. Logits are compared, never sampled
tokens; a sequence is compared up to its first router near-tie."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, read_spec,
                                              tensor_byte_ranges,
                                              write_model)
from distributed_llama_tpu.models import latent, reference_latent
from distributed_llama_tpu.models.llama import (forward, forward_batch_paged,
                                                gather_pages, init_cache,
                                                init_cache_paged,
                                                params_to_device,
                                                scatter_pages)
from distributed_llama_tpu.models.spec import (EXT4_STRUCT, ExpertLayout,
                                               LatentAttn, RopeScaling,
                                               Router, TransformerSpec)
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops import pallas_moe
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5
MARGIN_EPS = 1e-4
SEQ = 40


def toy_spec(**kw):
    base = dict(dim=256, hidden_dim=128, n_layers=3, n_heads=4, n_kv_heads=4,
                vocab_size=512, seq_len=64, weights_float_type=FloatType.Q40,
                n_experts=16, n_active_experts=4, rope_theta=10000.0,
                norm_eps=1e-6, latent=LatentAttn(128, 64, 32, 16, 32),
                layout=ExpertLayout(dense_layers=1, dense_hidden=384,
                                    shared=1, held=8, offset=4),
                router=Router("sigmoid", groups=4, groups_kept=2,
                              renormalise=True, scale=2.5, bias=True),
                rope_scaling=RopeScaling(40.0, 16, 32.0, 1.0, 1.0, 1.0))
    base.update(kw)
    return TransformerSpec(**base)


SPEC = toy_spec()


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=11)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(3, SPEC.vocab_size, SEQ)


@pytest.fixture(scope="module")
def want(tree, tokens):
    return reference_latent.forward(tree, SPEC, tokens)


def compared(margins, at_least):
    low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
    n = int(low[0]) if low.size else len(margins)
    assert n >= at_least, f"only {n} positions before a router near-tie"
    return n


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    """Codec leaves with XLA attention, and the packed Q40 kernels, the
    grouped expert kernels and the latent decode kernel in interpret mode."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", request.param)
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", request.param)
    return request.param


# -- prefill then decode, contiguous and paged, against the full forward ------

def test_prefill_then_decode_through_the_contiguous_cache(kernel_mode, tree,
                                                          tokens, want):
    ref, margins, _ = want
    params = params_to_device(tree, spec=SPEC)
    assert ("sh_w13" in params) == (kernel_mode == "pallas")
    assert "w_uk" in params and "w_uk" in params["dense"]
    cache = init_cache(SPEC)
    assert cache.c.shape == (3, 64, 128)     # 80 values in whole lane tiles
    step = jax.jit(lambda p, c, t, pos: forward(SPEC, p, c, t, pos))
    got, cache = step(params, cache, jnp.asarray(tokens[:24], jnp.int32),
                      jnp.int32(0))          # a 24-row chunk
    n = compared(margins, SEQ * 3 // 4)
    assert np.abs(np.asarray(got) - ref[:24]).max() < TOL
    for pos in range(24, n):
        got, cache = step(params, cache, jnp.asarray(tokens[pos:pos + 1]),
                          jnp.int32(pos))
        assert np.abs(np.asarray(got)[0] - ref[pos]).max() < TOL, pos
    assert not np.asarray(cache.c)[..., SPEC.latent.width:].any()


def test_prefill_then_decode_through_pages(kernel_mode, tree, tokens, want):
    """An admission as the engine makes it (gather, a chunk, scatter), then
    decode steps at two rows through a pool whose pages lie out of order."""
    ref, margins, counts_ref = want
    ps, params = 8, params_to_device(tree, spec=SPEC)
    pool = init_cache_paged(SPEC, 12, ps)
    table = np.zeros((2, SPEC.seq_len // ps), np.int32)
    table[0, :5], table[1, :5] = [7, 3, 9, 1, 4], [2, 11, 5, 8, 6]
    for b in range(2):
        seq = gather_pages(pool, jnp.asarray(table[b]), ps)
        _, seq = forward(SPEC, params, seq, jnp.asarray(tokens[:16]),
                         jnp.int32(0))
        pool = scatter_pages(pool, seq, jnp.asarray(table[b]), ps)
    n = compared(margins, SEQ * 3 // 4)
    step = jax.jit(lambda p, c, t, pos, tb: forward_batch_paged(
        SPEC, ps, p, c, t, pos, tb, moe_counts=True))
    for pos in range(16, n):
        got, pool, counts = step(params, pool, jnp.asarray([tokens[pos]] * 2),
                                 jnp.asarray([pos, pos]), jnp.asarray(table))
        assert np.abs(np.asarray(got) - ref[pos][None]).max() < TOL, pos
        # the counts keep the router's full width: both rows' picks
        want_counts = np.zeros((2, 16), np.int64)
        for layer in range(2):
            want_counts[layer, counts_ref[pos, layer]] += 2
        assert (np.asarray(counts) == want_counts).all()


def test_a_stale_page_reused_by_another_sequence_decodes_as_a_fresh_one(
        kernel_mode, tree, tokens, want):
    ref, margins, _ = want
    ps, params = 8, params_to_device(tree, spec=SPEC)
    junk = jnp.full((3, 6, ps, latent.plane_width(SPEC)), 3.0)
    table = np.zeros((1, SPEC.seq_len // ps), np.int32)
    table[0, :3] = [5, 2, 4]
    pools = [latent.LatentCache(junk), init_cache_paged(SPEC, 6, ps)]
    n = min(compared(margins, 10), 10)
    step = jax.jit(lambda p, c, t, pos, tb: forward_batch_paged(
        SPEC, ps, p, c, t, pos, tb))
    for pos in range(n):
        outs = []
        for i in range(2):
            got, pools[i] = step(params, pools[i],
                                 jnp.asarray([tokens[pos]]),
                                 jnp.asarray([pos]), jnp.asarray(table))
            outs.append(np.asarray(got)[0])
        assert np.abs(outs[0] - ref[pos]).max() < TOL
        assert np.abs(outs[0] - outs[1]).max() < 1e-6


# -- a chunk walks its live blocks only ------------------------------------------

WALK_SEQ = 192      # T = 16 divides it (the block is T); T = 20 does not (64)
WALK_TOL = TOL / 10


@pytest.fixture(scope="module", params=[128, 32])
def walk_model(request):
    """(spec, device params) at the two cells' head counts, toy widths."""
    spec = toy_spec(n_heads=request.param, n_kv_heads=request.param,
                    n_layers=2, seq_len=WALK_SEQ,
                    latent=LatentAttn(64, 64, 16, 16, 16))
    return spec, params_to_device(synth_params(spec, q40=True, seed=7),
                                  spec=spec)


def chunk_both_ways(spec, params, t_len, pos, monkeypatch, seed=0):
    """One chunk of t_len rows at pos over a plane of seeded rows, through
    the walk and through the whole-plane ``attend`` (the plain reference:
    no block at all), once more with NaN in every block the walk must not
    read. -> (walk, whole plane, walk over the NaN) as (logits, plane)."""
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(3, spec.vocab_size, t_len), jnp.int32)
    c = np.zeros(latent.init_cache(spec).c.shape, np.float32)
    c[:, :, :spec.latent.width] = 0.5 * rng.standard_normal(
        c[:, :, :spec.latent.width].shape)
    walked = latent.chunk_walked_positions(spec.seq_len, pos, t_len)
    dead = c.copy()
    dead[:, walked:] = np.nan

    def run(plane):
        got, cache = jax.jit(lambda p, c, t, at: forward(spec, p, c, t, at))(
            params, latent.LatentCache(jnp.asarray(plane)), toks,
            jnp.int32(pos))
        return np.asarray(got), np.asarray(cache.c)

    walk, over_nan = run(c), run(dead)
    monkeypatch.setattr(latent, "chunk_attn_block", lambda *_: None)
    return walk, run(c), over_nan, walked


@pytest.mark.parametrize("t_len,pos,blocks", [
    (16, 0, 1), (16, 48, 4), (16, WALK_SEQ - 16, 12),     # the block is T
    (20, 0, 1), (20, 70, 2), (20, WALK_SEQ - 20, 3)])     # the fallback, 64
def test_a_chunk_attends_its_live_blocks_and_reads_no_other(
        walk_model, t_len, pos, blocks, monkeypatch):
    spec, params = walk_model
    block = latent.chunk_attn_block(spec.seq_len, t_len)
    assert block == (t_len if t_len == 16 else 64)
    walk, whole, over_nan, walked = chunk_both_ways(
        spec, params, t_len, pos, monkeypatch)
    assert walked == blocks * block
    # logits up to 3.8 wide, two layers deep: 2.5e-6 to 3.5e-6 apart, with
    # ONE block as with twelve (the order of the softmax's sums, a dozen
    # float32 ulps at that size), a tenth of what the reference is held to
    assert 0 < np.abs(walk[0] - whole[0]).max() < WALK_TOL
    assert np.abs(walk[1] - whole[1]).max() < WALK_TOL    # the rows written
    # NaN past the walk reaches neither the logits nor a written row
    assert np.array_equal(over_nan[0], walk[0])
    assert np.array_equal(over_nan[1][:, :walked], walk[1][:, :walked])


def test_a_step_keeps_the_whole_plane():
    assert latent.chunk_attn_block(2048, 8) is None
    assert latent.chunk_attn_block(2048, 128) == 128
    assert latent.chunk_attn_block(2048, 100) == 512
    assert latent.chunk_attn_block(100, 24) is None     # no block divides
    assert latent.chunk_walked_positions(2048, 300, 1) == 2048
    assert latent.chunk_walked_positions(2048, 256, 128) == 384
    assert latent.chunk_walked_positions(2048, 2000, 100) == 2048


# -- absorbed against expanded, and the published numbers -------------------

def test_absorbed_attention_agrees_with_expanded(tree):
    """One layer's attention on seeded rows: the program's absorbed
    schedule over a cache of latent rows against the reference's expanded
    one over keys and values, to float32 rounding."""
    lw = {k: jax.tree_util.tree_map(lambda a: a[0], v)
          for k, v in tree["dense"].items()}
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (24, SPEC.dim)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = reference_latent.attention(SPEC, lw, x) - x
    q, rows = latent.latent_qkv(SPEC, lw, x, jnp.arange(24))
    mask = jnp.arange(24)[None, :] <= jnp.arange(24)[:, None]
    ao = latent.attention_out(SPEC, lw, latent.attend(SPEC, q, rows, mask))
    from distributed_llama_tpu.ops.linear import matmul

    got = matmul(lw["wo"], ao)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


def test_yarn_frequencies_and_scale_are_the_published_ones():
    spec = toy_spec(dim=7168, n_heads=128, n_kv_heads=128,
                    latent=LatentAttn(1536, 512, 128, 64, 128),
                    rope_scaling=RopeScaling(40.0, 4096, 32.0, 1.0, 1.0,
                                             1.0))
    for fn in (latent.rope_frequencies, reference_latent.rope_frequencies):
        freq, factor, scale = fn(spec)
        assert factor == 1.0 and freq.shape == (32,)
        # m = 0.1 ln 40 + 1 = 1.3689; the correction range is pairs 10..23
        assert scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2, rel=1e-4)
        plain = 10000.0 ** (-np.arange(32) / 32)
        assert np.allclose(freq[:11], plain[:11], rtol=1e-6)
        assert np.allclose(freq[23:], plain[23:] / 40, rtol=1e-6)
        assert plain[16] / 40 < freq[16] < plain[16]
    plain = dataclasses.replace(spec, rope_scaling=None)
    freq, factor, scale = latent.rope_frequencies(plain)
    assert scale == pytest.approx(192 ** -0.5) and freq[1] == pytest.approx(
        10000.0 ** (-1 / 32))


# -- the router -----------------------------------------------------------------

def _route(gate_rows, x, bias, router, k=2):
    w, ids = pallas_moe.route(jnp.asarray(gate_rows), jnp.asarray(x), k,
                              router, None if bias is None
                              else jnp.asarray(bias))
    return np.asarray(w), np.asarray(ids)


def test_the_router_chooses_on_s_plus_b_and_weighs_on_s():
    """8 experts in 4 groups of 2, 2 groups kept, 2 chosen. Scores by
    hand: logits l -> s = sigmoid(l)."""
    logits = np.array([[2.0, 1.0, 1.5, 1.4, 0.0, 0.1, -1.0, 3.0]], np.float32)
    gate, x = np.eye(8, dtype=np.float32), logits
    s = 1 / (1 + np.exp(-logits[0]))
    router = Router("sigmoid", 4, 2, True, 2.5, True)
    # no bias: groups score s0+s1, s2+s3, s4+s5, s6+s7: kept 0 and 1 (0.88 +
    # 0.73 and 0.82 + 0.80 beat 0.27 + 0.95); chosen 0 and 2
    w, ids = _route(gate, x, np.zeros(8, np.float32), router)
    assert sorted(ids[0]) == [0, 2]
    assert w[0].sum() == pytest.approx(2.5, rel=1e-6)
    assert w[0][list(ids[0]).index(0)] == pytest.approx(
        2.5 * s[0] / (s[0] + s[2]), rel=1e-6)
    # a bias lifts group 3 into the kept and expert 6 into the chosen, and
    # the WEIGHT of 6 is still its unbiased score
    bias = np.array([0, 0, 0, 0, 0, 0, 2.0, 0], np.float32)
    w, ids = _route(gate, x, bias, router)
    assert sorted(ids[0]) == [6, 7]
    assert w[0][list(ids[0]).index(6)] == pytest.approx(
        2.5 * s[6] / (s[6] + s[7]), rel=1e-6)
    # an expert outside the kept groups is never chosen, whatever it scores
    one = Router("sigmoid", 4, 1, False, 1.0, False)
    w, ids = _route(gate, x, None, one)
    assert sorted(ids[0]) == [2, 3]                # group 1: 0.82 + 0.80
    assert np.allclose(sorted(w[0]), sorted(s[[2, 3]]))   # not renormalised
    # a tie is broken as the reference breaks it: the lower index
    tie = np.zeros((1, 8), np.float32)
    _, ids = _route(gate, tie, None, Router("sigmoid", 4, 2, True, 1.0))
    rw, rids, _ = reference_latent.route(
        toy_spec(n_experts=8, n_active_experts=2,
                 layout=ExpertLayout(1, 384, 1, 0, 0),
                 router=Router("sigmoid", 4, 2, True, 1.0)), gate, None,
        jnp.asarray(tie))
    assert sorted(ids[0]) == sorted(np.asarray(rids)[0]) == [0, 1]
    # the default record is the softmax router as it was: top-k as they are
    w, ids = _route(gate, x, None, Router(), k=2)
    p = np.exp(logits[0]) / np.exp(logits[0]).sum()
    assert list(ids[0]) == [7, 0] and np.allclose(w[0], p[[7, 0]])


def test_the_program_routes_as_the_reference_on_seeded_rows(tree):
    h = np.random.default_rng(3).standard_normal((64, 256)).astype(np.float32)
    gate, bias = tree["moe_gate"][0], tree["moe_bias"][0]
    w, ids = _route(gate, h, bias, SPEC.router, k=4)
    with jax.default_matmul_precision("highest"):
        rw, rids, margin = reference_latent.route(SPEC, gate, bias,
                                                  jnp.asarray(h))
    sure = np.asarray(margin) > 1e-4
    assert sure.sum() > 50
    assert (np.sort(ids[sure]) == np.sort(np.asarray(rids)[sure])).all()
    assert np.allclose(np.sort(w[sure]), np.sort(np.asarray(rw)[sure]),
                       atol=1e-6)
    assert np.abs(bias).max() > 0.01      # choice and weight do differ


# -- the share test (model-configs guide, section 4) -----------------------------

def test_the_shares_routed_parts_and_the_shared_expert_add_up_to_the_layer():
    """Four shares of four experts each: their routed parts plus the
    shared expert counted ONCE are the uncut reference's expert layer, in
    the reference and in the program alike."""
    whole = toy_spec(layout=ExpertLayout(1, 384, 1, 0, 0))
    full = synth_params(whole, q40=True, seed=7)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (16, 256)).astype(np.float32))
    lw = {k: jax.tree_util.tree_map(lambda a: a[0], v)
          for k, v in full.items() if k not in (
              "tok_embedding", "rms_final", "wcls", "dense")}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_latent.experts(whole, lw, x)[0] - x)
        shared = np.asarray(reference_latent.experts(
            toy_spec(layout=ExpertLayout(1, 384, 1, 4, 0)),
            dict(lw, **{k: jax.tree_util.tree_map(lambda a: a[:4], lw[k])
                        for k in ("moe_w1", "moe_w2", "moe_w3")}), x)[0] - x
        ) - np.asarray(reference_latent.experts(
            toy_spec(layout=ExpertLayout(1, 384, 1, 4, 0)),
            dict(lw, **{k: jax.tree_util.tree_map(lambda a: a[:4], lw[k])
                        for k in ("moe_w1", "moe_w2", "moe_w3")}), x,
            shared=False)[0] - x)
    ref_sum, prog_sum, landed = shared.copy(), shared.copy(), 0
    from distributed_llama_tpu.ops.linear import rmsnorm

    h = rmsnorm(x, lw["rms_ffn"], whole.norm_eps)
    for share in range(4):
        spec = toy_spec(layout=ExpertLayout(1, 384, 1, 4, 4 * share))
        part = dict(lw, **{k: jax.tree_util.tree_map(
            lambda a: a[4 * share:4 * share + 4], lw[k])
            for k in ("moe_w1", "moe_w2", "moe_w3")})
        with jax.default_matmul_precision("highest"):
            ref_sum += np.asarray(reference_latent.experts(
                spec, part, x, shared=False)[0] - x)
        y, counts = pallas_moe.moe_ffn(spec, part, h)
        prog_sum += np.asarray(y)
        counts = np.asarray(counts)
        assert counts.shape == (16,) and counts.sum() == 16 * 4
        landed += counts[spec.held_columns].sum()
    assert landed == 16 * 4               # every pair landed on one share
    assert np.abs(ref_sum - want).max() < 2e-6
    assert np.abs(prog_sum - want).max() < 2e-5


@pytest.mark.parametrize("rows", [3, 16, 40])     # narrow slots twice, wide
def test_the_grouped_kernels_walk_held_experts_only(rows, monkeypatch):
    """Pairs that land on experts held elsewhere take no slot and add
    nothing: the packed kernels against the XLA scan, on a share."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    spec = toy_spec()
    tree = synth_params(spec, q40=True, seed=3)
    params = params_to_device(tree, spec=spec)
    from distributed_llama_tpu.models.llama import (layer_view,
                                                    split_layer_weights)

    stacked, scanned = split_layer_weights(params)
    lw = layer_view(stacked, {k: v[1] for k, v in scanned.items()}, 1)
    raw = {k: jax.tree_util.tree_map(lambda a: a[1], tree[k])
           for k in ("moe_gate", "moe_bias", "moe_w1", "moe_w2", "moe_w3")}
    h = jnp.asarray(np.random.default_rng(rows).standard_normal(
        (rows, 256)).astype(np.float32))
    got, counts = pallas_moe.moe_ffn(spec, lw, h)
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "xla")
    want, counts_x = pallas_moe.moe_ffn(spec, raw, h)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert (np.asarray(counts) == np.asarray(counts_x)).all()
    assert 0 < np.asarray(counts)[spec.held_columns].sum() < rows * 4


def test_build_slots_gives_a_pair_held_elsewhere_no_slot():
    topi = jnp.asarray([[0, -1], [-1, -1], [2, 0]], jnp.int32)
    (slot_expert, n_slots, fill, slot_rows, pair_slot, pair_lane,
     counts) = pallas_moe.build_slots(topi, 3, 2)
    assert int(n_slots) == 2 and list(np.asarray(counts)) == [2, 0, 1]
    assert np.asarray(fill)[:2].tolist() == [2, 1] and \
        not np.asarray(fill)[2:].any()
    a = slot_rows.shape[0]
    assert np.asarray(pair_slot).tolist() == [[0, a], [a, a], [1, 0]]
    assert np.asarray(slot_rows)[0].tolist() == [0, 2]
    none = pallas_moe.build_slots(jnp.full((2, 2), -1, jnp.int32), 3, 2)
    assert int(none[1]) == 0 and int(np.asarray(none[0]).min()) == 0
    assert not np.asarray(none[2]).any()              # no slot holds a row


# -- the file: header version 4, and the older versions byte for byte ---------

def test_header_version_4_round_trip():
    raw = SPEC.header()
    assert SPEC.header_version == 4 and len(raw) == EXT4_STRUCT.size == 192
    assert TransformerSpec.from_header(raw, FloatType.Q40) == SPEC
    assert SPEC.rope_gap_bytes == 0


@pytest.mark.parametrize("kw,version,size", [
    (dict(), 0, 28),
    (dict(n_experts=8, n_active_experts=2, qk_norm=True), 2, 52),
    (dict(qk_norm=True, qk_norm_per_head=True, attn_kind="retention",
          rope_theta=1e6, norm_eps=1e-6), 3, 72)])
def test_older_headers_read_and_write_byte_for_byte(kw, version, size):
    spec = TransformerSpec(256, 128, 2, 4, 4, 512, 64, **kw)
    raw = spec.header()
    assert (spec.header_version, len(raw)) == (version, size)
    again = TransformerSpec.from_header(raw)
    assert again == spec and again.header() == raw
    assert again.layout == ExpertLayout() and again.latent is None


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
    if ftype == FloatType.F32:
        for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(got)):
            assert np.array_equal(a, b)
    ranges = tensor_byte_ranges(spec)
    assert sum(r.nbytes for r in ranges) + spec.header_bytes == \
        spec.file_size()
    names = [r.name for r in ranges if r.layer == 1]
    assert names[:11] == ["rms_att", "rms_ffn", "rms_q_a", "rms_kv_a",
                          "wq_a", "wq_b", "wkv_a", "wkv_b", "wo",
                          "moe_gate", "moe_bias"]
    assert names.count("moe_w1") == 8 and "w1" not in names


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
    assert rc == 0 and "latent" in out.out


def test_spec_rejects_half_a_description():
    with pytest.raises(ValueError, match="set latent"):
        toy_spec(latent=None)
    with pytest.raises(ValueError, match="do not fit"):
        toy_spec(layout=ExpertLayout(1, 384, 1, 8, 12))     # 12 + 8 > 16
    with pytest.raises(ValueError, match="do not fit"):
        toy_spec(layout=ExpertLayout(3, 384, 1, 8, 0))      # no expert layer
    with pytest.raises(ValueError, match="scoring"):
        toy_spec(router=Router("tanh"))


# -- what it refuses, one test a line ------------------------------------------

def _engine(**kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    base = dict(slots=2, temperature=0.0, topp=0.9, seed=1, page_size=8,
                prefill_chunk=8)
    base.update(kw)
    return ContinuousEngine(SPEC, synth_params(SPEC, q40=True, seed=1),
                            **base)


def test_refuses_serving_without_pages():
    with pytest.raises(ValueError, match="--kv-page-size"):
        _engine(page_size=0)


def test_refuses_q8_pages():
    with pytest.raises(ValueError, match="--kv-quant q8"):
        _engine(kv_quant="q8")


def test_refuses_the_host_tier():
    with pytest.raises(ValueError, match="--kv-host-pages"):
        _engine(kv_host_pages=4)


def test_refuses_the_disk_tier(tmp_path):
    with pytest.raises(ValueError, match="--kv-disk-dir"):
        _engine(kv_disk_dir=str(tmp_path))


def test_refuses_the_disaggregated_handoff():
    with pytest.raises(ValueError, match="--disagg-role"):
        _engine(remote_pages=True)


def test_refuses_speculation():
    with pytest.raises(ValueError, match="--spec-k 3"):
        _engine(spec_k=3)


def test_refuses_mixed_dispatches():
    with pytest.raises(ValueError, match="--dispatch-tokens 16"):
        _engine(dispatch_tokens=16)


def test_refuses_fused_chains():
    with pytest.raises(ValueError, match="--block-steps 4"):
        _engine(block_steps=4)


def test_refuses_a_bfloat16_plane():
    with pytest.raises(ValueError, match="--kv-cache-dtype"):
        _engine(cache_dtype=jnp.bfloat16)


def test_refuses_tensor_parallel_ranks():
    from distributed_llama_tpu.parallel import make_mesh

    with pytest.raises(ValueError, match="--tp 2"):
        _engine(mesh=make_mesh(tp=2))


def test_every_refusal_names_its_flag_in_one_list():
    from distributed_llama_tpu.runtime.continuous import latent_refusals

    lines = latent_refusals(tp=4, page_size=0, spec_k=2, dispatch_tokens=8,
                            kv_quant="q8", kv_host_pages=1, disagg=True,
                            block_steps=2, kv_cache_dtype="bf16")
    assert len(lines) == 9 and latent_refusals(page_size=16) == []
    assert latent_refusals(serve=False) == []    # inference: one sequence


def test_cli_refuses_by_name(tmp_path, capsys):
    from distributed_llama_tpu.frontend.cli import main
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, SPEC, seed=4)
    write_synth_tokenizer(tok, SPEC.vocab_size)
    rc = main(["serve", "--model", model, "--tokenizer", tok,
               "--weights-float-type", "q40", "--kv-page-size", "8",
               "--spec-k", "3", "--port", "0"])
    err = capsys.readouterr().err
    assert rc == 2 and "refused: --spec-k 3" in err


# -- prefix sharing and the counters, through the engine ----------------------

def test_engine_serves_with_prefix_sharing_and_counts_its_share(kernel_mode,
                                                                tree):
    """Requests that share a two-page prefix through the paged engine: the
    second finds the first's pages (sharing works on page ids), every
    stream is the reference's greedy stream up to a near-tie, and the
    counters read the share."""
    from distributed_llama_tpu.obs.metrics import Registry
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)

    reg = Registry()
    eng = ContinuousEngine(SPEC, tree, slots=3, temperature=0.0, topp=0.9,
                           seed=3, page_size=8, prefill_chunk=8,
                           metrics=reg)
    rng = np.random.default_rng(9)
    shared = [1] + [int(t) for t in rng.integers(3, 500, 17)]
    prompts = [shared + [int(t) for t in rng.integers(3, 500, n)]
               for n in (3, 6)] + [[1] + [int(t) for t in rng.integers(
                   3, 500, n)] for n in (4, 11, 2)]
    first = eng.submit(Request(tokens=list(prompts[0]),
                               steps=len(prompts[0]) + 10))
    while eng.step_once():
        pass
    reqs = [first] + [eng.submit(Request(tokens=list(p), steps=len(p) + 10))
                      for p in prompts[1:]]
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    assert eng.allocator.prefix_hits >= 1
    for r, p in zip(reqs, prompts):
        assert list(r.out[:len(p) - 1]) == p[1:]     # the prompt's echo
        seq = [p[0]] + list(r.out)
        ref, margins, _ = reference_latent.forward(tree, SPEC, seq[:-1])
        low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
        stop = int(low[0]) if low.size else len(seq)
        for pos in range(len(p) - 1, min(stop, len(seq) - 1)):
            row = ref[pos]
            assert row.max() - row[seq[pos + 1]] < TOL
    st = eng.stats
    assert st.moe_pairs == st.steps * 3 * 4 * 2        # rows x k x layers
    assert 0 < st.moe_local_pairs < st.moe_pairs
    assert st.moe_active <= st.moe_local_pairs
    assert st.moe_load.shape == (16,)
    assert st.latent_pages > 0 and st.latent_positions > st.steps
    assert st.paged_kv_positions == 0     # a plain KV pool's counter
    assert reg.get("dllama_moe_local_pairs_total").value == \
        st.moe_local_pairs
    # three rows a dispatch: a held expert's rows fit one slot, and a pair
    # held elsewhere takes none
    assert st.moe_slots == st.moe_active
    assert 0 < st.moe_single_row_slots <= st.moe_slots
    assert reg.get("dllama_moe_slots_total").value == st.moe_slots
    assert reg.get("dllama_moe_single_row_slots_total").value == \
        st.moe_single_row_slots
    assert "dllama_moe_diag_slots_total 0" in reg.expose()
    assert "dllama_latent_pages_in_use" in reg.expose()


# -- chunks that walk, through both entries, and what the engine counts -------

def _two_prompts():
    rng = np.random.default_rng(23)     # no router near-tie inside either
    return [[1] + [int(t) for t in rng.integers(3, 500, n)] for n in (39, 19)]


def test_chunks_then_steps_through_serve_are_inferences_and_counted(tree):
    """Two prompts of 40 and 20 tokens admitted in chunks of 16 (the block:
    16 divides the 64 positions), then decode steps: every pick is the
    reference's maximum, ``inference``'s logits after the same chunks agree
    with the reference, and the counters add up what the chunks walked."""
    from distributed_llama_tpu.runtime.continuous import (ContinuousEngine,
                                                          Request)
    from distributed_llama_tpu.runtime.generate import Engine

    eng = ContinuousEngine(SPEC, tree, slots=2, temperature=0.0, topp=0.9,
                           seed=3, page_size=8, prefill_chunk=16)
    prompts = _two_prompts()
    reqs = [eng.submit(Request(tokens=list(p), steps=len(p) + 8))
            for p in prompts]
    while eng.step_once():
        pass
    assert all(r.done.is_set() and r.error is None for r in reqs)
    one = Engine(SPEC, tree)
    for r, p in zip(reqs, prompts):
        seq = [p[0]] + list(r.out)
        ref, margins, _ = reference_latent.forward(tree, SPEC, seq[:-1])
        low = np.nonzero(margins.min(axis=1) < MARGIN_EPS)[0]
        stop = int(low[0]) if low.size else len(seq)
        assert stop > len(p), "a near-tie inside the prompt: pick a seed"
        one.reset()
        one.prefill(p[:-1], chunk=16)
        for pos in range(len(p) - 1, min(stop, len(seq) - 1)):
            assert ref[pos].max() - ref[pos][seq[pos + 1]] < TOL
            got = one.infer(seq[pos], pos)
            assert np.abs(np.asarray(got) - ref[pos]).max() < TOL
    st = eng.stats
    # a prompt's last token goes through a step: 39 and 19 rows in chunks
    starts = [0, 16, 32, 0, 16]
    assert st.prefill_chunks == len(starts)
    assert st.chunk_walked_positions == sum(s + 16 for s in starts) == 144
    assert st.chunk_plane_positions == len(starts) * SPEC.seq_len
    assert st.chunk_walk_share == 144 / 320


def test_a_chunk_of_eight_or_fewer_counts_the_whole_plane(tree):
    from distributed_llama_tpu.runtime.continuous import Request

    eng = _engine()                 # prefill_chunk=8: today's whole plane
    r = eng.submit(Request(tokens=_two_prompts()[1], steps=22))
    while eng.step_once():
        pass
    assert r.error is None and eng.stats.prefill_chunks == 3
    assert eng.stats.chunk_walk_share == 1.0


def test_the_servers_summary_says_what_share_of_the_plane_chunks_walked(
        tree, capsys):
    import json
    import urllib.request

    from distributed_llama_tpu.runtime.server import InferenceServer

    class Tok:
        def encode(self, text, bos=True, eos=False):
            return [1] + [3 + b for b in text.encode()]

        def decode_piece(self, prev, tok):
            return b"<%d>" % tok

    srv = InferenceServer(SPEC, tree, Tok(), "127.0.0.1", 0, slots=2,
                          steps=4, temperature=0.0, topp=0.9, seed=5,
                          quiet=True, metrics=False, page_size=8,
                          prefill_chunk=16)
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=json.dumps({"prompt": "a" * 35, "steps": 40}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert json.loads(r.read())["tokens"]
    finally:
        srv.stop()
    # 36 tokens: 35 rows in chunks at 0, 16 and 32 of 64 positions
    assert srv.engine.stats.chunk_walked_positions == 16 + 32 + 48
    err = capsys.readouterr().err
    assert "chunks walked 50.0% of the plane" in err
    # ... and how the expert slot kernel engaged (the toy ``w2`` is 4 blocks
    # a row, off the block-diagonal body's grid: none took it)
    st = srv.engine.stats
    assert 0 < st.moe_single_row_slots <= st.moe_slots
    assert (f"; 0 of {st.moe_slots} expert slots took the block-diagonal "
            f"body ({st.moe_single_row_slots} held one row)") in err


# -- the converter, on a toy dict of the published names ---------------------

def test_converter_maps_the_published_names(tmp_path):
    import types

    from distributed_llama_tpu import convert

    # the converter writes the whole model: every routed expert held
    spec = dataclasses.replace(SPEC, weights_float_type=FloatType.F32,
                               layout=ExpertLayout(1, 384, 1))
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
    for name, key in convert.LATENT_TENSORS.items():
        for layer in range(3):
            stack, at = (dense["dense"], 0) if layer == 0 else (dense,
                                                                layer - 1)
            if name not in stack:
                continue
            if name.startswith("moe_w"):
                for e in range(16):
                    state[key.format(layer=layer, expert=e)] = _Tensor(
                        stack[name][at, e])
            else:
                state[key.format(layer=layer)] = _Tensor(stack[name][at])
    config = types.SimpleNamespace(
        model_type="deepseek_v3", hidden_size=256, moe_intermediate_size=128,
        intermediate_size=384, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, vocab_size=512, n_routed_experts=16,
        num_experts_per_tok=4, rope_theta=10000.0, rms_norm_eps=1e-6,
        q_lora_rank=128, kv_lora_rank=64, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, first_k_dense_replace=1,
        n_shared_experts=1, n_group=4, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        topk_method="noaux_tc", hidden_act="silu", moe_layer_freq=1,
        rope_scaling={"type": "yarn", "factor": 40,
                      "original_max_position_embeddings": 16,
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
    assert "mlp.gate.e_score_correction_bias" in \
        convert.LATENT_TENSORS["moe_bias"]
    assert np.array_equal(got["moe_w2"], dense["moe_w2"])
    assert np.array_equal(got["moe_bias"], dense["moe_bias"])
    assert np.array_equal(got["dense"]["wkv_a"], dense["dense"]["wkv_a"])
    assert np.array_equal(got["sh_w1"], dense["sh_w1"])
    config.quantization_config = {"quant_method": "fp8"}
    with pytest.raises(ValueError, match="FP8"):
        Stub().spec(FloatType.F32, 64)


# -- the analysis tools count a latent spec with held experts, or refuse it ----

def _published_share():
    return TransformerSpec(
        7168, 2048, 9, 128, 128, 16256, 2048, FloatType.Q40, n_experts=256,
        n_active_experts=8, norm_eps=1e-6,
        latent=LatentAttn(1536, 512, 128, 64, 128),
        layout=ExpertLayout(1, 18432, 1, 32, 0),
        router=Router("sigmoid", 8, 4, True, 2.5, True),
        rope_scaling=RopeScaling(40.0, 4096, 32.0, 1.0, 1.0, 1.0))


def test_memory_model_counts_one_chips_share():
    from distributed_llama_tpu.analysis import memory_model as mm

    spec = _published_share()
    # by hand: Q40 at 20 B a block of 32 in the kernel layout
    attn = 1536 * 7168 + 24576 * 1536 + 576 * 7168 + 7168 * 16384
    expert = 3 * 2048 * 7168
    values = (9 * attn + 3 * 18432 * 7168 + 8 * 33 * expert + 16256 * 7168)
    assert mm.weights_device_bytes(spec, 1) == values // 32 * 20
    assert mm.latent_absorbed_bytes(spec) == 9 * 32768 * 512 * 4
    resident = mm.weights_device_bytes(spec, 1) + mm.replicated_device_bytes(
        spec)
    assert round(resident / 2**30, 2) == 9.01     # the rehearsal's 9.01 GiB
    assert mm.kv_position_bytes(spec, 1) == 9 * 640 * 4
    assert mm.kv_page_pool_bytes(spec, 1, 4096, 16) == 4097 * 16 * 23040
    with pytest.raises(ValueError, match="one chip only"):
        mm.weights_device_bytes(spec, 4)
    with pytest.raises(ValueError, match="float32 on one chip"):
        mm.kv_position_bytes(spec, 1, kv_quant="q8")


def test_sharding_is_refused_by_name():
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.tp import (param_specs,
                                                   validate_sharding)

    with pytest.raises(ValueError, match="one chip only"):
        validate_sharding(SPEC, make_mesh(tp=2))
    with pytest.raises(ValueError, match="one chip only"):
        param_specs(synth_params(SPEC, q40=True, seed=1))


def test_body_policy_packs_every_dense_leaf_nb_major(monkeypatch):
    from distributed_llama_tpu.io.loader import Q40KernelNb
    from distributed_llama_tpu.ops.linear import (pack_q40_params,
                                                  q40_body_policy)

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    # a rule on shapes, not on the attention kind: nb 224 (in 7168) pads by
    # under a quarter, so the stock picks would leave it d-major, off the
    # 128 grid, and the chip would copy it in every step
    layout = q40_body_policy(_published_share(), rows=32)
    assert layout.label == "nb-major" and layout.force_nb_major
    assert "(1536, 7168)" in layout.reason and "nb 224" in layout.reason
    assert not layout.i4_chain
    # an expert spec whose stock picks are nb-major already keeps its line
    olmoe = TransformerSpec(2048, 1024, 16, 16, 16, 50304, 4096,
                            FloatType.Q40, n_experts=64, n_active_experts=8,
                            qk_norm=True)
    assert q40_body_policy(olmoe, rows=16).label == "d-major"
    # the latent row's projection: 80 outputs here (576 published), off the
    # 128-row grid; the model's own preparation gives it zero rows up to
    # the plane's width, and it packs like its neighbours
    tree = synth_params(SPEC, q40=True, seed=1)
    prepared = latent.prepare_latent_params(SPEC, tree)
    assert prepared["wkv_a"].qs.shape[-3] == latent.plane_width(SPEC) == 128
    packed = pack_q40_params(prepared, allow_nb_major=True, layout=layout)
    wkv_a = packed["wkv_a"]
    assert isinstance(wkv_a, Q40KernelNb) and wkv_a.qs_t.shape[-1] == 128
    assert not np.asarray(wkv_a.scale)[..., 80:].any()
    assert isinstance(packed["dense"]["w2"], Q40KernelNb)
