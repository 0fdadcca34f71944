"""NVIDIA-Nemotron-3-Nano's layout (``TransformerSpec.ssd``: a layer is ONE
mixer, Mamba-2, attention without positional encoding, or non-gated relu2
experts of which a share may be held) at a toy size that keeps the odd
shapes: an expert width that is not a lane multiple (96), 2 KV heads under 8
query heads, 2 groups under 4 Mamba heads, a list with all three kinds that
is not periodic. The Mamba-2 decode kernel (interpret mode) and the chunked
SSD form against the recurrence; the forward (``models/nemotron.py``:
prefill, then decode through state and pages) against
``models/reference_nemotron.py`` on LOGITS; the two shares of the experts
against the uncut layer; header version 10; ``convert.py`` on seeded tensors
of the published names."""

import dataclasses
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models import nemotron
from distributed_llama_tpu.models import reference_nemotron as ref
from distributed_llama_tpu.models.spec import (
    Activation, ExpertLayout, HybridLayers, HyperConnections, LatentAttn,
    MixerKind, MixerKinds, Router, SsdLayers, TransformerSpec, sambay_kinds)
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.ops import mamba2 as ssd
from distributed_llama_tpu.ops.quants import FloatType

TOL = 5e-5
SEQ = 40
LETTERS = {"M": "mamba2", "*": "full", "E": "experts"}
PATTERN = "MEM*EMME"


def tiny(pattern=PATTERN, wft=FloatType.Q40, held=0, offset=0, dim=64, **kw):
    kinds = tuple(LETTERS[c] for c in pattern)
    return TransformerSpec(
        dim=dim, hidden_dim=96, n_layers=len(kinds), n_heads=8, n_kv_heads=2,
        vocab_size=256, seq_len=64, weights_float_type=wft, n_experts=8,
        n_active_experts=3, layout=ExpertLayout(0, 0, 1, held, offset),
        router=Router("sigmoid", 1, 1, True, 2.5, bias=True),
        activation=Activation("relu2", gated=False),
        ssd=SsdLayers(kinds, heads=4, head_dim=16, groups=2, d_state=16,
                      head_size=16, chunk=8, shared_hidden=160), **kw)


SPEC = tiny()


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=True, seed=1)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(3, SPEC.vocab_size, SEQ)


@pytest.fixture(scope="module")
def want(tree, tokens):
    return ref.forward(tree, SPEC, tokens)[0]


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    """XLA everywhere, and every kernel (packed Q40, the expert slots, the
    paged decode attention) in interpret mode; the Mamba-2 decode kernel
    runs in interpret mode in both."""
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL"):
        monkeypatch.setenv(var, request.param)
    return request.param


# -- the Mamba-2 state: kernel and chunk form against the recurrence -------------

def _draw(rng, t, heads=4, p=16, groups=2, n=16):
    x = rng.standard_normal((t, heads, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((t, heads)))).astype(
        np.float32) * 0.3
    b = rng.standard_normal((t, groups, n)).astype(np.float32)
    c = rng.standard_normal((t, groups, n)).astype(np.float32)
    return x, dt, b, c


def _recurrence(h, a_log, x, dt, b, c):
    """float64, a position at a time: (y (T, H, P), the state after)."""
    per = x.shape[1] // b.shape[1]
    a = -np.exp(np.asarray(a_log, np.float64))
    h = np.asarray(h, np.float64).copy()
    ys = []
    for t in range(len(x)):
        bt, ct = np.repeat(b[t], per, 0), np.repeat(c[t], per, 0)
        h = (np.exp(dt[t] * a)[:, None, None] * h
             + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", h, ct))
    return np.stack(ys), h


A_LOG = np.log(np.linspace(1.0, 16.0, 4)).astype(np.float32)


@pytest.mark.parametrize("steps", [1, 6])
def test_decode_kernel_against_the_recurrence(steps):
    """Three rows of layer 1 of a two-layer stack: row 0 rides, row 1 takes
    no part (its state is left bit for bit), row 2 is at its sequence's
    first position (its state, whatever it holds, is emptied first). Layer
    0's rows are not touched."""
    rng = np.random.default_rng(steps)
    B = 3
    all0 = rng.standard_normal((2 * B, 4, 16, 16)).astype(np.float32)
    state = jnp.asarray(all0)
    live = jnp.asarray([True, False, True])
    want_h = [all0[B + 0], None, np.zeros_like(all0[0])]
    for step in range(steps):
        draws = [_draw(rng, 1) for _ in range(B)]
        x, dt, b, c = (jnp.asarray(np.concatenate([d[i] for d in draws]))
                       for i in range(4))
        fresh = jnp.asarray([False, False, step == 0])
        y, state = ssd.scan_decode(jnp.int32(1), state, jnp.asarray(A_LOG),
                                   x, dt, b, c, fresh, live)
        for r in (0, 2):
            want_y, want_h[r] = _recurrence(want_h[r], A_LOG, *draws[r])
            np.testing.assert_allclose(np.asarray(y[r]), want_y[0],
                                       rtol=2e-5, atol=2e-5)
    got = np.asarray(state)
    np.testing.assert_array_equal(got[:B], all0[:B])
    np.testing.assert_array_equal(got[B + 1], all0[B + 1])
    for r in (0, 2):
        np.testing.assert_allclose(got[B + r], want_h[r], rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("cuts,chunk", [
    ((21,), 8),             # two whole chunks and a ragged one
    ((16, 21, 37), 8),      # admission chunks: whole, ragged, two and a bit
    ((5,), 8),              # shorter than a chunk
    ((8, 9, 24), 8),        # a one-position admission between whole ones
    ((30,), 128),           # the published chunk, one part-filled
], ids=["ragged", "admissions", "short", "single", "chunk128"])
def test_chunk_form_equals_the_recurrence(cuts, chunk):
    """The SSD chunk form over admission chunks that end at ``cuts``, the
    state handed from one to the next, against the recurrence: y at every
    position and the state at the end; a padded tail (dt 0) changes
    neither."""
    rng = np.random.default_rng(len(cuts) + chunk)
    total = cuts[-1]
    x, dt, b, c = _draw(rng, total)
    h0 = rng.standard_normal((4, 16, 16)).astype(np.float32)
    want_y, want_h = _recurrence(h0, A_LOG, x, dt, b, c)
    h, lo, ys = jnp.asarray(h0), 0, []
    for hi in cuts:
        pad = -(hi - lo) % 4        # a chunk's padding: positions with dt 0
        part = [np.concatenate([v[lo:hi], np.zeros((pad, *v.shape[1:]),
                                                   np.float32)])
                for v in (x, dt, b, c)]
        y, h = ssd.ssd_chunk(h, jnp.asarray(A_LOG), *map(jnp.asarray, part),
                             chunk)
        ys.append(np.asarray(y)[:hi - lo])
        lo = hi
    np.testing.assert_allclose(np.concatenate(ys), want_y, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-4, atol=1e-4)


# -- the forward against the reference ------------------------------------------

def test_the_list_is_walked_as_scans():
    from distributed_llama_tpu.models import kindscan

    sigs = [(k,) for k in SPEC.ssd.kinds]
    segs = kindscan.segments(sigs)
    assert sum(len(u) * r for _, u, r in segs) == len(sigs)
    published = tuple(LETTERS[c] for c in
                      "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    segs = kindscan.segments([(k,) for k in published])
    assert sum(len(u) * r for _, u, r in segs) == 52 and len(segs) < 20
    assert [published.count(k) for k in ("mamba2", "experts", "full")] == [
        23, 23, 6]


def _params(tree, spec=SPEC):
    from distributed_llama_tpu.models.llama import params_to_device
    from distributed_llama_tpu.ops.linear import q40_body_policy

    return params_to_device(tree, layout=q40_body_policy(spec, rows=2))


@pytest.mark.parametrize("paged", [False, True], ids=["rows", "pages"])
def test_prefill_then_decode_on_logits(kernel_mode, paged, tree, tokens,
                                       want):
    """Chunks of 8 (three SSD chunks' worth in the first admission, a
    ragged one after), then decode: through the contiguous cache, or from a
    row of the paged cache whose state, conv rows and pages another
    sequence held."""
    params = _params(tree)
    n_pre, n = 21, (26 if kernel_mode == "pallas" else 34)
    cache = nemotron.init_cache(SPEC)
    chunk = jax.jit(lambda p, c, t, pos, nv: nemotron.forward_chunk(
        SPEC, p, c, t, pos, nv))
    logits, cache = chunk(params, cache, jnp.asarray(tokens[:16]),
                          jnp.int32(0), 16)
    worst = np.abs(np.asarray(logits) - want[:16]).max()
    tail = np.concatenate([tokens[16:n_pre], [0, 0, 0]])
    logits, cache = chunk(params, cache, jnp.asarray(tail), jnp.int32(16),
                          n_pre - 16)
    worst = max(worst, np.abs(np.asarray(logits)[:n_pre - 16]
                              - want[16:n_pre]).max())
    if not paged:
        one = jax.jit(lambda p, c, t, pos: nemotron.forward_chunk(
            SPEC, p, c, t, pos))
        for pos in range(n_pre, n):
            logits, cache = one(params, cache,
                                jnp.asarray(tokens[pos:pos + 1]),
                                jnp.int32(pos))
            worst = max(worst, np.abs(np.asarray(logits)[0]
                                      - want[pos]).max())
        assert worst < TOL
        return
    ps, slots = 8, 2
    pool = nemotron.init_cache_paged(SPEC, slots, 1 + 2 * SPEC.seq_len // ps,
                                     ps)
    pool = jax.tree_util.tree_map(lambda a: a + 7.0, pool)   # another's
    table = np.zeros((slots, SPEC.seq_len // ps), np.int32)
    table[1, :5] = [9, 3, 12, 5, 7]
    pool = nemotron.insert_sequence(pool, cache, 1, jnp.asarray(table[1]), ps)
    step = jax.jit(lambda p, c, t, pos, tb, act: nemotron.forward_batch(
        SPEC, p, c, t, pos, tb, act, page_size=ps, health=True,
        moe_counts=True))
    for pos in range(n_pre, n):
        toks = jnp.asarray([0, tokens[pos]])
        logits, pool, low, counts = step(
            params, pool, toks, jnp.asarray([0, pos]), jnp.asarray(table),
            jnp.asarray([0, 1]))
        worst = max(worst, np.abs(np.asarray(logits)[1] - want[pos]).max())
        assert 0.0 < float(low[0]) <= 1.0
        assert counts.shape == (3, 8) and int(counts.sum()) == 3 * 2 * 3
    assert worst < TOL


def test_a_reused_row_finds_its_state_empty(tree, tokens, want):
    """Position 0 on a row that holds another sequence's state and conv
    rows reads what an empty row reads; a row that takes no part keeps its
    state bit for bit."""
    params = _params(tree)
    cache = jax.tree_util.tree_map(
        lambda a: a + 3.0, nemotron.init_cache(SPEC, batch=2))
    logits, out = nemotron.forward_batch(
        SPEC, params, cache, jnp.asarray([tokens[0], tokens[0]]),
        jnp.asarray([0, 0]), active=jnp.asarray([1, 0]))
    assert np.abs(np.asarray(logits)[0] - want[0]).max() < TOL
    np.testing.assert_array_equal(np.asarray(out.ssm)[:, 1],
                                  np.asarray(cache.ssm)[:, 1])
    np.testing.assert_array_equal(np.asarray(out.conv)[:, 1],
                                  np.asarray(cache.conv)[:, 1])
    assert nemotron.state_bytes(cache) == (
        2 * 4 * 4 * (4 * 16 * 16 + 3 * 128), 0)


def test_the_two_shares_add_up_to_the_uncut_layer(kernel_mode):
    """The program's expert mixer on each half of the experts (held 4 at
    offset 0 and at offset 4; the router keeps its 8 outputs): the two
    routed parts plus the shared expert ONCE are the reference's uncut
    layer. Under the kernels at a width of 128, where the slot kernel takes
    the stacks: their hidden 96 packed as 256 and their 4 blocks a row as 8,
    zero blocks both."""
    from distributed_llama_tpu.io.loader import Q40KernelNb
    from distributed_llama_tpu.models.kindscan import is_packed
    from distributed_llama_tpu.models.reference_laguna import _layer_of
    from distributed_llama_tpu.ops.linear import StackedQ40

    packed = kernel_mode == "pallas"
    whole_spec = tiny(dim=128 if packed else 64)
    tree = synth_params(whole_spec, q40=True, seed=1)
    u = np.random.default_rng(4).standard_normal(
        (6, whole_spec.dim)).astype(np.float32)
    whole = _layer_of(tree["experts"], 1)
    want_y, _, ids = ref.experts(whole_spec, whole, jnp.asarray(u))
    shared = ref._activate(whole_spec, whole, jnp.asarray(u),
                           "sh_") @ ref._dense(whole["sh_w2"]).T
    total, landed = -np.asarray(shared), 0
    for offset in (0, 4):
        spec = dataclasses.replace(
            whole_spec, layout=ExpertLayout(0, 0, 1, 4, offset))
        part = {k: dict(v) if isinstance(v, dict) else v
                for k, v in tree.items()}
        for name in ("moe_w1", "moe_w2"):
            w = tree["experts"][name]
            part["experts"][name] = type(w)(
                *(a[:, offset:offset + 4] for a in w))
        stack = _params(part, spec)["experts"]
        if packed:
            assert isinstance(stack["moe_w1"], Q40KernelNb)
            assert stack["moe_w1"].qs_t.shape == (3, 4, 16, 8, 256)
            assert stack["moe_w2"].qs_t.shape == (3, 4, 16, 8, 128)
        lw = {k: StackedQ40(v, jnp.int32(1)) if is_packed(v)
              else jax.tree_util.tree_map(lambda a: a[1], v)
              for k, v in stack.items()}
        y, counts = nemotron._experts(spec, lw, jnp.asarray(u),
                                      jnp.zeros((3, 8), jnp.int32), 0)
        total = total + np.asarray(y)
        landed += int(np.asarray(counts)[0, spec.held_columns].sum())
        assert int(np.asarray(counts).sum()) == ids.size
    assert landed == ids.size
    assert np.abs(total - np.asarray(want_y)).max() < TOL


def test_a_leaf_off_the_8_grid_packs_with_zero_blocks(monkeypatch):
    """Under the kernels an ssd spec's layout pads a Q40 leaf's blocks a row
    to a multiple of 8 with zero blocks (the chip stores a uint8 plane whose
    second-minor dim is off that grid with another dim there, and every
    step would copy the leaf), and the matmul pads its input to match: the
    same products. A spec whose block counts are on the grid is packed as
    it was."""
    from distributed_llama_tpu.io.loader import Q40KernelNb, Q40Weight
    from distributed_llama_tpu.ops.linear import (Q40Layout, matmul,
                                                  pack_q40_params,
                                                  q40_body_policy)
    from distributed_llama_tpu.ops.quants import quantize_q40

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    layout = q40_body_policy(tiny(dim=96), rows=2)
    assert layout.label == "nb-major-pad8" and layout.pad_blocks == 8
    on_grid = TransformerSpec(     # Phi-4-mini-flash's widths: 80, 160, 320
        dim=2560, hidden_dim=10240, n_layers=8, n_heads=40, n_kv_heads=20,
        vocab_size=200064, seq_len=8704, weights_float_type=FloatType.Q40,
        hybrid=HybridLayers(sambay_kinds(8), 512, 5120, 16, 4, 160))
    assert q40_body_policy(on_grid, rows=32) == Q40Layout(
        "nb-major", q40_body_policy(on_grid, rows=32).reason)
    rng = np.random.default_rng(2)
    w = rng.standard_normal((2, 128, 96)).astype(np.float32) * 0.1
    x = rng.standard_normal((5, 96)).astype(np.float32)
    leaf = Q40Weight(*quantize_q40(w))
    packed = pack_q40_params({"in_zx": leaf}, True, allow_nb_major=True,
                             layout=layout)["in_zx"]
    assert isinstance(packed, Q40KernelNb)
    assert packed.qs_t.shape == (2, 16, 8, 128)        # 3 blocks a row as 8
    plain = pack_q40_params({"in_zx": leaf}, True, allow_nb_major=True,
                            layout=Q40Layout("nb-major", "test"))["in_zx"]
    assert plain.qs_t.shape == (2, 16, 3, 128)
    for rows in (x[:1], x):
        got = matmul(jax.tree_util.tree_map(lambda a: jnp.asarray(a[1]),
                                            packed), jnp.asarray(rows))
        want = matmul(jax.tree_util.tree_map(lambda a: a[1], leaf),
                      jnp.asarray(rows))
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# -- the file -----------------------------------------------------------------------

_MX = MixerKinds(("full", "sliding"), 8, 16, MixerKind(6, 5e5, 8),
                 MixerKind(8, 1e4))
_LA = LatentAttn(32, 32, 16, 8, 16)
_BASE = dict(dim=64, hidden_dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
             vocab_size=128, seq_len=64)
_MOE = dict(n_experts=8, n_active_experts=2)
OLDER = [
    (0, 28, dict()),
    (2, 52, dict(_MOE)),
    (3, 72, dict(rope_theta=5e5)),
    (4, 192, dict(_MOE, latent=_LA, layout=ExpertLayout(1, 96, 1))),
    (5, 352, dict(n_layers=8, hidden_dim=128, hybrid=HybridLayers(
        sambay_kinds(8), 8, 128, 16, 4, 4))),
    (6, 232, dict(_MOE, latent=_LA, layout=ExpertLayout(1, 96, 1),
                  hyper=HyperConnections(4))),
    (7, 468, dict(_MOE, n_heads=6, layout=ExpertLayout(1, 96, 1),
                  mixers=_MX)),
    (8, 496, dict(_MOE, n_heads=6, layout=ExpertLayout(1, 96, 1),
                  mixers=dataclasses.replace(_MX, value_scale=0.707))),
    (9, 416, dict(_MOE, latent=dataclasses.replace(_LA, gate=True),
                  layout=ExpertLayout(1, 96, 1))),
]


@pytest.mark.parametrize("version,size,fields", OLDER,
                         ids=[f"v{v}" for v, _, _ in OLDER])
def test_older_headers_are_what_they_were(version, size, fields):
    """A spec of every earlier header version writes its own version at its
    own size and reads back equal: version 10 took no field of theirs."""
    spec = TransformerSpec(**{**_BASE, **fields})
    raw = spec.header()
    assert (spec.header_version, len(raw)) == (version, size)
    assert TransformerSpec.from_header(raw) == spec
    assert spec.activation == Activation() and spec.activation.gated
    assert spec.ssd is None and not (spec.slotted and version < 5)


def test_header_version_10_round_trips(tmp_path):
    from distributed_llama_tpu.io.loader import load_model, write_model
    from distributed_llama_tpu.models.synth import write_synth_q40_model

    share = tiny(held=4, offset=4)
    for spec in (SPEC, share):
        raw = spec.header()
        assert spec.header_version == 10 and len(raw) == 368
        assert TransformerSpec.from_header(raw, FloatType.Q40) == spec
    assert SPEC.slotted and SPEC.planned and SPEC.window == 0
    assert (SPEC.head_size, SPEC.kv_dim, SPEC.n_expert_layers) == (16, 32, 3)
    assert SPEC.expert_matmul_shapes() == [("moe_w1", (96, 64)),
                                           ("moe_w2", (64, 96))]
    f32 = tiny(wft=FloatType.F32)
    dense = synth_params(f32, q40=False, seed=2)
    path = str(tmp_path / "m.bin")
    write_model(path, f32, dense)
    spec2, back = load_model(path, weights_float_type=FloatType.F32)
    assert spec2 == f32
    for kind in ("mamba2", "full", "experts"):
        assert set(back[kind]) == set(dense[kind])
        for name, a in dense[kind].items():
            np.testing.assert_array_equal(back[kind][name], a)
    q40 = str(tmp_path / "q.bin")
    assert write_synth_q40_model(q40, share, seed=1) == share.file_size()
    assert load_model(q40, weights_float_type=FloatType.Q40)[0] == share


@pytest.mark.parametrize("change,match", [
    (dict(ssd=dataclasses.replace(SPEC.ssd, kinds=SPEC.ssd.kinds[:-1]
                                  + ("sliding",))), "one of"),
    (dict(ssd=dataclasses.replace(SPEC.ssd, groups=3)), "multiples of"),
    (dict(activation=Activation("relu2")), "non-gated relu2"),
    (dict(activation=Activation("polynorm", 0.5)), "non-gated relu2"),
    (dict(layout=ExpertLayout(1, 96, 1)), "no leading dense"),
    (dict(n_experts=0, n_active_experts=0, layout=ExpertLayout(),
          router=Router()), "there alone"),
    (dict(ssd=None), "set latent or ssd"),
], ids=["kind", "groups", "gated-relu2", "polynorm", "dense-layers",
        "experts-without-n", "relu2-without-ssd"])
def test_a_bad_ssd_spec_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        TransformerSpec(**{**SPEC.__dict__, **change})


def test_convert_on_seeded_tensors_of_the_published_names(tmp_path):
    """``nemotron_spec`` on the catalog's keys, and ``convert_hf`` over a
    checkpoint of seeded tensors under the published names (``in_proj`` as
    published: [z | xBC | dt] rows; ``conv1d.weight`` (channels, 1, taps)):
    the file loads back as the tree they were cut from."""
    from distributed_llama_tpu.convert import (NEMOTRON_TENSORS, convert_hf,
                                               nemotron_spec,
                                               nemotron_tensor)
    from distributed_llama_tpu.io.loader import load_model

    c = types.SimpleNamespace(
        model_type="nemotron_h", hybrid_override_pattern=PATTERN,
        num_hidden_layers=8, hidden_size=64, moe_intermediate_size=96,
        moe_shared_expert_intermediate_size=160, num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, vocab_size=256,
        mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        conv_kernel=4, chunk_size=8, n_routed_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5, norm_eps=1e-5, mlp_hidden_act="relu2",
        mamba_hidden_act="silu", n_group=1, topk_group=1, n_shared_experts=1,
        use_conv_bias=True, expand=2, intermediate_size=96)
    spec = nemotron_spec(c, FloatType.F32, 64)
    assert spec == tiny(wft=FloatType.F32)
    with pytest.raises(ValueError, match="dense-MLP"):
        nemotron_spec(types.SimpleNamespace(**{
            **c.__dict__, "hybrid_override_pattern": "ME-*EMME"}),
            FloatType.F32, 64)
    tree = synth_params(spec, q40=False, seed=5)
    state, seen = {}, dict.fromkeys(LETTERS.values(), 0)
    for layer, kind in enumerate(spec.ssd.kinds):
        at = seen[kind]
        seen[kind] += 1
        for name, w in tree[kind].items():
            key = NEMOTRON_TENSORS[name]
            if name.startswith("moe_w"):
                for e in range(8):
                    state[key.format(layer=layer, expert=e)] = w[at, e]
            elif name == "in_zx":
                state[key.format(layer=layer)] = np.concatenate(
                    [w[at], tree[kind]["in_dt"][at]])
            elif name == "conv_w":
                state[key.format(layer=layer)] = w[at].T[:, None, :]
            elif name != "in_dt":
                state[key.format(layer=layer)] = w[at]
    for name in ("tok_embedding", "rms_final", "wcls"):
        state[NEMOTRON_TENSORS[name]] = tree[name]

    class Seeded:
        def spec(self, target, seq_len):
            return spec

        def tensor_by_name(self, name, layer, spec, expert=None):
            return nemotron_tensor(state.__getitem__, name, layer, spec,
                                   expert)

    out = convert_hf("seeded", "float32", str(tmp_path / "n.bin"), 64,
                     ckpt=Seeded())
    spec2, back = load_model(out, weights_float_type=FloatType.F32)
    assert spec2 == spec
    for kind in LETTERS.values():
        for name, a in tree[kind].items():
            np.testing.assert_array_equal(back[kind][name], a)
    np.testing.assert_array_equal(back["wcls"], tree["wcls"])


def test_the_memory_model_counts_the_state():
    from distributed_llama_tpu.analysis import memory_model as mm

    assert mm.state_slot_bytes(SPEC) == 4 * 4 * (4 * 16 * 16 + 3 * 128)
    assert mm.kv_position_bytes(SPEC, 1) == 1 * 2 * 32 * 4
    with pytest.raises(ValueError, match="one chip only"):
        mm.kv_position_bytes(SPEC, 2)
    published = SsdLayers(("mamba2",), 64, 64, 8, 128, 128)
    assert 4 * (published.d_inner * 128 + 3 * published.conv_dim) == 2170880
    assert math.prod((64, 64, 128)) * 4 == 2097152
