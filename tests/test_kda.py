"""Ling-3.0-flash's layout (``TransformerSpec.kda``: Kimi-Delta-Attention
layers beside latent attention with no query rank and a head-wise gate,
DeepSeek-V3's grouped router over a SHARE of the experts, a SwiGLU clamp a
layer) at a toy size: 4 heads of 16, two periods of (kda, kda, full), one
leading dense layer, 16 experts in 4 groups of which 8 are held, nonzero
limits in the last expert layers. The KDA decode kernel (interpret mode) and
the chunk form against the recurrence, with every gate AT the lower bound
and at 0; the forward (``models/kda.py``: prefill, then decode through
state, conv rows and pages) against ``models/reference_kda.py`` on LOGITS;
the eight shares of the experts against the uncut layer; header version 11
and every older version byte for byte; ``convert.py`` on seeded tensors of
the ``bailing_hybrid`` names."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.models import kda
from distributed_llama_tpu.models import reference_kda as ref
from distributed_llama_tpu.models.spec import (
    Activation, ExpertLayout, HybridLayers, HyperConnections, KdaLayers,
    LatentAttn, MixerKind, MixerKinds, Router, SsdLayers, TransformerSpec,
    sambay_kinds)
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.ops import kda as ops
from distributed_llama_tpu.ops.quants import FloatType

# float32 sums in another order (the chunk form's products, the absorbed
# latent schedule, the kernels' five bf16 passes): logits ~N(0, 1) agree to
# 1e-5 here; products in bfloat16 read 1e-2 and more (the last test)
TOL = 5e-5
SEQ = 40
KINDS = ("kda", "kda", "full") * 2


def tiny(wft=FloatType.Q40, held=8, offset=0, dim=64, groups=4, kept=2,
         **kw):
    return TransformerSpec(**{**dict(
        dim=dim, hidden_dim=32, n_layers=6, n_heads=4, n_kv_heads=4,
        vocab_size=256, seq_len=64, weights_float_type=wft, n_experts=16,
        n_active_experts=4, rope_theta=6e6, norm_eps=1e-6,
        latent=LatentAttn(0, 32, 16, 8, 16, kinds=KINDS, head_gate=True),
        layout=ExpertLayout(1, 96, 1, held, offset),
        router=Router("sigmoid", groups, kept, True, 2.5, bias=True),
        activation=Activation(limits=True),
        kda=KdaLayers(heads=4, head_dim=16, d_conv=4)), **kw})


SPEC = tiny()


@pytest.fixture(autouse=True)
def toy_chunk(monkeypatch):
    """The forward tiles a prompt's chunk form by ``ops/kda.CHUNK`` (64):
    8 here, so that the toy's prompts cross chunk boundaries."""
    from distributed_llama_tpu.ops import kda as kda_ops

    monkeypatch.setattr(kda_ops, "CHUNK", 8)


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
    paged latent decode attention, the KDA state kernel) in interpret
    mode."""
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL"):
        monkeypatch.setenv(var, request.param)
    return request.param


# -- the delta-rule state: kernel and chunk form against the recurrence ----------

def _draw(rng, t, gate=None, heads=4, d=16):
    q, k = (rng.standard_normal((t, heads, d)).astype(np.float32)
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((t, heads, d)).astype(np.float32)
    g = (-5.0 * rng.random((t, heads, d)) if gate is None
         else np.full((t, heads, d), gate)).astype(np.float32)
    return q, k, v, g, rng.random((t, heads)).astype(np.float32)


def _recurrence(s, q, k, v, g, b):
    """float64, a position at a time: (o (T, H, D), the state after)."""
    s = np.asarray(s, np.float64).copy()
    out = []
    for t in range(len(q)):
        s = np.exp(g[t].astype(np.float64))[..., None] * s
        u = np.einsum("hkv,hk->hv", s, k[t])
        s = s + (b[t][:, None] * k[t])[..., None] * (v[t] - u)[:, None, :]
        out.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.stack(out), s


@pytest.mark.parametrize("steps", [1, 6])
def test_decode_kernel_against_the_recurrence(steps):
    """Three rows of layer 1 of a two-layer stack: row 0 rides, row 1 takes
    no part (its state is left bit for bit), row 2 is at its sequence's
    first position (its state, whatever it holds, is emptied first). Layer
    0's rows are not touched. The kernel and ``recur_step`` alike."""
    rng = np.random.default_rng(steps)
    B = 3
    all0 = rng.standard_normal((2 * B, 4, 16, 16)).astype(np.float32)
    state = {True: jnp.asarray(all0), False: jnp.asarray(all0)}
    live = jnp.asarray([True, False, True])
    want_s = [all0[B + 0], None, np.zeros_like(all0[0])]
    for step in range(steps):
        draws = [_draw(rng, 1) for _ in range(B)]
        args = [jnp.asarray(np.concatenate([d[i] for d in draws]))
                for i in range(5)]
        fresh = jnp.asarray([False, False, step == 0])
        want_o = {}
        for r in (0, 2):
            want_o[r], want_s[r] = _recurrence(want_s[r], *draws[r])
        for kernel in (True, False):
            o, state[kernel] = ops.scan_decode(
                jnp.int32(1), state[kernel], *args, fresh, live,
                kernel=kernel)
            for r in (0, 2):
                np.testing.assert_allclose(np.asarray(o[r]), want_o[r][0],
                                           rtol=2e-5, atol=2e-5)
    for kernel in (True, False):
        got = np.asarray(state[kernel])
        np.testing.assert_array_equal(got[:B], all0[:B])
        np.testing.assert_array_equal(got[B + 1], all0[B + 1])
        for r in (0, 2):
            np.testing.assert_allclose(got[B + r], want_s[r], rtol=2e-5,
                                       atol=2e-5)


@pytest.mark.parametrize("cuts,chunk,gate", [
    ((128,), 64, -5.0),         # EVERY gate at the lower bound, 128 positions
    ((128,), 64, 0.0),          # ... and at 0: nothing is ever forgotten
    ((21,), 8, None),           # two whole chunks and a ragged one
    ((16, 21, 37), 8, None),    # admission chunks: whole, ragged, two and a bit
    ((5,), 8, None),            # shorter than a chunk
    ((8, 9, 24), 8, None),      # a one-position admission between whole ones
    ((30,), 64, None),          # the published chunk, one part-filled
], ids=["bound128", "zero128", "ragged", "admissions", "short", "single",
        "chunk64"])
def test_chunk_form_equals_the_recurrence(cuts, chunk, gate):
    """The chunk form over admission chunks that end at ``cuts``, the state
    handed from one to the next, against the recurrence in float64: o at
    every position and the state at the end, finite everywhere; a padded
    tail (g 0, b 0) changes neither. At the bound the running sum of g
    reaches -640 inside the second chunk... no: -320 a chunk of 64, where
    exp(+320) is not a float32: a form that divides by exp(G_j) gives inf
    or nan here."""
    rng = np.random.default_rng(len(cuts) + chunk)
    total = cuts[-1]
    q, k, v, g, b = _draw(rng, total, gate)
    s0 = rng.standard_normal((4, 16, 16)).astype(np.float32)
    want_o, want_s = _recurrence(s0, q, k, v, g, b)
    s, lo, outs = jnp.asarray(s0), 0, []
    for hi in cuts:
        pad = -(hi - lo) % 4        # a chunk's padding: positions with b 0
        part = [np.concatenate([x[lo:hi], np.zeros((pad, *x.shape[1:]),
                                                   np.float32)])
                for x in (q, k, v, g, b)]
        o, s = ops.kda_chunk(s, *map(jnp.asarray, part), chunk)
        assert np.isfinite(np.asarray(o)).all()
        outs.append(np.asarray(o)[:hi - lo])
        lo = hi
    np.testing.assert_allclose(np.concatenate(outs), want_o, rtol=1e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-4, atol=2e-5)


def test_the_solve_stands_keys_that_all_point_one_way():
    """64 positions whose keys are ONE direction, written at full strength
    with no decay: I + Diag(b) M is all ones under its diagonal, whose
    inverse's Neumann series has terms of 1e17 that cancel; forward
    substitution gives the recurrence's numbers."""
    rng = np.random.default_rng(3)
    q, k, v, g, b = _draw(rng, 64, 0.0)
    k[:] = k[0]
    b[:] = 0.999
    s0 = np.zeros((4, 16, 16), np.float32)
    want_o, want_s = _recurrence(s0, q, k, v, g, b)
    o, s = ops.kda_chunk(jnp.asarray(s0), *map(jnp.asarray, (q, k, v, g, b)))
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-4, atol=2e-5)


# -- the forward against the reference ------------------------------------------

def test_the_list_is_walked_as_scans():
    from distributed_llama_tpu.models import kindscan

    segs = kindscan.segments(kda.layer_stacks(SPEC))
    assert sum(len(u) * r for _, u, r in segs) == 6
    published = tuple("full" if (i + 1) % 6 == 0 else "kda"
                      for i in range(24))
    spec = tiny(n_layers=24, latent=dataclasses.replace(
        SPEC.latent, kinds=published))
    segs = kindscan.segments(kda.layer_stacks(spec))
    assert sum(len(u) * r for _, u, r in segs) == 24 and len(segs) <= 4
    assert (published.count("kda"), published.count("full")) == (20, 4)


def _params(tree, spec=SPEC):
    from distributed_llama_tpu.models.llama import params_to_device
    from distributed_llama_tpu.ops.linear import q40_body_policy

    return params_to_device(tree, layout=q40_body_policy(spec, rows=2),
                            spec=spec)


@pytest.mark.parametrize("mode,paged", [
    ("xla", False), ("xla", True), ("pallas", True)],
    ids=["xla-rows", "xla-pages", "pallas-pages"])
def test_prefill_then_decode_on_logits(mode, paged, tree, tokens, want,
                                       monkeypatch):
    """Chunks of 8 (two KDA chunks' worth in the first admission, a ragged
    one after), then decode: through the contiguous cache, or from a row of
    the paged cache whose state, conv rows and pages another sequence
    held. The toy's last expert layers clamp (``ffn_limit`` 0.5 / 0.75),
    its latent layers have no query rank and gate a head. In XLA, and with
    every kernel in interpret mode through the pages (the contiguous
    one-row step under the kernels is ``tests/test_ling_serve.py``'s
    ``inference`` engine)."""
    for var in ("DLLAMA_Q40_KERNEL", "DLLAMA_ATTN_KERNEL"):
        monkeypatch.setenv(var, mode)
    kernel_mode = mode
    params = _params(tree)
    n_pre, n = 21, (26 if kernel_mode == "pallas" else 34)
    cache = kda.init_cache(SPEC)
    chunk = jax.jit(lambda p, c, t, pos, nv: kda.forward_chunk(
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
        one = jax.jit(lambda p, c, t, pos: kda.forward_chunk(
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
    pool = kda.init_cache_paged(SPEC, slots, 1 + 2 * SPEC.seq_len // ps, ps)
    pool = jax.tree_util.tree_map(lambda a: a + 7.0, pool)   # another's
    table = np.zeros((slots, SPEC.seq_len // ps), np.int32)
    table[1, :5] = [9, 3, 12, 5, 7]
    pool = kda.insert_sequence(pool, cache, 1, jnp.asarray(table[1]), ps)
    step = jax.jit(lambda p, c, t, pos, tb, act: kda.forward_batch(
        SPEC, p, c, t, pos, tb, act, page_size=ps, health=True,
        moe_counts=True))
    for pos in range(n_pre, n):
        toks = jnp.asarray([0, tokens[pos]])
        logits, pool, health, counts = step(
            params, pool, toks, jnp.asarray([0, pos]), jnp.asarray(table),
            jnp.asarray([0, 1]))
        worst = max(worst, np.abs(np.asarray(logits)[1] - want[pos]).max())
        lo, mean, decay = (float(x) for x in health)
        assert 0.0 < lo <= mean < 1.0 and np.exp(-5.0) < decay <= 1.0
        assert counts.shape == (5, 16) and int(counts.sum()) == 5 * 2 * 4
    assert worst < TOL


def test_a_reused_row_finds_its_state_empty(tree, tokens, want):
    """Position 0 on a row that holds another sequence's state and conv
    rows reads what an empty row reads; a row that takes no part keeps its
    state bit for bit."""
    params = _params(tree)
    ps = 8
    cache = jax.tree_util.tree_map(
        lambda a: a + 3.0, kda.init_cache_paged(SPEC, 2, 9, ps))
    table = jnp.asarray([[1] + [0] * 7, [2] + [0] * 7], jnp.int32)
    logits, out = kda.forward_batch(
        SPEC, params, cache, jnp.asarray([tokens[0], tokens[0]]),
        jnp.asarray([0, 0]), table, jnp.asarray([1, 0]), page_size=ps)
    assert np.abs(np.asarray(logits)[0] - want[0]).max() < TOL
    np.testing.assert_array_equal(np.asarray(out.s)[:, 1],
                                  np.asarray(cache.s)[:, 1])
    np.testing.assert_array_equal(np.asarray(out.conv)[:, 1],
                                  np.asarray(cache.conv)[:, 1])
    assert kda.state_bytes(cache) == (
        2 * 4 * 4 * (4 * 16 * 16 + 3 * 3 * 64), 0)


def test_the_eight_shares_add_up_to_the_uncut_layer(kernel_mode):
    """The program's expert layer on each EIGHTH of the experts (held 2 at
    offsets 0, 2 .. 14: one routing group a chip; the router keeps its 16
    outputs, its bias, its 8 groups of which 4 are kept and 4 a token): the
    eight routed parts plus the shared expert ONCE are the reference's
    uncut layer, in a layer whose clamp bites. Under the kernels at widths
    of 256 (8 blocks a row: on the grid the published widths are on), where
    the slot kernel takes the stacks."""
    from distributed_llama_tpu.models.kindscan import is_packed
    from distributed_llama_tpu.models.llama import _swiglu
    from distributed_llama_tpu.ops.linear import StackedQ40
    from distributed_llama_tpu.ops.pallas_moe import moe_ffn

    packed = kernel_mode == "pallas"
    whole_spec = tiny(held=0, groups=8, kept=4, **(
        dict(dim=256, hidden_dim=256) if packed else {}))
    tree = synth_params(whole_spec, q40=True, seed=1)
    x = np.random.default_rng(4).standard_normal(
        (6, whole_spec.dim)).astype(np.float32) * 3
    layer = 4                       # the last expert layer: limits 0.5, 0.75
    whole = ref._layer_of(tree, layer)
    assert tuple(whole["ffn_limit"]) == (0.5, 0.75)
    want_y, _, ids = ref.experts_out(whole_spec, whole, jnp.asarray(x))
    free = dict(whole, ffn_limit=np.zeros(2, np.float32))
    assert np.abs(np.asarray(ref.experts_out(whole_spec, free,
                                             jnp.asarray(x))[0])
                  - np.asarray(want_y)).max() > 1e-2     # the clamp bites
    h = ref._rmsnorm(jnp.asarray(x), whole["rms_ffn"], whole_spec.norm_eps)
    total, landed = 0.0, 0
    for offset in range(0, 16, 2):
        spec = dataclasses.replace(
            whole_spec, layout=ExpertLayout(1, 96, 1, 2, offset))
        part = {k: dict(v) if isinstance(v, dict) else v
                for k, v in tree.items()}
        for name in ("moe_w1", "moe_w2", "moe_w3"):
            w = tree[name]
            part[name] = type(w)(*(a[:, offset:offset + 2] for a in w))
        stack = {k: v for k, v in _params(part, spec).items()
                 if k not in kda.TOP_LEVEL and not isinstance(v, dict)}
        if packed:
            assert is_packed(stack["moe_w13"]) and is_packed(stack["moe_w2"])
        lw = {k: StackedQ40(v, jnp.int32(layer)) if is_packed(v)
              else jax.tree_util.tree_map(lambda a: a[layer], v)
              for k, v in stack.items()}
        y, counts = moe_ffn(spec, lw, h)
        total = total + np.asarray(y)
        landed += int(np.asarray(counts)[spec.held_columns].sum())
        assert int(np.asarray(counts).sum()) == ids.size
        if offset == 0:     # what every chip computes alike, counted once
            total = total + np.asarray(_swiglu(spec, lw, h, "sh_"))
    assert landed == ids.size
    assert np.abs(total - np.asarray(want_y)).max() < TOL


def test_the_reference_reads_its_switches(tree, tokens, want):
    """The head-wise gate, the clamp and the decay's lower bound each move
    the reference's logits: none of them is a leaf nobody reads."""
    def moved(spec=SPEC, **leaves):
        t = {k: dict(v) if isinstance(v, dict) else v
             for k, v in tree.items()}
        for path, value in leaves.items():
            stack, _, name = path.rpartition("/")
            (t[stack] if stack else t)[name] = value
        return np.abs(ref.forward(t, spec, tokens)[0] - want).max()

    assert moved(**{"full/w_hgate": tree["full"]["w_hgate"] * 0}) > 1e-3
    assert moved(ffn_limit=tree["ffn_limit"] * 0) > 1e-3
    assert moved(dataclasses.replace(SPEC, kda=dataclasses.replace(
        SPEC.kda, lower_bound=-1.0))) > 1e-3


def test_bfloat16_products_fail_the_tolerance(tree, tokens, want):
    """The program with every matrix product in ONE bfloat16 pass
    (``ops/linear.bf16_prefill``, fast-prefill's mode) lies two hundred
    times the tolerance from the reference: the comparison is tight enough
    to tell a precision down."""
    from distributed_llama_tpu.ops.linear import bf16_prefill

    low = jax.jit(bf16_prefill(lambda p, c, t: kda.forward_chunk(
        SPEC, p, c, t, jnp.int32(0))))
    logits, _ = low(_params(tree), kda.init_cache(SPEC),
                    jnp.asarray(tokens[:24]))
    assert np.abs(np.asarray(logits) - want[:24]).max() > 100 * TOL


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
    (9, 416, dict(_MOE, latent=dataclasses.replace(
        _LA, gate=True, kinds=("full", "sliding"), window=8),
        layout=ExpertLayout(1, 96, 1))),
    (10, 368, dict(_MOE, layout=ExpertLayout(0, 0, 1), ssd=SsdLayers(
        ("mamba2", "experts"), 4, 16, 2, 16, 16, shared_hidden=160),
        activation=Activation("relu2", gated=False))),
]


@pytest.mark.parametrize("version,size,fields", OLDER,
                         ids=[f"v{v}" for v, _, _ in OLDER])
def test_older_headers_are_what_they_were(version, size, fields):
    """A spec of every earlier header version writes its own version at its
    own size, byte for byte what the version's own struct packs, and reads
    back equal: version 11 took no field of theirs (a version-9 header's
    kind bytes still index "full" and "sliding" as 0 and 1)."""
    import struct

    from distributed_llama_tpu.models import spec as sp

    spec = TransformerSpec(**{**_BASE, **fields})
    raw = spec.header()
    assert (spec.header_version, len(raw)) == (version, size)
    assert TransformerSpec.from_header(raw) == spec
    assert spec.kda is None and not spec.activation.limits
    assert not (spec.latent and spec.latent.head_gate)
    if version:
        layout = getattr(sp, "EXT_STRUCT" if version == 2
                         else f"EXT{version}_STRUCT")
        magic, ver, count, *vals = layout.unpack(raw)
        assert (magic, ver) == (sp.EXT_MAGIC, version)
        assert layout.pack(magic, ver, count, *vals) == raw
        assert vals[:7] == [spec.dim, spec.hidden_dim, spec.n_layers,
                            spec.n_heads, spec.n_kv_heads, spec.vocab_size,
                            spec.seq_len]
    else:
        assert raw == struct.pack("<7i", 64, 32, 2, 4, 2, 128, 64)
    if version == 9:        # the kind bytes: full 0, sliding 1, then 255
        assert raw[-128:-125] == bytes([0, 1, 255])


def test_header_version_11_round_trips(tmp_path):
    from distributed_llama_tpu.io.loader import load_model, write_model
    from distributed_llama_tpu.models.synth import write_synth_q40_model

    share = tiny(held=2, offset=6, groups=8, kept=4)
    for spec in (SPEC, share):
        raw = spec.header()
        assert spec.header_version == 11 and len(raw) == 456
        assert TransformerSpec.from_header(raw, FloatType.Q40) == spec
        assert raw[-40 - 128:-40 - 128 + 7] == bytes([2, 2, 0, 2, 2, 0, 255])
    assert SPEC.slotted and SPEC.planned and SPEC.window == 0
    assert (SPEC.head_size, SPEC.n_expert_layers) == (24, 5)
    assert SPEC.attn_matmul_shapes()[0] == ("wq", (4 * 24, 64))
    f32 = tiny(wft=FloatType.F32)
    dense = synth_params(f32, q40=False, seed=2)
    path = str(tmp_path / "m.bin")
    write_model(path, f32, dense)
    spec2, back = load_model(path, weights_float_type=FloatType.F32)
    assert spec2 == f32
    for stack in ("kda", "full", "dense"):
        assert set(back[stack]) == set(dense[stack])
        for name, a in dense[stack].items():
            np.testing.assert_array_equal(back[stack][name], a)
    np.testing.assert_array_equal(back["ffn_limit"], dense["ffn_limit"])
    assert dense["ffn_limit"][:3].max() == 0 and tuple(
        dense["ffn_limit"][-1]) == (0.5, 0.75)
    q40 = str(tmp_path / "q.bin")
    assert write_synth_q40_model(q40, share, seed=1) == share.file_size()
    assert load_model(q40, weights_float_type=FloatType.Q40)[0] == share


@pytest.mark.parametrize("change,match", [
    (dict(latent=dataclasses.replace(SPEC.latent, kinds=KINDS[:-1]
                                     + ("sliding",))), "some of each"),
    (dict(latent=dataclasses.replace(SPEC.latent, kinds=("kda",) * 6)),
     "some of each"),
    (dict(kda=dataclasses.replace(SPEC.kda, lower_bound=0.0)),
     "negative lower bound"),
    (dict(activation=Activation("polynorm", 0.5, limits=True)),
     "gated SiLU"),
    (dict(kda=None), "header version 11's"),
    (dict(hyper=HyperConnections(4)), "one residual stream"),
], ids=["sliding", "no-latent-layer", "bound", "polynorm", "kinds-without-kda",
        "streams"])
def test_a_bad_kda_spec_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        TransformerSpec(**{**SPEC.__dict__, **change})


def test_convert_on_seeded_tensors_of_the_published_names(tmp_path):
    """``ling_spec`` on the catalog's keys, and ``convert_hf`` over a
    checkpoint of seeded tensors under the ``bailing_hybrid`` names (a KDA
    layer's five projections apart, its three ``conv1d.weight`` (channels,
    1, taps); the limits in the CONFIG): the file loads back as the tree
    they were cut from."""
    from distributed_llama_tpu.convert import (LING_TENSORS, convert_hf,
                                               ling_spec, ling_tensor)
    from distributed_llama_tpu.io.loader import load_model

    c = types.SimpleNamespace(
        model_type="bailing_hybrid", layer_group_size=3,
        num_hidden_layers=6, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        vocab_size=256, first_k_dense_replace=1, num_experts=16,
        num_experts_per_tok=4, num_shared_experts=1, n_group=4, topk_group=2,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        score_function="sigmoid", moe_router_enable_expert_bias=True,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, q_lora_rank=None, rope_theta=6e6, rms_norm_eps=1e-6,
        rope_scaling=None, rope_interleave=True, short_conv_kernel_size=4,
        kda_lower_bound=-5, kda_safe_gate=True, no_kda_lora=True,
        use_kda_lora=False, linear_silu=True, use_qk_norm=True,
        group_norm_size=1, hidden_act="silu",
        gated_attention_proj_granularity_type="head_wise",
        expert_swiglu_limit_list=[0, 0, 0, 0, 0.5, 0.5],
        share_expert_swiglu_limit_list=[0, 0, 0, 0, 0.75, 0.75])
    spec = ling_spec(c, FloatType.F32, 64)
    assert spec == tiny(wft=FloatType.F32, held=0, kda=KdaLayers(4, 16, 4))
    with pytest.raises(ValueError, match="no query rank"):
        ling_spec(types.SimpleNamespace(**{**c.__dict__, "q_lora_rank": 32}),
                  FloatType.F32, 64)
    tree = synth_params(spec, q40=False, seed=5)
    tree["ffn_limit"] = np.asarray(
        [[0, 0]] * 3 + [[0.5, 0.75]] * 2, np.float32)
    state = {}
    plans = iter(spec.layer_plans())
    for layer in range(6):
        for stack, at, entries in (next(plans), next(plans)):
            src = tree[stack] if stack else tree
            for _, name, _, *e in entries:
                key = LING_TENSORS.get(name)
                w = src[name][(at, *e)]
                if name == "ffn_limit":
                    continue
                if name == "in_qkvag":
                    for k, part in zip(key, np.split(w, 5)):
                        state[k.format(layer=layer)] = part
                elif name == "conv_w":
                    for k, part in zip(key, np.split(w, 3, axis=1)):
                        state[k.format(layer=layer)] = part.T[:, None, :]
                else:
                    state[key.format(layer=layer,
                                     expert=e[0] if e else None)] = w
    for name in ("tok_embedding", "rms_final", "wcls"):
        state[LING_TENSORS[name]] = tree[name]
    limits = (c.expert_swiglu_limit_list, c.share_expert_swiglu_limit_list)

    class Seeded:
        def spec(self, target, seq_len):
            return spec

        def tensor_by_name(self, name, layer, spec, expert=None):
            return ling_tensor(state.__getitem__, name, layer, spec, expert,
                               limits)

    out = convert_hf("seeded", "float32", str(tmp_path / "l.bin"), 64,
                     ckpt=Seeded())
    spec2, back = load_model(out, weights_float_type=FloatType.F32)
    assert spec2 == spec
    for stack in ("kda", "full", "dense"):
        for name, a in tree[stack].items():
            np.testing.assert_array_equal(back[stack][name], a)
    for name in ("ffn_limit", "moe_gate", "moe_w3", "sh_w2", "wcls"):
        np.testing.assert_array_equal(back[name], tree[name])


def test_the_memory_model_counts_the_state():
    from distributed_llama_tpu.analysis import memory_model as mm

    assert mm.state_slot_bytes(SPEC) == 4 * 4 * (4 * 16 * 16 + 3 * 3 * 64)
    assert mm.kv_position_bytes(SPEC, 1) == 2 * 128 * 4
    published = KdaLayers(32, 128)
    assert 4 * (published.width * 128 + 3 * 3 * published.width) == 2244608
    assert 4 * 32 * 128 * 128 == 2097152        # the 2 MiB state alone
