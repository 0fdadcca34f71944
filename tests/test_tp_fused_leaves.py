"""A tensor-parallel tree is fused a rank (ISSUE 56): ``shard_params`` lays
``wqkv`` / ``w13`` RANK-MAJOR, so rank r's contiguous band is ``[q_r | k_r |
v_r]`` (``[w1_r | w3_r]``), assembled in the one host copy a shard gets.
What is placed, what the sharded forward gives on it against the members
placed unfused, which groups stay as they are, and the whole-leaf concat's
refusal. Host-device mesh, interpret-mode kernels."""

from __future__ import annotations

import numpy as np
import pytest

from distributed_llama_tpu.io.loader import Q40Kernel, Q40KernelNb

GROUPS = {"wqkv": ("wq", "wk", "wv"), "w13": ("w1", "w3")}
# output rows of the members: every tp-4 band a multiple of 128
ROWS = {"wq": 1024, "wk": 512, "wv": 512, "w1": 1536, "w3": 1536}
LAYERS, NB = 2, 8


def _kernel_tree(layout: str, rows: dict = ROWS, seed: int = 56) -> dict:
    """Stacked kernel leaves of seeded bytes, as ``pack_q40_params`` leaves
    them: nb-major ``qs_t`` (L, 16, nb, d) / ``scale`` (L, nb, d), d-major
    (L, 16, d, nb) / (L, d, nb)."""
    rng = np.random.default_rng(seed)
    tree = {}
    for name, d in rows.items():
        qs_t = rng.integers(0, 256, (LAYERS, 16, NB, d), dtype=np.uint8)
        scale = rng.random((LAYERS, NB, d), dtype=np.float32)
        tree[name] = (Q40KernelNb(qs_t, scale) if layout == "nb-major" else
                      Q40Kernel(np.ascontiguousarray(qs_t.swapaxes(-1, -2)),
                                np.ascontiguousarray(scale.swapaxes(-1, -2))))
    return tree


def _mesh(tp: int):
    import jax

    from distributed_llama_tpu.parallel import make_mesh

    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    return make_mesh(tp=tp, devices=jax.devices()[:tp])


@pytest.mark.parametrize("threaded", [False, True], ids=["plain", "threaded"])
@pytest.mark.parametrize("layout", ["nb-major", "d-major"])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_placed_shard_is_the_ranks_bands_of_the_members(tp, layout, threaded,
                                                        monkeypatch):
    """Rank r's shard of the placed ``wqkv`` / ``w13`` is the concat of rank
    r's bands of the members, also where the copy goes to the thread pool;
    with one rank it is ``fuse_q40_layer_matmuls``'s plain concat, byte for
    byte."""
    from distributed_llama_tpu.ops.linear import fuse_q40_layer_matmuls
    from distributed_llama_tpu.parallel import shard_params, tp as tp_mod

    if threaded:
        monkeypatch.setattr(tp_mod, "_CUT_THREAD_BYTES", 0)
    tree = _kernel_tree(layout)
    placed = shard_params(dict(tree), _mesh(tp), scheme="ref")
    assert sorted(placed) == ["w13", "wqkv"]
    axis = -1 if layout == "nb-major" else -2
    for fused, keys in GROUPS.items():
        assert isinstance(placed[fused], type(tree[keys[0]]))
        for plane in ("qs_t", "scale"):
            got = getattr(placed[fused], plane)
            members = [getattr(tree[k], plane) for k in keys]
            band = sum(m.shape[axis] for m in members) // tp
            assert got.shape[axis] == band * tp
            assert len(got.addressable_shards) == tp
            for shard in got.addressable_shards:
                r = (shard.index[axis].start or 0) // band
                want = np.concatenate(
                    [np.take(m, range(r * m.shape[axis] // tp,
                                      (r + 1) * m.shape[axis] // tp), axis)
                     for m in members], axis)
                np.testing.assert_array_equal(np.asarray(shard.data), want)
    if tp == 1:
        today = fuse_q40_layer_matmuls(dict(tree))
        for fused, keys in GROUPS.items():
            for plane in ("qs_t", "scale"):
                want = np.concatenate([getattr(tree[k], plane) for k in keys],
                                      axis)
                got = getattr(today[fused], plane)
                assert isinstance(got, np.ndarray)
                np.testing.assert_array_equal(got, want)
                np.testing.assert_array_equal(
                    np.asarray(getattr(placed[fused], plane)), want)


FWD_SPEC = dict(dim=512, hidden_dim=1024, n_layers=1, n_heads=4, n_kv_heads=4,
                vocab_size=256, seq_len=32)


@pytest.mark.parametrize("t", [1, 16], ids=["step", "chunk"])
@pytest.mark.parametrize("buffers", ["f32", "q80"])
@pytest.mark.parametrize("scheme", ["ref", "fused"])
def test_tp4_forward_on_fused_leaves_is_the_unfused_forward(scheme, buffers,
                                                            t, monkeypatch,
                                                            capfd):
    """The sharded forward on the fused placement (4 Q40 calls a layer)
    gives the logits of the same forward on the members placed unfused (7),
    bit for bit: the fused call computes the same rows from the same codes
    and scales against the same input."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import init_cache
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.models.synth import synth_params
    from distributed_llama_tpu.ops.linear import pack_q40_params
    from distributed_llama_tpu.ops.quants import FloatType
    from distributed_llama_tpu.parallel import (make_sharded_forward,
                                                shard_cache, shard_params)
    from distributed_llama_tpu.parallel.tp import (FUSED_INPUT_SHARDED,
                                                   place_params)

    mesh = _mesh(4)
    spec = TransformerSpec(
        **FWD_SPEC, weights_float_type=FloatType.Q40,
        buffer_float_type=FloatType.Q80 if buffers == "q80"
        else FloatType.F32)
    params = synth_params(spec, q40=True, seed=56, scale=0.2)
    tokens = jnp.asarray(np.arange(3, 3 + t), dtype=jnp.int32)
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "xla")

    capfd.readouterr()
    fused = shard_params(params, mesh, scheme=scheme)
    note = capfd.readouterr().err
    assert "fused: wqkv w13; 4 Q40 calls a layer" in note
    assert isinstance(fused["wqkv"], Q40KernelNb) and "wq" not in fused
    assert fused["wqkv"].qs_t.sharding.shard_shape(
        fused["wqkv"].qs_t.shape) == (1, 16, 16, 384)
    assert fused["w13"].scale.sharding.shard_shape(
        fused["w13"].scale.shape) == (1, 16, 512)
    members = place_params(
        pack_q40_params(params, tp=4, input_sharded=(
            FUSED_INPUT_SHARDED if scheme == "fused" else ())),
        mesh, scheme)
    assert isinstance(members["wq"], Q40KernelNb) and "wqkv" not in members

    fwd = make_sharded_forward(spec, mesh, scheme=scheme)
    got, _ = fwd(fused, shard_cache(init_cache(spec), mesh), tokens,
                 jnp.int32(0))
    want, _ = fwd(members, shard_cache(init_cache(spec), mesh), tokens,
                  jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# (layout, members' rows, blocks a row, ranks) -> fused or not, and why
RULE = {
    "nb-major-tp4": ("nb-major", (1024, 512, 512), 8, 4, True),
    "d-major-one-rank": ("d-major", (64, 32, 32), 4, 1, True),
    # a shard-local width with no one-row tile: 1,500 rows have no
    # multiple-of-8 divisor and do not fit one block
    "no-one-row-tile": ("d-major", (500, 500, 500), 4, 1, False),
    "no-one-row-tile-a-rank": ("d-major", (1000, 1000, 1000), 4, 2, False),
    # nb-major rows ride the lanes: 3 x 64 local rows are off the 128 grid
    "nb-major-off-the-lanes": ("nb-major", (256, 256, 256), 8, 4, False),
    # 864 rows tile at one row (432) and not at a chunk's 128, where each
    # member (288 rows, one whole block) does: the fused leaf would fall to
    # dequantize-then-dot where its members do not
    "chunk-tile-lost": ("d-major", (288, 288, 288), 9, 1, False),
    # ... while 1,376 = 11,008 / 8 has no chunk tile as a member either
    "chunk-tile-never-had": ("d-major", (11008, 11008), 4, 8, True),
    "rows-do-not-divide": ("nb-major", (1024, 258, 258), 8, 4, False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_fuse_rule_reads_the_shard_local_width(case):
    from distributed_llama_tpu.ops.linear import (RankMajor,
                                                  fuse_q40_layer_matmuls)

    layout, rows, nb, ranks, fuses = RULE[case]
    keys = GROUPS["wqkv" if len(rows) == 3 else "w13"]
    kind = Q40KernelNb if layout == "nb-major" else Q40Kernel
    tree = {}
    for k, d in zip(keys, rows):
        shape = (1, nb, d) if kind is Q40KernelNb else (1, d, nb)
        tree[k] = kind(np.zeros((1, 16) + shape[1:], np.uint8),
                       np.zeros(shape, np.float32))
    out = fuse_q40_layer_matmuls(dict(tree), ranks=ranks)
    if not fuses:
        assert out.keys() == tree.keys()
        assert all(out[k] is tree[k] for k in tree)
        return
    (name,) = out
    assert name == ("wqkv" if len(rows) == 3 else "w13")
    assert isinstance(out[name], kind)
    axis = -1 if kind is Q40KernelNb else -2
    assert out[name].scale.shape[axis] == sum(rows)
    assert isinstance(out[name].qs_t, np.ndarray if ranks == 1 else RankMajor)


@pytest.mark.parametrize("t", [1, 16], ids=["step", "chunk"])
def test_mixed_layouts_stay_unfused_and_right(t, monkeypatch, capfd):
    """kv bands of 64 rows are off the nb-major lanes and pack d-major
    beside an nb-major ``wq``: the group stays three leaves, ``w13`` still
    fuses, and the forward equals the one-device XLA forward."""
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                    params_to_device)
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.models.synth import synth_params
    from distributed_llama_tpu.ops.quants import FloatType
    from distributed_llama_tpu.parallel import (make_sharded_forward,
                                                shard_cache, shard_params)

    mesh = _mesh(4)
    spec = TransformerSpec(**dict(FWD_SPEC, n_heads=8),
                           weights_float_type=FloatType.Q40)
    params = synth_params(spec, q40=True, seed=57, scale=0.2)
    tokens = jnp.asarray(np.arange(3, 3 + t), dtype=jnp.int32)
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "xla")
    want, _ = forward(spec, params_to_device(params), init_cache(spec),
                      tokens, jnp.int32(0))
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "xla")
    capfd.readouterr()
    placed = shard_params(params, mesh, scheme="fused")
    assert "fused: w13; 6 Q40 calls a layer" in capfd.readouterr().err
    assert isinstance(placed["wq"], Q40KernelNb)
    assert isinstance(placed["wk"], Q40Kernel)
    assert isinstance(placed["w13"], Q40KernelNb) and "wqkv" not in placed
    fwd = make_sharded_forward(spec, mesh, scheme="fused")
    got, _ = fwd(placed, shard_cache(init_cache(spec), mesh), tokens,
                 jnp.int32(0))
    tol = dict(rtol=2e-5, atol=2e-5) if t == 1 else dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_a_tree_fused_over_the_whole_leaf_is_still_refused(group, tp):
    """A plain whole-leaf concat (what one chip holds) cut in contiguous tp
    bands would hand rank 0 only q rows: ``shard_params`` raises."""
    from distributed_llama_tpu.ops.linear import fuse_q40_layer_matmuls
    from distributed_llama_tpu.parallel import shard_params

    tree = _kernel_tree("nb-major", {k: ROWS[k] for k in GROUPS[group]})
    whole = fuse_q40_layer_matmuls(tree)
    assert list(whole) == [group]
    with pytest.raises(ValueError, match=f"{group}: a tree fused over the "
                                         f"WHOLE leaf"):
        shard_params(whole, _mesh(tp), scheme="fused")


def test_rank_major_plane_is_cut_a_band_at_a_time():
    from distributed_llama_tpu.ops.linear import RankMajor

    parts = [np.arange(2 * 8 * d, dtype=np.float32).reshape(2, 8, d)
             for d in (8, 4)]
    plane = RankMajor(parts, -1, 4)
    assert plane.shape == (2, 8, 12) and plane.band_shape == (2, 8, 3)
    full = slice(None)
    assert plane.rank_of((full, full, slice(6, 9))) == 2
    np.testing.assert_array_equal(
        plane.band(2), np.concatenate([parts[0][..., 4:6],
                                       parts[1][..., 2:3]], -1))
    with pytest.raises(ValueError, match="a rank's band at a time"):
        plane.rank_of((full, full, slice(0, 6)))
    with pytest.raises(ValueError, match="do not fuse"):
        RankMajor(parts, -1, 3)
