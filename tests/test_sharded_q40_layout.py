"""The pack-time layout rule of SHARDED Q40 trees (ISSUE 25): a leaf whose
shard-local block count is off the 128 grid packs nb-major (the order the
chip stores it in, so no step program copies it), and the spec table shards
the same logical axis through the transpose. Decision logic on abstract
shapes, then the sharded forward on the host-device mesh (interpret-mode
kernels) against the one-device XLA forward."""

from __future__ import annotations

import numpy as np
import pytest

from distributed_llama_tpu.io.loader import Q40Kernel, Q40KernelNb, Q40Weight
from distributed_llama_tpu.parallel.tp import FUSED_INPUT_SHARDED as FUSED

# (d, n) of Yi-34B's eight matmul leaves (benchmark/configs/yi-34b-q40-tp4)
YI = {"wq": (7168, 7168), "wk": (1024, 7168), "wv": (1024, 7168),
      "wo": (7168, 7168), "w1": (20480, 7168), "w2": (7168, 20480),
      "w3": (20480, 7168), "wcls": (64000, 7168)}

# ... of Mistral-7B's and Brumby-14B's (benchmark/configs/)
MISTRAL = {"wq": (4096, 4096), "wk": (1024, 4096), "wv": (1024, 4096),
           "wo": (4096, 4096), "w1": (14336, 4096), "w2": (4096, 14336),
           "w3": (14336, 4096), "wcls": (32000, 4096)}
BRUMBY = {"wq": (5120, 5120), "wk": (1024, 5120), "wv": (1024, 5120),
          "wo": (5120, 5120), "w1": (17408, 5120), "w2": (5120, 17408),
          "w3": (17408, 5120)}


def _abstract_pick(shapes: dict, **kw) -> dict:
    """Leaf kinds ``pack_q40_params`` picks for (d, n) shapes, with no
    weight in memory: the re-tilers are traced, not run."""
    import jax

    from distributed_llama_tpu.ops.linear import pack_q40_params

    tree = {k: Q40Weight(jax.ShapeDtypeStruct((d, n // 32, 16), np.uint8),
                         jax.ShapeDtypeStruct((d, n // 32), np.float16))
            for k, (d, n) in shapes.items()}
    out = jax.eval_shape(
        lambda t: pack_q40_params(t, enable=True, **kw), tree)
    return {k: type(v) for k, v in out.items()}


PICKS = {
    # every shard-local nb of Yi at tp=4 is 224, 56 (wo) or 160 (w2)
    "yi-tp4-fused": (YI, dict(tp=4, input_sharded=FUSED),
                     dict.fromkeys(YI, Q40KernelNb)),
    # the ref scheme shards wo/w2 on d too (d_local 1792): wo's nb 224 is
    # off the grid, w2's 640 is on it
    "yi-tp4-ref": (YI, dict(tp=4), {**dict.fromkeys(YI, Q40KernelNb),
                                    "w2": Q40Kernel}),
    # Mistral's nb 128 is on the grid (default layout already row-major);
    # its w2 (nb_local 448 / 4 = 112 under fused) is not
    "nb128-stays-d-major": (
        {"wq": (4096, 4096), "wo": (4096, 16384), "w2": (4096, 14336)},
        dict(tp=4, input_sharded=FUSED),
        {"wq": Q40Kernel, "wo": Q40Kernel, "w2": Q40KernelNb}),
    # the width of a decode dispatch is no input of the rule since PR 32
    # (every width has an nb-major kernel); what decides is each scheme's
    # shard-local block count. Mistral at tp=2: nb 128 on the grid, fused
    # wo 64 and w2 224 off it, ref w2 448 off it too
    "mistral-tp2-fused": (MISTRAL, dict(tp=2, input_sharded=FUSED),
                          {**dict.fromkeys(MISTRAL, Q40Kernel),
                           "wo": Q40KernelNb, "w2": Q40KernelNb}),
    "mistral-tp2-ref": (MISTRAL, dict(tp=2),
                        {**dict.fromkeys(MISTRAL, Q40Kernel),
                         "w2": Q40KernelNb}),
    # Brumby's widths at tp=4: nb 160 off the grid (fused wo 40, w2 136)
    "brumby-tp4-fused": (BRUMBY, dict(tp=4, input_sharded=FUSED),
                         dict.fromkeys(BRUMBY, Q40KernelNb)),
    # ... and under ref w2's d_local 1280 places, its nb 544 is off the grid
    "brumby-tp4-ref": (BRUMBY, dict(tp=4),
                       dict.fromkeys(BRUMBY, Q40KernelNb)),
    # d_local 1376 = 11008 / 8 has no 128-multiple divisor: today's pick
    "no-row-tiling": ({"w1": (11008, 5120)}, dict(tp=8), {"w1": Q40Kernel}),
    # tp == 1 is q40_body_policy's: nothing moves, opted in or not
    "tp1-default": (YI, dict(), dict.fromkeys(YI, Q40Kernel)),
    "tp1-opt-in-pad-1.14": (YI, dict(allow_nb_major=True),
                            dict.fromkeys(YI, Q40Kernel)),
    "tp1-opt-in-13b": ({"w2": (5120, 13824), "wq": (5120, 5120)},
                       dict(allow_nb_major=True),
                       {"w2": Q40Kernel, "wq": Q40KernelNb}),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_pack_rule_on_abstract_shapes(case):
    from distributed_llama_tpu.ops.linear import Q40_STOCK

    shapes, kw, want = PICKS[case]
    assert _abstract_pick(shapes, layout=Q40_STOCK, **kw) == want


@pytest.mark.parametrize("scheme", ["ref", "fused", "overlap"])
def test_nb_major_specs_shard_the_axis_the_table_names(scheme):
    """qs_t (L, 16, nb, d) / scale (L, nb, d): output bands on the last
    axis, the fused and overlap schemes' input bands on the nb axis; wcls
    (2-D) the same. expected_shard_names (J004) derives from it."""
    from jax.sharding import PartitionSpec as P

    from distributed_llama_tpu.parallel.tp import (expected_shard_names,
                                                   param_specs)

    tree = {k: Q40KernelNb(np.zeros(0), np.zeros(0))
            for k in ("wq", "wo", "w2", "wcls")}
    specs = param_specs(tree, scheme)
    assert specs["wq"] == Q40KernelNb(P(None, None, None, "tp"),
                                      P(None, None, "tp"))
    assert specs["wcls"] == Q40KernelNb(P(None, None, "tp"), P(None, "tp"))
    for k in ("wo", "w2"):
        want = (Q40KernelNb(P(None, None, None, "tp"), P(None, None, "tp"))
                if scheme == "ref" else
                Q40KernelNb(P(None, None, "tp", None), P(None, "tp", None)))
        assert specs[k] == want, k
    rows = dict(expected_shard_names(tree, scheme))
    assert rows["[0]['wq'].qs_t"] == {3: ("tp",)}
    assert rows["[0]['w2'].scale"] == ({2: ("tp",)} if scheme == "ref"
                                       else {1: ("tp",)})


@pytest.mark.parametrize("t", [1, 16])
@pytest.mark.parametrize("scheme", ["ref", "fused", "overlap"])
def test_sharded_forward_on_nb_major_leaves(scheme, t, monkeypatch, capfd):
    """Shard-local d a multiple of 128 and nb not (16, 32, 8): every leaf
    packs nb-major, shards through the spec table, and the sharded forward
    (T=1 matvec body, T=16 MXU body) equals the one-device XLA forward."""
    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                    params_to_device)
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.models.synth import synth_params
    from distributed_llama_tpu.ops.quants import FloatType
    from distributed_llama_tpu.parallel import (make_mesh,
                                                make_sharded_forward,
                                                shard_cache, shard_params)

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    spec = TransformerSpec(dim=512, hidden_dim=1024, n_layers=1, n_heads=4,
                           n_kv_heads=2, vocab_size=256, seq_len=32,
                           weights_float_type=FloatType.Q40)
    params = synth_params(spec, q40=True, seed=13, scale=0.2)
    tokens = jnp.asarray(np.arange(3, 3 + t), dtype=jnp.int32)

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "xla")
    want, _ = forward(spec, params_to_device(params), init_cache(spec),
                      tokens, jnp.int32(0))

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "xla")
    mesh = make_mesh(tp=2, devices=jax.devices()[:2])
    capfd.readouterr()
    sharded = shard_params(params, mesh, scheme=scheme)
    note = capfd.readouterr().err
    # the members of wqkv (256 + 2 x 128 local rows) and w13 (2 x 512)
    # are fused a rank since PR 56 (tests/test_tp_fused_leaves.py)
    leaves = ("wqkv", "wo", "w13", "w2", "wcls")
    assert all(isinstance(sharded[k], Q40KernelNb) for k in leaves)
    assert "Q40 sharded layout: nb-major: " in note and "d-major" not in note
    # every shard-local block count (8, 16 or 32) is a multiple of 8: the
    # one-row dispatch of all five leaves is the MXU matvec
    assert "t1 mxu 5/5; fused: wqkv w13; 4 Q40 calls a layer" in note
    in_banded = scheme != "ref"
    assert sharded["w2"].qs_t.sharding.shard_shape(
        sharded["w2"].qs_t.shape) == ((1, 16, 16, 512) if in_banded
                                      else (1, 16, 32, 256))
    fwd = make_sharded_forward(spec, mesh, scheme=scheme)
    got, _ = fwd(sharded, shard_cache(init_cache(spec), mesh), tokens,
                 jnp.int32(0))
    # T=1: what test_tp_sharded_forward_with_kernel_layout holds; the chunk
    # runs the MXU body, held where tests/test_pallas_q40.py holds it (the
    # d-major tree differs from the XLA forward by as much)
    tol = dict(rtol=2e-5, atol=2e-5) if t == 1 else dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("scheme", ["ref", "fused"])
def test_threaded_shard_copy_places_the_same_bytes(scheme, monkeypatch):
    """A large shard is copied in bands of its leading axis on a thread
    pool (tp._CUT_THREAD_BYTES): same arrays on the devices, also where
    the axis is shorter than the pool (empty bands)."""
    import jax

    from distributed_llama_tpu.parallel import make_mesh, shard_params
    from distributed_llama_tpu.parallel import tp

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setattr(tp, "_CUT_THREAD_BYTES", 0)
    rng = np.random.default_rng(3)
    tree = {"w2": Q40KernelNb(
                rng.integers(0, 256, (3, 16, 8, 256), dtype=np.uint8),
                rng.random((3, 8, 256), dtype=np.float32)),
            "wcls": Q40KernelNb(
                rng.integers(0, 256, (16, 4, 256), dtype=np.uint8),
                rng.random((4, 256), dtype=np.float32)),
            "wq": rng.random((37, 64, 32), dtype=np.float32),
            "rms_final": rng.random(32, dtype=np.float32)}
    placed = shard_params(tree, make_mesh(tp=2, devices=jax.devices()[:2]),
                          scheme=scheme)
    for got, want in zip(jax.tree_util.tree_leaves(placed),
                         jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(got), want)
    banded = placed["w2"].qs_t.sharding.shard_shape((3, 16, 8, 256))
    assert banded == ((3, 16, 8, 128) if scheme == "ref" else (3, 16, 4, 256))
