"""Pre-tiled kernel-layout sidecar cache (VERDICT r4 #7).

The cache must make the second real-model load an mmap (0 bytes
re-tiled) while producing a tree that is INDISTINGUISHABLE — same leaf
types, shapes, dtypes, and bytes — from the load-and-retile path, under
every layout the packer can pick (d-major, nb-major mix, codec
fallbacks). A stale or mismatched sidecar must rebuild, never feed a
wrong layout to the kernels.
"""

import os

import numpy as np
import pytest

from distributed_llama_tpu.io import kernel_cache as kc
from distributed_llama_tpu.io.loader import (Q40Kernel, Q40KernelNb,
                                             Q40Weight, write_model)
from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.ops.quants import FloatType

TINY = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=96, seq_len=32,
                       weights_float_type=FloatType.Q40)


def _model_file(tmp_path, spec=TINY, seed=7):
    rng = np.random.default_rng(seed)

    def t(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    tensors = {
        "tok_embedding": t(spec.vocab_size, spec.dim),
        "rms_att": t(spec.n_layers, spec.dim),
        "rms_ffn": t(spec.n_layers, spec.dim),
        "rms_final": t(spec.dim),
        "wcls": t(spec.vocab_size, spec.dim),
        **{name: t(spec.n_layers, *shape)
           for name, shape in spec.layer_matmul_shapes()},
    }
    path = str(tmp_path / "model.bin")
    write_model(path, spec, tensors)
    return path


def _trees_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        va, vb = a[k], b[k]
        # memmap is an ndarray subclass: compare container KIND (dense vs
        # the exact Q40 layout NamedTuple), not the concrete array class
        ka = type(va) if not isinstance(va, np.ndarray) else np.ndarray
        kb = type(vb) if not isinstance(vb, np.ndarray) else np.ndarray
        assert ka is kb, (k, type(va), type(vb))
        fa = [va] if isinstance(va, np.ndarray) else list(va)
        fb = [vb] if isinstance(vb, np.ndarray) else list(vb)
        for x, y in zip(fa, fb):
            assert x.dtype == y.dtype and x.shape == y.shape, k
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y), k)


def test_sidecar_roundtrip_bit_exact(tmp_path, monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")  # force packing on CPU
    path = _model_file(tmp_path)
    spec1, fresh = kc.load_model_packed(path)
    side = kc.sidecar_path(path)
    assert os.path.exists(side)
    # the packed tree has kernel-layout leaves (that is what's cached)
    assert any(isinstance(v, (Q40Kernel, Q40KernelNb))
               for v in fresh.values())

    spec2, cached = kc.load_model_packed(path)
    assert spec2 == spec1
    _trees_equal(fresh, cached)
    # and the cached leaves are memmap views, not fresh copies
    mmapped = [f for v in cached.values()
               for f in ([v] if isinstance(v, np.ndarray) else list(v))
               if isinstance(f, np.memmap) or isinstance(f.base, np.memmap)]
    assert mmapped, "cache hit did not return memmap-backed leaves"


def test_key_mismatch_rebuilds(tmp_path, monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    kc.load_model_packed(path)
    side = kc.sidecar_path(path)
    assert kc.load_packed(side, kc.layout_key(path)) is not None
    assert kc.load_packed(side, "v1|other|key") is None

    # the tile-row cap is a constant since PR 43 and still in the key, where
    # it stood: sidecars written before stay valid
    assert "|768|" in kc.layout_key(path)

    # overwriting the model .bin (same path, new contents) invalidates:
    # the key carries the source file's size+mtime
    os.utime(path, ns=(1, 1))
    assert kc.load_packed(side, kc.layout_key(path)) is None


def test_corrupt_sidecar_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    spec1, fresh = kc.load_model_packed(path)
    side = kc.sidecar_path(path)
    with open(side, "r+b") as fh:
        fh.write(b"garbage!")
    spec2, rebuilt = kc.load_model_packed(path)
    _trees_equal(fresh, rebuilt)
    # the rebuild rewrote a VALID sidecar
    assert kc.load_packed(side, kc.layout_key(path)) is not None


def test_disabled_modes_skip_sidecar(tmp_path, monkeypatch):
    # xla kernel mode: nothing to pre-tile, no sidecar written
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "xla")
    path = _model_file(tmp_path)
    _, tree = kc.load_model_packed(path)
    assert not os.path.exists(kc.sidecar_path(path))
    assert all(not isinstance(v, (Q40Kernel, Q40KernelNb))
               for v in tree.values())

    # pallas mode but cache opt-out: packed tree, still no sidecar
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setenv("DLLAMA_TILED_CACHE", "0")
    _, tree = kc.load_model_packed(path)
    assert not os.path.exists(kc.sidecar_path(path))
    assert any(isinstance(v, (Q40Kernel, Q40KernelNb))
               for v in tree.values())


def test_packed_tree_decodes_like_codec_tree(tmp_path, monkeypatch):
    """End-to-end: logits from the sidecar-cached tree equal the plain
    load_model tree's (the packed layouts are exact re-tilings)."""
    import jax.numpy as jnp

    from distributed_llama_tpu.io.loader import load_model
    from distributed_llama_tpu.models.llama import forward, init_cache

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    spec, codec = load_model(path, weights_float_type=FloatType.Q40)
    kc.load_model_packed(path)            # writes the sidecar
    _, cached = kc.load_model_packed(path)  # mmap hit

    tok = jnp.asarray([5], jnp.int32)
    logits1, _ = forward(spec, codec, init_cache(spec), tok, jnp.int32(0))
    logits2, _ = forward(spec, {k: (jnp.asarray(v) if isinstance(v, np.ndarray)
                                    else type(v)(*map(jnp.asarray, v)))
                                for k, v in cached.items()},
                         init_cache(spec), tok, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits2),
                               atol=2e-5)


def test_nb_major_force_invalidates(tmp_path, monkeypatch):
    """A layout that forces nb-major changes the packed tree, so it must
    re-key the sidecar (a d-major sidecar served to a forced run would
    silently ignore the layout request)."""
    from distributed_llama_tpu.ops.linear import Q40Layout

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    kc.load_model_packed(path)
    side = kc.sidecar_path(path)
    assert kc.load_packed(side, kc.layout_key(path)) is not None
    forced = Q40Layout("i4-nb", "test")
    assert kc.load_packed(side, kc.layout_key(path, layout=forced)) is None


def test_layout_key_strings_are_the_parents(tmp_path, monkeypatch):
    """The key for today's two cases is byte-equal to what the parent
    built from DLLAMA_NB_MAJOR (unset -> ``nb=auto``, the i4-nb policy ->
    ``nb=force``): no sidecar on disk is rebuilt by the change of
    mechanism."""
    from distributed_llama_tpu.ops.linear import Q40_STOCK, Q40Layout
    from distributed_llama_tpu.ops.pallas_layer import fusion_cache_key

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    st = os.stat(path)
    tail = f"|tp=1|wf=Q40|bf=F32|src={st.st_size}:{st.st_mtime_ns}"
    # 768: the tile-row cap, spelled out (a constant since PR 43; the parent
    # wrote its environment knob's default here)
    head = f"v1|pallas|768|{fusion_cache_key()}"
    assert kc.layout_key(path) == f"{head}|nb=auto{tail}"
    assert kc.layout_key(path, layout=Q40_STOCK) == f"{head}|nb=auto{tail}"
    assert kc.layout_key(path, layout=Q40Layout("d-major", "8 rows")) \
        == f"{head}|nb=auto{tail}"
    assert kc.layout_key(path, layout=Q40Layout("i4-nb", "auto")) \
        == f"{head}|nb=force{tail}"


def test_layout_key_folds_float_types(tmp_path, monkeypatch):
    """weights/buffer float types are part of the layout key: a future
    packed form for another float type cannot collide with the Q40/F32
    sidecar under the same key."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    base = kc.layout_key(path)
    assert "|wf=Q40|" in base and "|bf=F32" in base
    # defaults are spelled out: explicit Q40/F32 == the default key
    assert kc.layout_key(path, weights_float_type=FloatType.Q40,
                         buffer_float_type=FloatType.F32) == base
    assert kc.layout_key(path, weights_float_type=FloatType.F16) != base
    assert kc.layout_key(path, buffer_float_type=FloatType.Q80) != base
    # and the written sidecar round-trips under the default key
    kc.load_model_packed(path)
    assert kc.load_packed(kc.sidecar_path(path), base) is not None


def test_build_lock_skips_concurrent_write(tmp_path, monkeypatch, capsys):
    """A held build lock makes a racing load SKIP the sidecar write (no
    duplicate GB-scale .tmp<pid> streams — ADVICE r5) while still
    returning a fully packed in-memory tree; once the lock is released
    the next load writes normally."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    side = kc.sidecar_path(path)

    token = kc.try_build_lock(side)  # "another process" holds the lock
    assert token is not None
    assert kc.try_build_lock(side) is None  # held: second taker refused
    _, tree = kc.load_model_packed(path)
    assert not os.path.exists(side)  # write skipped
    assert any(isinstance(v, (Q40Kernel, Q40KernelNb))
               for v in tree.values())  # but the load itself is packed
    assert not [f for f in os.listdir(str(tmp_path))
                if ".kcache.tmp" in f]  # no orphan tmp sidecars

    kc.release_build_lock(token)
    kc.load_model_packed(path)
    assert os.path.exists(side)  # lock released: the write proceeds
    assert not os.path.exists(side + ".lock")  # and released its own lock


def test_build_lock_breaks_stale_holder(tmp_path, monkeypatch):
    """A lock whose holder crashed (old mtime) must not wedge sidecar
    writes forever: it is broken and re-acquired."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    path = _model_file(tmp_path)
    side = kc.sidecar_path(path)
    lock = side + ".lock"
    with open(lock, "w") as fh:
        fh.write("99999\n")
    os.utime(lock, (1, 1))  # ancient: way past _LOCK_STALE_S
    token = kc.try_build_lock(side)
    assert token is not None
    kc.release_build_lock(token)
    assert not os.path.exists(lock)
