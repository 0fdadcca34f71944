"""Pre-tiled kernel-layout sidecar cache (VERDICT r4 #7).

The reference's model load is mmap-and-stream (transformer.cpp:280-296).
Ours additionally re-tiles every Q40 tensor into the Pallas kernel layout
(csrc/host.cpp q40_tile_kernel_layout) and concatenates the fused
wqkv/w13 stacks — GB-scale host passes that used to repeat on EVERY load.
This module persists the FINAL packed+fused host tree next to the model
(`<model>.kcache`) in one mmap-able file; later loads memory-map the
leaves directly (~0 s host prep, pages stream from disk on demand during
device placement — the same thinness as the reference's loader).

File format (little-endian):
    MAGIC(8) | u32 header_len | header JSON | 4096-aligned raw arrays
header = {"key": layout-key, "entries": [{"name", "kind",
          "arrays": [{"shape", "dtype", "offset", "nbytes"}]}]}
kinds: dense (1 array), q40w (qs, d16), q40k (qs_t, scale),
       q40knb (qs_t, scale).

The layout key captures everything that changes the packed tree's
CONTENTS (kernel mode, tile-row cap, nb-major policy, fusion mode,
format version); a mismatch falls back to a rebuild, never to silently
wrong layouts. DLLAMA_TILED_CACHE=0 disables both read and write.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from ..obs.spans import startup_phase
from ..utils.compile_cache import cache_error
from .loader import Q40Kernel, Q40KernelNb, Q40Weight

MAGIC = b"DLKC0001"
_ALIGN = 4096

_KINDS = {
    "dense": (None, 1),
    "q40w": (Q40Weight, 2),
    "q40k": (Q40Kernel, 2),
    "q40knb": (Q40KernelNb, 2),
}


def _kind_of(v) -> str:
    if isinstance(v, Q40Weight):
        return "q40w"
    if isinstance(v, Q40Kernel):
        return "q40k"
    if isinstance(v, Q40KernelNb):
        return "q40knb"
    return "dense"


def layout_key(model_path: str | None = None, tp: int = 1,
               weights_float_type=None, buffer_float_type=None,
               layout=None) -> str:
    """Everything that decides the packed tree's contents: the kernel
    knobs and the model's resolved ``layout`` (an ops/linear.Q40Layout;
    None = the stock picks), the float types the
    tree was decoded/packed under (a future packed form for another float
    type must not collide under the same key), AND the model file's
    identity (size + mtime) — overwriting the .bin with a new checkpoint
    at the same path must invalidate the sidecar, never silently serve
    the old weights."""
    from ..ops.linear import q40_kernel_mode
    from ..ops.pallas_layer import fusion_cache_key
    from ..ops.pallas_q40 import _TILE_ROWS_CAP

    # the layout's i4 chain body is deliberately NOT in this key: the
    # sidecar stores the host u8 tree either way (the conversion is
    # in-chain)
    src = ""
    if model_path is not None:
        st = os.stat(model_path)
        src += f"|src={st.st_size}:{st.st_mtime_ns}"
    nbm = "force" if layout is not None and layout.force_nb_major else "auto"
    wf = getattr(weights_float_type, "name", weights_float_type) or "Q40"
    bf = getattr(buffer_float_type, "name", buffer_float_type) or "F32"
    pad = getattr(layout, "pad_blocks", 0)
    return (f"v1|{q40_kernel_mode()}|{_TILE_ROWS_CAP}|{fusion_cache_key()}"
            f"|nb={nbm}|tp={tp}|wf={wf}|bf={bf}{src}"
            + (f"|pad={pad}" if pad else ""))


def sidecar_path(model_path: str) -> str:
    return model_path + ".kcache"


# A build lock older than this is presumed orphaned (holder crashed between
# O_EXCL create and unlink) and is broken. GB-scale sidecar writes take
# minutes, not hours.
_LOCK_STALE_S = 3600.0


def _lock_path(side: str) -> str:
    return side + ".lock"


def try_build_lock(side: str):
    """O_EXCL lock file guarding the sidecar build: two concurrent loads of
    the same model must not BOTH stream GB-scale .tmp<pid> files onto disk
    (ADVICE r5). Returns an opaque token (pass to release_build_lock) or
    None when another live process holds the lock — the caller then skips
    the write; its own load already has the packed tree in memory, and the
    other process's completed sidecar serves every later load."""
    lock = _lock_path(side)
    for _ in range(2):
        try:
            fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            os.write(fd, f"{os.getpid()}\n".encode())
            os.close(fd)
            return lock
        except FileExistsError:
            try:
                age = time.time() - os.stat(lock).st_mtime
            except OSError:
                continue  # holder released between open and stat: retry
            if age < _LOCK_STALE_S:
                return None
            # stale: the holder crashed. Claim the break by RENAME (atomic;
            # exactly one racer succeeds) rather than unlink — a bare
            # unlink could delete a FRESH lock another breaker just
            # re-created, letting two writers in
            try:
                claimed = lock + f".stale{os.getpid()}"
                os.rename(lock, claimed)
                # the rename could still have grabbed a FRESH lock (a
                # racing breaker re-created it between our stat and our
                # rename): re-check on the claimed copy, and restore it
                # atomically (link fails if a new lock appeared) if so
                if time.time() - os.stat(claimed).st_mtime < _LOCK_STALE_S:
                    try:
                        os.link(claimed, lock)
                    except OSError:
                        pass  # a newer lock exists; it stands
                    os.unlink(claimed)
                    return None
                os.unlink(claimed)
            except OSError:
                return None  # another breaker won the rename: back off
        except OSError:
            return None  # unwritable dir: save_packed will say so itself
    return None


def release_build_lock(token) -> None:
    try:
        os.unlink(token)
    except OSError:
        pass


def save_packed(path: str, key: str, tree: dict) -> None:
    """Write the packed tree atomically (tmp + rename)."""
    entries = []
    arrays: list[np.ndarray] = []
    off = 0

    def admit(a):
        nonlocal off
        a = np.ascontiguousarray(a)
        off = (off + _ALIGN - 1) // _ALIGN * _ALIGN
        meta = {"shape": list(a.shape), "dtype": a.dtype.str,
                "offset": off, "nbytes": int(a.nbytes)}
        off += a.nbytes
        arrays.append(a)
        return meta

    for name, v in tree.items():
        kind = _kind_of(v)
        fields = [v] if kind == "dense" else list(v)
        entries.append({"name": name, "kind": kind,
                        "arrays": [admit(np.asarray(f)) for f in fields]})
    header = json.dumps({"key": key, "entries": entries}).encode()
    base = len(MAGIC) + 4 + len(header)
    base_pad = (base + _ALIGN - 1) // _ALIGN * _ALIGN

    tmp = path + f".tmp{os.getpid()}"
    try:
        with open(tmp, "wb") as fh:
            fh.write(MAGIC)
            fh.write(np.uint32(len(header)).tobytes())
            fh.write(header)
            pos = base
            for meta, a in zip(
                    [m for ent in entries for m in ent["arrays"]], arrays):
                want = base_pad + meta["offset"]
                fh.write(b"\x00" * (want - pos))
                fh.write(memoryview(a).cast("B"))
                pos = want + a.nbytes
        os.replace(tmp, path)
    except BaseException:
        # a GB-scale half-written tmp must not outlive a failed write
        # (ENOSPC would otherwise leak an orphan per retrying pid,
        # consuming the very space that made the write fail)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_packed(path: str, key: str) -> dict | None:
    """Memory-map a sidecar written by save_packed; None on any mismatch
    (wrong magic/key/shape trouble) — the caller rebuilds."""
    try:
        with open(path, "rb") as fh:
            if fh.read(len(MAGIC)) != MAGIC:
                return None
            hlen = int(np.frombuffer(fh.read(4), np.uint32)[0])
            header = json.loads(fh.read(hlen).decode())
        if header.get("key") != key:
            print(f"kernel cache key mismatch ({path}): cached for "
                  f"{header.get('key')!r}, want {key!r}; rebuilding",
                  file=sys.stderr)
            return None
        base = len(MAGIC) + 4 + hlen
        base_pad = (base + _ALIGN - 1) // _ALIGN * _ALIGN
        buf = np.memmap(path, dtype=np.uint8, mode="r")
        tree: dict = {}
        for e in header["entries"]:
            fields = []
            for m in e["arrays"]:
                start = base_pad + m["offset"]
                raw = buf[start:start + m["nbytes"]]
                fields.append(raw.view(np.dtype(m["dtype"]))
                              .reshape(m["shape"]))
            cls, n = _KINDS[e["kind"]]
            if len(fields) != n:
                return None
            tree[e["name"]] = fields[0] if cls is None else cls(*fields)
        return tree
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        cache_error("kcache", f"{path} unreadable, rebuilding", e)
        return None


def cache_enabled() -> bool:
    return os.environ.get("DLLAMA_TILED_CACHE", "1") != "0"


@startup_phase("load")
def load_model_packed(path: str, spec=None, weights_float_type=None,
                      buffer_float_type=None, layout=None):
    """load_model + pack_q40_params + fuse_q40_layer_matmuls, with the
    sidecar shortcut: a valid `<model>.kcache` skips BOTH the .bin walk
    and the GB-scale re-tiling/fusion (the tree's leaves are memmap views
    into the sidecar). ``layout`` is the model's resolved Q40Layout
    (ops/linear.q40_body_policy; None = the stock picks): it keys the
    sidecar AND packs the tree, so the two cannot disagree. Single-chip
    decode path only — mesh runs decide each leaf's layout on its
    shard-local shape, so they keep load_model + tp-aware packing
    (parallel/tp.shard_params)."""
    from ..ops.linear import (Q40_STOCK, fuse_q40_layer_matmuls,
                              pack_q40_params, q40_kernel_mode)
    from ..ops.quants import FloatType
    from .loader import load_model, read_spec

    wft = FloatType.Q40 if weights_float_type is None else weights_float_type
    kw = {} if buffer_float_type is None else {
        "buffer_float_type": buffer_float_type}
    packing = wft == FloatType.Q40 and q40_kernel_mode() == "pallas"
    use_cache = cache_enabled() and packing
    side = sidecar_path(path)
    layout = layout or Q40_STOCK
    key = layout_key(path, weights_float_type=wft,
                     buffer_float_type=buffer_float_type, layout=layout)
    if use_cache and os.path.exists(side):
        t0 = time.perf_counter()
        if spec is None:
            spec = read_spec(path, wft, **kw)
        tree = load_packed(side, key)
        if tree is not None:
            print(f"⏩ kernel-layout cache hit ({side}): "
                  f"{time.perf_counter() - t0:.1f}s host prep "
                  f"(mmap, 0 bytes re-tiled)", file=sys.stderr)
            return spec, tree
    spec, params = load_model(path, spec=spec, weights_float_type=wft, **kw)
    t0 = time.perf_counter()
    packed = fuse_q40_layer_matmuls(
        pack_q40_params(params, allow_nb_major=True, layout=layout))
    dt = time.perf_counter() - t0
    if packing:
        print(f"kernel re-tile + fuse: {dt:.1f}s", file=sys.stderr)
    if use_cache and any(isinstance(v, (Q40Kernel, Q40KernelNb))
                         for v in packed.values()):
        lock = try_build_lock(side)
        if lock is None:
            print(f"⏩ another process is writing {side}; skipping the "
                  f"sidecar write (this load keeps its in-memory tree)",
                  file=sys.stderr)
            return spec, packed
        try:
            t0 = time.perf_counter()
            save_packed(side, key, packed)
            print(f"⏩ kernel-layout cache written ({side}, "
                  f"{os.path.getsize(side) / 1e9:.2f} GB, "
                  f"{time.perf_counter() - t0:.1f}s); next load skips "
                  f"re-tiling", file=sys.stderr)
        except OSError as e:
            cache_error("kcache", f"{side} not written, loads keep "
                                  f"re-tiling", e)
        finally:
            release_build_lock(lock)
    return spec, packed
