"""ctypes bindings for the C++ host library (csrc/libdllama_host.so).

Builds on demand with make/g++ the first time it's needed; every entry point
has a pure-numpy fallback so the package works without a toolchain (slower).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "libdllama_host.so")
_lock = threading.Lock()
_lib = None
_build_failed = False


def _load():
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            return _load_locked()
        except Exception:
            _build_failed = True  # any build/load problem -> numpy fallback
            return None


def _load_locked():
    global _lib
    # make decides staleness (host.cpp or the Makefile's flags changed);
    # without a toolchain an existing library is used as it is
    try:
        subprocess.run(["make", "-C", _CSRC], capture_output=True)
    except OSError:
        pass
    lib = ctypes.CDLL(_SO)
    lib.xorshift_fill_f32.restype = ctypes.c_uint64
    lib.xorshift_fill_f32.argtypes = [
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.c_double]
    for name in ("q40_decode", "q80_decode"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                       ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    for name in ("q40_encode", "q80_encode"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [ctypes.POINTER(ctypes.c_float),
                       ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64]
    for name in ("q40_tile_kernel_layout", "q40_tile_kernel_layout_nb"):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint16),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
    lib.tok_create.restype = ctypes.c_void_p
    lib.tok_create.argtypes = [ctypes.POINTER(ctypes.c_uint8),
                               ctypes.POINTER(ctypes.c_int64),
                               ctypes.POINTER(ctypes.c_float), ctypes.c_int32]
    lib.tok_destroy.restype = None
    lib.tok_destroy.argtypes = [ctypes.c_void_p]
    lib.tok_encode.restype = ctypes.c_int64
    lib.tok_encode.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
                               ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)]
    lib.sample_logits.restype = ctypes.c_int32
    lib.sample_logits.argtypes = [ctypes.POINTER(ctypes.c_float),
                                  ctypes.c_int32, ctypes.c_float,
                                  ctypes.c_float, ctypes.c_float]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def xorshift_fill(state: int, n: int, divisor: float = 1.0) -> tuple[int, np.ndarray]:
    """Fill n f32 samples of the reference xorshift stream, divided (in double,
    like the reference test's ``randomF32(&state) / 120.0``).

    Returns (new_state, array). Native when possible; python fallback otherwise.
    """
    lib = _load()
    out = np.empty(n, dtype=np.float32)
    if lib is not None:
        new_state = lib.xorshift_fill_f32(
            ctypes.c_uint64(state),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, ctypes.c_double(divisor))
        return int(new_state), out
    from .rng import Xorshift64

    rng = Xorshift64(state)
    out[:] = (rng.f32_array(n).astype(np.float64) / divisor).astype(np.float32)
    return rng.state, out


def q40_decode_wire(buf: np.ndarray, nb: int) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    out = np.empty(nb * 32, dtype=np.float32)
    lib.q40_decode(buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                   out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), nb)
    return out


def q40_tile_kernel_layout(qs: np.ndarray, d16: np.ndarray,
                           n_threads: int | None = None,
                           nb_major: bool = False):
    """Threaded (..., d, nb, 16) -> (..., 16, d, nb) re-tiling + f16->f32
    scale upconvert — the load-time transform feeding the Pallas kernel
    layout; ``nb_major`` gives (..., 16, nb, d) codes and (..., nb, d)
    scales instead (io/loader.Q40KernelNb). Returns (qs_t, scale) or None
    when the native library is unavailable (callers fall back to numpy)."""
    lib = _load()
    if lib is None:
        return None
    if qs.dtype != np.uint8 or d16.dtype != np.float16:
        return None
    *lead, d, nb, sixteen = qs.shape
    if sixteen != 16:
        return None
    if d16.shape != qs.shape[:-1]:  # native loop trusts the sizes: check here
        raise ValueError(
            f"d16 shape {d16.shape} does not match qs {qs.shape[:-1]}")
    n_stacked = int(np.prod(lead)) if lead else 1
    qs_c = np.ascontiguousarray(qs)
    d16_c = np.ascontiguousarray(d16)
    plane = (nb, d) if nb_major else (d, nb)
    qs_t = np.empty((*lead, 16, *plane), dtype=np.uint8)
    scale = np.empty((*lead, *plane), dtype=np.float32)
    if n_threads is None:
        n_threads = min(16, os.cpu_count() or 1)
    tile = (lib.q40_tile_kernel_layout_nb if nb_major
            else lib.q40_tile_kernel_layout)
    tile(qs_c.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
         d16_c.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
         qs_t.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
         scale.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
         n_stacked, d, nb, n_threads)
    return qs_t, scale


def sample_logits(logits: np.ndarray, temperature: float, topp: float,
                  coin: float) -> int | None:
    """Native reference-semantics sampler (csrc sample_logits); None when the
    library is unavailable (callers run the numpy implementation)."""
    lib = _load()
    if lib is None:
        return None
    logits = np.ascontiguousarray(logits, dtype=np.float32)
    return int(lib.sample_logits(
        logits.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        len(logits), ctypes.c_float(temperature), ctypes.c_float(topp),
        ctypes.c_float(coin)))


class NativeBpe:
    """Native greedy-BPE encoder over a parsed vocab. None-able: callers use
    the Python merge loop when the toolchain/library is unavailable."""

    def __init__(self, pieces: list[bytes], scores: list[float]):
        self._lib = _load()
        self._handle = None
        if self._lib is None:
            return
        blob = b"".join(pieces)
        offs = np.zeros(len(pieces) + 1, dtype=np.int64)
        np.cumsum([len(p) for p in pieces], out=offs[1:])
        self._blob = np.frombuffer(blob, dtype=np.uint8).copy()
        self._scores = np.asarray(scores, dtype=np.float32)
        self._offs = offs
        self._handle = self._lib.tok_create(
            self._blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            self._scores.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            len(pieces))

    @property
    def available(self) -> bool:
        return self._handle is not None

    def encode(self, text: bytes) -> list[int]:
        buf = np.frombuffer(text, dtype=np.uint8)
        out = np.empty(max(len(text), 1), dtype=np.int32)
        n = self._lib.tok_encode(
            self._handle,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(text),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out[:n].tolist()

    def __del__(self):
        if getattr(self, "_handle", None) is not None:
            self._lib.tok_destroy(self._handle)
