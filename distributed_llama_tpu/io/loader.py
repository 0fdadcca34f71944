""".bin model file reader/writer (reference format parity).

Reader walks the exact tensor order of reference src/transformer.cpp:298-352
(see models/spec.py docstring for the layout) and returns a numpy parameter
pytree with per-layer weights stacked along a leading layer axis — the shape a
`lax.scan` over layers consumes. Quantized (Q40) matmul weights come back as
`Q40Weight(qs, d16)` planar pairs; F16 as float16 arrays; F32 as float32.

Writer emits the same byte layout (used by our converter and by tests to
synthesize models); the legacy freq_cis gap is written as zeros, matching what
``seek`` past EOF produces in the reference converter (converter.py:124-127).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from ..models.spec import MAX_HEADER_BYTES, TransformerSpec
from ..obs.spans import startup_phase
from ..ops.quants import (
    FloatType,
    pack_q40_bytes,
    quantize_q40,
    unpack_q40_bytes,
)


class Q40Weight(NamedTuple):
    """Planar Q40 tensor: qs uint8 (..., d, n/32, 16), d16 float16 (..., d, n/32).

    This is the codec-canonical layout (it mirrors the wire format's 16
    nibble-bytes per block, reference src/quants.hpp:16-19). The TPU matmul
    kernel wants ``Q40Kernel`` instead — see ``to_kernel_layout``.

    NamedTuple => automatically a jax pytree; usable directly under jit/scan.
    """

    qs: np.ndarray
    d16: np.ndarray

    @property
    def logical_shape(self) -> tuple[int, ...]:
        return (*self.qs.shape[:-2], self.qs.shape[-2] * 32)


class Q40Kernel(NamedTuple):
    """Kernel-tiled planar Q40: qs_t uint8 (..., 16, d, n/32), scale f32
    (..., d, n/32).

    The nibble-position axis leads so the Pallas kernel (ops/pallas_q40.py)
    streams plain 2D (rows, blocks) tiles whose minor dim is the block index:
    the per-block scale then lines up with the codes elementwise and the
    kernel needs no minor-dim reshape/interleave (which Mosaic does not
    support). Scales are f32 because Mosaic has no f16 vectors — f16->f32 is
    exact, so the value map is unchanged. Produced once at load time by
    ``to_kernel_layout`` — never re-tile inside a jitted per-token step.
    """

    qs_t: np.ndarray
    scale: np.ndarray

    @property
    def logical_shape(self) -> tuple[int, ...]:
        return (*self.scale.shape[:-1], self.scale.shape[-1] * 32)


class Q40KernelNb(NamedTuple):
    """Lane-aligned kernel tiling for awkward block counts: qs_t uint8
    (..., 16, nb, d), scale f32 (..., nb, d) — the OUTPUT dim d is minor.

    TPU physical layouts tile the last two dims to (8, 128); the standard
    ``Q40Kernel`` puts the block count nb minor, which pads nb up to a
    multiple of 128 — at 13B (dim 5120 -> nb=160 -> padded 256) that is a
    1.6x inflation of both HBM footprint AND every weight-streaming byte
    the decode loop reads. This transposed layout puts d minor instead
    (d is 128-aligned for every Llama shape), so there is NO padding.
    Selected automatically by ``pack_q40_params`` when the padding ratio
    is material; the matvec kernel has a dedicated body for it
    (ops/pallas_q40._matvec_body_nb).
    """

    qs_t: np.ndarray
    scale: np.ndarray

    @property
    def logical_shape(self) -> tuple[int, ...]:
        return (*self.scale.shape[:-2], self.scale.shape[-1],
                self.scale.shape[-2] * 32)


class Q40KernelNbI4(NamedTuple):
    """Signed-int4 plane form of ``Q40KernelNb``: qs4 int4 (..., 32, nb, d)
    holding (code - 8) directly (range -8..7 fits int4 exactly — planes
    0..15 are the low nibbles, 16..31 the high), scale f32 (..., nb, d).

    DEVICE-ONLY and chain-internal: this runtime cannot pass int4 arrays
    across a jit boundary (dispatch-layer recursion), so the fused decode
    chain materializes this form ON DEVICE from the resident uint8 tree
    at chain start (ops/pallas_q40.to_i4_planes) and the u8 original
    stays the placed argument. Why it exists: the T=1 matvec body drops
    from ~9 to ~3 VPU ops per packed byte (no mask, no shift, one convert,
    no xsum correction) — measured 701 GB/s vs 638 on the 13B w13 shape
    against a 746 GB/s DMA floor (probe since deleted; runtime of round
    5). A d-major leaf has no such form: its s4 body measured ~6x slower
    than u8 (ops/pallas_q40.chain_weight_prep).
    """

    qs4: np.ndarray
    scale: np.ndarray

    @property
    def logical_shape(self) -> tuple[int, ...]:
        return (*self.scale.shape[:-2], self.scale.shape[-1],
                self.scale.shape[-2] * 32)


def to_kernel_layout_nb(w: Q40Weight) -> Q40KernelNb:
    """(..., d, nb, 16) -> (..., 16, nb, d) with f32 scales (..., nb, d).
    numpy inputs take the threaded native tiler, as ``to_kernel_layout``
    does (the numpy strided transpose below is single-threaded)."""
    qs = w.qs
    if isinstance(qs, np.ndarray) and isinstance(w.d16, np.ndarray):
        from ..utils import native

        tiled = native.q40_tile_kernel_layout(qs, w.d16, nb_major=True)
        if tiled is not None:
            return Q40KernelNb(*tiled)
    nd = qs.ndim
    perm = tuple(range(nd - 3)) + (nd - 1, nd - 2, nd - 3)
    qs_t = qs.transpose(perm)
    if isinstance(qs_t, np.ndarray):
        qs_t = np.ascontiguousarray(qs_t)
    sperm = tuple(range(nd - 3)) + (nd - 2, nd - 3)
    scale = w.d16.transpose(sperm).astype(np.float32)
    if isinstance(scale, np.ndarray):
        scale = np.ascontiguousarray(scale)
    return Q40KernelNb(qs_t, scale)


def from_kernel_layout_nb(w: Q40KernelNb) -> Q40Weight:
    qs_t = w.qs_t
    nd = qs_t.ndim
    perm = tuple(range(nd - 3)) + (nd - 1, nd - 2, nd - 3)
    qs = qs_t.transpose(perm)
    if isinstance(qs, np.ndarray):
        qs = np.ascontiguousarray(qs)
    scale = np.ascontiguousarray(np.swapaxes(w.scale, -1, -2))
    return Q40Weight(qs, scale.astype(np.float16))


def to_kernel_layout(w: Q40Weight) -> Q40Kernel:
    """(..., d, nb, 16) -> (..., 16, d, nb), one-time load-side re-tiling.

    numpy inputs go through the THREADED C++ path when the host library is
    available (csrc/host.cpp q40_tile_kernel_layout — this is a GB-scale
    strided transpose at 7B/70B sizes); jax arrays and fallback use the
    numpy transpose.
    """
    qs = w.qs
    if isinstance(qs, np.ndarray) and isinstance(w.d16, np.ndarray):
        from ..utils import native

        tiled = native.q40_tile_kernel_layout(qs, w.d16)
        if tiled is not None:
            return Q40Kernel(*tiled)
    nd = qs.ndim
    perm = tuple(range(nd - 3)) + (nd - 1, nd - 3, nd - 2)
    qs_t = qs.transpose(perm)
    if isinstance(qs_t, np.ndarray):
        qs_t = np.ascontiguousarray(qs_t)
    return Q40Kernel(qs_t, w.d16.astype(np.float32))


def from_kernel_layout(w: Q40Kernel) -> Q40Weight:
    qs_t = w.qs_t
    nd = qs_t.ndim
    perm = tuple(range(nd - 3)) + (nd - 2, nd - 1, nd - 3)
    qs = qs_t.transpose(perm)
    if isinstance(qs, np.ndarray):
        qs = np.ascontiguousarray(qs)
    # scales were exactly upconverted f16->f32; the downcast is lossless
    return Q40Weight(qs, w.scale.astype(np.float16))


def read_spec(path: str, weights_float_type=FloatType.F32,
              buffer_float_type=FloatType.F32) -> TransformerSpec:
    with open(path, "rb") as f:
        raw = f.read(MAX_HEADER_BYTES)  # a shorter header reads its own
    return TransformerSpec.from_header(raw, weights_float_type, buffer_float_type)


class _Walker:
    def __init__(self, mm: np.ndarray, offset: int):
        self.mm = mm
        self.off = offset

    def take(self, nbytes: int) -> np.ndarray:
        chunk = self.mm[self.off:self.off + nbytes]
        if chunk.nbytes != nbytes:
            raise ValueError(
                f"file truncated: wanted {nbytes} bytes at {self.off}, "
                f"got {chunk.nbytes}")
        self.off += nbytes
        return chunk

    def f32(self, shape: tuple[int, ...]) -> np.ndarray:
        n = int(np.prod(shape))
        return self.take(n * 4).view(np.float32).reshape(shape).copy()

    def matmul(self, spec: TransformerSpec, shape: tuple[int, int]):
        ft = spec.weights_float_type
        raw = self.take(spec.matmul_bytes(shape))
        if ft == FloatType.F32:
            return raw.view(np.float32).reshape(shape).copy()
        if ft == FloatType.F16:
            return raw.view(np.float16).reshape(shape).copy()
        if ft == FloatType.Q40:
            qs, d16 = unpack_q40_bytes(raw, shape)  # unpack always copies
            return Q40Weight(qs, d16)
        raise ValueError(f"unsupported weights float type {ft}")


@startup_phase("load")
def load_model(path: str, spec: TransformerSpec | None = None,
               weights_float_type=FloatType.F32,
               buffer_float_type=FloatType.F32) -> tuple[TransformerSpec, dict]:
    """Load a .bin file into a stacked-layer numpy param tree.

    Size accounting is byte-exact, like the reference's missedBytes check
    (transformer.cpp:344-348).
    """
    if spec is None:
        spec = read_spec(path, weights_float_type, buffer_float_type)
    mm = np.memmap(path, dtype=np.uint8, mode="r")
    expected = spec.file_size()
    if mm.nbytes != expected:
        raise ValueError(
            f"file size mismatch: {path} has {mm.nbytes} bytes, "
            f"spec requires {expected}")
    w = _Walker(mm, spec.header_bytes)

    params: dict = {}
    params["tok_embedding"] = w.f32((spec.vocab_size, spec.dim))
    if spec.planned:
        _load_planned_layers(spec, w, params)
        params["rms_final"] = w.f32((spec.dim,))
        if spec.hybrid:
            params["rms_final_b"] = w.f32((spec.dim,))
        params["wcls"] = w.matmul(spec, (spec.vocab_size, spec.dim))
        if w.off != expected:
            raise ValueError(f"missed {expected - w.off} bytes")
        return spec, params

    # preallocate the stacked arrays and stream each layer straight into its
    # slot (avoids transiently holding list-of-layers + np.stack copies of
    # multi-GB tensors)
    shapes = spec.layer_matmul_shapes()
    experts = spec.expert_matmul_shapes()
    norms = spec.layer_norm_shapes()
    L, E = spec.n_layers, spec.n_experts
    ft = spec.weights_float_type
    for name, n in norms:
        params[name] = np.empty((L, n), np.float32)
    for lead, group in (((L,), shapes), ((L, E), experts)):
        for name, (dd, nn) in group:
            if ft == FloatType.Q40:
                params[name] = Q40Weight(
                    np.empty((*lead, dd, nn // 32, 16), np.uint8),
                    np.empty((*lead, dd, nn // 32), np.float16))
            else:
                dtype = np.float32 if ft == FloatType.F32 else np.float16
                params[name] = np.empty((*lead, dd, nn), dtype)
    if E:
        params["moe_gate"] = np.empty((L, E, spec.dim), np.float32)
    gate = spec.gate_shape   # a retention layer's, after wo
    if gate:
        params["w_gate"] = np.empty((L, *gate), np.float32)

    def place(name, at, val):
        if isinstance(val, Q40Weight):
            params[name].qs[at] = val.qs
            params[name].d16[at] = val.d16
        else:
            params[name][at] = val

    for layer in range(L):
        for name, n in norms:
            params[name][layer] = w.f32((n,))
        for name, shape in shapes:
            place(name, layer, w.matmul(spec, shape))
            if gate and name == "wo":
                params["w_gate"][layer] = w.f32(gate)
        if E:
            params["moe_gate"][layer] = w.f32((E, spec.dim))
        for e in range(E):
            for name, shape in experts:
                place(name, (layer, e), w.matmul(spec, shape))

    params["rms_final"] = w.f32((spec.dim,))
    w.take(spec.rope_gap_bytes)  # legacy freq_cis region, skipped
    params["wcls"] = w.matmul(spec, (spec.vocab_size, spec.dim))

    if w.off != expected:
        raise ValueError(f"missed {expected - w.off} bytes")  # parity check
    return spec, params


def stack_of(params: dict, stack: str) -> dict:
    """The dict a ``TransformerSpec.layer_plans`` stack's tensors live in:
    the tree itself, or ``params[stack]`` ("dense", or a hybrid spec's
    layer kind; made on first use)."""
    return params.setdefault(stack, {}) if stack else params


def _load_planned_layers(spec: TransformerSpec, w: _Walker,
                         params: dict) -> None:
    """The layers of a spec with several kinds of layer, in ``layer_plans``
    order, each tensor streamed into its preallocated stack."""
    q40 = spec.weights_float_type == FloatType.Q40
    dtype = np.float16 if spec.weights_float_type == FloatType.F16 \
        else np.float32
    for stack, name, kind, shape in spec.stack_leaves():
        dst = stack_of(params, stack)
        if kind == "mm" and q40:
            *lead, dd, nn = shape
            dst[name] = Q40Weight(
                np.empty((*lead, dd, nn // 32, 16), np.uint8),
                np.empty((*lead, dd, nn // 32), np.float16))
        else:
            dst[name] = np.empty(shape, dtype if kind == "mm"
                                 else np.float32)
    for stack, at, entries in spec.layer_plans():
        dst = stack_of(params, stack)
        for kind, name, shape, *e in entries:
            val = w.f32(shape) if kind == "f32" else w.matmul(spec, shape)
            if isinstance(val, Q40Weight):
                dst[name].qs[(at, *e)] = val.qs
                dst[name].d16[(at, *e)] = val.d16
            else:
                dst[name][(at, *e)] = val


class TensorRange(NamedTuple):
    """One tensor's byte placement in the .bin: ``rows`` is the output dim
    for matmul tensors (whose contiguous row bands are what MatmulSlice
    shards — band r of S occupies bytes [offset + r*(nbytes/rows)*(rows/S),
    ...)), None for replicated tensors (norms, embedding) and the rope gap.
    """

    name: str
    layer: int | None
    offset: int
    nbytes: int
    rows: int | None


def tensor_byte_ranges(spec: TransformerSpec) -> list[TensorRange]:
    """The exact byte placement of every tensor in a .bin of ``spec`` —
    the offset table slice-granular weight streaming fetches against
    (io/stream.fetch_model_slices; the reference's root likewise computes
    per-slice offsets into its mmap, transformer.cpp:250-273). Walks the
    same order as load_model; the total is asserted == spec.file_size().
    """
    out: list[TensorRange] = []
    off = spec.header_bytes

    def add(name, layer, nbytes, rows=None):
        nonlocal off
        out.append(TensorRange(name, layer, off, nbytes, rows))
        off += nbytes

    add("tok_embedding", None, spec.vocab_size * spec.dim * 4)
    shapes = spec.layer_matmul_shapes()
    experts = spec.expert_matmul_shapes()
    layer = -1
    for stack, _, entries in (spec.layer_plans() if spec.planned else ()):
        # a mixer-kinds spec has two runs a layer: its FFN's follows
        layer += not ((spec.mixers or spec.kda) and stack in ("", "dense"))
        for kind, name, shape, *_ in entries:
            if kind == "f32":
                add(name, layer, 4 * int(np.prod(shape)))
            else:
                add(name, layer, spec.matmul_bytes(shape), rows=shape[0])
    for layer in range(0 if spec.planned else spec.n_layers):
        for name, n in spec.layer_norm_shapes():
            add(name, layer, n * 4)
        for name, shape in shapes:
            add(name, layer, spec.matmul_bytes(shape), rows=shape[0])
            if spec.retention and name == "wo":
                add("w_gate", layer, 4 * spec.gate_shape[0]
                    * spec.gate_shape[1])
        if experts:
            add("moe_gate", layer, spec.n_experts * spec.dim * 4)
        for _ in range(spec.n_experts):   # expert e's three, e ascending
            for name, shape in experts:
                add(name, layer, spec.matmul_bytes(shape), rows=shape[0])
    add("rms_final", None, spec.dim * 4)
    if spec.hybrid:
        add("rms_final_b", None, spec.dim * 4)
    add("_rope_gap", None, spec.rope_gap_bytes)
    add("wcls", None, spec.matmul_bytes((spec.vocab_size, spec.dim)),
        rows=spec.vocab_size)
    assert off == spec.file_size(), (off, spec.file_size())
    return out


# ---------------------------------------------------------------------------
# Writer
# ---------------------------------------------------------------------------

def _write_matmul(f, spec: TransformerSpec, x: np.ndarray) -> None:
    ft = spec.weights_float_type
    if ft == FloatType.F32:
        f.write(np.ascontiguousarray(x, dtype=np.float32).tobytes())
    elif ft == FloatType.F16:
        f.write(np.ascontiguousarray(x, dtype=np.float32)
                .astype(np.float16).tobytes())
    elif ft == FloatType.Q40:
        qs, d16 = quantize_q40(np.ascontiguousarray(x, dtype=np.float32))
        f.write(pack_q40_bytes(qs, d16))
    else:
        raise ValueError(f"unsupported weights float type {ft}")


def write_model(path: str, spec: TransformerSpec, tensors: dict) -> None:
    """Write a reference-format .bin from f32 logical tensors.

    ``tensors`` keys match load_model's output (stacked layer axis), values f32.
    """
    with open(path, "wb") as f:
        f.write(spec.header())
        f.write(np.ascontiguousarray(
            tensors["tok_embedding"], dtype=np.float32).tobytes())
        for stack, at, entries in (spec.layer_plans() if spec.planned
                                   else ()):
            src = tensors[stack] if stack else tensors
            for kind, name, _, *e in entries:
                val = src[name][(at, *e)]
                if kind == "f32":
                    f.write(np.ascontiguousarray(
                        val, dtype=np.float32).tobytes())
                else:
                    _write_matmul(f, spec, val)
        for layer in range(0 if spec.planned else spec.n_layers):
            for name, _ in spec.layer_norm_shapes():
                f.write(np.ascontiguousarray(
                    tensors[name][layer], dtype=np.float32).tobytes())
            for name, _ in spec.layer_matmul_shapes():
                _write_matmul(f, spec, tensors[name][layer])
                if spec.retention and name == "wo":
                    f.write(np.ascontiguousarray(
                        tensors["w_gate"][layer], dtype=np.float32).tobytes())
            if spec.n_experts:
                f.write(np.ascontiguousarray(
                    tensors["moe_gate"][layer], dtype=np.float32).tobytes())
            for e in range(spec.n_experts):
                for name, _ in spec.expert_matmul_shapes():
                    _write_matmul(f, spec, tensors[name][layer][e])
        f.write(np.ascontiguousarray(
            tensors["rms_final"], dtype=np.float32).tobytes())
        if spec.hybrid:
            f.write(np.ascontiguousarray(
                tensors["rms_final_b"], dtype=np.float32).tobytes())
        f.write(b"\x00" * spec.rope_gap_bytes)
        _write_matmul(f, spec, tensors["wcls"])
    # byte-exact invariant
    import os

    assert os.path.getsize(path) == spec.file_size()


def densify_params(params: dict) -> dict:
    """Dequantize/upcast a loaded param tree to dense float32 — the training
    entry point (parallel/train.py optimizes dense weights; Q40/F16 files
    are inference formats). Q40Weight leaves decode with the exact codec
    value map; F16 upcasts exactly."""
    from ..ops.quants import dequantize_q40

    out = {}
    for name, val in params.items():
        if isinstance(val, dict):       # a second stack of layers
            out[name] = densify_params(val)
        elif isinstance(val, Q40Weight):
            out[name] = dequantize_q40(val.qs, val.d16)
        elif isinstance(val, Q40Kernel):  # pre-tiled: go through the codec
            w = from_kernel_layout(val)
            out[name] = dequantize_q40(w.qs, w.d16)
        elif isinstance(val, Q40KernelNb):  # nb-major pre-tiled likewise
            w = from_kernel_layout_nb(val)
            out[name] = dequantize_q40(w.qs, w.d16)
        else:
            out[name] = np.asarray(val, dtype=np.float32)
    return out
