"""Matmul dispatch over weight dtypes + the norm/activation kernels.

This is the XLA-side equivalent of reference src/funcs.cpp: the dtype-dispatched
``matmul`` (funcs.cpp:269-299), ``rms``/``rmsnorm`` (funcs.cpp:43-90),
``softmax`` (funcs.cpp:12-41) and SwiGLU glue (transformer-tasks.cpp:369-379).
Kernels are written for XLA fusion (elementwise chains fuse into the matmuls);
the Pallas fast path for Q40 weights lives in ops/pallas_q40.py and is picked
by ``matmul`` when enabled.

Semantics contract (BASELINE.md logit parity):
* matmul: weight w of shape (d, n), out[i] = sum_j w[i,j] * x[..., j], f32
  accumulation.
* rms: 1/sqrt(sum(x^2)/size + 1e-5) — eps added AFTER the mean
  (funcs.cpp:60-62).
* rmsnorm(out, x, rms, w): out = x * rms * w.
* silu(x) = x / (1 + e^-x).
"""

from __future__ import annotations

import functools

import contextlib
import contextvars
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.loader import (Q40Kernel, Q40KernelNb, Q40KernelNbI4, Q40Weight,
                         from_kernel_layout, to_kernel_layout,
                         to_kernel_layout_nb)
from ..obs.spans import startup_phase
from .quants import dequantize_q40_jax, dequantize_q80_jax, quantize_q80_jax

RMS_EPS = 1e-5

# trace-time matmul precision mode. "parity" = f32 accumulation at HIGHEST
# (the logit-parity contract; the Q40 MXU tile reaches the same products in
# five bf16 passes, ops/pallas_q40._five_pass_dot); "bf16" = bf16 MXU passes
# with f32 accumulation
# — ~3-6x the matmul throughput at a documented tolerance, used for the
# opt-in fast-prefill path (--fast-prefill) where T is large and the outputs
# only seed the KV cache. Read when a program is TRACED, so the mode must be
# active inside the jitted function being built (Engine wraps its prefill
# step in matmul_precision("bf16")); compiled parity programs are untouched.
_MATMUL_MODE = contextvars.ContextVar("dllama_matmul_mode", default="parity")


@contextlib.contextmanager
def matmul_precision(mode: str):
    if mode not in ("parity", "bf16"):
        raise ValueError(f"unknown matmul precision mode {mode!r}")
    token = _MATMUL_MODE.set(mode)
    try:
        yield
    finally:
        _MATMUL_MODE.reset(token)


def matmul_mode() -> str:
    return _MATMUL_MODE.get()


# Which rows of the decode dispatch being TRACED are live (a trace-time
# context like the mode above, but it holds data of the program: the rows a
# step's staged block marks). The step program sets it around its forward
# (runtime/continuous._with_pick); a Q40 call on exactly those rows reads it
# (ops/pallas_q40._q40_matmul_nbmajor picks its body by the live count). A
# forward traced outside it (the chain, verify, a chunk, ``inference``, a
# mesh's program) traces what it always did.
_LIVE_ROWS = contextvars.ContextVar("dllama_live_rows", default=None)


@contextlib.contextmanager
def live_rows(mask: jax.Array):
    """Trace the forward of a decode dispatch whose rows ``mask`` (B,)
    marks live (> 0) or dead: a free, paused or cancelled row, whose result
    nobody reads. The census is taken once, here."""
    from .pallas_q40 import live_census

    token = _LIVE_ROWS.set((mask.shape[0], live_census(mask)))
    try:
        yield
    finally:
        _LIVE_ROWS.reset(token)


def dispatch_live_rows(t: int):
    """``live_census`` of the dispatch being traced if its forward gave one
    and it has ``t`` rows (a call on anything else is not on those rows);
    else None."""
    got = _LIVE_ROWS.get()
    return got[1] if got is not None and got[0] == t else None


def bf16_prefill(fn):
    """Wrap a forward so it TRACES under bf16 matmul precision — THE one
    fast-prefill wrapper (Engine and ContinuousEngine both build their
    prefill programs through this, so the precision protocol lives in one
    place). Works on raw or already-jitted ``fn``: a jitted fn traces on
    first call, and the context is active around every call."""

    def wrapped(*args):
        with matmul_precision("bf16"):
            return fn(*args)

    return wrapped


class StackedQ40(NamedTuple):
    """A view of one layer inside a stacked Q40Kernel: the weight stays in
    its (L, ...) stacked array and the Pallas kernel DMAs layer ``layer``
    directly via scalar prefetch. This is how ``lax.scan`` over layers avoids
    materializing a per-step copy of each layer's packed weights (XLA's
    dynamic-slice before a pallas_call would triple weight HBM traffic)."""

    w: Any       # stacked Q40Kernel, qs_t (L, 16, d, nb)
    layer: Any   # traced scalar int32


def rms_inv(x: jax.Array, eps: float = RMS_EPS) -> jax.Array:
    """The reference's ``rms()``: inverse RMS with eps added after the mean."""
    ss = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    ss = ss / x.shape[-1] + eps
    return jax.lax.rsqrt(ss)


def rmsnorm(x: jax.Array, weight: jax.Array,
            eps: float = RMS_EPS) -> jax.Array:
    return (x * rms_inv(x, eps)) * weight


def silu(x: jax.Array) -> jax.Array:
    return x / (1.0 + jnp.exp(-x))


def polynorm(z: jax.Array, pn_w: jax.Array, scale: float, clamp: float,
             eps: float) -> jax.Array:
    """``scale * (w0 n(z^3) + w1 n(z^2) + w2 n(z) + clip(b, -clamp,
    clamp))``, ``n(u) = u / sqrt(mean(u^2) + eps)`` over z's last dim (an
    FFN's own width: a (token, expert) pair's needs nothing of another
    expert) and ``pn_w`` = (w0, w1, w2, b) (arXiv:2411.03884)."""
    from ..obs.spans import SCOPE_POLYNORM

    with jax.named_scope(SCOPE_POLYNORM):
        z = z.astype(jnp.float32)
        z2 = z * z
        bias = jnp.clip(pn_w[3], -clamp, clamp) if clamp else pn_w[3]
        out = sum(pn_w[i] * u * rms_inv(u, eps)
                  for i, u in enumerate((z2 * z, z2, z)))
        return jnp.float32(scale) * (out + bias)


def relu2(x: jax.Array) -> jax.Array:
    """``relu(x)^2``."""
    r = jnp.maximum(x, 0.0)
    return r * r


def ffn_activation(spec, lw):
    """What a layer's FFN applies to its gate projection (a gated FFN's:
    before the product with the up projection) or to its one up projection
    (``spec.activation.gated`` false): ``silu``, ``relu2``, or the spec's
    PolyNorm over the layer's ``pn_w`` (``TransformerSpec.activation``).
    The ONE place the four FFN sites (models/llama._swiglu's two,
    ops/pallas_moe's slot and XLA expert paths) take it from."""
    act = spec.activation
    if act.kind == "silu":
        return silu
    if act.kind == "relu2":
        return relu2
    return functools.partial(polynorm, pn_w=lw["pn_w"], scale=act.scale,
                             clamp=act.clamp, eps=spec.norm_eps)


def gated_product(spec, lw, prefix: str = ""):
    """``(gate, up) -> act(gate) * up`` of a gated FFN's two projections,
    ``act`` the layer's ``ffn_activation``. Where the layer carries
    ``ffn_limit`` (2,) (``spec.activation.limits``: a kda spec's expert
    layers), the gate is clamped above and the up projection both ways at
    L = ffn_limit[0] (the routed experts) or ffn_limit[1] (the shared
    expert, ``prefix`` "sh_") first; L = 0 is no clamp. The ONE place the
    gated FFN sites take the product from."""
    act = ffn_activation(spec, lw)
    limit = lw.get("ffn_limit")
    if limit is None:
        return lambda gate, up: act(gate) * up
    cap = limit[int(prefix == "sh_")]
    cap = jnp.where(cap > 0, cap, jnp.inf)
    return lambda gate, up: act(jnp.minimum(gate, cap)) * jnp.clip(
        up, -cap, cap)


def dequantize_weight(w) -> jax.Array:
    """Materialize any weight representation as f32 (d, n)."""
    if isinstance(w, StackedQ40):
        w = jax.tree_util.tree_map(lambda a: a[w.layer], w.w)
    if isinstance(w, Q40KernelNbI4):
        from .pallas_q40 import _dequant_i4

        return _dequant_i4(w)
    if isinstance(w, Q40KernelNb):
        from .pallas_q40 import _dequant_nb

        return _dequant_nb(jnp.asarray(w.qs_t), jnp.asarray(w.scale))
    if isinstance(w, Q40Kernel):
        w = from_kernel_layout(w)
    if isinstance(w, Q40Weight):
        return dequantize_q40_jax(w.qs, w.d16)
    return jnp.asarray(w).astype(jnp.float32)


def q40_kernel_mode() -> str:
    """'pallas' (fused HBM-packed kernel) or 'xla' (dequantize-then-dot).

    DLLAMA_Q40_KERNEL=pallas|xla|auto overrides; auto = pallas on TPU, xla
    elsewhere (the kernel still runs in interpret mode off-TPU when forced,
    which is what the parity tests do).
    """
    env = os.environ.get("DLLAMA_Q40_KERNEL", "auto")
    if env == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return env


def matmul(w, x: jax.Array, *, prefer_pallas: bool = False) -> jax.Array:
    """out[..., d] = w(d, n) @ x[..., n] with f32 accumulation.

    ``w`` may be a dense array (f32/f16/bf16) or a planar ``Q40Weight``. The
    dense path lets XLA drive the MXU directly; the Q40 path either calls the
    Pallas fused-dequant kernel (HBM traffic = packed bytes; the default on
    TPU) or dequantizes inline and dots (the XLA fallback).
    """
    if isinstance(w, StackedQ40):
        from .pallas_q40 import q40_matmul  # packing implies kernel support

        return q40_matmul(w.w, x, layer=w.layer)
    if isinstance(w, (Q40KernelNb, Q40KernelNbI4)):
        from .pallas_q40 import q40_matmul  # dedicated dispatches

        return q40_matmul(w, x)
    if isinstance(w, (Q40Weight, Q40Kernel)) and (
            prefer_pallas or q40_kernel_mode() == "pallas"):
        from .pallas_q40 import kernel_supports, q40_matmul  # lazy

        if kernel_supports(w.logical_shape[-2], w.logical_shape[-1]):
            return q40_matmul(w, x)
        # fall through: dims the matvec tiler can't place at all (large d
        # with no multiple-of-8 divisor) take the dequantize-then-dot path
        # below; supported dims with awkward T combos fall back INSIDE
        # q40_matmul instead
    wf = dequantize_weight(w)
    if matmul_mode() == "bf16":
        # fast-prefill mode: bf16 MXU passes, f32 accumulation
        return jnp.einsum("dn,...n->...d", wf.astype(jnp.bfloat16),
                          x.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    # HIGHEST: true f32 MXU accumulation — required for the 1e-5 logit-parity
    # contract on TPU (default TPU precision is bf16-input). The quantized
    # fast path (Pallas) has its own precision story.
    return jnp.einsum("dn,...n->...d", wf, x.astype(jnp.float32),
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


class Q40Layout(NamedTuple):
    """How one engine's Q40 leaves are laid out and which decode body its
    fused chain runs: the ONE value ``q40_body_policy`` resolves for a
    model and a dispatch width. Whoever builds an engine resolves it once
    and hands it down as an argument (loader, sidecar key, packer, chain
    builder); nothing reads it back from the process."""

    label: str    # "i4-nb" | "nb-major" | "nb-major-pad8" | "d-major"
    reason: str

    @property
    def pad_blocks(self) -> int:
        """> 0 ("nb-major-pad8": 8): every nb-major Q40 leaf's blocks a row
        are padded to a multiple of it with zero blocks when it is packed
        (``pack_q40_params``), and a matmul pads its input with zeros to
        match (ops/pallas_q40._q40_matmul_nbmajor, ops/pallas_moe.
        _experts_slots): the chip stores a uint8 array whose second-minor
        dim is off the 8 grid with ANOTHER dim there, and every step then
        copies the leaf row-major before its kernel call (6.3 GiB of
        temporaries in an ssd spec's step at hidden 2,688 = 84 blocks:
        benchmark/tools/rehearse_nemotron.py, PR 55)."""
        return 8 if self.label == "nb-major-pad8" else 0

    @property
    def force_nb_major(self) -> bool:
        """Every leaf the nb-major row tiler places packs nb-major (the i4
        body exists only there, so pad-free 7B-class shapes need it)."""
        return self.label in ("i4-nb", "nb-major", "nb-major-pad8")

    @property
    def i4_chain(self) -> bool:
        """The fused decode chain converts nb-major leaves to signed-int4
        planes at its start (ops/pallas_q40.chain_weight_prep)."""
        return self.label == "i4-nb"


# the stock per-leaf picks, u8 bodies: a sharded engine's value, and what a
# packer gets that is handed nothing and finds no shim
Q40_STOCK = Q40Layout("d-major", "stock per-leaf layout picks")

MOE_TP_REFUSAL = (
    "expert (mixture-of-experts) models run on one chip only: placing "
    "experts across tensor-parallel ranks is not implemented, so --tp > 1 "
    "(or any sharded mesh) refuses them")


MIXERS_TP_REFUSAL = (
    "a mixer-kinds model (window and full grouped-query attention layers, "
    "each kind with its own head count; models/laguna.py) runs on one chip "
    "only: neither its rings, its two shapes of wq / wo nor its full "
    "layers' pools are placed over tensor-parallel ranks, so --tp > 1 (or "
    "any sharded mesh) refuses it")


def q40_leaf_layout(d: int, nb: int, *, tp: int = 1,
                    layout: Q40Layout = Q40_STOCK,
                    allow_nb_major: bool = True, key: str = "") -> str:
    """THE layout rule of one Q40 leaf: ``"nb-major"``, ``"d-major"`` or
    ``"codec"``, from its shard-LOCAL shape ``(d, nb)`` (what the kernel
    tiles inside shard_map and what each chip stores), the tensor-parallel
    degree and the model's resolved ``layout``. The width of a decode
    dispatch is no input: every width has an nb-major kernel
    (ops/pallas_q40._q40_matmul_nbmajor).

    * A routed-expert stack (``key`` ``moe_*``): nb-major where both
      grouped kernels (ops/pallas_moe) place it, else codec (the XLA scan);
      ``moe_w1`` / ``moe_w3`` are fused along d afterwards, so twice their
      width must place too (a non-gated stack has no ``moe_w3`` and is
      never fused; twice a width that places, places).
    * Sharded (``tp > 1``): nb-major iff the local ``nb`` is off the 128
      grid. The chip stores an
      array whose minor dim is not a multiple of 128 with the second-minor
      dim minor instead: a d-major shard ``(16, d, nb)`` then lies d-minor
      in HBM, the Pallas call wants it row-major, and XLA copies (and pads)
      every such leaf at the top of EVERY step program, 22.9 ms of a
      37.2 ms step at Yi-34B tp=4 (ledger, PR 24). Packed nb-major the
      logical order IS that physical order. The one-chip padding test is
      the wrong one here: nb 224 pads by 1.14 and is copied all the same.
    * One chip: nb-major iff the model's layout forces it (its width was
      judged there, once, for the whole model) or d-major's lane padding of
      ``nb`` would inflate the packed bytes materially (13B: nb 160 -> 1.6x
      HBM and reads). ``allow_nb_major=False`` is the caller saying that
      its ``tp == 1`` is not one chip (an sp > 1 mesh): d-major or codec.

    nb-major needs the row tiler to place ``d``; d-major needs the matvec
    tiler; what neither places stays codec and takes the XLA fallback in
    ``matmul`` (packed, it would pay a re-transpose inside every step)."""
    from .pallas_q40 import _pick_rows_nb, kernel_supports

    if key.startswith("moe_"):
        from .pallas_moe import shape_places

        fused_ok = key == "moe_w2" or shape_places(2 * d, nb)
        return "nb-major" if fused_ok and shape_places(d, nb) else "codec"
    if tp > 1:
        wants_nb = nb % 128 != 0
    else:
        pad_ratio = (nb + (-nb % 128)) / nb  # lane padding of nb-minor
        wants_nb = allow_nb_major and (layout.force_nb_major
                                       or pad_ratio > 1.25)
    if wants_nb and _pick_rows_nb(d, nb) is not None:
        return "nb-major"
    return "d-major" if kernel_supports(d, nb * 32) else "codec"


def _pad_blocks(w: Q40Weight, multiple: int) -> Q40Weight:
    """``w`` with its blocks a row padded to a multiple of ``multiple`` by
    zero blocks (codes 0 under a delta of 0: they add 0 whatever the input
    holds there)."""
    pad = -w.qs.shape[-2] % multiple
    if not pad:
        return w
    lead = [(0, 0)] * (w.qs.ndim - 2)
    return Q40Weight(np.pad(w.qs, lead + [(0, pad), (0, 0)]),
                     np.pad(w.d16, lead + [(0, pad)]))


def _pad_plain_experts(params: dict, blocks: int) -> dict:
    """A NON-GATED expert stack (``moe_w1`` and ``moe_w2`` with no
    ``moe_w3``) on the grid of ``blocks`` blocks: its hidden width padded
    to whole 128-lane tiles of ``moe_w1``'s rows that are whole groups of
    ``blocks`` blocks of ``moe_w2``'s (256 at 8), and ``moe_w1``'s own
    blocks a row to a multiple of ``blocks`` in the same copy: zero rows
    and zero blocks, so that the slot kernels tile it (ops/pallas_moe.
    shape_places). Exact: the padded hidden values are act(0) = 0 for
    ``relu2`` and meet zero weights. A gated stack is left as it is (its
    widths are on the grid in every model here, and PolyNorm's mean runs
    over the width)."""
    w1, w2 = params.get("moe_w1"), params.get("moe_w2")
    if ("moe_w3" in params or not isinstance(w1, Q40Weight)
            or not isinstance(w2, Q40Weight)):
        return params
    rows = -w1.qs.shape[-3] % max(128, 32 * blocks)
    if not rows:
        return params
    lead = [(0, 0)] * (w1.qs.ndim - 3)
    nb = (0, -w1.qs.shape[-2] % blocks)
    return dict(
        params,
        moe_w1=Q40Weight(np.pad(w1.qs, lead + [(0, rows), nb, (0, 0)]),
                         np.pad(w1.d16, lead + [(0, rows), nb])),
        moe_w2=_pad_blocks(w2, blocks))


@startup_phase("pack")
def pack_q40_params(params: dict, enable: bool | None = None,
                    tp: int = 1, allow_nb_major: bool = False,
                    input_sharded=(),
                    layout: Q40Layout | None = None) -> dict:
    """Re-tile every Q40Weight in a param tree to the kernel layout, once.

    ``enable=None`` means "iff the Pallas kernel will be used" — so CPU/test
    runs keep the codec layout and the golden-parity paths are untouched.
    Each leaf's layout is ``q40_leaf_layout``'s answer on its shard-LOCAL
    shape: ``tp`` is the tensor-parallel degree the weights will be sharded
    to, ``input_sharded`` names the keys the fused tp scheme shards along
    the INPUT dim (wo/w2 — parallel/tp.py: local shape (d, n/tp) instead of
    (d/tp, n)), ``layout`` the model's resolved value
    (engines pass theirs; a by-hand packer that passes none gets what
    ``apply_q40_body_policy`` last recorded, else the stock picks).
    ``allow_nb_major`` defaults to off: tp == 1 does not imply one chip (an
    sp > 1 mesh packs with tp=1), so the truly-single-chip callers opt in.
    Call this at load time, before device_put; never inside a jitted step.
    Its seconds are the start-up account's ``pack`` (obs/spans).
    """
    if enable is None:
        enable = q40_kernel_mode() == "pallas"
    if not enable:
        return params
    if layout is None:
        layout = _APPLIED_LAYOUT or Q40_STOCK  # the shim's ONE reader
    pad = layout.pad_blocks if tp == 1 else 0
    given = params
    if pad:
        params = _pad_plain_experts(params, pad)

    def pick(k, v):
        if isinstance(v, dict):     # a second stack of layers ("dense")
            return pack_q40_params(v, enable, tp, allow_nb_major,
                                   input_sharded, layout)
        if not isinstance(v, Q40Weight):
            return v
        d, n = v.logical_shape[-2], v.logical_shape[-1]
        if k.startswith("moe_") and tp > 1:
            raise ValueError(MOE_TP_REFUSAL)
        if k in input_sharded and tp > 1:
            # fused-scheme wo/w2: full output rows, 1/tp of the input
            # blocks per shard — the nb axis is the sharded one, so the
            # local block count must stay whole (shard_params validated
            # divisibility already; re-check defensively)
            if (n // 32) % tp:
                raise ValueError(
                    f"{k}: input-dim sharding needs n/tp to be a "
                    f"32-multiple, got n={n} tp={tp}")
            d_loc, n_loc = d, n // tp
        elif d % tp:
            return v
        else:
            d_loc, n_loc = d // tp, n
        judge = functools.partial(q40_leaf_layout, d_loc, tp=tp,
                                  layout=layout, key=k,
                                  allow_nb_major=allow_nb_major)
        nb = n_loc // 32
        if pad and nb % pad and judge(nb + -nb % pad) == "nb-major":
            # zero blocks up to the grid (nb-major leaves alone: their
            # matmul pads its input to match)
            return to_kernel_layout_nb(_pad_blocks(v, pad))
        kind = judge(nb)
        if kind == "nb-major":
            return to_kernel_layout_nb(v)
        return to_kernel_layout(v) if kind == "d-major" else v

    out = {k: pick(k, v) for k, v in params.items()}
    moe = [k for k, v in given.items()
           if k.startswith("moe_w") and isinstance(v, Q40Weight)]
    if pad and not all(isinstance(out[k], Q40KernelNb) for k in moe):
        # an expert's leaves pack for the slot kernels together or stay
        # codec (and unpadded) together: ops/pallas_moe.moe_ffn takes one
        # path for both
        out.update({k: given[k] for k in moe})
    return out


class RankMajor:
    """One plane (``qs_t`` or ``scale``) of a fused leaf laid RANK-MAJOR
    over ``ranks`` tensor-parallel ranks: rank r's contiguous band of
    ``axis`` (the output rows) is ``[member 0's r-th band | member 1's |
    ...]``, so a contiguous ``P("tp")`` cut hands every rank the rows
    ``parallel/tp._tp_qkv`` / ``_swiglu_local`` split (``[q_r | k_r |
    v_r]``, ``[w1_r | w3_r]``). The concat is taken a rank, never over the
    whole leaf: the whole plane exists only as a ``shape``, and ``band``
    copies a rank's members' bands ONCE, into the buffer that is placed
    (``parallel/tp.shard_params``'s one copy a shard). With one rank the
    only band is the plain concat."""

    def __init__(self, parts, axis: int, ranks: int):
        self.parts, self.ranks = tuple(parts), ranks
        first = self.parts[0]
        self.axis = axis % first.ndim
        rest = {p.shape[:self.axis] + p.shape[self.axis + 1:]
                for p in self.parts}
        if len(rest) != 1 or any(p.shape[self.axis] % ranks
                                 for p in self.parts):
            raise ValueError(
                f"members of shapes {[p.shape for p in self.parts]} do not "
                f"fuse along axis {axis} over {ranks} ranks")
        self.dtype, self.ndim = first.dtype, first.ndim
        self.widths = [p.shape[self.axis] // ranks for p in self.parts]
        self.band_shape, self.shape = (
            (*first.shape[:self.axis], bands * sum(self.widths),
             *first.shape[self.axis + 1:]) for bands in (1, ranks))

    def rank_of(self, idx) -> int:
        """The rank whose band the index ``idx`` of the whole plane is (a
        shard's, as ``jax.make_array_from_callback`` hands it over)."""
        band = sum(self.widths)
        cut = idx[self.axis]
        if (cut.start is None or cut.start % band
                or cut.stop - cut.start != band):
            raise ValueError(
                f"a rank-major plane {self.shape} of {self.ranks} bands of "
                f"{band} on axis {self.axis} is cut a rank's band at a "
                f"time, not {idx}")
        return cut.start // band

    def band(self, rank: int, rows: slice = slice(None), out=None):
        """Rank ``rank``'s band, rows ``rows`` of its leading axis (whole
        by default; ``parallel/tp.shard_params`` threads over them), into
        ``out`` where given."""
        at = [slice(None)] * self.ndim
        at[0] = rows
        pieces = []
        for part, width in zip(self.parts, self.widths):
            at[self.axis] = slice(rank * width, (rank + 1) * width)
            pieces.append(part[tuple(at)])
        return np.concatenate(pieces, axis=self.axis, out=out)


def _row_tiles(leaf, d: int) -> tuple:
    """Which dispatch widths of a kernel leaf of ``d`` (shard-local) rows
    have a row tile, in its layout and at its blocks a row: one row, a
    decode dispatch's 8, a chunk's 128. The rest dequantize and dot
    (ops/pallas_q40.q40_matmul)."""
    from .pallas_q40 import (_pick_block_rows, _pick_block_t, _pick_planes,
                             _pick_rows_mxu, _pick_rows_t1)

    if isinstance(leaf, Q40KernelNb):
        nb = leaf.qs_t.shape[-2]
        tiles = [_pick_rows_t1(d, nb)]
        for t in (8, 128):
            block_t = _pick_block_t(t, nb)
            tiles.append(_pick_rows_mxu(d, nb, block_t,
                                        _pick_planes(nb, block_t)))
    else:
        nb = leaf.qs_t.shape[-1]
        tiles = [_pick_block_rows(d, t, nb, _pick_block_t(t, nb))
                 for t in (1, 8, 128)]
    return tuple(rows is not None for rows in tiles)


def fuse_q40_layer_matmuls(params: dict, ranks: int = 1) -> dict:
    """Fuse the stacked Q40 qkv (and w1/w3) kernel leaves along the output
    dim into single leaves ``wqkv`` / ``w13``, host-side, at load: with one
    rank (one chip; a rank's own band tree, parallel/shard_sim) the plain
    concat, over ``ranks`` tensor-parallel ranks the RANK-MAJOR one, whose
    planes are ``RankMajor`` values that ``parallel/tp.shard_params``
    assembles shard by shard as it places them.

    The three qkv matmuls (and the two SwiGLU input matmuls) share the same
    input vector; one wide kernel call replaces three (two) narrow ones,
    which matters for single-token decode: a call pays about 3.8 us of
    pipeline ends whatever it streams (PERF.md section 7), and the narrow
    ones stream little else. Row-wise the math is unchanged: outputs are
    split back by models/llama and parallel/tp (the reference computes the
    same three matmuls back to back, transformer-tasks.cpp:167-179).

    A group fuses only where every member is the same kernel layout
    (``Q40Kernel`` or ``Q40KernelNb``, i.e. after ``pack_q40_params``; a
    codec leaf or a dense array passes through), every member's rows divide
    over the ranks, the fused shard-local width has a row tile for the
    one-row body, and it has one at a decode dispatch's and a chunk's rows
    wherever every member has (``_row_tiles``): a fused leaf never falls to
    dequantize-then-dot where its members did not. Else the group stays as
    it is and the forwards take their unfused branch.
    """
    out = {k: fuse_q40_layer_matmuls(v, ranks) if isinstance(v, dict) else v
           for k, v in params.items()}

    def fuse(dst, keys):
        ws = [out.get(k) for k in keys]
        if all(isinstance(w, Q40Kernel) and w.qs_t.ndim == 4 for w in ws):
            axis = -2       # d-major: qs_t (L, 16, d, nb), scale (L, d, nb)
        elif all(isinstance(w, Q40KernelNb) and w.qs_t.ndim in (4, 5)
                 for w in ws):
            axis = -1       # nb-major: the output dim d is MINOR (an
            #                 expert stack has one more leading axis)
        else:
            return
        local = [w.scale.shape[axis] // ranks for w in ws]
        if any(w.scale.shape[axis] % ranks for w in ws):
            return
        fused = _row_tiles(ws[0], sum(local))
        members = [_row_tiles(w, d) for w, d in zip(ws, local)]
        if not fused[0] or any(all(m) and not f
                               for f, *m in zip(fused, *members)):
            return
        # host numpy tree by contract (runs after pack_q40_params, before
        # device placement)
        planes = (RankMajor([w.qs_t for w in ws], axis, ranks),
                  RankMajor([w.scale for w in ws], axis, ranks))
        out[dst] = type(ws[0])(*(p.band(0) if ranks == 1 else p
                                 for p in planes))
        for k in keys:
            del out[k]

    fuse("wqkv", ("wq", "wk", "wv"))
    fuse("w13", ("w1", "w3"))
    fuse("moe_w13", ("moe_w1", "moe_w3"))
    fuse("sh_w13", ("sh_w1", "sh_w3"))       # a shared expert's
    return out


# The in-chain i4 conversion transiently holds an extra ~half of the packed
# bytes while the chain runs, which OOMed 13B on a 16 GB chip (PARITY.md
# round-5 table): the i4 body is picked only for a model whose packed
# weights stay under this many GB (between 7B's ~4.2 and 13B's ~7.8).
Q40_I4_MAX_PACKED_GB = 6.0


def q40_body_policy(spec, rows: int = 1, sharded: bool = False) -> Q40Layout:
    """Resolve the Q40 layout and decode body of a model: a pure function
    of the spec and the kernel mode. Unpacks as (label, reason). ``rows``,
    the width of one decode dispatch (1 for plain ``inference``, the slot
    count for ``serve`` / ``--continuous``), is what every engine and
    driver passes and decides nothing: an nb-major leaf has a kernel at
    every width (ops/pallas_q40._q40_matmul_nbmajor), so ``serve`` at its
    default 8 slots packs as ``inference`` does. A ``sharded`` engine (a tp
    or sp mesh) gets the stock value: ``q40_leaf_layout`` judges each of
    its leaves on the shard-local shape, and the i4 chain body stays off.

    On the attached v5e at 7B (my chip run, PR 21; PERF.md): the fused
    chain runs 8.15 ms/token with the int4-plane body on forced nb-major
    layout against 8.60 d-major and 8.92 nb-major u8, and the per-token
    ``inference`` step 11.1 ms nb-major against 14.0 d-major (d-major's w2,
    nb = 344, is placed transposed by the device client and copied
    row-major inside every step).

    A slot-and-pages spec (``spec.slotted``) gets ``nb-major`` where any of
    its leaves has a block count off the 128 grid, else the stock picks.
    An expert spec gets ``nb-major`` (every dense leaf the row tiler
    places forced nb-major, u8 bodies) where the stock picks would leave
    one of its dense leaves d-major at an ``nb`` off the 128 grid. Else
    ``i4-nb`` iff ALL of (else ``d-major``: the stock per-leaf picks, u8
    bodies):
      * the Pallas kernel path is active (TPU; elsewhere layouts are moot),
      * every matmul leaf places on the nb-major row tiler (the i4 body is
        nb-major-only — pad-free 7B-class shapes need the forced layout),
      * the packed weights leave the conversion its headroom
        (``Q40_I4_MAX_PACKED_GB``),
      * the spec has no routed experts (no expert kernel has an i4 body).
    """
    if sharded:
        return Q40_STOCK
    if q40_kernel_mode() != "pallas":
        return Q40Layout("d-major",
                         "XLA matmul path (no Pallas kernels here)")
    from .pallas_q40 import _pick_rows_nb

    del rows  # every dispatch width has an nb-major kernel
    counted = spec.matmul_shape_counts()     # a layer's, experts included
    shapes = [shape for shape, _ in counted]
    shapes.append((spec.vocab_size, spec.dim))  # wcls
    if spec.slotted:
        # the i4 body runs in fused chains only, which a slot's state
        # refuses: a slot-and-pages spec packs u8 bodies, and ONE layout,
        # nb-major, as soon as a leaf has a block count off the 128 grid (a
        # d-major leaf there would be stored transposed and copied every
        # step: see the expert case below). 80, 160 and 320 blocks a row at
        # a hybrid spec's published widths; 16, 64 and 192 beside 256 at a
        # mixer-kinds spec's
        off = sorted({n // 32 for _, n in shapes if (n // 32) % 128})
        if off and any(nb % 8 for nb in off):
            # ... and a uint8 plane whose blocks a row are off the 8 grid
            # is stored with another dim second-minor and copied every step
            # all the same: such leaves pack with zero blocks up to it
            return Q40Layout("nb-major-pad8", (
                f"slot-and-pages spec with leaves of {off} blocks a row, "
                f"off the 128 grid and some off the 8 grid: every leaf the "
                f"row tiler places packs nb-major, u8 bodies, its blocks a "
                f"row padded to a multiple of 8 with zero blocks"))
        if off:
            return Q40Layout("nb-major", (
                f"slot-and-pages spec with leaves of {off} blocks a row, "
                f"off the 128 grid: every leaf the row tiler places packs "
                f"nb-major, u8 bodies"))
        return Q40Layout("d-major", (
            "slot-and-pages spec, every block count on the 128 grid: the "
            "stock picks, u8 bodies"))
    if spec.n_experts:
        # no expert kernel has an i4 body, so an expert spec's label says
        # only how its DENSE leaves pack. The stock picks leave a leaf
        # d-major while lane padding costs it under a quarter (nb 224 pads
        # to 256), but the chip stores a minor dim off the 128 grid
        # transposed, and the step then copies such a leaf row-major every
        # time it runs (nb 344: PR 21; nb 224 sharded: PR 24; nb 448: PR
        # 32). A dense spec escapes that by i4-nb; an expert spec with such
        # a leaf forces every dense leaf the row tiler places nb-major.
        dense = [shape for _, shape in (spec.layer_matmul_shapes()
                                        + spec.dense_layer_matmul_shapes())]
        copied = [(d, n) for d, n in dense + shapes[-1:]
                  if (n // 32) % 128 and _pick_rows_nb(d, n // 32) is not None
                  and q40_leaf_layout(d, n // 32) == "d-major"]
        if copied:
            d, n = copied[0]
            return Q40Layout("nb-major", (
                f"expert spec: the stock picks would leave shape {(d, n)} "
                f"d-major at nb {n // 32}, off the 128 grid, which the chip "
                f"stores transposed and every step copies row-major: every "
                f"dense leaf the row tiler places packs nb-major, u8 bodies "
                f"(expert stacks always pack nb-major)"))
    bad = [(d, n) for d, n in shapes if _pick_rows_nb(d, n // 32) is None]
    if bad:
        return Q40Layout("d-major", (
            f"shape {bad[0]} has no nb-major row tiling (rows must divide "
            f"by 128)"))
    packed_gb = (spec.n_layers * sum(c * d * (n // 32) * 18
                                     for (d, n), c in counted)
                 + spec.vocab_size * (spec.dim // 32) * 18) / 1e9
    if packed_gb > Q40_I4_MAX_PACKED_GB:
        return Q40Layout("d-major", (
            f"~{packed_gb:.1f} GB packed exceeds the "
            f"{Q40_I4_MAX_PACKED_GB:.0f} GB i4-conversion headroom gate "
            f"(13B-class OOM, BASELINE.md r5)"))
    if spec.n_experts:
        return Q40Layout("d-major", (
            f"expert spec, ~{packed_gb:.1f} GB packed: the stock picks "
            f"stand (expert stacks always pack nb-major) and the i4 chain "
            f"body has no expert kernel"))
    return Q40Layout("i4-nb", (
        f"auto: shapes place nb-major, ~{packed_gb:.1f} GB packed fits the "
        f"i4 headroom gate"))


def _dense_matmul_shapes(spec) -> list[tuple[int, int]]:
    """(d, n) of a one-chip model's dense matmul tensors: a layer's as the
    file has them (fusing along ``d`` changes no block count), a leading
    dense layer's, and the classifier; routed experts run the expert
    kernels."""
    return [shape for _, shape in (spec.layer_matmul_shapes()
                                   + spec.dense_layer_matmul_shapes())
            ] + [(spec.vocab_size, spec.dim)]


def t1_bodies(spec, layout: Q40Layout) -> str:
    """``t1 mxu M/N``: of a one-chip model's N dense matmul tensors (a
    layer's as the file has them, which fusing along ``d`` does not change,
    and the classifier; routed experts run the expert kernels), the M whose
    one-row dispatch takes the MXU matvec body
    (ops/pallas_q40._matvec_body_nb_mxu: an nb-major leaf whose block count
    is a multiple of 8); the others take a vector body or the XLA fallback.
    Static, like the body itself: shapes decide at trace time."""
    from .pallas_q40 import _t1_mxu

    shapes = _dense_matmul_shapes(spec)
    mxu = sum(q40_leaf_layout(d, n // 32, layout=layout) == "nb-major"
              and _t1_mxu(n // 32) for d, n in shapes)
    return f"t1 mxu {mxu}/{len(shapes)}"


def tile_planes(spec, layout: Q40Layout, rows: int) -> str:
    """``tile planes-a-dot: 8 x 7 leaves, 4 x 1, 2 x 1, 1 x 0``: of the dense
    matmul tensors ``t1_bodies`` counts, those that pack nb-major, by how
    many nibble planes one dot of the T > 1 MXU tile contracts over at a
    decode dispatch of ``rows`` rows (ops/pallas_q40._pick_planes: a pure
    function of the leaf's blocks a row and the rows of a t-tile), most
    planes first and a dot a plane always listed. Static, like the tile
    itself: shapes decide at trace time."""
    from .pallas_q40 import _PLANES, _pick_block_t, _pick_planes

    t = -(-rows // 8) * 8            # a dispatch pads to whole sublane tiles
    count = dict.fromkeys(_PLANES, 0)
    for d, n in _dense_matmul_shapes(spec):
        if q40_leaf_layout(d, n // 32, layout=layout) == "nb-major":
            count[_pick_planes(n // 32, _pick_block_t(t, n // 32))] += 1
    parts = [f"{g} x {c}" for g, c in sorted(count.items(), reverse=True)
             if c or g == 1]
    return "tile planes-a-dot: " + ", ".join(
        [parts[0] + " leaves", *parts[1:]])


def announce_q40_layout(layout: Q40Layout, spec=None, rows: int = 1) -> None:
    """The record of a pick: one stderr line, printed unconditionally even
    for quiet callers (a silent layout change would make runs
    incomparable), and the label on every log record's run stamp. With the
    ``spec`` both also carry ``t1_bodies``' count and, for a decode dispatch
    of ``rows`` > 1 rows, ``tile_planes``' histogram (where the Pallas
    kernels run at all)."""
    import sys

    from ..utils.fingerprint import stamp_q40_body

    bodies = ""
    if spec is not None and q40_kernel_mode() == "pallas":
        bodies = t1_bodies(spec, layout)
        if rows > 1:
            bodies += "; " + tile_planes(spec, layout, rows)
    stamp_q40_body(f"{layout.label} {bodies}".rstrip())
    print(f"💡 Q40 body policy: {layout.label} ({layout.reason}; "
          f"{bodies and bodies + '; '}the i4 body engages on fused decode "
          f"chains)", file=sys.stderr)


# What apply_q40_body_policy last resolved, for packers that are handed no
# layout. pack_q40_params is its one reader.
_APPLIED_LAYOUT: Q40Layout | None = None


def apply_q40_body_policy(spec, rows: int = 1) -> str:
    """A SHIM for callers that pack by hand after it
    (``pack_q40_params(tree, allow_nb_major=True)`` with no ``layout``, as
    three tools under benchmark/tools/ do): resolves ``q40_body_policy``,
    announces it, records it in ``_APPLIED_LAYOUT`` (overwritten by the
    next call, not first-wins) and returns the label. Engines never depend
    on it having been called: each resolves the same value from its own
    spec and dispatch width, so a call before building one is redundant
    and harmless. ROADMAP names its removal."""
    global _APPLIED_LAYOUT

    _APPLIED_LAYOUT = layout = q40_body_policy(spec, rows)
    announce_q40_layout(layout, spec, rows)
    return layout.label


def fake_quant_q80(x: jax.Array) -> jax.Array:
    """Quantize->dequantize through Q80, used when buffer_float_type == Q80.

    The reference quantizes activations at every sync point (and feeds the
    quantized form to the matmuls even single-node: transformer-tasks.cpp
    quantize* tasks run regardless of socket count). This reproduces the value
    rounding of that path within the documented 0.0043 tolerance.
    """
    qs, d = quantize_q80_jax(x)
    return dequantize_q80_jax(qs, d)
