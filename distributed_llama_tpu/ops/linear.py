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

import contextlib
import contextvars
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.loader import (Q40Kernel, Q40KernelI4, Q40KernelI4PackedD,
                         Q40KernelI4PackedNb, Q40KernelNb, Q40KernelNbI4,
                         Q40Weight, from_kernel_layout, to_kernel_layout,
                         to_kernel_layout_nb)
from .quants import dequantize_q40_jax, dequantize_q80_jax, quantize_q80_jax

RMS_EPS = 1e-5

# trace-time matmul precision mode. "parity" = f32 accumulation at HIGHEST
# (the logit-parity contract); "bf16" = bf16 MXU passes with f32 accumulation
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


def rms_inv(x: jax.Array) -> jax.Array:
    """The reference's ``rms()``: inverse RMS with eps added after the mean."""
    ss = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    ss = ss / x.shape[-1] + RMS_EPS
    return jax.lax.rsqrt(ss)


def rmsnorm(x: jax.Array, weight: jax.Array) -> jax.Array:
    return (x * rms_inv(x)) * weight


def silu(x: jax.Array) -> jax.Array:
    return x / (1.0 + jnp.exp(-x))


def dequantize_weight(w) -> jax.Array:
    """Materialize any weight representation as f32 (d, n)."""
    if isinstance(w, StackedQ40):
        w = jax.tree_util.tree_map(lambda a: a[w.layer], w.w)
    if isinstance(w, (Q40KernelI4PackedD, Q40KernelI4PackedNb)):
        from .pallas_q40 import unpack_i4_packed

        w = unpack_i4_packed(w)
    if isinstance(w, (Q40KernelI4, Q40KernelNbI4)):
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
    if isinstance(w, (Q40KernelNb, Q40KernelI4, Q40KernelNbI4,
                      Q40KernelI4PackedD, Q40KernelI4PackedNb)):
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


def pack_q40_params(params: dict, enable: bool | None = None,
                    tp: int = 1, allow_nb_major: bool | None = None,
                    input_sharded=(), rows: int = 1) -> dict:
    """Re-tile every Q40Weight in a param tree to the kernel layout, once.

    ``enable=None`` means "iff the Pallas kernel will be used" — so CPU/test
    runs keep the codec layout and the golden-parity paths are untouched.
    ``tp`` is the tensor-parallel degree the weights will be sharded to:
    kernel support AND the layout are decided on the shard-LOCAL shape,
    since that is what the kernel tiles inside shard_map and what each chip
    stores. ``input_sharded`` names the keys the fused tp scheme shards
    along the INPUT dim (wo/w2 — parallel/tp.py): their local shape is
    (d, n/tp) instead of (d/tp, n). ``rows`` is how many rows one decode
    dispatch of the sharded engine carries (as q40_body_policy's): the
    ``tp > 1`` rule reads it, ``allow_nb_major`` gates the ``tp == 1`` pick.
    Call this at load time, before device_put; never inside a jitted step.
    """
    if enable is None:
        enable = q40_kernel_mode() == "pallas"
    if not enable:
        return params
    if allow_nb_major is None:
        # tp==1 does not imply unsharded (an sp>1 mesh packs with tp=1), and
        # the one-chip pick belongs to q40_body_policy — so the
        # truly-single-chip callers must OPT IN explicitly
        # (params_to_device, shard_sim.rank_params_to_device, bench.py)
        allow_nb_major = False
    from .pallas_q40 import _pick_rows_nb, kernel_supports

    def pick(k, v):
        if not isinstance(v, Q40Weight):
            return v
        d, n = v.logical_shape[-2], v.logical_shape[-1]
        if k.startswith("moe_"):
            # routed-expert stacks (L, E, d, n): the grouped kernels
            # (ops/pallas_moe) read the nb-major layout only; a shape the
            # row tiler cannot place stays codec and takes the XLA scan
            if tp > 1:
                raise ValueError(MOE_TP_REFUSAL)
            from .pallas_moe import shape_places

            # w1 and w3 are fused along d afterwards: both widths must place
            fused_ok = k == "moe_w2" or shape_places(2 * d, n // 32)
            return (to_kernel_layout_nb(v)
                    if fused_ok and shape_places(d, n // 32) else v)
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
        nb = n // 32
        if tp > 1:
            nb_major = sharded_nb_major(d_loc, n_loc // 32, rows)
        else:
            pad_ratio = (nb + (-nb % 128)) / nb  # lane padding of nb-minor
            # nb-major layout when the standard tiling would pad the packed
            # bytes materially (13B: nb=160 -> 1.6x HBM and read inflation).
            # DLLAMA_NB_MAJOR=force takes it for EVERY eligible leaf (the
            # i4-formulation experiment arm: the int4 body exists only for
            # nb-major, so pad-free shapes need the forced layout to reach
            # it)
            force_nb = os.environ.get("DLLAMA_NB_MAJOR", "") == "force"
            nb_major = (allow_nb_major and (pad_ratio > 1.25 or force_nb)
                        and _pick_rows_nb(d, nb) is not None)
        if nb_major:
            return to_kernel_layout_nb(v)
        if kernel_supports(d_loc, n_loc):
            return to_kernel_layout(v)
        # untileable dims stay codec-layout: they take the XLA fallback in
        # matmul(), which would otherwise pay a full re-transpose inside
        # the jitted step on every call
        return v

    return {k: pick(k, v) for k, v in params.items()}


MOE_TP_REFUSAL = (
    "expert (mixture-of-experts) models run on one chip only: placing "
    "experts across tensor-parallel ranks is not implemented, so --tp > 1 "
    "(or any sharded mesh) refuses them")


def sharded_nb_major(d_local: int, nb_local: int, rows: int = 1) -> bool:
    """The layout rule of a SHARDED Q40 leaf, on its shard-local shape.

    The chip stores an array whose minor dim is not a multiple of 128 with
    the second-minor dim minor instead (no padding): a d-major shard
    ``(16, d, nb)`` with ``nb % 128 != 0`` lies d-minor in HBM, the Pallas
    call wants it row-major, and XLA copies (and pads) every such leaf at
    the top of EVERY step program — 22.9 ms of a 37.2 ms step at Yi-34B
    tp=4 (ledger, PR 24). Packed nb-major, the logical order IS that
    physical order and nothing is copied. So: nb-major iff the shard-local
    ``nb`` is off the 128 grid (on it, d-major is already row-major), the
    shard-local ``d`` places on the nb-major row tiler, and no dispatch is
    5..8 rows wide (no nb-major kernel serves those: every matmul would
    take dequantize-then-dot, see q40_body_policy). The one-chip
    ``pad_ratio`` test is the wrong one here: nb 224 pads by 1.14 and is
    copied all the same."""
    from .pallas_q40 import MULTI_T_MAX, NB_MULTI_T_MAX, _pick_rows_nb

    return (nb_local % 128 != 0
            and not NB_MULTI_T_MAX < rows <= MULTI_T_MAX
            and _pick_rows_nb(d_local, nb_local) is not None)


def fuse_q40_layer_matmuls(params: dict) -> dict:
    """Concatenate the stacked Q40 qkv (and w1/w3) weights along the output
    dim into single kernel tensors ``wqkv`` / ``w13``, host-side, at load.

    The three qkv matmuls (and the two SwiGLU input matmuls) share the same
    input vector; one wide kernel call replaces three (two) narrow ones,
    which matters for single-token decode where the d=4096 matvec runs at
    roughly half the bytes/s of the d>=11008 ones (grid too short to hide
    pipeline ramp). Row-wise the math is unchanged — outputs are split back
    by models/llama (the reference computes the same three matmuls back to
    back, transformer-tasks.cpp:167-179).

    Only fires on stacked Q40Kernel entries (i.e. after pack_q40_params on
    the single-chip path); dense/TP trees pass through untouched.
    """
    from .pallas_q40 import _pick_rows_nb, kernel_supports

    out = dict(params)

    def fuse(dst, keys):
        # host numpy tree by contract (runs after pack_q40_params, before
        # device placement) — np.concatenate takes the leaves directly
        ws = [out.get(k) for k in keys]
        if all(isinstance(w, Q40Kernel) and w.qs_t.ndim == 4 for w in ws):
            qs_t = np.concatenate([w.qs_t for w in ws], axis=2)
            scale = np.concatenate([w.scale for w in ws], axis=1)
            if not kernel_supports(qs_t.shape[2], qs_t.shape[3] * 32):
                return
            out[dst] = Q40Kernel(qs_t, scale)
        elif all(isinstance(w, Q40KernelNb) and w.qs_t.ndim in (4, 5)
                 for w in ws):
            # nb-major: the output dim d is MINOR — concat along it (an
            # expert stack has one more leading axis)
            qs_t = np.concatenate([w.qs_t for w in ws], axis=-1)
            scale = np.concatenate([w.scale for w in ws], axis=-1)
            if _pick_rows_nb(qs_t.shape[-1], qs_t.shape[-2]) is None:
                return
            out[dst] = Q40KernelNb(qs_t, scale)
        else:
            return
        for k in keys:
            del out[k]

    fuse("wqkv", ("wq", "wk", "wv"))
    fuse("w13", ("w1", "w3"))
    fuse("moe_w13", ("moe_w1", "moe_w3"))
    return out


def q40_body_policy(spec, rows: int = 1) -> tuple[str, str]:
    """Resolve the single-chip Q40 decode-body policy: (policy, reason).
    ``rows`` is how many rows one decode dispatch carries (1 for plain
    ``inference``, the slot count for ``serve`` / ``--continuous``).

    Promotes the bench's A/B winner into the real CLI path. On the attached
    v5e at 7B (my chip run, PR 21; PERF.md): the fused chain runs 8.15
    ms/token with the int4-plane body on forced nb-major layout against
    8.60 d-major and 8.92 nb-major u8, and the per-token ``inference`` step
    11.1 ms nb-major against 14.0 d-major (d-major's w2, nb = 344, is placed
    transposed by the device client and copied row-major inside every step).

    Explicit ``DLLAMA_Q40_I4``/``DLLAMA_NB_MAJOR`` env wins over
    everything (including DLLAMA_Q40_BODY — nothing ever unsets a user
    knob), and the returned label then REPORTS what that env actually
    engages rather than a policy nobody chose. Otherwise
    ``DLLAMA_Q40_BODY`` overrides: ``auto`` (default), ``i4-nb`` (force
    the winning combo), ``d-major`` (keep the stock layout picks). auto
    picks ``i4-nb`` iff ALL of:
      * the Pallas kernel path is active (TPU; elsewhere layouts are moot),
      * a decode dispatch is not 5..8 rows wide: the nb-major VPU body
        serves T <= 4 and the MXU body T > 8, so in between EVERY matmul
        takes the XLA dequantize-then-dot route — ``serve`` at 8 slots
        decoded at 75 ms/token that way against 37.5 d-major (same run),
      * every matmul leaf places on the nb-major row tiler (the i4 body is
        nb-major-only — pad-free 7B-class shapes need the forced layout),
      * the packed weights leave conversion headroom: the in-chain i4
        conversion transiently holds an extra ~half of the packed bytes
        while the chain runs, which OOMed 13B on a 16 GB chip (PARITY.md
        round-5 table) — gated at DLLAMA_Q40_BODY_MAX_GB packed (default
        6.0, between 7B's ~4.2 and 13B's ~7.8).
    """
    choice = os.environ.get("DLLAMA_Q40_BODY", "auto")
    if choice not in ("auto", "i4-nb", "d-major"):
        raise ValueError(f"DLLAMA_Q40_BODY={choice!r}: expected "
                         f"auto|i4-nb|d-major")
    i4 = os.environ.get("DLLAMA_Q40_I4")
    nbm = os.environ.get("DLLAMA_NB_MAJOR")
    if i4 or nbm:
        label = ("i4-nb" if i4 == "on" and nbm == "force"
                 else f"env(i4={i4 or 'off'}, nb-major={nbm or 'auto'})")
        return label, "explicit DLLAMA_Q40_I4/DLLAMA_NB_MAJOR env respected"
    if choice != "auto":
        return choice, "explicit DLLAMA_Q40_BODY"
    if q40_kernel_mode() != "pallas":
        return "d-major", "XLA matmul path (no Pallas kernels here)"
    from .pallas_q40 import MULTI_T_MAX, NB_MULTI_T_MAX, _pick_rows_nb

    if NB_MULTI_T_MAX < rows <= MULTI_T_MAX:
        return "d-major", (f"{rows}-row decode dispatches: no nb-major "
                           f"kernel serves T in {NB_MULTI_T_MAX + 1}.."
                           f"{MULTI_T_MAX}, d-major has the multi-T body")

    counted = spec.matmul_shape_counts()     # a layer's, experts included
    shapes = [shape for shape, _ in counted]
    shapes.append((spec.vocab_size, spec.dim))  # wcls
    bad = [(d, n) for d, n in shapes if _pick_rows_nb(d, n // 32) is None]
    if bad:
        return "d-major", (f"shape {bad[0]} has no nb-major row tiling "
                           f"(rows must divide by 128)")
    packed_gb = (spec.n_layers * sum(c * d * (n // 32) * 18
                                     for (d, n), c in counted)
                 + spec.vocab_size * (spec.dim // 32) * 18) / 1e9
    raw_gb = os.environ.get("DLLAMA_Q40_BODY_MAX_GB", "6")
    try:
        max_gb = float(raw_gb)
    except ValueError:
        raise ValueError(f"DLLAMA_Q40_BODY_MAX_GB={raw_gb!r}: expected a "
                         f"number of GB (e.g. 6)") from None
    if packed_gb > max_gb:
        return "d-major", (f"~{packed_gb:.1f} GB packed exceeds the "
                           f"{max_gb:.0f} GB i4-conversion headroom gate "
                           f"(DLLAMA_Q40_BODY_MAX_GB; 13B-class OOM, "
                           f"BASELINE.md r5)")
    if spec.n_experts:
        return "d-major", (f"expert spec, ~{packed_gb:.1f} GB packed: the "
                           f"stock picks stand (expert stacks always pack "
                           f"nb-major) and the i4 chain body has no expert "
                           f"kernel")
    return "i4-nb", (f"auto: shapes place nb-major, ~{packed_gb:.1f} GB "
                     f"packed fits the i4 headroom gate")


def apply_q40_body_policy(spec, rows: int = 1) -> str:
    """Apply q40_body_policy by setting the layout env knobs the packers
    and the decode chain already read (DLLAMA_NB_MAJOR=force +
    DLLAMA_Q40_I4=on), BEFORE any pack/sidecar load — the kcache layout
    key includes DLLAMA_NB_MAJOR. Prints the chosen policy to stderr
    unconditionally, even for quiet callers: a silent layout change would
    make runs incomparable. setdefault only: explicit user env is never
    overridden."""
    import sys

    policy, reason = q40_body_policy(spec, rows)
    if policy == "i4-nb":
        os.environ.setdefault("DLLAMA_NB_MAJOR", "force")
        os.environ.setdefault("DLLAMA_Q40_I4", "on")
    print(f"💡 Q40 body policy: {policy} ({reason}; the i4 body "
          f"engages on fused decode chains)", file=sys.stderr)
    return policy


def fake_quant_q80(x: jax.Array) -> jax.Array:
    """Quantize->dequantize through Q80, used when buffer_float_type == Q80.

    The reference quantizes activations at every sync point (and feeds the
    quantized form to the matmuls even single-node: transformer-tasks.cpp
    quantize* tasks run regardless of socket count). This reproduces the value
    rounding of that path within the documented 0.0043 tolerance.
    """
    qs, d = quantize_q80_jax(x)
    return dequantize_q80_jax(qs, d)
