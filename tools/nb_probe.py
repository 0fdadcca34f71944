"""Probe: nb-major Q40 matvec kernel formulations (VERDICT r4 #2).

The 13B decode budget is dominated by the nb-major wqkv/w13 matvecs
running at ~493 GB/s vs the d-major kernels' ~650 GB/s on the same chip
(BASELINE.md r4 attribution). This probe measures candidate second
formulations of the nb-major T=1 body on the real 13B shapes, each as its
own scanned+profiled program, and prints achieved GB/s per variant:

  dma   — DMA/stream floor: every packed byte + scale is loaded, 1 VPU op
          per plane (XOR fold), no unpack. The rate ceiling for ANY body
          on this tile geometry.
  v0    — the production body (_matvec_body_nb): per plane
          convert/and/shift/2x-convert/2x-mul/2x-add ≈ 9 vreg-ops/byte.
  v1    — mask-elimination: lo = q - 16*hi, so
          lo*xlo + hi*xhi = q*xlo + hi*(xhi - 16*xlo); precompute
          xhi16 = xhi - 16*xlo outside and the kernel drops the `& 0xF`
          (8 vreg-ops/byte). Same integers, same xsum correction.
  v0r   — v0 with x pre-replicated to a CONSTANT (NJ, nb, 128) block and
          the row tile forced to 128, so the kernel multiplies full-width
          tiles with no in-kernel lane-broadcast; the replicated block's
          index map is constant, so it streams once per call (~2.6 MB),
          not per grid step. Compare against v0_128 (the production body
          at the same 128-row tile) to isolate the broadcast cost from
          the tile-size effect.
  v0_128 — the production body with rows forced to 128 (the fair pair
          for v0r).
  i4    — signed int4 planes: the load-time layout stores (code - 8)
          directly as int4 (range -8..7 fits exactly), 32 planes of
          (nb, R) i4. Per plane: ONE convert + mul + add, no mask, no
          shift, no xsum correction. Same bytes in HBM (2 nibbles/byte),
          potentially ~2/3 the VPU ops — IF Mosaic's i4 load/convert is
          cheap.

Methodology (verify-skill notes): one jitted lax.scan per variant over
``--layers x --reps`` dependent kernel calls (the output feeds a
non-foldable epsilon back into x, so XLA can neither elide nor reorder
across steps), profiled in situ; the per-call device op time comes from
the trace (utils.it_split), never from wall-clock differencing. Weights
are synthesized ON DEVICE (no host tree, no upload).

Usage: python tools/nb_probe.py [--shape w13|wqkv] [--layers 8]
         [--reps 4] [--variants dma,v0,v1,v0r,i4]
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_llama_tpu.ops.pallas_q40 import (NJ, _VMEM64_PARAMS,
                                                  _pick_rows_nb, _split_x)
from distributed_llama_tpu.utils.it_split import (bucket_ops_from_splits,
                                                  parse_trace)

# 13B nb-major leaf shapes (d = output rows, n = input dim; nb = n/32)
SHAPES = {"w13": (27648, 5120), "wqkv": (15360, 5120), "wo": (5120, 5120),
          "tiny": (256, 256)}  # CPU/interpret smoke


# ---------------------------------------------------------------- kernels
def _k_dma(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref, xsum_ref,
           out_ref):
    del layer_ref, xlo_ref, xhi_ref, xsum_ref
    acc = None
    for j in range(NJ):
        q = qs_ref[0, j]
        acc = q if acc is None else acc ^ q
    out_ref[...] = jnp.sum(acc.astype(jnp.int32).astype(jnp.float32)
                           * scale_ref[0], axis=0, keepdims=True)


def _k_v0(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref, xsum_ref,
          out_ref):
    del layer_ref
    qs3, s = qs_ref[0], scale_ref[0]
    acc = None
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)
        wlo = (q & 0xF).astype(jnp.float32)
        whi = (q >> 4).astype(jnp.float32)
        a = wlo * xlo_ref[j] + whi * xhi_ref[j]
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xsum_ref[...]
    out_ref[...] = jnp.sum(acc * s, axis=0, keepdims=True)


def _k_v1(layer_ref, qs_ref, scale_ref, xlo_ref, xhi16_ref, xsum_ref,
          out_ref):
    """lo = q - 16*hi  =>  lo*xlo + hi*xhi = q*xlo + hi*(xhi - 16*xlo)."""
    del layer_ref
    qs3, s = qs_ref[0], scale_ref[0]
    acc = None
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)
        whi = (q >> 4).astype(jnp.float32)
        qf = q.astype(jnp.float32)
        a = qf * xlo_ref[j] + whi * xhi16_ref[j]
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xsum_ref[...]
    out_ref[...] = jnp.sum(acc * s, axis=0, keepdims=True)


def _k_v0r(layer_ref, qs_ref, scale_ref, xlo_ref, xhi_ref, xsum_ref,
           out_ref):
    """v0 with xlo/xhi already lane-replicated (NJ, nb, 128) and R=128:
    the multiply is full-width x full-width, no in-kernel lane-broadcast."""
    del layer_ref
    qs3, s = qs_ref[0], scale_ref[0]
    acc = None
    for j in range(NJ):
        q = qs3[j].astype(jnp.int32)
        wlo = (q & 0xF).astype(jnp.float32)
        whi = (q >> 4).astype(jnp.float32)
        a = wlo * xlo_ref[j] + whi * xhi_ref[j]
        acc = a if acc is None else acc + a
    acc = acc - 8.0 * xsum_ref[...]
    out_ref[...] = jnp.sum(acc * s, axis=0, keepdims=True)


def _k_i4(layer_ref, qs_ref, scale_ref, x32_ref, out_ref):
    """Signed-i4 planes: 32 planes of (nb, R), code-8 pre-applied — one
    convert+mul+add per plane, no mask/shift/xsum."""
    del layer_ref
    qs4, s = qs_ref[0], scale_ref[0]
    acc = None
    for j in range(2 * NJ):
        w = qs4[j].astype(jnp.float32)
        a = w * x32_ref[j]
        acc = a if acc is None else acc + a
    out_ref[...] = jnp.sum(acc * s, axis=0, keepdims=True)


# ------------------------------------------------------------- dispatchers
def _call_classic(kernel, layer, qs_t, scale, xlo, xhi, xsum, *, rows,
                  interpret=False):
    _, _, nb, d = qs_t.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // rows,),
        in_specs=[
            pl.BlockSpec((1, NJ, nb, rows), lambda i, L: (L[0], 0, 0, i)),
            pl.BlockSpec((1, nb, rows), lambda i, L: (L[0], 0, i)),
            # lane-replicated x (v0r): a constant full block, streamed
            # once per call; otherwise the (nb, 1) broadcast-in-kernel form
            pl.BlockSpec((NJ, nb, xlo.shape[-1]), lambda i, L: (0, 0, 0)),
            pl.BlockSpec((NJ, nb, xhi.shape[-1]), lambda i, L: (0, 0, 0)),
            pl.BlockSpec((nb, 1), lambda i, L: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows), lambda i, L: (0, i)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(layer, qs_t, scale, xlo, xhi, xsum)


def _call_i4(layer, qs4, scale, x32, *, rows, interpret=False):
    _, nj2, nb, d = qs4.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // rows,),
        in_specs=[
            pl.BlockSpec((1, nj2, nb, rows), lambda i, L: (L[0], 0, 0, i)),
            pl.BlockSpec((1, nb, rows), lambda i, L: (L[0], 0, i)),
            pl.BlockSpec((nj2, nb, 1), lambda i, L: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows), lambda i, L: (0, i)),
    )
    return pl.pallas_call(
        _k_i4, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        compiler_params=_VMEM64_PARAMS, interpret=interpret,
    )(layer, qs4, scale, x32)


# ------------------------------------------------------------- harness
def _synth(layers, nb, d, key):
    """On-device stacked nb-major tree: codes (L, NJ, nb, d) u8, scales
    (L, nb, d) f32 in a plausible Q40-delta range."""
    k1, k2 = jax.random.split(key)
    qs = jax.random.randint(k1, (layers, NJ, nb, d), 0, 256, jnp.int32)
    qs = qs.astype(jnp.uint8)
    scale = jax.random.uniform(k2, (layers, nb, d), jnp.float32,
                               0.005, 0.02)
    return qs, scale


def _ref_matvec(qs, scale, x):
    """NumPy float64 reference for one layer (parity check)."""
    nbv, d = scale.shape
    lo = (qs & 0xF).astype(np.float64) - 8        # (NJ, nb, d)
    hi = (qs >> 4).astype(np.float64) - 8
    x3 = x.astype(np.float64).reshape(nbv, 32)
    xlo = x3[:, :NJ].T[:, :, None]                # (NJ, nb, 1)
    xhi = x3[:, NJ:].T[:, :, None]
    acc = (lo * xlo + hi * xhi).sum(axis=0)       # (nb, d)
    return (acc * scale.astype(np.float64)).sum(axis=0)


def run_variant(name, spec_name, layers, reps, interpret=False):
    d, n = SHAPES[spec_name]
    nb = n // 32
    rows = _pick_rows_nb(d, nb)
    assert rows, (d, nb)
    key = jax.random.PRNGKey(0)
    qs, scale = jax.jit(functools.partial(_synth, layers, nb, d))(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, n), jnp.float32)

    # bytes one call must stream (codes + scales for one layer)
    call_bytes = NJ * nb * d + nb * d * 4

    if name == "i4":
        @jax.jit
        def to_i4(qs):
            lo = (qs & 0xF).astype(jnp.int32) - 8
            hi = (qs >> 4).astype(jnp.int32) - 8
            return jnp.concatenate([lo, hi], axis=1).astype(jnp.int4)

        # the i4 planes are built INSIDE each jitted program from the
        # resident u8 codes, as the decode chain builds them (a one-time
        # pass per chain; the per-kernel measurement comes from the trace
        # and is unaffected), and the parity copy stays int8
        qs4_i8_host = np.asarray(jax.jit(
            lambda q: to_i4(q)[0].astype(jnp.int8))(qs))

        def prep_x(x):
            xlo, xhi = _split_x(x, nb)             # (NJ, 1, nb)
            x32 = jnp.concatenate([xlo, xhi], axis=0)  # (32, 1, nb)
            return jnp.transpose(x32, (0, 2, 1))   # (32, nb, 1)

        def one(L, xv, w, s, ctx=None):
            qs4 = to_i4(w) if ctx is None else ctx
            return _call_i4(L, qs4, s, prep_x(xv), rows=rows,
                            interpret=interpret)

        setup = to_i4  # hoisted once per chain, outside the scan
    else:
        kernel = {"dma": _k_dma, "v0": _k_v0, "v1": _k_v1,
                  "v0r": _k_v0r, "v0_128": _k_v0}[name]
        rep = name == "v0r"
        if name in ("v0r", "v0_128"):
            rows = 128  # the matched pair isolating the lane-broadcast

        def prep_x(x):
            xlo, xhi = _split_x(x, nb)             # (NJ, 1, nb)
            xlo = jnp.transpose(xlo, (0, 2, 1))    # (NJ, nb, 1)
            xhi = jnp.transpose(xhi, (0, 2, 1))
            xsum = jnp.sum(xlo[:, :, 0] + xhi[:, :, 0], axis=0)[:, None]
            if name == "v1":
                xhi = xhi - 16.0 * xlo             # xhi16
            if rep:
                # lane-replicate to ONE 128-wide block (constant index
                # map: streams once per call, ~2.6 MB — not per grid step)
                xlo = jnp.broadcast_to(xlo, (NJ, nb, 128)) + 0.0
                xhi = jnp.broadcast_to(xhi, (NJ, nb, 128)) + 0.0
            return xlo, xhi, xsum

        def one(L, xv, w, s, ctx=None):
            del ctx
            xlo, xhi, xsum = prep_x(xv)
            return _call_classic(kernel, L, w, s, xlo, xhi, xsum,
                                 rows=rows, interpret=interpret)

        setup = None

    # the weight tree is an ARGUMENT, never a closure: a closed-over
    # device array is baked into the jaxpr as a multi-GB literal (the
    # verify-skill "captured constants" trap)
    @jax.jit
    def chain(x, w, s):
        ctx = setup(w) if setup is not None else None

        def body(carry, L):
            out = one(L, carry, w, s, ctx)
            # non-foldable dependency: out feeds an epsilon back into x
            eps = jnp.sum(out) * jnp.float32(1e-30)
            return carry + eps, jnp.sum(out)
        Ls = jnp.tile(jnp.arange(layers, dtype=jnp.int32), reps)
        carry, sums = jax.lax.scan(body, x, Ls[:, None])
        return carry, sums

    # parity gate (not for the dma floor, which computes garbage on
    # purpose); jitted so any layout prep (i4) fuses into one program
    if name != "dma":
        got = np.asarray(jax.jit(one)(
            jnp.zeros((1,), jnp.int32), x, qs, scale)).ravel()
        if name == "i4":
            lo_hi = qs4_i8_host                           # (32, nb, d)
            x3 = np.asarray(x).ravel().reshape(nb, 32)
            x32 = np.concatenate([x3[:, :NJ].T, x3[:, NJ:].T], axis=0)
            want = ((lo_hi * x32[:, :, None]).sum(axis=0)
                    * np.asarray(scale[0])).sum(axis=0)
        else:
            want = _ref_matvec(np.asarray(qs[0]), np.asarray(scale[0]),
                               np.asarray(x).ravel())
        # f32 accumulation over n=5120 random-walk sums (sigma ~ 6): a
        # few e-3 relative on near-zero outputs is float32 reassociation,
        # not a wrong value map; v1's q*xlo form multiplies raw codes
        # (<=255 vs <=15) so its cancellation error runs ~5x larger
        err = np.max(np.abs(got - want) / (np.abs(want) + 1.0))
        tol = 2e-2 if name == "v1" else 5e-3
        assert err < tol, f"{name} parity {err}"
        print(f"{name}: parity ok (max rel-ish err {err:.2e})",
              file=sys.stderr)

    n_calls = layers * reps
    prof = tempfile.mkdtemp(prefix=f"nbprobe-{name}-")
    carry, sums = chain(x, qs, scale)  # compile + warm
    np.asarray(sums)
    with jax.profiler.trace(prof):
        carry, sums = chain(x, qs, scale)
        np.asarray(sums)
    splits = parse_trace(prof)
    buckets = bucket_ops_from_splits(splits, n_calls)
    # the kernel's own op family: pallas custom calls keep the python name
    # each variant runs its own program, so the pallas custom call —
    # surfaced as 'closed_call' (or the kernel fn name on some
    # toolchains) — is unambiguously this variant's kernel
    kern_ms = 0.0
    for s in splits.values():
        for op, ns in s.ops.items():
            if ("_k_" in op or op.startswith(("closed_call", "custom"))):
                kern_ms += ns / 1e6 / n_calls
    gbps = call_bytes / (kern_ms * 1e6) if kern_ms else float("nan")
    print(f"{spec_name:5s} {name:4s} rows={rows:4d} "
          f"kernel {kern_ms:7.3f} ms/call  {gbps:6.1f} GB/s  "
          f"(buckets/call: {buckets})")
    return kern_ms, gbps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="w13", choices=sorted(SHAPES))
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--variants", default="dma,v0,v1,v0_128,v0r,i4")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()
    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    # --interpret is the off-chip correctness arm; its times mean nothing
    dev = device_triple() if args.interpret else require_tpu()
    print(f"backend: {dev}", file=sys.stderr)
    results = {}
    for v in args.variants.split(","):
        try:
            results[v] = run_variant(v, args.shape, args.layers, args.reps,
                                     interpret=args.interpret)
        except Exception as e:  # noqa: BLE001 - probe arms fail independently
            import traceback

            traceback.print_exc()
            print(f"{v}: FAILED ({type(e).__name__}: {e})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
