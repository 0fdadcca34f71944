"""Selective state-space scan (Mamba-1, arXiv:2312.00752; Mamba-2 / SSD,
a scalar decay a head on a state (heads, head_dim, d_state), is
``ops/mamba2.py``) on a state of
fixed size, one (d_state, d_inner) float32 plane a layer and sequence:

    s_t = exp(delta_t A) * s_{t-1} + B_t (delta_t x_t)^T,   A = -exp(A_log)
    y_t = s_t^T C_t

with x_t, delta_t (d_inner,) and B_t, C_t (d_state,). The state is held
with the STATE index before the channel, ``s[n, c]``: the channel runs along
lanes (d_inner a multiple of 128), the d_state rows along sublanes, so B_t
and C_t broadcast along lanes (they arrive spread over 128 lanes:
``spread``) and x_t, delta_t along sublanes, and y_t is a sublane
reduction. Everything is float32 on the VPU; there is no matmul here.

The stacked state ``ssm_all`` is (layers * rows, d_state, d_inner); the two
kernels read and write the plane they are pointed at in place
(``input_output_aliases``):

* ``mamba_decode_step``: one position for each of B rows. Each state
  element is read once and written once. A row whose ``delta`` is 0 leaves
  its state exactly as it is (exp(0) = 1, nothing added): how a row that
  takes no part is masked. ``keep`` 0 empties the state first (a row at its
  sequence's first position finds it empty whatever it holds).
* ``mamba_prefill_chunk``: T positions of ONE sequence, the recurrence run
  in order, a lane tile of channels at a time with the state tile in
  registers. A padded position is given ``delta`` 0 by the caller and so
  does not reach the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
DECODE_KERNEL = "mamba_decode_step"
CHUNK_KERNEL = "mamba_prefill_chunk"
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)

TP_REFUSAL = (
    "a hybrid (state-space and attention) model runs on one chip only: "
    "neither the recurrent state (Mamba-1's a channel, Mamba-2's a head) "
    "nor the window ring is sharded over "
    "tensor-parallel ranks, so --tp > 1 (or any sharded mesh) refuses it")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def spread(v: jax.Array) -> jax.Array:
    """(..., d_state) -> (..., d_state, 128): each value on every lane."""
    return jnp.broadcast_to(v[..., None], (*v.shape, LANES))


def _decode_kernel(layer_ref, s_ref, a_ref, aux_ref, bc_ref, s_out, y_out,
                   *, d_state: int, n_tiles: int):
    """One row: s (d_state, d_inner); a_log likewise; aux rows [x | delta |
    keep on every lane]; bc rows [B spread | C spread]."""
    del layer_ref
    b = bc_ref[0:d_state, :]
    c = bc_ref[d_state:2 * d_state, :]
    for t in range(n_tiles):
        sl = slice(t * LANES, (t + 1) * LANES)
        x, delta, keep = (aux_ref[i:i + 1, sl] for i in range(3))
        a = -jnp.exp(a_ref[:, sl])
        s = jnp.exp(delta * a) * (s_ref[:, sl] * keep) + b * (delta * x)
        s_out[:, sl] = s
        y_out[0:1, sl] = jnp.sum(s * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_decode_step(layer, ssm_all, a_log, aux, bc, *, interpret: bool):
    """``ssm_all`` (L * B, d_state, d_inner) with layer ``layer``'s B rows
    adjacent; ``layer`` (1,) int32; ``a_log`` (d_state, d_inner) of that
    layer; ``aux`` (B, 8, d_inner) rows [x, delta, keep]; ``bc`` (B, 2 *
    d_state, 128). Returns (ssm_all updated in place, y (B, d_inner))."""
    n_rows, _, d_inner = aux.shape
    d_state = a_log.shape[0]
    at = lambda r, L: (L[0] * n_rows + r, 0, 0)
    row = lambda r, L: (r, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n_rows,),
        in_specs=[pl.BlockSpec((None, d_state, d_inner), at),
                  pl.BlockSpec((d_state, d_inner), lambda r, L: (0, 0)),
                  pl.BlockSpec((None, SUBLANES, d_inner), row),
                  pl.BlockSpec((None, 2 * d_state, LANES), row)],
        out_specs=[pl.BlockSpec((None, d_state, d_inner), at),
                   pl.BlockSpec((None, 1, d_inner), row)])
    ssm_all, y = pl.pallas_call(
        functools.partial(_decode_kernel, d_state=d_state,
                          n_tiles=d_inner // LANES),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ssm_all.shape, ssm_all.dtype),
                   jax.ShapeDtypeStruct((n_rows, 1, d_inner), jnp.float32)],
        # operands count the scalar-prefetch argument: ssm_all is 1
        input_output_aliases={1: 0},
        compiler_params=_PARAMS, interpret=interpret, name=DECODE_KERNEL,
    )(layer, ssm_all, a_log, aux, bc)
    return ssm_all, y[:, 0]


def _chunk_kernel(meta_ref, s_ref, a_ref, x_ref, d_ref, b_ref, c_ref, s_out,
                  y_out, *, t_len: int):
    """One lane tile of channels: s (d_state, 128); x, delta (T, 128); B, C
    spread (T, d_state, 128). ``meta`` = [state plane, keep]."""
    a = -jnp.exp(a_ref[...])
    s0 = s_ref[...] * (meta_ref[1] != 0).astype(jnp.float32)

    def eight(i, s):
        t0 = pl.multiple_of(i * SUBLANES, SUBLANES)
        x8 = x_ref[pl.ds(t0, SUBLANES), :]
        d8 = d_ref[pl.ds(t0, SUBLANES), :]
        ys = []
        for j in range(SUBLANES):
            dj = d8[j:j + 1, :]
            s = jnp.exp(dj * a) * s + b_ref[t0 + j] * (dj * x8[j:j + 1, :])
            ys.append(jnp.sum(s * c_ref[t0 + j], axis=0, keepdims=True))
        y_out[pl.ds(t0, SUBLANES), :] = jnp.concatenate(ys, axis=0)
        return s

    s_out[...] = jax.lax.fori_loop(0, t_len // SUBLANES, eight, s0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_prefill_chunk(meta, ssm_all, a_log, x, delta, b, c, *,
                        interpret: bool):
    """``meta`` (2,) int32 [the sequence's plane in ``ssm_all``, 0 to empty
    the state first]; ``x``, ``delta`` (T, d_inner), T a multiple of 8;
    ``b``, ``c`` (T, d_state, 128) spread. Returns (ssm_all updated in
    place, y (T, d_inner))."""
    t_len, d_inner = x.shape
    d_state = a_log.shape[0]
    plane = lambda t, m: (m[0], 0, t)
    tile = lambda t, m: (0, t)
    whole = lambda t, m: (0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(d_inner // LANES,),
        in_specs=[pl.BlockSpec((None, d_state, LANES), plane),
                  pl.BlockSpec((d_state, LANES), tile),
                  pl.BlockSpec((t_len, LANES), tile),
                  pl.BlockSpec((t_len, LANES), tile),
                  pl.BlockSpec((t_len, d_state, LANES), whole),
                  pl.BlockSpec((t_len, d_state, LANES), whole)],
        out_specs=[pl.BlockSpec((None, d_state, LANES), plane),
                   pl.BlockSpec((t_len, LANES), tile)])
    return pl.pallas_call(
        functools.partial(_chunk_kernel, t_len=t_len),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ssm_all.shape, ssm_all.dtype),
                   jax.ShapeDtypeStruct((t_len, d_inner), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=_PARAMS, interpret=interpret, name=CHUNK_KERNEL,
    )(meta, ssm_all, a_log, x, delta, b, c)


# -- what the model's layer calls --------------------------------------------

def scan_decode(layer, ssm_all, a_log, x, delta, b, c, fresh, live):
    """One position for each of B rows: x, delta (B, d_inner), b, c (B,
    d_state); ``fresh`` (B,) True where the row is at its sequence's first
    position; ``live`` (B,) False for a row that takes no part. Returns
    (y (B, d_inner), ssm_all)."""
    n_rows, d_inner = x.shape
    keep = jnp.where(fresh & live, 0.0, 1.0)
    delta = jnp.where(live[:, None], delta, 0.0)
    aux = jnp.zeros((n_rows, SUBLANES, d_inner), jnp.float32)
    aux = aux.at[:, 0].set(x).at[:, 1].set(delta).at[:, 2].set(
        jnp.broadcast_to(keep[:, None], (n_rows, d_inner)))
    bc = jnp.concatenate([spread(b), spread(c)], axis=1)
    ssm_all, y = mamba_decode_step(
        jnp.reshape(layer, (1,)).astype(jnp.int32), ssm_all, a_log, aux, bc,
        interpret=_interpret())
    return y, ssm_all


def scan_chunk(plane, ssm_all, a_log, x, delta, b, c, fresh, n_valid):
    """T positions (a multiple of 8) of the ONE sequence whose state is
    plane ``plane`` of ``ssm_all``; the first ``n_valid`` count."""
    valid = jnp.arange(x.shape[0]) < n_valid
    delta = jnp.where(valid[:, None], delta, 0.0)
    meta = jnp.stack([jnp.asarray(plane, jnp.int32),
                      jnp.where(fresh, 0, 1).astype(jnp.int32)])
    ssm_all, y = mamba_prefill_chunk(meta, ssm_all, a_log, x, delta,
                                     spread(b), spread(c),
                                     interpret=_interpret())
    return y, ssm_all
