"""Power retention of degree 2: a recurrent state of fixed size in place of
a KV cache (Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239). For one KV head, token t, head size d, gate g_t in (0, 1):

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

with phi: R^d -> R^D, D = d(d+1)/2, phi(u).phi(w) = (u.w)^2. q and k arrive
here scaled by d^(-1/4), so phi(q).phi(k) = (q.k)^2 / d.

THE LAYOUT. phi(u) is held as ``n_off = d/2 + 1`` rows of d lanes, one row
per offset o between the two factors, the pair wrapping around:

    phi(u)[o, a] = c_o * u[a] * u[(a + o) mod d],   c_0 = 1, c_o = sqrt 2
                                                   (0 < o < d/2), c_{d/2} = 1

Offset 0 is the squares; an offset 0 < o < d/2 holds each unordered pair
{a, a+o} once (weight sqrt 2, so its square is the 2 of the cross term);
offset d/2 holds each of its d/2 pairs TWICE (a and a + d/2 name the same
pair), at weight 1, which again sums to 2. So phi(u).phi(w) = (u.w)^2
exactly, in D' = (d/2 + 1) d = 8320 stored entries for d = 128 where the
mathematics needs D = 8256: 0.8 % over, every row a whole vector register
row, and phi is d/2 lane rotations of u with no gather. The state keeps
the VALUE index before the key index: ``S[o, dv, a]``, so that both phi
rows a step needs (phi(k)[o, :], phi(q)[o, :]) vary along lanes and
broadcast along sublanes, and v is one column a head.

State arrays (float32): ``s`` (..., n_kv, n_off, d, d) and ``z``
(..., n_kv, n_off, d); 33.8 MB + 0.27 MB a layer and sequence at d = 128
and 8 KV heads. The two kernels read and write the layer they are handed
inside the stacked arrays, in place (``input_output_aliases``).

* ``retention_decode_step``: one position a row. Bandwidth-bound: each
  state element is read once and written once, for 2 + 2 m operations (m
  query heads a KV head) on the VPU.
* ``retention_prefill_chunk``: T positions of ONE sequence. The chunk's own
  positions are computed in the attention form (scores squared, decayed by
  the cumulative gates: exact, no cancellation), the earlier ones through
  the state, and the state is advanced by the whole chunk; matmuls on the
  MXU in float32 at HIGHEST precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6            # added to the normaliser phi(q).z (assumed)
SUBLANES = 8
_HIGHEST = jax.lax.Precision.HIGHEST
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=96 * 1024 * 1024)

TP_REFUSAL = (
    "power-retention models run on one chip only: the recurrent state is "
    "not sharded over tensor-parallel ranks (it would split by KV head), so "
    "--tp > 1 (or any sharded mesh) refuses them")


def n_offsets(head_size: int) -> int:
    return head_size // 2 + 1


def state_shapes(n_kv: int, head_size: int) -> tuple[tuple, tuple]:
    """Trailing shapes of ``s`` and ``z`` for one sequence and layer."""
    n_off = n_offsets(head_size)
    return ((n_kv, n_off, head_size, head_size), (n_kv, n_off, head_size))


def state_bytes(n_kv: int, head_size: int) -> int:
    """Stored float32 bytes of one sequence's state in one layer."""
    s, z = state_shapes(n_kv, head_size)
    return 4 * (int(np.prod(s)) + int(np.prod(z)))


def phi(u: jax.Array) -> jax.Array:
    """(..., d) -> (..., n_off, d): the degree-2 feature map in the offset
    layout of the module docstring, float32."""
    d = u.shape[-1]
    half = d // 2
    uu = jnp.concatenate([u, u], axis=-1)
    rolled = jnp.stack([uu[..., o:o + d] for o in range(half + 1)], axis=-2)
    c = np.full((half + 1, 1), np.sqrt(2.0), np.float32)
    c[0] = c[half] = 1.0
    return c * u[..., None, :] * rolled


# -- the decode step ----------------------------------------------------------

def _decode_kernel(layer_ref, s_ref, z_ref, pq_ref, pk_ref, aux_ref,
                   s_out, z_out, y_out, n_out, vcol_ref, acc_ref, *,
                   n_off: int, m: int):
    """One (row, KV head): s (n_off, d, d) [o, dv, a], z (n_off, d), phi(q)
    (m, n_off, d), phi(k) (n_off, d), aux rows [v | g broadcast]. Writes
    the advanced state, and what the state read BEFORE the step: the m
    unnormalised outputs (rows of y_out) and the normalisers, still spread
    over lanes (rows of n_out). The step's own position is the caller's,
    in the attention form."""
    del layer_ref
    d = s_ref.shape[-1]
    g = aux_ref[1:2, :]                                   # (1, d), one value
    # v as a column: v_col[dv, a] = v[dv]
    vcol_ref[...] = jnp.transpose(jnp.broadcast_to(aux_ref[0:1, :], (d, d)))
    z_old = z_ref[...]
    z_out[...] = g * z_old + pk_ref[...]
    y_out[...] = jnp.zeros_like(y_out)
    n_out[...] = jnp.zeros_like(n_out)
    for i in range(m):
        n_out[i:i + 1, :] = jnp.sum(pq_ref[i] * z_old, axis=0, keepdims=True)

    def rows(blk, carry):
        # eight value rows at a time: the m accumulators stay one vector
        # register each across the offsets
        r0 = pl.multiple_of(blk * SUBLANES, SUBLANES)
        sl = pl.ds(r0, SUBLANES)
        vcol = vcol_ref[sl, :]
        acc = [jnp.zeros((SUBLANES, d), jnp.float32) for _ in range(m)]
        for o in range(n_off):
            old = s_ref[o, sl, :]
            s_out[o, sl, :] = g * old + vcol * pk_ref[o:o + 1, :]
            for i in range(m):
                acc[i] = acc[i] + old * pq_ref[i, o:o + 1, :]
        for i in range(m):
            acc_ref[i, sl, :] = acc[i]
        return carry

    jax.lax.fori_loop(0, d // SUBLANES, rows, 0)
    for i in range(m):
        # y[dv] = sum_a acc[dv, a]: as a row, through the transpose
        y_out[i:i + 1, :] = jnp.sum(jnp.transpose(acc_ref[i]), axis=0,
                                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_decode_step(layer, s_all, z_all, phi_q, phi_k, aux, *,
                          interpret: bool):
    """Read ``R`` (row, KV head) states of layer ``layer`` and advance each
    by one position.

    ``s_all`` (L * R, n_off, d, d) and ``z_all`` (L * R, n_off, d): the
    stacked state, rows of one layer adjacent; ``layer`` (1,) int32;
    ``phi_q`` (R, m, n_off, d); ``phi_k`` (R, n_off, d) (zeros leave the
    state's content as it is); ``aux`` (R, 8, d): row 0 the value vector,
    row 1 the gate on every lane. Returns (s_all, z_all, y, nrm): the state
    updated in place, and of the state as it was BEFORE the update ``y``
    (R, m, d) = phi(q)^T S unnormalised and ``nrm`` (R, m) = phi(q).z."""
    n_rows, m, n_off, d = phi_q.shape
    m8 = -(-m // SUBLANES) * SUBLANES
    at = lambda r, L: (L[0] * n_rows + r, 0, 0, 0)
    at_z = lambda r, L: (L[0] * n_rows + r, 0, 0)
    row = lambda r, L: (r, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n_rows,),
        in_specs=[
            pl.BlockSpec((None, n_off, d, d), at),
            pl.BlockSpec((None, n_off, d), at_z),
            pl.BlockSpec((None, m, n_off, d), lambda r, L: (r, 0, 0, 0)),
            pl.BlockSpec((None, n_off, d), row),
            pl.BlockSpec((None, SUBLANES, d), row),
        ],
        out_specs=[
            pl.BlockSpec((None, n_off, d, d), at),
            pl.BlockSpec((None, n_off, d), at_z),
            pl.BlockSpec((None, m8, d), row),
            pl.BlockSpec((None, m8, d), row),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((m, d, d), jnp.float32)],
    )
    s_all, z_all, y, nrm = pl.pallas_call(
        functools.partial(_decode_kernel, n_off=n_off, m=m),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct(z_all.shape, z_all.dtype),
                   jax.ShapeDtypeStruct((n_rows, m8, d), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows, m8, d), jnp.float32)],
        # operands count the scalar-prefetch argument: s_all is 1, z_all 2
        input_output_aliases={1: 0, 2: 1},
        compiler_params=_PARAMS, interpret=interpret,
        name="retention_decode_step",
    )(layer, s_all, z_all, phi_q, phi_k, aux)
    return s_all, z_all, y[:, :m], jnp.sum(nrm[:, :m], axis=-1)


# -- the prefill chunk --------------------------------------------------------

def _offset_tile(n_off: int, cap: int = 5) -> int:
    return max(t for t in range(1, cap + 1) if n_off % t == 0)


def _chunk_kernel(layer_ref, s_ref, z_ref, pq_ref, pk_ref, q_ref, k_ref,
                  v_ref, vw_ref, w_ref, dec_ref, e_ref, gl_ref,
                  s_out, z_out, y_out, yacc_ref, nacc_ref, *, to: int):
    """One KV head, ``to`` offsets of it a grid step. s (to, d, d), z
    (n_off, d) whole, phi(q) (to, mT, d), phi(k) (to, T, d); q (mT, d), k
    and v (T, d); vw (d, T) = (w v)^T, w (8, T) the weights that carry a
    chunk position to the chunk's end; dec (mT, T) the causal decay inside
    the chunk; e (mT, d) what carries the earlier state to each row; gl
    (8, d) what carries it to the chunk's end."""
    del layer_ref
    t = pl.program_id(1)
    nt = (((1,), (1,)), ((), ()))        # a @ b^T

    @pl.when(t == 0)
    def _():
        yacc_ref[...] = jnp.zeros_like(yacc_ref)
        nacc_ref[...] = jnp.zeros_like(nacc_ref)

    gl = gl_ref[0:1, :]
    for oo in range(to):
        o = t * to + oo
        pq = pq_ref[oo]
        s = s_ref[oo]
        z_row = z_ref[pl.ds(o, 1), :]
        yacc_ref[...] += jax.lax.dot_general(
            pq, s, nt, precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        nacc_ref[...] += pq * z_row
        pk = pk_ref[oo]
        s_out[oo] = gl * s + jnp.dot(vw_ref[...], pk, precision=_HIGHEST,
                                     preferred_element_type=jnp.float32)
        z_out[pl.ds(o, 1), :] = gl * z_row + jnp.dot(
            w_ref[...], pk, precision=_HIGHEST,
            preferred_element_type=jnp.float32)[0:1]

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        sc = jax.lax.dot_general(q_ref[...], k_ref[...], nt,
                                 precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
        a = sc * sc * dec_ref[...]
        e = e_ref[...]
        y = jnp.dot(a, v_ref[...], precision=_HIGHEST,
                    preferred_element_type=jnp.float32) + e * yacc_ref[...]
        n = (jnp.sum(a, axis=-1, keepdims=True)
             + e[:, 0:1] * jnp.sum(nacc_ref[...], axis=-1, keepdims=True))
        y_out[...] = y / (n + EPS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_prefill_chunk(layer, s_all, z_all, phi_q, phi_k, q, k, v, vw,
                            w, dec, e, gl, *, interpret: bool):
    """T positions of one sequence through layer ``layer``'s state.

    ``s_all`` (L * n_kv, n_off, d, d), ``z_all`` (L * n_kv, n_off, d);
    per KV head (leading axis n_kv): ``phi_q`` (n_off, mT, d) with row
    i * T + t query head i at position t, ``phi_k`` (n_off, T, d), ``q``
    (mT, d), ``k``, ``v`` (T, d), ``vw`` (d, T), ``w`` (8, T), ``dec``
    (mT, T), ``e`` (mT, d), ``gl`` (8, d): see ``_chunk_kernel``. Returns
    (s_all, z_all, y (n_kv, mT, d)): the state advanced in place and the
    normalised outputs."""
    n_kv, n_off, mt, d = phi_q.shape
    t_len = k.shape[1]
    to = _offset_tile(n_off)
    head = lambda j, t, L: (j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n_kv, n_off // to),
        in_specs=[
            pl.BlockSpec((None, to, d, d),
                         lambda j, t, L: (L[0] * n_kv + j, t, 0, 0)),
            pl.BlockSpec((None, n_off, d),
                         lambda j, t, L: (L[0] * n_kv + j, 0, 0)),
            pl.BlockSpec((None, to, mt, d), lambda j, t, L: (j, t, 0, 0)),
            pl.BlockSpec((None, to, t_len, d), lambda j, t, L: (j, t, 0, 0)),
            pl.BlockSpec((None, mt, d), head),
            pl.BlockSpec((None, t_len, d), head),
            pl.BlockSpec((None, t_len, d), head),
            pl.BlockSpec((None, d, t_len), head),
            pl.BlockSpec((None, SUBLANES, t_len), head),
            pl.BlockSpec((None, mt, t_len), head),
            pl.BlockSpec((None, mt, d), head),
            pl.BlockSpec((None, SUBLANES, d), head),
        ],
        out_specs=[
            pl.BlockSpec((None, to, d, d),
                         lambda j, t, L: (L[0] * n_kv + j, t, 0, 0)),
            pl.BlockSpec((None, n_off, d),
                         lambda j, t, L: (L[0] * n_kv + j, 0, 0)),
            pl.BlockSpec((None, mt, d), head),
        ],
        scratch_shapes=[pltpu.VMEM((mt, d), jnp.float32),
                        pltpu.VMEM((mt, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, to=to), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct(z_all.shape, z_all.dtype),
                   jax.ShapeDtypeStruct((n_kv, mt, d), jnp.float32)],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=_PARAMS, interpret=interpret,
        name="retention_prefill_chunk",
    )(layer, s_all, z_all, phi_q, phi_k, q, k, v, vw, w, dec, e, gl)


# -- what the model's layer calls --------------------------------------------

def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def decode_attention(head_size: int, kv_mul: int, q, k, v, log_g, s_all,
                     z_all, layer, fresh, active=None):
    """One position for each of B rows against the stacked state.

    q (B, n_q * d), k, v (B, n_kv * d), already normed, rotated; ``log_g``
    (B, n_kv) the log of the gates; ``s_all`` (L * B * n_kv, n_off, d, d),
    ``z_all`` (L * B * n_kv, n_off, d); ``fresh`` (B,) True where the row
    is at its sequence's first position (the state before it is empty,
    whatever the row holds); ``active`` (B,) False for a row that takes no
    part (its state is left as it is). Returns (y (B, n_q * d), s_all,
    z_all, the smallest normaliser among the active rows).

    The earlier positions are read through the state, gated once more;
    the step's OWN position is added in the attention form, (q.k)^2 / d
    exactly: y = (g phi(q)^T S + a v) / (g phi(q).z + a + eps). A
    sequence's first position is then exact (the state term is zero), and
    the cancellation in phi(q).z over D products only ever weighs against
    a normaliser that holds the step's own, uncancelled term."""
    d, m = head_size, kv_mul
    B = q.shape[0]
    n_kv = k.shape[-1] // d
    scale = jnp.float32(d) ** -0.25
    live = jnp.ones((B,), bool) if active is None else active
    g = jnp.where(fresh[:, None], 0.0, jnp.exp(log_g))
    g = jnp.where(live[:, None], g, 1.0)                       # (B, n_kv)
    pq = phi(q.reshape(B * n_kv, m, d) * scale)
    pk = phi(k.reshape(B, n_kv, d) * scale)
    pk = jnp.where(live[:, None, None, None], pk, 0).reshape(
        B * n_kv, n_offsets(d), d)
    aux = jnp.zeros((B * n_kv, SUBLANES, d), jnp.float32)
    aux = aux.at[:, 0].set(v.reshape(B * n_kv, d))
    aux = aux.at[:, 1].set(jnp.broadcast_to(g.reshape(B * n_kv, 1),
                                            (B * n_kv, d)))
    s_all, z_all, y, nrm = retention_decode_step(
        jnp.reshape(layer, (1,)).astype(jnp.int32), s_all, z_all,
        pq, pk, aux, interpret=_interpret())
    gate = g.reshape(B * n_kv, 1)
    qs = q.reshape(B * n_kv, m, d) * scale
    own = jnp.sum(qs * (k.reshape(B * n_kv, 1, d) * scale), axis=-1) ** 2
    nrm = gate * nrm + own                                    # (B n_kv, m)
    y = (gate[..., None] * y + own[..., None] * v.reshape(B * n_kv, 1, d))
    y = y / (nrm[..., None] + EPS)
    low = jnp.min(jnp.where(jnp.repeat(live, n_kv)[:, None], nrm, jnp.inf))
    return y.reshape(B, n_kv * m * d), s_all, z_all, low


def chunk_attention(head_size: int, kv_mul: int, q, k, v, log_g, s_all,
                    z_all, layer, fresh, n_valid):
    """T positions of one sequence against the stacked state (L * n_kv
    rows). q (T, n_q * d), k, v (T, n_kv * d), ``log_g`` (T, n_kv);
    ``fresh`` scalar: the chunk opens its sequence; ``n_valid`` how many of
    the T positions are the sequence's (the rest is padding and leaves the
    state alone). Returns (y (T, n_q * d), s_all, z_all)."""
    d, m = head_size, kv_mul
    T = q.shape[0]
    n_kv = k.shape[-1] // d
    scale = jnp.float32(d) ** -0.25
    valid = jnp.arange(T) < n_valid
    c = jnp.cumsum(jnp.where(valid[:, None], log_g, 0.0), axis=0).T  # (n_kv,T)
    t_i = jnp.arange(T)
    dec = jnp.where((t_i[None, :] <= t_i[:, None]) & valid[None, :],
                    jnp.exp(c[:, :, None] - c[:, None, :]), 0.0)
    before = jnp.where(fresh, 0.0, 1.0)
    e = jnp.exp(c) * before                                      # (n_kv, T)
    w = jnp.exp(c[:, -1:] - c) * valid[None, :]                  # (n_kv, T)
    gl = jnp.exp(c[:, -1]) * before                              # (n_kv,)
    qh = jnp.transpose(q.reshape(T, n_kv, m, d) * scale,
                       (1, 2, 0, 3)).reshape(n_kv, m * T, d)
    kh = jnp.transpose(k.reshape(T, n_kv, d) * scale, (1, 0, 2))
    vh = jnp.transpose(v.reshape(T, n_kv, d), (1, 0, 2))
    pq = jnp.swapaxes(phi(qh), 1, 2)
    pk = jnp.swapaxes(phi(kh), 1, 2)
    s_all, z_all, y = retention_prefill_chunk(
        jnp.reshape(layer, (1,)).astype(jnp.int32), s_all, z_all, pq, pk,
        qh, kh, vh, jnp.swapaxes(vh * w[:, :, None], 1, 2),
        jnp.broadcast_to(w[:, None, :], (n_kv, SUBLANES, T)),
        jnp.tile(dec, (1, m, 1)),
        jnp.broadcast_to(jnp.tile(e, (1, m))[:, :, None], (n_kv, m * T, d)),
        jnp.broadcast_to(gl[:, None, None], (n_kv, SUBLANES, d)),
        interpret=_interpret())
    y = jnp.transpose(y.reshape(n_kv, m, T, d), (2, 0, 1, 3))
    return y.reshape(T, n_kv * m * d), s_all, z_all
