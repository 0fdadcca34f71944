"""The Mamba-2 (SSD, arXiv:2405.21060) state update on a state of fixed
size, one (H heads, P channels, N states) float32 block a layer and
sequence, N along the lanes:

    h_t[h] = exp(dt_t[h] A[h]) h_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]
    y_t[h] = h_t[h] C_t[g(h)],        A = -exp(A_log) a SCALAR a head

with x_t (H, P), dt_t (H,), and B_t, C_t (G, N) shared by the H / G heads of
a group (``ops/mamba.py`` is Mamba-1: a decay a (state, channel), a state of
(d_state, d_inner)). ``D x`` and everything around the state (projections,
the convolution, the gate and the grouped norm) is the caller's
(``models/nemotron.py``).

The stacked state ``ssm_all`` is (layers * rows, H, P, N):

* ``mamba2_decode_step``: a Pallas kernel, one position for each of B rows,
  in place (``input_output_aliases``): a program a row, the row's whole
  state block read once and written once, everything float32 on the VPU
  (an MXU product would hold the state as its stationary side for ONE row
  of C: 20 times the DMA's time). A head's channels lie along SUBLANES, so
  x_t arrives transposed, (P, heads on lanes), and a head's column is a
  static lane slice; y_t leaves the same way. A row whose ``dt`` is 0 leaves
  its state exactly as it is (exp(0) = 1, nothing added): how a row that
  takes no part is masked. ``keep`` 0 empties the state first (a row at its
  sequence's first position finds it empty whatever it holds).
* ``ssd_chunk``: T positions of ONE sequence as the published chunked form,
  XLA matrix products in float32 at highest precision (no kernel: ROADMAP
  queues one): inside a chunk of Q positions, with a_t = dt_t A and s_t its
  running sum,
    Y[t] = sum_{tau<=t} exp(s_t - s_tau) (C_t . B_tau) dt_tau x_tau
           + exp(s_t) C_t h_prev
    h_next = exp(s_Q) h_prev + sum_tau exp(s_Q - s_tau) dt_tau x_tau (outer) B_tau
  equal to the recurrence; every exponent is <= 0. A padded position is
  given ``dt`` 0 by the caller and so does not reach the state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DECODE_KERNEL = "mamba2_decode_step"
HIGHEST = jax.lax.Precision.HIGHEST
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _decode_kernel(layer_ref, s_ref, x_ref, d_ref, bc_ref, s_out, y_out, *,
                   heads: int, groups: int):
    """One row: s (H, P, N); x, d (P, L) with head h on lane h: dt x and
    keep * exp(dt A); bc rows [B of each group | C of each group] (2 G, N).
    y (P, L), head h's channels on lane h."""
    del layer_ref
    per = heads // groups
    lane = jax.lax.broadcasted_iota(jnp.int32, y_out.shape, 1)
    y = jnp.zeros(y_out.shape, jnp.float32)
    for h in range(heads):
        g = h // per
        s = d_ref[:, h:h + 1] * s_ref[h] \
            + x_ref[:, h:h + 1] * bc_ref[g:g + 1, :]
        s_out[h] = s
        col = jnp.sum(s * bc_ref[groups + g:groups + g + 1, :], axis=1,
                      keepdims=True)
        y = jnp.where(lane == h, col, y)
    y_out[...] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_decode_step(layer, ssm_all, x, decay, bc, *, interpret: bool):
    """``ssm_all`` (L * B, H, P, N) with layer ``layer``'s B rows adjacent;
    ``layer`` (1,) int32; ``x`` (B, P, L) = dt x transposed, head h on lane
    h (L lanes: H rounded up to whole tiles on the chip); ``decay`` (B, P,
    L) = keep * exp(dt A), a head's value down its lane; ``bc`` (B, 2 G,
    N). Returns (ssm_all updated in place, y (B, P, L))."""
    n_rows, p, lanes = x.shape
    _, heads, _, n = ssm_all.shape
    groups = bc.shape[1] // 2
    at = lambda r, L: (L[0] * n_rows + r, 0, 0, 0)
    row = lambda r, L: (r, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n_rows,),
        in_specs=[pl.BlockSpec((None, heads, p, n), at),
                  pl.BlockSpec((None, p, lanes), row),
                  pl.BlockSpec((None, p, lanes), row),
                  pl.BlockSpec((None, 2 * groups, n), row)],
        out_specs=[pl.BlockSpec((None, heads, p, n), at),
                   pl.BlockSpec((None, p, lanes), row)])
    return pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, groups=groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ssm_all.shape, ssm_all.dtype),
                   jax.ShapeDtypeStruct((n_rows, p, lanes), jnp.float32)],
        # operands count the scalar-prefetch argument: ssm_all is 1
        input_output_aliases={1: 0},
        compiler_params=_PARAMS, interpret=interpret, name=DECODE_KERNEL,
    )(layer, ssm_all, x, decay, bc)


# -- what the model's layer calls --------------------------------------------

def scan_decode(layer, ssm_all, a_log, x, dt, b, c, fresh, live):
    """One position for each of B rows: x (B, H, P), dt (B, H) after its
    softplus, b, c (B, G, N); ``fresh`` (B,) True where the row is at its
    sequence's first position; ``live`` (B,) False for a row that takes no
    part. Returns (y (B, H, P), ssm_all)."""
    n_rows, heads, p = x.shape
    dt = jnp.where(live[:, None], dt, 0.0)
    keep = jnp.where(fresh & live, 0.0, 1.0)
    decay = keep[:, None] * jnp.exp(-dt * jnp.exp(a_log))         # (B, H)
    lanes = heads if _interpret() else -(-heads // LANES) * LANES
    pad = [(0, 0), (0, 0), (0, lanes - heads)]
    xt = jnp.pad(jnp.swapaxes(dt[..., None] * x, 1, 2), pad)
    dc = jnp.pad(jnp.broadcast_to(decay[:, None, :], (n_rows, p, heads)),
                 pad)
    ssm_all, y = mamba2_decode_step(
        jnp.reshape(layer, (1,)).astype(jnp.int32), ssm_all, xt, dc,
        jnp.concatenate([b, c], axis=1), interpret=_interpret())
    return jnp.swapaxes(y[:, :, :heads], 1, 2), ssm_all


def _ssd_one(a, h_prev, x, dt, b, c):
    """One chunk of Q positions (module docstring): x (Q, H, P), dt (Q, H),
    b, c (Q, G, N), ``a`` (H,) negative, h_prev (H, P, N). Returns (h_next,
    y (Q, H, P))."""
    q_len, heads, _ = x.shape
    per = heads // b.shape[1]
    ein = functools.partial(jnp.einsum, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    s = jnp.cumsum(dt * a, axis=0)                               # (Q, H)
    seen = jnp.tril(jnp.ones((q_len, q_len), bool))[:, :, None]
    # exp(s_t - s_tau), 0 where tau > t (masked BEFORE the exponential)
    fade = jnp.exp(jnp.where(seen, s[:, None, :] - s[None, :, :], -jnp.inf))
    cb = jnp.repeat(ein("tgn,sgn->tsg", c, b), per, axis=2)      # (Q, Q, H)
    xdt = x * dt[..., None]
    y = ein("tsh,shp->thp", fade * cb, xdt)
    c_h, b_h = jnp.repeat(c, per, axis=1), jnp.repeat(b, per, axis=1)
    y = y + jnp.exp(s)[..., None] * ein("thn,hpn->thp", c_h, h_prev)
    tail = jnp.exp(s[-1][None] - s)                              # (Q, H)
    h_next = jnp.exp(s[-1])[:, None, None] * h_prev + ein(
        "shp,shn->hpn", xdt * tail[..., None], b_h)
    return h_next, y


def ssd_chunk(h_prev, a_log, x, dt, b, c, chunk: int):
    """T positions of ONE sequence from the state ``h_prev`` (H, P, N): x
    (T, H, P), dt (T, H) after its softplus and 0 at a padded position, b, c
    (T, G, N); chunks of ``chunk`` positions, the state handed on. Returns
    (y (T, H, P), h_next)."""
    t_len = x.shape[0]
    a = -jnp.exp(a_log)
    if t_len <= chunk:
        h_next, y = _ssd_one(a, h_prev, x, dt, b, c)
        return y, h_next
    pad = -t_len % chunk

    def cut(v):     # (T, ...) -> (chunks, chunk, ...), zeros past T
        v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        return v.reshape(-1, chunk, *v.shape[1:])

    h_next, y = jax.lax.scan(
        lambda h, xs: _ssd_one(a, h, *xs), h_prev,
        (cut(x), cut(dt), cut(b), cut(c)))
    return y.reshape(-1, *y.shape[2:])[:t_len], h_next
