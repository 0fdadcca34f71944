"""Kimi Delta Attention (KDA, arXiv:2510.26692): a gated DELTA rule on a
state of fixed size, one (H heads, dk key channels, dv value channels)
float32 block a layer and sequence, the value channels along the lanes:

    S <- Diag(a_t) S;    S <- S + b_t k_t (v_t - S^T k_t)^T;    o_t = S^T q_t

with q_t, k_t (H, dk), v_t (H, dv), a decay a_t = exp(g_t) in (0, 1] that is
a VECTOR over a head's key channels (g_t (H, dk) <= 0) and a write strength
b_t (H,) in [0, 1]. Where a retention or an SSD state ADDS a rank-1 term
after a decay (``ops/retention.py``, ``ops/mamba2.py``), this one reads the
state before it writes it: ``S^T k`` is a matrix-vector product against the
state inside the update. The projections, the convolution, the norms and
the gates around the state are the caller's (``models/kda.py``).

The stacked state ``s_all`` is (layers * rows, H, dk, dv):

* ``kda_decode_step``: a Pallas kernel, one position for each of B rows, in
  place (``input_output_aliases``): a program a row, the row's whole state
  block read ONCE and written ONCE. With d = a (.) S the decayed state,
  u = d^T k = S^T (a (.) k), S' = d + (b k) (v - u)^T and
  o = S'^T q = S^T (a (.) q) + b (k . q) (v - u): both matrix-vector
  products are taken against the state AS IT LIES, so a head's tile passes
  the vector unit twice (the two products; the update) while it is in VMEM.
  The contraction runs over dk, which lies along SUBLANES: a product is one
  multiply and one add a vector register and a last fold of eight sublanes,
  no lane reduction (what PR 59 took off the vector unit in the attention
  fold was a reduction ALONG lanes). An MXU product would hold a head's
  (128, 128) tile as its stationary side for two rows of q and k, three
  times over for float32's three bf16 pieces: 96 tile loads a row, three
  times the DMA's time (``ops/mamba2.py`` met the same arithmetic). A
  head's key-channel columns (a, a k, a q, b k) arrive as ONE (dk, 128)
  block a row, head h's four columns on lanes h, H + h, 2 H + h, 3 H + h
  (``_columns``); v and b (k . q) as rows. A row that takes no part is
  given a = 1 and b = 0 and leaves its state exactly as it is; a row at its
  sequence's first position (``fresh``) finds it empty whatever it holds.
* ``kda_chunk``: T positions of ONE sequence as the chunked (WY / UT) form,
  XLA matrix products in float32 at highest precision (no kernel: ROADMAP
  queues one). Inside a chunk of C positions, with G_t the running sum of
  g (so G <= 0 and decreasing) and S_0 the state it starts from:

    M_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)
    (I + Diag(b) M) D = Diag(b) (V - (K (.) exp(G)) S_0)   a triangular solve
    O   = (Q (.) exp(G)) S_0 + P D
    S_C = Diag(exp(G_C)) S_0 + (K (.) exp(G_C - G))^T D

  equal to the recurrence (D's row j is b_j (v_j - u_j), what position j
  adds along k_j). EVERY decay is the exponential of a DIFFERENCE
  G_i - G_j with j <= i, never a quotient exp(G_i) / exp(G_j): at the lower
  bound g = -5 a position, exp(-G_j) leaves float32 after 18 positions. The
  solve's left side does not depend on the state, so W = T (K (.) exp(G))
  and U = T V with T = (I + Diag(b) M)^-1 Diag(b) are formed for ALL the
  chunks of a dispatch at once (forward substitution, which is backward
  stable where the Neumann product's powers of M are not: a row at a time
  inside sub-blocks of 16, a block at a time across them), and the scan
  that hands the state on is D = U - W S_0 and three products. M and P are
  formed in the same sub-blocks (``_pair_sums``). A padded position is
  given g = 0 and b = 0 by the caller and so neither decays the state nor
  writes to it. (The first form, which took a (64, 64, 128) plane of
  exponentials a head and 64 sequential rows of substitution over every
  chunk's 256 columns, ran an admission chunk of 512 in 688 ms on the chip:
  PERF.md section 6, PR 60.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16        # positions of a sub-block of the chunk form (``_pair_sums``)
DECODE_KERNEL = "kda_decode_step"
HIGHEST = jax.lax.Precision.HIGHEST
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)
TP_REFUSAL = ("a delta-rule state (ops/kda.py) is a (heads, dk, dv) block a "
              "layer and sequence that no tensor-parallel rank is given a "
              "share of yet")

_ein = functools.partial(jnp.einsum, precision=HIGHEST,
                         preferred_element_type=jnp.float32)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- one position for each of B rows ----------------------------------------------

def recur_step(s, q, k, v, g, b):
    """The recurrence as it is written, one position: s (..., H, dk, dv); q,
    k, g (..., H, dk); v (..., H, dv); b (..., H). Returns (o (..., H, dv),
    the new state). XLA, float32 at highest precision: what the decode
    kernel and the chunk form are held to, and the decode step off the
    chip."""
    s = jnp.exp(g)[..., None] * s
    u = _ein("...hkv,...hk->...hv", s, k)
    s = s + (b[..., None] * k)[..., None] * (v - u)[..., None, :]
    return _ein("...hkv,...hk->...hv", s, q), s


def _decode_kernel(layer_ref, fresh_ref, s_ref, col_ref, v_ref, w_ref,
                   s_out, o_out, *, heads: int):
    """One row: s (H, dk, dv); col (dk, 4 H lanes) the columns a | a k | a q
    | b k of each head; v (H, dv); w (H, dv) = b (k . q) down a head's row.
    o (H, dv)."""
    del layer_ref
    empty = fresh_ref[pl.program_id(0)] != 0
    for h in range(heads):
        s = jnp.where(empty, 0.0, s_ref[h])                      # (dk, dv)
        a, ak, aq, bk = (col_ref[:, j * heads + h:j * heads + h + 1]
                         for j in range(4))                      # (dk, 1)
        d = v_ref[h:h + 1, :] - jnp.sum(s * ak, axis=0, keepdims=True)
        o_out[h:h + 1, :] = (jnp.sum(s * aq, axis=0, keepdims=True)
                             + w_ref[h:h + 1, :] * d)
        s_out[h] = a * s + bk * d


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(layer, fresh, s_all, cols, v, w, *, interpret: bool):
    """``s_all`` (L * B, H, dk, dv) with layer ``layer``'s B rows adjacent;
    ``layer`` (1,) int32; ``fresh`` (B,) int32, nonzero: the row's state
    counts as empty; ``cols`` (B, dk, 4 H) (``_columns``); ``v``, ``w`` (B,
    H, dv). Returns (s_all updated in place, o (B, H, dv))."""
    n_rows, heads, dv = v.shape
    dk = cols.shape[1]
    at = lambda r, L, F: (L[0] * n_rows + r, 0, 0, 0)
    row = lambda r, L, F: (r, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_rows,),
        in_specs=[pl.BlockSpec((None, heads, dk, dv), at),
                  pl.BlockSpec((None, dk, cols.shape[2]), row),
                  pl.BlockSpec((None, heads, dv), row),
                  pl.BlockSpec((None, heads, dv), row)],
        out_specs=[pl.BlockSpec((None, heads, dk, dv), at),
                   pl.BlockSpec((None, heads, dv), row)])
    return pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct((n_rows, heads, dv), jnp.float32)],
        # operands count the scalar-prefetch arguments: s_all is 2
        input_output_aliases={2: 0},
        compiler_params=_PARAMS, interpret=interpret, name=DECODE_KERNEL,
    )(layer, fresh, s_all, cols, v, w)


def _columns(q, k, a, b):
    """(B, dk, 4 H): head h's key-channel columns a, a k, a q and b k on
    lanes h, H + h, 2 H + h and 3 H + h (H = 32: one 128-lane tile)."""
    cols = jnp.concatenate([a, a * k, a * q, b[..., None] * k], axis=1)
    return jnp.swapaxes(cols, 1, 2)


def scan_decode(layer, s_all, q, k, v, g, b, fresh, live, *, kernel: bool):
    """One position for each of B rows against layer ``layer``'s rows of the
    stacked state: q, k, g (B, H, dk), v (B, H, dv), b (B, H); ``fresh``
    (B,) True where the row is at its sequence's first position; ``live``
    (B,) False for a row that takes no part. ``kernel``: the Pallas kernel
    (interpret mode off the chip), else ``recur_step``. Returns (o (B, H,
    dv), s_all)."""
    n_rows = q.shape[0]
    g = jnp.where(live[:, None, None], g, 0.0)
    b = jnp.where(live[:, None], b, 0.0)
    empty = fresh & live
    if not kernel:
        s = jax.lax.dynamic_slice_in_dim(s_all, layer * n_rows, n_rows, 0)
        s = jnp.where(empty[:, None, None, None], 0.0, s)
        o, s = recur_step(s, q, k, v, g, b)
        return o, jax.lax.dynamic_update_slice_in_dim(
            s_all, s, layer * n_rows, 0)
    w = (b * jnp.sum(k * q, axis=-1))[..., None]
    s_all, o = kda_decode_step(
        jnp.reshape(layer, (1,)).astype(jnp.int32), empty.astype(jnp.int32),
        s_all, _columns(q, k, jnp.exp(g), b), v,
        jnp.broadcast_to(w, v.shape), interpret=_interpret())
    return o, s_all


# -- T positions of one sequence ----------------------------------------------------

def _row_inverse(a):
    """(I + tril(a, -1))^-1 of a (..., c, c), c small: forward substitution
    on the identity a row a turn, unrolled (row i needs rows j < i alone):
    backward stable, where the Neumann product's powers of ``a`` are not
    (keys that all point one way give it terms of 1e17 that cancel)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    rows = []
    for i in range(c):
        x_i = jnp.broadcast_to(eye[i], a.shape[:-2] + (c,))
        if rows:
            x_i = x_i - jnp.sum(a[..., i, :i, None] * jnp.stack(rows, -2),
                                axis=-2)
        rows.append(x_i)
    return jnp.stack(rows, axis=-2)


def _blocks(x, n: int):
    """(..., n s, n s) -> [[(..., s, s)] * n] * n."""
    s = x.shape[-1] // n
    return [[x[..., i * s:(i + 1) * s, j * s:(j + 1) * s] for j in range(n)]
            for i in range(n)]


def _unit_lower_inverse(a):
    """(I + tril(a, -1))^-1 of a (..., C, C), C up to ``SUB`` or a multiple
    of it: the diagonal blocks of ``SUB`` by ``_row_inverse``, then block
    forward substitution, T_ij = -T_ii sum_{j <= k < i} A_ik T_kj: small
    matrix products in place of C sequential rows."""
    c = a.shape[-1]
    if c <= SUB:
        return _row_inverse(a)
    n = c // SUB
    blk = _blocks(a, n)
    t = [[None] * n for _ in range(n)]
    mm = functools.partial(_ein, "...ab,...bc->...ac")
    for i in range(n):
        t[i][i] = _row_inverse(blk[i][i])
        for j in range(i):
            t[i][j] = -mm(t[i][i], sum(mm(blk[i][k], t[k][j])
                                       for k in range(j, i)))
        for j in range(i + 1, n):
            t[i][j] = jnp.zeros_like(blk[i][j])
    return jnp.concatenate([jnp.concatenate(row, axis=-1) for row in t],
                           axis=-2)


def _pair_sums(q, k, gc):
    """(M, P) of one chunk (module docstring): q, k, gc (C, H, dk), ``gc``
    the running sum of g -> (H, C, C) each, M_ij = sum_c k_ic k_jc e_ijc
    and P_ij = sum_c q_ic k_jc e_ijc with e = exp(G_i - G_j) where j <= i
    and 0 elsewhere. In sub-blocks of ``SUB`` positions: a diagonal block
    takes the differences as they are, a (SUB, SUB, dk) plane a head (masked
    BEFORE the exponential); a block I against the positions j before it
    takes exp(G_i - G_r) exp(G_r - G_j) with r the block's first position,
    BOTH exponents <= 0 (r lies between j and i), so that it is one matrix
    product of two decayed operands and still no quotient: a factor that
    underflows does so where the whole decay does."""
    c, h, _ = q.shape
    s = min(SUB, c)
    n = c // s
    qb, kb, gb = (x.reshape(n, s, *x.shape[1:]) for x in (q, k, gc))
    seen = jnp.tril(jnp.ones((s, s), bool))[None, :, :, None, None]
    e = jnp.exp(jnp.where(seen, gb[:, :, None] - gb[:, None, :], -jnp.inf))
    ek = e * kb[:, None]                                # (n, s, s, H, dk)
    diag = [jnp.transpose(jnp.sum(x[:, :, None] * ek, axis=-1), (0, 3, 1, 2))
            for x in (kb, qb)]                          # (n, H, s, s) each
    rows = [[], []]
    for i in range(n):
        left = jnp.exp(gb[i] - gb[i, :1])               # (s, H, dk), <= 1
        right = k[:i * s] * jnp.exp(gb[i, :1] - gc[:i * s])
        for out, x, d in zip(rows, (kb, qb), diag):
            parts = [_ein("ihc,jhc->hij", x[i] * left, right)] if i else []
            parts.append(d[i])
            if i + 1 < n:
                parts.append(jnp.zeros((h, s, c - (i + 1) * s), q.dtype))
            out.append(jnp.concatenate(parts, axis=-1))
    return tuple(jnp.concatenate(r, axis=-2) for r in rows)


def kda_chunk(s0, q, k, v, g, b, chunk: int = CHUNK):
    """T positions of ONE sequence from the state ``s0`` (H, dk, dv): q, k,
    g (T, H, dk), v (T, H, dv), b (T, H); g = 0 and b = 0 at a padded
    position. Chunks of ``chunk`` positions, the state handed on under a
    scan. Returns (o (T, H, dv), the state after position T - 1)."""
    t_len = q.shape[0]
    c = min(chunk, t_len)
    if c > SUB:         # whole sub-blocks (padding neither decays nor writes)
        c = -(-c // SUB) * SUB
    pad = -t_len % c

    def cut(x):     # (T, ...) -> (chunks, C, ...), zeros past T
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape(-1, c, *x.shape[1:])

    q, k, v, g, b = map(cut, (q, k, v, g, b))
    gc = jnp.cumsum(g, axis=1)                           # (N, C, H, dk)
    m, p = jax.vmap(_pair_sums)(q, k, gc)                # (N, H, C, C)
    bh = jnp.swapaxes(b, 1, 2)[..., None]                # (N, H, C, 1)
    by_head = lambda x: jnp.swapaxes(x, 1, 2)            # noqa: E731
    dk = k.shape[-1]
    # W | U = (I + Diag(b) M)^-1 Diag(b) [K (.) exp(G) | V], every chunk at
    # once: the left side does not know the state
    wu = _ein("nhij,nhjx->nhix", _unit_lower_inverse(bh * m),
              bh * by_head(jnp.concatenate([k * jnp.exp(gc), v], axis=-1)))
    q_in = by_head(q * jnp.exp(gc))                      # (N, H, C, dk)
    g_end = gc[:, -1]                                    # (N, H, dk)
    k_out = by_head(k * jnp.exp(g_end[:, None] - gc))    # (N, H, C, dk)

    def hand_on(s, xs):
        wu_n, p_n, q_n, k_n, end = xs
        d = wu_n[..., dk:] - _ein("hck,hkv->hcv", wu_n[..., :dk], s)
        o = _ein("hck,hkv->hcv", q_n, s) + _ein("hij,hjv->hiv", p_n, d)
        s = jnp.exp(end)[..., None] * s + _ein("hck,hcv->hkv", k_n, d)
        return s, o

    s, o = jax.lax.scan(hand_on, s0, (wu, p, q_in, k_out, g_end))
    o = jnp.swapaxes(o, 1, 2).reshape(-1, o.shape[1], o.shape[-1])
    return o[:t_len], s
