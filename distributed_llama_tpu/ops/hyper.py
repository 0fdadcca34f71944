"""A residual path of several streams: manifold-constrained
hyper-connections (arXiv:2512.24880; ``TransformerSpec.hyper``,
``models/reference_hyper.py`` states the layer in full).

Where a layer computes ``x + F(x)``, a spec with ``hyper`` carries n streams
X (held (n, R, C): a stream is one plane of whole (8, 128) tiles; with the
streams on the second axis the chip pads 4 sublanes to 8 and copies every
slice) and computes, for each sub-layer F, from per-token coefficients

  xhat  = vec(X) / sqrt(mean(vec(X)^2) + eps)                (R, n C)
  z     = xhat @ phi^T                                       (R, 2 n + n^2)
  H_pre = sigmoid(a_pre z[:n] + b_pre);  H_post = 2 sigmoid(a_post z[n:2n] + b_post)
  H_res = sinkhorn(exp(clip(a_res mat(z[2n:]) + B_res, lo, hi)))

the sub-layer's input ``h = sum_i H_pre[i] X[i]`` and the streams' update
``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(h)``.

``residual_in`` and ``residual_out`` are the ONE residual function of every
forward: without ``hyper`` the first hands the carry through and the second
is the add that was there before, so a spec without streams traces to that
one ``add`` and to nothing else (tests/test_hyper.py holds it to that).

Everything is float32; the projection is a dot at ``HIGHEST``; the two
mixes are elementwise multiply-adds over the n (static) streams, which the
compiler fuses. The coefficient stage (flat norm, projection, sigmoids and
the unrolled Sinkhorn rounds on (n, rows) tiles, rows on the lanes) is plain
XLA: the chip's compiler makes four or five small fusions of every Sinkhorn
round, some 120 device ops a sub-layer, of a tenth of a microsecond each.
One Pallas call a sub-layer for the stage was built and measured level with
it end to end (23.69 against 23.72 ms a step), so it was taken out again
(PERF.md section 6 has both readings; ROADMAP R10 c has what would pay).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..obs.spans import SCOPE_HC_COEF, SCOPE_HC_MIX

HIGHEST = jax.lax.Precision.HIGHEST


class Coefficients(NamedTuple):
    """A sub-layer's per-token coefficients, the ROWS LAST (a coefficient
    of every row is one lane vector)."""
    pre: jax.Array    # (n, R)
    post: jax.Array   # (n, R)
    res: jax.Array    # (n, n, R): X'[i] takes res[i, j] of X[j]


def _sinkhorn_rows(m: list, iters: int, eps: float) -> list:
    """``m[i]`` (n, rows) is row i of exp(clamped logits), its n entries on
    the sublanes and the tokens on the lanes -> the rows after ``iters``
    rounds of: each column over (its sum + eps), then each row likewise."""
    eps = jnp.float32(eps)
    for _ in range(iters):
        col = sum(m) + eps                                  # (n, rows), by j
        m = [r / col for r in m]
        m = [r / (jnp.sum(r, axis=0, keepdims=True) + eps) for r in m]
    return m


def _gate_rows(gate: jax.Array, n: int) -> jax.Array:
    """(a_pre, a_post, a_res) -> (2 n + n^2, 1): each logit's gate."""
    return jnp.repeat(gate, jnp.asarray([n, n, n * n]),
                      total_repeat_length=n * (2 + n))[:, None]


def _from_logits(hc, n: int, z: jax.Array):
    """z (2 n + n^2, rows), gated and biased -> (pre, post, [res rows])."""
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    # a spec that states no clamp (both bounds infinite) computes none
    clip = ((lambda a: jnp.clip(a, hc.clamp_min, hc.clamp_max))
            if hc.clamped else (lambda a: a))
    rows = [jnp.exp(clip(z[(2 + i) * n:(3 + i) * n])) for i in range(n)]
    return pre, post, _sinkhorn_rows(rows, hc.sinkhorn_iters, hc.eps)


def _flat_norm(x: list, norm_eps: float):
    """1 / sqrt(mean(vec(X)^2) + eps) a row, (rows, 1), of streams x[i]
    (rows, C)."""
    ss = sum(jnp.sum(xi * xi, axis=-1, keepdims=True) for xi in x)
    return jax.lax.rsqrt(ss / jnp.float32(len(x) * x[0].shape[-1])
                         + jnp.float32(norm_eps))


def _project(phi, x: list, inv):
    """z (2n + n^2, rows) = phi @ xhat^T, a stream's columns at a time."""
    dim = x[0].shape[-1]
    return sum(jax.lax.dot_general(
        phi[:, i * dim:(i + 1) * dim], xi * inv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)
        for i, xi in enumerate(x))


def coefficients(hc, norm_eps: float, phi: jax.Array, gate: jax.Array,
                 bias: jax.Array, x: jax.Array) -> Coefficients:
    """One sub-layer's per-token coefficients from the streams x (n, R, C),
    ``phi`` (2 n + n^2, n C), ``gate`` (3,), ``bias`` (2 n + n^2,)."""
    n, rows, _ = x.shape
    with jax.named_scope(SCOPE_HC_COEF):
        streams = [x[i] for i in range(n)]
        z = _project(phi, streams, _flat_norm(streams, norm_eps))
        pre, post, res = _from_logits(
            hc, n, z * _gate_rows(gate, n) + bias[:, None])
    return Coefficients(pre, post, jnp.stack(res).reshape(n, n, rows))


def fan_out(spec, x: jax.Array) -> jax.Array:
    """The embedding (R, C) as the carry: every stream a copy of it."""
    if spec.hyper is None:
        return x
    return jnp.broadcast_to(x[None], (spec.hyper.streams, *x.shape))


def fold_in(spec, x: jax.Array) -> jax.Array:
    """The carry as what the final norm reads: the streams' sum."""
    if spec.hyper is None:
        return x
    with jax.named_scope(SCOPE_HC_MIX):
        return sum(x[i] for i in range(x.shape[0]))


def residual_in(spec, lw: dict[str, Any], sub: str, x: jax.Array):
    """(a sub-layer's input (R, C), what ``residual_out`` needs) from the
    carry: the carry itself and None without streams."""
    if spec.hyper is None:
        return x, None
    coef = coefficients(spec.hyper, spec.norm_eps, lw[f"hc_{sub}_phi"],
                        lw[f"hc_{sub}_gate"], lw[f"hc_{sub}_bias"], x)
    with jax.named_scope(SCOPE_HC_MIX):
        h = sum(coef.pre[i][:, None] * x[i] for i in range(x.shape[0]))
    return h, coef


def residual_out(coef: Coefficients | None, x: jax.Array,
                 y: jax.Array, clamp: float = 0.0) -> jax.Array:
    """The carry after a sub-layer whose output is y (R, C); the streams
    written back clipped to +- ``clamp`` where the spec states one
    (``HyperConnections.stream_clamp``)."""
    if coef is None:
        return x + y
    n = x.shape[0]
    with jax.named_scope(SCOPE_HC_MIX):
        out = jnp.stack(
            [sum(coef.res[i, j][:, None] * x[j] for j in range(n))
             + coef.post[i][:, None] * y for i in range(n)], axis=0)
        return jnp.clip(out, -clamp, clamp) if clamp else out
