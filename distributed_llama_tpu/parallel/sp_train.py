"""Sequence-parallel training: ring attention over an sp-sharded sequence.

The reference has no training and no long-context story at all (SURVEY.md
§5); parallel/train.py adds dp x tp training at replicated sequence length.
This module adds the LONG-SEQUENCE axis: tokens are sharded over the "sp"
mesh axis in contiguous chunks, every rank runs the transformer on its
chunk, and attention is the ppermute ring of parallel/ring.ring_attention —
O(T_local) memory per device, K/V moving once around the ring per layer.
Gradients flow through the ring (JAX differentiates ppermute), so this is a
real training step, not just a forward.

Sharding: batch over dp, sequence over sp, params replicated (tp composes
later; the reference's TP applies to inference parity, training tp lives in
parallel/train.py). The next-token shift crosses chunk boundaries, so the
host-side wrapper shifts BEFORE sharding: step(tokens (B, T+1)) slices
inputs/targets globally and shard_map splits both over sp.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import forward_seq
from ..models.spec import TransformerSpec
from .ring import ring_attention

_shard_map = functools.partial(jax.shard_map, check_vma=False)


def _local_forward_seq(spec: TransformerSpec, params: dict[str, Any],
                       tokens_local: jax.Array, sp_index, n_sp: int):
    """Per-rank transformer over this rank's sequence chunk (inside
    shard_map): forward_seq with shard-offset positions and ring attention
    across the sp axis. tokens_local (B, T_loc) -> logits (B, T_loc, vocab).
    """
    t_loc = tokens_local.shape[1]
    q_start = sp_index * t_loc
    n_q, n_kv, hs = spec.n_heads, spec.n_kv_heads, spec.head_size

    def ring_attn(q, k, v):
        def ring_one(qb, kb, vb):
            return ring_attention(hs, spec.kv_mul,
                                  qb.reshape(t_loc, n_q, hs),
                                  kb.reshape(t_loc, n_kv, hs),
                                  vb.reshape(t_loc, n_kv, hs),
                                  q_start, t_loc, axis="sp",
                                  axis_size=n_sp)

        return jax.vmap(ring_one)(q, k, v)           # (B, T_loc, n_q*hs)

    return forward_seq(spec, params, tokens_local,
                       positions=q_start + jnp.arange(t_loc),
                       attention_fn=ring_attn)


def make_sp_train_step(spec: TransformerSpec, mesh: Mesh,
                       optimizer: optax.GradientTransformation | None = None,
                       learning_rate: float = 1e-4):
    """Build (init_fn, step_fn) for dp x sp sequence-parallel training.

    step_fn(params, opt_state, tokens (B, T+1)) -> (params, opt_state, loss);
    T must divide by the mesh's sp size. Loss is the global mean next-token
    CE — identical (up to f32 reduction order) to train.make_train_step on
    the same tokens, which is the parity gate in test_sp_train.py.
    """
    optimizer = optimizer or optax.adamw(learning_rate)
    n_sp = mesh.shape["sp"]

    def local_loss(params, inputs, targets):
        sp_index = jax.lax.axis_index("sp")
        logits = _local_forward_seq(spec, params, inputs, sp_index, n_sp)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        # global mean over (dp, sp): every rank holds an equal token count
        return jax.lax.pmean(ce.mean(), ("dp", "sp"))

    def sharded_loss(params, inputs, targets):
        fn = _shard_map(
            local_loss, mesh=mesh,
            in_specs=(P(), P("dp", "sp"), P("dp", "sp")),
            out_specs=P())
        return fn(params, inputs, targets)

    def step(params, opt_state, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]  # global shift FIRST
        loss, grads = jax.value_and_grad(sharded_loss)(params, inputs,
                                                       targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def init_fn(params):
        repl = NamedSharding(mesh, P())
        params = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), repl), params)
        opt_state = jax.jit(optimizer.init)(params)
        return params, opt_state

    return init_fn, jax.jit(step, donate_argnums=(0, 1))
