"""On-device generation loop: the whole token loop as ONE jitted program.

The reference's generation loop (tokenizer.cpp:321-394) calls infer() once per
token from the host. On TPU that per-token host round-trip costs more than the
7B forward pass itself (dispatch + transfer latency), so the TPU-native hot
path moves the loop on device: a ``lax.scan``
over decode steps where each step runs the forward pass AND picks the next
token, with no host involvement until the whole chain is done.

Sampling runs on device with the reference's semantics (tokenizer.cpp:206-319):
argmax at temperature 0, otherwise softmax(logits/temp) + nucleus top-p with
the (1-p)/(n-1) cutoff pre-filter, or a plain multinomial CDF walk when topp
is outside (0,1). The per-step random coins are the ONE thing precomputed on
the host: the reference draws them from a stateful xorshift64* stream
(utils.cpp:27-38), and the stream is data-independent, so the host pre-draws
``coins[i]`` for every post-prompt step and the device consumes them in order
— bit-identical coin sequence, no uint64 emulation on device.

Early stop: the reference breaks on BOS before decoding it. The single-
sequence loop is a ``lax.while_loop`` that terminates on a produced BOS, so
an early stop costs only the steps actually run; unwritten tail slots of the
token buffer read as BOS, and the host truncates at the first BOS as always.
The batch loop is a fixed-length scan (lockstep rows share the position
clock), with finished rows frozen to emit the same BOS-filled tail — the two
paths share one post-BOS output contract.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

# step_fn(params, cache, tokens (1,), pos) -> (logits (1, V), cache)
StepFn = Callable[..., tuple[jax.Array, Any]]


def _mult_walk(probs: jax.Array, coin: jax.Array) -> jax.Array:
    """Multinomial CDF walk (tokenizer.cpp:226-238)."""
    v = probs.shape[-1]
    cdf = jnp.cumsum(probs)
    return jnp.minimum(jnp.searchsorted(cdf, coin, side="right"),
                       v - 1).astype(jnp.int32)


def _nucleus_walk(probs: jax.Array, coin: jax.Array,
                  topp: jax.Array | float) -> jax.Array:
    """Nucleus pick (tokenizer.cpp:240-281): cutoff pre-filter, stable
    descending sort, cut at cum > topp, CDF walk over the kept prefix
    scaled by coin*cum. Works with static or traced ``topp`` — the ONE
    copy of the math shared by sample_device and sample_device_dynamic.
    When the cutoff keeps nothing (possible for topp < 1/v) falls back to
    the argmax, like the host Sampler."""
    v = probs.shape[-1]
    cutoff = (1.0 - topp) / (v - 1)
    kept = jnp.where(probs >= cutoff, probs, 0.0)
    order = jnp.argsort(-kept)  # stable: ties keep index order
    p_sorted = kept[order]
    cum = jnp.cumsum(p_sorted)
    # first index where cumulative prob exceeds topp (== last kept index)
    last = jnp.argmax(cum > topp)
    last = jnp.where(cum[-1] > topp, last, v - 1)
    r = coin * cum[last]
    idx = jnp.minimum(jnp.searchsorted(cum, r, side="right"), last)
    nuc = order[idx].astype(jnp.int32)
    return jnp.where(cum[-1] > 0.0, nuc,
                     jnp.argmax(probs).astype(jnp.int32))


def sample_device(logits: jax.Array, coin: jax.Array, temperature: float,
                  topp: float) -> jax.Array:
    """Reference Sampler::sample on device. logits (V,) f32; coin scalar f32.

    temperature/topp are static (fixed per generation run), so the strategy
    branch resolves at trace time.
    """
    if temperature == 0.0:
        return jnp.argmax(logits).astype(jnp.int32)
    probs = jax.nn.softmax(logits.astype(jnp.float32) / temperature)
    if topp <= 0 or topp >= 1:
        return _mult_walk(probs, coin)
    return _nucleus_walk(probs, coin, topp)


def sample_device_dynamic(logits: jax.Array, coin: jax.Array,
                          temperature: jax.Array,
                          topp: jax.Array) -> jax.Array:
    """Reference sampler with TRACED temperature/topp — the per-row variant
    for the fused continuous chain (runtime/continuous.step_many), where
    each slot carries its own request's sampling params. Computes the
    greedy/multinomial/nucleus candidates and selects (the strategy branch
    cannot resolve at trace time); semantics mirror sample_device and the
    host Sampler, including the degenerate-nucleus argmax fallback.
    """
    greedy = jnp.argmax(logits).astype(jnp.int32)
    safe_t = jnp.where(temperature == 0.0, 1.0, temperature)
    probs = jax.nn.softmax(logits.astype(jnp.float32) / safe_t)
    in01 = (topp > 0.0) & (topp < 1.0)
    return jnp.where(temperature == 0.0, greedy,
                     jnp.where(in01, _nucleus_walk(probs, coin, topp),
                               _mult_walk(probs, coin)))


def greedy_verify_tokens(logits: jax.Array) -> jax.Array:
    """Device-side argmax over a (B, K, V) speculative-verify logit block
    (runtime/continuous.step_spec): when EVERY active row is greedy the
    host replay needs only the argmax ids, so the chain ships a (B, K)
    int32 block instead of the full f32 logit cube — the same transfer cut
    the fused chain's greedy_only branch makes. Ties break lowest-index,
    matching np.argmax in the host sampler (sample_argmax), so the greedy
    bitwise-parity contract is unchanged."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _make_decode_run(step_fn: StepFn, max_steps: int, temperature: float,
                     topp: float, i4: bool = False):
    """Build run(params, cache, prompt_padded, first_token, coins,
    start_pos, num_steps) -> (tokens (max_steps,), cache): the fused
    generation loop (raw traceable fn; make_decode_loop jits it).

    ``i4``: the chain converts its nb-major Q40 leaves to int4 planes at
    its start (the builder's resolved ``Q40Layout.i4_chain``,
    ops/linear.q40_body_policy; ops/pallas_q40.chain_weight_prep).

    ``max_steps`` (typically seq_len) fixes the BUFFER shapes only; the
    actual step budget ``num_steps`` is a traced scalar bound of the
    while_loop, so every --steps value reuses ONE compilation (a distinct
    --steps used to recompile the whole chain — the round-1 cold-start
    trap). The int32 token buffer is max_steps long: seq_len=2048 costs
    8 kB, nothing, against a ~minute XLA compile per distinct shape.

    prompt_padded: (max_steps+1,) int32, prompt tokens then -1 padding.
    Step ``i`` (absolute position start_pos + i) forces prompt_padded[i+1]
    when >= 0, else samples — exactly the forced-prompt-then-sample
    schedule of the reference loop (tokenizer.cpp:360-366). coins:
    (max_steps,) f32, consumed at sampled steps. start_pos: 0 for a fresh
    generation, the checkpointed position for a resumed one.
    """

    from ..io.tokenizer import BOS

    def run(params, cache, prompt_padded, first_token, coins, start_pos,
            num_steps):
        """start_pos: absolute position of the first step — 0 for a fresh
        generation, the checkpointed position for a resumed one (the cache
        must already hold positions 0..start_pos-1; runtime/checkpoint.py).

        The loop is a lax.while_loop, not a scan: a sampled BOS ends the
        chain EARLY on device (the reference's stop condition), so a
        2048-step budget that terminates at step 50 costs 50 forwards, not
        2048 — and a num_steps budget below max_steps likewise stops at
        num_steps. The token buffer is BOS-initialized — untouched slots
        read as the terminator, so the host-side truncation is unchanged.
        """
        if isinstance(params, dict):
            from ..ops.pallas_q40 import chain_weight_prep

            params = chain_weight_prep(params, i4)
        toks0 = jnp.full((max_steps,), BOS, dtype=jnp.int32)

        def cond(carry):
            i, done, token, cache, toks = carry
            return (i < num_steps) & ~done

        def body(carry):
            i, done, token, cache, toks = carry
            logits, cache = step_fn(params, cache, token[None],
                                    start_pos + i)
            sampled = sample_device(logits[0], coins[i], temperature, topp)
            nxt = jnp.where(prompt_padded[i + 1] >= 0, prompt_padded[i + 1],
                            sampled)
            # stop on a PRODUCED BOS (the input token at i=0 is legitimately
            # BOS — every prompt starts with it)
            return (i + 1, nxt == BOS, nxt, cache, toks.at[i].set(nxt))

        _, _, _, cache, toks = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.bool_(False), first_token, cache,
                         toks0))
        return toks, cache

    run.__name__ = "decode_chain"
    return run


def make_decode_loop(step_fn: StepFn, max_steps: int, temperature: float,
                     topp: float, i4: bool = False):
    """The fused generation loop, jitted (see _make_decode_run)."""
    return jax.jit(_make_decode_run(step_fn, max_steps, temperature, topp,
                                    i4), donate_argnums=1)


def make_decode_loop_aot(step_fn: StepFn, max_steps: int,
                         temperature: float, topp: float,
                         exe_cache_dir: str | None = None,
                         i4: bool = False):
    """make_decode_loop variant that AOT-compiles with the parameter layouts
    PINNED to what the placed arrays actually have, instead of letting the
    AOT compiler choose compact input layouts and convert them inside the
    program.

    Why: with unconstrained inputs the compiler may pick a parameter layout
    different from what the Pallas kernels pin (row-major), materializing
    layout-conversion copies of every multi-GB weight stack INSIDE the
    chain — at 13B those tile-padded temps alone are ~10 GB, an OOM on a
    16 GB chip. The device client places an array in a layout of its own
    choosing (for an awkward minor dim, e.g. 7B's w2 with nb = 344, not
    row-major), and Layout.AUTO can publish formats the final executable
    then rejects. So the one self-consistent order is place FIRST, read
    each leaf's actual ``Array.format``, and compile with exactly those —
    the executable accepts the arrays by construction, and any residual
    conversion is the compiler's explicit, visible choice.

    ``exe_cache_dir`` (VERDICT r2 #7, sub-minute warm start): persist the
    fully-compiled executable via jax.experimental.serialize_executable,
    keyed by the sha256 of the LOWERED HLO (any code/shape/kernel change
    re-keys cleanly) + jax version + platform. Unlike the persistent HLO
    compile cache, the serialized executable also carries the compiled
    custom-call artifacts.

    Returns compile_and_place(params_host, cache, prompt, first, coins,
    start, n) -> (compiled, params_on_device).
    """
    import numpy as np

    run = _make_decode_run(step_fn, max_steps, temperature, topp, i4)

    def compile_and_place(params_host, *rest):
        def sds(a):
            # dlint: allow[D001] host-tree leaves only — shape/dtype probe
            a = np.asarray(a) if not hasattr(a, "dtype") else a
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        placed = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a)), params_host)
        param_formats = jax.tree_util.tree_map(lambda a: a.format, placed)
        jitted = jax.jit(run, donate_argnums=1,
                         in_shardings=(param_formats,) + (None,) * 6)
        abstract = (jax.tree_util.tree_map(sds, placed),
                    *(jax.tree_util.tree_map(sds, r) for r in rest))
        lowered = jitted.lower(*abstract)
        return _load_or_compile(lowered, exe_cache_dir), placed

    return compile_and_place


def _load_or_compile(lowered, exe_cache_dir: str | None):
    """Deserialize a cached executable for this exact lowering, else
    compile and serialize it. A failure in the serialization layer degrades
    to a plain compile (never blocks the run) and is reported and counted
    (utils/compile_cache.cache_error)."""
    if not exe_cache_dir:
        return lowered.compile()
    import hashlib
    import os
    import pickle
    import sys

    import jaxlib
    from jax.experimental.serialize_executable import (deserialize_and_load,
                                                       serialize)

    from ..utils.compile_cache import cache_error

    # key on everything that could invalidate a compiled binary: jax +
    # runtime lib versions, the CHIP KIND (default_backend() is just 'tpu'
    # for every TPU generation), and the lowered HLO itself (which embeds
    # source line numbers in op metadata — so ANY edit to files on the
    # traced path re-keys; conservative by design)
    dev = jax.devices()[0]
    salt = (jax.__version__ + jaxlib.__version__ + jax.default_backend()
            + dev.device_kind)
    key = hashlib.sha256((salt + lowered.as_text()).encode()).hexdigest()[:32]
    path = os.path.join(exe_cache_dir, f"exe_{key}.pkl")
    if os.path.exists(path):
        try:
            with open(path, "rb") as fh:
                payload, in_tree, out_tree = pickle.load(fh)
            compiled = deserialize_and_load(payload, in_tree, out_tree)
            print(f"⏩ loaded serialized executable ({path})",
                  file=sys.stderr)
            return compiled
        except Exception as e:  # noqa: BLE001 - any unreadable entry
            # corrupt/stale entry: drop it and fall through to a fresh
            # compile + re-serialize below (returning early here would
            # leave the cache empty for the NEXT process too)
            cache_error("exe", f"dropping unreadable entry {path}", e)
            try:
                os.unlink(path)
            except OSError:
                pass
    compiled = lowered.compile()
    try:  # serialize/write failures must not recompile or kill the run
        os.makedirs(exe_cache_dir, exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump(serialize(compiled), fh)
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 - cache must never kill the run
        cache_error("exe", f"could not write {path}", e)
    return compiled


def make_batch_decode_loop(spec, steps: int, temperature: float, topp: float,
                           step_fn: StepFn | None = None):
    """Fused decode loop over B sequences in lockstep (models/llama.
    forward_batch) — the throughput path the reference lacks (batch=1 only).

    run(params, cache, prompts (B, steps+1), first_tokens (B,),
        coins (B, steps)) -> (tokens (B, steps), cache).

    All rows share the position clock (the shared-pos contract that keeps
    the cache update an in-place dynamic_update_slice — see forward_batch).
    Ragged prompts right-pad with -1: at position p a row forces
    prompts[b, p+1] when >= 0, else samples with its own coin (vmapped
    reference sampler semantics).

    ``step_fn`` overrides the single-chip forward_batch with another
    (params, cache, tokens (B,), pos) -> (logits (B, V), cache) step — the
    tensor-parallel composition passes parallel/tp.make_sharded_forward_batch.
    """
    import functools

    from ..models.llama import forward_batch

    if steps > spec.seq_len:
        raise ValueError(f"steps={steps} exceeds seq_len={spec.seq_len}")
    if step_fn is None:
        step_fn = functools.partial(forward_batch, spec)

    from ..io.tokenizer import BOS

    def run(params, cache, prompts, first_tokens, coins):
        def body(carry, xs):
            tokens, active, cache = carry
            pos, coin_row = xs
            logits, cache = step_fn(params, cache, tokens, pos)
            sampled = jax.vmap(
                lambda lg, c: sample_device(lg, c, temperature, topp)
            )(logits, coin_row)
            forced = prompts[:, pos + 1]
            nxt = jnp.where(forced >= 0, forced, sampled)
            # a finished row (produced BOS earlier) freezes its input token
            # and emits BOS — the same post-BOS tail the single-sequence
            # while_loop's untouched buffer yields
            rec = jnp.where(active, nxt, BOS)
            active = active & (nxt != BOS)
            tokens = jnp.where(active, nxt, tokens)
            return (tokens, active, cache), rec

        B = first_tokens.shape[0]
        xs = (jnp.arange(steps, dtype=jnp.int32), coins.T)
        (_, _, cache), toks = jax.lax.scan(
            body, (first_tokens, jnp.ones((B,), bool), cache), xs)
        return toks.T, cache  # (B, steps)

    return jax.jit(run, donate_argnums=1)


