"""Generation loop + engine (reference generate(), tokenizer.cpp:321-394).

Engine wraps the jitted forward (single-chip or tensor-parallel) behind the
reference's `Inference::infer(token, pos) -> logits` shape
(transformer-tasks.cpp:535-547), and the loop reproduces the reference's
observable behavior: prompt tokens forced one at a time, sampling after the
prompt, stop on BOS, per-token stats line and final averages.

At temperature 0 the loop runs ONE STEP AHEAD of the host: the step program
also returns the argmax of its logits (what ``Sampler.sample`` gives at
temperature 0), and ``Engine.infer(..., pick=True)`` enqueues step n+1 on that
token, still on the device, before it waits for step n's four bytes. The
chip then never waits for the host between tokens. With a temperature the
host samples from the logits as before: on the chip the device sampler of
the ``--fast`` chain misses the host ``Sampler``'s token where the
distribution is near uniform (PERF.md section 6, PR 27), and this loop's
contract is the host's stream.

Stats: the reference splits per-token time into I (inference) and T (transfer)
via task-type timing (utils.cpp:104-106) and counts socket bytes. Under XLA
the collectives are fused into the step, so we report:
  I = the call of Engine.infer: launch and wait. Running ahead, the launch
      is the NEXT step's and the wait is for this step's token (the step
      has been running since the call before, so I is the device step less
      the host's time between two calls)
  T = host-side time between infer's return and the token being known: the
      host sampler (0 where the device picks)
  S/R = analytic per-token collective bytes (parallel/comm_stats.py)
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

from ..io.tokenizer import BOS, Tokenizer
from ..models.llama import forward, init_cache
from ..models.spec import TransformerSpec
from ..obs.log import log_event
from ..obs.metrics import summarize_values
from ..obs.spans import host_phase, named_program, startup_phase
from ..parallel.comm_stats import (CommStats, ici_all_gather_bytes,
                                   sp_lse_bytes, tp_scheme)
from .sampling import Sampler


@dataclasses.dataclass
class _Step:
    """One enqueued run of the step program: its results, still on the
    device, and what ``Engine.infer`` needs to hand it out or drop it."""
    pos: int
    logits: Any
    picked: Any            # (1,) int32: the next step's input, never fetched
    moe: list              # [(L, E) routed-row counts] of an expert spec
    norm_min: list         # [(L,) smallest normaliser] of a retention spec
    token: int | None = None  # input token, once the host knows it


def _with_pick(step):
    """``step`` (a forward: logits, cache[, moe counts]) AND the greedy pick
    of the next token from row 0 of its logits (lowest index on a tie, as
    the host's ``sample_argmax``), as one traceable function. The picked
    token stays on the device as the next step's (1,) input."""
    def step_and_pick(params, cache, tokens, pos):
        import jax.numpy as jnp

        logits, cache, *moe = step(params, cache, tokens, pos)
        picked = jnp.argmax(logits[0]).astype(jnp.int32)
        return (logits, picked[None], cache, *moe)

    return step_and_pick


class Engine:
    """Owns params + cache + the jitted step; exposes infer(token, pos)."""

    @startup_phase("engine")   # less the pack, place and cache inside it
    def __init__(self, spec: TransformerSpec, params: dict[str, Any],
                 mesh=None, cache_dtype=None, fast_prefill: bool = False,
                 q40_layout=None):
        import functools

        import jax
        import jax.numpy as jnp

        self.spec = spec
        self.jnp = jnp
        self.mesh = mesh
        self.fast_prefill = fast_prefill
        # f32 = logit-parity default; bf16 halves cache memory + attention
        # HBM traffic (the reference's cache is f32, transformer.cpp:198-199)
        self.cache_dtype = cache_dtype or jnp.float32
        self.tp = mesh.shape["tp"] if mesh is not None else 1
        self.sp = mesh.shape.get("sp", 1) if mesh is not None else 1
        self.sharded = self.tp > 1 or self.sp > 1
        # resolved ONCE: the engine's program, its comm accounting, and the
        # stats line all describe the same collective schedule
        self.tp_scheme = tp_scheme()
        # likewise: how the Q40 leaves lie and which body the fused chain
        # runs (ops/linear.Q40Layout); one dispatch here is one row wide
        from ..ops.linear import q40_body_policy

        self.q40_layout = q40_layout or q40_body_policy(
            spec, rows=1, sharded=self.sharded)
        self._loops: dict = {}  # (temp, topp) -> compiled device loop
        # routed (row, expert) pairs and distinct experts summed over layers
        # and ``infer`` steps (expert specs; GenStats carries them)
        self.moe_pairs = self.moe_active = 0
        # a retention spec: the smallest normaliser phi(q).z any ``infer``
        # step read, and the position its state stands at. A state cannot
        # be rewound: a step anywhere else than there or at 0 is refused
        self.min_normaliser = float("inf")
        self.ssm_min_decay = 1.0  # a hybrid spec's: smallest state decay
        self.gate_min = 1.0       # a mixer-kinds spec's: smallest head gate
        self._state_pos = 0
        self._ahead: _Step | None = None  # the step enqueued ahead, if any
        # steps found in flight and handed out / enqueued ahead and dropped
        self.ahead_used = self.ahead_dropped = 0
        tok_sharding = None  # one chip: the default device
        chunk_fwd = None     # the T>8 chunk's forward, where not the step's
        if self.sharded:
            from ..parallel import (make_sharded_forward, shard_cache,
                                    shard_params, validate_sharding)

            validate_sharding(spec, mesh)  # clear error before any device_put
            tok_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            self.params = shard_params(params, mesh, scheme=self.tp_scheme)
            with startup_phase("cache"):
                self.cache = shard_cache(init_cache(spec, self.cache_dtype),
                                         mesh)
            # shard_map wrapper under a jit of its own; traceable in scan
            # and inside the step program below
            step = self._step_raw = make_sharded_forward(
                spec, mesh, scheme=self.tp_scheme, name="inference_step")
        else:
            from ..models.llama import params_to_device

            self.params = params_to_device(params, spec=spec,
                                           layout=self.q40_layout)
            with startup_phase("cache"):
                self.cache = init_cache(spec, self.cache_dtype)
            self._step_raw = functools.partial(forward, spec)
            # an expert spec's step also hands out the (L, E) count of rows
            # routed to each expert (``infer`` fetches it with the token);
            # the loops and the prefill chunks keep the two-result forward
            step = (functools.partial(forward, spec, moe_counts=True)
                    if spec.n_experts else self._step_raw)
            if spec.retention:
                from ..models.llama import forward_retention

                # likewise the (L,) smallest normaliser; the chunk program
                # is told how many of its positions are the sequence's
                step = functools.partial(forward_retention, spec,
                                         norm_min=True)
                self._step_raw = functools.partial(forward_retention, spec)
            if spec.slotted:
                from ..models.llama import slot_counts, slot_model

                # likewise the smallest decay a state took (a mixer-kinds
                # spec: its gate gauges, then an expert spec's counts); a
                # chunk runs the self-decoder alone (no logits are read
                # of it)
                fwd = slot_model(spec).forward_chunk
                step = functools.partial(fwd, spec, health=True,
                                         **slot_counts(spec))
                chunk_fwd = functools.partial(fwd, spec, xdec=False)

        # host tokens are placed as the step's own ``picked`` result is, or
        # the mesh's step program would compile once for each of the two
        self._put = functools.partial(jax.device_put, device=tok_sharding)
        # ONE step program, (logits, picked, cache[, moe counts]): a caller
        # that wants logits fetches those, ``pick`` callers four bytes
        self._fwd = jax.jit(
            named_program("inference_step", _with_pick(step)),
            donate_argnums=1)
        # a SECOND jit of the same forward for the T>8 prefill chunks, so
        # that a capture tells a chunk's program run from a decode step's
        # by name; decode and the T=1 prefill tail share ``_fwd``. Under
        # fast_prefill it is traced with bf16 matmul precision
        # (ops/linear.bf16_prefill). Documented tolerance:
        # tests/test_prefill.py pins the prefilled-cache drift bound.
        chunk_fwd = chunk_fwd or self._step_raw
        if fast_prefill:
            from ..ops.linear import bf16_prefill

            chunk_fwd = bf16_prefill(chunk_fwd)
        self._fwd_prefill = jax.jit(
            named_program("inference_prefill_chunk", chunk_fwd),
            donate_argnums=1)

    def _launch(self, tokens, pos: int) -> _Step:
        """Enqueue one run of the step program (returns at once) on
        ``tokens``: a list from the host, or a step's ``picked``."""
        if isinstance(tokens, list):
            tokens = self._put(np.array(tokens, np.int32))
        self._state_moves(pos, int(tokens.shape[0]))
        logits, picked, self.cache, *more = self._fwd(
            self.params, self.cache, tokens, np.int32(pos))
        if self.spec.stateful:    # its health reading, then any counts
            return _Step(pos, logits, picked, more[1:], more[:1])
        return _Step(pos, logits, picked, more, [])

    def _state_moves(self, pos: int, n: int) -> None:
        """A retention spec's state advances by ``n`` positions from
        ``pos``: it has to stand there, or ``pos`` is 0 (a sequence's first
        position finds the state empty whatever it holds)."""
        if not self.spec.stateful:
            return
        if pos not in (0, self._state_pos):
            raise ValueError(
                f"a recurrent state cannot be rewound or skipped ahead: it "
                f"stands at position {self._state_pos}, asked for {pos} "
                f"(start over at position 0)")
        self._state_pos = pos + n

    def drop_ahead(self) -> None:
        """Forget the step enqueued ahead, if there is one: the caller is
        not going where ``infer`` assumed (a BOS stop, a forced token, a
        jump, ``reset``). The cache slot it wrote is overwritten before
        anything reads it (``prefill``'s invariant); a retention spec's
        state HAS moved by it, and only a start at position 0 follows."""
        if self._ahead is not None:
            self._ahead = None
            self.ahead_dropped += 1

    def infer(self, token: int, pos: int, pick: bool = False,
              last: bool = False) -> np.ndarray | int:
        """One decode step; returns f32 logits (vocab,). Blocks on device.

        With ``pick`` it returns the id of the NEXT token instead, the
        argmax of those logits taken on the device: what a ``Sampler`` at
        temperature 0 gives. And unless ``last``, before it waits it
        enqueues the step after this one, on the picked token (still on the
        device) at ``pos + 1``; the next call finds that step in flight if
        it is handed the token returned here and ``pos + 1``, and only
        fetches four bytes. Any other call drops it (``drop_ahead``) and
        runs as usual. Pass ``last`` where no such call will follow (the
        step budget's end).
        """
        with host_phase("inference.dispatch"):
            step = self._ahead
            if pick and step is not None and (step.token, step.pos) == (
                    token, pos):
                self._ahead = None
                self.ahead_used += 1
            else:
                self.drop_ahead()
                step = self._launch([token], pos)
            if pick and not last and pos + 1 < self.spec.seq_len:
                self._ahead = self._launch(step.picked, pos + 1)
        with host_phase("inference.fetch"):  # the wait and the transfer
            if step.norm_min:  # (L,) floats beside the rest
                health = np.asarray(step.norm_min[0])  # dlint: allow[D001] normaliser counter
                low = float(health.min())
                if self.spec.mixers or self.spec.latent:
                    # (smallest gate, mean gate[, a kda spec's smallest
                    # decay])
                    self.gate_min = min(self.gate_min, float(health[0]))
                    if self.spec.kda:
                        self.ssm_min_decay = min(self.ssm_min_decay,
                                                 float(health[2]))
                elif self.spec.hybrid or self.spec.ssd:
                    self.ssm_min_decay = min(self.ssm_min_decay, low)
                else:
                    self.min_normaliser = min(self.min_normaliser, low)
            if step.moe:  # an expert spec on one chip: 4 KB beside the rest
                counts = np.asarray(step.moe[0])  # dlint: allow[D001] routed-rows counters
                self.moe_pairs += int(counts.sum())
                self.moe_active += int(
                    (counts[:, self.spec.held_columns] > 0).sum())
            if not pick:
                return np.asarray(step.logits)[0]  # dlint: allow[D001] host sampler input
            out = int(np.asarray(step.picked)[0])  # dlint: allow[D001] four bytes a token
            if self._ahead is not None:
                self._ahead.token = out
            return out

    def prefill(self, tokens: list[int], pos0: int = 0,
                chunk: int = 128) -> None:
        """Fill the KV cache for ``tokens`` at positions pos0.. in T=chunk
        forward passes — the prompt fast path (the reference replays its
        T=1 decode per prompt token, tokenizer.cpp:352-366; chunked T>1
        runs ~20x the tokens/s on TPU because the matmuls become MXU work).

        Chunks are FIXED-size (one XLA compilation): the tail pads with
        token 0 and simply writes junk at positions past the real prefix.
        That junk is invisible and short-lived — decode always writes cache
        slot p before attending 0..p, so every padded slot is overwritten
        before anything reads it. A padded window that would cross seq_len
        is NOT issued (dynamic_update_slice would clamp the start and shift
        the writes back over real positions); that tail runs as T=1 steps,
        reusing the decode compilation. Logits are discarded; callers
        continue with the next real token through the decode path.

        Two or more full windows run as ONE device program (a fori_loop
        over the chunk index with the cache donated through), so a long
        prompt pays one dispatch instead of one per chunk. The traced
        chunk-count bound means one compilation per chunk size serves every
        prompt length.
        """
        jnp = self.jnp
        seq_len = self.spec.seq_len
        self.drop_ahead()
        if pos0 + len(tokens) > seq_len:
            # fail loudly before any cache write: past here the fused path
            # would raise an opaque numpy broadcast error and the unfused
            # path would clamp cache writes — divergent, silent corruption
            raise ValueError(
                f"prefill overflow: pos0={pos0} + {len(tokens)} tokens "
                f"> seq_len={seq_len}")
        c = min(chunk, seq_len)
        if self.spec.stateful:
            # a state has no slot to overwrite: a padded position must not
            # reach it, so every chunk says how many of its positions count
            def fwd_valid(part, start, n_valid):
                with host_phase("inference.prefill_chunk"):
                    self._state_moves(start, n_valid)
                    _, self.cache = self._fwd_prefill(
                        self.params, self.cache,
                        jnp.asarray(part, jnp.int32), jnp.int32(start),
                        jnp.int32(n_valid))

            run_chunked_prefill(fwd_valid, tokens, pos0, c, seq_len,
                                valid=True)
            return
        n_full = len(tokens) // c
        rest, rest_pos = tokens, pos0
        if n_full >= 2 and c > 8:
            import numpy as _np

            max_chunks = seq_len // c
            mat = _np.zeros((max_chunks, c), _np.int32)
            # dlint: allow[D001] host prompt list -> numpy, no device value
            mat[:n_full] = _np.asarray(tokens[:n_full * c],
                                       _np.int32).reshape(n_full, c)
            self.cache = self._prefill_loop(c)(
                self.params, self.cache, jnp.asarray(mat),
                jnp.int32(pos0), jnp.int32(n_full))
            rest = tokens[n_full * c:]
            rest_pos = pos0 + n_full * c
        if not rest:
            return

        def fwd(part, start):
            # the chunk program (bf16 under fast-prefill) runs the T>8
            # MXU-bound chunks only; the T=1 tail shares the decode
            # parity program (``_launch``: its logits and pick go unread)
            with host_phase("inference.prefill_chunk"):
                if len(part) > 8:
                    _, self.cache = self._fwd_prefill(
                        self.params, self.cache,
                        jnp.asarray(part, jnp.int32), jnp.int32(start))
                else:
                    self._launch(part, start)

        run_chunked_prefill(fwd, rest, rest_pos, chunk, seq_len)

    def _prefill_loop(self, chunk: int):
        """Compiled whole-prompt prefill (cached per chunk size): fori_loop
        over full T=chunk windows, cache donated, chunk count traced. Traces
        under the engine's prefill precision (bf16_prefill when
        fast_prefill is set, parity otherwise)."""
        import jax

        key = ("prefill", chunk)
        if key not in self._loops:
            jnp = self.jnp
            step = self._step_raw
            if self.fast_prefill:
                from ..ops.linear import bf16_prefill

                step = bf16_prefill(step)

            def run(params, cache, toks_mat, pos0, n_chunks):
                def body(i, cache):
                    part = jax.lax.dynamic_index_in_dim(
                        toks_mat, i, 0, keepdims=False)
                    _, cache = step(params, cache, part,
                                    pos0 + i * jnp.int32(chunk))
                    return cache
                return jax.lax.fori_loop(0, n_chunks, body, cache)

            self._loops[key] = jax.jit(run, donate_argnums=1)
        return self._loops[key]

    def decode_loop(self, temperature: float, topp: float):
        """Compiled on-device generation loop for this engine (cached).

        Keyed on the sampling config ONLY: the step budget rides through
        the loop as a traced bound (decode.make_decode_loop), so changing
        --steps costs nothing — one seq_len-shaped compilation serves
        every budget (VERDICT r1 #6: the old (steps, temp, topp) key
        recompiled the full chain per distinct --steps)."""
        from .decode import make_decode_loop

        key = (temperature, topp)
        if key not in self._loops:
            self._loops[key] = make_decode_loop(
                self._step_raw, self.spec.seq_len, temperature, topp,
                i4=self.q40_layout.i4_chain)
        return self._loops[key]

    def reset(self):
        self.drop_ahead()
        self._state_pos = 0
        self.cache = init_cache(self.spec, self.cache_dtype)
        if self.sharded:
            from ..parallel import shard_cache

            self.cache = shard_cache(self.cache, self.mesh)

    def comm_stats(self) -> CommStats:
        tp_st = ici_all_gather_bytes(self.spec, self.tp, self.tp_scheme)
        sp_st = sp_lse_bytes(self.spec, self.sp, self.tp)
        return CommStats(tp_st.sent_bytes + sp_st.sent_bytes,
                         tp_st.recv_bytes + sp_st.recv_bytes)


def run_chunked_prefill(fwd, tokens: list[int], pos0: int, chunk: int,
                        seq_len: int, valid: bool = False) -> None:
    """The ONE fixed-chunk prefill schedule, shared by Engine.prefill and
    the continuous engine's admission prefill: full T=chunk windows, a
    zero-padded partial window when it stays inside seq_len, and a per-token
    tail when padding would cross seq_len (dynamic_update_slice would clamp
    the start and shift writes over real positions). ``fwd(part, start)``
    runs one forward pass and owns the cache state. ``valid`` (a retention
    spec: no position of a state is a slot to clamp or overwrite) pads
    every partial window and calls ``fwd(part, start, n_valid)``."""
    chunk = min(chunk, seq_len)
    for lo in range(0, len(tokens), chunk):
        part = tokens[lo:lo + chunk]
        start = pos0 + lo
        if valid:
            fwd(part + [0] * (chunk - len(part)), start, len(part))
        elif len(part) == chunk:
            fwd(part, start)
        elif start + chunk <= seq_len:
            fwd(part + [0] * (chunk - len(part)), start)
        else:  # padded window would cross seq_len: per-token tail
            for i, t in enumerate(part):
                fwd([t], start + i)


@dataclasses.dataclass
class GenStats:
    tokens: int = 0
    total_ms: float = 0.0
    infer_ms: float = 0.0
    host_ms: float = 0.0
    final_pos: int = 0    # next step's pos — checkpoint/resume anchor
    final_token: int = 0  # next step's input token
    token_ms: list = dataclasses.field(default_factory=list)
    # ^ per-token wall ms (per-step loop only; the fused loop is one
    #   device program) — feeds the final-line latency histogram summary
    prompt_rest: list = dataclasses.field(default_factory=list)
    # ^ prompt tokens NOT yet consumed when the run ended (forced-token tail
    #   for a resumed continuation; empty once the prompt is exhausted)
    moe_pairs: int = 0   # expert specs, per-step loop: routed (row, expert)
    moe_active: int = 0  # pairs / distinct experts, summed over layers+steps
    ahead_used: int = 0     # temperature 0: steps found already in flight
    ahead_dropped: int = 0  # steps enqueued ahead and thrown away (BOS stop)
    # ``generate``'s entry to the first SAMPLED token's emit (tokenizer,
    # prefill, the echo and the first step): the one-shot CLI's TTFT.
    # None where no token was sampled
    first_token_ms: float | None = None

    @property
    def avg(self) -> tuple[float, float, float]:
        n = max(self.tokens, 1)
        return self.total_ms / n, self.infer_ms / n, self.host_ms / n


def _prefill_prefix(engine: Engine, prompt_tokens: list[int], steps: int,
                    chunk: int, out_tokens: list[int],
                    emit: Callable[[str], None] | None,
                    tokenizer) -> int | None:
    """Shared prefill gate for both loops: fill the cache for the prompt
    prefix in T=chunk passes and echo the forced tokens into ``out_tokens``
    (the loops append forced prompt tokens to the output — reference
    behavior — so the prefilled region must appear there too).

    Returns the start position for the decode loop (= len(prompt) - 1), or
    None when prefill doesn't apply (short prompt, or prompt doesn't fit in
    ``steps`` — then the per-token path keeps the reference's forced-token
    output semantics exactly).
    """
    from ..io.tokenizer import BOS as _BOS

    n_pre = len(prompt_tokens) - 1
    if chunk <= 1 or n_pre < 2 or n_pre >= steps:
        return None
    if _BOS in prompt_tokens[1:]:
        # a mid-stream BOS stops the per-token loop (tokenizer.cpp:376);
        # only that path reproduces the truncated output
        return None
    engine.prefill(prompt_tokens[:n_pre], 0, chunk)
    prev = prompt_tokens[0]
    with host_phase("inference.echo"):   # while the chunks run
        for t in prompt_tokens[1:n_pre + 1]:
            out_tokens.append(t)
            if emit is not None:
                piece = tokenizer.decode_piece(prev, t)
                emit(piece.decode("utf-8", errors="replace"))
            prev = t
    return n_pre


def generate(engine: Engine, tokenizer: Tokenizer, sampler: Sampler,
             prompt: str, steps: int,
             emit: Callable[[str], None] | None = None,
             quiet: bool = False,
             resume: tuple[int, int] | None = None,
             resume_prompt: list[int] | None = None,
             prefill_chunk: int = 0) -> tuple[list[int], GenStats]:
    """Reference generation loop (tokenizer.cpp:321-394).

    Encodes the prompt with BOS (no EOS), forces prompt tokens, samples after,
    stops early on BOS, prints the per-token stats line and final averages.

    ``resume=(pos, token)`` continues an interrupted generation instead of
    starting one: the engine's cache and the sampler's RNG must have been
    restored first (runtime/checkpoint.py), the prompt argument is ignored
    (``resume_prompt`` carries any prompt tail the interrupted run had not
    yet consumed — GenStats.prompt_rest), and up to ``steps`` more positions
    run.

    ``prefill_chunk > 1`` fills the cache for the prompt prefix in chunked
    T>1 passes (Engine.prefill) instead of forcing tokens through the T=1
    decode path — the same output token stream, minus the per-prompt-token
    stats lines (those positions never run the loop; stats cover the decode
    phase).
    """
    t_entry = time.perf_counter()
    spec = engine.spec
    out_tokens: list[int] = []
    if resume is not None:
        start_pos, token = resume
        # re-anchor the unconsumed prompt tail at absolute positions: the
        # loop forces prompt_tokens[pos + 1], so pad the consumed prefix
        prompt_tokens = ([-1] * (start_pos + 1)) + list(resume_prompt or [])
        steps = min(start_pos + steps, spec.seq_len)
    else:
        start_pos, steps = 0, min(steps, spec.seq_len)
        with host_phase("inference.encode"):
            prompt_tokens = tokenizer.encode(prompt or "", bos=True,
                                             eos=False)
        if not prompt_tokens:
            raise ValueError(
                "something is wrong, expected at least 1 prompt token")
        token = prompt_tokens[0]
        pre = _prefill_prefix(engine, prompt_tokens, steps, prefill_chunk,
                              out_tokens, emit, tokenizer)
        if pre is not None:
            start_pos, token = pre, prompt_tokens[pre]

    comm = engine.comm_stats()
    stats = GenStats(final_pos=start_pos, final_token=token)
    moe0 = engine.moe_pairs, engine.moe_active
    ahead0 = engine.ahead_used, engine.ahead_dropped
    pos = start_pos
    while pos < steps:
        t0 = time.perf_counter()
        forced = pos + 1 < len(prompt_tokens)
        # at temperature 0 the device takes the argmax and runs one step
        # ahead of this loop, but not past the budget: a step nobody wants
        # would sit in front of the next generation's prefill
        greedy = not forced and sampler.temperature == 0.0
        out = engine.infer(token, pos, pick=greedy, last=pos + 1 >= steps)
        t1 = time.perf_counter()
        if forced:
            next_token = prompt_tokens[pos + 1]
        elif greedy:
            next_token = out
        else:
            with host_phase("inference.sampler"):
                next_token = sampler.sample(out)
        t2 = time.perf_counter()

        with host_phase("inference.emit"):
            gen_ms = (t2 - t0) * 1000
            stats.tokens += 1
            stats.total_ms += gen_ms
            stats.infer_ms += (t1 - t0) * 1000
            stats.host_ms += (t2 - t1) * 1000
            stats.token_ms.append(gen_ms)

            pos += 1
            stats.final_pos, stats.final_token = pos, int(next_token)
            stats.prompt_rest = [t for t in prompt_tokens[pos + 1:]
                                 if t >= 0]
            if next_token == BOS:
                # reference stops on BOS before decoding it
                # (tokenizer.cpp:376); the step enqueued on it is the one
                # step ever wasted
                engine.drop_ahead()
                break
            out_tokens.append(next_token)
            piece = tokenizer.decode_piece(token, next_token)
            if emit is not None:
                emit(piece.decode("utf-8", errors="replace"))
            if not forced and stats.first_token_ms is None:
                stats.first_token_ms = (time.perf_counter() - t_entry) * 1e3
            if not quiet:
                # the 🔶 reference stats line, or one NDJSON object per token
                # with the same fields under DLLAMA_LOG_JSON=1 (obs/log.py)
                log_event(
                    "decode.token",
                    f"🔶 G {gen_ms:7.2f} ms I {(t1 - t0) * 1000:7.2f} ms "
                    f"T {(t2 - t1) * 1000:7.2f} ms "
                    f"S {comm.sent_bytes / 1024:7.0f} kB "
                    f"R {comm.recv_bytes / 1024:7.0f} kB "
                    f"{piece.decode('utf-8', errors='replace')!r}",
                    pos=pos, token=int(next_token),
                    gen_ms=round(gen_ms, 3),
                    infer_ms=round((t1 - t0) * 1000, 3),
                    host_ms=round((t2 - t1) * 1000, 3),
                    sent_bytes=comm.sent_bytes, recv_bytes=comm.recv_bytes,
                    piece=piece.decode("utf-8", errors="replace"))
            token = next_token

    stats.moe_pairs = engine.moe_pairs - moe0[0]
    stats.moe_active = engine.moe_active - moe0[1]
    stats.ahead_used = engine.ahead_used - ahead0[0]
    stats.ahead_dropped = engine.ahead_dropped - ahead0[1]
    if stats.tokens:
        # the SAME summary shape the serving metrics expose (/health,
        # bench.py rows): p50/p95/p99 over the per-token wall times plus
        # the analytic per-token collective bytes
        lat = summarize_values(stats.token_ms)
        if not quiet:
            g, i, t = stats.avg
            print(f"Generated tokens:    {stats.tokens}")
            print(f"Avg generation time: {g:.2f} ms")
            print(f"Avg inference time:  {i:.2f} ms")
            print(f"Avg transfer time:   {t:.2f} ms")
            print(f"Latency ms/token:    p50 {lat['p50']:.2f}  "
                  f"p95 {lat['p95']:.2f}  p99 {lat['p99']:.2f} | "
                  f"ICI S {comm.sent_bytes / 1024:.0f} kB "
                  f"R {comm.recv_bytes / 1024:.0f} kB /token")
            print(f"Steps run ahead:     {stats.ahead_used} used, "
                  f"{stats.ahead_dropped} dropped")
            if stats.first_token_ms is not None:
                print(f"First sampled token: {stats.first_token_ms:.2f} ms "
                      f"after the call (tokenizer, prefill, first step)")
            if stats.moe_active:
                print(f"Routed experts:      "
                      f"{stats.moe_pairs / stats.moe_active:.2f} rows per "
                      f"active expert, {stats.moe_active / stats.tokens:.1f} "
                      f"active (summed over layers) per token")
        log_event("run.summary", None, tokens=stats.tokens,
                  avg_ms=round(stats.total_ms / stats.tokens, 3),
                  latency_ms={k: round(v, 3) for k, v in lat.items()},
                  sent_bytes_per_token=comm.sent_bytes,
                  recv_bytes_per_token=comm.recv_bytes,
                  ahead_used=stats.ahead_used,
                  ahead_dropped=stats.ahead_dropped,
                  first_token_ms=(None if stats.first_token_ms is None
                                  else round(stats.first_token_ms, 3)))
    return out_tokens, stats


def generate_batch(spec: TransformerSpec, params: dict[str, Any],
                   tokenizer: Tokenizer, prompts: list[str], steps: int,
                   temperature: float, topp: float, seed: int,
                   cache_dtype=None, mesh=None, quiet: bool = False,
                   q40_layout=None) -> tuple[list[list[int]], GenStats]:
    """Generate for B prompts in one fused lockstep batch.

    A capability extension (the reference is strictly batch=1): all rows
    decode in lockstep via models/llama.forward_batch; ragged prompts
    right-pad and start sampling when their own prompt runs out. Each row
    samples from its own xorshift stream seeded ``seed + row`` (batch has
    no single-stream reference semantics to preserve). Rows stop at BOS on
    the host, like generate().

    With a ``mesh`` (tp > 1) the step runs tensor-parallel: weights in
    MatmulSlice bands, batched cache kv-head-sharded, same per-layer
    collectives as the B=1 sharded path (parallel/tp.py).
    """
    import jax
    import jax.numpy as jnp

    from ..models.llama import init_cache_batch, params_to_device
    from ..utils.rng import Xorshift64
    from .decode import make_batch_decode_loop

    B = len(prompts)
    steps = min(steps, spec.seq_len)
    dtype = cache_dtype or jnp.float32
    with host_phase("inference.encode"):
        toks_per_row = [tokenizer.encode(p or "", bos=True, eos=False)
                        for p in prompts]
    padded = np.full((B, steps + 1), -1, dtype=np.int32)
    coins = np.zeros((B, steps), dtype=np.float32)
    for b, pt in enumerate(toks_per_row):
        pt = pt[:steps + 1]
        padded[b, :len(pt)] = pt
        n_sampled = steps - (len(pt) - 1)
        if n_sampled > 0 and temperature != 0.0:
            coins[b, len(pt) - 1:] = Xorshift64(seed + b).f32_array(n_sampled)

    if mesh is not None and (mesh.shape["tp"] > 1
                             or mesh.shape.get("sp", 1) > 1):
        from ..parallel import (make_sharded_forward_batch, shard_cache_batch,
                                shard_params, validate_sharding)

        scheme = tp_scheme()  # one resolution for program + params
        validate_sharding(spec, mesh)
        dev_params = shard_params(params, mesh, scheme=scheme)
        cache0 = shard_cache_batch(init_cache_batch(spec, B, dtype), mesh)
        step_fn = make_sharded_forward_batch(spec, mesh, scheme=scheme)
        run = make_batch_decode_loop(spec, steps, temperature, topp,
                                     step_fn=step_fn)
    else:
        from ..ops.linear import q40_body_policy

        # batch: T>1 paths, no mega prep; a dispatch is B rows wide
        dev_params = params_to_device(
            params, layout=q40_layout or q40_body_policy(spec, rows=B))
        cache0 = init_cache_batch(spec, B, dtype)
        run = make_batch_decode_loop(spec, steps, temperature, topp)
    t0 = time.perf_counter()
    toks, _ = run(dev_params, cache0,
                  jnp.asarray(padded),
                  jnp.asarray([p[0] for p in toks_per_row], jnp.int32),
                  jnp.asarray(coins))
    toks = np.asarray(toks)  # dlint: allow[D001] whole-chain result drain
    total_ms = (time.perf_counter() - t0) * 1000

    outs: list[list[int]] = []
    for b in range(B):
        row: list[int] = []
        for t in map(int, toks[b]):
            if t == BOS:
                break
            row.append(t)
        outs.append(row)
        if not quiet:
            prev = toks_per_row[b][0]
            text = b""
            for t in row:
                text += tokenizer.decode_piece(prev, t)
                prev = t
            print(f"[{b}] {text.decode('utf-8', errors='replace')!r}")
    n_tokens = sum(len(r) for r in outs)
    stats = GenStats(tokens=n_tokens, total_ms=total_ms, infer_ms=total_ms)
    if not quiet:
        print(f"Generated tokens:    {n_tokens} across {B} rows")
        print(f"Avg generation time: {total_ms / max(1, B * steps):.2f} "
              f"ms/token ({B} rows x {steps} lockstep steps)")
    return outs, stats


def generate_fast(engine: Engine, tokenizer: Tokenizer, sampler: Sampler,
                  prompt: str, steps: int,
                  quiet: bool = False,
                  resume: tuple[int, int] | None = None,
                  resume_prompt: list[int] | None = None,
                  prefill_chunk: int = 0) -> tuple[list[int], GenStats]:
    """The fused-loop generation path: one device program for the whole chain.

    Same observable token stream as generate() (forced prompt, reference
    sampler semantics via runtime/decode.py, stop on BOS) but per-token
    timing collapses into one on-device scan — the TPU-idiomatic hot path.
    Pieces and the averaged stats line print after the device loop returns.

    ``resume=(pos, token)`` continues an interrupted generation (same
    contract as generate(): cache + sampler RNG restored first via
    runtime/checkpoint.py, ``resume_prompt`` is the unconsumed prompt tail,
    up to ``steps`` more positions run) — the scan simply starts its
    position clock at ``pos``.

    ``prefill_chunk > 1``: the prompt prefix fills the cache in chunked
    T>1 passes (Engine.prefill) and the fused chain starts at the last
    prompt token — same output stream, far less time on long prompts.
    """
    spec = engine.spec
    pre_out: list[int] = []
    if resume is not None:
        start_pos, first = resume
        # the loop's forced stream is relative to the chain: [first] + tail
        prompt_tokens = [first] + list(resume_prompt or [])
        steps = min(steps, spec.seq_len - start_pos)
    else:
        start_pos = 0
        steps = min(steps, spec.seq_len)
        with host_phase("inference.encode"):
            prompt_tokens = tokenizer.encode(prompt or "", bos=True,
                                             eos=False)
        if not prompt_tokens:
            raise ValueError(
                "something is wrong, expected at least 1 prompt token")
        emit_fn = None if quiet else (
            lambda s: print(s, end="", flush=True))
        pre = _prefill_prefix(engine, prompt_tokens, steps, prefill_chunk,
                              pre_out, emit_fn, tokenizer)
        if pre is not None:
            # chain takes over at the last prompt token; its forced stream
            # is empty (relative prompt = [prompt[-1]]), clock starts at pre
            start_pos = pre
            prompt_tokens = prompt_tokens[pre:]
            steps = steps - pre
    prompt_tail = prompt_tokens[steps + 1:]  # beyond this chain: resume tail
    if len(prompt_tokens) > steps + 1:
        prompt_tokens = prompt_tokens[:steps + 1]

    run = engine.decode_loop(sampler.temperature, sampler.topp)

    jnp = engine.jnp
    # buffers are seq_len-shaped (the loop's ONE compiled shape); the actual
    # budget rides in as the traced num_steps bound
    max_steps = spec.seq_len
    padded = np.full((max_steps + 1,), -1, dtype=np.int32)
    padded[:len(prompt_tokens)] = prompt_tokens
    # pre-draw the xorshift coins for every potentially-sampled step, in the
    # order the device consumes them (positions >= len(prompt)-1); drawn on a
    # THROWAWAY copy of the rng so the sampler's stream can be rewound to
    # exactly what the per-step loop would have consumed (BOS early stop
    # means later coins were never "really" drawn)
    coins = np.zeros((max_steps,), dtype=np.float32)
    n_sampled = steps - (len(prompt_tokens) - 1)
    if n_sampled > 0 and sampler.temperature != 0.0:
        coins[len(prompt_tokens) - 1:steps] = sampler.rng.clone().f32_array(
            n_sampled)

    t0 = time.perf_counter()
    toks, engine.cache = run(engine.params, engine.cache,
                             jnp.asarray(padded),
                             jnp.int32(prompt_tokens[0]), jnp.asarray(coins),
                             jnp.int32(start_pos), jnp.int32(steps))
    toks = np.asarray(toks)  # dlint: allow[D001] whole-chain result drain
    total_ms = (time.perf_counter() - t0) * 1000

    out_tokens: list[int] = list(pre_out)  # prefilled prompt echo, if any
    prev = prompt_tokens[0]
    for t in map(int, toks):
        if t == BOS:
            break
        out_tokens.append(t)
        if not quiet:
            piece = tokenizer.decode_piece(prev, t)
            print(piece.decode("utf-8", errors="replace"), end="", flush=True)
        prev = t
    # all chain accounting is in CHAIN terms: out_tokens also carries the
    # prefill-echoed prompt tokens, which the chain never produced
    chain_generated = len(out_tokens) - len(pre_out)
    # advance the sampler's real stream by only the coins the per-step loop
    # would have consumed: one per SAMPLED iteration, including the one that
    # produced a terminating BOS (the loop breaks after drawing it)
    if n_sampled > 0 and sampler.temperature != 0.0:
        early_bos = chain_generated < steps
        last_iter = chain_generated if early_bos else steps - 1
        consumed = max(0, last_iter - (len(prompt_tokens) - 1) + 1)
        if consumed:
            sampler.rng.f32_array(min(consumed, n_sampled))
    # stats cover the timed fused chain (like generate()'s loop iterations;
    # the prefill phase is separate work and would deflate ms/token)
    n = max(1, chain_generated)
    stats = GenStats(tokens=chain_generated, total_ms=total_ms,
                     infer_ms=total_ms, host_ms=0.0)
    early_bos = chain_generated < steps
    if steps > 0 and not early_bos:  # no early BOS: resumable
        # the buffer is seq_len long; the chain's last written slot is
        # steps-1 (slots past it are BOS padding)
        stats.final_pos = start_pos + steps
        stats.final_token = int(toks[steps - 1])
        stats.prompt_rest = prompt_tail
    # the while_loop stops on a produced BOS: executed = generated
    # tokens + the terminating step, not the whole budget
    executed = chain_generated + 1 if early_bos else steps
    if not quiet:
        print(f"\nGenerated tokens:    {stats.tokens}")
        print(f"Avg generation time: {total_ms / n:.2f} ms "
              f"(fused loop, {executed} device steps)")
    log_event("run.summary", None, tokens=stats.tokens,
              avg_ms=round(total_ms / n, 3), fused=True,
              device_steps=executed)
    return out_tokens, stats
