"""Continuous batching: per-slot position clocks + mid-flight admission.

The lockstep batch path (runtime/decode.make_batch_decode_loop) shares one
position clock across rows, so the batch finishes at the pace of its slowest
row and new work waits for the whole batch. This engine removes both limits —
the TPU analog of vLLM-style continuous batching, far beyond the reference's
strict batch=1 loop (tokenizer.cpp:321-394):

* a fixed pool of B cache slots, each with its OWN position clock
  (models/llama.forward_batch_ragged: per-row RoPE, per-row cache column,
  per-row attention visibility);
* a host-side scheduler that retires a row the moment it stops (BOS or step
  budget) and admits the next queued request into the freed slot at pos 0
  while the other rows keep decoding.

Prompt tokens are forced through the same decode step (one per iteration,
the reference's own prompt handling); each request samples from its own
xorshift stream seeded ``seed + request_index`` with reference Sampler
semantics, so a request's token stream is IDENTICAL to running it alone
through generate() with that seed — the scheduling is invisible in the
output (the parity gate of tests/test_continuous.py).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading
import time
from typing import Any

import numpy as np

from ..io.tokenizer import BOS
from ..models.spec import TransformerSpec
from ..obs import tracectx
from ..obs.ledger import CensusRing, LedgerBook
from ..obs.spans import host_phase, named_program, startup_phase
from .sampling import Sampler


@dataclasses.dataclass
class Request:
    """One generation request flowing through the slot pool.

    ``tokens`` is the encoded prompt (BOS included, non-empty); optional
    per-request sampling overrides fall back to the engine defaults. The
    engine fills ``out`` and sets ``done`` when the request retires —
    online callers (runtime/server.py) wait on it.
    """
    tokens: list
    steps: int
    temperature: float | None = None
    topp: float | None = None
    seed: int | None = None
    out: list = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    index: int = -1  # submission order; assigned by submit()
    error: str | None = None  # set (before done) if the engine failed it
    cancelled: bool = False  # consumer gone: retire at the next step
    # () -> bool, asked once where the request leaves the queue: False and
    # the consumer has gone while it waited, so it is completed as cancelled
    # and no admission prefill is spent on it (runtime/server.py sets it to
    # a look at the client's socket). None: nobody to ask.
    alive: Any = None
    # SLO priority class (obs/slo.py): None = the policy's default class;
    # the tracker resolves it at retire. Ignored on engines without a
    # policy.
    slo_class: str | None = None
    # crash recovery (runtime/journal.py): coins the request's sampler
    # already consumed in a previous life — admission fast-forwards the
    # xorshift stream by exactly this many draws so the continuation is
    # bitwise the uninterrupted stream. 0 for fresh requests.
    coin_cursor: int = 0
    # journal id of the previous life this request replays (recover()
    # sets it): the admit record carries it as ``recovers`` so ONE
    # append atomically opens the new life and closes the old — a crash
    # can never leave both live. None for fresh requests.
    recovered_from: int | None = None
    # DCN handoff durability (ISSUE 14): True when
    # ContinuousEngine.prejournal already assigned this request's index
    # and journaled its admit record — submit() then only queues it
    # (appending a second admit would corrupt the journal)
    prejournaled: bool = False
    # distributed-trace identity (ISSUE 15, obs/tracectx.TraceContext):
    # minted at request ingress (runtime/server.py) or by submit() when
    # absent; carried into every span this request produces, the journal
    # admit record, and the handoff wire form — a recovered/handed-off
    # continuation keeps the SAME trace_id with a recovers/handoff link
    trace: Any = None
    # streaming hook: called from the scheduler thread with each token as it
    # lands in ``out`` (prompt echoes included, prefill echoes in one burst);
    # must be fast and must not raise — it runs inside the decode loop
    on_token: Any = None
    # lifecycle timestamps (time.monotonic; 0.0 = not reached): queue wait =
    # t_admit - t_enqueue, TTFT = t_first_token - t_enqueue. t_first_token
    # marks the first SAMPLED token — forced prompt echo is input replay,
    # not generation. obs/trace.EngineMetrics derives histograms from these.
    t_enqueue: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_finish: float = 0.0
    n_sampled: int = 0  # sampled (non-forced) tokens emitted
    # cost accounting (ISSUE 16, obs/ledger.py): the live RequestLedger
    # handle submit() opens (seam code — the DCN handoff — charges
    # through it without a book lookup), and the snapshot a previous
    # life carried across a recovery/handoff seam (the journal record's
    # ``ledger`` field) — merged into this life's snapshot so the bill
    # stays whole across seams
    ledger: Any = None
    carried_cost: dict | None = None


_PROGRAM_MEMO: dict = {}


def _shared_program(key: tuple, build):
    """Process-wide memo for the engine's jitted step programs.

    Every engine build used to re-jit its own ``functools.partial`` /
    sharded-builder closure, so two engines with EQUAL (spec, mesh,
    scheme, page_size, kv_quant, ...) each paid a full XLA compile for
    byte-identical programs — the dominant cost of multi-engine
    processes (the disagg two-pool topology, loadgen sweeps, every
    stream-parity test). Sharing the jitted callable itself is
    deterministic by construction: callers get the SAME executable
    object, not a deserialized copy, so bitwise pins only get stronger.
    (jax's persistent disk cache is NOT a substitute — measured on the
    test suite, deserialized executables are not always bit-identical
    to fresh compiles of the same HLO.) Donation is per-call state, so
    sharing across engines is safe; nothing here is ever evicted — keys
    are bounded by the distinct engine configurations of the process."""
    fn = _PROGRAM_MEMO.get(key)
    if fn is None:
        fn = _PROGRAM_MEMO[key] = build()
    return fn


def _maybe_bf16(fn, enable: bool, jax_mod, jit: bool = False):
    """Route a prefill forward through the shared fast-prefill wrapper
    (ops/linear.bf16_prefill) when enabled. Unlike Engine.prefill's T>8
    gate, admission prefill runs ALL its chunks (tail included) through
    this one dedicated program — the whole prefilled prefix shares one
    documented tolerance."""
    if enable:
        from ..ops.linear import bf16_prefill

        fn = bf16_prefill(fn)
    if not jit:
        return fn
    return jax_mod.jit(named_program("serve_admit_prefill_chunk", fn),
                       donate_argnums=1)


_q8_fallback_warned = False


def _warn_q8_xla_fallback(spec: TransformerSpec, page_size: int,
                          n_slices: int) -> None:
    """One-time loud note when --kv-quant q8 is requested but the paged
    flash kernel cannot take this layout for the DECODE shape (t_len=1,
    the per-token hot path), so attention runs the XLA gather fallback
    (which dequantizes the WHOLE gathered plane per step). Mirrors the
    explicit prefill-flash degrade warning: the fallback computes the
    same attention, just slower — a warning, not a raise. Silent on
    CPU/interpret engines (kernel mode 'xla' is the documented default
    there, not a degrade). A spec_k window past the kernel's bound only
    degrades the verify dispatch, not decode — that case stays quiet."""
    global _q8_fallback_warned
    if _q8_fallback_warned:
        return
    from ..ops.pallas_attention import attn_kernel_mode
    from ..ops.pallas_paged_attention import would_use_paged_kernel

    kv_loc = spec.n_kv_heads // n_slices
    if (attn_kernel_mode() != "pallas"
            or would_use_paged_kernel(page_size, kv_loc, spec.head_size,
                                      1, itemsize=1, q8=True)):
        return
    _q8_fallback_warned = True
    import sys

    print(f"⚠️  --kv-quant q8 requested but the paged flash-decode Pallas "
          f"kernel does not apply to this layout (page_size {page_size}, "
          f"n_kv/tp {kv_loc}, head_size {spec.head_size}); decode "
          f"attention takes the XLA gather fallback, which dequantizes "
          f"the whole gathered plane every step — the HBM saving stands "
          f"but the per-token attention cost does not improve. Use a "
          f"head_size multiple of 128 and a page size whose K/V planes "
          f"fit the kernel's VMEM scratch budget "
          f"(ops/pallas_paged_attention.supports_paged).",
          file=sys.stderr)


def sequence_caches(spec) -> frozenset:
    """What one sequence of ``spec`` caches, by kind: "pages" (K and V of
    every position, which page), "plane" (a latent spec's one row
    [c_kv | k_rope] a position and full layer, behind the same page tables:
    a spec whose layers are all "full" caches that plane a layer and
    nothing else, which is what "a latent spec caches one plane" means
    wherever this module and models/latent.py say it) and "state" (a slot
    of fixed size that a step rewrites: a recurrent state, a window ring).
    A hybrid spec keeps a state AND one layer's pages (an ssd spec: a
    Mamba-2 state AND each attention layer's pages), a mixer-kinds spec
    rings AND its full layers' pages ("rings" rides along: its slot is
    window rings alone), a latent spec with sliding layers rings of latent
    rows AND its full layers' plane, a kda spec a delta-rule state and
    conv rows a KDA layer AND its latent layers' plane. "streams" rides
    along where the
    residual path is several streams (``spec.hyper``): nothing a sequence
    caches, but the list below names it in its reasons."""
    if spec.mixers:
        return frozenset({"state", "pages", "rings"})
    if spec.kda:
        return frozenset({"state", "plane"})
    if spec.latent and spec.slotted:
        return frozenset({"state", "plane", "rings"}
                         | ({"streams"} if spec.hyper else set()))
    if spec.hybrid or spec.ssd:
        return frozenset({"state", "pages"})
    if spec.retention:
        return frozenset({"state"})
    if spec.latent and spec.hyper:
        return frozenset({"plane", "streams"})
    return frozenset({"plane"} if spec.latent else {"pages"})


_WHY = {
    frozenset({"state"}): "a retention model keeps a recurrent state of "
                          "fixed size, not a KV cache",
    frozenset({"plane"}): "a latent-attention model caches one plane "
                          "[c_kv | k_rope] a layer, not K and V",
    frozenset({"plane", "streams"}): "a latent-attention model with several "
                                     "residual streams caches one plane "
                                     "[c_kv | k_rope] a layer, not K and V",
    frozenset({"state", "pages"}): "a hybrid model keeps a recurrent state "
                                   "(and, where it has window layers, a "
                                   "ring) of fixed size beside its "
                                   "attention layers' KV pages",
    frozenset({"state", "pages", "rings"}): "a mixer-kinds model keeps a "
                                            "window ring of fixed size a "
                                            "sliding layer beside its "
                                            "full layers' KV pages",
}
_WHY[frozenset({"state", "plane"})] = (
    "a delta-rule model keeps a recurrent state and conv rows of fixed size "
    "a KDA layer beside its latent layers' plane [c_kv | k_rope], not K "
    "and V")
_WHY[frozenset({"state", "plane", "rings"})] = (
    "a latent-attention model with sliding layers keeps a ring of latent "
    "rows of fixed size a sliding layer beside its full layers' plane "
    "[c_kv | k_rope], not K and V")
_WHY[frozenset({"state", "plane", "rings", "streams"})] = (
    "a latent-attention model with sliding layers and several residual "
    "streams keeps a ring of latent rows of fixed size a sliding layer "
    "beside its full layers' plane [c_kv | k_rope], not K and V")


def cache_refusals(caches: frozenset, *, tp: int = 1, page_size: int = 0,
                   kv_pages: int = 0, prefix_share: bool = False,
                   spec_k: int = 0, dispatch_tokens: int = 0,
                   kv_quant: str = "f32", kv_host_pages: int = 0,
                   kv_disk_dir=None, journal: bool = False,
                   disagg: bool = False, block_steps: int = 1,
                   kv_cache_dtype: str = "f32",
                   serve: bool = True) -> list[str]:
    """What a spec whose sequences cache ``caches`` (``sequence_caches``)
    cannot run, one line for each feature asked for, naming its flag and the
    reason: THE list, of which a retention spec's, a latent spec's and a
    hybrid spec's are cases. A "state" (ops/retention.py, ops/mamba.py, a
    window ring) has no positions to page, and can be neither shared by
    page, nor rolled back, nor resumed part-way, nor shipped without a
    snapshot, which nothing writes yet; a "plane" (models/latent.py) sits
    behind the same page tables as a KV pool, so what works on page ids and
    token ids (prefix sharing, the journal) runs, and what reads or writes
    the two planes of a KV page does not carry it. Plain KV pages refuse
    nothing. The engine and the CLI refuse with these lines; nothing stands
    in for a refused feature."""
    why = _WHY.get(caches)
    if why is None:
        return []
    state, plane = "state" in caches, "plane" in caches
    paged = bool(caches & {"pages", "plane"})
    out = []

    def refuse(flag: str, for_state: str | None, for_plane: str | None):
        # rings of latent rows beside a plane: what a state refuses, and
        # what a plane refuses where a state has nothing to say
        reason = (for_state if state and for_state else
                  for_plane if plane else None)
        if reason is not None:
            out.append(f"{flag}: {why}; {reason}")

    if tp > 1:
        if "rings" in caches:
            out.append(f"--tp {tp}: {why}; neither the rings, the kinds' "
                       f"head counts"
                       + (", the latent plane" if plane else "")
                       + " nor the experts held here are placed "
                       "over tensor-parallel ranks")
        elif state:
            from ..ops import kda, mamba, retention

            out.append(f"--tp {tp}: " + (
                kda if plane else mamba if paged else retention).TP_REFUSAL)
        else:
            refuse(f"--tp {tp}", None, (
                "neither the streams' carry and per-token mixes, "
                if "streams" in caches else "neither ")
                + "the latent plane nor the experts held here are placed "
                "over tensor-parallel ranks")
    if (page_size or kv_pages) and not paged:
        refuse("--kv-page-size / --kv-pages", "a sequence's memory is one "
               "slot of fixed size, with no positions to page", None)
    if serve and not page_size and paged:
        refuse("serve without --kv-page-size", "serve reads the full "
               + ("layers' plane" if plane else "layers' K / V")
               + " through pages only (pass --kv-page-size)",
               "serve reads it through pages only (pass --kv-page-size)")
    if prefix_share:
        refuse("prefix sharing (prefix_share)", "a state cannot be shared "
               "by page, and a radix prefix cannot be resumed from without "
               "a state snapshot", None)
    if spec_k:
        refuse(f"--spec-k {spec_k}", "rejected drafts roll back by "
               "truncating a page table, and a state cannot be rolled back "
               "without a snapshot", "the verify window has no latent "
               "attention")
    if dispatch_tokens:
        refuse(f"--dispatch-tokens {dispatch_tokens}", "the mixed window "
               "writes through per-row page tables", "the mixed window has "
               "no latent attention")
    if kv_quant != "f32":
        refuse(f"--kv-quant {kv_quant}", "q8 quantizes KV pages, and the "
               "state is float32 (bfloat16 fails the reference's tolerance)",
               "q8 pages quantize a (n_kv, head) row in K and V planes")
    if kv_host_pages or kv_disk_dir:
        refuse("--kv-host-pages / --kv-disk-dir", "the host and disk tiers "
               "spill and promote KV pages", "the host and disk tiers spill "
               "and promote (k, v) page planes")
    if journal:
        refuse("--journal", "recovery resumes a sequence part-way, which "
               "needs a state snapshot", None)
    if disagg:
        refuse("--disagg-role", "the handoff ships prefilled KV pages",
               "the page wire and the handoff ship (k, v) page planes")
    if block_steps > 1:
        refuse(f"--block-steps {block_steps}", "the fused chain masks rows "
               "by parking their writes on a scrap page", "the fused chain "
               "was not carried over to it")
    if kv_cache_dtype != "f32":
        refuse(f"--kv-cache-dtype {kv_cache_dtype}", "the state is float32",
               "the plane is float32 (what the reference's tolerance was "
               "read on)")
    return out


def retention_refusals(**flags) -> list[str]:
    """``cache_refusals`` of a power-retention spec (``inference`` and
    ``serve`` alike: it has no pages to ask for)."""
    return cache_refusals(frozenset({"state"}), **flags)


def latent_refusals(**flags) -> list[str]:
    """``cache_refusals`` of a latent-attention spec."""
    return cache_refusals(frozenset({"plane"}), **flags)


@dataclasses.dataclass
class _Slot:
    req: Request | None = None   # None = free
    pos: int = 0                 # this row's position clock
    token: int = 0               # next input token
    forced: list = dataclasses.field(default_factory=list)
    budget: int = 0              # max positions for this request
    sampler: Sampler | None = None
    # paged KV mode only: physical page ids in logical order (position p
    # lives in pages[p // page_size]); the first ``shared`` entries came
    # from the radix tree (prefix sharing) — refcounted, never written by
    # this slot (decode writes start at the page-aligned share boundary)
    pages: list = dataclasses.field(default_factory=list)
    shared: int = 0
    # KV tiering (ISSUE 12): True while the slot's shared-prefix pages
    # await an async promotion upload — admission prefill is deferred and
    # the slot rides dispatches masked inactive (pages-starved semantics)
    # until the payload lands at a step boundary (_settle_promotions)
    await_promo: bool = False
    # chunk-boundary prefill preemption (ISSUE 14): True when admission
    # prefill parked at a page-aligned chunk boundary (a higher-priority
    # arrival preempted it) — the scheduler re-enters _maybe_prefill_slot
    # for this slot on later iterations until the prompt is covered
    prefill_pending: bool = False

    @property
    def free(self) -> bool:
        return self.req is None


@dataclasses.dataclass
class _Flight:
    """One run of ``step_once``'s program that is launched and not yet
    landed: its results are still on the device and the pool is as it was
    before the step."""
    rows: list      # per row: the _Slot that took part, else None
    reqs: list      # ... and the request it held then (it may have left)
    paused: Any     # slots that rode masked (a step launched from the host)
    logits: Any     # (B, vocab) float32, fetched only for a temperature
    picked: Any     # (B,) int32 argmax: the greedy rows' tokens
    moe: Any        # an expert spec's (L, E) routed-rows counts, else None
    norm_min: Any   # a retention spec's (L,) smallest normaliser, else None
    t0: float       # when the device could start it (time.monotonic)
    ahead: bool     # launched on the previous step's picks, unread
    # what was enqueued between the launch before this one and this one,
    # so stands before it on the device queue: admission prefill chunks,
    # and admissions that enqueued device work
    chunks_ahead: int = 0
    admits_ahead: int = 0
    # an expert spec's: (rows, (L, E) routed-rows counts, still on the
    # device) of each of those chunks; complete once this step has landed
    chunk_moe: tuple = ()
    wait: float = 0.0  # seconds ``_fetch`` stood in the blocking read of it

    def rode(self) -> list:
        """(row, slot) of the rows whose slot still holds the request it
        took part for: the others stopped or were cancelled meanwhile and
        their result is dropped."""
        return [(b, s) for b, (s, r) in enumerate(zip(self.rows, self.reqs))
                if s is not None and s.req is r]


def _with_pick(step, paged: bool, vocab: int, state: bool = False,
               tell_live: bool = True):
    """``step_once``'s program around a decode forward ``step`` (logits,
    cache[, moe counts]): the row inputs arrive as ONE staged int32 block
    (B, 3[ + pages]) = [override | pos | page table | live], split here,
    and a row's input token is its override or, where that is -1, the
    previous step's pick, which never left the device. Beside the forward's
    results it returns ``picked``, the argmax of each row's logits (lowest
    index on a tie, as the host's ``sample_argmax``). The last column says
    which rows ride (a free, paused or cancelled row is dead: nobody reads
    its result): a ``state`` engine's forward takes it as an argument (a
    row that does not take part leaves its state as it is), and every
    forward is traced knowing it (``ops/linear.live_rows``: a Q40 call on a
    part-filled dispatch picks its body by the live rows) unless
    ``tell_live`` is False (a mesh's program, whose forward is traced
    inside ``shard_map``)."""
    def run(params, cache, prev_picked, blk):
        import jax.numpy as jnp

        from ..ops.linear import live_rows

        override, live = blk[:, 0], blk[:, -1]
        tokens = jnp.where(override >= 0, override, prev_picked)
        table = ((blk[:, 2:-1],) if paged else ()) + ((live,) if state
                                                      else ())
        with live_rows(live) if tell_live else contextlib.nullcontext():
            logits, cache, *moe = step(params, cache, tokens, blk[:, 1],
                                       *table)
        picked = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return (logits, picked, cache, *moe)

    return run


def _dense_diag_rows(params, rows: int) -> int:
    """Most live rows of a ``rows``-row decode dispatch at which EVERY dense
    nb-major Q40 leaf of ``params`` takes the stacked block-diagonal body
    (``ops/pallas_q40.live_rows_top``: the question each call asks of its
    own shapes); 0 where a leaf never does, or the tree holds none."""
    import jax

    from ..io.loader import Q40KernelNb
    from ..ops.pallas_q40 import live_rows_top

    def nb_major(v):
        return isinstance(v, Q40KernelNb)

    # (an expert stack, (L, E, ...), goes to the slot kernel: not counted)
    tops = [live_rows_top(rows, w.qs_t.shape[-1], w.qs_t.shape[-2])
            for w in jax.tree_util.tree_leaves(params, is_leaf=nb_major)
            if nb_major(w) and w.qs_t.ndim <= 4]
    return min(tops, default=0)


@dataclasses.dataclass
class ContinuousStats:
    tokens: int = 0          # generated (emitted) tokens
    steps: int = 0           # device steps executed
    total_ms: float = 0.0
    max_active: int = 0
    sum_active: int = 0      # sum of active slots over device steps
    # speculative decoding (spec_k > 0): drafter proposals fed to verify
    # dispatches and how many the model accepted — the accept-rate /
    # ms-per-accepted-token bench columns (ISSUE 7)
    spec_proposed: int = 0
    spec_accepted: int = 0
    # admission-pressure accounting (ISSUE 8): page-starved slot pauses
    # (a slot rode one dispatch masked inactive) and head-of-queue
    # requeues (paged admission found the pool dry) — kept on stats so
    # metric-less engines (the loadgen driver) still see them
    pauses: int = 0
    requeues: int = 0
    # admission-prefill forward passes executed (one per chunk window /
    # per-token tail dispatch): the virtual-clock cost term the two-pool
    # sweep charges prefill with (ISSUE 14) — without it a colocated
    # engine's prefill interference would be invisible to the clock.
    # Counted at DISPATCH (inside the per-window fwd closure), so a chunk
    # that parks at a boundary and resumes there is charged exactly once.
    prefill_chunks: int = 0
    # token-budget mixed dispatches (ISSUE 18): virtual EXTRA device
    # steps a dispatch would have cost had its total span honored the
    # budget — ceil(sum(span) / budget) - 1 per dispatch, 0 in healthy
    # runs. Nonzero only under the overrun-budget chaos mutation (the
    # prefill slice ignores the remaining budget); the virtual clock
    # charges it as real step time so loadcheck's gate catches the
    # overrun as inflated decode latency.
    overrun_steps: int = 0
    # routed experts (an expert spec's decode steps; 0 / None otherwise):
    # (row, expert) pairs routed, distinct experts summed over layers and
    # steps (the expert tiles a step must read), and the rows each expert
    # took summed over layers (an (E,) vector: how uneven the router is)
    # Where the spec holds a SHARE of the experts the counts keep the
    # router's width: ``moe_pairs`` and ``moe_load`` are of every pair
    # routed, ``moe_local_pairs`` of those that landed on an expert held
    # here, and ``moe_active`` counts held experts only (all of them, for a
    # spec that holds every expert: local == pairs then)
    moe_pairs: int = 0
    moe_local_pairs: int = 0
    moe_active: int = 0
    moe_load: Any = None
    # how the slot kernel engaged (ops/pallas_moe): live slots of the
    # counted dispatches (a held expert's rows in slots of ``slot_cap``
    # rows: ceil(count / cap), summed over layers and steps), and those of
    # them that held ONE row, and those that took the block-diagonal body
    # (1 to ``ops/pallas_moe.diag_rows`` live rows; the fuller ran the tile)
    moe_slots: int = 0
    moe_single_row_slots: int = 0
    moe_diag_slots: int = 0
    # landed decode steps whose dense Q40 leaves took the stacked
    # block-diagonal body and not the 8-row tile (ops/pallas_q40: a
    # dispatch of 1 to ``LIVE_ROWS_MAX`` live rows), counted from the rows
    # the step's block was staged from; over ``steps`` the hit share
    dense_diag_steps: int = 0
    # the same of admission prefill chunks, which the counters above leave
    # out: pairs that landed on held experts, and the live slots they
    # filled at the chunk's capacity (``slot_cap`` of its rows). Pairs over
    # slots x capacity is the fill; slots a chunk and layer is what the
    # kernel walked
    moe_chunk_pairs: int = 0
    moe_chunk_slots: int = 0
    # a latent spec: pool pages in use (each page_size positions x
    # latent.width x layers of ONE plane; pages the prefix tree keeps
    # count), as of the last landed step; and the cached positions the
    # launched decode steps' rows read, summed (a row at position p reads
    # p + 1): what the latent decode kernel must move, a layer
    latent_pages: int = 0
    latent_positions: int = 0
    # a plain KV page pool (a Llama-family spec's): the cached positions the
    # launched decode steps' rows read in ONE layer, summed the same way:
    # what ``paged_decode_attention_kernel`` must move, a layer
    paged_kv_positions: int = 0
    # ... and of its admission chunks: the positions of the gathered plane
    # a chunk's attention read, a layer (models/latent.attend_live walks
    # the blocks up to start + T; a T <= 8 chunk reads them all), and the
    # positions of the plane, both summed over chunks
    chunk_walked_positions: int = 0
    chunk_plane_positions: int = 0
    # a spec with several residual streams (ops/hyper.py): how many, and
    # the sub-layers a decode step mixes them around (two a layer); fixed
    # by the spec, 0 without streams
    hc_streams: int = 0
    hc_sublayers_a_step: int = 0
    # step_once's run-ahead: steps launched on the previous step's picks
    # while those were still on the device, and rows of such steps whose
    # result was thrown away (the row had stopped on a token only the
    # landing told, or was cancelled meanwhile)
    steps_ahead: int = 0
    rows_dropped_ahead: int = 0
    # a retention spec: resident bytes of the slots' states, and the
    # smallest normaliser phi(q).z any decode step read among its active
    # rows (a state decayed to nothing, or a first position read by
    # cancellation, shows here)
    state_bytes: int = 0
    min_normaliser: float = float("inf")
    # a hybrid spec: resident bytes of the slots' window rings (its
    # recurrent states are ``state_bytes``); pool pages of its ONE full
    # layer in use, as of the last landed step; the cached positions the
    # launched decode steps' rows read in ONE of the layers that read that
    # layer's K / V, summed (a row at position p reads p + 1); positions
    # admitted (prompt tokens) and those of them at which the
    # cross-decoder ran (a prompt that took admission chunks: its last
    # token alone; one that crawled through decode steps: all of them);
    # and the smallest decay exp(delta A) any active row's state took in a
    # decode step (near 0: a seeded state that forgets everything at once)
    window_bytes: int = 0
    shared_kv_pages: int = 0
    shared_kv_positions: int = 0
    window_kv_positions: int = 0    # ... in ONE window layer: min(p + 1, W)
    prompt_positions: int = 0
    xdec_positions: int = 0
    ssm_min_decay: float = 1.0
    # an ssd spec (its Mamba-2 states and conv rows are ``state_bytes``,
    # its attention layers' pool pages ``shared_kv_pages``, and
    # ``shared_kv_positions`` counts ONE attention layer's reads): layers
    # the landed decode steps ran, by kind (a layer there is ONE mixer)
    layers_run: dict = dataclasses.field(default_factory=dict)
    # a mixer-kinds spec (its rings are ``window_bytes``, its full layers'
    # pool pages ``shared_kv_pages``, and ``window_kv_positions`` /
    # ``shared_kv_positions`` count ONE sliding / ONE full layer's reads):
    # the smallest value any decode step's per-head output gate took over
    # its layers, active rows and heads, and the steps' mean gate summed
    # (``gate_mean`` divides by ``gate_steps``): a gate near 0 shuts a
    # head, and a mean near 0 or 1 says the seeded gates saturate
    gate_min: float = 1.0
    gate_mean_sum: float = 0.0
    gate_steps: int = 0
    # the admission account, kept for every landed dispatch, dark or not,
    # on time.monotonic. ``land_s``: the sum of the landing intervals (a
    # step run ahead: landing to landing, which with the device never idle
    # is the device time of whatever was queued between the two steps; a
    # step launched from the host: from where the device was free to run
    # the admissions queued before it, else from its launch; a chain, mixed
    # or verify dispatch: launch to landing), so the time the engine was
    # stepping. ``lands_behind_admit`` / ``land_behind_admit_s``: the steps
    # and the intervals of the dispatches that stood behind at least one
    # admission's programs on the device queue, and of the landing before
    # each of them (``book_land``: the host may land that one late, and
    # the two intervals add up to the truth). ``admit_prefills``:
    # admissions that enqueued device work (a gather or a scratch state,
    # chunks, a scatter or an insert); ``prefill_chunks`` above counts
    # their chunks. ``admits_back_to_back_max``: the most admissions ahead
    # of one dispatch. ``fetch_wait_s``: the time the host stood in the
    # blocking read of a dispatch's results, and
    # ``fetch_wait_behind_admit_s`` that of the dispatches booked behind
    # admissions (the host's own part of an iteration is read from the
    # plain ones: behind a burst it also stands in held-up enqueues).
    # ``admit_pages_moved`` / ``admit_pages_table``: a paged pool's
    # admissions: the pages, a layer, they gathered into and scattered
    # from the scratch sequence (the pages below the prompt's start, and
    # those its chunks filled), against twice the slot's table an
    # admission, which is what moving the whole table both ways took;
    # ``admit_gathers``: the admissions that ran a gather at all (a shared
    # prefix or a resumed prompt: an unshared prompt has nothing to read)
    land_s: float = 0.0
    lands_behind_admit: int = 0
    land_behind_admit_s: float = 0.0
    admit_prefills: int = 0
    admits_back_to_back_max: int = 0
    fetch_wait_s: float = 0.0
    fetch_wait_behind_admit_s: float = 0.0
    admit_pages_moved: int = 0
    admit_pages_table: int = 0
    admit_gathers: int = 0

    def book_land(self, dt_s: float, steps: int, chunks: int, admits: int,
                  wait_s: float = 0.0, enqueued_since: bool = False) -> bool:
        """One landed dispatch of ``steps`` device steps whose landing
        interval is ``dt_s``, of which the host stood ``wait_s`` in the
        blocking read of its results, with ``chunks`` admission prefill
        chunks of ``admits`` admissions ahead of it on the device queue.
        ``enqueued_since``: the host has enqueued admission programs since
        the landing before this one (they stand before the NEXT step). The
        runtime holds the host in an enqueue once enough programs are in
        flight, so this landing can come long after its step ended (0.7 s
        before a burst of 13 chunks, measured), and the next interval is
        short by as much: both are booked behind the admission, so that
        their sum, which does not move with the lateness, is what the
        stall is read from. Returns whether it was booked behind one."""
        self.land_s += dt_s
        self.fetch_wait_s += wait_s
        behind = bool(chunks or admits or enqueued_since)
        if behind:
            self.lands_behind_admit += steps
            self.land_behind_admit_s += dt_s
            self.fetch_wait_behind_admit_s += wait_s
            if admits > self.admits_back_to_back_max:
                self.admits_back_to_back_max = admits
        return behind

    def count_moe(self, counts, held: slice = slice(None),
                  slots: tuple = (0, 0, 0)) -> None:
        """One dispatch's (L, E) rows-per-expert counts; ``held`` the
        columns of the experts held here; ``slots`` the dispatch's
        ``ops/pallas_moe.slot_census``."""
        self.moe_pairs += int(counts.sum())
        self.moe_local_pairs += int(counts[:, held].sum())
        self.moe_active += int((counts[:, held] > 0).sum())
        self.moe_slots += slots[0]
        self.moe_single_row_slots += slots[1]
        self.moe_diag_slots += slots[2]
        load = counts.sum(axis=0, dtype=np.int64)
        self.moe_load = load if self.moe_load is None else self.moe_load + load

    def count_gate(self, low: float, mean: float) -> None:
        """One decode step's smallest and mean per-head gate value."""
        self.gate_min = min(self.gate_min, low)
        self.gate_mean_sum += mean
        self.gate_steps += 1

    @property
    def gate_mean(self) -> float:
        return self.gate_mean_sum / max(self.gate_steps, 1)

    def count_moe_chunk(self, local_pairs: int, slots: int) -> None:
        """One admission prefill chunk: the pairs that landed on held
        experts, summed over layers, and the live slots they filled."""
        self.moe_chunk_pairs += local_pairs
        self.moe_chunk_slots += slots

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / max(self.total_ms / 1000, 1e-9)

    @property
    def avg_active(self) -> float:
        """Sustained concurrency: mean active slots per device step (rows
        entering a fused chain count for its whole span) — the
        continuous_bench column paged KV exists to move."""
        return self.sum_active / max(self.steps, 1)

    @property
    def spec_accept_rate(self) -> float:
        """Accepted / proposed drafts (0.0 before any proposal)."""
        return self.spec_accepted / max(self.spec_proposed, 1)

    @property
    def plain_step_ms(self) -> float:
        """A step with no admission ahead of it, landing to landing."""
        return 1e3 * (self.land_s - self.land_behind_admit_s) / max(
            self.steps - self.lands_behind_admit, 1)

    @property
    def admit_stall_s(self) -> float:
        """What the steps that stood behind admissions took beyond plain
        steps: the time decode rows stood still for admissions."""
        return max(0.0, self.land_behind_admit_s
                   - self.lands_behind_admit * self.plain_step_ms / 1e3)

    @property
    def admit_stall_ms_per_chunk(self) -> float:
        """... over the prefill chunks: a chunk with its admission's share
        of gather and scatter (or state insert)."""
        return 1e3 * self.admit_stall_s / max(self.prefill_chunks, 1)

    @property
    def admit_share(self) -> float:
        """... over the time the engine was stepping."""
        return self.admit_stall_s / max(self.land_s, 1e-9)

    @property
    def host_ms_per_step(self) -> float:
        """A plain step's interval outside the blocking read of its
        results: what the host itself takes of an iteration (hidden under
        the step in flight where it runs ahead)."""
        return 1e3 * (
            (self.land_s - self.land_behind_admit_s)
            - (self.fetch_wait_s - self.fetch_wait_behind_admit_s)) / max(
                self.steps - self.lands_behind_admit, 1)

    @property
    def chunk_walk_share(self) -> float:
        """The share of the plane a latent spec's chunks walked."""
        return self.chunk_walked_positions / max(self.chunk_plane_positions,
                                                 1)

    @property
    def admit_pages_share(self) -> float:
        """The share of their tables a paged pool's admissions moved."""
        return self.admit_pages_moved / max(self.admit_pages_table, 1)

    @property
    def admission_clause(self) -> str:
        """The account in one clause, for the summaries."""
        return (f"admission {self.admit_share:.1%} of stepping time, "
                f"{self.admit_stall_ms_per_chunk:.1f} ms a chunk over "
                f"{self.prefill_chunks} chunks; plain step "
                f"{self.plain_step_ms:.2f} ms; host "
                f"{self.host_ms_per_step:.2f} ms a step; at most "
                f"{self.admits_back_to_back_max} admissions back to back"
                + (f"; {self.admit_pages_moved} of {self.admit_pages_table} "
                   f"table pages moved ({self.admit_pages_share:.1%}), "
                   f"{self.admit_gathers} admissions gathered"
                   if self.admit_pages_table else ""))


class ContinuousEngine:
    """Owns the slot cache + jitted ragged step; schedules requests.

    ``slots`` bounds concurrent sequences (cache memory = slots x seq_len);
    any number of requests stream through the pool.
    """

    @startup_phase("engine")   # less the pack, place and cache inside it
    def __init__(self, spec: TransformerSpec, params: dict[str, Any],
                 slots: int, temperature: float, topp: float, seed: int,
                 cache_dtype=None, mesh=None, prefill_chunk: int = 0,
                 block_steps: int = 1, use_native_sampler: bool = True,
                 fast_prefill: bool = False, metrics=None,
                 page_size: int = 0, kv_pages: int = 0,
                 prefix_share: bool | None = None, spec_k: int = 0,
                 spec_ngram: int = 3, dispatch_tokens: int = 0,
                 slo=None, chaos=None,
                 journal=None, watchdog=None, kv_quant: str = "f32",
                 kv_host_pages: int = 0, kv_disk_dir: str | None = None,
                 kv_disk_bytes: int = 0, kv_tier_async: bool = True,
                 remote_pages: bool = False, slo_priority: bool = False,
                 q40_layout=None):
        import jax
        import jax.numpy as jnp

        from ..models.llama import (forward_batch_mixed_paged,
                                    forward_batch_paged,
                                    forward_batch_ragged,
                                    forward_batch_spec_paged, gather_pages,
                                    gather_pages_q8, init_cache_batch,
                                    init_cache_paged, init_cache_paged_q8,
                                    params_to_device, scatter_pages,
                                    scatter_pages_q8)

        self.spec = spec
        caches = sequence_caches(spec)
        # a slot of fixed size a sequence (a recurrent state, a window
        # ring); a hybrid spec keeps one AND pages of its full layer
        self._state = "state" in caches
        self._hybrid = spec.slotted
        if prefix_share is None:    # where the spec's cache can be shared
            prefix_share = not self._state
        refused = cache_refusals(
            caches,
            tp=max(mesh.shape["tp"], mesh.shape.get("sp", 1))
            if mesh is not None else 1,
            page_size=page_size, kv_pages=kv_pages,
            prefix_share=bool(prefix_share and page_size),
            spec_k=spec_k, dispatch_tokens=dispatch_tokens,
            kv_quant=kv_quant, kv_host_pages=kv_host_pages,
            kv_disk_dir=kv_disk_dir, journal=journal is not None,
            disagg=remote_pages, block_steps=block_steps,
            kv_cache_dtype="f32" if cache_dtype in (
                None, jnp.float32) else str(cache_dtype))
        if refused:
            raise ValueError("; ".join(refused))
        self.slots = slots
        self.temperature = temperature
        self.topp = topp
        self.seed = seed
        self.jnp = jnp
        self.prefill_chunk = prefill_chunk
        # deterministic fault injection (runtime/chaos.py ChaosMonkey):
        # consulted pre-dispatch (latency spikes), at page allocation
        # (transient starvation), and on cancelled-release (the seeded
        # leak mutation). None = zero overhead, like the metrics handle.
        self._chaos = chaos
        # paged KV mode (page_size > 0): the cache becomes a fixed pool of
        # (page_size)-position pages shared by all slots through per-slot
        # page tables, with radix-tree prefix sharing on admission
        # (runtime/paging.py). page_size == 0 keeps the contiguous
        # slots x seq_len layout. ``kv_pages`` sizes the pool (default:
        # slots * seq_len/page_size — byte-parity with contiguous; pass
        # fewer pages to oversubscribe slots at equal HBM, the
        # continuous_bench concurrency lever).
        self.page_size = page_size
        self._alloc = None
        if kv_pages and page_size <= 0:
            raise ValueError("kv_pages requires page_size > 0 (pass "
                             "--kv-page-size with --kv-pages)")
        # KV page quantization (ISSUE 11): 'q8' stores pool pages in the
        # Q80 int8+scale wire layout (models/llama.PagedKVQ8) — ~1/3.8 of
        # the f32 page bytes, so the same HBM holds ~3.8x pages. Decode
        # quantizes on write; attention dequantizes on read (inside the
        # paged flash kernel's page loop, or in the XLA gather fallback).
        self.kv_quant = kv_quant
        if kv_quant not in ("f32", "q8"):
            raise ValueError(f"kv_quant={kv_quant!r}: expected f32|q8")
        if kv_quant == "q8" and page_size <= 0:
            raise ValueError("kv_quant='q8' quantizes PAGE planes; pass "
                             "page_size > 0 (--kv-page-size with "
                             "--kv-quant q8)")
        if kv_quant == "q8":
            from ..parallel.tp import validate_kv_quant

            validate_kv_quant(spec, (mesh.shape["tp"] if mesh is not None
                                     else 1), kv_quant)
            _warn_q8_xla_fallback(spec, page_size,
                                  mesh.shape["tp"] if mesh is not None
                                  else 1)
        if (kv_host_pages or kv_disk_dir) and page_size <= 0:
            raise ValueError("KV tiering spills PAGES: pass page_size > 0 "
                             "(--kv-page-size with --kv-host-pages/"
                             "--kv-disk-dir)")
        # DCN handoff ingestion (ISSUE 14): the decode pool of a
        # disaggregated topology adopts remotely-prefilled KV pages — the
        # transfer unit is the PAGE, so the paged pool is mandatory
        if remote_pages and page_size <= 0:
            raise ValueError("remote_pages ingests KV PAGES: pass "
                             "page_size > 0 (--kv-page-size with "
                             "--disagg-role decode)")
        if slo_priority and slo is None:
            raise ValueError("slo_priority orders admission by SLO class: "
                             "pass an SLO policy (slo=...)")
        if kv_disk_bytes and not kv_disk_dir:
            raise ValueError("kv_disk_bytes without kv_disk_dir: the disk "
                             "tier needs a directory (--kv-disk-dir)")
        if page_size > 0:
            from .paging import PagedAllocator

            if spec.seq_len % page_size:
                raise ValueError(f"page_size={page_size} must divide "
                                 f"seq_len={spec.seq_len}")
            self._max_pages = spec.seq_len // page_size
            n_pages = kv_pages or slots * self._max_pages
            self._alloc = PagedAllocator(n_pages, page_size,
                                         prefix_share=prefix_share,
                                         host_pages=kv_host_pages,
                                         disk_dir=kv_disk_dir,
                                         disk_bytes=kv_disk_bytes)
            # persistent page-table staging row block (dlint D004): one
            # int32 (slots, max_pages) buffer, rewritten host-side per
            # step and shipped as ONE upload; free/short rows park their
            # tail on the scrap page
            self._stage_tbl = np.zeros((slots, self._max_pages), np.int32)
        # self-speculative decoding (ISSUE 7): each scheduler iteration
        # drafts up to spec_k - 1 tokens per row (runtime/speculative.py
        # n-gram lookup) and verifies them with current-token + drafts in
        # ONE K-query dispatch — the per-dispatch collective schedule is
        # paid once for up to spec_k emitted tokens. Needs the paged cache:
        # rejected-suffix KV rolls back by truncating the page table.
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        if spec_k:
            if spec_k < 2:
                raise ValueError(f"spec_k={spec_k}: the verify window is "
                                 f"current token + K-1 drafts, so K >= 2 "
                                 f"(K=0 disables)")
            if page_size <= 0:
                raise ValueError(
                    "spec_k requires the paged KV cache (pass "
                    "--kv-page-size with --spec-k): acceptance rollback "
                    "truncates the page-table logical length")
            # persistent (slots, K) verify-window staging block: the K
            # input tokens per row ride ONE int32 upload per dispatch
            # (dlint D004), exactly like the chain's staged_i32 rows
            self._stage_spec = np.zeros((slots, spec_k), np.int32)
        # token-budget mixed dispatches (ISSUE 18): every dispatch carries
        # a fixed budget of ``dispatch_tokens`` query positions filled
        # with all active decode rows (1 token each) plus ONE prefill
        # slice cut to the remaining budget, in a single fused forward
        # (models/llama.forward_batch_mixed_paged). -1 = auto: sized from
        # the chunk knob — room for every slot's decode token plus a
        # chunk-wide slice.
        if dispatch_tokens == -1:
            dispatch_tokens = slots - 1 + max(prefill_chunk, 2)
        self.dispatch_tokens = dispatch_tokens
        if dispatch_tokens:
            if dispatch_tokens < 2:
                raise ValueError(
                    f"dispatch_tokens={dispatch_tokens}: the budget holds "
                    f"decode rows plus a prefill slice, so it must be "
                    f">= 2 (0 disables, -1 sizes from the chunk knob)")
            if page_size <= 0:
                raise ValueError(
                    "dispatch_tokens requires the paged KV cache (pass "
                    "--kv-page-size with --dispatch-tokens): the mixed "
                    "window writes through per-row page tables")
            if spec_k:
                raise ValueError(
                    "dispatch_tokens is incompatible with spec_k: the "
                    "verify window and the prefill slice both claim the "
                    "per-row span (unifying them is follow-up work)")
            # persistent (slots, budget + 2) mixed staging block: per row
            # [span, pos, token window...] — ONE int32 upload per dispatch
            # (dlint D004); the jitted program splits device-side
            self._stage_mixed = np.zeros((slots, dispatch_tokens + 2),
                                         np.int32)
            # rotating fairness cursor: when active decode rows exceed the
            # budget, deferral rotates so no row starves (budget_wait)
            self._mixed_rr = 0
        # multi-host SPMD runs MUST pin the numpy sampler: native and numpy
        # can differ by float ulps across libm builds (sampling.Sampler
        # docstring), and divergent hosts feed different tokens into the
        # lockstep step — silent corruption. cli.py passes False whenever
        # --coordinator is set, mirroring the single-sequence Engine path.
        self.use_native_sampler = use_native_sampler
        self.block_steps = block_steps  # >1: fused K-step chains (step_many)
        dtype = cache_dtype or jnp.float32
        self._cache_dtype = dtype
        from ..models.llama import KVCache, forward, init_cache

        def _insert(cache_b, c1, b):
            # write sequence-cache planes (L, S, kv, hs) into row b of the
            # batched (L, B, S, kv, hs) cache, in place (the sharded case
            # is pure per-shard work: the two caches share the S/kv-head
            # sharding axes, and the batch axis is unsharded); a retention
            # spec's (L, kv, ...) state likewise into its (L, B, kv, ...)
            return type(cache_b)(*(
                jax.lax.dynamic_update_slice(
                    whole, one[:, None], (0, b) + (0,) * (one.ndim - 1))
                for whole, one in zip(cache_b, c1)))

        sharded = mesh is not None and (mesh.shape["tp"] > 1
                                        or mesh.shape.get("sp", 1) > 1)
        # how the Q40 leaves lie (ops/linear.Q40Layout), resolved once: one
        # decode dispatch of this engine is ``slots`` rows wide
        from ..ops.linear import q40_body_policy

        self.q40_layout = q40_layout or q40_body_policy(
            spec, rows=slots, sharded=sharded)
        if sharded:
            # sharded step: same program as the lockstep batch path, driven
            # with a (B,) position vector
            from ..parallel import (make_sharded_forward,
                                    make_sharded_forward_batch,
                                    make_sharded_forward_batch_paged,
                                    make_sharded_mixed,
                                    make_sharded_verify, shard_cache,
                                    shard_cache_batch, shard_cache_paged,
                                    shard_params, validate_sharding)
            from ..parallel.comm_stats import tp_scheme

            scheme = tp_scheme()  # one resolution: decode + prefill +
            #                       params all run the same schedule
            validate_sharding(spec, mesh)
            self.params = shard_params(params, mesh, scheme=scheme)
            if self._alloc is not None:
                # +1 physical page: the reserved scrap page 0
                self._step = _shared_program(
                    ("sh_step_paged", spec, mesh, page_size, scheme,
                     kv_quant),
                    lambda: make_sharded_forward_batch_paged(
                        spec, mesh, page_size, scheme=scheme,
                        kv_quant=kv_quant))  # rejects sp>1
                decode_key = ("sh_decode_paged", spec, mesh, page_size,
                              scheme, kv_quant)
                if spec_k:
                    self._verify_base = _shared_program(
                        ("sh_verify", spec, mesh, page_size, scheme,
                         kv_quant),
                        lambda: make_sharded_verify(
                            spec, mesh, page_size, scheme=scheme,
                            kv_quant=kv_quant))
                if dispatch_tokens:
                    self._mixed_base = _shared_program(
                        ("sh_mixed", spec, mesh, page_size, scheme,
                         kv_quant),
                        lambda: make_sharded_mixed(
                            spec, mesh, page_size, scheme=scheme,
                            kv_quant=kv_quant))
                with startup_phase("cache"):
                    self.cache = shard_cache_paged(
                        init_cache_paged_q8(spec, self._alloc.n_pages + 1,
                                            page_size)
                        if kv_quant == "q8" else
                        init_cache_paged(spec, self._alloc.n_pages + 1,
                                         page_size, dtype), mesh)
            else:
                with startup_phase("cache"):
                    self.cache = shard_cache_batch(
                        init_cache_batch(spec, slots, dtype), mesh)
                self._step = _shared_program(
                    ("sh_step_batch", spec, mesh, scheme),
                    lambda: make_sharded_forward_batch(spec, mesh,
                                                       scheme=scheme))
                decode_key = ("sh_decode_batch", spec, mesh, scheme)
            # the jitted shard_map wrapper traces inline under step_once's
            # program, which takes its name
            decode_fwd = self._step
            if prefill_chunk > 1:
                # admission prefill: the sharded single-sequence forward
                # (T=chunk under sp/tp) fills a sharded scratch cache
                self._prefill_fwd = _shared_program(
                    ("sh_prefill", spec, mesh, scheme, fast_prefill),
                    lambda: _maybe_bf16(
                        make_sharded_forward(
                            spec, mesh, scheme=scheme,
                            name="serve_admit_prefill_chunk"),
                        fast_prefill, jax))
                self._scratch_cache = lambda: shard_cache(
                    init_cache(spec, dtype), mesh)
        else:
            # a latent spec's tree is prepared by its spec (the absorbed
            # halves of wkv_b); no other serve tree takes the spec's extras
            self.params = params_to_device(
                params, layout=self.q40_layout,
                spec=spec if spec.latent else None)
            if self._alloc is not None:
                # pages and, a slotted spec, the rows' rings and states
                with startup_phase("cache"):
                    self.cache = (
                        init_cache_paged_q8(spec, self._alloc.n_pages + 1,
                                            page_size)
                        if kv_quant == "q8" else
                        init_cache_paged(spec, self._alloc.n_pages + 1,
                                         page_size, dtype, slots=slots))
                self._step = _shared_program(
                    ("step_paged", spec, page_size, kv_quant),
                    lambda: jax.jit(
                        named_program("serve_decode_step", functools.partial(
                            forward_batch_paged, spec, page_size,
                            kv_quant=kv_quant)),
                        donate_argnums=1))
                # step_once's forward: an expert spec's also hands out the
                # (L, E) routed-rows counts (the chains keep the
                # two-result ``_step``)
                decode_key = ("decode_paged", spec, page_size, kv_quant)
                decode_fwd = functools.partial(
                    forward_batch_paged, spec, page_size, kv_quant=kv_quant,
                    moe_counts=bool(spec.n_experts))
                if self._hybrid:
                    from ..models.llama import slot_counts, slot_model

                    # also takes which rows take part, and hands out the
                    # smallest decay a state took (a mixer-kinds spec: its
                    # gate gauges, and an expert spec's counts after them)
                    decode_fwd = functools.partial(
                        slot_model(spec).forward_batch, spec,
                        page_size=page_size, health=True,
                        **slot_counts(spec))
                if spec_k:
                    self._verify_base = _shared_program(
                        ("verify", spec, page_size, kv_quant),
                        lambda: jax.jit(
                            functools.partial(forward_batch_spec_paged,
                                              spec, page_size,
                                              kv_quant=kv_quant),
                            donate_argnums=1))
                if dispatch_tokens:
                    self._mixed_base = _shared_program(
                        ("mixed", spec, page_size, kv_quant),
                        lambda: jax.jit(
                            functools.partial(forward_batch_mixed_paged,
                                              spec, page_size,
                                              kv_quant=kv_quant),
                            donate_argnums=1))
            else:
                with startup_phase("cache"):
                    self.cache = init_cache_batch(spec, slots, dtype)
                self._step = _shared_program(
                    ("step_ragged", spec),
                    lambda: jax.jit(
                        named_program("serve_decode_step", functools.partial(
                            forward_batch_ragged, spec)),
                        donate_argnums=1))
                decode_key = ("decode_ragged", spec)
                decode_fwd = functools.partial(forward_batch_ragged, spec)
                if self._state:
                    from ..models.llama import forward_batch_retention

                    # step_once's forward also takes which rows take part
                    # and hands out the (L,) smallest normaliser
                    decode_fwd = functools.partial(
                        forward_batch_retention, spec, norm_min=True)
            if prefill_chunk > 1:
                # admission prefill: single-sequence T=chunk forward into a
                # scratch cache + plane insert (a retention spec's chunk
                # is also told how many of its positions are the prompt's)
                from ..models.llama import forward_retention

                # an expert spec's chunk hands out its (L, E) routed-rows
                # counts as its decode step does
                chunk_fwd = (
                    functools.partial(forward_retention, spec) if self._state
                    else functools.partial(forward, spec,
                                           moe_counts=bool(spec.n_experts)))
                if self._hybrid:
                    from ..models.llama import slot_counts, slot_model

                    # the self-decoder alone: the cross-decoder runs where
                    # the prompt's last token takes its decode step (a
                    # mixer-kinds spec's chunk: every layer, no classifier)
                    chunk_fwd = functools.partial(
                        slot_model(spec).forward_chunk, spec, xdec=False,
                        **slot_counts(spec))
                self._prefill_fwd = _shared_program(
                    ("prefill", spec, fast_prefill),
                    lambda: _maybe_bf16(chunk_fwd, fast_prefill, jax,
                                        jit=True))
                self._scratch_cache = lambda: init_cache(spec, dtype)
        # step_once's ONE program (``_with_pick``): logits, picked, cache[,
        # counts] from the previous step's picks and one staged block
        paged = self._alloc is not None
        self._decode = _shared_program(
            decode_key, lambda: jax.jit(
                named_program("serve_decode_step", _with_pick(
                    decode_fwd, paged, spec.vocab_size, self._state,
                    tell_live=not sharded)),
                donate_argnums=1))
        # columns of a launch's staged block:
        # [override | pos | page table | live]
        self._blk_cols = 3 + (self._max_pages if paged else 0)
        # most live rows of a dispatch whose dense Q40 leaves take the
        # stacked block-diagonal body instead of the tile (0: none does)
        self._dense_diag_rows = 0 if sharded else _dense_diag_rows(
            self.params, slots)
        # the newest launch's picks, the next launch's ``prev_picked``; the
        # first is placed as a step's result is, or a mesh's program would
        # compile once for each of the two placements
        self._picked = jax.device_put(
            np.zeros((slots,), np.int32),
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            if sharded else None)
        self._flight: _Flight | None = None  # launched, not landed
        # a paged pool's admissions: the scratch sequence their chunks fill,
        # kept from one to the next (_maybe_prefill_slot)
        self._admit_scratch = None
        # [prefill chunks, admissions with device work] enqueued since the
        # last launch: what the next dispatch will stand behind; and since
        # when the device has been free to run them (the first one's
        # enqueue, or the landing after which nothing else was in flight)
        self._ahead = [0, 0]
        self._ahead_t0 = 0.0
        # an expert spec's: those chunks' (rows, routed-rows counts), left
        # on the device until the dispatch behind them has landed
        self._chunk_moe: list = []
        # rows that left the pool to land with the step in flight
        # (_hand_over), until it has landed: fail_all must reach them
        self._leaving: list[_Slot] = []
        if prefill_chunk > 1:
            # donate only the batched cache (updated in place); the scratch
            # sequence cache can't alias the rank-5 output
            if self._hybrid:
                from ..models.llama import slot_model

                # state and rings into the row, the full layers' K / V
                # into the row's pages: ONE program
                _insert = functools.partial(slot_model(spec).insert_sequence,
                                            page_size=page_size)
            self._insert = _shared_program(
                ("insert", self._state, self._hybrid and page_size,
                 bool(spec.mixers), bool(spec.latent), bool(spec.kda)),
                lambda: jax.jit(
                    named_program("serve_admit_state_insert" if self._state
                                  else "serve_admit_insert", _insert),
                    donate_argnums=0))
            if self._alloc is not None and not self._hybrid:
                # paged prefill plumbing: a virtual contiguous sequence
                # cache the engine keeps from one admission to the next
                # (``_admit_scratch``; an engine with no row left drops it:
                # ``_retire``), the pages below the prompt's start
                # gathered into it (a shared prefix, or what a preempted
                # prompt already holds: suffix chunks must attend over
                # them), prefill into that, and the pages the chunks
                # filled scattered back into the pool in place. Q8 pools
                # dequantize on gather and re-quantize on scatter (the
                # scatter's range starts past the pages an EARLIER encode
                # published — quantize∘dequantize is not byte-idempotent,
                # and a shared page must keep the bytes its first
                # prefiller wrote). Both take the whole table where the
                # range is left out.
                gp = gather_pages_q8 if kv_quant == "q8" else gather_pages
                sp_ = (scatter_pages_q8 if kv_quant == "q8"
                       else scatter_pages)
                self._gather_pages = _shared_program(
                    ("gather", kv_quant, page_size),
                    lambda: jax.jit(
                        named_program(
                            "serve_admit_gather",
                            lambda c, t, into=None, stop=None, gp=gp: gp(
                                c, t, page_size, into=into, stop=stop)),
                        donate_argnames="into"))
                self._scatter_pages = _shared_program(
                    ("scatter", kv_quant, page_size),
                    lambda: jax.jit(
                        named_program(
                            "serve_admit_scatter",
                            lambda c, s, t, start=None, stop=None, sp_=sp_:
                            sp_(c, s, t, page_size, start=start, stop=stop)),
                        donate_argnums=0))
        # KV tiering (ISSUE 12): bind the allocator's device I/O — the
        # demotion read (pool page planes -> host numpy, models/llama.
        # fetch_page_planes), the promotion stage (host payload ->
        # device(-sharded) arrays, run by a background PageUploader so
        # the host->device copy hides behind decode steps), and the
        # donated apply jit the scheduler runs at step boundaries
        # (_settle_promotions). kv_tier_async=False stages inline at
        # promotion time — the deterministic mode the virtual-clock
        # bench/tests drive.
        self._uploader = None
        self._tier_write = None
        self._tier_seen = {"prom": 0, "dem": 0, "hbm": 0, "host": 0,
                           "disk": 0}
        if self._alloc is not None and (self._alloc.tiered or remote_pages):
            from ..models.llama import fetch_page_planes, write_page_planes
            from .paging import PageUploader

            if mesh is not None:
                from ..parallel.tp import stage_page_planes

                q8 = kv_quant == "q8"
                stage = lambda planes: stage_page_planes(  # noqa: E731
                    planes, mesh, q8=q8)
            else:
                stage = lambda planes: tuple(  # noqa: E731
                    jax.device_put(p) for p in planes)
            if self._alloc.tiered:
                if kv_tier_async:
                    self._uploader = PageUploader(stage=stage)
                self._alloc.bind_device_io(
                    lambda pid: fetch_page_planes(self.cache, pid),
                    stage=stage, uploader=self._uploader)
                if chaos is not None:
                    # hook consulted per demotion; the monkey's
                    # drop_on_demote flag decides (like deny_page)
                    self._alloc.corrupt_demote = chaos.demote_drop
            else:
                # remote-only (DCN decode pool): no demotion reads — just
                # the promotion stage + apply for adopted handoff pages
                self._alloc.bind_device_io(None, stage=stage)
            if remote_pages:
                self._alloc.remote = True
            self._tier_write = _shared_program(
                ("tier_write",),
                lambda: jax.jit(write_page_planes, donate_argnums=0))
        # write-ahead request journal (runtime/journal.py, ISSUE 9): every
        # submit/sampled-token/retire appends a record; recover() replays
        # incomplete requests after a crash. None = zero overhead, like
        # the chaos and metrics handles. New request ids start past the
        # journal's highest so appended records never alias old requests.
        self._journal = journal
        self._suspending = False  # drain: retire without journaling
        # per-dispatch hang detection (runtime/supervisor.StepWatchdog):
        # armed around every device call — decode steps, fused chains,
        # verify dispatches, and admission prefill
        self._watchdog = watchdog
        # SLO-aware admission (ISSUE 14): with slo_priority on, _pop_request
        # takes the best-ranked class first (rank = position in the policy's
        # class order, FIFO within a class) instead of plain FIFO — the
        # prefill pool's routing-by-class lever. Scheduling never changes a
        # request's own stream, so priority is stream-invisible.
        self._prio = slo.rank if slo_priority else None
        # chunk-boundary prefill preemption hook (ISSUE 14): a callable
        # consulted at page-aligned chunk boundaries of admission prefill;
        # True parks the slot there (s.prefill_pending) so a higher-priority
        # arrival's prefill runs first. Paged engines only (the contiguous
        # scratch-cache prefill is not resumable). None = never preempt.
        self.prefill_hold = None
        # DCN handoff intake (decode pool): handler threads queue
        # (tokens, planes, request) triples here; the SCHEDULER thread
        # adopts + submits at its next iteration — the radix tree is
        # scheduler-owned and must never be mutated from a handler
        self._remote_inbox: list = []
        # ... and the prefill-pool twin: handler threads queue export
        # requests (tokens, box) and the scheduler fulfils them with the
        # tree-held prompt pages' wire payloads (same ownership rule)
        self._export_inbox: list = []
        self._pool = [_Slot() for _ in range(slots)]
        # persistent host-side staging buffers (dlint D004): the per-step
        # pool scan writes rows here and each step ships ONE upload per
        # buffer instead of B-element Python lists boxed into fresh arrays
        # on every step. Rows: i32 = (token, pos, budget); f32 = (temp,
        # topp). jnp.asarray COPIES host memory into the device buffer at
        # dispatch, so reusing the staging arrays across steps is safe.
        self._stage_i32 = np.zeros((3, slots), np.int32)
        self._stage_f32 = np.zeros((2, slots), np.float32)
        self._stage_active = np.zeros((slots,), np.bool_)
        self._queue: list[Request] = []
        self._lock = threading.Lock()
        self._submitted = 0 if journal is None else journal.next_id
        self._chains: dict = {}  # (k, greedy_only) -> fused chain program
        self.stats = ContinuousStats()
        if self._hybrid:
            from ..models.llama import slot_model

            self.stats.state_bytes, self.stats.window_bytes = slot_model(
                spec).state_bytes(self.cache)
        elif self._state:
            self.stats.state_bytes = sum(int(a.nbytes) for a in self.cache)
        if spec.hyper:
            self.stats.hc_streams = spec.hyper.streams
            self.stats.hc_sublayers_a_step = 2 * spec.n_layers
        # request-cost accounting + dispatch census (ISSUE 16, obs/
        # ledger.py): always on like stats and the SLOTracker — pure
        # host bookkeeping charged once per DISPATCH, not per token; the
        # Prometheus pushes stay behind the self._obs guard below
        self._book = LedgerBook()
        self._census = CensusRing(slots)
        self._ici_row_bytes = 0.0  # per-row ICI bytes per device step
        # telemetry is opt-in: ``metrics`` is an obs.metrics.Registry; when
        # None (the default) self._obs stays None and every guarded call
        # site below is skipped — the hot path makes ZERO registry calls
        # (the off-unless-enabled contract, tests/test_obs.py)
        if metrics is not None:
            from ..obs.spans import SpanTracer
            from ..obs.trace import EngineMetrics

            self._obs = EngineMetrics(metrics)
            self._obs.state_bytes.set(self.stats.state_bytes)
            self._obs.window_bytes.set(self.stats.window_bytes)
            self._obs.hc_streams.set(self.stats.hc_streams)
            self._obs.hc_sublayers_a_step.set(self.stats.hc_sublayers_a_step)
            if self._alloc is not None:
                # a fresh paged server must scrape as fully free, not as
                # exhausted (the gauge default 0)
                self._obs.kv_pages_free.set(self._alloc.n_free)
                # pool byte accounting (ISSUE 11): the GLOBAL logical
                # bytes of the allocated page planes (scrap included;
                # whole pool across tp shards — per-device is /tp) +
                # the KV-quant info series, so a dashboard can prove the
                # equal-HBM capacity claim from the scrape alone
                pool_bytes = sum(int(a.nbytes) for a in self.cache)
                self._obs.bind_kv_pool(kv_quant, pool_bytes,
                                       self._alloc.n_pages + 1)
            # the span timeline (GET /debug/timeline) rides the same
            # opt-in: a disabled engine records nothing. Ring overflow
            # feeds dllama_spans_dropped_total (ISSUE 15 satellite).
            self._spans = SpanTracer(on_drop=self._obs.spans_dropped.inc)
            if mesh is not None and mesh.shape["tp"] > 1:
                # export the analytic collective schedule as labeled
                # /metrics series — the budget a measured collective census
                # is reconciled against. Bytes scale by the slot
                # count: every batched collective moves B rows.
                from ..parallel.comm_stats import tp_collective_budget

                self._obs.bind_collectives(
                    tp_collective_budget(spec, mesh.shape["tp"], scheme),
                    scheme, rows=slots)
                # per-row share of the budget's per-step bytes — the
                # ledger's pro-rated ICI attribution (ISSUE 16)
                self._ici_row_bytes = (self._obs.ici_bytes_per_step
                                       / max(slots, 1))
        else:
            self._obs = None
            self._spans = None
        if journal is not None and self._obs is not None:
            journal.bind_metrics(self._obs.journal_records)
        # SLO verdict tracking (obs/slo.py, ISSUE 8): independent of the
        # metrics toggle — a policy without a registry still tallies
        # (loadcheck's virtual-clock engines), a registry without a
        # policy exposes no SLO series. The tracker is written only at
        # retire, off the per-token hot path.
        if slo is not None:
            from ..obs.slo import SLOTracker

            self._slo = SLOTracker(slo, metrics)
        else:
            self._slo = None

    @property
    def slo_tracker(self):
        """The obs.slo.SLOTracker when a policy was configured, else None
        — the server's /health "slo" block reads snapshot() here."""
        return self._slo

    @property
    def ledger_book(self):
        """The obs.ledger.LedgerBook (always constructed) — the server's
        /health "sched" block and GET /debug/sched read it."""
        return self._book

    @property
    def sched_census(self):
        """The obs.ledger.CensusRing of per-dispatch composition records
        (always constructed) — exported at GET /debug/sched."""
        return self._census

    def close(self) -> None:
        """Release engine-owned background resources — today the KV-tier
        PageUploader thread (ISSUE 12). Idempotent; the engine must not
        step after close(). Server shutdown (runtime/server.InferenceServer
        .stop) and the bench arms call this; short-lived engines may rely
        on the thread being a daemon instead."""
        if self._uploader is not None:
            self._uploader.close()
            self._uploader = None

    def audit_pages(self) -> list[str]:
        """Page-accounting invariant check (paging.PagedAllocator.audit
        over the live slot tables) — the chaos-drill oracle; [] on
        contiguous engines and clean pools."""
        if self._alloc is None:
            return []
        return self._alloc.audit([s.pages for s in self._pool])

    @property
    def allocator(self):
        """The paging.PagedAllocator when page_size > 0, else None — the
        bench and server read pool occupancy / prefix-hit counters here."""
        return self._alloc

    def _chain(self, k: int, greedy_only: bool):
        """Build (and cache) the fused K-step device program: K ragged
        decode steps in ONE dispatch, with per-row active masks so rows
        freeze in place the moment they hit BOS or their budget (a frozen
        row keeps rewriting the same k/v at its frozen position — identical
        values, harmless). Admission/retirement happen on the host BETWEEN
        chains (admission latency <= k steps, the documented trade for
        k fewer host round-trips)."""
        import jax
        import jax.numpy as jnp

        key = (k, greedy_only)
        if key in self._chains:
            return self._chains[key]

        from .decode import sample_device_dynamic

        step = self._step
        paged = self._alloc is not None

        def chain(params, cache, staged_i32, active, forced, coins,
                  staged_f32, table):
            # staged_i32 (3, B) = token/pos/budget rows, staged_f32 (2, B)
            # = temp/topp rows — each ONE host->device upload per chain
            # (dlint D004); the splits below are device-side slices.
            # ``table`` (B, max_pages) is the paged page-table block (a
            # zero-width dummy in contiguous mode): constant across the K
            # steps — step_many pre-allocates page coverage for the whole
            # chain, so no page boundary can strand a mid-chain write
            tokens, pos, budget = (staged_i32[0], staged_i32[1],
                                   staged_i32[2])
            temps, topps = staged_f32[0], staged_f32[1]

            def body(carry, xs):
                tokens, pos, active, cache = carry
                forced_i, coins_i = xs                      # (B,), (B,)
                if paged:
                    logits, cache = step(params, cache, tokens, pos, table)
                else:
                    logits, cache = step(params, cache, tokens, pos)
                if greedy_only:
                    sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    sampled = jax.vmap(sample_device_dynamic)(
                        logits, coins_i, temps, topps)
                nxt = jnp.where(forced_i >= 0, forced_i, sampled)
                rec_active = active
                new_active = (active & (nxt != BOS)
                              & (pos + 1 < budget))
                pos = jnp.where(new_active, pos + 1, pos)
                tokens = jnp.where(new_active, nxt, tokens)
                return (tokens, pos, new_active, cache), (nxt, rec_active)

            (_, _, _, cache), (toks, acts) = jax.lax.scan(
                body, (tokens, pos, active, cache), (forced, coins))
            return cache, toks, acts                       # ys: (K, B)

        # keyed on the step program OBJECT (identity): equal-config
        # engines share a memoized step, so their chains collapse to one
        # compile; a patched step (chaos proxies) gets its own chain
        self._chains[key] = _shared_program(
            ("chain", step, k, greedy_only, paged),
            lambda: jax.jit(chain, donate_argnums=1))
        return self._chains[key]

    # -- speculative decoding (spec_k > 0) ----------------------------------

    def _verify_program(self, greedy_only: bool):
        """The jitted K-query verify dispatch (built once per variant).
        The base program scores all K window positions; when EVERY active
        row is greedy the wrapper argmaxes ON DEVICE and ships a (B, K)
        int32 block instead of the f32 logit cube (decode.
        greedy_verify_tokens) — the same transfer cut the fused chain's
        greedy_only branch makes. Mixed/sampled pools ship full logits:
        rejection-sampling acceptance needs whole distributions with the
        host Sampler's exact semantics."""
        import jax

        key = ("spec", greedy_only)
        if key in self._chains:
            return self._chains[key]
        base = self._verify_base

        from .decode import greedy_verify_tokens

        def run(params, cache, tokens, pos, table):
            logits, cache = base(params, cache, tokens, pos, table)
            out = greedy_verify_tokens(logits) if greedy_only else logits
            return out, cache

        self._chains[key] = _shared_program(
            ("verify_prog", base, greedy_only),
            lambda: jax.jit(run, donate_argnums=1))
        return self._chains[key]

    def _mixed_program(self, greedy_only: bool):
        """The jitted token-budget mixed dispatch (built once per
        variant). The staged (slots, budget + 2) block splits DEVICE-side
        into [span | pos | token window] so the host ships ONE int32
        upload per dispatch (dlint D004, _verify_program's transfer
        shape). All-greedy pools argmax on device and ship a (B, T) int32
        block instead of the f32 logit cube (decode.greedy_verify_tokens
        — the same cut as the verify program); sampled pools ship full
        logits for the host Sampler's exact semantics."""
        import jax

        key = ("mixed", greedy_only)
        if key in self._chains:
            return self._chains[key]
        base = self._mixed_base

        from .decode import greedy_verify_tokens

        def run(params, cache, blk, table):
            span, pos, tokens = blk[:, 0], blk[:, 1], blk[:, 2:]
            logits, cache = base(params, cache, tokens, pos, span, table)
            out = greedy_verify_tokens(logits) if greedy_only else logits
            return out, cache

        self._chains[key] = _shared_program(
            ("mixed_prog", base, greedy_only),
            lambda: jax.jit(run, donate_argnums=1))
        return self._chains[key]

    def step_mixed(self, quiet: bool = True) -> int:
        """One token-budget mixed dispatch over the pool (ISSUE 18):
        every active decode row contributes its 1 pending token and ONE
        row with forced prompt tokens left (the prefill slice — the
        best-SLO-ranked such row, FIFO within a class) contributes up to
        the remaining budget, all in a single fused forward
        (forward_batch_mixed_paged). Prefill therefore never stalls
        in-flight decodes behind a separate chunk dispatch, and PR 14's
        chunk-boundary preemption collapses into slice selection: a
        higher-priority arrival simply wins the next dispatch's slice
        (no parked-slot bookkeeping on this path — _maybe_prefill_slot
        is gated off entirely).

        When active decode rows exceed the budget, the overflow rides
        this dispatch deferred (span 0, masked junk, ledger/census cause
        ``budget_wait``) under a rotating fairness cursor. The host
        replay applies exactly step_once's per-token bookkeeping (forced
        pops, sampler/argmax, BOS + budget stops via _advance), and
        window construction guarantees a row's sampler is consulted only
        at its LAST window position (span <= 1 + len(forced)), so the
        emitted stream is token-for-token the separate-dispatch engine's
        (greedy and seeded-sampled — the tests/test_mixed_batch.py
        parity gates). Returns active slots after the iteration."""
        jnp = self.jnp
        T = self.dispatch_tokens
        self._intake()
        self._admit()
        with host_phase("serve.grow_pages"):
            self._settle_promotions(quiet)
        pool = self._pool
        # span assignment BEFORE page growth: every candidate decode row
        # wants 1 position; the slice row wants its span. Deferral
        # (budget_wait) happens here too — a deferred row needs no pages.
        candidates = [b for b, s in enumerate(pool) if not s.free]
        spans: dict[int, int] = {}
        deferred: set = set()
        if len(candidates) > T:
            order = sorted(candidates,
                           key=lambda b: (b - self._mixed_rr) % self.slots)
            deferred = set(order[T:])
            self._mixed_rr = (self._mixed_rr + T) % self.slots
            for b in order[:T]:
                spans[b] = 1
        else:
            for b in candidates:
                spans[b] = 1
            room = T - len(candidates)
            # ONE prefill slice: among rows with forced tokens pending,
            # the best SLO rank wins (FIFO within a class) — arrival
            # priority replaces the parked-slot preemption machinery
            slice_rows = [b for b in candidates if pool[b].forced]
            if room > 0 and slice_rows:
                rank = self._prio or (lambda cls: 0)
                win = min(slice_rows,
                          key=lambda b: (rank(pool[b].req.slo_class),
                                         pool[b].req.index))
                s = pool[win]
                extra = min(len(s.forced), room)
                if (self._chaos is not None
                        and self._chaos.budget_overrun()):
                    # mutation arm: the slice ignores the remaining
                    # budget and takes the whole staging width
                    extra = min(len(s.forced), T - 1)
                spans[win] = 1 + extra
        with host_phase("serve.grow_pages"):
            paused = self._grow_pages(pool, 1, quiet, spans=spans)
        if all(s.free for s in pool):
            self._journal_sync()  # cover sweep/admit records this iteration
            return self._n_outstanding()
        with host_phase("serve.stage"):
            blk = self._stage_mixed
            greedy_only = True
            for b, s in enumerate(pool):
                span = 0 if (s.free or b in paused or b in deferred) \
                    else spans.get(b, 0)
                spans[b] = span
                blk[b, 0] = span
                blk[b, 1] = s.pos
                blk[b, 2:] = 0
                if span <= 0:
                    continue
                if s.sampler.temperature != 0.0:
                    greedy_only = False
                blk[b, 2] = s.token
                for i, t in enumerate(s.forced[:span - 1]):
                    blk[b, 3 + i] = t
            n_active0 = sum(1 for v in spans.values() if v > 0)
            total_span = sum(spans.values())
            # virtual overrun charge: a healthy dispatch fits the budget
            # (sum(span) <= T); the overrun-budget mutation does not, and the
            # virtual clock must see the extra device time it would cost
            self.stats.overrun_steps += max(0, -(-total_span // T) - 1)
            table = self._stage_tables()
            run = self._mixed_program(greedy_only)
        t0 = time.monotonic()  # census/ledger wall charges need it even
        #                        when the engine runs metrics-dark
        with self._span("mixed", "decode", budget=T, tokens=total_span,
                        active=n_active0), self._watch():
            if self._chaos is not None:
                self._chaos.on_dispatch()  # inside the armed window (the
                #   injected stall IS the hang the watchdog must detect)
            with host_phase("serve.stage"):
                staged = jnp.asarray(blk)
            with host_phase("serve.dispatch"):
                out, self.cache = run(self.params, self.cache, staged,
                                      table)
            t_wait = time.monotonic()
            with host_phase("serve.fetch"):
                out = np.asarray(out)  # dlint: allow[D001] host replay reads ids/logits
            now = time.monotonic()
            dt = now - t0
            self._book_land(dt, 1, *self._queued_ahead(), now - t_wait)
            if self._obs is not None:
                self._obs.record_step(dt, n_active0)
                if self._alloc is not None:
                    self._obs.kv_pages_free.set(self._alloc.n_free)
        with host_phase("serve.census"):
            self.stats.steps += 1
            self.stats.sum_active += n_active0
            self.stats.max_active = max(self.stats.max_active, n_active0)
            self._census_dispatch("mixed", 1, paused, n_active0,
                                  time.monotonic() - t0, deferred=deferred)
        with host_phase("serve.sample"):
            # host replay: exactly step_once's per-token bookkeeping over each
            # row's live window (forced pops first; the sampler is consulted
            # only at the last position, where the fed inputs ran out)
            for b, s in enumerate(pool):
                if s.free:
                    continue
                if s.req.cancelled:  # consumer vanished during the dispatch
                    self._retire(s, quiet)
                    continue
                span = spans.get(b, 0)
                if span <= 0:
                    continue
                for i in range(span):
                    if s.forced:
                        nxt, sampled = s.forced.pop(0), False
                    elif greedy_only:
                        nxt, sampled = int(out[b, i]), True
                    else:
                        nxt, sampled = int(s.sampler.sample(out[b, i])), True
                    if self._advance(s, nxt, quiet, sampled=sampled):
                        break
        self._admit()
        self._journal_sync()
        return self._n_outstanding()

    def step_spec(self, quiet: bool = True) -> int:
        """One draft → verify → accept iteration over the pool (ISSUE 7).

        Each active row feeds [current token | window] where the window is
        its pending FORCED tokens first (prompt replay — guaranteed to
        match, so the dispatch doubles as K-wide prompt chunking), then up
        to K-1 n-gram drafts (runtime/speculative.draft_tokens). The
        K-query verify forward scores every window position in ONE
        dispatch; the host replay applies exactly step_once's bookkeeping
        per position (forced pops, sampler/argmax, BOS + budget stops via
        _advance) and stops at the first position whose outcome differs
        from the fed input — later logits were conditioned on a wrong
        token. Greedy rows accept drafts by exact argmax match, so the
        emitted stream is BITWISE the spec-off stream; sampled rows run
        Leviathan rejection sampling (speculative.accept_or_resample) —
        coin-stream alignment: each resolved draft position draws its
        accept coin (plus one residual-resample coin on rejection), and
        positions never reached consume NO coin, so a seeded engine
        replays deterministically. Rejected-suffix KV is discarded by
        rolling the page table back to the accepted length (_trim_pages)
        — pages whose only content was rejected tokens return to the
        pool. Returns active slots after the iteration."""
        jnp = self.jnp
        K = self.spec_k
        from .speculative import accept_or_resample, draft_tokens

        self._intake()
        self._admit()
        pool = self._pool
        with host_phase("serve.grow_pages"):
            self._settle_promotions(quiet)
            self._resume_prefills()
            paused = self._grow_pages(pool, K, quiet)
        if all(s.free for s in pool):
            self._journal_sync()  # cover sweep/admit records this iteration
            return self._n_outstanding()
        with host_phase("serve.stage"):
            st = self._stage_spec
            st_pos = self._stage_i32  # row 1 = per-slot positions, as ever
            active0 = self._stage_active
            kinds: list = [()] * self.slots  # window entry i (= input i+1):
            #                                   'f' forced | 'd' drafted
            greedy_only = True
            for b, s in enumerate(pool):
                active0[b] = not s.free and b not in paused
                st[b, 0] = s.token
                st[b, 1:] = 0
                st_pos[1, b] = s.pos
                if not active0[b]:
                    continue
                if s.sampler.temperature != 0.0:
                    greedy_only = False
                window = list(s.forced[:K - 1])
                row_kinds = ["f"] * len(window)
                room = K - 1 - len(window)
                if room > 0 and not s.forced[K - 1:]:
                    # drafting starts only past the forced prompt; the lookup
                    # history is the emitted stream plus the forced tokens fed
                    # ahead of the drafts in THIS window
                    history = [s.req.tokens[0]] + s.req.out + window
                    drafts = draft_tokens(history, room, max_n=self.spec_ngram)
                    self.stats.spec_proposed += len(drafts)
                    if drafts:
                        self._census.count_tokens("spec", len(drafts))
                        if s.req.ledger is not None:
                            s.req.ledger.charge_spec(len(drafts), 0)
                    if self._obs is not None:
                        self._obs.spec_proposed.inc(len(drafts))
                        if drafts:
                            self._obs.count_dispatch_tokens("spec",
                                                            len(drafts))
                    window += [int(t) for t in drafts]
                    row_kinds += ["d"] * len(drafts)
                for i, t in enumerate(window):
                    st[b, 1 + i] = t
                kinds[b] = tuple(row_kinds)
            n_active0 = int(active0.sum())
            table = self._stage_tables()
            run = self._verify_program(greedy_only)
        t0 = time.monotonic()  # census/ledger wall charges need it even
        #                        when the engine runs metrics-dark
        with self._span("verify", "decode", k=K, active=n_active0), \
                self._watch():
            if self._chaos is not None:
                self._chaos.on_dispatch()  # inside the armed window: an
                #   injected stall is device work as far as the watchdog
                #   can tell — exactly the hang it must detect
            with host_phase("serve.stage"):
                staged = (jnp.asarray(st), jnp.asarray(st_pos[1]), table)
            with host_phase("serve.dispatch"):
                out, self.cache = run(self.params, self.cache, *staged)
            t_wait = time.monotonic()
            with host_phase("serve.fetch"):
                out = np.asarray(out)  # dlint: allow[D001] host replay reads ids/logits
            now = time.monotonic()
            dt = now - t0
            self._book_land(dt, 1, *self._queued_ahead(), now - t_wait)
            self._count_chunk_moe(self._take_chunk_moe())
            if self._obs is not None:
                self._obs.record_step(dt, n_active0)
                if self._alloc is not None:
                    self._obs.kv_pages_free.set(self._alloc.n_free)
        with host_phase("serve.census"):
            self.stats.steps += 1
            self.stats.sum_active += n_active0
            self.stats.max_active = max(self.stats.max_active, n_active0)
            self._census_dispatch("spec", 1, paused, n_active0,
                                  time.monotonic() - t0)
        with host_phase("serve.sample"):
            # host replay: exactly step_once's per-position bookkeeping over
            # the accepted prefix of each row's window
            for b, s in enumerate(pool):
                if s.free:
                    continue
                if s.req.cancelled:  # consumer vanished during the dispatch
                    self._retire(s, quiet)
                    continue
                if not active0[b]:
                    continue
                row_kinds = kinds[b]
                retired = False
                for i in range(K):
                    accepted_draft = False
                    if s.forced:
                        nxt, sampled = s.forced.pop(0), False
                    elif s.sampler.temperature == 0.0:
                        nxt = (int(out[b, i]) if greedy_only
                               else int(np.argmax(
                                   out[b, i][:self.spec.vocab_size])))
                        sampled = True
                        accepted_draft = (i < len(row_kinds)
                                          and row_kinds[i] == "d"
                                          and nxt == int(st[b, i + 1]))
                    elif i < len(row_kinds) and row_kinds[i] == "d":
                        nxt, accepted_draft = accept_or_resample(
                            out[b, i], int(st[b, i + 1]), s.sampler)
                        sampled = True
                    else:  # no draft fed here: the plain sampler path
                        nxt, sampled = int(s.sampler.sample(out[b, i])), True
                    if accepted_draft:
                        self.stats.spec_accepted += 1
                        if s.req.ledger is not None:
                            s.req.ledger.charge_spec(0, 1)
                        if self._obs is not None:
                            self._obs.spec_accepted.inc()
                    if self._advance(s, nxt, quiet, sampled=sampled):
                        retired = True
                        break
                    if (i + 1 >= K or i >= len(row_kinds)
                            or nxt != int(st[b, i + 1])):
                        # window exhausted, or the fed input was wrong:
                        # logits[i+1] were conditioned on a bad token
                        break
                if not retired:
                    self._trim_pages(s)
        self._admit()
        self._journal_sync()
        return self._n_outstanding()

    def _trim_pages(self, s: _Slot) -> None:
        """Speculative rollback: drop a slot's trailing pages past the
        accepted position. After a verify dispatch, positions >= s.pos may
        hold rejected-draft KV; positions 0..s.pos-1 are live and position
        s.pos is rewritten by the next dispatch before anything reads it,
        so pages covering ONLY positions >= s.pos return to the pool
        (refcounted: a page the radix tree also holds just drops this
        slot's ref). The shared prefix always survives — s.pos never
        rolls below the share boundary."""
        keep = max(self._alloc.pages_for(s.pos), s.shared)
        if len(s.pages) > keep:
            self._alloc.release_pages(s.pages[keep:])
            del s.pages[keep:]
            if self._obs is not None:
                self._obs.kv_pages_free.set(self._alloc.n_free)

    # -- paged-KV bookkeeping (page_size > 0) -------------------------------

    def _settle_promotions(self, quiet: bool = True) -> None:
        """Step-boundary promotion apply (KV tiering, ISSUE 12): write
        every staged promotion payload into its target pool page (ONE
        donated jit per page — in place), then release slots that were
        waiting on those pages: their deferred admission prefill runs now
        (suffix-only, exactly as for an HBM-resident prefix) and they
        dispatch on the next step. Scheduler thread only — the pool cache
        must never be written concurrently with a dispatch."""
        alloc = self._alloc
        if alloc is None or not alloc.pending_capable:
            return
        jobs = alloc.take_staged_promotions()
        for job in jobs:
            self.cache = self._tier_write(self.cache,
                                          self.jnp.int32(job.page),
                                          tuple(job.staged))
            alloc.promotion_applied(job)
        for b, s in enumerate(self._pool):
            if s.free or not s.await_promo:
                continue
            if alloc.slot_pending(s.pages):
                continue  # still uploading: stays paused
            s.await_promo = False
            self._maybe_prefill_slot(b, s)
            if s.req.cancelled:
                self._retire(s, quiet)
        if jobs:
            self._update_tier_obs()

    def _update_tier_obs(self) -> None:
        """Push the allocator's tier ledger into the Prometheus series
        (delta-tracked: obs counters only move forward)."""
        if self._obs is None or self._alloc is None \
                or not self._alloc.tiered:
            return
        a = self._alloc
        for tier, gauge in self._obs.tier_pages.items():
            gauge.set(a.tier_pages.get(tier, 0))
        seen = self._tier_seen

        def push(key, got, counter):
            # cumulative < seen means allocator.reset_counters() ran (the
            # bench warm-up boundary): re-base without incrementing, so
            # the Prometheus counters keep moving instead of stalling
            # until the count re-exceeds its pre-reset high-water mark
            if got > seen[key]:
                counter.inc(got - seen[key])
            seen[key] = got

        push("prom", sum(a.promotions.values()), self._obs.tier_promotions)
        push("dem", sum(a.demotions.values()), self._obs.tier_demotions)
        for tier, counter in self._obs.tier_saved.items():
            push(tier, a.tokens_saved_by_tier.get(tier, 0), counter)

    def _ensure_pages(self, s: _Slot, n_positions: int) -> bool:
        """Grow a slot's page list to cover ``n_positions`` sequence
        positions, evicting idle radix leaves when the free list is dry
        (paging.PagedAllocator.alloc_page). False = the pool cannot cover
        it even after eviction — the caller fails or requeues the
        request. Never shrinks here: pages free at retire, or via the
        speculative rollback (_trim_pages) when a verify dispatch rejects
        a drafted suffix."""
        need = self._alloc.pages_for(min(n_positions, self.spec.seq_len))
        while len(s.pages) < need:
            if self._chaos is not None and self._chaos.deny_page():
                return False  # injected transient starvation (chaos drill)
            pid = self._alloc.alloc_page()
            if pid is None:
                return False
            s.pages.append(pid)
        return True

    def _grow_pages(self, pool, k: int, quiet: bool,
                    spans: dict | None = None) -> set:
        """Pre-chain page coverage: every active slot gets pages for the
        next ``k`` positions (ONE host round per chain — mid-chain writes
        can then never cross into an unmapped page). ``spans`` (the mixed
        path) overrides k per slot — a deferred row (span 0) needs no new
        pages this dispatch. A slot the pool
        cannot serve yet is PAUSED for this chain (returned in the paused
        set): it rides through the device step masked inactive — its dead
        rewrite lands on the scrap page, its replay is skipped, and its
        sampler consumes nothing, so the eventual stream is untouched —
        and retries once a retirement frees pages. Only when EVERY active
        slot is starved (a true deadlock: no retirement can ever free a
        page) does the youngest request fail; preemption/swap-out is the
        ROADMAP item-4 follow-up."""
        while True:
            paused = set()
            promo = set()
            active = 0
            for b, s in enumerate(pool):
                if s.free:
                    continue
                active += 1
                if s.await_promo or (
                        self._alloc.pending_capable
                        and self._alloc.slot_pending(s.pages)):
                    # shared-prefix pages still riding a promotion upload
                    # (KV tiering): the slot pauses like a page-starved
                    # one, but resolves by itself when the upload lands —
                    # never a deadlock, so the breaker must not see it
                    promo.add(b)
                    continue
                if s.prefill_pending:
                    # parked (preempted) admission prefill: the slot makes
                    # progress only through _resume_prefills — masking it
                    # out of dispatches keeps its position clock at the
                    # page-aligned park point (load-bearing for q8: a
                    # forced step advancing mid-page would force the next
                    # scatter to re-quantize a partially-written page).
                    # Self-resolving, so the deadlock breaker skips it.
                    promo.add(b)
                    continue
                need = k if spans is None else spans.get(b, 0)
                if not self._ensure_pages(s, min(s.pos + need, s.budget)):
                    paused.add(b)
            if promo or not paused or len(paused) < active:
                if paused or promo:
                    self.stats.pauses += len(paused) + len(promo)
                    if self._obs is not None:
                        self._obs.pauses.inc(len(paused) + len(promo))
                return paused | promo
            victim = max(paused, key=lambda b: pool[b].req.index)
            s = pool[victim]
            if self._obs is not None:
                self._obs.reject("deadlock")
            s.req.error = (
                f"kv page pool exhausted: {self._alloc.n_pages} pages of "
                f"{self.page_size} positions, all pinned by concurrent "
                f"requests (deadlock broken by failing the youngest)")
            self._retire(s, quiet)  # frees its pages; survivors retry
            #                        (record_retire counts the failure)

    def _stage_tables(self):
        """Rewrite the persistent page-table staging block from the pool
        state and ship it as ONE int32 upload (dlint D004). Free slots and
        unmapped tail entries park on the scrap page — their dead writes
        and masked gathers land on page 0 by construction."""
        from .paging import SCRAP_PAGE

        tbl = self._stage_tbl
        for b, s in enumerate(self._pool):
            n = len(s.pages)
            tbl[b, :n] = s.pages
            tbl[b, n:] = SCRAP_PAGE
        return self.jnp.asarray(tbl)

    def step_many(self, k: int, quiet: bool = True) -> int:
        """Like ``k`` step_once calls in ONE device dispatch. Per-request
        token streams are identical to the per-step path (the parity gate);
        only scheduling differs: a slot freed mid-chain re-admits at the
        chain boundary. Returns active slots after the chain.

        Parity caveat (same class of contract as PARITY.md's native==numpy
        note): the chain samples on DEVICE (decode.sample_device_dynamic)
        while step_once samples on HOST, so token-for-token equality at
        temperature > 0 holds only while the two softmax/CDF implementations
        agree to the ulp at every CDF boundary — pinned by tests on the
        shipped configs, but an XLA or libm change could flip a
        knife-edge coin. temperature == 0 (argmax) is exact by
        construction."""
        if self.dispatch_tokens:
            # token-budget mode (ISSUE 18): every scheduler iteration IS
            # a mixed dispatch (decode rows + one prefill slice under one
            # budget), superseding both per-step and block-step chaining
            return self.step_mixed(quiet=quiet)
        if self.spec_k:
            # speculative mode: every scheduler iteration IS a fused
            # multi-position dispatch (draft → one K-query verify), so the
            # spec path supersedes block-step chaining — chaining verifies
            # would stack drafts on unverified drafts
            return self.step_spec(quiet=quiet)
        if k <= 1:
            return self.step_once(quiet=quiet)
        jnp = self.jnp
        self._drain_flight(quiet)  # a step_once call before this one
        self._intake()
        self._admit()
        pool = self._pool
        with host_phase("serve.grow_pages"):
            self._settle_promotions(quiet)
            self._resume_prefills()
            paused = (self._grow_pages(pool, k, quiet)
                      if self._alloc is not None else ())
        if all(s.free for s in pool):
            self._journal_sync()  # cover sweep/admit records this iteration
            return self._n_outstanding()
        with host_phase("serve.stage"):
            B = self.slots
            st_i32, st_f32 = self._stage_i32, self._stage_f32
            active0 = self._stage_active
            forced = np.full((k, B), -1, dtype=np.int32)
            coins = np.zeros((k, B), dtype=np.float32)
            for b, s in enumerate(pool):
                active0[b] = not s.free and b not in paused
                st_i32[0, b] = s.token
                st_i32[1, b] = s.pos
                st_i32[2, b] = 0 if s.free else s.budget
                st_f32[0, b] = 0.0 if s.free else s.sampler.temperature
                st_f32[1, b] = 0.9 if s.free else s.sampler.topp
                if s.free:
                    continue
                for i, t in enumerate(s.forced[:k]):
                    forced[i, b] = t
                if s.sampler.temperature != 0.0:
                    # pre-draw on a THROWAWAY copy; the real stream
                    # advances during replay by exactly the coins the
                    # per-step loop would consume. Coin alignment: forced
                    # steps draw NO coin, so chain step i uses draw
                    # #(i - n_forced) — the stream position the per-step
                    # loop would be at
                    n_forced = min(len(s.forced), k)
                    if n_forced < k:
                        coins[n_forced:, b] = \
                            s.sampler.rng.clone().f32_array(k - n_forced)

            n_active0 = int(active0.sum())
            table = (self._stage_tables() if self._alloc is not None
                     else jnp.zeros((B, 0), jnp.int32))
            run = self._chain(k, greedy_only=not st_f32[0].any())
        t0 = time.monotonic()  # census/ledger wall charges need it even
        #                        when the engine runs metrics-dark
        with self._span("chain", "decode", steps=k, active=n_active0), \
                self._watch():
            if self._chaos is not None:
                self._chaos.on_dispatch()  # inside the armed window (the
                #   injected stall IS the hang the watchdog must detect)
            with host_phase("serve.stage"):
                staged = (jnp.asarray(st_i32), jnp.asarray(active0),
                          jnp.asarray(forced), jnp.asarray(coins),
                          jnp.asarray(st_f32), table)
            with host_phase("serve.dispatch"):
                self.cache, toks, acts = run(self.params, self.cache,
                                             *staged)
            t_wait = time.monotonic()
            with host_phase("serve.fetch"):
                toks = np.asarray(toks)  # dlint: allow[D001] chain outputs drive
                acts = np.asarray(acts)  # dlint: allow[D001] the host replay below
            now = time.monotonic()
            dt = now - t0
            self._book_land(dt, k, *self._queued_ahead(), now - t_wait)
            self._count_chunk_moe(self._take_chunk_moe())
            if self._obs is not None:
                self._obs.record_step(dt, n_active0, steps=k)
                if self._alloc is not None:
                    self._obs.kv_pages_free.set(self._alloc.n_free)
        with host_phase("serve.census"):
            self.stats.steps += k
            self.stats.sum_active += n_active0 * k
            self.stats.max_active = max(self.stats.max_active, n_active0)
            self._census_dispatch("decode", k, paused, n_active0,
                                  time.monotonic() - t0)
        with host_phase("serve.sample"):
            # host replay: apply the recorded per-step outcomes with
            # exactly step_once's bookkeeping (forced pops, RNG draws,
            # BOS/budget stops)
            for b, s in enumerate(pool):
                if s.free:
                    continue
                if s.req.cancelled:  # consumer vanished during the chain
                    self._retire(s, quiet)  # paused rows free their pages too
                    continue
                if not active0[b]:
                    continue
                for i in range(k):
                    if not acts[i, b]:
                        break
                    sampled = not s.forced
                    if s.forced:
                        s.forced.pop(0)
                    elif s.sampler.temperature != 0.0:
                        s.sampler.rng.f32()  # the coin the chain consumed
                    if self._advance(s, int(toks[i, b]), quiet,
                                     sampled=sampled):
                        break
        self._admit()
        self._journal_sync()
        return self._n_outstanding()

    @contextlib.contextmanager
    def _span(self, name: str, cat: str, phase: str | None = "serve.decode",
              **meta):
        """A span on both rails: the ring's ``name`` on ``perf_counter``
        when tracing is on (a dark engine records none: the zero-calls-
        when-disabled contract covers the span tracer too), and ALWAYS its
        twin ``phase`` on the profiler's clock, so a capture of an engine
        built without a registry shows phases too. A dispatch's twin is
        ``serve.decode``, parent of its stage, dispatch and fetch phases;
        the admission prefill has none of its own (``phase=None``: its
        gather, chunk and scatter phases tile it)."""
        null = contextlib.nullcontext()
        ring = (self._spans.span(name, cat, **meta)
                if self._spans is not None else null)
        with host_phase(phase) if phase else null, ring:
            yield

    def _intake(self) -> None:
        """An iteration's head: handoff pages and cancellations that
        arrived since the last dispatch."""
        with host_phase("serve.intake"):
            self._drain_remote_inbox()
            self._sweep_cancelled()

    def _watch(self):
        """Arm the step watchdog around a device dispatch (supervisor.
        StepWatchdog context manager); free when no watchdog is set."""
        if self._watchdog is None:
            return contextlib.nullcontext()
        return self._watchdog

    def _journal_sync(self) -> None:
        """Step-boundary journal durability point: one fsync covering the
        iteration's records (batch policy), plus the compaction rotation
        check. Called at the end of every step path."""
        if self._journal is None:
            return
        with host_phase("serve.journal"):
            self._journal.sync()
            self._journal.maybe_compact()

    # -- cost accounting (ISSUE 16) -----------------------------------------

    def _census_dispatch(self, kind: str, k: int, paused, active: int,
                         dt_s: float, deferred=(), rode=None) -> None:
        """Charge BOTH accounting halves from one pool walk after a
        decode/spec dispatch: per-slot ledger charges (row steps, page
        steps, stalls by cause, pro-rated ICI bytes) and the whole-
        dispatch census record. The two sides take independent
        arithmetic paths — tools/costcheck.py verifies they agree
        EXACTLY, and the chaos ``double_count_dispatch`` mutation
        multiplies only the ledger side (``reps``) so that check must
        catch it. The census stays mutation-clean by construction."""
        reps = 2 if (self._chaos is not None
                     and self._chaos.dispatch_double()) else 1
        alloc = self._alloc
        dt_share = dt_s / max(active, 1)
        pages_held = 0
        parked: dict = {}
        class_page_s: dict = {}
        slots = list(enumerate(self._pool))
        if rode is not None:
            # step_once: the slots that rode. One that has left the pool
            # (_hand_over) is charged with them; one admitted after the
            # launch holds pages and rode nothing
            in_pool = {id(s) for s in self._pool}
            slots += [(-1, s) for s in rode if id(s) not in in_pool]
            rode = {id(s) for s in rode}
        for b, s in slots:
            if s.free:
                continue
            led = s.req.ledger
            npages = len(s.pages)
            if npages:
                pages_held += npages
                if led is not None:
                    led.charge_pages(npages, k, dt_s, reps)
                cls = self._bill_class(s.req.slo_class)
                class_page_s[cls] = (class_page_s.get(cls, 0.0)
                                     + npages * dt_s)
            if b in paused:
                # re-distinguish what _grow_pages lumped into one set:
                # promo/prefill parks are self-resolving; pool_dry waits
                # on a retirement to free pages
                if s.await_promo or (alloc is not None
                                     and alloc.pending_capable
                                     and alloc.slot_pending(s.pages)):
                    cause = "promo_pending"
                elif s.prefill_pending:
                    cause = "prefill_hold"
                else:
                    cause = "pool_dry"
                parked[cause] = parked.get(cause, 0) + 1
                if led is not None:
                    led.charge_stall(cause, k, dt_s, reps)
            elif b in deferred:
                # mixed path (ISSUE 18): more active rows than the token
                # budget holds — this row rode the dispatch deferred
                # (span 0) and retries under the rotating cursor
                parked["budget_wait"] = parked.get("budget_wait", 0) + 1
                if led is not None:
                    led.charge_stall("budget_wait", k, dt_s, reps)
            elif led is not None and (rode is None or id(s) in rode):
                led.charge_rows(k, dt_share, reps)
                if self._ici_row_bytes:
                    led.charge_ici(self._ici_row_bytes * k, reps)
        with self._lock:
            queued = list(self._queue)
        for req in queued:
            if req.ledger is not None:
                req.ledger.charge_stall("queue_wait", k, dt_s, reps)
        tier = (alloc.tier_page_counts()
                if alloc is not None and alloc.tiered else None)
        self._census.record(kind, k, active, parked, len(queued),
                            pages_held, tier_pages=tier)
        if self._obs is not None:
            for cause, n in parked.items():
                self._obs.add_stall_seconds(cause, n * dt_s)
            if queued:
                self._obs.add_stall_seconds("queue_wait",
                                            len(queued) * dt_s)
            for cls, page_s in class_page_s.items():
                self._obs.add_page_seconds(cls, page_s)
            self._obs.set_class_queue_depth(
                collections.Counter(self._bill_class(r.slo_class)
                                    for r in queued))

    def _close_ledger(self, rid: int, status: str) -> None:
        """Close a request's cost ledger at its terminal event and export
        the per-class cost histograms. The chaos ``leak_ledger`` mutation
        skips the close — tools/costcheck.py's orphaned-ledger check must
        flag it."""
        if self._chaos is not None and self._chaos.ledger_leak():
            return
        snap = self._book.close_request(rid, status)
        if snap is not None and self._obs is not None:
            self._obs.observe_request_cost(snap)

    def prejournal(self, req: Request) -> Request:
        """Assign a request's index and journal its admit record NOW
        without queueing it — the decode pool's durability point BEFORE
        a DCN page transfer (ISSUE 14): a crash between here and
        submit() recovers the request from the journal exactly like a
        crash mid-decode would. The caller must eventually submit() (the
        flag makes that append-free) or retire the journaled life
        (``abandon_prejournaled``) — leaving it dangling re-admits it on
        the next recovery, which is the safe failure mode, not the
        intended one."""
        if not req.tokens:
            raise ValueError("request has no prompt tokens")
        if self._journal is None:
            raise ValueError("prejournal() without a journal has no "
                             "durability to offer; call submit()")
        req.t_enqueue = time.monotonic()
        with self._lock:
            req.index = self._submitted
            self._submitted += 1
        if req.trace is None:
            # mint BEFORE the admit lands: the durable record must carry
            # the trace identity a post-crash recovery continues
            req.trace = tracectx.mint()
        self._journal_admit(req)
        self._journal.sync(force=True)  # durable BEFORE any page moves
        req.prejournaled = True
        return req

    def abandon_prejournaled(self, req: Request) -> None:
        """Retire a prejournaled life that will never be submitted (the
        handoff fell back to local serving): without this, the next
        recovery would replay the request AND the fallback would serve
        it — twice the work, twice the stream."""
        if self._journal is not None and req.prejournaled:
            self._journal.retire(req.index, "cancelled")
            self._journal.sync(force=True)

    def _journal_admit(self, req: Request) -> None:
        """The one admit-record append (submit/prejournal share it)."""
        self._journal.admit(
            req.index, req.tokens, steps=req.steps,
            temperature=(req.temperature if req.temperature is not None
                         else self.temperature),
            topp=req.topp if req.topp is not None else self.topp,
            seed=(req.seed if req.seed is not None
                  else self.seed + req.index),
            slo=req.slo_class, cursor=req.coin_cursor,
            recovers=req.recovered_from,
            trace=(req.trace.to_header() if req.trace is not None
                   else None),
            ledger=req.carried_cost)

    def _trace_admit(self, req: Request) -> None:
        """Trace bookkeeping at the one request entry point (ISSUE 15):
        mint a root context for requests that arrived without one (the
        server minted at HTTP ingress; offline/test paths mint here),
        and materialize a continuation LINK span — zero-duration, cat
        'link' — when this life crossed a seam (recovers/handoff), so
        the joined timeline shows WHERE the trace changed processes."""
        if req.trace is None:
            req.trace = tracectx.mint()
        if self._spans is not None and req.trace.link:
            self._spans.add(req.trace.link, "link", time.perf_counter(),
                            0.0, index=req.index,
                            **tracectx.span_fields(req.trace))

    def _bill_class(self, name: str | None) -> str:
        """The accounting class for a request: None resolves through the
        SLO policy's default class (so ``cost_by_class`` joins the
        ``slo`` block 1:1 — an unlabeled request must not bill under a
        phantom "default" row while its verdict lands on "interactive");
        the literal "default" only exists when no policy is configured."""
        if self._slo is not None:
            return name or self._slo.policy.default_class
        return name or "default"

    def submit(self, req: Request) -> Request:
        """Queue a request (thread-safe; HTTP handler threads call this while
        the scheduler thread steps). ``req.done`` fires when it retires."""
        if not req.tokens:
            raise ValueError("request has no prompt tokens")
        if req.prejournaled:
            self._trace_admit(req)
            if req.ledger is None:
                req.ledger = self._book.open_request(
                    req.index, self._bill_class(req.slo_class),
                    carried=req.carried_cost)
            # index + admit record already durable (prejournal): queue
            with self._lock:
                self._queue.append(req)
                if self._obs is not None:
                    self._obs.set_queue_depth(len(self._queue))
            return req
        req.t_enqueue = time.monotonic()
        with self._lock:
            req.index = self._submitted
            self._submitted += 1
        # open the cost ledger at the id assignment (ISSUE 16): every
        # charge from here to the terminal close lands on this handle; a
        # recovered/handed-off life seeds its previous bill as `carried`
        req.ledger = self._book.open_request(req.index,
                                             self._bill_class(req.slo_class),
                                             carried=req.carried_cost)
        self._trace_admit(req)  # before the journal admit: the durable
        #                         record carries the trace identity
        if self._journal is not None:
            # write-AHEAD means ahead of the SCHEDULER ever seeing the
            # request: the admit record (with the RESOLVED sampler config
            # — the engine-default seed is `seed + index`, which a
            # restarted process would re-derive differently) must be
            # journaled before the queue insert below, or a fast
            # scheduler could sample a token for an id the journal has
            # never admitted. Outside the engine lock: fsync=always
            # blocks on disk here, and the id counter above already
            # reserved our index.
            self._journal_admit(req)
        with self._lock:
            self._queue.append(req)
            if self._obs is not None:
                self._obs.set_queue_depth(len(self._queue))
        return req

    def cancel(self, req: Request) -> None:
        """Cancel a request NOW, from any thread (the server's
        mid-stream-disconnect path). A still-queued request is removed
        and completed immediately; an in-flight one is marked and the
        scheduler's pre-dispatch sweep (_sweep_cancelled) retires it —
        freeing its slot AND its KV pages — before the next chain
        launches, instead of letting a long fused chain decode its whole
        span for a consumer that is gone."""
        req.on_token = None
        req.cancelled = True
        with self._lock:
            if req in self._queue:
                self._queue.remove(req)
                if self._obs is not None:
                    self._obs.set_queue_depth(len(self._queue))
            else:
                return  # in flight (or already done): the sweep owns it
        if self._journal is not None:
            self._journal.retire(req.index, "cancelled")
        if self._obs is not None:
            self._obs.cancelled.inc()
        self._close_ledger(req.index, "cancelled")
        req.done.set()

    def recover(self, quiet: bool = True) -> int:
        """Re-admit every incomplete journaled request (crash recovery,
        ISSUE 9). Each entry re-enters through the NORMAL submit path as a
        fresh request whose prompt is the original prompt PLUS the tokens
        already sampled in the previous life: they ride the forced-token
        window (the PR 7 prompt-chunking path), so prefill re-derives
        their KV — mostly through the radix tree once siblings re-admit —
        and the sampler fast-forwards to the journaled coin cursor
        (_admit), making the continued stream BITWISE the uninterrupted
        run's. The new admit record carries ``recovers=<old rid>``, so
        ONE atomic append opens the new life and retires the old — a
        crash at any point (mid-recovery included) replays exactly one
        live entry per request. Returns the number of requests
        re-admitted."""
        journal = self._journal
        if journal is None:
            raise ValueError("recover() needs a journal (construct the "
                             "engine with journal=...): the atomic "
                             "old-life handoff must land in the journal "
                             "new records are written to")
        # config guard (PR 10): a journal recorded under different model
        # dims / quant types / tp scheme / seed policy / weights would
        # replay bitwise-DETERMINISTIC but bitwise-WRONG streams — refuse
        # before re-admitting anything (JournalConfigMismatch; legacy
        # headers without a fingerprint recover unchecked). With NOTHING
        # live there is nothing a config change could corrupt: adopt the
        # serving config instead of stranding the deployment on an
        # upgrade (e.g. a tp-scheme switch over a fully-retired journal).
        entries = journal.incomplete()
        if entries:
            journal.check_config()
        else:
            journal.adopt_config()
        for e in entries:
            trace = None
            if e.trace:
                try:
                    # continue the SAME trace: new span parented on the
                    # journaled one, linked 'recovers' (ISSUE 15)
                    trace = tracectx.from_header(
                        e.trace, link=tracectx.LINK_RECOVERS)
                except ValueError:
                    trace = None  # a damaged header never blocks recovery
            req = Request(tokens=e.replay_tokens, steps=e.steps,
                          temperature=e.temperature, topp=e.topp,
                          seed=e.seed, slo_class=e.slo,
                          coin_cursor=e.cursor, recovered_from=e.rid,
                          trace=trace, carried_cost=e.ledger)
            self.submit(req)
            if self._obs is not None:
                self._obs.recoveries.inc()
            if not quiet:
                print(f"[recover] request {e.rid} -> {req.index}: "
                      f"{len(e.tokens)} prompt + {len(e.sampled)} sampled "
                      f"tokens, coin cursor {e.cursor}")
        journal.sync(force=True)
        return len(entries)

    def suspend(self, message: str = "draining: request journaled for "
                                     "recovery") -> int:
        """Graceful-drain wrap-up (runtime/server.py SIGTERM path): give
        up on every still-outstanding request WITHOUT retiring it in the
        journal — their admit + token records stay live, so the next
        process recovers them with recover(). Waiters wake with ``error``
        set (the stream handler ends the response; the client retries or
        reconnects after restart). Requires a journal: suspending without
        one would silently drop work — that is fail_all's job, and it
        says "failed". Returns the number of requests left journaled."""
        if self._journal is None:
            raise ValueError("suspend() without a journal would drop "
                             "in-flight work on the floor; use fail_all")
        n = self._n_outstanding()
        self._suspending = True
        try:
            self.fail_all(message)
        finally:
            self._suspending = False
        self._journal.sync(force=True)
        return n

    def ingest_remote(self, tokens, planes, req: Request) -> None:
        """Thread-safe DCN handoff intake (ISSUE 14, decode pool): queue
        shipped page payloads plus the re-admission request for the
        scheduler thread to adopt at its next iteration. ``planes`` is
        the CRC-verified plane tuples in full-prompt-page window order
        (None entries mark pages that never arrived — adoption stops at
        the gap and prefill re-derives)."""
        if self._alloc is None or not self._alloc.remote:
            raise ValueError("ingest_remote needs a remote_pages=True "
                             "paged engine (the decode pool role)")
        with self._lock:
            self._remote_inbox.append((tokens, planes, req))

    def export_prefix_sync(self, tokens, timeout: float = 30.0) -> list:
        """Thread-safe prefill-pool page export (ISSUE 14): ask the
        scheduler thread for the wire payloads of the tree-held full
        prompt pages of ``tokens`` and wait for the answer (the server's
        POST /prefill handler calls this — it must never walk the tree
        itself). [] when nothing is shared (or the scheduler never
        answered inside ``timeout``) — the handoff then ships nothing
        and the decode pool re-derives via prefill."""
        box = {"ev": threading.Event(), "planes": None}
        with self._lock:
            self._export_inbox.append((list(tokens), box))
        box["ev"].wait(timeout)
        return box["planes"] or []

    def _drain_remote_inbox(self) -> None:
        """Scheduler-thread half of ingest_remote/export_prefix_sync:
        adopt shipped pages into the radix tree (promotion-pending) and
        submit their requests so admission finds the prefix already
        published; fulfil pending page exports from the tree."""
        with self._lock:
            if not (self._remote_inbox or self._export_inbox):
                return
            items, self._remote_inbox = self._remote_inbox, []
            exports, self._export_inbox = self._export_inbox, []
        for tokens, planes, req in items:
            self._alloc.adopt_remote_pages(tokens, planes)
            self.submit(req)
        if exports:
            from .disagg import export_prefix_pages

            for tokens, box in exports:
                try:
                    box["planes"] = export_prefix_pages(self, tokens)
                finally:
                    box["ev"].set()

    def _sweep_cancelled(self) -> None:
        """Retire every cancelled in-flight request BEFORE the next
        dispatch (scheduler thread only): pages and slots free at the
        sweep, not after another full chain. The post-dispatch checks in
        the step paths still catch cancellations that land mid-chain."""
        for s in self._pool:
            if not s.free and s.req.cancelled:
                self._retire(s, quiet=True)

    def _n_outstanding(self) -> int:
        """Active slots + queued requests — the step functions' return
        value. Counting the QUEUE matters when admission could not place
        anything (dry pool / injected starvation) while the pool sits
        empty: a bare active count would read 0 and the caller's drive
        loop (run(), the server scheduler) would stop with work still
        waiting."""
        with self._lock:
            queued = len(self._queue) + len(self._remote_inbox)
        return sum(not s.free for s in self._pool) + queued

    def decode_program_text(self) -> str:
        """The compiled text of ``step_once``'s ONE program at this engine's
        shapes. A capture names a device op by its instruction there, and an
        instruction's ``op_name`` carries the ``jax.named_scope``s it was
        traced under (``obs/spans.SCOPE_*``), which the capture does not:
        this is how a trace reader tells a layer's ops by what they are.
        Compiles, or reads the compile cache: not for a measured window."""
        staged = self.jnp.zeros((self.slots, self._blk_cols), self.jnp.int32)
        return self._decode.lower(self.params, self.cache, self._picked,
                                  staged).compile().as_text()

    def step_once(self, quiet: bool = True) -> int:
        """Admit queued requests, land ONE device step over the pool, and
        retire finished rows. Returns the number of active slots after the
        step (0 = idle: nothing queued, nothing in flight). Must be called
        from a single scheduler thread; submit() may race freely.

        The iteration runs one step ahead of the host where the pool lets
        it (``_runs_ahead``): with step n in flight it stages and launches
        step n+1 on step n's picks, which are still on the device, and
        only then lands step n (fetches its picks, advances, notifies and
        retires its rows) while step n+1 runs. Where it does not, the step
        is launched and landed here, on its logits: the iteration this
        engine always had. Either way a call lands exactly one step."""
        self._intake()
        flight, paused = self._flight, ()
        if flight is not None:
            self._hand_over(flight)
        self._admit()
        pool = self._pool
        with host_phase("serve.grow_pages"):
            self._settle_promotions(quiet)
            self._resume_prefills()
            if flight is None and self._alloc is not None:
                paused = self._grow_pages(pool, 1, quiet)
        if flight is None and all(s.free for s in pool):
            self._journal_sync()  # cover sweep/admit records this iteration
            return self._n_outstanding()
        # the slots that ride the step to land: all that are occupied and
        # not paused for one launched here
        fresh = flight is None
        riding = ({b: s for b, s in enumerate(pool)
                   if not s.free and b not in paused} if fresh
                  else dict(flight.rode()))
        go_ahead = self._runs_ahead(riding, paused)
        # the watchdog arms around the wait for a step: from the launch of
        # one launched here, around the fetch of one already in flight
        active0 = len(riding)
        with self._span("step", "decode", active=active0), \
                contextlib.ExitStack() as armed:
            if fresh:
                armed.enter_context(self._watch())
                if self._chaos is not None:
                    self._chaos.on_dispatch()  # inside the armed window
                    #   (the injected stall IS the hang the watchdog must
                    #   detect)
                flight = self._launch(None, paused)
            self._flight = self._launch(flight) if go_ahead else None
            if not fresh:
                armed.enter_context(self._watch())
            out, on_host = self._fetch(flight)
        self._land(flight, out, on_host, quiet)
        self._leaving.clear()
        self._admit()
        ahead = self._flight
        if ahead is not None and not ahead.rode():
            # every row it was launched for stopped meanwhile: nothing to
            # land (its dead writes are in pages that were those rows')
            self._count_dropped(sum(r is not None for r in ahead.reqs))
            self._ahead[0] += ahead.chunks_ahead  # still ahead of the next
            self._ahead[1] += ahead.admits_ahead
            self._chunk_moe[:0] = ahead.chunk_moe
            self._ahead_t0 = time.monotonic()
            self._flight = None
        self._journal_sync()
        return self._n_outstanding()

    def _hand_over(self, flight: _Flight) -> None:
        """Before admission, with ``flight`` still on the device: a row it
        stops for a reason the host knows already (its budget, a forced
        BOS) leaves the pool now and lands with ``flight`` from the slot
        object the flight keeps, so that its place is filled in THIS
        iteration and the next launch carries the new row, as it would
        were the step landed first. The leaving row's pages stay its own
        until it retires at the landing; its full prompt pages are
        published now (``_retire`` would, a moment too late for the
        request that takes its place; ``flight`` writes the last of them
        before any program enqueued from here on reads it)."""
        for b, s in flight.rode():
            if s is self._pool[b] and self._stops_known(s):
                self._pool[b] = _Slot()
                self._leaving.append(s)
                if self._alloc is not None and not s.req.cancelled:
                    n_ins = min(s.pos + 1, len(s.req.tokens))
                    self._alloc.insert_prefix(s.req.tokens[:n_ins], s.pages)

    @staticmethod
    def _stops_known(s: _Slot) -> bool:
        """Whether the step ``s`` rides in now is its last for a reason the
        host knows before the token is back: its budget, a forced BOS."""
        return s.pos + 1 >= s.budget or (bool(s.forced)
                                         and s.forced[0] == BOS)

    def _runs_ahead(self, riding: dict, paused) -> bool:
        """Whether the step after the one ``riding`` ({row: the slot that
        takes part in it}) may be launched before that one lands. The
        rule reads the pool and nothing a user sets:
        every occupied row is greedy (a row with a temperature needs its
        logits on the host, where ``Sampler`` draws its coin), none is
        paused (starved of pages, waiting on a promotion upload, parked
        mid-prefill: those iterations mask rows and may fail one, and stay
        the synchronous ones), the pool covers the positions the next step
        writes, and no chaos monkey is counting dispatches."""
        if self._chaos is not None or paused:
            return False
        alloc = self._alloc
        for s in self._pool:
            if s.free:
                continue
            if (s.sampler.temperature != 0.0 or s.await_promo
                    or s.prefill_pending
                    or (alloc is not None and alloc.pending_capable
                        and alloc.slot_pending(s.pages))):
                return False
        if alloc is None:
            return True
        with host_phase("serve.grow_pages"):
            for b, s in enumerate(self._pool):
                # a riding row writes position pos + 1 next
                ahead = 2 if riding.get(b) is s else 1
                if not s.free and not self._ensure_pages(
                        s, min(s.pos + ahead, s.budget)):
                    return False
        return True

    def _launch(self, prev: _Flight | None, paused=()) -> _Flight | None:
        """Stage and enqueue one run of the step program; returns at once.
        Without ``prev`` every row rides on the host's token for it
        (``paused`` rows too, masked: their result is not read). With
        ``prev``, the step in flight, its rows ride at ``pos + 1`` on its
        picks (override -1) or on the forced token the host knows; rows
        admitted since ride on the host's token; a row of ``prev`` that
        is known to stop there and is still in the pool (cancelled since
        the sweep) rides masked like a free slot, on the scrap page. None
        when no row would take part."""
        from .paging import SCRAP_PAGE

        # a step launched from the host stands behind whatever admissions
        # have enqueued since the device fell idle: its interval runs from
        # there (the enqueue itself can hold the host for most of it)
        t0 = self._ahead_t0 if any(self._ahead) else time.monotonic()
        # a NEW block per launch, shipped as one upload: the last one may
        # still be in transfer (or, on a CPU backend, be the memory the
        # step in flight reads)
        blk = np.empty((self.slots, self._blk_cols), np.int32)
        rows: list = [None] * self.slots
        with host_phase("serve.stage"):
            for b, s in enumerate(self._pool):
                token, pos, pages = s.token, s.pos, s.pages
                if (prev is not None and prev.rows[b] is s
                        and prev.reqs[b] is s.req):
                    if s.req.cancelled or self._stops_known(s):
                        token, pos, pages = 0, 0, ()
                    else:
                        token = s.forced[0] if s.forced else -1
                        pos += 1
                        rows[b] = s
                elif not s.free and b not in paused:
                    rows[b] = s
                row = blk[b]
                row[0], row[1] = token, pos
                row[2:2 + len(pages)] = pages
                row[2 + len(pages):-1] = SCRAP_PAGE
                row[-1] = rows[b] is not None   # live: the last column
            if prev is not None and not any(r is not None for r in rows):
                return None
            if (self.spec.latent or self._hybrid
                    or self._alloc is not None):
                # what each riding row reads: itself and what came before
                depth = [int(blk[b, 1]) + 1 for b, s in enumerate(rows)
                         if s is not None]
                if self._hybrid:
                    w = self.spec.window
                    self.stats.shared_kv_positions += sum(depth)
                    self.stats.window_kv_positions += sum(
                        min(d, w) for d in depth)
                elif self.spec.latent:
                    self.stats.latent_positions += sum(depth)
                else:
                    self.stats.paged_kv_positions += sum(depth)
            staged = self.jnp.asarray(blk)
        with host_phase("serve.dispatch"):
            logits, picked, self.cache, *more = self._decode(
                self.params, self.cache, self._picked, staged)
        self._picked = picked
        if prev is not None:
            self.stats.steps_ahead += 1
            if self._obs is not None:
                self._obs.steps_ahead.inc()
        reqs = [None if s is None else s.req for s in rows]
        # what the spec's kind counts: a state's health reading, an
        # expert spec's routed-rows counts, or both in that order
        norm_min = more.pop(0) if more and self._state else None
        moe = more[0] if more else None
        return _Flight(rows, reqs, paused, logits, picked, moe, norm_min,
                       t0, prev is not None, *self._queued_ahead(),
                       self._take_chunk_moe())

    def _count_layers_run(self) -> None:
        """An ssd or a kda spec's layers a landed decode step ran, by kind
        (a kda spec's latent layers count as "latent")."""
        kinds = (self.spec.ssd.kinds if self.spec.ssd else tuple(
            "latent" if k == "full" else k for k in self.spec.latent.kinds))
        run = self.stats.layers_run
        for kind in kinds:
            run[kind] = run.get(kind, 0) + 1
        if self._obs is not None:
            self._obs.record_layers_run(kinds)

    def _queued_ahead(self) -> tuple[int, int]:
        """(admission prefill chunks, admissions with device work) enqueued
        since the last launch, for the dispatch launched now: both
        ``_admit`` calls of an iteration and ``_resume_prefills`` come
        before the next launch, so this is the device queue's order."""
        chunks, admits = self._ahead
        self._ahead = [0, 0]
        return chunks, admits

    def _take_chunk_moe(self) -> tuple:
        """The routed-rows counts of the chunks enqueued since the last
        dispatch (an expert spec's; still on the device), for the dispatch
        launched now to read once it has landed."""
        taken, self._chunk_moe = tuple(self._chunk_moe), []
        return taken

    def _slot_census(self, local, rows: int) -> tuple[int, int, int]:
        """(live slots, slots of one row, slots that took the
        block-diagonal body) of a ``rows``-row dispatch whose (L, E held)
        routed-rows counts are ``local``, at the rows a slot its shape gives
        (``ops/pallas_moe.slot_cap``) and the fill the body takes on BOTH
        of an expert's leaves (``diag_rows`` of their block counts)."""
        from ..ops.pallas_moe import diag_rows, slot_cap, slot_census

        cap = slot_cap(rows, self.spec.n_active_experts, local.shape[1])
        return slot_census(local, cap, diag_rows(
            cap, self.spec.dim // 32, self.spec.hidden_dim // 32))

    def _count_chunk_moe(self, chunk_moe) -> None:
        """Count chunks whose programs are known to have run (a dispatch
        enqueued behind them has landed): no wait, a few KB each."""
        held = self.spec.held_columns
        for rows, counts in chunk_moe:
            local = np.asarray(counts)[:, held]  # dlint: allow[D001] complete: the step behind it landed
            pairs, slots = int(local.sum()), self._slot_census(local, rows)[0]
            self.stats.count_moe_chunk(pairs, slots)
            if self._obs is not None:
                self._obs.record_moe_chunk(pairs, slots)

    def _book_land(self, dt: float, steps: int, chunks: int, admits: int,
                   wait: float, enqueued_since: bool = False) -> None:
        """The admission account of one landed dispatch
        (``ContinuousStats.book_land``), and its ``/metrics`` twin."""
        behind = self.stats.book_land(dt, steps, chunks, admits, wait,
                                      enqueued_since)
        if self._obs is not None:
            self._obs.record_land(dt, wait, steps if behind else 0)

    def _fetch(self, flight: _Flight):
        """Wait for ``flight`` and bring back what its rows need: the
        picks (4 bytes a row), or the logits where a row of it samples
        with a temperature; an expert spec's counts beside them. Returns
        (array, whether it holds logits)."""
        on_host = any(s.sampler.temperature != 0.0
                      for _, s in flight.rode())
        with host_phase("serve.fetch"):  # the wait and the transfer
            t_wait = time.monotonic()
            if on_host:
                out = np.asarray(flight.logits)  # dlint: allow[D001] host sampler needs logits
            else:
                out = np.asarray(flight.picked)  # dlint: allow[D001] four bytes a row
            flight.wait = time.monotonic() - t_wait
            if flight.norm_min is not None:  # (L,) floats
                health = np.asarray(flight.norm_min)  # dlint: allow[D001] normaliser counter
                low = float(health.min())
                if self.spec.mixers or self.spec.latent:
                    # smallest and mean gate (the smallest comes first)
                    self.stats.count_gate(float(health[0]),
                                          float(health[1]))
                    if self.spec.kda:   # and a state's smallest decay
                        self.stats.ssm_min_decay = min(
                            self.stats.ssm_min_decay, float(health[2]))
                elif self._hybrid:
                    self.stats.ssm_min_decay = min(self.stats.ssm_min_decay,
                                                   low)
                elif low < self.stats.min_normaliser:
                    self.stats.min_normaliser = low
                    if self._obs is not None:
                        self._obs.retention_min_normaliser.set(low)
            if flight.moe is not None:  # 4 KB beside them
                moe = np.asarray(flight.moe)  # dlint: allow[D001] routed-rows counters
                held = self.spec.held_columns
                census = self._slot_census(moe[:, held], self.slots)
                self.stats.count_moe(moe, held, census)
                if self._obs is not None:
                    self._obs.record_moe(moe, held, census)
            self._count_chunk_moe(flight.chunk_moe)
            if self.spec.latent:
                self.stats.latent_pages = (self._alloc.n_pages
                                           - self._alloc.n_free)
                if self._obs is not None:
                    self._obs.latent_pages.set(self.stats.latent_pages)
            if self._hybrid:
                self.stats.shared_kv_pages = (self._alloc.n_pages
                                              - self._alloc.n_free)
                if self._obs is not None:
                    self._obs.record_hybrid(self.stats)
        return out, on_host

    def _land(self, flight: _Flight, out, on_host: bool, quiet: bool) -> None:
        """The host's part of a step whose results ``_fetch`` brought back:
        counters, census, and per row the token, ``_advance`` and
        ``_retire``. A row of ``flight`` whose slot no longer holds its
        request (stopped by the step before, cancelled and swept) is
        dropped: the pool has moved on without it.

        All of it inside the host phase ``serve.land``, which opens at the
        landing's instant and holds one empty ``serve.land.chunk`` per
        admission prefill chunk the step stood behind: a capture pairs a
        landing with the chunks it paid for by containment, on the host's
        lines alone."""
        with host_phase("serve.land"):
            now = time.monotonic()
            for _ in range(flight.chunks_ahead):
                with host_phase("serve.land.chunk"):
                    pass
            pool = self._pool
            dt = now - flight.t0  # landing to landing for a step run ahead
            if self._flight is not None:
                self._flight.t0 = now  # the device starts it as this one ends
            rode = flight.rode()
            if flight.ahead:
                self._count_dropped(
                    sum(r is not None for r in flight.reqs) - len(rode)
                    + sum(s.req.cancelled for _, s in rode))
            active0 = len(rode)
            # what this iteration's admissions enqueued before the fetch
            # stands before the step launched ahead, or waits for the next
            # launch; either way this landing may be late by it
            ahead = self._flight
            if ahead is None:
                self._ahead_t0 = now  # what is queued starts as this ends
            self._book_land(
                dt, 1, flight.chunks_ahead, flight.admits_ahead, flight.wait,
                bool(ahead.chunks_ahead or ahead.admits_ahead)
                if ahead is not None else any(self._ahead))
            if self._obs is not None:
                self._obs.record_step(dt, active0)
                if self._alloc is not None:
                    self._obs.kv_pages_free.set(self._alloc.n_free)
            with host_phase("serve.census"):
                self.stats.steps += 1
                if 1 <= sum(r is not None for r in flight.rows) \
                        <= self._dense_diag_rows:
                    self.stats.dense_diag_steps += 1
                    if self._obs is not None:
                        self._obs.dense_diag_steps.inc()
                if self.spec.ssd or self.spec.kda:
                    self._count_layers_run()
                self.stats.sum_active += active0
                self.stats.max_active = max(self.stats.max_active, active0)
                self._census_dispatch("decode", 1, flight.paused, active0,
                                      dt, rode=[s for _, s in rode])
            with host_phase("serve.sample"):
                for s in {id(s): s for s in (*flight.rows, *pool)
                          if s is not None and not s.free
                          and s.req.cancelled}.values():
                    self._retire(s, quiet)  # consumer gone: free the slot
                for b, s in rode:  # the others are paused, or admitted since
                    if s.free:     # (cancelled: retired above)
                        continue
                    if s.forced:
                        self._advance(s, s.forced.pop(0), quiet)
                    else:
                        nxt = int(s.sampler.sample(out[b]) if on_host
                                  else out[b])
                        self._advance(s, nxt, quiet, sampled=True)

    def _count_dropped(self, n: int) -> None:
        """``n`` rows of a step run ahead whose result is thrown away."""
        if n:
            self.stats.rows_dropped_ahead += n
            if self._obs is not None:
                self._obs.rows_dropped_ahead.inc(n)

    def _drain_flight(self, quiet: bool) -> None:
        """Land the step in flight, if any, launching nothing after it:
        for a caller about to dispatch another program on the pool."""
        flight, self._flight = self._flight, None
        if flight is not None:
            with self._watch():
                out, on_host = self._fetch(flight)
            self._land(flight, out, on_host, quiet)

    def _advance(self, s: _Slot, nxt: int, quiet: bool,
                 sampled: bool = False) -> bool:
        """Apply one decode outcome to a slot — the per-token bookkeeping
        (position clock, BOS stop, output append/notify/count, budget stop)
        shared by step_once and step_many's replay so the two paths cannot
        drift. ``sampled`` marks a token the sampler produced (vs forced
        prompt replay) — the TTFT anchor. Returns True when the slot
        retired."""
        s.pos += 1
        if sampled:
            s.req.n_sampled += 1
            if not s.req.t_first_token:
                s.req.t_first_token = time.monotonic()
                if self._obs is not None and s.req.t_admit:
                    self._obs.prefill.observe(s.req.t_first_token
                                              - s.req.t_admit)
        if nxt == BOS:  # reference stop: BOS before decoding it
            self._retire(s, quiet)
            return True
        s.req.out.append(nxt)
        if sampled and self._journal is not None:
            # journal SAMPLED tokens only (forced echoes re-derive from
            # the admit record) with the cumulative coin cursor — the
            # sampler drew its coins before _advance ran, so rng.draws is
            # already the post-token cursor (speculative accept/resample
            # double-draws included)
            self._journal.token(s.req.index, nxt, s.sampler.rng.draws)
        self._notify(s.req, nxt)
        self.stats.tokens += 1
        self._census.count_tokens("decode")
        if s.req.ledger is not None:
            s.req.ledger.charge_tokens()
        if self._obs is not None:
            self._obs.generated.inc()
            self._obs.count_dispatch_tokens("decode")
        s.token = nxt
        if s.pos >= s.budget:
            self._retire(s, quiet)
            return True
        return False

    def _pop_request(self) -> Request | None:
        """Next live queued request (cancelled-before-admission ones are
        completed and skipped), or None when the queue is empty. With
        slo_priority, the best-ranked SLO class pops first (FIFO within a
        class — stable, so batch work still drains in order)."""
        while True:
            with self._lock:
                if not self._queue:
                    return None
                at = 0
                if self._prio is not None and len(self._queue) > 1:
                    rank = self._prio
                    at = min(range(len(self._queue)),
                             key=lambda i: (rank(self._queue[i].slo_class),
                                            i))
                req = self._queue.pop(at)
                if self._obs is not None:
                    self._obs.set_queue_depth(len(self._queue))
            if not req.cancelled and req.alive is not None \
                    and not req.alive():
                req.on_token, req.cancelled = None, True
                if self._obs is not None:
                    self._obs.cancelled.inc()
            if not req.cancelled:
                return req
            if self._journal is not None:
                self._journal.retire(req.index, "cancelled")
            self._close_ledger(req.index, "cancelled")
            req.done.set()  # consumer gone before admission

    def _requeue_front(self, s: _Slot) -> None:
        """Undo an admission the page pool could not serve: release any
        shared-prefix refs, park the slot free, and put the request back at
        the HEAD of the queue (FCFS — later smaller requests do not jump
        a starved one; preemption is the ROADMAP item-4 follow-up)."""
        req = s.req
        self._alloc.release_pages(s.pages)
        s.pages, s.shared, s.await_promo = [], 0, False
        s.prefill_pending = False
        s.req, s.pos, s.token, s.forced, s.sampler = None, 0, 0, [], None
        req.t_admit = 0.0
        self.stats.requeues += 1
        if self._obs is not None:
            self._obs.reject("pool_dry")
        with self._lock:
            self._queue.insert(0, req)
            if self._obs is not None:
                self._obs.set_queue_depth(len(self._queue))

    def _admit_paged(self, s: _Slot) -> str:
        """Paged admission: walk the radix tree for a shared page-aligned
        prompt prefix (copy-free: the slot's table maps the SAME physical
        pages, refcounted), then allocate fresh pages covering the rest of
        the prompt. Returns 'ok' or 'dry' (pool exhausted — requeue).

        A shared prefix of m positions parks the row at pos m with exactly
        the forced-echo bookkeeping the prefill path uses: the prompt
        tokens it skips still land in ``out`` (output meaning is
        toggle-invariant) and only tokens[m:] remain to process. The same
        gates as admission prefill apply (short prompts, budget overruns,
        mid-stream BOS) — sharing must never change a request's stream.
        """
        req = s.req
        tokens = req.tokens
        # the pool itself bounds a request's positions, exactly like the
        # seq_len clamp above: a 3-page pool can hold 3 pages of history,
        # so the budget caps there instead of letting the deadlock breaker
        # kill the request mid-stream at the pool edge
        s.budget = min(s.budget, self._alloc.n_pages * self.page_size)
        n_pre = len(tokens) - 1
        attempted = (self._alloc.prefix_share and n_pre >= 2
                     and n_pre < s.budget and BOS not in tokens[1:])
        if attempted:
            s.pages = self._alloc.match_prefix(tokens[:n_pre])
            s.shared = len(s.pages)
        if not self._ensure_pages(s, min(len(tokens), s.budget)):
            return "dry"
        if attempted:
            # counted only now that the admission sticks — a dry-pool
            # requeue above re-matches on every retry and must not inflate
            # the hit/saved figures (they are pinned equal to the
            # Prometheus series by tests/test_obs.py)
            self._alloc.record_admission(s.shared)
        m = s.shared * self.page_size
        if m:
            s.pos = m
            s.token = tokens[m]
            s.forced = list(tokens[m + 1:])
            req.out.extend(tokens[1:m + 1])
            for t in tokens[1:m + 1]:
                self._notify(req, t)
            self.stats.tokens += m
            # the shared-prefix echo is prefill-kind work: positions the
            # radix tree covered instead of a forward pass
            self._census.count_tokens("prefill", m)
            if req.ledger is not None:
                req.ledger.charge_tokens(m)
                req.ledger.charge_prefill(0, m, 0.0)
            if self._obs is not None:
                self._obs.generated.inc(m)
                self._obs.prefix_hits.inc()
                self._obs.prefill_saved.inc(m)
                self._obs.count_dispatch_tokens("prefill", m)
        return "ok"

    def _admit(self):
        """Fill free slots from the queue, each admission inside the host
        phase ``serve.admit`` (with the request's trace identity as the
        event's arguments)."""
        for slot_index, s in enumerate(self._pool):
            while s.free:
                req = self._pop_request()
                if req is None:
                    return
                with host_phase("serve.admit",
                                **tracectx.span_fields(req.trace)):
                    if not self._place(slot_index, s, req):
                        return  # pool dry: requeued at the head

    def _place(self, slot_index: int, s: _Slot, req: Request) -> bool:
        """Put ``req`` into the free slot ``s``: sampler, pages, admission
        prefill. False = the page pool cannot serve it yet (requeued, and
        admission stops); True otherwise, the slot then holds the request
        unless its consumer vanished meanwhile (retired: the slot is free
        again and ``_admit`` pops the next)."""
        spec = self.spec
        req.t_admit = time.monotonic()
        if self._hybrid:    # every prompt token takes a decode step, and
            #   with it the cross-decoder, unless admission chunks take it
            self.stats.prompt_positions += len(req.tokens)
            self.stats.xdec_positions += len(req.tokens)
        s.req, s.pos = req, 0
        s.token = req.tokens[0]
        s.forced = list(req.tokens[1:])
        s.budget = min(req.steps, spec.seq_len)
        temp = (req.temperature if req.temperature is not None
                else self.temperature)
        topp = req.topp if req.topp is not None else self.topp
        seed = (req.seed if req.seed is not None
                else self.seed + req.index)
        s.sampler = Sampler(spec.vocab_size, temp, topp, seed,
                            use_native=self.use_native_sampler)
        if req.coin_cursor:
            # journal recovery: fast-forward the xorshift stream
            # past the coins a previous life already consumed —
            # the already-sampled tokens ride the forced window
            # (no draws), so the first NEW sample uses exactly
            # the coin the uninterrupted run would have
            s.sampler.rng.skip(req.coin_cursor)
        if self._alloc is not None:
            if self._admit_paged(s) == "dry":
                self._requeue_front(s)
                return False
            if self._alloc.pending_capable \
                    and self._alloc.slot_pending(s.pages):
                # shared prefix promoting from host/disk (or
                # riding a DCN handoff upload): defer
                # admission prefill until the upload lands
                # (_settle_promotions) — gathering now would
                # read junk where the payload hasn't arrived
                s.await_promo = True
                return True
        self._maybe_prefill_slot(slot_index, s)
        if s.req.cancelled:
            # consumer vanished during admission/prefill: free the
            # slot AND its pages NOW — a cancelled prefill must not
            # pin pool pages until the next chain boundary
            self._retire(s, quiet=True)
        return True

    def _maybe_prefill_slot(self, slot_index: int, s: _Slot):
        """Admission prefill: fill the slot's cache rows for the prompt
        prefix in T=chunk single-sequence passes (Engine.prefill's scheme:
        fixed chunks, pad-safe, junk-invisible) and park the slot at the
        last prompt token — long prompts stop crawling through per-token
        steps. On sharded engines the scratch cache and forward are the
        sharded single-sequence ones (same S/kv sharding axes as the
        batched cache, so the insert is pure per-shard work). Same gates
        as generate._prefill_prefix: off for short prompts, prompts that
        exceed the budget (the forced-echo output is load-bearing), or a
        mid-stream BOS (only the step loop reproduces that early stop)."""
        chunk = self.prefill_chunk
        tokens = s.req.tokens
        n_pre = len(tokens) - 1
        start = s.pos  # 0, the page-aligned prefix-share boundary, or a
        #                preemption park point (s.prefill_pending resume)
        if self.dispatch_tokens:
            # token-budget mode (ISSUE 18): the prompt rides mixed
            # dispatches as the per-dispatch prefill slice (step_mixed) —
            # no separate chunk dispatches, no parked-slot bookkeeping
            s.prefill_pending = False
            return
        if (getattr(self, "_prefill_fwd", None) is None or chunk <= 1
                or n_pre - start < 2 or n_pre >= s.budget
                or BOS in tokens[1:]):
            s.prefill_pending = False
            return
        from ..models.latent import chunk_walked_positions
        from .generate import run_chunked_prefill

        t0 = time.monotonic()  # census/ledger wall charges need it even
        #                        when the engine runs metrics-dark
        chunks0 = self.stats.prefill_chunks
        self.stats.admit_prefills += 1
        if not any(self._ahead):
            self._ahead_t0 = t0
        self._ahead[1] += 1
        jnp = self.jnp
        # a hybrid spec prefills a scratch sequence from position 0 (no
        # shared prefix to gather) and inserts state, rings and pages at once
        paged = self._alloc is not None and not self._hybrid
        # chunk-boundary preemption (ISSUE 14): paged f32 pools only —
        # the contiguous path's fresh scratch cache cannot resume
        # mid-prompt, and a q8 pool quantizes at every scatter, so a
        # resumed prompt would attend over DEQUANTIZED earlier positions
        # where the single-pass run attends f32: accumulated rounding
        # breaks the bitwise single-pool contract. q8 pools keep the
        # SLO-priority admission order; they just never park mid-prompt.
        hold = (self.prefill_hold
                if paged and self.kv_quant == "f32" else None)
        end = n_pre
        with self._span("prefill", "prefill", phase=None, slot=slot_index,
                        tokens=n_pre - start,
                        **tracectx.span_fields(s.req.trace)):
            with host_phase("serve.admit.gather"):
                if paged:
                    # the scratch sequence the engine keeps while it has
                    # rows (else zeros: a gather of no page). The chunks
                    # write positions start.. before any later chunk reads
                    # them and what lies past them is an earlier
                    # sequence's finite K / V under the causal mask, so
                    # only the pages below ``start`` are gathered: a shared
                    # prefix, or what a preempted prompt already holds (the
                    # scratch may have served another slot meanwhile)
                    from .paging import SCRAP_PAGE

                    ps = self.page_size
                    tbl = np.full((self._max_pages,), SCRAP_PAGE, np.int32)
                    tbl[:len(s.pages)] = s.pages
                    tbl_dev = jnp.asarray(tbl)
                    seq = self._admit_scratch
                    if seq is None:
                        seq = self._gather_pages(self.cache, tbl_dev,
                                                 stop=np.int32(0))
                    n_gather = -(-start // ps)
                    if n_gather:
                        seq = self._gather_pages(self.cache, tbl_dev,
                                                 into=seq,
                                                 stop=np.int32(n_gather))
                        self.stats.admit_gathers += 1
                    self._admit_scratch = None  # the chunks donate it
                    cache_box = [seq]
                else:
                    cache_box = [self._scratch_cache()]

            def fwd(part, start_pos, *n_valid):
                self.stats.prefill_chunks += 1
                self._ahead[0] += 1
                with host_phase("serve.admit.prefill_chunk"):
                    _, cache_box[0], *moe = self._prefill_fwd(
                        self.params, cache_box[0],
                        jnp.asarray(part, jnp.int32), jnp.int32(start_pos),
                        *(jnp.int32(n) for n in n_valid))
                if moe:  # read where the dispatch behind it lands
                    self._chunk_moe.append((len(part), moe[0]))
                if self.spec.latent:
                    self.stats.chunk_walked_positions += \
                        chunk_walked_positions(self.spec.seq_len, start_pos,
                                               len(part))
                    self.stats.chunk_plane_positions += self.spec.seq_len

            # a retention spec's chunk says how many of its positions are
            # the prompt's (``valid``): a padded one must not reach a state
            if hold is None:
                run_chunked_prefill(fwd, tokens[start:n_pre], start, chunk,
                                    self.spec.seq_len, valid=self._state)
            else:
                # the same window schedule, one chunk at a time, yielding
                # at PAGE-ALIGNED chunk boundaries when hold(s) says a
                # higher-priority arrival should prefill first. Page
                # alignment is load-bearing for q8 pools: a park inside a
                # page would re-quantize that page's earlier positions on
                # resume (quantize∘dequantize moves bytes)
                lo = start
                while lo < n_pre:
                    hi = min(lo + chunk, n_pre)
                    run_chunked_prefill(fwd, tokens[lo:hi], lo, chunk,
                                        self.spec.seq_len, valid=self._state)
                    lo = hi
                    if (lo < n_pre and lo % self.page_size == 0
                            and hold(s)):
                        end = lo
                        break
            with host_phase("serve.admit.state_insert" if self._state
                            else "serve.admit.scatter"):
                if paged:
                    # the pages the chunks filled and no others: the
                    # range's lower bound is also the q8 rule (a page an
                    # EARLIER encode published, the shared prefix's or a
                    # resumed prompt's own, is not quantized twice)
                    lo, hi = start // ps, -(-end // ps)
                    self.cache = self._scatter_pages(
                        self.cache, cache_box[0], tbl_dev,
                        start=np.int32(lo), stop=np.int32(hi))
                    self._admit_scratch = cache_box[0]
                    self.stats.admit_pages_moved += n_gather + hi - lo
                    self.stats.admit_pages_table += 2 * self._max_pages
                    # publish the freshly prefilled full prompt pages NOW
                    # (not just at retire): a same-system-prompt request
                    # admitted into the next slot this very round already
                    # shares them
                    self._alloc.insert_prefix(tokens[:end], s.pages)
                elif self._hybrid:
                    from .paging import SCRAP_PAGE

                    tbl = np.full((self._max_pages,), SCRAP_PAGE, np.int32)
                    tbl[:len(s.pages)] = s.pages
                    self.cache = self._insert(self.cache, cache_box[0],
                                              jnp.int32(slot_index),
                                              jnp.asarray(tbl))
                else:
                    self.cache = self._insert(self.cache, cache_box[0],
                                              jnp.int32(slot_index))
        # echo the prefilled prompt tokens into the output AND the token
        # count (the step loop both appends forced tokens and counts them —
        # "Generated tokens" must not change meaning with the toggle)
        s.req.out.extend(tokens[start + 1:end + 1])
        for t in tokens[start + 1:end + 1]:
            self._notify(s.req, t)
        dt_prefill = time.monotonic() - t0  # the ENQUEUE of the programs:
        #   the host's work, which the ledger bills; what they cost the
        #   device is the admission account's (ContinuousStats.book_land)
        self.stats.tokens += end - start
        # prefill census record: steps=0 so the step/stall/page-step
        # conservation totals (decode/spec currency) are untouched — the
        # record documents the dispatch's token composition only
        self._census.count_tokens("prefill", end - start)
        self._census.record("prefill", 0, 0, {}, 0, 0,
                            prefill_tokens=end - start)
        if s.req.ledger is not None:
            s.req.ledger.charge_tokens(end - start)
            s.req.ledger.charge_prefill(
                self.stats.prefill_chunks - chunks0, end - start,
                dt_prefill)
        if self._obs is not None:
            self._obs.generated.inc(end - start)
            self._obs.admit_prefills.inc()
            self._obs.admit_prefill_chunks.inc(
                self.stats.prefill_chunks - chunks0)
            self._obs.count_dispatch_tokens("prefill", end - start)
        s.pos = end
        s.token = tokens[end]
        s.forced = list(tokens[end + 1:]) if end < n_pre else []
        s.prefill_pending = end < n_pre
        if self._hybrid:    # the chunks ran no cross-decoder
            self.stats.xdec_positions -= end - start

    def _resume_prefills(self) -> None:
        """Continue chunk-preempted admission prefills (ISSUE 14): every
        slot parked at a page-aligned boundary re-enters
        _maybe_prefill_slot — which may park it again if the hold still
        fires — so a preempted batch prompt keeps making chunk progress
        instead of crawling through per-token forced steps."""
        for b, s in enumerate(self._pool):
            if s.free or not s.prefill_pending or s.await_promo \
                    or s.req.cancelled:
                continue
            self._maybe_prefill_slot(b, s)

    @staticmethod
    def _notify(req: Request, token: int):
        """Streaming hook dispatch — exceptions must never reach the
        scheduler loop (a broken client is that client's problem)."""
        if req.on_token is not None:
            try:
                req.on_token(token)
            except Exception:
                req.on_token = None  # stop notifying a broken consumer

    def _retire(self, s: _Slot, quiet: bool):
        if not quiet:
            print(f"[{s.req.index}] done: {len(s.req.out)} tokens "
                  f"(pos {s.pos}/{s.budget})")
        if self._alloc is not None and s.pages:
            # publish the request's FULL prompt pages into the radix tree
            # (positions 0..pos-1 hold prompt k/v up to min(pos, prompt));
            # cancelled/failed requests publish nothing. Then drop this
            # slot's refs — tree-held pages survive for prefix reuse until
            # LRU eviction reclaims them.
            if s.req.error is None and not s.req.cancelled:
                n_ins = min(s.pos, len(s.req.tokens))
                self._alloc.insert_prefix(s.req.tokens[:n_ins], s.pages)
            elif self._chaos is not None and s.req.cancelled:
                # chaos mutation arm (leak_on_cancel): deliberately drop a
                # page from the release so the drill audit must flag it
                s.pages = self._chaos.filter_release(s.pages)
            self._alloc.release_pages(s.pages)
            s.pages, s.shared, s.await_promo = [], 0, False
            if self._obs is not None:
                self._obs.kv_pages_free.set(self._alloc.n_free)
                self._update_tier_obs()
        s.prefill_pending = False
        if all(t.free or t is s for t in (*self._pool, *self._leaving)):
            # the last row leaves: an idle engine holds no scratch sequence
            # (4,096 positions of a 7B model's K / V are 1 GB; the next
            # admission's is a fill of zeros)
            self._admit_scratch = None
        s.req.t_finish = time.monotonic()
        if self._journal is not None and not self._suspending:
            # a drain-suspended request writes NO retirement: its admit +
            # token records stay live, so the next process recovers it
            self._journal.retire(
                s.req.index,
                "cancelled" if s.req.cancelled
                else "failed" if s.req.error is not None else "done")
        if self._obs is not None:
            self._obs.record_retire(s.req, s.req.t_finish)
        if self._slo is not None:
            # verdict at retire (obs/slo.py): met/violated from the wall
            # lifecycle stamps, failed on engine error; cancelled
            # requests record nothing (client-side, not a serving SLO)
            self._slo.observe_request(s.req, s.req.t_finish)
        if self._spans is not None and s.req.t_admit:
            # request lifecycle timestamps are time.monotonic; re-anchor the
            # admit→finish window onto the tracer's perf_counter timeline
            # (the two clocks share a rate, not necessarily an epoch)
            dur = s.req.t_finish - s.req.t_admit
            start = time.perf_counter() - (time.monotonic() - s.req.t_admit)
            self._spans.add("request", "request", start, dur,
                            index=s.req.index, tokens=len(s.req.out),
                            sampled=s.req.n_sampled,
                            cancelled=s.req.cancelled,
                            **tracectx.span_fields(s.req.trace))
        self._close_ledger(
            s.req.index,
            "cancelled" if s.req.cancelled
            else "failed" if s.req.error is not None else "done")
        s.req.done.set()
        s.req = None
        # park the freed slot at pos 0: a retired row's clock can equal
        # seq_len, and feeding that to the flash kernel would DMA one
        # chunk past the end of the cache row (free slots still ride
        # through the fixed-B step; their writes at pos 0 are dead until
        # the slot is re-admitted, which restarts at pos 0 anyway)
        s.pos, s.token = 0, 0

    def fail_all(self, message: str):
        """Fail every queued and in-flight request (scheduler error path —
        runtime/server.py): sets ``error`` then ``done`` so waiters wake.
        A step in flight is forgotten unlanded, like one never run."""
        self._flight = None
        with self._lock:
            pending = self._queue
            self._queue = []
            if self._obs is not None:
                self._obs.set_queue_depth(0)
        for req in pending:
            req.error = message
            if self._journal is not None and not self._suspending:
                self._journal.retire(req.index, "failed")
            if self._obs is not None:
                self._obs.failed.inc()
            if self._slo is not None:
                # never admitted, but attempted: a failed attempt in its
                # class (queue-killed work is an SLO event)
                self._slo.observe(req.slo_class, None, None, 0,
                                  failed=True)
            self._close_ledger(req.index, "failed")
            req.done.set()
        for s in (*self._pool, *self._leaving):
            if not s.free:
                s.req.error = message
                self._retire(s, quiet=True)
        self._leaving.clear()
        if self._alloc is not None:
            # tear the radix tree down with the rest of the engine state:
            # a post-fault serving loop restarts from an empty, fully-free
            # pool instead of silently inheriting published prefixes
            self._alloc.tree.clear()
            if self._obs is not None:
                self._obs.kv_pages_free.set(self._alloc.n_free)

    def run(self, requests: list[list[int]], steps: int,
            quiet: bool = True) -> tuple[list[list[int]], ContinuousStats]:
        """Offline entry: decode every request (a non-empty prompt token
        list, BOS included) to BOS or ``steps`` positions; returns outputs
        in request order."""
        for i, r in enumerate(requests):
            if not r:
                raise ValueError(f"request {i} has no prompt tokens")
        self.stats = ContinuousStats()
        with self._lock:
            # per-run request indices: request i samples from seed + i, so a
            # re-used engine reproduces the same streams run after run (the
            # solo-parity contract in the module docstring); the counter
            # keeps advancing monotonically in online mode (server) and
            # whenever a journal is bound — resetting would alias new
            # journal records onto already-journaled request ids
            if self._journal is None:
                self._submitted = 0
        reqs = [self.submit(Request(tokens=list(r), steps=steps))
                for r in requests]
        t0 = time.perf_counter()
        while self.step_many(self.block_steps, quiet=quiet):
            pass
        self.stats.total_ms = (time.perf_counter() - t0) * 1000
        assert all(r.done.is_set() for r in reqs)
        return [r.out for r in reqs], self.stats


def decode_stream(tokenizer, first_token: int, tokens: list[int]) -> str:
    """Decode a generated token stream to text, chaining decode_piece's
    prev-token context from the prompt's first token — the ONE decode loop
    shared by the CLI row printer and the HTTP server."""
    prev, text = first_token, b""
    for t in tokens:
        text += tokenizer.decode_piece(prev, t)
        prev = t
    return text.decode("utf-8", errors="replace")


def generate_continuous(spec: TransformerSpec, params: dict[str, Any],
                        tokenizer, prompts: list[str], steps: int,
                        temperature: float, topp: float, seed: int,
                        slots: int = 0, cache_dtype=None, mesh=None,
                        prefill_chunk: int = 0, block_steps: int = 1,
                        quiet: bool = False, use_native_sampler: bool = True,
                        fast_prefill: bool = False, metrics=None,
                        page_size: int = 0, kv_pages: int = 0,
                        spec_k: int = 0, spec_ngram: int = 3,
                        dispatch_tokens: int = 0,
                        kv_quant: str = "f32", kv_host_pages: int = 0,
                        kv_disk_dir: str | None = None,
                        kv_disk_bytes: int = 0, q40_layout=None):
    """CLI entry: encode prompts, stream them through a slot pool, print
    rows in the --prompts-file format ("[i] 'text'")."""
    reqs = [tokenizer.encode(p or "", bos=True, eos=False) for p in prompts]
    slots = slots or min(len(reqs), 8)
    eng = ContinuousEngine(spec, params, slots, temperature, topp, seed,
                           cache_dtype=cache_dtype, mesh=mesh,
                           prefill_chunk=prefill_chunk,
                           block_steps=block_steps,
                           use_native_sampler=use_native_sampler,
                           fast_prefill=fast_prefill, metrics=metrics,
                           page_size=page_size, kv_pages=kv_pages,
                           spec_k=spec_k, spec_ngram=spec_ngram,
                           dispatch_tokens=dispatch_tokens,
                           kv_quant=kv_quant, kv_host_pages=kv_host_pages,
                           kv_disk_dir=kv_disk_dir,
                           kv_disk_bytes=kv_disk_bytes,
                           q40_layout=q40_layout)
    outs, stats = eng.run(reqs, steps, quiet=quiet)
    for b, (req, row) in enumerate(zip(reqs, outs)):
        if not quiet:
            print(f"[{b}] {decode_stream(tokenizer, req[0], row)!r}")
    if not quiet:
        print(f"Generated tokens:    {stats.tokens} across {len(reqs)} "
              f"requests ({slots} slots, {stats.steps} steps)")
        print(f"Avg generation time: "
              f"{stats.total_ms / max(1, stats.tokens):.2f} ms/token "
              f"({stats.tokens_per_s:.1f} tok/s)")
        if stats.steps_ahead:
            print(f"Steps run ahead:     {stats.steps_ahead} of "
                  f"{stats.steps}, {stats.rows_dropped_ahead} rows dropped")
        print(f"Admission account:   {stats.admission_clause}")
        if eng.allocator is not None:
            a = eng.allocator
            print(f"Paged KV:            {a.n_pages} pages x "
                  f"{a.page_size} positions ({eng.kv_quant}), "
                  f"{a.n_free} free; prefix hit "
                  f"rate {a.hit_rate:.0%}, {a.tokens_saved} prefill "
                  f"tokens saved, {a.evictions} evictions")
            if a.tiered:
                counts = a.tier_page_counts()
                saved = a.tokens_saved_by_tier
                print(f"KV tiers:            hbm {counts['hbm']} / host "
                      f"{counts['host']} / disk {counts['disk']} pages; "
                      f"{sum(a.demotions.values())} demotions, "
                      f"{sum(a.promotions.values())} promotions; "
                      f"{saved['host'] + saved['disk']} prefill tokens "
                      f"rescued from spilled tiers")
        if eng.dispatch_tokens:
            print(f"Token budget:        {eng.dispatch_tokens} "
                  f"tokens/dispatch over {stats.steps} mixed dispatches")
        if eng.spec_k:
            print(f"Speculative:         K={eng.spec_k}, "
                  f"{stats.spec_accepted}/{stats.spec_proposed} drafts "
                  f"accepted ({stats.spec_accept_rate:.0%}); "
                  f"{stats.total_ms / max(1, stats.tokens):.2f} "
                  f"ms/accepted token over {stats.steps} verify dispatches")
    return outs, stats
