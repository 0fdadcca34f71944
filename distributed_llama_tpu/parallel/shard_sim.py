"""Single-chip execution of ONE tp-rank's program — the 70B measurement path.

The north-star workload (Llama-2-70B Q40 on a v5e-8, vs the reference's
4842.81 ms/token on 8 RasPis, /root/reference/README.md:48) cannot run whole
on one chip (~38.7 GB packed), and this environment exposes exactly one real
chip. What CAN run whole is one tp=8 rank: its weight bands are ~5 GB packed
(wq 1024x8192 etc., 80 layers, GQA 1 kv head/rank), and its per-layer program
is EXACTLY tp.make_local_step — the function shard_map runs on every chip of
a real v5e-8 — with the per-layer collectives (all_gathers in the ref
scheme; psum / psum_scatter+gather combines in the fused scheme) swapped
for local stand-ins (band tile ``jnp.concatenate([band]*8)``, identity,
band slice): same output shapes, same post-collective
memory writes, no ICI. Measuring this on the real chip gives the per-chip
compute+HBM cost of the real 8-way program; the ICI side is added
analytically (comm_stats byte counts over measured-assumption link bandwidth
+ per-collective latency) to produce the projected full-system ms/token with
the collective budget itemized (bench.py --config 70b-tp8).

What the tile does NOT reproduce: ICI serialization and any compute-
collective overlap XLA would schedule. The projection therefore reports
compute + collectives as a straight SUM — the conservative (no-overlap)
estimate.

Values are garbage by construction (every gathered band repeats this rank's
values), so this path is for timing/shape work only; logit parity of the
identical program is gated at small scale by tests/test_tensor_parallel.py
(real collectives, tp ∈ {1,2,4,8}) and test_shard_sim.py (sim == real
program structure, sim(tp=1) == single-chip forward exactly).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from ..models.spec import TransformerSpec
from .comm_stats import tp_collective_budget, tp_scheme


def make_tile_gather(n_slices: int):
    """A gather_fn (tp._ici_gather signature) that replicates the local band
    n_slices times along the gather axis: full-size output tensor, full
    post-gather write traffic, zero ICI."""
    import jax.numpy as jnp

    def tile(a, axis):
        if n_slices == 1:
            return a
        return jnp.concatenate([a] * n_slices, axis=axis)

    return tile


def _sim_psum(a):
    """psum stand-in (tp._ici_psum signature) for the one-rank sim: the
    row-parallel partial already has the full output shape, and the real
    psum's local arithmetic is a negligible add tree — identity keeps the
    shapes and memory traffic honest with zero ICI."""
    return a


def make_tile_scatter(n_slices: int):
    """psum_scatter stand-in (tp._ici_scatter signature): keep this rank's
    1/n_slices band of the axis — same local output shape as the real
    reduce_scatter, zero ICI. (Values are garbage by construction, like the
    tile gather's.)"""
    import jax.lax

    def scatter(a, axis):
        if n_slices == 1:
            return a
        return jax.lax.slice_in_dim(a, 0, a.shape[axis] // n_slices,
                                    axis=axis)

    return scatter


def _sim_permute(a, shift, n_slices):
    """ppermute stand-in (tp._ici_ppermute signature) for the one-rank sim:
    identity — same chunk shape lands in the ring stash, same slice/update/
    fold memory traffic, zero ICI. (Every 'received' chunk is this rank's
    own send, so values are garbage by construction, like the tile
    gather's.)"""
    return a


def _sim_rank():
    """tp._tp_rank stand-in: the sim runs outside any mesh axis, so the
    one simulated rank is rank 0 (chunk indices stay in-range; which rank
    the sim 'is' cannot matter — values are garbage anyway)."""
    return 0


def synth_rank_q40(spec: TransformerSpec, n_slices: int, seed: int = 0,
                   embed_dtype=None,
                   scheme: str | None = None) -> dict[str, Any]:
    """Random Q40 params at ONE rank's band shapes (models/synth.synth_q40_fast
    semantics: packed bytes directly — timing is value-independent).

    Replicated tensors (tok_embedding, norms) come at full size, exactly what
    every chip of the real mesh holds; matmul weights come as the rank's
    band under the active tp ``scheme`` (tp.py): output-dim bands for
    wq/wk/wv/w1/w3/wcls in both schemes, and for wo/w2 either output-dim
    bands (ref: wo/w2 (dim/S, dim)/(dim/S, hidden)) or INPUT-dim bands
    (fused: wo (dim, dim/S), w2 (dim, hidden/S)).
    ``embed_dtype`` (e.g. bf16) shrinks the 1 GB-at-70B replicated embedding
    table; timing impact is negligible (one row read per token).
    """
    from ..io.loader import Q40Weight

    scheme = scheme or tp_scheme()
    if spec.n_heads % n_slices or spec.n_kv_heads % n_slices:
        raise ValueError(f"tp={n_slices} does not divide heads "
                         f"{spec.n_heads}/{spec.n_kv_heads}")
    if scheme in ("fused", "overlap"):  # overlap shares the fused layout
        for name, n_in in (("wo", spec.dim), ("w2", spec.hidden_dim)):
            if (n_in // n_slices) % 32:
                raise ValueError(
                    f"{scheme} tp scheme slices {name}'s Q40 input dim: "
                    f"{n_in}/{n_slices} must be a 32-multiple")
    rng = np.random.default_rng(seed)

    def t(*shape):
        return (rng.standard_normal(shape) * 0.05).astype(
            embed_dtype or np.float32)

    def mm(*shape):
        *lead, d, n = shape
        qs = rng.integers(0, 256, (*lead, d, n // 32, 16), dtype=np.uint8)
        d16 = (rng.random((*lead, d, n // 32), dtype=np.float32)
               * 0.01 + 1e-4).astype(np.float16)
        return Q40Weight(qs, d16)

    S = n_slices
    p = {"tok_embedding": t(spec.vocab_size, spec.dim),
         "rms_final": t(spec.dim).astype(np.float32),
         "rms_att": t(spec.n_layers, spec.dim).astype(np.float32),
         "rms_ffn": t(spec.n_layers, spec.dim).astype(np.float32),
         "wcls": mm(spec.vocab_size // S, spec.dim)}
    for name, (d, n) in spec.layer_matmul_shapes():
        if scheme in ("fused", "overlap") and name in ("wo", "w2"):
            p[name] = mm(spec.n_layers, d, n // S)  # input-dim band
        else:
            p[name] = mm(spec.n_layers, d // S, n)
    return p


def make_rank_step(spec: TransformerSpec, n_slices: int,
                   scheme: str | None = None):
    """One rank's raw (traceable) step fn — feed this to the fused decode
    loop (runtime/decode.make_decode_loop) so the whole chain is one device
    program, like the flagship bench path. All the collective hooks get
    local stand-ins (tile gather / identity psum / band-slice scatter /
    identity ppermute + rank-0 index for the overlap ring), so the sim
    runs whichever scheme's exact compute program — chunk slices, ring
    stash updates, rank-order fold, deferred-gather carry included — with
    zero ICI."""
    from .tp import make_local_step

    return make_local_step(spec, n_slices, 1,
                           gather_fn=make_tile_gather(n_slices),
                           scheme=scheme, psum_fn=_sim_psum,
                           scatter_fn=make_tile_scatter(n_slices),
                           permute_fn=_sim_permute, rank_fn=_sim_rank)


def make_rank_forward(spec: TransformerSpec, n_slices: int,
                      scheme: str | None = None):
    """Jitted fn(params, cache, tokens (T,), pos) running one rank's program
    on the local chip (tp.make_local_step with the tile stand-ins). The
    cache argument is the rank-local (L, seq, n_kv/S, hs) shard."""
    import jax

    return jax.jit(make_rank_step(spec, n_slices, scheme), donate_argnums=1)


def init_rank_cache(spec: TransformerSpec, n_slices: int, dtype=None):
    """The rank's KV-cache shard: n_kv/S heads of the full sequence."""
    import jax.numpy as jnp

    from ..models.llama import KVCache

    dtype = dtype or jnp.float32
    shape = (spec.n_layers, spec.seq_len, spec.n_kv_heads // n_slices,
             spec.head_size)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def rank_params_to_device(params: dict[str, Any]) -> dict[str, Any]:
    """Kernel-pack + fuse + device_put ONE rank's band tree (shapes are
    already local, so pack with tp=1 — identical layout to the band a real
    shard_params places on each chip: packing is band-local in both
    schemes, whichever dim the band slices).
    The rank's wq/wk/wv (and w1/w3) bands are fused into wqkv/w13 by the
    plain concat, which is safe HERE because the tree holds one rank's rows
    and nothing is cut after it: it is the band [q_r | k_r | v_r] that
    shard_params assembles for rank r when it fuses the whole model's tree
    rank-major (ops/linear.fuse_q40_layer_matmuls over n_tp ranks), so the
    sim and the mesh run the same 4 kernel calls a layer. The concat that is
    refused is the whole leaf's ([q; k; v] of the full model, cut in
    contiguous tp bands afterwards): shard_params raises on such a tree."""
    import jax
    import jax.numpy as jnp

    from ..ops.linear import fuse_q40_layer_matmuls, pack_q40_params

    params = fuse_q40_layer_matmuls(pack_q40_params(params, tp=1,
                                                    allow_nb_major=True))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.asarray(a)), params)


# ---- analytic ICI model ---------------------------------------------------

# Per-direction ICI bandwidth per v5e chip along a ring, and a per-collective
# launch/sync latency. 45 GB/s/link ~ public v5e figure (1600 Gbps aggregate
# across 4 links, 2 usable along a 1-D ring axis); latency ~1 us/hop is the
# conservative end of published ICI microbenchmarks. Both are overridable in
# project_full_system for sensitivity bands.
V5E_ICI_GBPS_PER_DIRECTION = 90.0  # 2 links x 45 GB/s, 1-D ring axis
ICI_COLLECTIVE_LATENCY_US = 1.0    # per all_gather launch+sync, per hop


def modeled_ici_ms(spec: TransformerSpec, n_slices: int,
                   scheme: str | None = None,
                   gbps: float = V5E_ICI_GBPS_PER_DIRECTION,
                   latency_us: float = ICI_COLLECTIVE_LATENCY_US,
                   ) -> tuple[float, float]:
    """(bandwidth_ms, latency_ms) per token for the scheme's collective
    schedule — the ONE formula behind project_full_system's ICI columns
    and any check of measured collective time, so the projection the bench
    prints and the band a measurement is held to cannot diverge. This is
    TOTAL collective time (what a profiler capture measures); the overlap
    scheme's hidden share is modeled separately
    (modeled_overlap_hidden_ms) and only project_full_system subtracts it.
    Hop accounting is per kind (comm_stats.collective_hops): a ring
    collective walks all S-1 hops per launch, a shift-by-k ppermute
    launch costs one."""
    from .comm_stats import collective_hops

    budget = tp_collective_budget(spec, n_slices, scheme)
    bw_ms = budget.moved_bytes / (gbps * 1e9) * 1e3
    lat_ms = sum(count * collective_hops(kind, n_slices) * latency_us
                 for kind, count, _ in budget.entries) / 1e3
    return bw_ms, lat_ms


def modeled_dcn_handoff_ms(spec: TransformerSpec, n_slices: int,
                           n_prompt_positions: int, page_size: int,
                           kv_quant: str = "f32",
                           gbps: float | None = None,
                           latency_us: float | None = None) -> float:
    """Modeled wall ms to ship one request's full prompt pages from the
    prefill pool to the decode pool over the DCN (ISSUE 14) — the
    handoff's whole cost, to weigh against the interference it removes
    (every colocated decode step that would have queued behind the
    prefill dispatch). Same shape as modeled_ici_ms: bytes from the one
    DCN budget (comm_stats.dcn_handoff_budget), bandwidth and fixed
    latency from planning constants (analysis/memory_model.DCN_GBPS) —
    overridable for sensitivity bands; measured cells stay honest N/A
    until a two-host session."""
    from ..analysis.memory_model import (DCN_GBPS,
                                         DCN_HANDOFF_LATENCY_US, GIB)
    from .comm_stats import dcn_handoff_budget

    budget = dcn_handoff_budget(spec, n_slices, n_prompt_positions,
                                page_size, kv_quant)
    gbps = DCN_GBPS if gbps is None else gbps
    latency_us = (DCN_HANDOFF_LATENCY_US if latency_us is None
                  else latency_us)
    return budget["bytes"] / (gbps * GIB) * 1e3 + latency_us / 1e3


def _weight_frac(spec: TransformerSpec, names) -> float:
    """Fraction of one decode step's weight-streaming bytes owed to the
    named per-layer matmuls — the weight-bound shard-time attribution the
    speculative model already leans on (batch-1 decode streams every
    weight once per token, so time shares track byte shares)."""
    per_layer = {name: d * n for name, (d, n) in spec.layer_matmul_shapes()}
    total = (spec.n_layers * sum(per_layer.values())
             + spec.vocab_size * spec.dim)  # + wcls
    return spec.n_layers * sum(per_layer[n] for n in names) / total


def modeled_overlap_hidden_ms(spec: TransformerSpec, n_slices: int,
                              shard_ms: float,
                              gbps: float = V5E_ICI_GBPS_PER_DIRECTION,
                              latency_us: float = ICI_COLLECTIVE_LATENCY_US,
                              ) -> float:
    """Collective time the overlap scheme hides behind compute (ISSUE 10).

    Two hideable terms, each min'd against the compute available to hide
    behind — per ring step the exposed cost is max(compute_chunk,
    ring_hop), i.e. the hop is free exactly while chunk compute covers it:

    * the ring hops (2L*(S-1) ppermutes): overlap the combines' chunked
      wo/w2 work — capacity = the wo+w2 share of the measured shard time
      (weight-streaming-bound decode: time shares track weight-byte
      shares), scaled by (S-1)/S (the first chunk has no hop in flight);
    * the deferred ffn gathers (L of the 2L+1 all_gathers): consumed at
      the top of layer N+1, so they hide behind everything up to the next
      ffn — capacity = the non-wo/w2 compute share.

    The attention gathers and the logits gather are consumed immediately
    and stay exposed — they are the ~0.29 ms/token floor the projected
    13b-tp8 row keeps (vs the fused scheme's 0.600). Returns 0 for
    schemes without a ring (callers guard) and for tp=1.
    """
    if n_slices <= 1:
        return 0.0
    budget = tp_collective_budget(spec, n_slices, "overlap")
    by_kind = {k: (c, b) for k, c, b in budget.entries}
    pp_count, pp_bytes = by_kind.get("ppermute", (0, 0))
    ag_count, ag_bytes = by_kind.get("all_gather", (0, 0))
    ring_ms = (pp_bytes / (gbps * 1e9) * 1e3
               + pp_count * latency_us / 1e3)
    # the deferred (ffn) gathers are L of the 2L+1; charge them their
    # launch latency + a proportional bytes share
    L = spec.n_layers
    defer_frac = L / max(ag_count, 1)
    defer_ms = (ag_bytes / (gbps * 1e9) * 1e3 * defer_frac
                + L * (n_slices - 1) * latency_us / 1e3)
    combine_ms = shard_ms * _weight_frac(spec, ("wo", "w2"))
    other_ms = max(shard_ms - combine_ms, 0.0)
    s = n_slices
    hidden = (min(ring_ms, combine_ms * (s - 1) / s)
              + min(defer_ms, other_ms))
    return hidden


def expected_accepted_span(alpha: float, k: int) -> float:
    """Expected tokens emitted per K-query verify dispatch at per-draft
    accept rate ``alpha``: the bonus/corrected token always lands, and
    draft j (1-indexed) lands iff drafts 1..j all match — E = sum_{j=0}^{
    k-1} alpha^j = (1 - alpha^k)/(1 - alpha), the Leviathan et al. 2023
    expected-walk length for a window of k-1 drafts + 1 scored token."""
    if not 0.0 <= alpha <= 1.0:
        raise ValueError(f"accept rate alpha={alpha} outside [0, 1]")
    if k < 1:
        raise ValueError(f"verify window k={k} must be >= 1")
    return float(sum(alpha ** j for j in range(k)))


@dataclasses.dataclass(frozen=True)
class SpeculativeProjection:
    """Modeled ms/accepted-token of a K-query verify dispatch (ISSUE 7).

    Per dispatch: shard compute is charged UNCHANGED — batch-1 decode is
    weight-streaming-bound, and the K query rows reuse the same weight
    traffic (the standard speculative-decoding economics; the CPU rank-sim
    cannot measure the real K-row cost, so PARITY.md's measured cells stay
    N/A pending a TPU session) — the ICI bandwidth term scales by K (every
    collective moves K activation rows, comm_stats t_len), and the
    per-collective LATENCY term is paid ONCE: the 1.13 ms/token floor of
    BENCH_r05 divides by the expected accepted span."""
    k: int                   # verify window (1 current + k-1 drafts)
    alpha: float             # modeled per-draft accept rate
    expected_tokens: float   # E[emitted/dispatch] = (1-a^k)/(1-a)
    dispatch_ms: float       # shard_ms + k*bw_ms + lat_ms
    ms_per_accepted_token: float
    baseline_ms_per_token: float  # the spec-off projection (total_ms)

    @property
    def speedup(self) -> float:
        return self.baseline_ms_per_token / self.ms_per_accepted_token


@dataclasses.dataclass(frozen=True)
class MixedProjection:
    """Modeled economics of one token-budget MIXED dispatch (ISSUE 18).

    Batch-1 accounting, like SpeculativeProjection: per dispatch the
    stream emits ONE decode token and a prefill slice advances by
    ``budget - 1`` prompt positions, all through one fused forward. Shard
    compute is charged weight-bound-unchanged (the budget rows reuse the
    decode step's weight traffic — same economics as the K-query verify),
    the ICI bandwidth term scales by the budget (comm_stats t_len), and
    the per-collective latency floor is paid ONCE for the whole window.
    The alternative — a separate chunk-prefill dispatch of the same
    ``budget - 1`` tokens — pays shard compute and the latency floor a
    SECOND time and stalls the decode stream behind it for a full
    dispatch. ``prefill_speedup`` (separate / piggybacked marginal cost)
    is the modeled half of the attainment gap tools/loadcheck.py
    --budget measures empirically."""
    budget: int              # tokens per dispatch (--dispatch-tokens)
    slice_tokens: int        # budget - 1 piggybacked prefill positions
    dispatch_ms: float       # shard_ms + budget*bw_ms + lat_ms - hidden
    # marginal cost of the piggybacked slice: what the dispatch costs
    # BEYOND the decode step it was making anyway, per slice token
    prefill_ms_per_token: float
    # the same slice as its own chunk-prefill dispatch, per token
    separate_prefill_ms_per_token: float
    baseline_ms_per_token: float  # the plain decode projection (total_ms)

    @property
    def prefill_speedup(self) -> float:
        """Separate-dispatch vs piggybacked marginal slice cost (> 1
        whenever shard compute or the latency floor is non-zero)."""
        return (self.separate_prefill_ms_per_token
                / self.prefill_ms_per_token)


@dataclasses.dataclass(frozen=True)
class FullSystemProjection:
    """Measured shard compute + modeled ICI = projected full-system ms/token,
    with the per-layer collective budget itemized (VERDICT r1 #1) and the
    per-device HBM verdict (analysis/memory_model.py) alongside — a
    projection for a config that cannot FIT is advertising a number no
    machine can serve."""
    shard_ms: float          # measured: one rank's program on the real chip
    ici_bandwidth_ms: float  # modeled: bytes over ring bandwidth
    ici_latency_ms: float    # modeled: per-collective launch/sync
    n_slices: int
    gather_bytes_per_chip: int
    n_collectives: int
    # per-device HBM footprint vs the budget table (closed-form components;
    # shardcheck's traced activation peak refines these by a few MB only)
    hbm_per_device_gib: float = 0.0
    hbm_headroom_gib: float = 0.0
    hbm_fits: bool = True
    # overlap scheme only: modeled collective time hidden behind compute
    # (modeled_overlap_hidden_ms — the max(compute_chunk, ring_hop) term);
    # 0 for ref/fused, whose projection stays the conservative no-overlap
    # straight sum
    ici_hidden_ms: float = 0.0
    scheme: str = ""

    @property
    def total_ms(self) -> float:
        # conservative straight sum for serialized schemes; the overlap
        # scheme subtracts its modeled hidden share (never below the
        # compute floor: hidden is capped by the ICI total by construction)
        return (self.shard_ms + self.ici_bandwidth_ms + self.ici_latency_ms
                - self.ici_hidden_ms)

    def speculative(self, k: int, alpha: float) -> SpeculativeProjection:
        """The speculative term (ISSUE 7): modeled ms/accepted-token when
        each dispatch verifies k positions at per-draft accept rate
        ``alpha``. Composes this projection's own components — bandwidth
        scales by k (comm_stats t_len), latency is paid once per dispatch,
        shard compute is charged weight-bound-unchanged (see
        SpeculativeProjection) — so the bench's speculative rows and the
        headline projection cannot drift apart."""
        e = expected_accepted_span(alpha, k)
        dispatch_ms = (self.shard_ms + k * self.ici_bandwidth_ms
                       + self.ici_latency_ms - self.ici_hidden_ms)
        return SpeculativeProjection(
            k=k, alpha=alpha, expected_tokens=round(e, 3),
            dispatch_ms=round(dispatch_ms, 3),
            ms_per_accepted_token=round(dispatch_ms / e, 3),
            baseline_ms_per_token=round(self.total_ms, 3))

    def mixed(self, budget: int) -> MixedProjection:
        """The token-budget term (ISSUE 18): modeled dispatch cost when
        every decode step also carries a ``budget - 1``-token prefill
        slice. Composes this projection's own components — bandwidth
        scales by the budget (comm_stats t_len), latency is paid once
        per dispatch, shard compute is charged weight-bound-unchanged —
        so the loadcheck --budget gate and the headline projection lean
        on ONE accounting. The marginal slice cost is the dispatch's
        excess over the decode step the stream was paying anyway; the
        separate-dispatch comparison re-charges shard compute and the
        latency floor for a standalone chunk of the same size."""
        if budget < 2:
            raise ValueError(f"mixed budget={budget} must be >= 2 "
                             f"(1 decode token + a non-empty slice)")
        slice_tokens = budget - 1
        dispatch_ms = (self.shard_ms + budget * self.ici_bandwidth_ms
                       + self.ici_latency_ms - self.ici_hidden_ms)
        marginal_ms = (dispatch_ms - self.total_ms) / slice_tokens
        separate_ms = (self.shard_ms + slice_tokens * self.ici_bandwidth_ms
                       + self.ici_latency_ms
                       - self.ici_hidden_ms) / slice_tokens
        return MixedProjection(
            budget=budget, slice_tokens=slice_tokens,
            dispatch_ms=round(dispatch_ms, 3),
            prefill_ms_per_token=round(marginal_ms, 6),
            separate_prefill_ms_per_token=round(separate_ms, 6),
            baseline_ms_per_token=round(self.total_ms, 3))


def project_full_system(spec: TransformerSpec, n_slices: int,
                        shard_ms: float,
                        gbps: float = V5E_ICI_GBPS_PER_DIRECTION,
                        latency_us: float = ICI_COLLECTIVE_LATENCY_US,
                        scheme: str | None = None) -> FullSystemProjection:
    """Combine a measured rank time with the analytic collective budget.

    Byte counts and the collective count come from ONE source of truth,
    comm_stats.tp_collective_budget for the active (or given) ``scheme`` —
    the same accounting the runtime prints, the J001 contract pins to the
    traced program, and (under Q80 buffers) the same int8+f16 payload the
    real gathers carry. Ring accounting: an all_gather of per-shard size b
    moves (S-1)*b per chip over full-duplex links; a psum moves
    2*(S-1)/S of its payload and is charged as ONE collective launch (its
    reduce and gather phases pipeline on the counter-rotating rings, and
    the launch/sync overhead this latency term models — dominant 13:1 over
    bandwidth at 13b-tp8 — is paid per issued collective). That per-launch
    count is what the fused scheme halves: 2L+1 vs the ref scheme's 4L+1
    under f32 buffers (budget.n_collectives; under the Q80 wire the fused
    combine decomposes into scatter+gather pairs and the count returns to
    4L+1 with the packed payload preserved).
    """
    from ..analysis.memory_model import GIB, device_footprint

    scheme = scheme or tp_scheme()
    budget = tp_collective_budget(spec, n_slices, scheme)
    n_coll = budget.n_collectives
    bw_ms, lat_ms = modeled_ici_ms(spec, n_slices, scheme, gbps, latency_us)
    hidden_ms = 0.0
    if scheme == "overlap":
        # the overlap term (ISSUE 10): ring hops and deferred ffn gathers
        # hide behind compute — per step max(compute_chunk, ring_hop)
        # replaces compute + collective. Capped by the collective total so
        # total_ms can never dip below the measured compute floor.
        hidden_ms = min(
            modeled_overlap_hidden_ms(spec, n_slices, shard_ms, gbps,
                                      latency_us),
            bw_ms + lat_ms)
    mem = device_footprint(spec, n_slices, scheme)
    return FullSystemProjection(shard_ms, bw_ms, lat_ms, n_slices,
                                budget.moved_bytes, n_coll,
                                hbm_per_device_gib=round(
                                    mem.total_bytes / GIB, 3),
                                hbm_headroom_gib=round(
                                    mem.headroom_bytes / GIB, 3),
                                hbm_fits=mem.fits,
                                ici_hidden_ms=round(hidden_ms, 6),
                                scheme=scheme)
