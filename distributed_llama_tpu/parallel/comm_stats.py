"""Analytic per-token communication accounting — per TP scheme.

The reference's benchmark metric includes sent/received kB per token measured
by atomic socket counters (src/socket.cpp:114-123, printed at
tokenizer.cpp:381). On an ICI mesh the collectives are compiler-issued, so we
account analytically — both for OUR schemes (what actually crosses ICI per
chip) and for the REFERENCE's star topology (root-side S/R, which the README
tables publish) so runs can print comparable numbers.

Two tp collective schemes exist (selected by ``DLLAMA_TP_SCHEME``, see
``tp_scheme``); every per-token budget in this module is derived from ONE
budget function (``tp_collective_budget``) so the runtime print, the bench
projection, and the dlint J001 jaxpr contract all read the same numbers:

  ref      the reference's all-output-sliced MatmulSlice port: 4 all_gathers
           per layer + the logits gather (parallel/tp.py ref branch) — the
           bit-parity anchor against the reference binaries.
  fused    Megatron-style pairing (Shoeybi et al. 2019; Pope et al. 2022):
           wo/w2 are INPUT-dim sharded, so attention-out and ffn-out are
           row-parallel partial sums combined with ONE psum per block under
           f32 buffers (2 collectives/layer), or a psum_scatter + Q80-packed
           all_gather pair under Q80 buffers (the wire-quantization cut
           point is preserved on the gather half).
  overlap  the fused layout with each block combine RING-DECOMPOSED
           (Wang et al., ASPLOS '23 collective-matmul lineage): the psum /
           psum_scatter reduce half becomes tp-1 chunked ``ppermute`` hops
           (1 ICI hop each, schedulable concurrently with the combine's
           remaining chunk work) feeding a deterministic rank-order f32
           fold, followed by the SAME gather half as fused; the ffn
           combine's gather is double-buffered — issued at the bottom of
           layer N, consumed at the top of layer N+1 — so it too hides
           behind compute. Counts go UP (2(S-1) ppermutes + 2 gathers per
           layer) but almost all of the collective time is hideable; see
           shard_sim.project_full_system's overlap term.

Validated against the published tables (README.md:58-69) in
tests/test_comm_stats.py; pinned to the traced program in
tests/test_collective_pinning.py and analysis/jaxpr_contracts.py (J001).
"""

from __future__ import annotations

import dataclasses
import os

from ..models.spec import TransformerSpec
from ..ops.quants import FloatType, batch_bytes

SCHEMES = ("ref", "fused", "overlap")

# ICI hops one collective launch of each kind serializes on: a ppermute is
# one neighbor hop (shift-by-k permutes pipeline through the ring and the
# launch itself costs one hop of sync); every ring-collective walks the
# whole ring. The latency term of shard_sim.modeled_ici_ms multiplies the
# per-kind launch count by this hop count.
def collective_hops(kind: str, n_slices: int) -> int:
    return 1 if kind == "ppermute" else max(n_slices - 1, 1)


def tp_scheme() -> str:
    """The active tp collective scheme: DLLAMA_TP_SCHEME=ref|fused|overlap.

    Default ``fused`` — the fastest *serialized* policy (half the per-layer
    collective launches, the dominant term of the multi-chip latency
    budget; ISSUE 3 / BENCH_r05). ``overlap`` (ISSUE 10) ring-decomposes
    the fused combines so the remaining collectives hide behind compute —
    bitwise equal to ``fused``, modeled faster on real meshes, pending a
    TPU session to graduate to default. ``ref`` keeps the reference's
    4-gather MatmulSlice schedule and remains the bit-parity anchor
    against the reference binaries.
    """
    s = os.environ.get("DLLAMA_TP_SCHEME", "fused")
    if s not in SCHEMES:
        raise ValueError(f"DLLAMA_TP_SCHEME={s!r}: expected one of "
                         f"{'|'.join(SCHEMES)}")
    return s


def _vb(ftype: FloatType, n: int) -> int:
    """Wire bytes of an n-value vector in the buffer float type."""
    return batch_bytes(ftype, n)


@dataclasses.dataclass(frozen=True)
class CommStats:
    sent_bytes: int
    recv_bytes: int

    @property
    def total_kib(self) -> float:
        return (self.sent_bytes + self.recv_bytes) / 1024.0


@dataclasses.dataclass(frozen=True)
class CollectiveBudget:
    """The per-token tp collective schedule, aggregated by primitive kind.

    ``entries`` holds (kind, count, moved_bytes) per collective kind, where
    ``moved_bytes`` is the ring-accounted bytes each chip moves per token
    for ALL collectives of that kind (logits gather included). This is the
    ONE structure the analytic model exposes: the runtime byte counters,
    the bench ICI projection, and the J001 jaxpr contract all consume it —
    a collective added to the forward without a term here fails J001 (and
    dlint D006 flags the source site).
    """

    entries: tuple  # ((kind, count, moved_bytes), ...)

    @property
    def n_collectives(self) -> int:
        return sum(c for _, c, _ in self.entries)

    @property
    def moved_bytes(self) -> int:
        return sum(b for _, _, b in self.entries)

    def kind_counts(self) -> dict[str, int]:
        return {k: c for k, c, _ in self.entries}

    def bytes_by_kind(self) -> dict[str, int]:
        """kind -> moved bytes/chip/token — the per-kind join key the
        a measured census is reconciled on; same rows as
        ``entries``, keyed like ``kind_counts``."""
        return {k: b for k, _, b in self.entries}


def tp_collective_budget(spec: TransformerSpec, n_slices: int,
                         scheme: str | None = None,
                         t_len: int = 1) -> CollectiveBudget:
    """Per-chip/token collective schedule of the tp forward, per scheme.

    Ring accounting (S = n_slices, b = per-shard payload bytes):
      all_gather      moves (S-1)*b out of and into every chip;
      reduce_scatter  moves (S-1)*p/S for a full per-chip payload p;
      psum            moves 2*(S-1)*p/S (reduce-scatter + gather phases).
    A psum is charged as ONE collective: its two phases ride the counter-
    rotating rings of the full-duplex ICI links back to back, and the term
    the count feeds (per-collective launch/sync latency, see
    shard_sim.project_full_system) is paid once per issued collective —
    halving the launches is exactly the fused scheme's win.

    Under Q80 buffer mode the gather halves carry the REAL packed payload
    (int8 codes + f16 deltas, tp._wire_gather); reduce halves stay f32 —
    partial sums cannot ride the wire quantized without compounding each
    shard's rounding error into the total.

    ``t_len`` widens every activation payload to t_len query rows while
    the COUNTS stay the one-step schedule — the speculative K-query verify
    dispatch (models/llama.forward_batch_spec_paged / tp.
    make_sharded_verify): every cut moves a (t_len, width) block through
    the same per-layer collectives one decode step issues, so bytes scale
    by exactly t_len (the logits gather included) and launches do not.
    The token-budget MIXED dispatch (ISSUE 18, tp.make_sharded_mixed)
    reuses the same scaling with t_len = the dispatch token budget: decode
    rows plus one prefill slice fill a (budget, width) block per cut,
    paying the per-collective launch floor ONCE for the whole window —
    the analytic half of jaxpr_contracts.contract_mixed_collectives
    and shard_sim.FullSystemProjection.mixed.
    That launches-don't-scale property IS the speculative amortization
    (shard_sim.FullSystemProjection.speculative), and J001's verify
    census (analysis/jaxpr_contracts.contract_verify_collectives) pins
    the traced program to this scaling.
    """
    scheme = scheme or tp_scheme()
    if scheme not in SCHEMES:
        raise ValueError(f"unknown tp scheme {scheme!r}")
    if n_slices <= 1:
        return CollectiveBudget(())
    ft = spec.buffer_float_type
    s, L, t = n_slices, spec.n_layers, t_len
    logits_bytes = t * (s - 1) * _vb(FloatType.F32, spec.vocab_size // s)
    if scheme == "ref":
        per_layer = t * (s - 1) * (3 * _vb(ft, spec.dim // s)
                                   + _vb(ft, spec.hidden_dim // s))
        return CollectiveBudget(
            (("all_gather", 4 * L + 1, L * per_layer + logits_bytes),))
    if scheme == "overlap":
        # ring-decomposed fused combines: the reduce half of each of the
        # 2 per-layer combines is S-1 chunked ppermute hops (each moving
        # one f32 dim/S chunk — partial sums never ride the wire
        # quantized, same rule as the fused scatter half), and the gather
        # half is the SAME per-combine all_gather the fused Q80 path
        # issues (packed Q80 band under Q80 buffers; f32 band under f32 —
        # the decomposition of the fused psum). Per-chip ppermute bytes
        # equal the fused reduce_scatter's (S-1)/S of the payload exactly.
        pp_bytes = t * 2 * L * (s - 1) * (spec.dim // s) * 4
        band = (FloatType.Q80 if ft == FloatType.Q80 else FloatType.F32)
        ag_bytes = t * 2 * L * (s - 1) * _vb(band, spec.dim // s)
        return CollectiveBudget(
            (("ppermute", 2 * L * (s - 1), pp_bytes),
             ("all_gather", 2 * L + 1, ag_bytes + logits_bytes)))
    # fused: wo/w2 row-parallel — one combine per block, 2 blocks/layer,
    # both of width dim (attention out and ffn out are residual-stream
    # vectors; hidden_dim never crosses the wire in this scheme)
    if ft == FloatType.Q80:
        rs_bytes = t * 2 * L * (s - 1) * (spec.dim // s) * 4
        ag_bytes = t * 2 * L * (s - 1) * _vb(FloatType.Q80, spec.dim // s)
        return CollectiveBudget(
            (("reduce_scatter", 2 * L, rs_bytes),
             ("all_gather", 2 * L + 1, ag_bytes + logits_bytes)))
    psum_bytes = t * 2 * L * 2 * (s - 1) * (spec.dim // s) * 4
    return CollectiveBudget(
        (("psum", 2 * L, psum_bytes),
         ("all_gather", 1, logits_bytes)))


def collective_staging_bytes(spec: TransformerSpec, n_slices: int,
                             scheme: str | None = None,
                             t_len: int = 1) -> int:
    """Per-chip HBM transiently held by the largest in-flight collective.

    The footprint model (analysis/memory_model.py) charges collectives a
    double-buffer bound: the full output payload of the single largest
    collective in the schedule, twice (source shard staging + assembled
    output live at once). Derived from the SAME cut points as
    ``tp_collective_budget`` so the two cannot drift:

      ref    gathers of dim- and hidden-wide vectors (buffer float type on
             the wire) + the f32 logits gather;
      fused  f32 psum / psum_scatter payloads of dim width (partial sums
             never ride the wire quantized) + the f32 logits gather.

    ``t_len`` scales every payload — the activation-vector cuts AND the
    logits gather — for multi-query traffic: prefill chunks and the
    speculative K-query verify dispatch both assemble (t_len, width)
    blocks at each cut (decode is t_len=1). Zero when n_slices == 1 — no
    wire, no staging.
    """
    scheme = scheme or tp_scheme()
    if scheme not in SCHEMES:
        raise ValueError(f"unknown tp scheme {scheme!r}")
    if n_slices <= 1:
        return 0
    ft = spec.buffer_float_type
    logits = t_len * _vb(FloatType.F32, spec.vocab_size)
    if scheme == "ref":
        payloads = (t_len * _vb(ft, spec.dim),
                    t_len * _vb(ft, spec.hidden_dim), logits)
    else:
        # fused/overlap: the combine payload is the full residual-width f32
        # vector on the psum, the scatter+gather decomposition, and the
        # overlap ring's (S, T, dim/S) chunk-term stash alike
        payloads = (t_len * _vb(FloatType.F32, spec.dim), logits)
    base = 2 * max(payloads)
    if scheme == "overlap":
        # chunked-staging charge: the deferred ffn gather is double-
        # buffered — the layer-N output buffer is still live while layer
        # N+1's is being gathered — so the wire payload (packed Q80 band
        # concat under Q80 buffers, f32 vector under f32) is held twice
        # ON TOP of the in-flight-collective bound above.
        band = (FloatType.Q80 if ft == FloatType.Q80 else FloatType.F32)
        base += 2 * t_len * _vb(band, spec.dim)
    return base


def ici_all_gather_bytes(spec: TransformerSpec, n_slices: int,
                         scheme: str | None = None) -> CommStats:
    """Per-chip bytes/token of the active (or given) scheme's collectives.

    Historic name — under the fused scheme the bytes include psum /
    reduce_scatter traffic, not only gathers. Sent == received: every
    collective here is ring-symmetric.
    """
    moved = tp_collective_budget(spec, n_slices, scheme).moved_bytes
    return CommStats(moved, moved)


def sp_lse_bytes(spec: TransformerSpec, n_sp: int, n_tp: int = 1,
                 t_len: int = 1) -> CommStats:
    """Per-chip bytes/token of the sp flash-partial combine (ring.py).

    Per layer each chip all-reduces m and l ((T, heads_loc, 1) each) and o
    ((T, heads_loc, head_size)) across sp — a ring all-reduce moves
    ~2*(S-1)/S of the payload out of and into every chip.
    """
    if n_sp <= 1:
        return CommStats(0, 0)
    heads_loc = spec.n_heads // n_tp
    per_layer_vals = t_len * heads_loc * (2 + spec.head_size)
    payload = per_layer_vals * 4 * spec.n_layers
    moved = int(2 * payload * (n_sp - 1) / n_sp)
    return CommStats(moved, moved)


def dcn_page_bytes(spec: TransformerSpec, n_slices: int, page_size: int,
                   kv_quant: str = "f32",
                   cache_itemsize: int = 4) -> int:
    """Wire bytes of ONE shipped KV page (all layers, K+V, codes+deltas
    for q8) — identical to the disk tier's record for the same page
    (runtime/pagewire packs both), so the DCN budget and the tier model
    price the same bytes. Delegates to the one per-position byte model
    (analysis/memory_model.kv_position_bytes; lazy import — analysis
    already imports this module)."""
    from ..analysis.memory_model import kv_page_bytes

    return kv_page_bytes(spec, n_slices, page_size, cache_itemsize,
                         kv_quant)


def dcn_handoff_budget(spec: TransformerSpec, n_slices: int,
                       n_prompt_positions: int, page_size: int,
                       kv_quant: str = "f32",
                       cache_itemsize: int = 4) -> dict:
    """The per-request DCN budget of a prefill->decode handoff (ISSUE
    14): pages x wire bytes, priced per kv_quant. Only FULL prompt pages
    ship (the radix tree's sharing unit — a partial tail page is private
    to its request and re-derives via suffix prefill on the decode
    pool), so the page count is floor(prompt positions / page_size).
    ``skipped_positions`` is the suffix the decode pool re-prefills —
    the honest remainder the budget does NOT cover."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    pages = max(0, int(n_prompt_positions)) // page_size
    per_page = dcn_page_bytes(spec, n_slices, page_size, kv_quant,
                              cache_itemsize)
    return {
        "pages": pages,
        "page_bytes": per_page,
        "bytes": pages * per_page,
        "skipped_positions": max(0, int(n_prompt_positions))
        - pages * page_size,
        "kv_quant": kv_quant,
    }


def reference_star_bytes(spec: TransformerSpec, n_slices: int) -> CommStats:
    """Root-side S/R bytes/token of the reference's socket scheme.

    Per layer (transformer-tasks.cpp task table):
      send: 3 unit-buffer broadcasts of dim to each worker (syncRmsAtt,
            syncMultiheadAtt, syncRmfFfn) + the O(S^2) star all-gather of hb
            (syncFfnB: each worker receives the S-1 slices it lacks).
      recv: per worker slices of q,k,v (dim/S, kvDim/S, kvDim/S), wo out
            (dim/S), hb (hidden/S), w2 out (dim/S).
    """
    if n_slices <= 1:
        return CommStats(0, 0)
    ft = spec.buffer_float_type
    s = n_slices
    w = s - 1  # workers
    send_layer = (3 * w * _vb(ft, spec.dim)
                  + w * (s - 1) * _vb(ft, spec.hidden_dim // s))
    recv_layer = w * (_vb(ft, spec.dim // s) + 2 * _vb(ft, spec.kv_dim // s)
                      + _vb(ft, spec.dim // s) + _vb(ft, spec.hidden_dim // s)
                      + _vb(ft, spec.dim // s))
    return CommStats(spec.n_layers * send_layer, spec.n_layers * recv_layer)
