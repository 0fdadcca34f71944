"""Closed-form per-device HBM accounting for the sharded serving path.

Megatron-LM budgets per-device memory analytically before a job ever
touches an accelerator; vLLM refuses to serve a config that cannot fit.
This module is that arithmetic for our (model, tp, scheme, dtype) grid —
every term hand-checkable against the spec dims:

  weights      Q40 shards resident in the Pallas kernel layout (16 B codes
               + 4 B f32 scale per 32-block — see io/loader.to_kernel_layout;
               the on-disk codec layout is 18 B/block, ``q40_codec_bytes``),
               f16/f32 shards at 2/4 B per value. Every matmul weight is
               sharded 1/tp in BOTH schemes (output bands everywhere in
               ref; wo/w2 flip to input bands in fused — same byte count).
  replicated   the f32 embedding table + rms norms every chip holds whole.
  kv_cache     2 (K and V) x L x B x S/sp x n_kv/tp x head_size planes.
  activations  live-interval peak of the traced rank program
               (``live_interval_peak``; analysis/shardcheck.py feeds it the
               shard_map body), or the closed-form vector bound
               (``activation_bytes_analytic``) on no-trace paths like the
               bench projection column.
  collectives  double-buffer staging for the largest in-flight collective
               (parallel/comm_stats.collective_staging_bytes — same cut
               points as the ICI byte budget).

The budget table is v5e-centric (16 GiB HBM/chip) with a 10% headroom
reserve for the XLA runtime, compiled executables, and fragmentation; a
config "fits" when the component total stays inside the usable fraction.
``analysis/shardcheck.py`` gates the declared support matrix on these
verdicts; ``parallel/shard_sim.project_full_system`` and bench.py surface
the same fits/headroom numbers next to every multi-chip projection.
"""

from __future__ import annotations

import dataclasses
import math

from ..models.spec import TransformerSpec
from ..ops.quants import QK, FloatType

GIB = 1024 ** 3

# Per-device HBM by accelerator. v5e is the measurement platform of record
# (BASELINE.json); add entries as new device kinds appear in bench rows.
DEVICE_HBM_BYTES = {"v5e": 16 * GIB}
# Fraction of HBM reserved for the XLA runtime/executables/fragmentation —
# the footprint must fit in (1 - headroom) * HBM.
HBM_HEADROOM_FRACTION = 0.10

Q40_KERNEL_BLOCK_BYTES = 16 + 4   # u8 nibble planes + f32 scale (resident)
Q40_CODEC_BLOCK_BYTES = 16 + 2    # u8 nibble planes + f16 delta (file/wire)


def usable_hbm_bytes(device: str = "v5e") -> int:
    return int(DEVICE_HBM_BYTES[device] * (1 - HBM_HEADROOM_FRACTION))


def q40_kernel_bytes(values: int) -> int:
    """Resident bytes of ``values`` Q40-quantized scalars in the Pallas
    kernel layout (f32 scales — io/loader.to_kernel_layout)."""
    return (values // QK) * Q40_KERNEL_BLOCK_BYTES


def q40_codec_bytes(values: int) -> int:
    """File/wire bytes of ``values`` Q40 scalars (f16 deltas)."""
    return (values // QK) * Q40_CODEC_BLOCK_BYTES


def weight_values_per_device(spec: TransformerSpec, n_slices: int) -> int:
    """Matmul-weight scalars per device: all 7 per-layer matmuls plus wcls
    shard exactly 1/tp of their values in both schemes (tp.py). An expert
    spec counts every expert's three tensors and holds them on ONE chip
    (tp.py refuses it across ranks, and so does this model)."""
    if spec.n_experts and n_slices > 1:
        from ..ops.linear import MOE_TP_REFUSAL

        raise ValueError(MOE_TP_REFUSAL)
    if spec.retention and n_slices > 1:
        from ..ops.retention import TP_REFUSAL

        raise ValueError(TP_REFUSAL)
    if spec.slotted and not spec.latent:
        _refuse_sharded_slotted(spec, n_slices)
        # each layer its kind's tensors (a held expert's counted once
        # each), and the classifier (a hybrid spec's: the tied copy)
        return spec.vocab_size * spec.dim + sum(
            e[2][0] * e[2][1] for _, _, entries in spec.layer_plans()
            for e in entries if e[0] == "mm")
    per_layer = sum(c * d * n for (d, n), c in spec.matmul_shape_counts())
    if spec.latent:
        # two kinds of layer, the experts HELD (not the router's width),
        # and ``wkv_b`` apart: the device holds it as float32
        # (``latent_absorbed_bytes``), not in the weights' float type
        dense = sum(d * n for _, (d, n) in spec.dense_layer_matmul_shapes())
        la = spec.latent
        kvb = spec.latent_groups * (la.nope_dim + la.v_dim) * la.kv_rank
        return (spec.n_expert_layers * per_layer
                + spec.n_dense_layers * dense - spec.n_layers * kvb
                + spec.vocab_size * spec.dim)
    total = spec.n_layers * per_layer + spec.vocab_size * spec.dim
    return total // n_slices


def _refuse_sharded_slotted(spec, n_slices: int, n_sp: int = 1) -> None:
    """A hybrid or a mixer-kinds spec over more than one chip: refused by
    name, as parallel/tp.py refuses it."""
    if n_slices > 1 or n_sp > 1:
        from ..ops.linear import MIXERS_TP_REFUSAL
        from ..ops.mamba import TP_REFUSAL

        raise ValueError(MIXERS_TP_REFUSAL if spec.mixers else TP_REFUSAL)


def latent_absorbed_bytes(spec: TransformerSpec) -> int:
    """A latent spec's ``w_uk`` / ``w_uv`` (models/latent.py): ``wkv_b`` of
    every layer dequantized to float32 for the absorbed products."""
    if not spec.latent:
        return 0
    la = spec.latent
    return (4 * spec.n_layers * spec.latent_groups
            * (la.nope_dim + la.v_dim) * la.kv_rank)


def hyper_bytes(spec: TransformerSpec) -> int:
    """The float32 tensors of a residual path of several streams
    (``TransformerSpec.hyper_shapes``: two projections of (2 n + n^2, n
    dim), their gates and biases, a layer; the projection's 2 n + n^2 rows
    lie in whole 8-row tiles on the device, which is what they are at
    n = 4)."""
    return 4 * spec.n_layers * sum(math.prod(shape)
                                   for _, shape in spec.hyper_shapes())


def weights_device_bytes(spec: TransformerSpec, n_slices: int) -> int:
    """Resident bytes of this device's matmul-weight shards."""
    values = weight_values_per_device(spec, n_slices)
    ft = spec.weights_float_type
    if ft == FloatType.Q40:
        return q40_kernel_bytes(values)
    if ft == FloatType.F16:
        return 2 * values
    if ft == FloatType.F32:
        return 4 * values
    raise ValueError(f"no weight byte model for {ft!r}")


def replicated_device_bytes(spec: TransformerSpec) -> int:
    """Bytes every chip holds whole regardless of tp: the f32 embedding
    table and the rms norm vectors (2 per layer + final)."""
    embedding = spec.vocab_size * spec.dim * 4
    if spec.slotted:    # every float32 leaf of every layer, the final norm
        import math

        return embedding + 4 * ((2 if spec.hybrid else 1) * spec.dim + sum(
            math.prod(e[2]) for _, _, entries in spec.layer_plans()
            for e in entries if e[0] == "f32"))
    norms = (spec.n_layers * sum(n for _, n in spec.layer_norm_shapes())
             + spec.dim) * 4
    expert_layers = spec.n_expert_layers if spec.latent else spec.n_layers
    routers = expert_layers * spec.n_experts * (
        spec.dim + spec.router.bias) * 4                       # f32, whole
    gates = (spec.n_layers * spec.n_kv_heads * spec.dim * 4
             if spec.retention else 0)                         # f32, whole
    return (embedding + norms + routers + gates + latent_absorbed_bytes(spec)
            + hyper_bytes(spec))


def state_slot_bytes(spec: TransformerSpec) -> int:
    """A retention spec's per-sequence memory on its one chip: the state
    of every layer (ops/retention.state_bytes: float32, fixed whatever the
    context). What ``kv_position_bytes`` x positions is to a softmax spec."""
    from ..ops.retention import state_bytes

    if spec.kda:
        # a kda spec's slot: each KDA layer's state (heads, head_dim,
        # head_dim) and its conv rows, float32 (models/kda.py); its latent
        # layers' plane is pages (``kv_position_bytes``)
        kd = spec.kda
        return 4 * spec.latent.count("kda") * (
            kd.width * kd.head_dim + (kd.d_conv - 1) * 3 * kd.width)
    if spec.latent and spec.slotted:
        # a latent spec's slot: each sliding layer's ring of latent rows,
        # float32, in whole lane tiles (models/latent.plane_width)
        la = spec.latent
        return (4 * la.count("sliding") * la.window
                * -(-la.width // 128) * 128)
    if spec.mixers:
        # a mixer-kinds spec's slot: each sliding layer's ring of K and V,
        # float32 (models/laguna.py); its full layers' K / V are pages
        mx = spec.mixers
        return (4 * mx.count("sliding") * mx.window
                * spec.kv_cached("sliding"))
    if spec.ssd:
        # an ssd spec's slot: each Mamba-2 layer's state (heads, head_dim,
        # d_state) and its conv rows, float32 (models/nemotron.py); its
        # attention layers' K / V are pages (``kv_position_bytes``)
        sd = spec.ssd
        return 4 * sd.count("mamba2") * (
            sd.d_inner * sd.d_state + (sd.d_conv - 1) * sd.conv_dim)
    if spec.hybrid:
        # a hybrid spec's slot: each Mamba layer's conv inputs and state,
        # each window layer's ring of K and V, float32 (models/sambay.py);
        # its full layer's K / V are pages (``kv_position_bytes``)
        hy = spec.hybrid
        return 4 * (hy.count("mamba") * hy.d_inner * (
            hy.d_state + hy.d_conv - 1)
            + hy.count("swa") * hy.window * 2 * spec.kv_dim)
    if not spec.retention:
        raise ValueError("state_slot_bytes prices a retention, a hybrid or "
                         "a mixer-kinds spec's slot")
    return spec.n_layers * state_bytes(spec.n_kv_heads, spec.head_size)


def kv_cache_device_bytes(spec: TransformerSpec, n_slices: int,
                          batch: int = 1, n_sp: int = 1,
                          cache_itemsize: int = 4) -> int:
    """K+V planes at max sequence: kv heads shard over tp, sequence chunks
    over sp (tp.CACHE_SPEC / CACHE_SPEC_BATCH). A retention spec holds
    ``batch`` states instead (one chip only), of no length."""
    if spec.retention:
        if n_slices > 1 or n_sp > 1:
            from ..ops.retention import TP_REFUSAL

            raise ValueError(TP_REFUSAL)
        return batch * state_slot_bytes(spec)
    if spec.latent:     # its full layers' plane, and its rings where any
        return batch * (spec.seq_len * kv_position_bytes(
            spec, n_slices, cache_itemsize)
            + (state_slot_bytes(spec) if spec.slotted else 0))
    if spec.slotted:    # ``batch`` slots, and the full layers' K / V
        _refuse_sharded_slotted(spec, n_slices, n_sp)
        return batch * (state_slot_bytes(spec) + spec.seq_len
                        * kv_position_bytes(spec, 1, cache_itemsize))
    return (2 * spec.n_layers * batch * (spec.seq_len // n_sp)
            * (spec.n_kv_heads // n_slices) * spec.head_size
            * cache_itemsize)


# The page size the documented tables/benches use (positions per page).
# Small enough that a chat-sized request strands < page_size positions,
# large enough that page-table gathers stay coarse; the engine knob
# (--kv-page-size) accepts any divisor of seq_len.
DEFAULT_PAGE_SIZE = 16


def default_kv_pages(spec: TransformerSpec, batch: int,
                     page_size: int = DEFAULT_PAGE_SIZE) -> int:
    """The engine's default pool sizing: byte-parity with the contiguous
    ``batch``-slot cache (runtime/continuous.ContinuousEngine)."""
    return batch * (spec.seq_len // page_size)


def kv_position_bytes(spec: TransformerSpec, n_slices: int,
                      cache_itemsize: int = 4,
                      kv_quant: str = "f32") -> int:
    """K+V bytes of ONE sequence position on one device (all layers).

    f32/bf16: ``cache_itemsize`` per value. q8 (ISSUE 11): the Q80 wire
    layout from ops/quants.py — 1 int8 code per value plus one f16 delta
    per 32-value block of the flattened (n_kv/tp, hs) row
    (models/llama.PagedKVQ8), i.e. 34 bytes per 32 values: a 32/34 ≈
    3.76x cut vs f32 (1.88x vs bf16). Exact, not approximate — the
    equal-HBM page multiplier the engine/bench use is derived from this
    number, and the shardcheck KV-quant column pins it."""
    if spec.latent:
        # ONE plane of latent.width values a position, stored in whole
        # 128-lane tiles (models/latent.plane_width: 576 lies in 640)
        if n_slices > 1 or kv_quant != "f32":
            raise ValueError("a latent-attention spec's plane is float32 on "
                             "one chip (runtime/continuous.latent_refusals)")
        return (spec.latent_kinds.count("full")
                * -(-spec.latent.width // 128) * 128 * cache_itemsize)
    if spec.slotted:    # the full layers' K and V alone (a hybrid spec
        #                     has ONE), float32, one chip
        _refuse_sharded_slotted(spec, n_slices)
        if kv_quant != "f32":
            raise ValueError("a hybrid or mixer-kinds spec's pages are "
                             "float32 (runtime/continuous.cache_refusals)")
        if spec.mixers:     # the kind's KV heads, K's and V's head sizes
            return (spec.mixers.count("full") * spec.kv_cached("full")
                    * cache_itemsize)
        if spec.ssd:        # K and V of every attention layer
            return (spec.ssd.count("full") * 2 * spec.kv_dim
                    * cache_itemsize)
        return 2 * spec.kv_dim * cache_itemsize
    kv_dim = (spec.n_kv_heads // n_slices) * spec.head_size
    if kv_quant == "q8":
        per = kv_dim + 2 * (kv_dim // QK)   # int8 codes + f16 deltas
    elif kv_quant == "f32":
        per = kv_dim * cache_itemsize
    else:
        raise ValueError(f"no KV byte model for kv_quant={kv_quant!r}")
    return 2 * spec.n_layers * per


def equal_hbm_kv_pages(spec: TransformerSpec, n_slices: int,
                       n_pages_f32: int,
                       page_size: int = DEFAULT_PAGE_SIZE,
                       cache_itemsize: int = 4) -> int:
    """How many q8 pages the HBM of ``n_pages_f32`` f32 pages holds — the
    capacity lever the continuous_bench equal-HBM section drives (~3.76x
    at f32 baseline, ~1.88x at bf16)."""
    f32_bytes = n_pages_f32 * page_size * kv_position_bytes(
        spec, n_slices, cache_itemsize, "f32")
    page_q8 = page_size * kv_position_bytes(spec, n_slices, kv_quant="q8")
    return f32_bytes // page_q8


def kv_page_pool_bytes(spec: TransformerSpec, n_slices: int, n_pages: int,
                       page_size: int = DEFAULT_PAGE_SIZE,
                       cache_itemsize: int = 4,
                       include_scrap: bool = True,
                       kv_quant: str = "f32") -> int:
    """Paged-pool K+V bytes: 2 x L x pages x page_size x n_kv/tp x hs
    (per-position pricing via ``kv_position_bytes`` — q8 pages charge the
    Q80 codes + f16 block deltas exactly).

    The paged lever: ``n_pages`` is a FREE knob — contiguous slots charge
    ``slots * seq_len`` positions whether requests use them or not, the
    pool charges exactly what it holds. At the engine's default sizing
    (default_kv_pages) the two layouts are byte-identical per position
    (shardcheck pins that equivalence across the whole support matrix);
    undersized pools trade eviction pressure for concurrency at equal
    HBM (the continuous_bench columns). ``include_scrap`` charges the
    reserved dead-write page 0 the engine actually allocates
    (models/llama.init_cache_paged gets n_pages + 1)."""
    pages = n_pages + (1 if include_scrap else 0)
    return pages * page_size * kv_position_bytes(spec, n_slices,
                                                 cache_itemsize, kv_quant)


# -- KV tier hierarchy (ISSUE 12) -------------------------------------------

# Modeled transfer rates for the tier hierarchy's promotion/demotion
# paths. Host<->device rides PCIe (a v5e host link — the TPU's non-ICI
# attach point); disk is a modest NVMe read stream. Like the ICI numbers
# in shard_sim these are MODELED planning constants, not measurements —
# PARITY.md carries the honest-N/A measured column.
HOST_DEVICE_GBPS = 16.0
DISK_READ_GBPS = 1.5
# per-page fixed cost of a promotion apply (dispatch + descriptor work)
TIER_PROMOTE_LATENCY_US = 30.0


def kv_page_bytes(spec: TransformerSpec, n_slices: int,
                  page_size: int = DEFAULT_PAGE_SIZE,
                  cache_itemsize: int = 4, kv_quant: str = "f32") -> int:
    """Bytes of ONE physical page's planes on one device (all layers,
    K+V, codes+deltas for q8) — the unit every tier transfer moves."""
    return page_size * kv_position_bytes(spec, n_slices, cache_itemsize,
                                         kv_quant)


def kv_tier_model(spec: TransformerSpec, n_slices: int,
                  hbm_pages: int, host_pages: int = 0,
                  disk_bytes: int = 0,
                  page_size: int = DEFAULT_PAGE_SIZE,
                  cache_itemsize: int = 4,
                  kv_quant: str = "f32") -> dict:
    """Per-tier capacity + bandwidth model of the KV hierarchy: bytes
    held per tier, pages the budgets buy, and the modeled per-page
    promotion/demotion cost — the numbers that justify spilling instead
    of recomputing. The comparison that matters: promoting one page
    costs ~page_bytes/PCIe-bw, while re-PREFILLING its page_size
    positions costs a full forward pass over them — at 7B shapes the
    upload is microseconds against milliseconds of recompute, priced
    per kv_quant (q8 pages move ~3.76x cheaper than f32). Budgets are
    per-device for HBM (kv heads shard over tp) and per-HOST for the
    host/disk tiers (one host feeds its local devices)."""
    pb = kv_page_bytes(spec, n_slices, page_size, cache_itemsize, kv_quant)
    host_ms = pb / (HOST_DEVICE_GBPS * GIB) * 1e3
    disk_ms = pb / (DISK_READ_GBPS * GIB) * 1e3
    lat_ms = TIER_PROMOTE_LATENCY_US / 1e3
    return {
        "page_size": page_size,
        "kv_quant": kv_quant,
        "page_bytes": pb,
        "hbm": {"pages": hbm_pages, "bytes": hbm_pages * pb},
        "host": {"pages": host_pages, "bytes": host_pages * pb},
        "disk": {"bytes": disk_bytes,
                 "pages": (disk_bytes // pb) if disk_bytes else 0},
        # promotion = upload (+ disk read below host); demotion mirrors
        # the upload cost (device->host readback at the same link rate)
        "promote_host_ms_per_page": round(host_ms + lat_ms, 6),
        "promote_disk_ms_per_page": round(host_ms + disk_ms + lat_ms, 6),
        "demote_ms_per_page": round(host_ms + lat_ms, 6),
    }


# -- prefill/decode disaggregation (ISSUE 14) -------------------------------

# Modeled DCN bandwidth between the prefill and decode pools: a 25 GbE
# data-center link's useful throughput. A planning constant like the
# PCIe/disk numbers above — PARITY.md's measured column stays honest N/A
# until a hardware session.
DCN_GBPS = 3.0
# per-handoff fixed cost (connection reuse + framing + the admit RPC)
DCN_HANDOFF_LATENCY_US = 200.0


def disagg_pool_model(spec: TransformerSpec, n_slices: int,
                      prefill_pages: int, decode_pages: int,
                      page_size: int = DEFAULT_PAGE_SIZE,
                      cache_itemsize: int = 4, kv_quant: str = "f32",
                      prompt_positions: int = 512) -> dict:
    """Per-pool capacity + handoff-bandwidth model of the two-pool
    topology: page-pool bytes per pool, and the modeled cost of shipping
    one request's full prompt pages over the DCN — the number that
    justifies disaggregation's trade. The comparison that matters: a
    handoff moves pages/request x page_bytes at DCN_GBPS (milliseconds),
    while the interference it removes is every decode step that would
    have queued behind the prefill dispatch on a colocated chip. Priced
    per kv_quant: q8 pages ship ~3.76x cheaper than f32 — the PR 11 wire
    cut compounds straight into the DCN budget."""
    from ..parallel.comm_stats import dcn_handoff_budget

    pb = kv_page_bytes(spec, n_slices, page_size, cache_itemsize, kv_quant)
    budget = dcn_handoff_budget(spec, n_slices, prompt_positions,
                                page_size, kv_quant, cache_itemsize)
    ship_ms = budget["bytes"] / (DCN_GBPS * GIB) * 1e3 \
        + DCN_HANDOFF_LATENCY_US / 1e3
    return {
        "page_size": page_size,
        "kv_quant": kv_quant,
        "page_bytes": pb,
        "prefill": {"pages": prefill_pages, "bytes": prefill_pages * pb},
        "decode": {"pages": decode_pages, "bytes": decode_pages * pb},
        "handoff": {**budget,
                    "dcn_gbps": DCN_GBPS,
                    "ship_ms_per_page": round(
                        pb / (DCN_GBPS * GIB) * 1e3, 6),
                    "ship_ms_per_request": round(ship_ms, 6)},
    }


def activation_bytes_analytic(spec: TransformerSpec, n_slices: int,
                              t_len: int = 1) -> int:
    """No-trace activation bound for projection columns: the residual
    stream + norm buffer + local qkv/swiglu bands + full and local logits,
    all f32. The traced live-interval peak (shardcheck) supersedes this
    where a jaxpr is available; both land within a few MB of each other at
    decode shapes — activations are a rounding error next to weights/KV."""
    s = n_slices
    # a spec with n residual streams carries X and writes X' (n dim each)
    # where the others carry x and write x + y
    streams = 2 * (spec.hyper.streams - 1) * spec.dim if spec.hyper else 0
    vecs = (4 * spec.dim + streams            # x, xb, gathered block outs
            + 2 * (spec.hidden_dim // s)      # swiglu bands
            + (spec.dim + 2 * spec.kv_dim) // s   # local q/k/v
            + spec.vocab_size + spec.vocab_size // s)  # logits full + band
    return 4 * t_len * vecs


# -- live-interval walk -----------------------------------------------------


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def sub_jaxprs(eqn):
    """Inner jaxprs of an eqn (scan/while/cond/pjit bodies, tuple-valued
    branch params included), unwrapped to raw Jaxpr — the ONE recursion
    helper for both the live walk and shardcheck's eqn searches."""
    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            # unwrap ClosedJaxpr (which also proxies .eqns) to its Jaxpr
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                out.append(inner)
            elif hasattr(item, "eqns") and hasattr(item, "outvars"):
                out.append(item)
    return out


def live_interval_peak(jaxpr, exclude_eqn=None) -> int:
    """Peak bytes of simultaneously-live *intermediate* values in ``jaxpr``.

    A linear walk over the eqns in program order: each eqn allocates its
    outputs, and a value is freed after its last use — the classic live-
    interval model of a straight-line allocator. What the model charges:

    * jaxpr invars/constvars are NOT counted — weights, cache, and tokens
      are accounted by the closed-form components (and per-layer weight
      slices of a scan over top-level invars are a CPU-fallback artifact:
      the serving path reads stacked Q40 weights in place via scalar
      prefetch, ops/linear.StackedQ40);
    * a ``dynamic_update_slice`` whose operand is dead after the eqn (or is
      an untracked input — the donated-cache carry) updates in place, and a
      scan/while carry output whose carry INIT is untracked or dies at the
      loop aliases that init: zero new bytes — mirroring XLA's donation and
      loop-carry aliasing on the real device (the decode cache rides the
      scan carry donated; charging it again would double-count the KV
      component);
    * control-flow eqns recurse: a scan's peak is its body's peak (plus the
      per-iteration slices of any *intermediate* scanned xs), branches take
      the max, and the inner peak lands on top of everything live outside;
    * ``exclude_eqn(eqn)`` -> True drops that eqn's outputs from the model —
      shardcheck passes the dequant-site filter so registered XLA-fallback
      dequant transients (absent on the Pallas path) don't read as serving
      HBM.
    """
    def is_var(v) -> bool:
        # core.Var (hashable, has aval); Literals carry .val and are not
        # hashable — they hold no buffer and are skipped
        return hasattr(v, "aval") and not hasattr(v, "val")

    eqns = list(jaxpr.eqns)
    last_use: dict = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if is_var(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if is_var(v):
            last_use[v] = len(eqns)

    live: dict = {}       # var -> counted bytes
    live_total = 0
    peak = 0
    for i, eqn in enumerate(eqns):
        prim = eqn.primitive.name
        excluded = exclude_eqn is not None and exclude_eqn(eqn)

        def freeable(v, i=i):
            # an operand that is untracked (jaxpr input: donated/accounted
            # elsewhere) or dead after this eqn can be updated in place
            return not is_var(v) or v not in live \
                or last_use.get(v, -1) == i

        alias_out: set = set()
        if prim == "dynamic_update_slice" and eqn.invars \
                and freeable(eqn.invars[0]):
            alias_out.add(id(eqn.outvars[0]))
        elif prim == "scan":
            nc = eqn.params.get("num_consts", 0)
            ncar = eqn.params.get("num_carry", 0)
            for k in range(min(ncar, len(eqn.outvars))):
                if freeable(eqn.invars[nc + k]):
                    alias_out.add(id(eqn.outvars[k]))
        elif prim == "while":
            n_carry = len(eqn.outvars)
            inits = eqn.invars[len(eqn.invars) - n_carry:]
            for k, init in enumerate(inits):
                if freeable(init):
                    alias_out.add(id(eqn.outvars[k]))

        inner = 0
        subs = sub_jaxprs(eqn)
        if subs:
            inner = max(live_interval_peak(s, exclude_eqn) for s in subs)
            if prim == "scan":
                n_xs = (len(eqn.invars) - eqn.params.get("num_consts", 0)
                        - eqn.params.get("num_carry", 0))
                length = max(int(eqn.params.get("length", 1)), 1)
                for v in eqn.invars[len(eqn.invars) - n_xs:]:
                    if is_var(v) and v in live:
                        # intermediate xs: per-iteration slice copy
                        inner += live[v] // length

        counted = []
        if not excluded:
            counted = [v for v in eqn.outvars
                       if is_var(v) and id(v) not in alias_out]
        out_bytes = sum(_aval_bytes(v.aval) for v in counted)
        peak = max(peak, live_total + out_bytes + inner)
        for v in counted:
            live[v] = _aval_bytes(v.aval)
            live_total += live[v]
        for v in eqn.invars + list(eqn.outvars):
            if is_var(v) and v in live and last_use.get(v, -1) <= i:
                live_total -= live.pop(v)
    return peak


# -- the assembled report ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MemoryReport:
    """Per-device HBM footprint of one (spec, tp, scheme) config."""

    model: str
    tp: int
    scheme: str
    weights_float_type: str
    weights_bytes: int
    replicated_bytes: int
    kv_cache_bytes: int
    activation_bytes: int
    collective_bytes: int
    budget_bytes: int
    # KV-tiering promotion staging (ISSUE 12): the double-buffered page
    # upload target (2 pages of planes) a tiered engine keeps device-side.
    # 0 (the default) for untiered configs — pinned totals unchanged.
    tier_staging_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        return (self.weights_bytes + self.replicated_bytes
                + self.kv_cache_bytes + self.activation_bytes
                + self.collective_bytes + self.tier_staging_bytes)

    @property
    def headroom_bytes(self) -> int:
        return self.budget_bytes - self.total_bytes

    @property
    def fits(self) -> bool:
        return self.headroom_bytes >= 0

    def as_json(self) -> dict:
        gib = {k: round(getattr(self, k) / GIB, 3)
               for k in ("weights_bytes", "replicated_bytes",
                         "kv_cache_bytes", "activation_bytes",
                         "collective_bytes")}
        if self.tier_staging_bytes:
            gib["tier_staging_bytes"] = round(
                self.tier_staging_bytes / GIB, 3)
        return {
            "model": self.model, "tp": self.tp, "scheme": self.scheme,
            "weights_float_type": self.weights_float_type,
            "components_gib": {k.replace("_bytes", ""): v
                               for k, v in gib.items()},
            "total_gib": round(self.total_bytes / GIB, 3),
            "budget_gib": round(self.budget_bytes / GIB, 3),
            "headroom_gib": round(self.headroom_bytes / GIB, 3),
            "fits": self.fits,
        }


def device_footprint(spec: TransformerSpec, n_slices: int, scheme: str,
                     model: str = "?", batch: int = 1,
                     activation_bytes: int | None = None,
                     device: str = "v5e", kv_page_size: int = 0,
                     kv_pages: int | None = None,
                     spec_k: int = 0, kv_quant: str = "f32",
                     tier_staging_pages: int = 0,
                     mixed_budget: int = 0) -> MemoryReport:
    """Assemble the per-device report; ``activation_bytes`` overrides the
    analytic bound with a traced live-interval peak when available.
    ``kv_page_size > 0`` charges KV as the paged pool (default pool =
    engine default: byte-parity with ``batch`` contiguous slots, plus the
    scrap page) instead of ``batch`` contiguous max-seq stripes.
    ``spec_k > 0`` charges activations and collective staging at the
    K-query verify width (the speculative dispatch runs batch * spec_k
    activation rows through every layer — ISSUE 7); weights and KV are
    unchanged, which is exactly why the verify dispatch is nearly free in
    HBM terms. ``kv_quant='q8'`` (paged only) prices the pool at the Q80
    codes+deltas byte rate (kv_position_bytes). ``tier_staging_pages``
    (ISSUE 12) charges the KV-tiering promotion staging buffer — the
    device-side upload target a tiered engine double-buffers (2 pages is
    the engine's shape) — priced at the pool's page byte rate.
    ``mixed_budget > 0`` (ISSUE 18) charges activations and collective
    staging at the token-budget dispatch width — the mixed forward runs
    batch * budget activation rows through every layer, same shape math
    as the verify window; mutually exclusive with ``spec_k`` (the engine
    rejects the pairing, so a report pricing both would describe a
    config that cannot exist)."""
    from ..parallel.comm_stats import collective_staging_bytes

    if spec_k and mixed_budget:
        raise ValueError("spec_k and mixed_budget are mutually exclusive "
                         "(the engine rejects --spec-k with "
                         "--dispatch-tokens; price one dispatch shape)")
    t_len = max(1, spec_k, mixed_budget)
    if kv_quant != "f32" and kv_page_size <= 0:
        raise ValueError(f"kv_quant={kv_quant!r} prices PAGE planes; "
                         f"pass kv_page_size > 0")
    if tier_staging_pages and kv_page_size <= 0:
        raise ValueError("tier_staging_pages prices PAGE planes; pass "
                         "kv_page_size > 0")
    if activation_bytes is None:
        activation_bytes = activation_bytes_analytic(spec, n_slices,
                                                     t_len=t_len)
    if spec.retention and (kv_page_size or kv_quant != "f32" or spec_k
                           or mixed_budget or tier_staging_pages):
        raise ValueError(
            "a retention spec's memory is `batch` states of fixed size: "
            "pages, q8 pages, the verify window, the mixed budget and the "
            "tier staging buffer do not apply (the engine refuses them)")
    if spec.slotted and (kv_quant != "f32" or spec_k or mixed_budget
                         or tier_staging_pages):
        raise ValueError(
            "a hybrid or mixer-kinds spec's memory is `batch` slots of "
            "fixed size and float32 pages of its full layers: q8 pages, the verify window, the "
            "mixed budget and the tier staging buffer do not apply (the "
            "engine refuses them)")
    if kv_page_size > 0:
        pages = (kv_pages if kv_pages is not None
                 else default_kv_pages(spec, batch, kv_page_size))
        kv_bytes = kv_page_pool_bytes(spec, n_slices, pages, kv_page_size,
                                      kv_quant=kv_quant)
        if spec.slotted:    # the slots beside the pool
            kv_bytes += batch * state_slot_bytes(spec)
    else:
        kv_bytes = kv_cache_device_bytes(spec, n_slices, batch=batch)
    return MemoryReport(
        model=model, tp=n_slices, scheme=scheme,
        weights_float_type=FloatType(spec.weights_float_type).name,
        weights_bytes=weights_device_bytes(spec, n_slices),
        replicated_bytes=replicated_device_bytes(spec),
        kv_cache_bytes=kv_bytes,
        activation_bytes=int(activation_bytes),
        collective_bytes=collective_staging_bytes(spec, n_slices, scheme,
                                                  t_len=t_len),
        budget_bytes=usable_hbm_bytes(device),
        tier_staging_bytes=(tier_staging_pages * kv_page_bytes(
            spec, n_slices, kv_page_size, kv_quant=kv_quant)
            if tier_staging_pages else 0))
