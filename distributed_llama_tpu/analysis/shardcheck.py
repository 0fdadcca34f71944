"""shardcheck: static sharding & HBM-footprint verifier for the tp grid.

Third analysis head, beside the AST lint (rules.py) and the jaxpr
contracts (jaxpr_contracts.py). For every config in the declared support
matrix — model in {7B, 13B, 70B} x tp in {1,2,4,8} x scheme in
{ref, fused, overlap} x weights in {Q40, F16}, 72 configs — it proves,
statically, on CPU, with zero weight bytes materialized:

  HBM     the per-device footprint (analysis/memory_model.py: weight
          shards, replicated tensors, KV cache at max sequence, traced
          activation peak, collective staging) fits the device budget with
          headroom, and the verdict AGREES with the declared matrix — a
          config that stops fitting fails loudly, and a config that starts
          fitting flags the matrix as stale. Megatron budgets memory this
          way before a job starts; vLLM rejects un-servable configs before
          serving — this is the same gate for our grid, where an OOM or a
          silent full replication on an 8-chip 70B run is the most
          expensive bug class we can hit.
  J004    the traced program's per-operand sharding (shard_map in_names)
          equals parallel/tp.py's declared contract
          (tp.expected_shard_names), and no matmul-weight operand rides
          replicated on a tp>1 mesh (an accidental everywhere-copy /
          all-gather of weight bytes).
  J005    no weight-scale int->f32 materialization outside the registered
          dequant sites (ops/dequant_sites.py) — a rogue dequant is an 8x
          HBM transient the memory model does not account for.
  J006    shapes shard uniformly: ragged head/vocab/block bands would give
          every rank a different program (one compile per rank) — reported
          as findings instead of a mid-load traceback.

Traces ride ``jax.make_jaxpr`` over abstract trees (ShapeDtypeStruct
leaves), so even the 70B grid verifies in seconds. Run under
JAX_PLATFORMS=cpu with an 8-device virtual mesh (the CLI forces it, like
the contract head); ``tools/shardcheck.py`` emits the machine-readable
JSON report that PARITY.md's footprint table is generated from.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from ..parallel.comm_stats import SCHEMES
from .memory_model import (GIB, MemoryReport, device_footprint,
                           live_interval_peak, sub_jaxprs)

# avals at or above this many bytes count as "weight-shaped" for the J004
# replication hazard and the J005 rogue-dequant detector (activation
# vectors at decode shapes sit orders of magnitude below it)
WEIGHT_BYTES_THRESHOLD = 1 << 18

MODELS = ("7b", "13b", "70b")
WEIGHT_TYPES = ("q40", "f16")

# The declared support matrix: per (model, weights) x tp, does the config
# fit a v5e chip (16 GiB, 10% headroom reserve)? Derived from the closed-
# form footprint and pinned here so MODEL DRIFT IS LOUD: if the memory
# model (or a spec dim) changes a verdict, shardcheck fails until this
# table is consciously updated. The scheme does not move a verdict (both
# schemes shard every matmul 1/tp; only the ~KB staging term differs).
_EXPECT_FITS = {
    ("7b", "q40"): {1: True, 2: True, 4: True, 8: True},
    ("7b", "f16"): {1: False, 2: True, 4: True, 8: True},
    ("13b", "q40"): {1: True, 2: True, 4: True, 8: True},
    ("13b", "f16"): {1: False, 2: True, 4: True, 8: True},
    ("70b", "q40"): {1: False, 2: False, 4: True, 8: True},
    ("70b", "f16"): {1: False, 2: False, 4: False, 8: False},
}


@dataclasses.dataclass(frozen=True)
class MatrixEntry:
    model: str
    tp: int
    scheme: str
    wtype: str
    expect_fits: bool
    # KV page quantization column (ISSUE 11): 'f32' prices the contiguous
    # max-seq KV stripe (the historical verdicts); 'q8' prices the paged
    # pool at the engine's default page count in the Q80 codes+deltas
    # layout (memory_model.kv_position_bytes) — a SMALLER KV term, so a
    # config can only gain headroom, never lose it, and the declared
    # verdict must still agree (an undeclared/stale q8 verdict fails
    # exactly like the PR 4 stale-matrix case).
    kv_quant: str = "f32"

    @property
    def label(self) -> str:
        base = f"{self.model}-tp{self.tp}-{self.scheme}-{self.wtype}"
        return base if self.kv_quant == "f32" else f"{base}-{self.kv_quant}"


SUPPORT_MATRIX = tuple(
    MatrixEntry(m, tp, s, w, _EXPECT_FITS[(m, w)][tp])
    for m in MODELS for tp in (1, 2, 4, 8)
    for s in SCHEMES for w in WEIGHT_TYPES) + tuple(
    # the q8 KV-quant column: the serving codec (q40 weights) across the
    # tp grid under the fused scheme (KV pricing is scheme-invariant;
    # one scheme keeps the matrix's trace cost flat). q8 KV only SHRINKS
    # the footprint, and none of the q40 verdicts sits within one KV
    # stripe of its budget edge, so the verdict column matches f32 —
    # pinned here so a memory-model edit that flips one fails loudly.
    MatrixEntry(m, tp, "fused", "q40", _EXPECT_FITS[(m, "q40")][tp],
                kv_quant="q8")
    for m in MODELS for tp in (1, 2, 4, 8))


@dataclasses.dataclass(frozen=True)
class ShardFinding:
    rule: str     # J004 | J005 | J006 | HBM-BUDGET | KV-PAGED | TRACE
    config: str
    detail: str

    def render(self) -> str:
        return f"shardcheck: {self.config} FAIL {self.rule}: {self.detail}"


@dataclasses.dataclass(frozen=True)
class ConfigResult:
    config: str
    expect_fits: bool | None
    report: MemoryReport | None
    findings: tuple
    kv_quant: str = "f32"  # the matrix entry's KV-quant column, verbatim

    @property
    def ok(self) -> bool:
        return not self.findings


def model_spec(model: str, wtype: str):
    from ..models import synth
    from ..ops.quants import FloatType

    factory = {"7b": synth.llama2_7b_spec, "13b": synth.llama2_13b_spec,
               "70b": synth.llama2_70b_spec}[model]
    ft = {"q40": FloatType.Q40, "f16": FloatType.F16,
          "f32": FloatType.F32}[wtype]
    return factory(weights_float_type=ft)


def abstract_model_params(spec):
    """The param tree as avals for the spec's weights_float_type — Q40
    leaves as codec-layout (qs, d16) pairs, dense leaves as f16/f32. Built
    under eval_shape, so nothing is materialized at any scale."""
    import jax
    import jax.numpy as jnp

    from ..io.loader import Q40Weight
    from ..models.synth import _build_tree
    from ..ops.quants import QK, FloatType

    ft = spec.weights_float_type

    def t(*shape):
        return jnp.zeros(shape, jnp.float32)

    def mm(*shape):
        if ft == FloatType.Q40:
            *lead, d, n = shape
            return Q40Weight(jnp.zeros((*lead, d, n // QK, 16), jnp.uint8),
                             jnp.zeros((*lead, d, n // QK), jnp.float16))
        dt = jnp.float16 if ft == FloatType.F16 else jnp.float32
        return jnp.zeros(shape, dt)

    return jax.eval_shape(lambda: _build_tree(spec, t, mm))


# -- tracing ----------------------------------------------------------------


def _find_shard_map(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            return eqn
        for sub in sub_jaxprs(eqn):  # incl. tuple-valued cond branches
            found = _find_shard_map(sub)
            if found is not None:
                return found
    return None


def trace_tp_forward(spec, tp: int, scheme: str, forward_builder=None):
    """make_jaxpr the real tp entry point (or a test-supplied builder of
    the same signature) over abstract params/cache/token avals. Returns
    (closed_jaxpr, abstract_params_tree)."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import init_cache
    from ..parallel import make_mesh, make_sharded_forward

    if len(jax.devices()) < tp:
        raise RuntimeError(
            f"needs {tp} devices, have {len(jax.devices())} — set "
            f"--xla_force_host_platform_device_count (the CLI does)")
    mesh = make_mesh(tp=tp, devices=jax.devices()[:tp])
    builder = forward_builder or make_sharded_forward
    fwd = builder(spec, mesh, scheme)
    params = abstract_model_params(spec)
    cache = jax.eval_shape(lambda: init_cache(spec, jnp.float32))
    tokens = jax.ShapeDtypeStruct((1,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    closed = jax.make_jaxpr(fwd)(params, cache, tokens, pos)
    return closed, params


def mutant_replicated_forward(replicate=("wcls",)):
    """A forward builder that OVERRIDES the named weights' partition spec
    to fully replicated — the seeded J004 fixture (guards the checker
    against rot; tests/test_shardcheck_repo.py). Only weights whose
    replication is shape-silent downstream (e.g. wcls: the widened logits
    gather has no later consumer) stay traceable."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel import tp as tp_mod
    from ..parallel.tp import _shard_map

    def build(spec, mesh, scheme):
        n_slices = mesh.shape["tp"]
        local_step = tp_mod.make_local_step(spec, n_slices, 1, scheme=scheme)

        def wrap(params, cache, tokens, pos):
            specs = tp_mod.param_specs(params, scheme)
            for name in replicate:
                specs[name] = P()  # fully replicated: the seeded hazard
            in_specs = (specs, tp_mod.CACHE_SPEC, P(), P())
            fn = _shard_map(local_step, mesh=mesh, in_specs=in_specs,
                            out_specs=(P(), tp_mod.CACHE_SPEC))
            return fn(params, cache, tokens, pos)

        return jax.jit(wrap, donate_argnums=1)

    build.replicated = tuple(replicate)
    return build


# -- the contract checks ----------------------------------------------------


def _user_frames(eqn):
    from jax._src import source_info_util

    return list(source_info_util.user_frames(eqn.source_info.traceback))


def _dequant_site_filter():
    from ..ops.dequant_sites import frames_allowed

    def allowed(eqn) -> bool:
        try:
            return frames_allowed(_user_frames(eqn))
        except Exception:  # noqa: BLE001 - source info is best-effort
            return False

    return allowed


def check_traced_sharding(closed_jaxpr, params, scheme: str, tp: int,
                          config: str, expected=None) -> list[ShardFinding]:
    """J004: shard_map's recorded in_names vs tp.expected_shard_names, plus
    the replication hazard — a matmul-weight operand with no 'tp' axis on a
    tp>1 mesh is an everywhere-copy the memory model never budgeted.
    ``expected`` overrides the declared rows (mutation self-tests)."""
    from ..parallel import tp as tp_mod

    sm = _find_shard_map(closed_jaxpr.jaxpr)
    if sm is None:
        return [ShardFinding("J004", config,
                             "no shard_map eqn in the traced forward — "
                             "jaxpr structure changed?")]
    rows = expected if expected is not None else \
        tp_mod.expected_shard_names(params, scheme)
    # the shard_map eqn records one PartitionSpec per operand, hoisted
    # consts first
    in_names = [tp_mod.spec_axis_names(spec)
                for spec in sm.params["in_specs"]]
    if len(in_names) < len(rows):
        return [ShardFinding("J004", config,
                             f"{len(in_names)} traced operands < "
                             f"{len(rows)} declared leaves")]
    tail_names = in_names[-len(rows):]
    tail_vars = sm.invars[-len(rows):]
    matmul_keys = tp_mod.LAYER_KEYS[2:] + ("wcls",)  # wq..w3 + classifier
    findings = []
    # operands BEFORE the declared leaves are consts jax hoisted out of the
    # body (closed-over values). They carry no declared spec and ride
    # replicated — fine for iota/rope tables, but a weight-sized hoisted
    # const is the silent-full-replication hazard J004 exists to catch
    n_consts = len(in_names) - len(rows)
    for var, names in zip(sm.invars[:n_consts], in_names[:n_consts]):
        aval = getattr(var, "aval", None)
        if aval is None or any("tp" in ax for ax in dict(names).values()):
            continue
        if tp > 1 and aval.size * aval.dtype.itemsize \
                >= WEIGHT_BYTES_THRESHOLD:
            findings.append(ShardFinding(
                "J004", config,
                f"const hoisted into shard_map: weight-shaped closed-over "
                f"value ({tuple(aval.shape)} {aval.dtype}) is REPLICATED "
                f"on a tp={tp} mesh — pass it through the params tree with "
                f"a partition spec"))
    for (name, want), got, var in zip(rows, tail_names, tail_vars):
        got = {int(k): tuple(v) for k, v in dict(got).items()}
        want = {int(k): tuple(v) for k, v in want.items()}
        if got != want:
            findings.append(ShardFinding(
                "J004", config,
                f"{name}: traced sharding {got} != declared {want} "
                f"(tp.py param_specs drifted from the program)"))
            continue
        is_matmul = any(f"'{k}'" in name for k in matmul_keys)
        aval = getattr(var, "aval", None)
        big = aval is not None and aval.size * aval.dtype.itemsize \
            >= WEIGHT_BYTES_THRESHOLD
        sharded_over_tp = any("tp" in axes for axes in got.values())
        if tp > 1 and is_matmul and big and not sharded_over_tp:
            findings.append(ShardFinding(
                "J004", config,
                f"{name}: weight-shaped operand "
                f"({tuple(aval.shape)} {aval.dtype}) is REPLICATED on a "
                f"tp={tp} mesh — every chip pays full bytes (accidental "
                f"all-gather)"))
    return findings


def check_dequant_sites(closed_jaxpr, config: str,
                        threshold: int = WEIGHT_BYTES_THRESHOLD
                        ) -> list[ShardFinding]:
    """J005: every weight-scale int->float materialization must descend
    from a registered dequant site (ops/dequant_sites.py)."""
    from ..ops.dequant_sites import frames_allowed
    from .jaxpr_contracts import walk_eqns

    int_names = {"uint8", "int8", "int4", "uint4"}
    findings = []
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        iv, ov = eqn.invars[0].aval, eqn.outvars[0].aval
        if ov.dtype.name not in ("float32", "bfloat16"):
            continue
        if iv.dtype.name not in int_names:
            continue
        if ov.size * ov.dtype.itemsize < threshold:
            continue
        try:
            frames = _user_frames(eqn)
        except Exception:  # noqa: BLE001 - no source info, cannot attribute
            frames = []
        if frames_allowed(frames):
            continue
        where = (f"{frames[0].file_name.rsplit('/', 1)[-1]}:"
                 f"{frames[0].function_name}" if frames else "<unknown>")
        findings.append(ShardFinding(
            "J005", config,
            f"{tuple(iv.shape)} {iv.dtype} -> {ov.dtype} materialization "
            f"at {where}, outside the registered dequant sites "
            f"(ops/dequant_sites.py)"))
    return findings


def check_uniform_shards(spec, tp: int, scheme: str,
                         config: str) -> list[ShardFinding]:
    """J006: ragged shards force per-rank shapes, hence one compile per
    rank — the same constraints parallel/tp.validate_sharding raises on,
    reported as findings plus the Q40-block granularity of the fused
    scheme's input-sharded wo/w2."""
    from ..ops.quants import QK, FloatType

    findings = []

    def ragged(value, what):
        findings.append(ShardFinding(
            "J006", config,
            f"{what}={value} does not divide over tp={tp}: ranks get "
            f"ragged shards (distinct shapes => one compile per rank)"))

    for value, what in ((spec.n_heads, "n_heads"),
                        (spec.n_kv_heads, "n_kv_heads"),
                        (spec.hidden_dim, "hidden_dim"),
                        (spec.vocab_size, "vocab_size")):
        if value % tp:
            ragged(value, what)
    if scheme in ("fused", "overlap") \
            and spec.weights_float_type == FloatType.Q40:
        for value, what in ((spec.dim, "dim"),
                            (spec.hidden_dim, "hidden_dim")):
            if tp > 1 and value % tp == 0 and (value // tp) % QK:
                findings.append(ShardFinding(
                    "J006", config,
                    f"{scheme} scheme shards {what}={value} along the Q40 "
                    f"input-block axis: {value}/{tp} must be a "
                    f"{QK}-multiple"))
    if scheme == "overlap" and tp > 1 and spec.dim % tp:
        findings.append(ShardFinding(
            "J006", config,
            f"overlap scheme ring-chunks the residual width: "
            f"dim={spec.dim} does not divide over tp={tp}"))
    if spec.buffer_float_type == FloatType.Q80:
        for value, what in ((spec.dim, "dim"), (spec.hidden_dim,
                                                "hidden_dim")):
            if value % tp == 0 and (value // tp) % QK:
                findings.append(ShardFinding(
                    "J006", config,
                    f"Q80 buffers need {what}/tp to be a {QK}-multiple, "
                    f"got {value}/{tp}"))
    return findings


def check_kv_quant_pricing(spec, tp: int, config: str) -> list[ShardFinding]:
    """KV-QUANT: the q8 page-byte formula must price the Q80 wire layout
    EXACTLY — per position, kv_dim int8 codes + one f16 delta per 32-value
    block of the flattened shard-local row (34 bytes per 32 values) — and
    the equal-HBM page multiplier vs f32 must clear 2x (it is 32*4/34 ≈
    3.76x at f32 pages; the acceptance floor is the ~2x capacity claim).
    Recomputed here from first principles so a memory_model edit cannot
    silently drift the capacity math the engine and bench rely on."""
    from ..ops.quants import QK
    from .memory_model import (DEFAULT_PAGE_SIZE, default_kv_pages,
                               equal_hbm_kv_pages, kv_position_bytes)

    findings = []
    kv_loc = (spec.n_kv_heads // tp) * spec.head_size
    if kv_loc % QK:
        findings.append(ShardFinding(
            "KV-QUANT", config,
            f"shard-local kv width {kv_loc} does not divide into "
            f"{QK}-value Q80 blocks — q8 KV pages cannot run this config"))
        return findings
    want = 2 * spec.n_layers * (kv_loc + 2 * (kv_loc // QK))
    got = kv_position_bytes(spec, tp, kv_quant="q8")
    if got != want:
        findings.append(ShardFinding(
            "KV-QUANT", config,
            f"q8 position bytes {got} != {want} (Q80 codes+deltas) — the "
            f"memory_model q8 formula drifted from the wire layout"))
    pages = default_kv_pages(spec, 1, DEFAULT_PAGE_SIZE)
    q8_pages = equal_hbm_kv_pages(spec, tp, pages, DEFAULT_PAGE_SIZE)
    if q8_pages < 2 * pages:
        findings.append(ShardFinding(
            "KV-QUANT", config,
            f"equal-HBM q8 pool holds {q8_pages} pages for {pages} f32 "
            f"pages — below the 2x capacity floor the q8 column claims"))
    return findings


def check_paged_equivalence(spec, tp: int, config: str,
                            contiguous_bytes: int) -> list[ShardFinding]:
    """KV-PAGED: the paged pool at the engine's default sizing (one slot's
    worth of pages, scrap excluded) must charge EXACTLY the bytes of the
    contiguous max-seq stripe — the invariant that lets the support
    matrix's HBM verdicts carry over to paged engines unchanged, and that
    the --kv-pages oversubscription math rests on. Checked across the
    whole matrix so a drifting page-size default or a pool formula edit
    fails loudly (tests/test_shardcheck_repo.py mutation-tests it)."""
    from .memory_model import (DEFAULT_PAGE_SIZE, default_kv_pages,
                               kv_page_pool_bytes)

    findings = []
    ps = DEFAULT_PAGE_SIZE
    if spec.seq_len % ps:
        findings.append(ShardFinding(
            "KV-PAGED", config,
            f"seq_len={spec.seq_len} is not a multiple of the default "
            f"page size {ps} — paged engines cannot run this config"))
        return findings
    paged = kv_page_pool_bytes(spec, tp, default_kv_pages(spec, 1, ps), ps,
                               include_scrap=False)
    if paged != contiguous_bytes:
        findings.append(ShardFinding(
            "KV-PAGED", config,
            f"paged pool at default sizing charges {paged} B but the "
            f"contiguous stripe charges {contiguous_bytes} B — the "
            f"memory_model formulas drifted apart"))
    return findings


def check_tier_staging(spec, tp: int, config: str, report,
                       kv_quant: str, expect_fits: bool) -> list:
    """KV-TIER: price the tiering promotion staging buffer (the 2-page
    double-buffered upload target a tiered engine keeps device-side,
    ISSUE 12) in device_footprint and require that it (a) follows the
    page-byte formula exactly and (b) fits inside a fitting config's
    declared headroom — turning on --kv-host-pages/--kv-disk-dir must
    never flip a support-matrix verdict."""
    from .memory_model import (DEFAULT_PAGE_SIZE, device_footprint,
                               kv_page_bytes)

    findings = []
    staged = device_footprint(spec, tp, report.scheme, model=report.model,
                              kv_page_size=DEFAULT_PAGE_SIZE,
                              kv_quant=kv_quant, tier_staging_pages=2)
    want = 2 * kv_page_bytes(spec, tp, DEFAULT_PAGE_SIZE,
                             kv_quant=kv_quant)
    if staged.tier_staging_bytes != want:
        findings.append(ShardFinding(
            "KV-TIER", config,
            f"tier staging priced {staged.tier_staging_bytes} B != "
            f"{want} B (2 pages at the pool byte rate) — the "
            f"memory_model staging formula drifted"))
    if expect_fits and report.fits and not staged.fits:
        findings.append(ShardFinding(
            "KV-TIER", config,
            f"the 2-page tiering staging buffer "
            f"({staged.tier_staging_bytes / GIB:.3f} GiB) pushes this "
            f"fitting config over budget — tiering cannot be enabled "
            f"on it; shrink the page size or update the matrix"))
    return findings


def check_mixed_budget(spec, tp: int, config: str, report,
                       kv_quant: str, expect_fits: bool,
                       budget: int = 16) -> list:
    """MIXED-HBM: price the token-budget mixed dispatch (ISSUE 18) in
    device_footprint and require that (a) the activation/staging width
    follows the same t_len shape math as the K-query verify dispatch
    (pricing spec_k=budget and mixed_budget=budget must agree exactly —
    one formula, two knobs) and (b) a fitting config still fits with the
    default budget window enabled — turning on --dispatch-tokens must
    never flip a support-matrix verdict. Weights and KV are unchanged by
    construction; only the per-dispatch activation rows widen."""
    from .memory_model import DEFAULT_PAGE_SIZE, device_footprint

    findings = []
    mixed = device_footprint(spec, tp, report.scheme, model=report.model,
                             kv_page_size=DEFAULT_PAGE_SIZE,
                             kv_quant=kv_quant, mixed_budget=budget)
    twin = device_footprint(spec, tp, report.scheme, model=report.model,
                            kv_page_size=DEFAULT_PAGE_SIZE,
                            kv_quant=kv_quant, spec_k=budget)
    if mixed.total_bytes != twin.total_bytes:
        findings.append(ShardFinding(
            "MIXED-HBM", config,
            f"mixed_budget={budget} prices {mixed.total_bytes} B but "
            f"spec_k={budget} prices {twin.total_bytes} B — the two "
            f"t_len knobs drifted apart in memory_model"))
    if expect_fits and report.fits and not mixed.fits:
        findings.append(ShardFinding(
            "MIXED-HBM", config,
            f"the {budget}-token mixed dispatch window "
            f"({mixed.total_bytes / GIB:.3f} GiB) pushes this fitting "
            f"config over budget — --dispatch-tokens cannot be enabled "
            f"on it; shrink the budget or update the matrix"))
    return findings


# -- per-config driver ------------------------------------------------------


def check_config(entry: MatrixEntry, device: str = "v5e",
                 forward_builder=None, spec=None) -> ConfigResult:
    """Run every check for one matrix entry. Trace failures become TRACE
    findings (the CLI reports them and fails), not crashes. ``spec``
    overrides the model lookup (synth-model mutation self-tests)."""
    spec = spec if spec is not None else model_spec(entry.model, entry.wtype)
    config = entry.label
    kv_quant = getattr(entry, "kv_quant", "f32")
    if kv_quant not in ("f32", "q8"):
        return ConfigResult(config, entry.expect_fits, None, (ShardFinding(
            "KV-QUANT", config,
            f"unknown kv_quant {kv_quant!r} (expected f32|q8) — the "
            f"matrix declares a column the memory model cannot price"),
        ), kv_quant=kv_quant)
    if spec.n_experts:
        # tp.py refuses an expert spec; a check of its sharding has nothing
        # to trace, and a footprint that skipped the experts would be wrong
        from ..ops.linear import MOE_TP_REFUSAL

        raise ValueError(f"shardcheck {config}: {MOE_TP_REFUSAL}")
    if spec.retention:
        # likewise a retention spec: tp.py refuses it, and its memory is
        # states of fixed size (memory_model.state_slot_bytes), not pages
        from ..ops.retention import TP_REFUSAL

        raise ValueError(f"shardcheck {config}: {TP_REFUSAL}")
    if spec.hybrid or spec.ssd:
        # and a hybrid spec: slots of fixed size and one layer's pages
        from ..ops.mamba import TP_REFUSAL

        raise ValueError(f"shardcheck {config}: {TP_REFUSAL}")
    if spec.mixers:
        # and a mixer-kinds spec: rings and its full layers' pages
        from ..ops.linear import MIXERS_TP_REFUSAL

        raise ValueError(f"shardcheck {config}: {MIXERS_TP_REFUSAL}")
    findings = check_uniform_shards(spec, entry.tp, entry.scheme, config)
    act_bytes = None
    if not findings and kv_quant == "q8":
        # the q8 column prices KV only: its (spec, tp, scheme, wtype)
        # twin in the f32 matrix already traced this exact forward
        # (J004/J005 and the activation peak are kv-quant-invariant —
        # the trace carries no KV-quant dimension), so re-tracing 12
        # identical programs would just slow every --all run. The
        # footprint uses the analytic activation bound, which lands
        # within a few MB of the traced peak at decode shapes
        # (memory_model.activation_bytes_analytic).
        pass
    elif not findings:
        try:
            closed, params = trace_tp_forward(spec, entry.tp, entry.scheme,
                                              forward_builder)
            sm = _find_shard_map(closed.jaxpr)
            if sm is not None:
                act_bytes = live_interval_peak(
                    sm.params["jaxpr"], exclude_eqn=_dequant_site_filter())
            findings += check_traced_sharding(closed, params, entry.scheme,
                                              entry.tp, config)
            findings += check_dequant_sites(closed, config)
        except ValueError as e:
            # validate_sharding raises on the same ragged shapes J006
            # models — surface under the contract id, not as a crash
            findings.append(ShardFinding("J006", config,
                                         f"trace rejected the config: {e}"))
        except Exception as e:  # noqa: BLE001 - report, don't crash the run
            findings.append(ShardFinding(
                "TRACE", config, f"raised {type(e).__name__}: {e}"))
    if kv_quant == "q8":
        # the q8 column prices the paged pool at the ENGINE default page
        # count in the Q80 layout; the pricing check pins the formula and
        # the 2x equal-HBM capacity floor
        from .memory_model import DEFAULT_PAGE_SIZE

        report = device_footprint(spec, entry.tp, entry.scheme,
                                  model=entry.model,
                                  activation_bytes=act_bytes,
                                  device=device,
                                  kv_page_size=DEFAULT_PAGE_SIZE,
                                  kv_quant="q8")
        findings += check_kv_quant_pricing(spec, entry.tp, config)
    else:
        report = device_footprint(spec, entry.tp, entry.scheme,
                                  model=entry.model,
                                  activation_bytes=act_bytes,
                                  device=device)
        findings += check_paged_equivalence(spec, entry.tp, config,
                                            report.kv_cache_bytes)
    from .memory_model import DEFAULT_PAGE_SIZE

    if spec.seq_len % DEFAULT_PAGE_SIZE == 0:
        findings += check_tier_staging(spec, entry.tp, config, report,
                                       kv_quant, entry.expect_fits)
        findings += check_mixed_budget(spec, entry.tp, config, report,
                                       kv_quant, entry.expect_fits)
    if report.fits != entry.expect_fits:
        if entry.expect_fits:
            findings.append(ShardFinding(
                "HBM-BUDGET", config,
                f"declared to fit but total "
                f"{report.total_bytes / GIB:.2f} GiB exceeds the "
                f"{report.budget_bytes / GIB:.2f} GiB usable budget by "
                f"{-report.headroom_bytes / GIB:.2f} GiB"))
        else:
            findings.append(ShardFinding(
                "HBM-BUDGET", config,
                f"declared NOT to fit but total "
                f"{report.total_bytes / GIB:.2f} GiB now leaves "
                f"{report.headroom_bytes / GIB:.2f} GiB headroom — "
                f"update the support matrix"))
    return ConfigResult(config, entry.expect_fits, report, tuple(findings),
                        kv_quant=kv_quant)


def run_shardcheck(matrix=None, device: str = "v5e") -> list[ConfigResult]:
    return [check_config(e, device=device)
            for e in (matrix if matrix is not None else SUPPORT_MATRIX)]


def load_matrix(path) -> tuple[MatrixEntry, ...]:
    """A JSON support matrix override: a list of {model, tp, scheme,
    wtype, expect_fits} objects (tools/shardcheck --matrix; also the
    seeded-violation path of the CLI tests)."""
    raw = json.loads(Path(path).read_text(encoding="utf-8"))
    return tuple(MatrixEntry(e["model"], int(e["tp"]), e["scheme"],
                             e["wtype"], bool(e["expect_fits"]),
                             kv_quant=e.get("kv_quant", "f32"))
                 for e in raw)


def report_json(results: list[ConfigResult], device: str = "v5e") -> dict:
    """The machine-readable memory report (tools/shardcheck emits this;
    PARITY.md's footprint table is generated from it)."""
    return {
        "device": device,
        "n_configs": len(results),
        "n_violations": sum(not r.ok for r in results),
        "configs": [{
            "config": r.config,
            "kv_quant": r.kv_quant,
            "expect_fits": r.expect_fits,
            "ok": r.ok,
            "findings": [{"rule": f.rule, "detail": f.detail}
                         for f in r.findings],
            "report": r.report.as_json() if r.report else None,
        } for r in results],
    }
