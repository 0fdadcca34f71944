"""Tier-1 repo gate for shardcheck (analysis/shardcheck.py).

Four layers of defense, mirroring the dlint gate's structure:

* the FULL declared support matrix (7B/13B/70B x tp 1-8 x ref/fused x
  Q40/F16) verifies clean — sharding == tp.py's contract, no rogue
  dequants, uniform shards, HBM verdicts match the declaration;
* the closed-form weight+KV footprints match INDEPENDENT hand
  calculations (raw spec dims, no memory_model helpers) to within 1%;
* mutation self-tests: a deliberately replicated weight reports J004, a
  KV-budget overshoot reports the budget failure, a rogue dequant reports
  J005, ragged heads report J006 — the checker itself cannot rot green;
* the dequant-site registry resolves to real functions, so a renamed
  sanctioned site fails here instead of silently allowing nothing.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from distributed_llama_tpu.analysis import shardcheck as sc
from distributed_llama_tpu.analysis.memory_model import (
    GIB, device_footprint, live_interval_peak)
from distributed_llama_tpu.models.synth import small_bench_spec
from distributed_llama_tpu.ops.quants import FloatType


@pytest.fixture(scope="module")
def matrix_results():
    return sc.run_shardcheck()


def test_full_support_matrix_is_clean(matrix_results):
    # 72 f32 configs + the 12-entry q8 KV-quant column (ISSUE 11)
    assert len(matrix_results) == len(sc.SUPPORT_MATRIX) == 84
    bad = [f.render() for r in matrix_results for f in r.findings]
    assert not bad, "\n".join(bad)


def test_matrix_covers_the_declared_grid():
    labels = {e.label for e in sc.SUPPORT_MATRIX}
    for m in ("7b", "13b", "70b"):
        for tp in (1, 2, 4, 8):
            for s in ("ref", "fused", "overlap"):
                for w in ("q40", "f16"):
                    assert f"{m}-tp{tp}-{s}-{w}" in labels
            # the q8 KV-quant column rides the serving codec (q40) under
            # the fused scheme across the whole tp grid
            assert f"{m}-tp{tp}-fused-q40-q8" in labels


# -- closed-form hand calculations (independent arithmetic) -----------------

# (dim, hidden, layers, heads, kv_heads, vocab, seq)
_DIMS = {"7b": (4096, 11008, 32, 32, 32, 32000, 2048),
         "13b": (5120, 13824, 40, 40, 40, 32000, 2048),
         "70b": (8192, 28672, 80, 64, 8, 32000, 2048)}


def _hand_weight_values(model: str) -> int:
    d, h, L, nh, nkv, v, _ = _DIMS[model]
    kv = d * nkv // nh
    per_layer = d * d + kv * d + kv * d + d * d + h * d + d * h + h * d
    return L * per_layer + v * d  # + wcls


@pytest.mark.parametrize("model", ("7b", "13b", "70b"))
@pytest.mark.parametrize("tp", (1, 2, 4, 8))
@pytest.mark.parametrize("wtype", ("q40", "f16"))
def test_weight_and_kv_footprints_match_hand_calc(matrix_results, model,
                                                  tp, wtype):
    d, h, L, nh, nkv, v, seq = _DIMS[model]
    values = _hand_weight_values(model) // tp
    # Q40 kernel layout: 16 B codes + 4 B f32 scale per 32 values
    want_w = values // 32 * 20 if wtype == "q40" else 2 * values
    want_kv = 2 * L * seq * (nkv // tp) * (d // nh) * 4
    label = f"{model}-tp{tp}-fused-{wtype}"
    rep = next(r.report for r in matrix_results if r.config == label)
    assert abs(rep.weights_bytes - want_w) <= 0.01 * want_w
    assert abs(rep.kv_cache_bytes - want_kv) <= 0.01 * want_kv
    # replicated embedding: vocab x dim f32, norms are noise next to it
    assert abs(rep.replicated_bytes - v * d * 4) <= 0.01 * (v * d * 4) \
        + (2 * L + 1) * d * 4


def test_headline_70b_tp8_q40_fits_with_headroom(matrix_results):
    rep = next(r.report for r in matrix_results
               if r.config == "70b-tp8-fused-q40")
    assert rep.fits
    # ~5.0 GiB weights + ~1 GiB embedding + small KV: well under 14.4 GiB
    assert 5.5 * GIB < rep.total_bytes < 8 * GIB
    assert rep.headroom_bytes > 6 * GIB


def test_70b_never_fits_unsharded(matrix_results):
    for r in matrix_results:
        if r.config.startswith("70b-tp1"):
            assert not r.report.fits


# -- mutation self-tests (the checker must catch what it claims to) ---------


def test_mutant_replicated_weight_reports_j004():
    entry = sc.MatrixEntry("13b", 4, "fused", "q40", True)
    res = sc.check_config(
        entry, forward_builder=sc.mutant_replicated_forward(("wcls",)))
    rules = {f.rule for f in res.findings}
    assert "J004" in rules, res.findings
    assert any("wcls" in f.detail for f in res.findings)


def test_replication_hazard_branch_names_the_all_gather():
    # drive the hazard branch directly: expected rows AGREE with the
    # mutant (no drift), so the finding must come from the replicated-
    # weight detector itself
    from distributed_llama_tpu.parallel import tp as tp_mod

    spec = sc.model_spec("13b", "q40")
    closed, params = sc.trace_tp_forward(
        spec, 4, "fused", sc.mutant_replicated_forward(("wcls",)))
    rows = tp_mod.expected_shard_names(params, "fused")
    mutated = [(n, {} if "'wcls'" in n else d) for n, d in rows]
    findings = sc.check_traced_sharding(closed, params, "fused", 4,
                                        "mutant", expected=mutated)
    assert findings and all(f.rule == "J004" for f in findings)
    assert any("REPLICATED" in f.detail for f in findings)


def test_mutant_kv_overshoot_reports_budget_failure():
    # a synth model whose KV cache alone busts the 14.4 GiB usable budget
    spec = small_bench_spec(seq_len=1 << 21,
                            weights_float_type=FloatType.Q40)
    entry = sc.MatrixEntry("synth", 1, "ref", "q40", True)
    res = sc.check_config(entry, spec=spec)
    rules = {f.rule for f in res.findings}
    assert "HBM-BUDGET" in rules, res.findings
    assert not res.report.fits
    assert res.report.kv_cache_bytes > res.report.budget_bytes


def test_declared_unfit_config_that_fits_flags_matrix_drift():
    entry = sc.MatrixEntry("7b", 8, "fused", "q40", False)  # wrong decl
    res = sc.check_config(entry)
    assert any(f.rule == "HBM-BUDGET" and "update the support matrix"
               in f.detail for f in res.findings)


def test_rogue_dequant_reports_j005():
    def rogue(qs, d16):
        lo = (qs & 0xF).astype(jnp.int8) - jnp.int8(8)
        hi = (qs >> 4).astype(jnp.int8) - jnp.int8(8)
        codes = jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)
        return (codes * d16.astype(jnp.float32)[..., None]).sum()

    qs = jax.ShapeDtypeStruct((4096, 128, 16), jnp.uint8)
    d16 = jax.ShapeDtypeStruct((4096, 128), jnp.float16)
    closed = jax.make_jaxpr(rogue)(qs, d16)
    findings = sc.check_dequant_sites(closed, "seeded")
    assert findings and all(f.rule == "J005" for f in findings)
    assert "rogue" in findings[0].detail


def test_sanctioned_dequant_does_not_fire_j005():
    # the real forward dequantizes via ops/linear.dequantize_weight (the
    # registered XLA-fallback site) at every Q40 matmul — zero findings
    spec = sc.model_spec("13b", "q40")
    closed, _ = sc.trace_tp_forward(spec, 4, "ref")
    assert sc.check_dequant_sites(closed, "repo") == []


def test_ragged_heads_report_j006():
    spec = small_bench_spec(n_heads=6, n_kv_heads=6)
    findings = sc.check_uniform_shards(spec, 4, "ref", "seeded")
    assert findings and all(f.rule == "J006" for f in findings)
    assert any("n_heads" in f.detail for f in findings)


def test_fused_q40_block_granularity_is_j006():
    # dim/tp not a 32-multiple: the fused scheme cannot slice wo's input
    # blocks — reported as a finding, not a mid-load traceback
    spec = small_bench_spec(dim=448, n_heads=4, n_kv_heads=4,
                            hidden_dim=448)  # 448/4 = 112, not 32-aligned
    findings = sc.check_uniform_shards(spec, 4, "fused", "seeded")
    assert any(f.rule == "J006" and "32-multiple" in f.detail
               for f in findings)


def test_const_hoisted_weight_reports_j004():
    # a weight CLOSED OVER by the body gets hoisted as a shard_map const
    # operand (prepended to in_names, replicated) — it never appears in the
    # declared leaf rows, so the tail-aligned check alone would miss it
    from jax.sharding import PartitionSpec as P

    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.tp import _shard_map

    mesh = make_mesh(tp=4, devices=jax.devices()[:4])
    big = jnp.ones((512, 512), jnp.float32)  # 1 MiB, closed over

    def local(x):
        return x + big.sum()

    fn = _shard_map(local, mesh=mesh, in_specs=(P(),), out_specs=P())
    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), jnp.float32))
    findings = sc.check_traced_sharding(closed, None, "ref", 4, "seeded",
                                        expected=[("x", {})])
    assert any(f.rule == "J004" and "hoisted" in f.detail
               for f in findings), findings


# -- registry anti-rot ------------------------------------------------------


def test_dequant_registry_entries_resolve_to_real_functions():
    import importlib

    from distributed_llama_tpu.ops.dequant_sites import ALLOWED_DEQUANT_SITES

    for suffix, fn_name in ALLOWED_DEQUANT_SITES:
        mod_name = ("distributed_llama_tpu."
                    + suffix.replace(".py", "").replace("/", "."))
        mod = importlib.import_module(mod_name)
        assert callable(getattr(mod, fn_name)), (suffix, fn_name)


# -- live-interval walk unit pins ------------------------------------------


def test_live_peak_counts_simultaneous_intermediates():
    def f(x):
        a = x * 2.0          # 1 MB live
        b = x + 1.0          # +1 MB live
        return a + b         # peak: x excluded, a+b+out

    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)  # 1 MiB
    peak = live_interval_peak(jax.make_jaxpr(f)(x).jaxpr)
    assert peak == 3 * (1 << 20)  # a, b, and the sum live together


def test_live_peak_aliases_in_place_cache_update():
    def f(cache, v):
        return jax.lax.dynamic_update_slice(cache, v, (0, 0))

    cache = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB
    v = jax.ShapeDtypeStruct((1, 1024), jnp.float32)
    peak = live_interval_peak(jax.make_jaxpr(f)(cache, v).jaxpr)
    # operand is an (untracked, donated-style) input: in-place, no 4 MiB
    assert peak < (1 << 20)


def test_live_peak_excludes_filtered_eqns():
    def f(x):
        big = x.astype(jnp.float32)  # the "dequant" stand-in
        return big.sum()

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.uint8)
    jaxpr = jax.make_jaxpr(f)(x).jaxpr
    full = live_interval_peak(jaxpr)
    none = live_interval_peak(
        jaxpr, exclude_eqn=lambda e: e.primitive.name
        == "convert_element_type")
    assert full >= 4 * (1 << 20) and none < full


# -- projection + report surfaces ------------------------------------------


def test_projection_carries_hbm_verdict():
    from distributed_llama_tpu.parallel.shard_sim import project_full_system

    spec = sc.model_spec("70b", "q40")
    fits = project_full_system(spec, 8, 10.0, scheme="fused")
    assert fits.hbm_fits and fits.hbm_headroom_gib > 6
    no = project_full_system(spec, 2, 10.0, scheme="fused")
    assert not no.hbm_fits and no.hbm_headroom_gib < 0
    assert no.hbm_per_device_gib > 20


def test_report_json_is_machine_readable(matrix_results):
    rep = sc.report_json(matrix_results)
    assert rep["n_configs"] == 84 and rep["n_violations"] == 0
    assert sum(r["kv_quant"] == "q8" for r in rep["configs"]) == 12
    row = rep["configs"][0]
    assert set(row) >= {"config", "ok", "findings", "report"}
    comp = row["report"]["components_gib"]
    assert set(comp) == {"weights", "replicated", "kv_cache", "activation",
                         "collective"}
    assert row["report"]["total_gib"] == pytest.approx(
        sum(comp.values()), abs=0.01)


def test_staging_term_tracks_the_budget_cut_points():
    from distributed_llama_tpu.parallel.comm_stats import (
        collective_staging_bytes)

    spec = sc.model_spec("70b", "q40")
    assert collective_staging_bytes(spec, 1, "ref") == 0
    ref = collective_staging_bytes(spec, 8, "ref")
    fused = collective_staging_bytes(spec, 8, "fused")
    # both schemes' largest payload is the f32 logits gather at these dims
    assert ref == fused == 2 * 32000 * 4
    # Q80 buffers shrink the ref gathers but never the f32 logits
    spec80 = dataclasses.replace(spec, buffer_float_type=FloatType.Q80)
    assert collective_staging_bytes(spec80, 8, "ref") == 2 * 32000 * 4
