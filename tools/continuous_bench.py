"""Continuous-batching throughput on the current backend.

Measures the slot-pool scheduler end to end (admission, fused chains,
retirement) at a 7B-shaped Q40 config with synthetic weights — the
measurement behind BASELINE.md's continuous-batching rows. Runs one warm-up
pass (compile) and times a second identical pass; stream equality between
the two passes is asserted (the schedule is deterministic).

A paged-KV comparison section (on by default) then drives a
shared-system-prompt workload through (a) the contiguous engine at
``--slots`` and (b) a paged engine holding the SAME modeled KV HBM
(analysis/memory_model: pool pages = slots x seq_len/page_size) but
``--oversub`` x the slots — the ISSUE-6 acceptance columns: sustained
concurrency at equal HBM, prefix-hit rate, and prefill tokens saved.
Streams must match the contiguous engine token for token (scheduling and
paging stay invisible in outputs).

A speculative-decoding section (ISSUE 7, on by default) then runs the SAME
paged workload spec-off and spec-on at equal HBM (identical pool), both at
one device dispatch per scheduler iteration — the dispatch-for-dispatch
comparison speculative decoding exists to win: spec-on emits up to K
tokens per dispatch where spec-off emits one. Columns: accept rate and
ms/accepted-token, with the greedy streams asserted token-identical
(losslessness is not a tolerance).

A KV-quant comparison section (ISSUE 11, on by default) runs the paged
workload twice at EQUAL modeled KV HBM: f32 pages vs Q8 pages holding
~3.76x the page count (memory_model.equal_hbm_kv_pages), with
sustained-concurrency and tokens/s columns in the fingerprinted row —
the capacity half of the paged-kernel + quantized-pages PR.

The final stdout line is a JSON row stamped with utils/fingerprint.
env_fingerprint (jax/jaxlib/device-kind/clock — the same drift defense as
bench.py rows), so BENCH_* archives stay joinable across sessions.

Usage:
  python tools/continuous_bench.py [--slots 4] [--block-steps 16]
      [--kv-cache-dtype f32|bf16] [--requests 6] [--steps 48] [--small]
      [--page-size 16] [--oversub 4] [--no-paged-compare]
      [--spec-k 4] [--no-spec-compare]

--block-steps 16 amortizes the per-dispatch host round-trip;
--block-steps 1 measures the per-step scheduling floor.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _shared_prompt_requests(page_size: int, n: int) -> list:
    """A shared-system-prompt workload: every request opens with the same
    2-full-page system prefix (page-aligned => radix-shareable) and ends
    with a short unique tail — the millions-of-users chat shape."""
    sys_prefix = [1] + [7 + (i % 90) for i in range(2 * page_size)]
    return [sys_prefix + [3 + i % 100, 5 + (i * 7) % 100] for i in range(n)]


def paged_compare(spec, params, args, dtype) -> dict:
    """The equal-HBM concurrency section; returns the JSON sub-row."""
    from distributed_llama_tpu.analysis.memory_model import (
        kv_cache_device_bytes, kv_page_pool_bytes)
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    ps = args.page_size
    max_pages = spec.seq_len // ps
    pool_pages = args.slots * max_pages   # byte-parity with --slots stripes
    paged_slots = args.slots * args.oversub
    reqs = _shared_prompt_requests(ps, args.requests)
    steps = args.steps

    def run(label, **kw):
        eng = ContinuousEngine(spec, params, temperature=0.0, topp=0.9,
                               seed=3, block_steps=args.block_steps,
                               cache_dtype=dtype, prefill_chunk=ps, **kw)
        eng.run(reqs, steps=steps)            # warm-up (compile)
        if eng.allocator is not None:
            # report the timed pass alone (warm-tree steady state), not a
            # cold+warm blend accumulated across both passes
            eng.allocator.reset_counters()
        t0 = time.perf_counter()
        outs, st = eng.run(reqs, steps=steps)
        dt = time.perf_counter() - t0
        print(f"{label}: {st.tokens} tokens {dt:.2f}s "
              f"{st.tokens / dt:.1f} tok/s, sustained concurrency "
              f"{st.avg_active:.2f} (max {st.max_active})", file=sys.stderr)
        return eng, outs, st, dt

    _, outs_c, st_c, dt_c = run(f"contiguous slots={args.slots}",
                                slots=args.slots)
    eng_p, outs_p, st_p, dt_p = run(
        f"paged slots={paged_slots} pool={pool_pages}x{ps}",
        slots=paged_slots, page_size=ps, kv_pages=pool_pages)
    assert outs_p == outs_c, "paged scheduling changed a token stream?!"

    a = eng_p.allocator
    kv_contig = kv_cache_device_bytes(spec, 1, batch=args.slots)
    kv_paged = kv_page_pool_bytes(spec, 1, pool_pages, ps,
                                  include_scrap=False)
    assert kv_paged == kv_contig, "equal-HBM sizing drifted"
    row = {
        "page_size": ps, "pool_pages": pool_pages,
        "kv_hbm_bytes": kv_contig,
        "contiguous": {"slots": args.slots, "tok_s": st_c.tokens / dt_c,
                       "sustained_concurrency": st_c.avg_active,
                       "steps": st_c.steps},
        "paged": {"slots": paged_slots, "tok_s": st_p.tokens / dt_p,
                  "sustained_concurrency": st_p.avg_active,
                  "steps": st_p.steps},
        "concurrency_ratio": st_p.avg_active / max(st_c.avg_active, 1e-9),
        "prefix_hit_rate": a.hit_rate,
        "prefill_tokens_saved": a.tokens_saved,
        "evictions": a.evictions,
    }
    print(f"equal-HBM ({kv_contig / 2**20:.0f} MiB KV): concurrency "
          f"{st_c.avg_active:.2f} -> {st_p.avg_active:.2f} "
          f"({row['concurrency_ratio']:.2f}x), prefix hit rate "
          f"{a.hit_rate:.0%}, {a.tokens_saved} prefill tokens saved",
          file=sys.stderr)
    return row


def kv_quant_compare(spec, params, args, dtype) -> dict:
    """The equal-HBM q8-vs-f32 section (ISSUE 11): both arms run the paged
    engine over the SAME shared-system-prompt workload, but the q8 arm's
    pool holds the pages the f32 arm's KV HBM buys at the Q80 byte rate
    (memory_model.equal_hbm_kv_pages — ~3.76x pages at f32 baseline) and
    scales its slot count by the same multiplier. Columns: sustained
    concurrency + tokens/s per arm — the two wins of this PR compound on
    this row: the paged kernel makes each token cheaper (on TPU), the q8
    pool admits more concurrent sessions at equal HBM. Greedy q8 streams
    are asserted DETERMINISTIC (pass-identical); q8-vs-f32 equality is a
    distribution-tolerance property, not a bitwise one, and is pinned by
    the engine tests on the CPU smoke model instead."""
    from distributed_llama_tpu.analysis.memory_model import (
        equal_hbm_kv_pages, kv_page_pool_bytes)
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    ps = args.page_size
    max_pages = spec.seq_len // ps
    pool_f32 = args.slots * max_pages
    # price the baseline arm at its ACTUAL page byte rate (bf16 pages
    # halve it), so "equal HBM" means the bytes this run's pool holds
    base_itemsize = 2 if args.kv_cache_dtype == "bf16" else 4
    pool_q8 = equal_hbm_kv_pages(spec, 1, pool_f32, ps,
                                 cache_itemsize=base_itemsize)
    factor = pool_q8 / pool_f32
    slots_f32 = args.slots * args.oversub
    slots_q8 = min(max(slots_f32, int(args.slots * args.oversub * factor)),
                   max(args.requests, 1))
    reqs = _shared_prompt_requests(ps, args.requests)

    def run(label, slots, pool, kv_quant):
        eng = ContinuousEngine(spec, params, slots=slots, temperature=0.0,
                               topp=0.9, seed=3, cache_dtype=dtype,
                               block_steps=args.block_steps,
                               prefill_chunk=ps, page_size=ps,
                               kv_pages=pool, kv_quant=kv_quant)
        eng.run(reqs, steps=args.steps)       # warm-up (compile)
        t0 = time.perf_counter()
        outs, st = eng.run(reqs, steps=args.steps)
        dt = time.perf_counter() - t0
        outs2, _ = eng.run(reqs, steps=args.steps)
        assert outs2 == outs, f"{label}: non-deterministic streams?!"
        print(f"{label}: {st.tokens} tokens {dt:.2f}s "
              f"{st.tokens / dt:.1f} tok/s, sustained concurrency "
              f"{st.avg_active:.2f} (max {st.max_active})", file=sys.stderr)
        return outs, st, dt

    _, st_f, dt_f = run(
        f"kv {args.kv_cache_dtype} slots={slots_f32} pool={pool_f32}x{ps}",
        slots_f32, pool_f32, "f32")
    _, st_q, dt_q = run(f"kv q8  slots={slots_q8} pool={pool_q8}x{ps}",
                        slots_q8, pool_q8, "q8")
    hbm_f32 = kv_page_pool_bytes(spec, 1, pool_f32, ps,
                                 include_scrap=False,
                                 cache_itemsize=base_itemsize)
    hbm_q8 = kv_page_pool_bytes(spec, 1, pool_q8, ps,
                                include_scrap=False, kv_quant="q8")
    assert hbm_q8 <= hbm_f32, "equal-HBM sizing drifted (q8 over budget)"
    row = {
        "page_size": ps, "baseline_kv_dtype": args.kv_cache_dtype,
        "kv_hbm_bytes_baseline": hbm_f32, "kv_hbm_bytes_q8": hbm_q8,
        "pages_baseline": pool_f32, "pages_q8": pool_q8,
        "page_multiplier": round(factor, 3),
        "baseline": {"slots": slots_f32, "tok_s": st_f.tokens / dt_f,
                     "sustained_concurrency": st_f.avg_active,
                     "steps": st_f.steps},
        "q8": {"slots": slots_q8, "tok_s": st_q.tokens / dt_q,
               "sustained_concurrency": st_q.avg_active,
               "steps": st_q.steps},
        "concurrency_ratio": st_q.avg_active / max(st_f.avg_active, 1e-9),
        "streams_deterministic": True,
    }
    print(f"equal-HBM KV quant ({hbm_f32 / 2**20:.0f} MiB "
          f"{args.kv_cache_dtype} budget): "
          f"{pool_f32} -> {pool_q8} pages ({factor:.2f}x), concurrency "
          f"{st_f.avg_active:.2f} -> {st_q.avg_active:.2f} "
          f"({row['concurrency_ratio']:.2f}x), "
          f"{st_f.tokens / dt_f:.1f} -> {st_q.tokens / dt_q:.1f} tok/s",
          file=sys.stderr)
    return row


def spec_compare(spec, params, args, dtype) -> dict:
    """The spec-on vs spec-off section at equal HBM; returns the JSON
    sub-row. Both arms run the paged cache with the SAME pool (identical
    modeled KV HBM — the verify dispatch adds only K-wide activations,
    analysis/memory_model device_footprint(spec_k=K)) and ONE device
    dispatch per scheduler iteration, so the ms/accepted-token column
    isolates exactly what speculation amortizes: per-dispatch overhead
    (host round-trip + launch here; the collective-latency floor on a
    real mesh)."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    ps = args.page_size
    pool_pages = args.slots * (spec.seq_len // ps)
    reqs = _shared_prompt_requests(ps, args.requests)

    def run(label, **kw):
        eng = ContinuousEngine(spec, params, slots=args.slots,
                               temperature=0.0, topp=0.9, seed=3,
                               cache_dtype=dtype, page_size=ps,
                               kv_pages=pool_pages, **kw)
        eng.run(reqs, steps=args.steps)       # warm-up (compile)
        t0 = time.perf_counter()
        outs, st = eng.run(reqs, steps=args.steps)
        dt = time.perf_counter() - t0
        print(f"{label}: {st.tokens} tokens {st.steps} dispatches "
              f"{dt:.2f}s -> {dt * 1000 / st.tokens:.2f} ms/token",
              file=sys.stderr)
        return outs, st, dt

    outs_off, st_off, dt_off = run("spec-off (1 tok/dispatch)")
    outs_on, st_on, dt_on = run(f"spec-on  (K={args.spec_k})",
                                spec_k=args.spec_k)
    assert outs_on == outs_off, \
        "speculative decoding changed a greedy token stream?!"
    ms_off = dt_off * 1000 / max(1, st_off.tokens)
    ms_on = dt_on * 1000 / max(1, st_on.tokens)
    row = {
        "k": args.spec_k,
        "accept_rate": round(st_on.spec_accept_rate, 4),
        "drafts_proposed": st_on.spec_proposed,
        "drafts_accepted": st_on.spec_accepted,
        "dispatches_off": st_off.steps, "dispatches_on": st_on.steps,
        "ms_per_accepted_token_off": round(ms_off, 3),
        "ms_per_accepted_token_on": round(ms_on, 3),
        "speedup": round(ms_off / max(ms_on, 1e-9), 3),
        "streams_identical": True,
    }
    print(f"speculative K={args.spec_k}: accept rate "
          f"{st_on.spec_accept_rate:.0%} "
          f"({st_on.spec_accepted}/{st_on.spec_proposed}), "
          f"{ms_off:.2f} -> {ms_on:.2f} ms/accepted token "
          f"({row['speedup']:.2f}x, {st_off.steps} -> {st_on.steps} "
          f"dispatches), streams identical", file=sys.stderr)
    return row


def tiering_compare(spec, params, args, dtype) -> dict:
    """The KV-tiering section (ISSUE 12): prefix-hit prefill savings at a
    working set ~10x the HBM page pool, three arms over the SAME
    two-pass workload (pass 1 publishes N distinct shared prefixes, pass
    2 revisits every one — counters are step-based and deterministic,
    the virtual-clock property the CI gate needs):

    * all-HBM — pool holds the whole working set (the savings ceiling);
    * tiered  — HBM pool ~1/10 of the working set + host pool + disk
      segments: cold prefixes demote write-behind, pass-2 hits promote
      them back (async upload + admission PAUSE);
    * drop    — the same tiny pool with drop-on-evict (pre-ISSUE-12
      behavior): pass 2 recomputes everything.

    The acceptance gate asserts IN the section: tiered pass-2 savings
    within 20% of all-HBM, drop-arm savings below half the ceiling,
    streams identical across arms, the three-tier audit green, and the
    promotion/demotion counters consistent with the page ledger."""
    import tempfile

    from distributed_llama_tpu.analysis.memory_model import kv_tier_model
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    ps = args.page_size
    n_prefix = args.tiering_prefixes
    prefix_pages = 2
    working_set = n_prefix * prefix_pages
    hbm_pages = max(8, working_set // 10)     # >= 10x oversubscription
    host_pages = max(4, working_set // 2)
    steps = (prefix_pages + 2) * ps

    def wave(tail):
        return [[1] + [(7 * i + j) % 90 + 5
                       for j in range(prefix_pages * ps)] + [tail + i % 40]
                for i in range(n_prefix)]

    def run(label, **kw):
        eng = ContinuousEngine(spec, params, slots=2, temperature=0.0,
                               topp=0.9, seed=3, cache_dtype=dtype,
                               prefill_chunk=ps, page_size=ps, **kw)
        o1, _ = eng.run(wave(3), steps=steps)     # pass 1: publish
        eng.allocator.reset_counters()
        o2, st = eng.run(wave(9), steps=steps)    # pass 2: revisit
        a = eng.allocator
        print(f"{label}: pass-2 prefill saved {a.tokens_saved} "
              f"(by tier {a.tokens_saved_by_tier}), "
              f"{sum(a.demotions.values())} demotions, "
              f"{sum(a.promotions.values())} promotions, "
              f"{st.pauses} pauses", file=sys.stderr)
        eng.close()  # the tiered arm's uploader thread
        return eng, (o1, o2), a

    _, outs_full, a_full = run(
        f"tier all-hbm pool={working_set + 8}x{ps}",
        kv_pages=working_set + 8)
    disk_dir = tempfile.mkdtemp(prefix="dllama-bench-tier-")
    eng_t, outs_t, a_t = run(
        f"tier 3-tier  pool={hbm_pages}x{ps} host={host_pages} disk",
        kv_pages=hbm_pages, kv_host_pages=host_pages,
        kv_disk_dir=disk_dir)
    _, outs_d, a_d = run(f"tier drop     pool={hbm_pages}x{ps}",
                         kv_pages=hbm_pages)

    # the acceptance gates (ISSUE 12) — assert, don't just report
    assert outs_t == outs_full and outs_d == outs_full, \
        "tiering changed a token stream?!"
    ceiling = a_full.tokens_saved
    assert ceiling > 0, "all-HBM arm saved nothing — workload broken"
    assert a_t.tokens_saved >= 0.8 * ceiling, \
        (f"tiered savings {a_t.tokens_saved} fell below 80% of the "
         f"all-HBM ceiling {ceiling}")
    assert a_d.tokens_saved <= 0.5 * ceiling, \
        (f"drop-on-evict baseline saved {a_d.tokens_saved} of {ceiling} "
         f"— the working set no longer exceeds the pool; enlarge it")
    audit = eng_t.audit_pages()
    assert audit == [], f"three-tier audit violations: {audit}"
    # counters vs ledger: every promotion/demotion pairs with tier
    # population movement the recount can see (audit already cross-
    # checked the incremental ledger against the tree)
    assert sum(a_t.promotions.values()) > 0 and \
        sum(a_t.demotions.values()) > 0, "no tier churn at 10x HBM?!"
    spilled_saved = (a_t.tokens_saved_by_tier["host"]
                     + a_t.tokens_saved_by_tier["disk"])
    model = kv_tier_model(spec, 1, hbm_pages, host_pages=host_pages,
                          page_size=ps,
                          cache_itemsize=2 if dtype is not None else 4)
    row = {
        "page_size": ps, "working_set_pages": working_set,
        "hbm_pages": hbm_pages, "host_pages": host_pages,
        "oversubscription": round(working_set / hbm_pages, 2),
        "prefill_saved_ceiling": ceiling,
        "prefill_saved_tiered": a_t.tokens_saved,
        "prefill_saved_drop_baseline": a_d.tokens_saved,
        "savings_vs_ceiling": round(a_t.tokens_saved / ceiling, 4),
        "saved_by_tier": dict(a_t.tokens_saved_by_tier),
        "demotions": dict(a_t.demotions),
        "promotions": dict(a_t.promotions),
        "crc_drops": a_t.crc_drops,
        "audit_clean": True, "streams_identical": True,
        "modeled": {k: model[k] for k in
                    ("page_bytes", "promote_host_ms_per_page",
                     "promote_disk_ms_per_page", "demote_ms_per_page")},
    }
    print(f"tiering at {row['oversubscription']:.0f}x HBM working set: "
          f"prefill saved {a_t.tokens_saved}/{ceiling} "
          f"({row['savings_vs_ceiling']:.0%} of all-HBM; drop baseline "
          f"{a_d.tokens_saved}), {spilled_saved} tokens rescued from "
          f"spilled tiers, audit clean", file=sys.stderr)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-steps", type=int, default=16)
    ap.add_argument("--kv-cache-dtype", default="f32",
                    choices=("f32", "bf16"))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--small", action="store_true",
                    help="tiny config for CI/CPU smoke runs")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged-compare page size (positions per page)")
    ap.add_argument("--oversub", type=int, default=4,
                    help="paged-compare slot multiplier at equal KV HBM")
    ap.add_argument("--paged-compare", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the equal-HBM paged-vs-contiguous section")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative verify window for the spec section")
    ap.add_argument("--spec-compare", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the spec-on vs spec-off section (equal HBM, "
                         "one dispatch per iteration, streams asserted "
                         "identical)")
    ap.add_argument("--kv-quant-compare",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="run the equal-HBM q8-vs-f32 KV-quant section "
                         "(ISSUE 11): the q8 arm serves the page count "
                         "the f32 arm's KV HBM buys at the Q80 byte "
                         "rate — sustained-concurrency and tokens/s "
                         "columns, greedy streams asserted deterministic")
    ap.add_argument("--tiering-compare",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="run the KV-tiering section (ISSUE 12): prefix-"
                         "hit prefill savings at a working set ~10x the "
                         "HBM page pool — all-HBM ceiling vs three-tier "
                         "(HBM+host+disk) vs drop-on-evict baseline, "
                         "streams asserted identical, three-tier audit "
                         "asserted clean")
    ap.add_argument("--tiering-prefixes", type=int, default=40,
                    help="distinct shared prefixes in the tiering "
                         "section's working set (2 full pages each)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                    small_bench_spec,
                                                    synth_q40_fast)
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine
    from distributed_llama_tpu.utils.fingerprint import env_fingerprint

    from distributed_llama_tpu.utils.chip import device_triple, require_tpu

    dev = device_triple() if args.small else require_tpu()
    print(f"backend: {dev}", file=sys.stderr)
    spec = small_bench_spec() if args.small else llama2_7b_spec()
    t0 = time.perf_counter()
    params = synth_q40_fast(spec)
    print(f"synth weights: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    dtype = jnp.bfloat16 if args.kv_cache_dtype == "bf16" else None
    # ragged prompts of length 2, 3, 4 cycling
    reqs = [[1, 3 + i % 90, 5 + i % 80, 7 + i % 70][:2 + i % 3]
            for i in range(args.requests)]
    t0 = time.perf_counter()
    eng = ContinuousEngine(spec, params, slots=args.slots, temperature=0.0,
                           topp=0.9, seed=3, block_steps=args.block_steps,
                           cache_dtype=dtype)
    print(f"engine up: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    t0 = time.perf_counter()
    outs, _ = eng.run(reqs, steps=args.steps)
    print(f"warm-up (compile) pass: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    t0 = time.perf_counter()
    outs2, st = eng.run(reqs, steps=args.steps)
    dt = time.perf_counter() - t0
    assert outs2 == outs, "non-deterministic schedule?!"
    print(f"{st.tokens} tokens, {st.steps} device steps, {dt:.2f}s -> "
          f"{st.tokens / dt:.1f} tok/s ({dt * 1000 / st.steps:.2f} ms/step, "
          f"slots={args.slots}, block={args.block_steps}, "
          f"cache={args.kv_cache_dtype})")

    timings = {"tok_s": st.tokens / dt, "ms_step": dt * 1000 / st.steps}
    row = {
        "tool": "continuous_bench",
        "spec": "small" if args.small else "7b",
        "slots": args.slots, "block_steps": args.block_steps,
        "kv_cache_dtype": args.kv_cache_dtype,
        "requests": args.requests, "steps": args.steps,
        "timing": timings,
    }
    # per-scheme modeled tp rows (ISSUE 10): this tool measures a
    # single-chip engine, so the tp collective side is MODELED — the same
    # one-source budget bench.py projects from — for all three schemes at
    # tp=8, so continuous rows archived next to BENCH_* stay joinable on
    # the scheme axis. Bytes scale by the slot count (batched collectives
    # move B rows per launch).
    from distributed_llama_tpu.parallel.comm_stats import (
        SCHEMES, tp_collective_budget)
    from distributed_llama_tpu.parallel.shard_sim import modeled_ici_ms

    schemes_row = {}
    for scheme in SCHEMES:
        b = tp_collective_budget(spec, 8, scheme)
        bw_ms, lat_ms = modeled_ici_ms(spec, 8, scheme)
        schemes_row[scheme] = {
            "n_collectives_per_dispatch": b.n_collectives,
            "kb_per_chip_per_row": round(b.moved_bytes / 1024, 1),
            "modeled_ici_ms_total": round(bw_ms + lat_ms, 3),
        }
    row["tp_schemes_modeled"] = {
        "tp": 8, "note": ("single-chip measurement; ICI modeled from "
                          "comm_stats per scheme — overlap's hidden "
                          "share needs a rank measurement (bench.py "
                          "projection rows)"),
        "schemes": schemes_row,
    }
    if args.paged_compare:
        row["paged_equal_hbm"] = paged_compare(spec, params, args, dtype)
    if args.spec_compare:
        row["speculative"] = spec_compare(spec, params, args, dtype)
    if args.kv_quant_compare:
        row["kv_quant_equal_hbm"] = kv_quant_compare(spec, params, args,
                                                     dtype)
    if args.tiering_compare:
        row["kv_tiering"] = tiering_compare(spec, params, args, dtype)

    # the machine-readable row, fingerprint-stamped like bench.py's
    row["env_fingerprint"] = env_fingerprint()
    print(json.dumps(row))


if __name__ == "__main__":
    main()
