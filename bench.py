"""Benchmark: Llama-2 Q40 single-token decode, reference protocol.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload matches the reference benchmark (README.md:40-50): Q40 weights,
single-token generation, wall-clock/token averaged over the run. Baselines
(vs_baseline = baseline_ms / our_ms, higher = faster) are the reference's
BEST published figures per model: 7B 494.00 ms (4x RasPi), 13B 848.19 ms
(4x RasPi), 70B 4842.81 ms (8x RasPi) — README.md:46-48 / BASELINE.md.

Configs (--config):
  all      (default) run 7b + 13b + 70b-tp8 + the six scaling rows below,
           each in its own subprocess, write the FULL table to
           BENCH_FULL.json, and emit ONE COMPACT JSON line (headline +
           per-row ms/x, I/T on the tp rows, "scaling_x_vs_same_n" pairs — the driver command; VERDICT
           r2 #1/r3 #2/r4 #1 — every claim driver-verifiable and the
           stdout line sized for the driver's capture).
  7b       whole model on one chip — the headline row.
  13b      whole model on one chip (~8 GB Q40 + 3.4 GB f32 KV cache).
  70b-tp8  ONE tp=8 rank's exact program on one chip (parallel/shard_sim:
           tp.make_local_step with gathers tiled locally), plus the analytic
           ICI collective budget -> projected v5e-8 ms/token with the
           itemization printed to stderr. Replaces round 1's 70B
           extrapolation with measured 70B-shaped data (VERDICT r1 #1).
  {7b,13b}-tp{2,4,8}  the scaling curve (VERDICT r3 #2): one tp-rank of
           7B/13B measured whole on the chip like 70b-tp8, baselined
           against the reference's SAME-device-count row (README.md:46-47)
           — the analog of its 1/2/4/8 table, including where TP stops
           paying on each side.
  small    tiny config for CI/CPU smoke runs (= --small).

One deliberate protocol deviation: the default run generates 64 tokens, not
the reference's 16, so the fixed dispatch+sync cost of a launched chain
weighs less on the per-token figure. ms/token is still total wall clock /
tokens generated (nothing is subtracted); --samples 16 reproduces the
reference count for an apples-to-apples run.

Every config but ``small`` reports device metrics and refuses to run without
a TPU (utils/chip.require_tpu); a failure is a failure — no retry on another
kernel path — and ``--config all`` exits non-zero when any row failed.

Weights are synthetic (timing is value-independent); the structure — Q40
planar blocks resident in device memory, dequant-fused matmuls, scan over
layers, static KV cache — is the real decode program. Synthetic-weight
chains force a fixed token stream (the junk argmax could hit BOS and
truncate the chain; the forced path still computes logits and the sampled
candidate every step, it just never terminates early). --model runs keep
real sampling.

Usage: python bench.py [--config NAME] [--samples N] [--model PATH]
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

_PROC_T0 = time.perf_counter()  # warm-start accounting anchor
_STARTUP: dict = {}


def _tree_shapes_cached(spec, rank_tp: int, build, build_sig: str = "",
                        layout_label: str = ""):
    """Shape manifest for the packed host tree (synthetic benches only).

    The host-side prep for a synthetic bench — RNG synth + kernel re-tiling
    + load-time fusions — costs ~65 s at 7B and exists ONLY to discover the
    final tree's leaf shapes/dtypes (device_params_like regenerates the
    values on device). Cache the manifest (treedef + shapes) under the
    compile cache's directory so warm runs skip the whole host prep.
    Stale-manifest risk is a loud compile/shape error, never silent skew;
    DLLAMA_SHAPE_CACHE=0 disables, and a load or save error falls back to a
    fresh build, reported and counted (utils/compile_cache.cache_error).
    """
    import hashlib
    import pickle

    import jax

    from distributed_llama_tpu.ops.linear import q40_kernel_mode
    from distributed_llama_tpu.ops.pallas_layer import fusion_cache_key
    from distributed_llama_tpu.ops.pallas_q40 import _TILE_ROWS_CAP
    from distributed_llama_tpu.utils.compile_cache import (cache_error,
                                                           default_cache_dir)

    # every knob that changes the packed tree's CONTENTS must be in the
    # key: layer fusion adds the wo_mega stack only in 'mega' mode
    # (prepare_mega_params), the kernel mode decides kernel-vs-codec
    # layout, the tile-row cap feeds the layout picks, and builder
    # kwargs (e.g. the 70b rank tree's embed_dtype) change leaf
    # shapes/dtypes
    from distributed_llama_tpu.parallel.comm_stats import tp_scheme

    # tp scheme is in the key: the fused scheme's rank trees slice wo/w2
    # along the INPUT dim, so a warm ref-scheme manifest has wrong shapes
    key = hashlib.sha256(
        f"v4|{spec!r}|{rank_tp}|{q40_kernel_mode()}|{fusion_cache_key()}"
        f"|{_TILE_ROWS_CAP}|layout={layout_label}"
        f"|tpscheme={tp_scheme()}|{build_sig}"
        .encode()).hexdigest()[:16]
    path = os.path.join(default_cache_dir(), "shapes", f"tree_{key}.pkl")
    if os.environ.get("DLLAMA_SHAPE_CACHE", "1") != "0" \
            and os.path.exists(path):
        try:
            with open(path, "rb") as fh:
                treedef, leaves = pickle.load(fh)
            sds = [jax.ShapeDtypeStruct(s, np.dtype(d)) for s, d in leaves]
            print(f"shape manifest hit ({path})", file=sys.stderr)
            return jax.tree_util.tree_unflatten(treedef, sds)
        except Exception as e:  # noqa: BLE001 - rebuild on any cache trouble
            cache_error("shapes", f"{path} unreadable, rebuilding", e)
    tree = build()
    try:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        dts = [a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype
               for a in leaves]
        manifest = (treedef,
                    [(tuple(a.shape), str(d))
                     for a, d in zip(leaves, dts)])
        for (_, name), want in zip(manifest[1], dts):
            # a dtype whose str() doesn't round-trip through np.dtype
            # (e.g. an unregistered extension type) would otherwise make
            # every LOAD fail and silently rebuild each run — detect the
            # non-cacheable tree at save time instead
            if np.dtype(name) != want:
                raise TypeError(f"dtype {want!r} does not round-trip "
                                f"via np.dtype({name!r})")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump(manifest, fh)
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 - a manifest is only a shortcut
        cache_error("shapes", f"{path} not saved", e)
    return tree


def _env_fingerprint() -> dict:
    """Session fingerprint recorded with every row (bench drift defense,
    ISSUE 3): the round-5 records drifted ±5-8% between sessions —
    pinning the jax/runtime versions, the chip kind, and the clock source
    makes rows from different sessions comparable (or visibly not). ONE copy, shared with the --log-json
    stamp so log streams join against rows (utils/fingerprint)."""
    import jax  # noqa: F401 - ensure the device fields are populated

    from distributed_llama_tpu.utils.fingerprint import env_fingerprint

    return env_fingerprint()


def _bench_trials() -> int:
    """Timed-chain repeat count (median-of-N; N recorded in the row and
    printed next to the number). DLLAMA_BENCH_TRIALS overrides the
    default 3 — raise it when chasing the documented session drift."""
    raw = os.environ.get("DLLAMA_BENCH_TRIALS", "3")
    try:
        n = int(raw)
    except ValueError:
        raise SystemExit(f"DLLAMA_BENCH_TRIALS={raw!r}: expected an int")
    if n < 1:
        raise SystemExit(f"DLLAMA_BENCH_TRIALS must be >= 1, got {n}")
    return n


def _record_latency(times_ms) -> None:
    """Row-JSON latency summary — the SAME p50/p95/p99 shape the serving
    metrics report (/health, generate()'s final line), via
    obs/metrics.summarize_values."""
    from distributed_llama_tpu.obs.metrics import summarize_values

    _STARTUP["latency_ms"] = {
        k: round(v, 3) for k, v in summarize_values(times_ms).items()}


def _bench(spec, params, samples: int, per_step: bool = False,
           rank_tp: int = 0, forced: bool = False) -> float:
    """ms/token of single-token Q40 decode.

    Default protocol: the fused on-device loop (runtime/decode.py) — the
    whole `samples`-token chain is ONE device program, ms/token = total /
    samples. --per-step instead times individual host-dispatched steps (the
    reference's per-token call pattern, reported for the I/T-style
    comparison).

    ``rank_tp`` > 0: ``params`` is ONE tp-rank's band tree and the step is
    the rank-local program (parallel/shard_sim). ``forced``: drive a fixed
    token stream instead of sampling (synthetic-weight chains; see module
    docstring).
    """
    import functools

    import jax
    import jax.numpy as jnp

    from distributed_llama_tpu.models.llama import forward, init_cache

    cache_dtype = (jnp.bfloat16 if os.environ.get("DLLAMA_BENCH_KV_BF16")
                   else jnp.float32)
    # ONE pack+fuse recipe for both branches (kernel layout + wqkv/w13
    # fusion; band shapes are rank-local already on the rank_tp path, where
    # per-rank fusion is valid by construction — shard_sim)
    from distributed_llama_tpu.ops.linear import (Q40_STOCK,
                                                  announce_q40_layout,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params,
                                                  q40_body_policy)

    # the whole-model row takes the layout an engine would resolve for it
    # (7B: forced nb-major + the int4 chain body, 9.645 vs 9.98-10.37
    # ms/token, BASELINE.md r5); a rank row is one band of a sharded model:
    # the stock per-leaf picks, u8 bodies
    layout = Q40_STOCK if rank_tp else q40_body_policy(spec, rows=1)
    announce_q40_layout(layout, None if rank_tp else spec)

    def prep():
        t0 = time.perf_counter()
        p = params() if callable(params) else params
        print(f"synth weights: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        # nb-major is legal on any UNSHARDED tree; rank band trees are
        # local by construction (shard_sim runs them as plain jit, not
        # shard_map), and the pad-ratio gate (>1.25) decides per leaf.
        # Under the ref scheme rank bands slice the OUTPUT dim only
        # (shard_sim.synth_rank_q40), so each band keeps the whole model's
        # input dim and pad ratio: 7B/70B shapes (nb 128/344/256...) pad
        # <=1.19 and keep d-major everywhere; 13B's nb=160 leaves (wq..wo,
        # w1/w3, wcls, pad 1.6x) switch to nb-major while its w2 (nb=432,
        # 1.19x) stays d-major. The fused scheme's wo/w2 bands slice the
        # INPUT dim (nb/S), which can move their pad ratio — the layout
        # the program actually ran is recorded in the row JSON either way
        hp = fuse_q40_layer_matmuls(pack_q40_params(
            p, allow_nb_major=True, layout=layout))
        # the int4 chain body needs NO host prep: the chain converts u8
        # nb-major leaves to int4 planes in-program (chain_weight_prep) —
        # the astype-produced s4 arrays get XLA-native layouts, which the
        # packed-u8-carrier + bitcast route does NOT (measured 4.7x rank
        # slowdown from the bitcast-materialized layout; BASELINE.md r5)
        if rank_tp == 0:
            # whole-layer megakernel prep (permuted-wo stack) if supported
            from distributed_llama_tpu.ops.pallas_layer import (
                prepare_mega_params)

            hp = prepare_mega_params(spec, hp)
        return hp

    if forced:
        # synthetic weights: discover the packed tree's SHAPES (manifest
        # cache skips the ~65 s host synth+retile when warm) and generate
        # the values ON DEVICE (same shapes/dtypes/layout prep; timing
        # never depends on values): no multi-GB host tree, no upload.
        from distributed_llama_tpu.models.synth import device_params_like

        if callable(params):
            fn = getattr(params, "func", params)
            build_sig = (f"{getattr(fn, '__name__', repr(fn))}"
                         f"|{getattr(params, 'args', ())!r}"
                         f"|{sorted(getattr(params, 'keywords', {}).items())!r}")
        else:
            build_sig = ""
        host_params = _tree_shapes_cached(spec, rank_tp, prep, build_sig,
                                          layout.label)
        t_gen = time.perf_counter()
        host_params = device_params_like(host_params)
        jax.block_until_ready(host_params)
        print(f"on-device weight synth: "
              f"{time.perf_counter() - t_gen:.1f}s", file=sys.stderr)
    else:
        host_params = prep()
    # record which Q40 layouts the measured program actually runs (ADVICE
    # r4: rank rows pack with allow_nb_major=True — legal for the plain-jit
    # rank program, but the shard_map sharding specs reject nb-major, so a
    # deployed tp program would run d-major; the caveat must ride the JSON)
    from distributed_llama_tpu.io.loader import Q40KernelNb

    leaves = jax.tree_util.tree_leaves(
        host_params, is_leaf=lambda x: isinstance(x, Q40KernelNb))
    has_nb = any(isinstance(x, Q40KernelNb) for x in leaves)
    _STARTUP["q40_layout"] = ("nb-major+d-major mix" if has_nb
                              else "d-major")
    # int4-plane chain conversion active? (nb-major leaves only — the
    # layout label above reports the HOST tree, which stays u8)
    _STARTUP["q40_i4"] = "on" if layout.i4_chain else "off"
    if rank_tp and has_nb:
        _STARTUP["rank_layout_caveat"] = (
            "rank measured with nb-major leaves (unsharded-plain-jit-only "
            "layout); a shard_map tp program runs d-major — see BASELINE.md")
    if rank_tp:
        from distributed_llama_tpu.parallel import shard_sim

        step = shard_sim.make_rank_step(spec, rank_tp)
        init_cache = functools.partial(shard_sim.init_rank_cache, spec,
                                       rank_tp, cache_dtype)
    else:
        step = functools.partial(forward, spec)
        init_cache = functools.partial(init_cache, spec, cache_dtype)
    if per_step:
        # per-step path: plain placement (no AOT chain to take layouts from)
        t_put = time.perf_counter()
        params = jax.tree_util.tree_map(jnp.asarray, host_params)
        jax.block_until_ready(params)
        print(f"weights to device: {time.perf_counter() - t_put:.1f}s",
              file=sys.stderr)
        cache = init_cache()
        jstep = jax.jit(step, donate_argnums=1)
        tok = jnp.asarray([7], dtype=jnp.int32)
        t_compile = time.perf_counter()
        logits, cache = jstep(params, cache, tok, jnp.int32(0))
        logits.block_until_ready()
        print(f"compile+first step: {time.perf_counter() - t_compile:.1f}s",
              file=sys.stderr)
        pos = 1
        for _ in range(4):  # warmup steps at growing pos
            logits, cache = jstep(params, cache, tok, jnp.int32(pos))
            pos += 1
        logits.block_until_ready()
        times = []
        for _ in range(samples):
            t0 = time.perf_counter()
            logits, cache = jstep(params, cache, tok, jnp.int32(pos))
            logits.block_until_ready()
            times.append((time.perf_counter() - t0) * 1000)
            pos += 1
        ms = float(np.mean(times))
        print(f"per-token ms: mean {ms:.2f}  min {min(times):.2f}  "
              f"max {max(times):.2f}", file=sys.stderr)
        _record_latency(times)
        return ms, samples

    # seq_len-shaped buffers + traced num_steps bound: every --samples value
    # (and every later process, via the persistent compile cache) reuses ONE
    # compiled chain. AOT with row-major param layouts pinned to what the
    # Pallas kernels require: weights are device_put straight into the
    # program's layouts — no in-program layout-conversion copies (at 13B
    # those temps alone OOM a 16 GB chip; see decode.make_decode_loop_aot).
    from distributed_llama_tpu.runtime.decode import make_decode_loop_aot
    from distributed_llama_tpu.utils.compile_cache import default_cache_dir

    # serialized-executable cache (VERDICT r2 #7): a warm process skips both
    # the XLA compile AND the first-execution kernel-compile round-trips
    compile_and_place = make_decode_loop_aot(
        step, spec.seq_len, temperature=0.0, topp=0.9,
        exe_cache_dir=os.path.join(default_cache_dir(), "aot"),
        i4=layout.i4_chain)
    padded = np.full((spec.seq_len + 1,), -1, dtype=np.int32)
    padded[0] = 7
    if forced:  # fixed token stream: junk-argmax BOS can't truncate the chain
        padded[:] = 7
    coins = jnp.zeros((spec.seq_len,), dtype=jnp.float32)
    t_compile = time.perf_counter()
    run, params = compile_and_place(host_params, jax.eval_shape(init_cache),
                                    jnp.asarray(padded), jnp.int32(7), coins,
                                    jnp.int32(0), jnp.int32(samples))
    jax.block_until_ready(params)
    print(f"compile+weights to device: "
          f"{time.perf_counter() - t_compile:.1f}s", file=sys.stderr)
    args = lambda: (params, init_cache(), jnp.asarray(padded),
                    jnp.int32(7), coins, jnp.int32(0), jnp.int32(samples))
    t_compile = time.perf_counter()
    np.asarray(run(*args())[0])  # the token buffer: the whole chain has run
    print(f"first chain: {time.perf_counter() - t_compile:.1f}s",
          file=sys.stderr)
    # warm-start metric (VERDICT r2 #7): process start -> first generated
    # chain fully executed (includes weight synth/load, placement, compile
    # or executable-cache load, and the first chain's kernel warmup)
    _STARTUP["startup_to_first_token_s"] = round(
        time.perf_counter() - _PROC_T0, 1)
    # time synced chains: reading the tokens back means the whole chain has
    # executed; the median of the trials damps per-chain dispatch jitter.
    # ms/token divides by the steps the chain actually RAN: the while_loop decode stops early on
    # a produced BOS (possible with real weights; BOS fills the tail), and
    # elapsed/samples would then understate the true per-token cost
    from distributed_llama_tpu.io.tokenizer import BOS

    times = []
    executed = samples
    n_trials = _bench_trials()
    for _ in range(n_trials):
        t0 = time.perf_counter()
        toks, _ = run(*args())
        toks = np.asarray(toks)
        elapsed_ms = (time.perf_counter() - t0) * 1000
        # a BOS INSIDE the budget ended the chain at that step; slots past
        # the budget are buffer padding (the token buffer is seq_len long)
        bos = np.flatnonzero(toks[:samples] == BOS)
        executed = int(bos[0]) + 1 if len(bos) else samples
        times.append(elapsed_ms / executed)
    ms = float(np.median(times))
    _STARTUP["trials"] = n_trials
    print(f"fused-loop per-token ms: {ms:.2f} (median of {n_trials} timed "
          f"chains, {executed} steps/chain"
          + ("" if executed == samples else f" — BOS-terminated early of "
             f"{samples}")
          + f", trials {[round(t, 2) for t in times]})", file=sys.stderr)
    # fused chains yield one ms/token per trial, not per token: the summary
    # spreads over chain trials (the per-step path summarizes real
    # per-token samples) — same shape either way for the row JSON
    _record_latency(times)
    return ms, executed


def _project_tp(spec, rank_tp: int, ms: float, baseline: float) -> dict:
    """Projection fields for any measured-rank config (70b-tp8 and the
    7b/13b scaling rows): measured rank compute + modeled ICI, under
    BOTH buffer modes (f32 gathers vs the packed Q80 wire), under ALL
    THREE tp schemes (the active scheme carries the headline; the ref
    scheme rides along as the parity anchor against the reference
    binaries; the overlap scheme's row subtracts its modeled hidden
    collective time — the ISSUE 10 overlap term),
    plus a latency sensitivity row (VERDICT r2 #4 asked for both to be
    printed — the per-collective latency constant is asserted from
    published microbenchmarks, unmeasurable on one chip, so the JSON
    carries how the projection moves if it is 10x worse). The headline
    value stays the f32 (reference-parity buffer) projection. The Q80 row
    reuses the f32-mode shard measurement: the wire pack/unpack is
    elementwise glue the rank step would fuse, a second-order term vs the
    13:1 latency:bandwidth split. The cross-scheme rows reuse the active
    scheme's shard measurement too — the FLOPs are identical, only the
    wo/w2 band orientation differs (recorded in the note).
    """
    import dataclasses as _dc

    from distributed_llama_tpu.ops.quants import FloatType
    from distributed_llama_tpu.parallel.comm_stats import SCHEMES, tp_scheme
    from distributed_llama_tpu.parallel.shard_sim import (
        ICI_COLLECTIVE_LATENCY_US, V5E_ICI_GBPS_PER_DIRECTION,
        project_full_system)

    scheme = tp_scheme()
    spec80 = _dc.replace(spec, buffer_float_type=FloatType.Q80)
    by_scheme = {s: project_full_system(spec, rank_tp, ms, scheme=s)
                 for s in SCHEMES}
    proj = by_scheme[scheme]  # the headline IS the active scheme's row
    proj80 = project_full_system(spec80, rank_tp, ms, scheme=scheme)
    lat10 = {
        "f32_total_ms": round(project_full_system(
            spec, rank_tp, ms, scheme=scheme,
            latency_us=10 * ICI_COLLECTIVE_LATENCY_US).total_ms, 3),
        "q80_total_ms": round(project_full_system(
            spec80, rank_tp, ms, scheme=scheme,
            latency_us=10 * ICI_COLLECTIVE_LATENCY_US).total_ms, 3),
    }
    for label, p in ([(f"{s:<5} f32", by_scheme[s]) for s in SCHEMES]
                     + [(f"{scheme} q80", proj80)]):
        fit = (f"fits, {p.hbm_headroom_gib:+.1f} GiB headroom"
               if p.hbm_fits else
               f"DOES NOT FIT ({p.hbm_headroom_gib:+.1f} GiB)")
        sum_note = (f"- {p.ici_hidden_ms:.3f} ms hidden behind compute "
                    f"(overlap term)" if p.ici_hidden_ms
                    else "(no-overlap sum)")
        print(f"collective budget [{label}] (tp={rank_tp}, per token): "
              f"{p.gather_bytes_per_chip / 1024:.0f} kB/chip over "
              f"{p.n_collectives} collectives -> "
              f"{p.ici_bandwidth_ms:.3f} ms bandwidth "
              f"(@{V5E_ICI_GBPS_PER_DIRECTION:.0f} GB/s/chip ring) + "
              f"{p.ici_latency_ms:.3f} ms latency "
              f"(@{ICI_COLLECTIVE_LATENCY_US:.1f} us/hop); "
              f"measured rank compute {p.shard_ms:.3f} ms "
              f"-> projected v5e-8 total {p.total_ms:.3f} ms/token "
              f"{sum_note}; HBM {p.hbm_per_device_gib:.1f} GiB/chip "
              f"({fit})", file=sys.stderr)
    print(f"latency sensitivity (x10 -> "
          f"{10 * ICI_COLLECTIVE_LATENCY_US:.0f} us/hop, {scheme}): "
          f"f32 {lat10['f32_total_ms']:.3f} ms, "
          f"q80 {lat10['q80_total_ms']:.3f} ms"
          + (" (bar: 48.4 ms)" if spec.n_layers == 80 else ""),
          file=sys.stderr)
    # speculative decoding term (ISSUE 7): modeled ms/accepted-token when
    # each dispatch verifies K positions at per-draft accept rate alpha —
    # the collective-latency floor divides by the expected accepted span
    # (shard_sim.FullSystemProjection.speculative). MODELED ONLY: the
    # CPU rank-sim cannot measure the K-row shard cost (PARITY.md carries
    # the honest N/A); the shard term is charged weight-bound-unchanged.
    spec_rows = {}
    for k in (2, 4, 8):
        spec_rows[f"k{k}"] = {
            f"alpha{a}": {
                "expected_tokens_per_dispatch": sp.expected_tokens,
                "ms_per_accepted_token": sp.ms_per_accepted_token,
                "speedup_vs_spec_off": round(sp.speedup, 2),
            }
            for a in (0.5, 0.7, 0.9)
            for sp in (proj.speculative(k, a),)}
    mid = proj.speculative(4, 0.7)
    print(f"speculative (modeled, {scheme} f32): K=4 alpha=0.7 -> "
          f"{mid.expected_tokens:.2f} tok/dispatch, "
          f"{mid.ms_per_accepted_token:.3f} ms/accepted token "
          f"({mid.speedup:.2f}x vs {proj.total_ms:.3f}); latency floor "
          f"{proj.ici_latency_ms:.3f} ms amortizes over the span "
          f"(measured accept rate needs a TPU session)", file=sys.stderr)

    def row(p):
        out = {
            "total_ms": round(p.total_ms, 3),
            "vs_baseline": round(baseline / p.total_ms, 2),
            "ici_bandwidth_ms_modeled": round(p.ici_bandwidth_ms, 3),
            "ici_latency_ms_modeled": round(p.ici_latency_ms, 3),
            "ici_gather_kb_per_chip_per_token":
                round(p.gather_bytes_per_chip / 1024, 1),
            "n_collectives_per_token": p.n_collectives,
            # shardcheck's memory model: does this config FIT the chip?
            "hbm_per_device_gib": p.hbm_per_device_gib,
            "hbm_headroom_gib": p.hbm_headroom_gib,
            "hbm_fits": p.hbm_fits,
        }
        if p.ici_hidden_ms:
            # overlap scheme: modeled collective time hidden behind
            # compute (total_ms already subtracts it — the overlap term)
            out["ici_hidden_ms_modeled"] = round(p.ici_hidden_ms, 3)
        return out

    schemes_out = {s: row(p) for s, p in by_scheme.items()}
    schemes_out["ref"]["note"] = ("parity anchor: the reference's "
                                  "4-gather MatmulSlice schedule")
    schemes_out["overlap"]["note"] = (
        "ring-decomposed combines (bitwise == fused); total subtracts the "
        "modeled hidden collective time — no capture has been held "
        "to it")
    if scheme != "ref":
        # APPEND: the overlap caveat above is load-bearing in archived
        # rows and must survive being the active scheme
        extra = ("rank compute measured under this scheme's band layout; "
                 "other schemes reuse it (identical FLOPs, different "
                 "wo/w2 bands)")
        prior = schemes_out[scheme].get("note")
        schemes_out[scheme]["note"] = (f"{prior}; {extra}" if prior
                                       else extra)
    return {
        "value": round(proj.total_ms, 3),
        "vs_baseline": round(baseline / proj.total_ms, 2),
        "tp_scheme": scheme,
        "shard_ms_measured": round(proj.shard_ms, 3),
        "ici_bandwidth_ms_modeled": round(proj.ici_bandwidth_ms, 3),
        "ici_latency_ms_modeled": round(proj.ici_latency_ms, 3),
        "ici_gather_kb_per_chip_per_token":
            round(proj.gather_bytes_per_chip / 1024, 1),
        "n_collectives_per_token": proj.n_collectives,
        "hbm_per_device_gib": proj.hbm_per_device_gib,
        "hbm_headroom_gib": proj.hbm_headroom_gib,
        "hbm_fits": proj.hbm_fits,
        "buffer_modes": {"f32": row(proj), "q80_wire": row(proj80)},
        "schemes_f32": schemes_out,
        "ici_latency_sensitivity_10x": lat10,
        "speculative_modeled": spec_rows,
    }


def _compact_summary(configs, rows, curve) -> dict:
    """The driver-parseable stdout line (VERDICT r4 #1): round 4's full
    table outgrew the driver protocol's capture (BENCH_r04 recorded a
    2000-char truncation -> parsed=null), so the stdout line now carries
    only the headline per row (ms, x-vs-reference, I/T on the tp rows) and
    the scaling table as [ms, x-vs-same-n] pairs; everything else lives in
    BENCH_FULL.json. A guard test pins the line length (test_bench_smoke)."""
    def brief(r):
        if "value" not in r:
            return {"error": r.get("error", "?")}
        b = {"ms": r["value"], "x": r["vs_baseline"]}
        if "shard_ms_measured" in r:  # tp rows: modeled ICI is the T analog
            b["I"] = r["shard_ms_measured"]
            b["T"] = round(r["ici_bandwidth_ms_modeled"]
                           + r["ici_latency_ms_modeled"], 3)
        return b

    out_rows = {cfg: brief(r) for cfg, r in rows.items()}
    scaling = {m: {n: [p["ms_per_token"], p["vs_reference_same_n"]]
                   for n, p in pts.items()}
               for m, pts in curve.items()} if curve else None
    head = rows.get(configs[0], {})
    out = {
        "metric": "llama2 q40 single-token decode (7b headline; tp rows: "
                  "I/T=measured rank/modeled ICI ms/token; full table: "
                  "BENCH_FULL.json)",
        "value": head["value"],
        "unit": "ms/token",
        "vs_baseline": head["vs_baseline"],
        "rows": out_rows,
    }
    if scaling:
        out["scaling_x_vs_same_n"] = scaling
    return out


def _run_all(args) -> int:
    """Default driver protocol (VERDICT r2 #1 + r3 #2): run the 7b, 13b,
    70b-tp8 configs plus the six {7b,13b}-tp{2,4,8} scaling rows — each in
    its OWN subprocess, so a 16 GB chip never holds two models' weights at
    once and a crash in one row cannot take down the others. The FULL
    table (every row field + the assembled scaling_curve) is written to
    BENCH_FULL.json in the repo; stdout gets ONE COMPACT line (VERDICT r4 #1 — round 4's full-table line overflowed
    the driver's capture and the round recorded parsed=null). The headline
    value/vs_baseline stay the 7B row, the chart the driver has tracked
    since round 1. DLLAMA_BENCH_CONFIGS overrides the config list (test
    hook; CI smokes the aggregation with 'small')."""
    import subprocess

    configs = [c for c in os.environ.get(
        "DLLAMA_BENCH_CONFIGS",
        "7b,13b,70b-tp8,7b-tp2,7b-tp4,7b-tp8,13b-tp2,13b-tp4,13b-tp8"
    ).split(",") if c]
    if not configs:
        raise SystemExit("DLLAMA_BENCH_CONFIGS is set but names no configs")
    rows: dict[str, dict] = {}
    for cfg in configs:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--config", cfg, "--samples", str(args.samples)]
        print(f"=== bench --config {cfg} ===", file=sys.stderr)
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        dt = time.perf_counter() - t0
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
            else ""
        if proc.returncode != 0 or not line.startswith("{"):
            print(f"--config {cfg} FAILED (rc={proc.returncode}) after "
                  f"{dt:.0f}s", file=sys.stderr)
            rows[cfg] = {"error": f"rc={proc.returncode}"}
            continue
        rows[cfg] = json.loads(line)
        print(f"--config {cfg}: {rows[cfg]['value']} ms/token "
              f"(x{rows[cfg]['vs_baseline']} vs reference; "
              f"{dt:.0f}s wall)", file=sys.stderr)
    head = rows.get(configs[0], {})
    failed = [cfg for cfg, r in rows.items() if "value" not in r]
    if "value" not in head:
        # headline row failed: emit what we have, fail the run loudly
        print(json.dumps({"metric": "llama2 q40 decode (headline FAILED)",
                          "value": -1.0, "unit": "ms/token",
                          "vs_baseline": 0.0, "rows": rows}))
        return 1
    curve = _scaling_curve(rows)
    full = {
        "metric": "llama2 q40 single-token decode "
                  "(7b headline; rows: " + "/".join(configs) + ")",
        "value": head["value"],
        "unit": "ms/token",
        "vs_baseline": head["vs_baseline"],
        "rows": rows,
    }
    if curve:
        full["scaling_curve"] = curve
    full_path = os.environ.get(
        "DLLAMA_BENCH_FULL_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_FULL.json"))
    try:
        with open(full_path, "w") as fh:
            json.dump(full, fh, indent=1)
            fh.write("\n")
        print(f"full table -> {full_path}", file=sys.stderr)
    except OSError as e:
        # hours of measured rows must survive a bad path/full disk: the
        # compact stdout line below is the record of last resort
        print(f"could not write {full_path} ({e}); full table lost, "
              f"compact line still emitted", file=sys.stderr)
    print(json.dumps(_compact_summary(configs, rows, curve)))
    if failed:
        # the table above is still the record of the rows that ran, but a
        # failed row fails the run: nothing reads exit 0 as "all measured"
        print(f"FAILED rows: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


# reference README.md:46-48 — ms/token per (model, device count)
_REF_CURVE = {"7b": {1: 1312.50, 2: 793.69, 4: 494.00, 8: 588.19},
              "13b": {2: 1497.19, 4: 848.19, 8: 1114.88}}


def _scaling_curve(rows: dict) -> dict:
    """Assemble the 1/2/4/8 scaling table (VERDICT r3 #2) from the row
    results: tp=1 is the measured single-chip config, tp>1 rows are
    measured-rank + modeled-ICI projections, each against the reference's
    SAME-device-count published figure (README.md:46-48) so the table
    reads exactly like the reference's — including where TP stops paying
    on each side."""
    curve: dict = {}
    for model in ("7b", "13b"):
        pts = {}
        one = rows.get(model, {})
        if "value" in one:
            pts["1"] = {"ms_per_token": one["value"],
                        "kind": "measured single chip",
                        "reference_ms": _REF_CURVE[model].get(1),
                        "vs_reference_same_n":
                            (round(_REF_CURVE[model][1] / one["value"], 2)
                             if 1 in _REF_CURVE[model] else None)}
        if "1" in pts:
            # the tp=1 13b row measures with a bf16 cache (f32 exceeds one
            # chip) while the rank rows run f32 — carry each point's basis
            # so the curve never silently mixes memory-traffic bases
            pts["1"]["kv_cache"] = one.get("kv_cache")
        for n in (2, 4, 8):
            r = rows.get(f"{model}-tp{n}", {})
            if "value" not in r:
                continue
            pts[str(n)] = {
                "ms_per_token": r["value"],
                "kind": "measured rank + modeled ICI",
                "kv_cache": r.get("kv_cache"),
                "shard_ms_measured": r.get("shard_ms_measured"),
                "ici_bandwidth_ms_modeled":
                    r.get("ici_bandwidth_ms_modeled"),
                "ici_latency_ms_modeled": r.get("ici_latency_ms_modeled"),
                "reference_ms": _REF_CURVE[model][n],
                "vs_reference_same_n":
                    round(_REF_CURVE[model][n] / r["value"], 2),
            }
        if pts:
            curve[model] = pts
    return curve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="all",
                    choices=("all", "7b", "13b", "70b-tp8", "small",
                             "7b-tp2", "7b-tp4", "7b-tp8",
                             "13b-tp2", "13b-tp4", "13b-tp8"),
                    help="benchmark workload (see module docstring); "
                         "'all' (the driver default) runs 7b+13b+70b-tp8 "
                         "plus the 7b/13b tp-rank scaling rows in "
                         "subprocesses and emits one combined JSON line")
    ap.add_argument("--small", action="store_true",
                    help="alias for --config small")
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--model", default=None,
                    help="bench a real .bin (Q40) instead of synthetic weights")
    ap.add_argument("--per-step", action="store_true",
                    help="time individual host-dispatched steps (reference "
                         "call pattern) instead of the fused device loop")
    args = ap.parse_args()
    if args.small:
        args.config = "small"
    if args.config == "all":
        if args.model or args.per_step:
            raise SystemExit("--model/--per-step need a single --config")
        raise SystemExit(_run_all(args))
    # "=0" means f32 for EVERY config (the 13b branch advertises it);
    # normalize once so the truthiness checks downstream can't invert it
    if os.environ.get("DLLAMA_BENCH_KV_BF16") == "0":
        del os.environ["DLLAMA_BENCH_KV_BF16"]

    import jax

    from distributed_llama_tpu.utils.chip import device_triple, require_tpu
    from distributed_llama_tpu.utils.compile_cache import (
        cache_error_count, enable_persistent_cache)

    cache_dir = enable_persistent_cache()
    # every config but the CI smoke reports device metrics: no TPU, no row
    dev = device_triple() if args.config == "small" else require_tpu()
    print(f"backend: {dev['platform']} x{dev['count']} ({dev['kind']}; "
          f"compile cache: {cache_dir})", file=sys.stderr)

    from distributed_llama_tpu.ops.quants import FloatType

    rank_tp = 0
    forced = False
    # best published reference figure per model (README.md:46-48) for the
    # single-chip rows; for the scaling rows (VERDICT r3 #2) the baseline
    # is the reference's SAME-DEVICE-COUNT figure, mirroring its 1/2/4/8
    # table — including the rows where the reference itself regresses
    # (7B@8: 588.19 > 494.00; 13B@8: 1114.88 > 848.19)
    _BASE = {"7b": (494.00, "llama2-7b-q40 single-token decode"),
             "small": (494.00, "llama2-7b-q40 single-token decode (small)"),
             "13b": (848.19, "llama2-13b-q40 single-token decode"),
             "70b-tp8": (4842.81,
                         "llama2-70b-q40 tp8 decode "
                         "(1-rank measured + modeled ICI)"),
             # scaling rows: baseline = _REF_CURVE[model][n], ONE source
             # of truth with the scaling_curve table
             **{f"{m}-tp{n}": (_REF_CURVE[m][n],
                               f"llama2-{m}-q40 tp{n} decode "
                               f"(1-rank measured + modeled ICI)")
                for m in ("7b", "13b") for n in (2, 4, 8)}}
    baseline, metric = _BASE[args.config]
    if "-tp" in args.config:
        if args.model:
            raise SystemExit(f"--config {args.config} benches one synthetic "
                             "rank; it cannot load a whole .bin (--model)")
        if args.per_step:
            raise SystemExit("--per-step times host dispatch, not rank "
                             "compute; it cannot feed a rank projection")
    if args.model:
        # sidecar-cached load (VERDICT r4 #7): the second --model run
        # memory-maps the pre-tiled kernel tree and skips the GB-scale
        # host re-tiling (--config tp rows already rejected --model above)
        from distributed_llama_tpu.io.kernel_cache import load_model_packed

        spec, params = load_model_packed(args.model,
                                         weights_float_type=FloatType.Q40)
    else:
        from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                        llama2_13b_spec,
                                                        llama2_70b_spec,
                                                        small_bench_spec,
                                                        synth_q40_fast)

        forced = True  # synthetic values: junk argmax must not truncate
        if args.config == "small":
            spec, params = small_bench_spec(), None
        elif args.config == "13b":
            spec, params = llama2_13b_spec(), None
            # 13B MHA@2048 + tile-padded Q40 weights exceeds one 16 GB chip
            # with an f32 cache — bf16 is the documented basis for this row
            # (recorded in the JSON); export DLLAMA_BENCH_KV_BF16=0 to try
            # f32 anyway
            if os.environ.get("DLLAMA_BENCH_KV_BF16") is None:
                os.environ["DLLAMA_BENCH_KV_BF16"] = "1"
                print("13b: defaulting to bf16 KV cache (f32 exceeds one "
                      "16 GB chip)", file=sys.stderr)
        elif args.config == "70b-tp8":
            from distributed_llama_tpu.parallel.shard_sim import synth_rank_q40

            spec, rank_tp = llama2_70b_spec(), 8
            # f16 embedding halves the 1 GB replicated table; one row
            # read/token, timing-neutral
            params = functools.partial(synth_rank_q40, spec, rank_tp,
                                       embed_dtype=np.float16)
        elif "-tp" in args.config:
            # scaling-curve rows (VERDICT r3 #2): ONE tp-rank of 7B/13B,
            # measured whole on the real chip like the 70b-tp8 row; the
            # per-point ICI model is added by _project_tp below
            from distributed_llama_tpu.parallel.shard_sim import synth_rank_q40

            model_name, tp_name = args.config.split("-tp")
            spec = llama2_7b_spec() if model_name == "7b" \
                else llama2_13b_spec()
            rank_tp = int(tp_name)
            params = functools.partial(synth_rank_q40, spec, rank_tp)
        else:
            spec, params = llama2_7b_spec(), None
        if params is None:
            # a BUILDER, not a tree: _bench's shape-manifest cache skips the
            # host synth entirely on warm runs (the values are regenerated
            # on device either way)
            params = functools.partial(synth_q40_fast, spec)

    ms, executed = _bench(spec, params, args.samples, per_step=args.per_step,
                          rank_tp=rank_tp, forced=forced)
    result = {
        "metric": metric,
        "value": round(ms, 3),
        "unit": "ms/token",
        "vs_baseline": round(baseline / ms, 2),
        "samples": args.samples,  # reference protocol = 16 (--samples 16)
        # the ms/token denominator: < samples when the greedy chain
        # BOS-terminated early (possible with real weights)
        "executed": executed,
        # f32 is the reference-parity cache; DLLAMA_BENCH_KV_BF16=1 halves
        # it (13B MHA @2048 ctx + Q40 weights exceeds a 16 GB chip at f32 —
        # recorded here so the comparison basis is explicit)
        "kv_cache": ("bf16" if os.environ.get("DLLAMA_BENCH_KV_BF16")
                     else "f32"),
        "device": dev,
        "cache_errors": cache_error_count(),
        **_STARTUP,
    }
    # the reference benchmark line carries socket kB/token; ours carries the
    # analytic per-chip ICI collective bytes (parallel/comm_stats) — 0/0 on
    # a single chip, the per-rank collective budget on tp rows (under the
    # active DLLAMA_TP_SCHEME)
    from distributed_llama_tpu.parallel.comm_stats import ici_all_gather_bytes

    comm = ici_all_gather_bytes(spec, rank_tp or 1)
    result["ici_bytes_per_token"] = {"sent": comm.sent_bytes,
                                     "recv": comm.recv_bytes}
    # session drift defense (ISSUE 3): every row says where it was measured
    result["env_fingerprint"] = _env_fingerprint()
    if rank_tp:
        result.update(_project_tp(spec, rank_tp, ms, baseline))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
