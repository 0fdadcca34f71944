"""Tensor-parallel forward: two collective schemes as one shard_map program.

``DLLAMA_TP_SCHEME`` selects the per-layer collective schedule
(comm_stats.tp_scheme; default ``fused``):

**ref** — the reference's MatmulSlice port (src/transformer.cpp:14-50):
every one of the 7 per-layer matmuls is sharded along its OUTPUT dim into
contiguous row bands, one band per tp-mesh coordinate, and 4 all_gathers
per layer stitch the bands back together. The bit-parity anchor against the
reference binaries.

Collective map, ref scheme (ours ⇄ reference transformer-tasks.cpp):
  all_gather(att out)   ⇄ quantizeMultiheadAtt+syncMultiheadAtt broadcast (:280-290)
  all_gather(wo out)    ⇄ syncAtt gather + next broadcast      (:303-315)
  all_gather(ffn hb)    ⇄ syncFfnA gather + syncFfnB star all-gather (:389-399,
                           O(S^2) on the wire there; one ICI all_gather here)
  all_gather(w2 out)    ⇄ syncFfn2 gather (:417-427)
  all_gather(logits)    ⇄ (none: reference wcls is root-only, :474-483; we
                           shard the vocab dim too)

**fused** — the Megatron-LM pairing (Shoeybi et al. 2019; Pope et al. 2022):
the INPUT matmuls of each block stay column-parallel (output-dim bands, as
in ref), but ``wo`` and ``w2`` re-shard along their INPUT dim, so each block
ends in a row-parallel matmul whose full-width outputs are partial sums —
combined with ONE collective per block instead of two. 2 collectives per
layer (f32 buffers), halving the per-collective launch latency that
dominates the multi-chip T term (BENCH_r05: 13b-tp8 paid 1.127 of 1.174 ms
in launch latency across 161 collectives/token).

Collective map, fused scheme (ours ⇄ reference transformer-tasks.cpp):
  (local _wire quant)   ⇄ quantizeMultiheadAtt (:280; no wire here — the
                           attention out is already rank-local)
  psum(wo partials)     ⇄ syncMultiheadAtt + syncAtt collapsed (:280-315)
  (local _wire quant)   ⇄ quantizeFfnA (:389; hb never crosses the wire)
  psum(w2 partials)     ⇄ syncFfnA + syncFfnB + syncFfn2 collapsed (:389-427)
  all_gather(logits)    ⇄ (none; as above)
Under Q80 buffers each psum decomposes into psum_scatter (f32 — partial
sums cannot ride the wire quantized without compounding per-shard rounding)
+ the SAME packed-Q80 ``_wire_gather`` the ref scheme uses, so the wire-
quantization cut point of the reference is preserved on the gather half.

**overlap** — the fused layout with latency-hiding collectives (ISSUE 10;
the collective-matmul decomposition lineage of Wang et al., ASPLOS '23).
Param layout, matmuls, and quantization cut points are EXACTLY the fused
scheme's; only the combines change shape:

* each block combine's reduce half is RING-DECOMPOSED (``_ici_ring_reduce``):
  the full-width row-parallel partial splits into tp chunks, and chunk
  ``k``'s shift-by-k ``ppermute`` hop (1 ICI hop; ``_ici_ppermute``) carries
  it straight to its owner rank while the combine's remaining chunk sends
  and the surrounding wo/w2/next-block matmuls proceed — the hops have no
  data dependency on each other, so the XLA latency-hiding scheduler can
  run them all concurrently with compute. Received chunks land in a
  rank-indexed stash summed in ASCENDING RANK ORDER — the same
  deterministic left-fold XLA's all_reduce applies — so the overlap scheme
  is BITWISE equal to the fused scheme (pinned by
  tests/test_overlap_scheme.py across f32/Q80/Q40 and
  contiguous/paged/speculative layouts);
* the ffn combine's gather half is DOUBLE-BUFFERED: layer N issues the
  gather (packed Q80 wire bytes, or the f32 band concat) and carries the
  un-consumed buffer through the scan; layer N+1 dequantizes and applies
  the residual add at its top, so the gather overlaps layer N+1's qkv
  matmuls. Two staging buffers are live at once (the carried layer-N
  output and the in-flight layer-N+1 gather) — the chunked-staging HBM
  charge in comm_stats.collective_staging_bytes. The attention combine's
  gather is consumed in-layer (the ffn rmsnorm needs x immediately) and
  stays on the critical path — the exposed remainder
  shard_sim.project_full_system's overlap term models.

Collective census per layer: 2*(tp-1) ppermutes + 2 all_gathers (vs the
fused scheme's 2 psums f32 / 2 scatter+gather pairs Q80) — MORE launches,
but each ppermute is one ring hop hidden behind compute, which is what
a capture has to show (no cell measures this scheme). Requires dim/tp to
divide (the ring chunks the residual width) and sp == 1.

In both schemes the reference's syncRmsAtt broadcast (:161) disappears: x is
replicated, every device computes the (cheap) rmsnorm itself. Attention runs
fully head-parallel with the KV cache sharded over kv heads — the idiomatic
upgrade over the reference's root-only attention (transformer-tasks.cpp:
206-278), with identical math — in both schemes (q/k/v are always
output-dim bands).

With buffer_float_type == Q80 every all_gather moves the ACTUAL Q80 payload —
int8 codes + f16 block deltas, 34 bytes per 32 values (_wire_gather) — the
wire-quantization the reference applies in its quantize*/sync* task pairs,
reproduced at the same cut points with the same ~4x transfer cut
(README.md:67-69); dequantization happens after the gather, so values match
the round-1 quantize-dequantize-then-gather scheme bit for bit.

The collective map is load-bearing in four places that must move together:
this forward, the analytic model (parallel/comm_stats.py), the jaxpr
contract (analysis/jaxpr_contracts.py J001), and the bench projection
(parallel/shard_sim.py). dlint D006 flags any collective added here outside
the _ici_* helpers those four know about.

Requirements: tp divides n_heads, n_kv_heads, hidden_dim, vocab_size (the
reference's analogous constraint is `assert(d % nSlices == 0)`,
transformer.cpp:15); the fused scheme additionally needs dim/tp and
hidden_dim/tp to be 32-block multiples when weights are Q40 (wo/w2 shard
along their quantized input axis) or buffers are Q80.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..io.loader import Q40Kernel, Q40KernelNb, Q40Weight
from ..models.llama import (KVCache, PagedKVQ8, attention_core,
                            batch_decode_attention, causal_cache_mask,
                            layer_view, mixed_attention, paged_attention_q8,
                            paged_cache_planes, paged_decode_attention,
                            rebuild_paged_cache, rope_rotate,
                            spec_verify_attention, split_layer_weights)
from ..models.spec import TransformerSpec
# canonical trace-scope names (obs/spans.py): every phase and collective
# scope this forward emits is a name a reader of captures buckets by — the
# attribution contract lives THERE, the emission lives HERE
from ..obs.spans import (SCOPE_ATTN, SCOPE_EMBED, SCOPE_FFN, SCOPE_ICI_GATHER,
                         SCOPE_ICI_PPERMUTE, SCOPE_ICI_PSUM,
                         SCOPE_ICI_SCATTER, SCOPE_LAYER, SCOPE_LOGITS,
                         named_program, startup_phase, startup_placed)
from ..ops.linear import fake_quant_q80, matmul, rmsnorm, silu
from ..ops.quants import (QK, FloatType, dequantize_q80_jax,
                          quantize_q80_jax)
from .comm_stats import tp_scheme

# every program here is manual-axes code that does its own replication
# bookkeeping; the varying-manual-axes check rejects it
_shard_map = functools.partial(jax.shard_map, check_vma=False)

# params tree -> PartitionSpec for the stacked arrays (layer axis leading).
# Output-dim sharding = axis 1 for per-layer matmuls, axis 0 for wcls.
_MATMUL_SPECS = {
    "wq": P(None, "tp", None), "wk": P(None, "tp", None),
    "wv": P(None, "tp", None), "wo": P(None, "tp", None),
    "w1": P(None, "tp", None), "w2": P(None, "tp", None),
    "w3": P(None, "tp", None),
    # NOTE: the fused leaves are output bands like their members, which is
    # right for ONE concat only: the rank-major one, where rank r's
    # contiguous band is [q_r | k_r | v_r] ([w1_r | w3_r]), the rows _tp_qkv
    # / _swiglu_local split. shard_params makes it, after the tp-aware pack
    # (ops/linear.fuse_q40_layer_matmuls over n_tp ranks), a shard at a time.
    # A concat over the WHOLE leaf ([q; k; v], what one chip and shard_sim's
    # rank-local tree hold) would hand rank 0 only q rows, silently wrong:
    # a tree that reaches shard_params already fused is refused there.
    "wqkv": P(None, "tp", None), "w13": P(None, "tp", None),
    "wcls": P("tp", None),
}
# what shard_params fuses a layer's wq/wk/wv and w1/w3 into, a rank
FUSED_GROUPS = ("wqkv", "w13")
_REPL_SPECS = {
    "tok_embedding": P(), "rms_att": P(), "rms_ffn": P(), "rms_final": P(),
}
# fused/overlap schemes: wo/w2 re-shard along their INPUT dim (axis 2 of the
# stacked (L, d_out, n_in) array) — row-parallel matmuls whose outputs are
# partial sums, combined by _combine (fused) or _ici_ring_reduce + gather
# (overlap; same layout, ring-decomposed combine). For Q40 leaves the input
# axis is the nb block axis, so n_in/tp must stay a 32-multiple (checked in
# shard_params).
_FUSED_OVERRIDES = {"wo": P(None, None, "tp"), "w2": P(None, None, "tp")}
# the keys pack_q40_params must judge on shard-LOCAL input width (fused)
FUSED_INPUT_SHARDED = frozenset(_FUSED_OVERRIDES)
# schemes sharing the fused wo/w2 input-band layout
_INPUT_SHARDED_SCHEMES = ("fused", "overlap")


def param_specs(params: dict[str, Any],
                scheme: str | None = None) -> dict[str, Any]:
    scheme = scheme or tp_scheme()
    if any(name.startswith("moe_") for name in params):
        from ..ops.linear import MOE_TP_REFUSAL

        raise ValueError(MOE_TP_REFUSAL)  # refuse, never mis-shard an expert
    if "w_gate" in params:
        from ..ops.retention import TP_REFUSAL

        raise ValueError(TP_REFUSAL)      # nor a retention spec's state
    if "mamba" in params:
        from ..ops.mamba import TP_REFUSAL

        raise ValueError(TP_REFUSAL)      # nor a hybrid spec's slot
    if "sliding" in params:
        from ..ops.linear import MIXERS_TP_REFUSAL

        raise ValueError(MIXERS_TP_REFUSAL)   # nor a mixer-kinds spec's
    specs: dict[str, Any] = {}
    for name, val in params.items():
        spec = _MATMUL_SPECS.get(name) or _REPL_SPECS.get(name)
        if scheme in _INPUT_SHARDED_SCHEMES:
            spec = _FUSED_OVERRIDES.get(name, spec)
        if spec is None:
            raise KeyError(f"unknown param {name}")
        if isinstance(val, Q40Weight):
            # qs (L, d, nb, 16) and d16 (L, d, nb) shard the same logical
            # axis the spec names — d (output bands) or, for the fused
            # scheme's wo/w2, nb (input-block bands)
            extra = len(val.qs.shape) - len(spec)
            qs_spec = P(*spec, *([None] * extra))
            d_spec = P(*spec, *([None] * (len(val.d16.shape) - len(spec))))
            specs[name] = Q40Weight(qs_spec, d_spec)
        elif isinstance(val, Q40Kernel):
            # qs_t (..., 16, d, nb): the sharded d axis moves to -2, with the
            # nibble-plane axis inserted before it; scale (..., d, nb) keeps
            # the logical spec shape
            base = tuple(spec)
            qs_spec = P(*base[:-2], None, *base[-2:])
            d_spec = P(*base, *([None] * (len(val.scale.shape) - len(base))))
            specs[name] = Q40Kernel(qs_spec, d_spec)
        elif isinstance(val, Q40KernelNb):
            # qs_t (..., 16, nb, d) and scale (..., nb, d): the transpose
            # carries the sharded axis with it — output bands on the LAST
            # axis, the fused scheme's input bands on the nb axis
            *lead, ax_d, ax_n = spec
            specs[name] = Q40KernelNb(P(*lead, None, ax_n, ax_d),
                                      P(*lead, ax_n, ax_d))
        else:
            specs[name] = spec
    return specs


# cache (L, S, n_kv, hs): sequence chunks over sp, kv heads over tp
CACHE_SPEC = KVCache(P(None, "sp", "tp", None), P(None, "sp", "tp", None))


def spec_axis_names(spec) -> dict:
    """A PartitionSpec as ``{axis index: (mesh axes...)}``, unsharded axes
    left out — the row shape of expected_shard_names, and what
    analysis/shardcheck.py flattens a traced shard_map's in_specs to."""
    return {i: tuple(ax) if isinstance(ax, (tuple, list)) else (ax,)
            for i, ax in enumerate(spec) if ax is not None}


def expected_shard_names(params: dict[str, Any], scheme: str | None = None):
    """The sharding contract as flat, machine-checkable rows: one
    ``(leaf_name, {axis_index: (mesh_axis, ...)})`` per leaf of the
    (params, cache, tokens, pos) argument tree of make_sharded_forward, in
    tree-flatten order — exactly the ``in_names`` jax's shard_map records
    per operand in the traced program. analysis/shardcheck.py verifies the
    trace against THIS export (contract J004), so the declared layout and
    the checked layout come from one place: the spec tables above.
    ``params`` may be abstract (ShapeDtypeStruct leaves)."""
    import jax

    specs = (param_specs(params, scheme), CACHE_SPEC, P(), P())
    is_p = lambda x: isinstance(x, P)  # noqa: E731 - local predicate
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=is_p)
    rows = []
    for path, spec in leaves_with_path:
        name = jax.tree_util.keystr(path)
        rows.append((name, spec_axis_names(spec)))
    return rows


# shard_params copies a shard at least this large on several threads
_CUT_THREAD_BYTES = 1 << 26


def shard_params(params: dict[str, Any], mesh: Mesh,
                 scheme: str | None = None) -> dict[str, Any]:
    """Place the param tree with the active scheme's shardings (ref:
    MatmulSlice output-dim bands everywhere; fused: wo/w2 input-dim bands).

    Q40 weights are re-tiled to the Pallas kernel layout first (host side,
    once) when the Q40 fast path is active, each leaf laid out by
    ops/linear.q40_leaf_layout on its shard-local shape, and a layer's
    wq/wk/wv (w1/w3) kernel leaves are then fused RANK-MAJOR into wqkv (w13)
    where ops/linear.fuse_q40_layer_matmuls's rule lets them: one kernel
    call a group, the fused shard assembled in the one host copy a shard
    gets anyway. Placement goes
    through ``make_array_from_callback``, not ``device_put``: each process
    materializes ONLY its addressable shards (a multi-host device_put both
    asserts bitwise-equal full values on every host — which slice-streamed
    weights deliberately violate, their unfetched bands being zeros — and
    would ship n_hosts copies of every tensor across the wire).
    """
    import sys

    from ..ops.linear import fuse_q40_layer_matmuls, pack_q40_params

    scheme = scheme or tp_scheme()
    n_tp = mesh.shape["tp"]
    whole = [k for k in FUSED_GROUPS if k in params]
    if whole:
        raise ValueError(
            f"{' '.join(whole)}: a tree fused over the WHOLE leaf cannot be "
            f"sharded (a contiguous tp band of a [q; k; v] concat is not a "
            f"rank's [q_r | k_r | v_r]): hand shard_params the members, it "
            f"fuses them a rank")
    if scheme in _INPUT_SHARDED_SCHEMES and n_tp > 1:
        # quantized wo/w2 shard along their nb block axis: fail with the
        # clear constraint here, not a sharding traceback mid-device_put
        for name in FUSED_INPUT_SHARDED:
            v = params.get(name)
            if isinstance(v, Q40Weight) and v.qs.shape[-2] % n_tp:
                raise ValueError(
                    f"{name}: {scheme} tp scheme shards the input dim, but "
                    f"{v.qs.shape[-2]} Q40 blocks do not divide over "
                    f"tp={n_tp} (need input_dim/tp to be a 32-multiple)")
    params = pack_q40_params(           # the start-up account's ``pack``
        params, tp=n_tp,
        input_sharded=(FUSED_INPUT_SHARDED
                       if scheme in _INPUT_SHARDED_SCHEMES else ()))
    params = fuse_q40_layer_matmuls(params, ranks=n_tp)
    layouts = {label: [k for k, v in params.items() if isinstance(v, kind)]
               for label, kind in (("nb-major", Q40KernelNb),
                                   ("d-major", Q40Kernel),
                                   ("codec", Q40Weight))}
    if n_tp > 1 and (layouts["nb-major"] or layouts["d-major"]):
        # unconditionally, as the one-chip policy note: a silent layout
        # change would make runs incomparable
        picks = "; ".join(f"{label}: {' '.join(keys)}"
                          for label, keys in layouts.items() if keys)
        # the one-row body of each shard, as the one-chip line counts it
        # (ops/linear.t1_bodies): an nb-major leaf whose LOCAL block count
        # is a multiple of 8 takes the MXU matvec
        from ..ops.pallas_q40 import _t1_mxu

        sharded_in = (FUSED_INPUT_SHARDED
                      if scheme in _INPUT_SHARDED_SCHEMES else ())
        mxu = sum(_t1_mxu(params[k].qs_t.shape[-2]
                          // (n_tp if k in sharded_in else 1))
                  for k in layouts["nb-major"])
        groups = [k for k in FUSED_GROUPS if k in params]
        print(f"💡 Q40 sharded layout: {picks} (tp={n_tp} {scheme}; a "
              f"shard-local block count off the 128 grid packs nb-major; "
              f"t1 mxu {mxu}/{sum(map(len, layouts.values()))}; fused: "
              f"{' '.join(groups) or 'none'}; "
              f"{sum(k in params for k in LAYER_KEYS[2:])} Q40 calls a "
              f"layer)", file=sys.stderr)
    return place_params(params, mesh, scheme)


def place_params(params: dict[str, Any], mesh: Mesh,
                 scheme: str) -> dict[str, Any]:
    """``shard_params``'s placement alone: the tree as it is handed over
    (packed, fused or neither) onto the mesh by ``param_specs``, a shard a
    host copy."""
    import concurrent.futures
    import os

    import numpy as np

    from ..ops.linear import RankMajor

    specs = param_specs(params, scheme)
    threads = min(16, os.cpu_count() or 1)

    def cut(a, idx):
        # host tree by contract (loader/synth/pack all emit numpy): this
        # contiguous copy of one shard is the one conversion point, and
        # where a rank-major leaf's shard is assembled from its members'
        # bands. It is bound by first-touch page faults, not bandwidth
        # (0.75 GB/s on one thread: 28 of Yi-34B's 38 s of placement), so
        # bands of a large shard's leading axis go to threads
        if isinstance(a, RankMajor):
            rank, shape = a.rank_of(idx), a.band_shape

            def copy(out, lo, hi):
                a.band(rank, slice(lo, hi), out[lo:hi])
        else:
            view = a[idx]
            shape = view.shape
            if view.ndim < 2 or view.nbytes < _CUT_THREAD_BYTES:
                return np.ascontiguousarray(view)

            def copy(out, lo, hi):
                out[lo:hi] = view[lo:hi]

        out = np.empty(shape, a.dtype)
        if out.nbytes < _CUT_THREAD_BYTES:
            copy(out, 0, len(out))
        else:
            bands = np.linspace(0, len(out), threads + 1).astype(int)
            list(pool.map(functools.partial(copy, out), bands[:-1],
                          bands[1:]))
        return out

    def put(a, s):
        return jax.make_array_from_callback(
            np.shape(a), NamedSharding(mesh, s),
            lambda idx, a=a: cut(a, idx))

    # ``make_array_from_callback`` cuts each shard on the host before it
    # returns: ``place`` holds the copies, the transfers are enqueued and
    # run behind the next leaf's copies (the tree's order: the largest
    # leaves first read 3.3 s MORE of Yi-34B's placement, PERF.md PR 56)
    with startup_phase("place"), \
            concurrent.futures.ThreadPoolExecutor(threads) as pool:
        placed = jax.tree_util.tree_map(put, params, specs)
    startup_placed(placed)
    return placed


def shard_cache(cache: KVCache, mesh: Mesh) -> KVCache:
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        cache, CACHE_SPEC)


def _wire(spec: TransformerSpec, x: jax.Array) -> jax.Array:
    """Quantize a tensor consumed locally in Q80 buffer mode (the reference
    quantizes xb before the qkv matmuls even single-node, quantizeRmsAtt —
    there is no collective at this cut, so quantize-dequantize in place)."""
    if spec.buffer_float_type == FloatType.Q80:
        return fake_quant_q80(x)
    return x


def _ici_gather(a: jax.Array, axis: int) -> jax.Array:
    """The tp gather collective: all_gather over the mesh axis, shard order
    = band order. Layer-program builders take this as a ``gather_fn``
    parameter so parallel/shard_sim.py can swap in a local band-tile and run
    ONE rank's exact program on a single chip (the 70B measurement path).

    _ici_gather/_ici_psum/_ici_scatter are the ONLY places the tp forward
    may issue a collective: comm_stats models exactly these, J001 pins the
    traced program to that model, and dlint D006 flags any jax.lax
    collective in this module outside the three helpers. Each helper emits
    its named scope (obs/spans.COLLECTIVE_SCOPE_KINDS), so a profiler
    capture labels every collective with the budget kind it must
    reconcile against — BOTH schemes are labeled at source."""
    with jax.named_scope(SCOPE_ICI_GATHER):
        return jax.lax.all_gather(a, "tp", axis=axis, tiled=True)


def _ici_psum(a: jax.Array) -> jax.Array:
    """The fused scheme's f32 combine: ONE all_reduce of the row-parallel
    partial block outputs over tp (swappable like _ici_gather; shard_sim
    substitutes identity — the local partial already has the full shape)."""
    with jax.named_scope(SCOPE_ICI_PSUM):
        return jax.lax.psum(a, "tp")


def _ici_scatter(a: jax.Array, axis: int) -> jax.Array:
    """The fused scheme's Q80 reduce half: psum_scatter leaves each device
    the EXACT f32 sum of its band of ``axis`` (band order = shard order),
    which _wire_gather then moves as the packed Q80 payload."""
    with jax.named_scope(SCOPE_ICI_SCATTER):
        return jax.lax.psum_scatter(a, "tp", scatter_dimension=axis,
                                    tiled=True)


def _ici_ppermute(a: jax.Array, shift: int, n_slices: int) -> jax.Array:
    """The overlap scheme's ring hop: a shift-by-``shift`` collective
    permute over the tp axis (rank i -> rank (i+shift) mod S). ONE launch
    per hop, no reduction — the cheapest collective the mesh has, and the
    only one with no serialization against compute (comm_stats charges it
    1 hop of latency; the ring budget kind is 'ppermute'). Swappable like
    the other _ici_* hooks so shard_sim can run the overlap program with
    an identity stand-in."""
    perm = [(i, (i + shift) % n_slices) for i in range(n_slices)]
    with jax.named_scope(SCOPE_ICI_PPERMUTE):
        return jax.lax.ppermute(a, "tp", perm)


def _tp_rank():
    """This shard's tp coordinate (swappable: shard_sim substitutes a
    constant 0 — the sim runs outside any mesh axis)."""
    return jax.lax.axis_index("tp")


def _ici_ring_reduce(part: jax.Array, n_slices: int,
                     permute_fn=_ici_ppermute,
                     rank_fn=_tp_rank) -> jax.Array:
    """The overlap scheme's block-combine reduce: decompose the full-width
    row-parallel partial ``part`` (..., W) into ``n_slices`` chunks and
    ring them home — rank d sends chunk (d+k) mod S via a shift-by-k
    ppermute at step k, so every chunk makes exactly ONE launch straight
    to its owner while the later chunks' sends (and the surrounding
    matmuls — nothing here depends on them) overlap it. Each rank
    collects the S partial terms of ITS chunk into a rank-indexed stash
    and sums them in ASCENDING RANK ORDER — the deterministic f32
    left-fold XLA's all_reduce/reduce_scatter applies — so the returned
    (..., W/S) band is BITWISE the fused scheme's psum_scatter band (and
    the band-concat equals the fused psum output bit for bit; pinned by
    tests/test_overlap_scheme.py).

    Per-chip bytes: S-1 chunk payloads sent = (S-1)/S of the full payload
    — exactly the fused reduce_scatter's ring accounting
    (comm_stats.tp_collective_budget, 'ppermute' entry). Only sanctioned
    collective site: the ppermute binds inside _ici_ppermute (dlint D006
    blesses the _ici_* family and nothing else)."""
    s = n_slices
    if s == 1:
        return part
    chunk = part.shape[-1] // s
    d = rank_fn()
    own = jax.lax.dynamic_slice_in_dim(part, d * chunk, chunk, axis=-1)
    stash = jnp.zeros((s, *own.shape), own.dtype)
    stash = jax.lax.dynamic_update_slice_in_dim(stash, own[None],
                                                jnp.mod(d, s), axis=0)
    for k in range(1, s):
        send = jax.lax.dynamic_slice_in_dim(
            part, jnp.mod(d + k, s) * chunk, chunk, axis=-1)
        recv = permute_fn(send, k, s)  # arrives from rank (d - k) mod S
        stash = jax.lax.dynamic_update_slice_in_dim(
            stash, recv[None], jnp.mod(d - k, s), axis=0)
    acc = stash[0]
    for j in range(1, s):  # rank-order left fold — the determinism pin
        acc = acc + stash[j]
    return acc


def _gather(x: jax.Array, gather_fn=_ici_gather) -> jax.Array:
    """Concatenate the tp bands along the feature axis (device-order bands =
    MatmulSlice's contiguous row bands)."""
    return gather_fn(x, x.ndim - 1)


def _wire_gather(spec: TransformerSpec, x: jax.Array,
                 gather_fn=_ici_gather) -> jax.Array:
    """Move a shard-local band across the tp 'wire' into a full vector.

    Under buffer_float_type == Q80 the collective carries the REAL quantized
    payload — int8 codes + one f16 delta per 32-block, 34 bytes per 32
    values, a ~3.8x wire-byte cut vs f32 — exactly the transfer compression
    the reference implements in its quantize*/sync* task pairs
    (transformer-tasks.cpp:97-136; byte tables README.md:67-69). Codes and
    deltas are packed into ONE gathered uint8 buffer of contiguous 34-byte
    blocks (the reference's wire block layout, quants.hpp:21-24), so each
    cut issues a single collective — per-collective launch latency, the
    dominant term of the 70B ICI budget, is paid once per cut instead of
    twice (VERDICT r2 #4). Values are identical to
    quantize->dequantize->gather (packing is a lossless bitcast, the gather
    reorders nothing within a shard, and validate_sharding pins shard width
    to a 32-block multiple), so tp parity gates are unchanged. comm_stats
    reports these same byte counts — what actually crosses ICI.
    """
    return _wire_unpack(spec, _gather(_wire_pack(spec, x), gather_fn))


def _wire_pack(spec: TransformerSpec, x: jax.Array) -> jax.Array:
    """The quantize+pack half of the wire cut: Q80 buffers pack ``x`` into
    the reference's contiguous 34-byte block layout (int8 codes + f16
    delta per 32 values — _wire_gather docstring); f32 buffers pass
    through. Split out so the overlap scheme can gather the packed bytes
    in layer N and defer _wire_unpack to layer N+1 (the double-buffered
    gather) without duplicating the byte layout."""
    if spec.buffer_float_type == FloatType.Q80:
        qs, d = quantize_q80_jax(x)  # (..., nb, 32) int8, (..., nb) f16
        nb = qs.shape[-2]
        blocks = jnp.concatenate(
            [jax.lax.bitcast_convert_type(qs, jnp.uint8),       # (..., nb, 32)
             jax.lax.bitcast_convert_type(d, jnp.uint8)],       # (..., nb, 2)
            axis=-1)                                            # (..., nb, 34)
        return blocks.reshape(*blocks.shape[:-2], nb * 34)
    return x


def _wire_unpack(spec: TransformerSpec, wire: jax.Array) -> jax.Array:
    """Invert _wire_pack (after any gather/concat of packed shards: 34-byte
    blocks concatenate cleanly, so shard order is value order). Lossless
    bitcasts + the same dequantize the in-line path applies — values are
    identical wherever the unpack runs, which is what lets the overlap
    scheme defer it across the layer boundary."""
    if spec.buffer_float_type == FloatType.Q80:
        nb = wire.shape[-1] // 34
        blocks = wire.reshape(*wire.shape[:-1], nb, 34)
        qs = jax.lax.bitcast_convert_type(blocks[..., :32], jnp.int8)
        d = jax.lax.bitcast_convert_type(blocks[..., 32:], jnp.float16)
        return dequantize_q80_jax(qs, d)               # (..., nb*32)
    return wire


def _tp_qkv(spec: TransformerSpec, n_slices: int, lw, x, positions):
    """Shard-local attention input path: norm -> (q80 wire) -> local q/k/v
    bands -> RoPE. x is the replicated activations, (T, dim) or (B, dim).

    Contiguous-band slicing => local features start at a head boundary, and
    RoPE's angle depends only on (feature index mod head_size): local == global.
    """
    xb = rmsnorm(x, lw["rms_att"])
    xb = _wire(spec, xb)  # reference quantizes xb before qkv (quantizeRmsAtt)
    if "wqkv" in lw:  # load-time fused local bands (one kernel call)
        d_loc = spec.dim // n_slices
        kv_loc = spec.kv_dim // n_slices
        qkv = matmul(lw["wqkv"], xb)
        q = qkv[..., :d_loc]
        k = qkv[..., d_loc:d_loc + kv_loc]
        v = qkv[..., d_loc + kv_loc:]
    else:
        q = matmul(lw["wq"], xb)                   # (T, dim/S)
        k = matmul(lw["wk"], xb)                   # (T, kvDim/S)
        v = matmul(lw["wv"], xb)
    q = rope_rotate(q, positions, spec.head_size)
    k = rope_rotate(k, positions, spec.head_size)
    return q, k, v


def _combine(spec: TransformerSpec, part: jax.Array,
             gather_fn=_ici_gather, psum_fn=_ici_psum,
             scatter_fn=_ici_scatter) -> jax.Array:
    """Fused-scheme block combine: sum the row-parallel partial outputs.

    F32 buffers: ONE psum — the Megatron combine, half the ref scheme's
    collective launches per block. Q80 buffers: psum_scatter in f32 (the
    sums must be exact before quantization — quantizing per-shard partials
    would compound S rounding errors into the total), then _wire_gather, so
    the gather half carries the reference's packed int8+f16 wire payload at
    the same quantization cut point as the ref scheme."""
    if spec.buffer_float_type == FloatType.Q80:
        shard = scatter_fn(part, part.ndim - 1)    # (T, dim/S) exact sums
        return _wire_gather(spec, shard, gather_fn)
    return psum_fn(part)


def _swiglu_local(lw, xb):
    """Shard-local SwiGLU input bands (w1/w3, or the load-time-fused w13):
    (T, hidden/S) — shared by both schemes' tails."""
    if "w13" in lw:  # fused local SwiGLU input bands
        h13 = matmul(lw["w13"], xb)
        hid_loc = h13.shape[-1] // 2
        return silu(h13[..., :hid_loc]) * h13[..., hid_loc:]
    return silu(matmul(lw["w1"], xb)) * matmul(lw["w3"], xb)


def _deferred_init(spec: TransformerSpec, t_len: int):
    """The overlap scheme's dummy layer-(-1) pending buffer: the carried
    ffn-combine gather output shape — packed Q80 wire bytes or the f32
    vector. Layer 0 never consumes it (_consume_deferred selects the raw
    carry there), so the zeros are schedule filler, not values."""
    if spec.buffer_float_type == FloatType.Q80:
        return jnp.zeros((t_len, (spec.dim // 32) * 34), jnp.uint8)
    return jnp.zeros((t_len, spec.dim), jnp.float32)


def _consume_deferred(spec: TransformerSpec, x, pending, idx):
    """Top-of-layer consumption of the PREVIOUS layer's deferred ffn
    combine (overlap scheme): unpack the carried gather buffer and apply
    the residual add layer N deferred — the same two operands, the same
    add, just moved past the gather so the wire time hides behind this
    layer's matmuls. Layer 0 has no previous combine: the select returns
    the raw carry bitwise (never `x + 0`, which would flip -0.0)."""
    with jax.named_scope(SCOPE_FFN):
        return jnp.where(idx == 0, x, x + _wire_unpack(spec, pending))


def _tp_tail(spec: TransformerSpec, x, lw, ao, gather_fn=_ici_gather,
             scheme: str = "ref", psum_fn=_ici_psum,
             scatter_fn=_ici_scatter, n_slices: int = 1,
             permute_fn=_ici_ppermute, rank_fn=_tp_rank):
    """Shard-local layer tail: attention output -> wo -> residual -> ffn.

    ref scheme: the four all_gathers here are THE per-layer tp collectives
    (see module docstring for the reference sync-task mapping); under Q80
    buffer mode each moves the real int8+f16 payload (_wire_gather).

    fused scheme: wo/w2 are input-dim bands consuming the SHARD-LOCAL
    attention out / hb, so the only per-layer collectives are the two block
    combines (_combine). The reference's quantize cut points survive as
    local fake-quants (_wire) where no wire remains.

    overlap scheme: the fused matmuls verbatim, with each combine's reduce
    ring-decomposed (_ici_ring_reduce) and the ffn combine's gather left
    UN-CONSUMED — returned as ``(x_pre_residual, pending)`` for the scan
    carry; the next layer's _consume_deferred applies the residual add.
    """
    if scheme == "overlap":
        with jax.named_scope(SCOPE_ATTN):
            ao = _wire(spec, ao)                   # ⇄ quantizeMultiheadAtt
            part = matmul(lw["wo"], ao)            # (T, dim) partial sums
            band = _ici_ring_reduce(part, n_slices, permute_fn, rank_fn)
            # attention combine consumed in-layer: ffn's rmsnorm needs x
            x = x + _wire_gather(spec, band, gather_fn)
        with jax.named_scope(SCOPE_FFN):
            xb = rmsnorm(x, lw["rms_ffn"])
            xb = _wire(spec, xb)                   # ⇄ quantizeRmfFfn
            hb = _wire(spec, _swiglu_local(lw, xb))  # ⇄ quantizeFfnA (local)
            part = matmul(lw["w2"], hb)            # (T, dim) partial sums
            band = _ici_ring_reduce(part, n_slices, permute_fn, rank_fn)
            # gather issued HERE, consumed at the top of the next layer
            # (_consume_deferred) — the double-buffered wire cut
            pending = _gather(_wire_pack(spec, band), gather_fn)
        return x, pending
    if scheme == "fused":
        with jax.named_scope(SCOPE_ATTN):
            ao = _wire(spec, ao)                   # ⇄ quantizeMultiheadAtt
            xb2 = matmul(lw["wo"], ao)             # (T, dim) partial sums
            x = x + _combine(spec, xb2, gather_fn, psum_fn,
                             scatter_fn)       # ⇄ syncMultiheadAtt+syncAtt

        with jax.named_scope(SCOPE_FFN):
            xb = rmsnorm(x, lw["rms_ffn"])
            xb = _wire(spec, xb)                   # ⇄ quantizeRmfFfn
            hb = _wire(spec, _swiglu_local(lw, xb))  # ⇄ quantizeFfnA (local)
            xb2 = matmul(lw["w2"], hb)             # (T, dim) partial sums
            return x + _combine(spec, xb2, gather_fn, psum_fn,
                                scatter_fn)        # ⇄ syncFfnA/B+syncFfn2
    with jax.named_scope(SCOPE_ATTN):
        xb = _wire_gather(spec, ao, gather_fn)     # ⇄ syncMultiheadAtt
        xb2 = matmul(lw["wo"], xb)                 # (T, dim/S)
        x = x + _wire_gather(spec, xb2, gather_fn)  # ⇄ syncAtt + residual

    with jax.named_scope(SCOPE_FFN):
        xb = rmsnorm(x, lw["rms_ffn"])
        xb = _wire(spec, xb)                       # ⇄ quantizeRmfFfn
        hb = _wire_gather(spec, _swiglu_local(lw, xb),
                          gather_fn)               # ⇄ syncFfnA+syncFfnB
        xb2 = matmul(lw["w2"], hb)                 # (T, dim/S)
        return x + _wire_gather(spec, xb2,
                                gather_fn)         # ⇄ syncFfn2 + residual


def _local_layer(spec: TransformerSpec, n_slices: int, n_sp: int, x, lw,
                 k_all, v_all, idx, pos, positions, gather_fn=_ici_gather,
                 scheme: str = "ref", psum_fn=_ici_psum,
                 scatter_fn=_ici_scatter, permute_fn=_ici_ppermute,
                 rank_fn=_tp_rank, pending=None):
    """Per-device layer body. x replicated (T, dim); lw holds local tp bands;
    k/v_all hold this device's STACKED (L, sp-chunk, tp-kv-heads, hs) cache
    shard — updated in place at layer ``idx`` (see models/llama.forward on
    why the stack rides in the carry). Returns (x, k_all, v_all, pending);
    ``pending`` is the overlap scheme's deferred ffn-combine buffer (None
    for ref/fused — their carries never grow)."""
    if scheme == "overlap":
        # apply the PREVIOUS layer's deferred ffn combine before anything
        # reads x (layer 0 selects the raw carry)
        x = _consume_deferred(spec, x, pending, idx)
    t_len = x.shape[0]
    heads_loc = spec.n_heads // n_slices
    kv_heads_loc = spec.n_kv_heads // n_slices
    seq_chunk = spec.seq_len // n_sp

    # qkv + rope + cache write + attention core run under the `attn` trace
    # scope; the layer tail scopes its own attn (wo/combine) and ffn halves
    with jax.named_scope(SCOPE_ATTN):
        q, k, v = _tp_qkv(spec, n_slices, lw, x, positions)
        dt = k_all.dtype  # f32 parity default; bf16 halves cache HBM/memory
        k_new = k.reshape(t_len, kv_heads_loc, spec.head_size).astype(dt)
        v_new = v.reshape(t_len, kv_heads_loc, spec.head_size).astype(dt)
        qh = q.reshape(t_len, heads_loc, spec.head_size)

        if n_sp == 1:
            k_all = jax.lax.dynamic_update_slice(k_all, k_new[None],
                                                 (idx, pos, 0, 0))
            v_all = jax.lax.dynamic_update_slice(v_all, v_new[None],
                                                 (idx, pos, 0, 0))

            from ..ops.pallas_attention import maybe_flash_decode

            # per-shard flash-decode over the LOCAL kv heads: contiguous
            # bands keep h -> h//kvMul local, so the kernel's grouping
            # applies unchanged at shard scope (live-chunk reads, like the
            # single-chip path)
            ao = maybe_flash_decode(
                qh, k_all, v_all, idx, pos, seq_len=spec.seq_len,
                head_size=spec.head_size, t_len=t_len, n_kv=kv_heads_loc,
                kv_mul=spec.kv_mul)
            if ao is None:
                k_c = jax.lax.dynamic_index_in_dim(k_all, idx, 0,
                                                   keepdims=False)
                v_c = jax.lax.dynamic_index_in_dim(v_all, idx, 0,
                                                   keepdims=False)
                # local-head attention (math of transformer-tasks.cpp:
                # 206-278 per head)
                ao = attention_core(
                    spec.head_size, spec.kv_mul, qh, k_c, v_c,
                    causal_cache_mask(spec.seq_len, pos, t_len))
        else:
            from .ring import sp_cache_attention, update_sp_cache

            sp_index = jax.lax.axis_index("sp")
            k_c = jax.lax.dynamic_index_in_dim(k_all, idx, 0, keepdims=False)
            v_c = jax.lax.dynamic_index_in_dim(v_all, idx, 0, keepdims=False)
            k_c = update_sp_cache(k_c, k_new, pos, sp_index, seq_chunk)
            v_c = update_sp_cache(v_c, v_new, pos, sp_index, seq_chunk)
            k_all = jax.lax.dynamic_update_slice(k_all, k_c[None],
                                                 (idx, 0, 0, 0))
            v_all = jax.lax.dynamic_update_slice(v_all, v_c[None],
                                                 (idx, 0, 0, 0))
            ao = sp_cache_attention(spec.head_size, spec.kv_mul, seq_chunk,
                                    sp_index, qh, k_c, v_c, pos)

    if scheme == "overlap":
        x, pending = _tp_tail(spec, x, lw, ao, gather_fn, scheme, psum_fn,
                              scatter_fn, n_slices, permute_fn, rank_fn)
        return x, k_all, v_all, pending
    x = _tp_tail(spec, x, lw, ao, gather_fn, scheme, psum_fn, scatter_fn)
    return x, k_all, v_all, None


# a layer's leaves, its matmuls from [2:]
LAYER_KEYS = ("rms_att", "rms_ffn", "wq", "wk", "wv", "wo", "w1", "w2",
              "w3") + FUSED_GROUPS


def validate_sharding(spec: TransformerSpec, mesh: Mesh,
                      scheme: str | None = None) -> None:
    """Check the spec divides onto the mesh — BEFORE any device_put, so
    callers get one clear error instead of a sharding traceback mid-load.

    The reference's analogous constraint is `assert(d % nSlices == 0)`
    (transformer.cpp:15) plus the implicit 2^n-nodes rule (README.md:20);
    ours is head-granular because attention is head-sharded (tp.py
    docstring). ``scheme`` (default: the active DLLAMA_TP_SCHEME) adds the
    overlap scheme's constraints: the ring chunks the residual width, so
    dim/tp must divide, and the double-buffered carry assumes whole
    sequences — sp must be 1.
    """
    n_slices = mesh.shape["tp"]
    n_sp = mesh.shape.get("sp", 1)
    scheme = scheme or tp_scheme()
    if spec.n_experts:
        from ..ops.linear import MOE_TP_REFUSAL

        raise ValueError(MOE_TP_REFUSAL)
    if spec.retention:
        from ..ops.retention import TP_REFUSAL

        raise ValueError(TP_REFUSAL)
    if spec.hybrid or spec.ssd:
        from ..ops.mamba import TP_REFUSAL

        raise ValueError(TP_REFUSAL)
    if spec.mixers:
        from ..ops.linear import MIXERS_TP_REFUSAL

        raise ValueError(MIXERS_TP_REFUSAL)
    if spec.header_version == 3:
        raise ValueError(
            "the sharded forward runs RoPE base 10000, RMSNorm eps 1e-5 "
            "and q/k-norm gains over the whole projection only: this "
            f"spec (rope_theta {spec.rope_theta}, norm_eps {spec.norm_eps}"
            f", per-head q/k-norm {spec.qk_norm_per_head}) runs on one chip")
    for req, name in ((spec.n_heads, "n_heads"),
                      (spec.n_kv_heads, "n_kv_heads"),
                      (spec.hidden_dim, "hidden_dim"),
                      (spec.vocab_size, "vocab_size")):
        if req % n_slices != 0:
            raise ValueError(f"{name}={req} not divisible by tp={n_slices}")
    if spec.seq_len % n_sp != 0:
        raise ValueError(f"seq_len={spec.seq_len} not divisible by sp={n_sp}")
    if scheme == "overlap" and n_slices > 1:
        if n_sp > 1:
            raise ValueError(
                f"overlap tp scheme requires sp=1, got sp={n_sp} (the "
                f"ring-decomposed combines and the deferred ffn gather "
                f"assume un-chunked sequences; use --tp-scheme fused "
                f"with sp>1)")
        if spec.dim % n_slices:
            raise ValueError(
                f"overlap tp scheme ring-chunks the residual width: "
                f"dim={spec.dim} must divide by tp={n_slices}")
    if spec.buffer_float_type == FloatType.Q80:
        for req, name in ((spec.dim, "dim"), (spec.hidden_dim, "hidden_dim")):
            if (req // n_slices) % 32 != 0:
                raise ValueError(
                    f"Q80 buffer needs {name}/tp divisible by 32, got "
                    f"{req}/{n_slices}")


def _effective_scheme(scheme: str | None, n_slices: int) -> str:
    """Resolve the scheme a program is BUILT with: at tp=1 the overlap
    scheme has no wire to hide (the ring/gather degenerate), so it builds
    the fused program — same math, no dead pending plumbing."""
    scheme = scheme or tp_scheme()
    if scheme == "overlap" and n_slices == 1:
        return "fused"
    return scheme


def make_local_step(spec: TransformerSpec, n_slices: int, n_sp: int,
                    gather_fn=_ici_gather, scheme: str | None = None,
                    psum_fn=_ici_psum, scatter_fn=_ici_scatter,
                    permute_fn=_ici_ppermute, rank_fn=_tp_rank):
    """ONE tp-rank's single-sequence step program (embed -> scanned layers ->
    final norm -> vocab-band logits). This is the function shard_map runs on
    every chip (make_sharded_forward); parallel/shard_sim.py runs the same
    function on a single chip with tiling/identity collective stand-ins
    (``gather_fn``/``psum_fn``/``scatter_fn``/``permute_fn``/``rank_fn``)
    to measure the per-chip cost of shapes too big to run whole (70B tp=8).
    ``scheme`` picks the collective schedule (module docstring); default =
    the active DLLAMA_TP_SCHEME. Under the overlap scheme the scan carry
    additionally threads the deferred ffn-combine buffer (two staging
    buffers in flight — the double-buffered wire cut)."""
    scheme = _effective_scheme(scheme, n_slices)
    overlap = scheme == "overlap"

    def local_step(params, cache, tokens, pos):
        t_len = tokens.shape[0]
        positions = pos + jnp.arange(t_len)
        with jax.named_scope(SCOPE_EMBED):
            x = params["tok_embedding"][tokens].astype(jnp.float32)

        stacked, scanned = split_layer_weights(params)

        def body(carry, per_layer):
            if overlap:
                x, k_all, v_all, pending = carry
            else:
                (x, k_all, v_all), pending = carry, None
            idx, lw_slice = per_layer
            with jax.named_scope(SCOPE_LAYER):
                lw = layer_view(stacked, lw_slice, idx)
                x, k_all, v_all, pending = _local_layer(
                    spec, n_slices, n_sp, x, lw, k_all, v_all, idx, pos,
                    positions, gather_fn, scheme, psum_fn, scatter_fn,
                    permute_fn, rank_fn, pending)
            out = ((x, k_all, v_all, pending) if overlap
                   else (x, k_all, v_all))
            return out, None

        idxs = jnp.arange(spec.n_layers, dtype=jnp.int32)
        init = (x, cache.k, cache.v)
        if overlap:
            init += (_deferred_init(spec, t_len),)
        carry, _ = jax.lax.scan(body, init, (idxs, scanned))
        if overlap:
            x, k_new, v_new, pending = carry
            with jax.named_scope(SCOPE_FFN):
                # the LAST layer's deferred combine lands before the norm
                x = x + _wire_unpack(spec, pending)
        else:
            x, k_new, v_new = carry
        with jax.named_scope(SCOPE_LOGITS):
            x = rmsnorm(x, params["rms_final"])
            # vocab bands -> full
            logits = _gather(matmul(params["wcls"], x), gather_fn)
        return logits, KVCache(k_new, v_new)

    return local_step


def make_sharded_forward(spec: TransformerSpec, mesh: Mesh,
                         scheme: str | None = None, name: str = "wrap"):
    """Build the jitted tensor-parallel forward for this mesh.

    Returns fn(params, cache, tokens (T,), pos) -> (logits (T, vocab), cache).
    Works for any tp size on the mesh, including tp=1 (then it reduces to the
    single-chip program; parity across tp sizes is the stage-4 gate of
    SURVEY.md §7). ``scheme`` (default: the active DLLAMA_TP_SCHEME) is
    resolved ONCE here — the built program never re-reads the env.
    ``name`` is what a profiler capture calls the program's runs
    (``jit_<name>``): the one forward serves as decode step and as
    prefill chunk, which a capture must tell apart.
    """
    n_slices = mesh.shape["tp"]
    n_sp = mesh.shape.get("sp", 1)
    scheme = _effective_scheme(scheme, n_slices)
    validate_sharding(spec, mesh, scheme)
    local_step = make_local_step(spec, n_slices, n_sp, scheme=scheme)

    def wrap(params, cache, tokens, pos):
        in_specs = (param_specs(params, scheme), CACHE_SPEC, P(), P())
        out_specs = (P(), CACHE_SPEC)
        fn = _shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
        return fn(params, cache, tokens, pos)

    return jax.jit(named_program(name, wrap), donate_argnums=1)


# batched cache (L, B, S, n_kv, hs): sequence chunks over sp, kv heads
# over tp — the same axes as the single-sequence CACHE_SPEC, one batch dim in
CACHE_SPEC_BATCH = KVCache(P(None, None, "sp", "tp", None),
                           P(None, None, "sp", "tp", None))


def shard_cache_batch(cache: KVCache, mesh: Mesh) -> KVCache:
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        cache, CACHE_SPEC_BATCH)


def _batch_sp_attention(spec: TransformerSpec, seq_chunk: int, q, k, v,
                        k_all, v_all, idx, pos, kv_loc: int, hs: int):
    """Batch decode attention over the sp-sharded cache: the single-sequence
    sp primitives (ring.update_sp_cache / sp_cache_attention — per-chunk
    masked writes, LSE-combined partials over the sp axis) vmapped over the
    batch rows, each with its own position clock. The pmax/psum inside the
    LSE combine batch cleanly under vmap (per-row independent reductions).

    q (B, n_q_loc*hs); k/v (B, kv_loc*hs); k/v_all (L*B, C, kv_loc, hs)
    rank-4 carries of the sp-LOCAL chunks. Returns (ao, k_all, v_all).
    """
    from .ring import sp_cache_attention, update_sp_cache

    B = q.shape[0]
    sp_index = jax.lax.axis_index("sp")
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    k_c = jax.lax.dynamic_slice_in_dim(k_all, idx * B, B, 0)
    v_c = jax.lax.dynamic_slice_in_dim(v_all, idx * B, B, 0)

    def upd(chunk, new, p):
        return update_sp_cache(chunk, new, p, sp_index, seq_chunk)

    k_c = jax.vmap(upd)(k_c, k.reshape(B, 1, kv_loc, hs).astype(k_all.dtype),
                        pos_b)
    v_c = jax.vmap(upd)(v_c, v.reshape(B, 1, kv_loc, hs).astype(v_all.dtype),
                        pos_b)
    k_all = jax.lax.dynamic_update_slice(k_all, k_c, (idx * B, 0, 0, 0))
    v_all = jax.lax.dynamic_update_slice(v_all, v_c, (idx * B, 0, 0, 0))

    def att(q1, kc, vc, p):
        return sp_cache_attention(hs, spec.kv_mul, seq_chunk, sp_index,
                                  q1, kc, vc, p)

    ao = jax.vmap(att)(q.reshape(B, 1, -1, hs), k_c, v_c, pos_b)  # (B, 1, d)
    return ao.reshape(B, -1), k_all, v_all


# paged pool cache (L, P, page_size, n_kv, hs): kv heads over tp, the page
# axis replicated (every chip holds all pages for its LOCAL kv heads — the
# page table is pure host bookkeeping, identical on every chip). Paged KV
# does not compose with sp: sequence chunking assumes contiguous position
# strides, which a page table deliberately breaks.
CACHE_SPEC_PAGED = KVCache(P(None, None, None, "tp", None),
                           P(None, None, None, "tp", None))

# Q8 page pool (models/llama.PagedKVQ8): code planes shard the kv-head
# axis like the f32 pool; delta planes (L, P, ps, nb) shard the BLOCK
# axis — the flattened (n_kv, hs) row is head-major, so a rank's delta
# band is exactly its head band's blocks (validate_kv_quant pins the
# (n_kv/tp * hs) % 32 == 0 granularity this alignment needs).
CACHE_SPEC_PAGED_Q8 = PagedKVQ8(P(None, None, None, "tp", None),
                                P(None, None, None, "tp"),
                                P(None, None, None, "tp", None),
                                P(None, None, None, "tp"))


def shard_cache_paged(cache, mesh: Mesh):
    spec = (CACHE_SPEC_PAGED_Q8 if isinstance(cache, PagedKVQ8)
            else CACHE_SPEC_PAGED)
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        cache, spec)


# ONE page's planes (the pool spec minus the page axis): (L, ps, n_kv, hs)
# kv-head-sharded; Q8 delta planes (L, ps, nb) on the aligned block bands.
# The KV-tiering promotion path stages host payloads through these so the
# upload lands pre-sharded instead of replicating every plane onto every
# chip and resharding inside the apply jit.
PAGE_PLANE_SPECS = (P(None, None, "tp", None),) * 2
PAGE_PLANE_SPECS_Q8 = (P(None, None, "tp", None), P(None, None, "tp"),
                       P(None, None, "tp", None), P(None, None, "tp"))


def stage_page_planes(planes, mesh: Mesh, q8: bool = False) -> tuple:
    """Host→device staging for one demoted page's payload (KV tiering):
    device_put each plane under its pool sharding — the sharded twin of
    the single-chip ``jax.device_put`` stage, run by the PageUploader off
    the scheduler thread so the transfer hides behind decode steps."""
    specs = PAGE_PLANE_SPECS_Q8 if q8 else PAGE_PLANE_SPECS
    return tuple(jax.device_put(a, NamedSharding(mesh, s))
                 for a, s in zip(planes, specs))


def validate_kv_quant(spec: TransformerSpec, n_slices: int,
                      kv_quant: str) -> None:
    """Q8 KV pages quantize each position's flattened shard-LOCAL
    (n_kv/tp, hs) row in 32-value Q80 blocks — blocks must not straddle
    the shard boundary, or per-shard quantization would disagree with the
    single-chip encoding. Checked BEFORE any device_put, like
    validate_sharding."""
    if kv_quant not in ("f32", "q8"):
        raise ValueError(f"kv_quant={kv_quant!r}: expected f32|q8")
    if kv_quant == "q8":
        kv_loc = (spec.n_kv_heads // n_slices) * spec.head_size
        if kv_loc % QK:
            raise ValueError(
                f"q8 KV pages need the shard-local kv width to divide "
                f"into {QK}-value Q80 blocks: n_kv_heads/tp * head_size "
                f"= {spec.n_kv_heads}/{n_slices} * {spec.head_size} = "
                f"{kv_loc} is not a {QK}-multiple")


def make_sharded_forward_batch_paged(spec: TransformerSpec, mesh: Mesh,
                                     page_size: int,
                                     scheme: str | None = None,
                                     kv_quant: str = "f32"):
    """Tensor-parallel paged decode step: make_sharded_forward_batch's twin
    over the page-pool cache (models/llama.forward_batch_paged semantics,
    per-shard over the LOCAL kv heads).

    Returns fn(params, cache, tokens (B,), pos (B,), table (B, S/ps))
    -> (logits (B, vocab), cache) with cache (L, P, ps, n_kv, hs)
    kv-head-sharded over tp (CACHE_SPEC_PAGED) and the page table
    replicated (host bookkeeping is chip-invariant). Works under BOTH
    collective schemes — attention runs before the layer tail, so the
    ref/fused schedule difference never sees the page table. sp > 1 is
    rejected: pages break the contiguous position strides sequence
    chunking slices by.

    ``kv_quant='q8'`` (ISSUE 11) runs the Q80-quantized page pool
    (models/llama.PagedKVQ8, kv-head-sharded like the f32 pool with the
    delta planes on the aligned block bands) — quantize-on-write /
    dequantize-on-read is per-shard-local and block-aligned, so the
    sharded encoding is exactly the single-chip encoding sliced.
    """
    n_slices = mesh.shape["tp"]
    n_sp = mesh.shape.get("sp", 1)
    if n_sp > 1:
        raise ValueError(f"paged KV cache requires sp=1, got sp={n_sp} "
                         f"(page tables break contiguous sequence chunks)")
    scheme = _effective_scheme(scheme, n_slices)
    validate_sharding(spec, mesh, scheme)
    validate_kv_quant(spec, n_slices, kv_quant)
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    L, hs = spec.n_layers, spec.head_size
    overlap = scheme == "overlap"
    q8 = kv_quant == "q8"
    cache_spec = CACHE_SPEC_PAGED_Q8 if q8 else CACHE_SPEC_PAGED

    def local_step(params, cache, tokens, pos, table):
        B = tokens.shape[0]
        with jax.named_scope(SCOPE_EMBED):
            x = params["tok_embedding"][tokens].astype(jnp.float32)  # (B, d)
        positions = pos if jnp.ndim(pos) == 1 else jnp.full((B,), pos)
        # rank-4 (L*P, ps, kv_loc, hs) carry views — forward_batch_paged's
        # layout rationale, per shard (the shared plane pack)
        planes, n_pages = paged_cache_planes(cache)
        stacked, scanned = split_layer_weights(params)

        def body(carry, per_layer):
            if overlap:
                x, *kv, pending = carry
            else:
                (x, *kv), pending = carry, None
            idx, lw_slice = per_layer
            with jax.named_scope(SCOPE_LAYER):
                if overlap:
                    x = _consume_deferred(spec, x, pending, idx)
                lw = layer_view(stacked, lw_slice, idx)
                with jax.named_scope(SCOPE_ATTN):
                    q, k, v = _tp_qkv(spec, n_slices, lw, x, positions)
                    if q8:
                        ao, *kv = paged_attention_q8(
                            hs, spec.kv_mul, page_size, n_pages,
                            q[:, None], k[:, None], v[:, None], *kv, idx,
                            pos, table)
                        ao = ao.reshape(B, -1)
                    else:
                        ao, *kv = paged_decode_attention(
                            hs, spec.kv_mul, page_size, n_pages, q, k, v,
                            *kv, idx, pos, table)
                if overlap:
                    x, pending = _tp_tail(spec, x, lw, ao, scheme=scheme,
                                          n_slices=n_slices)
                    return (x, *kv, pending), None
                x = _tp_tail(spec, x, lw, ao, scheme=scheme)
            return (x, *kv), None

        idxs = jnp.arange(L, dtype=jnp.int32)
        init = (x, *planes)
        if overlap:
            init += (_deferred_init(spec, B),)
        carry, _ = jax.lax.scan(body, init, (idxs, scanned))
        if overlap:
            x, *kv, pending = carry
            with jax.named_scope(SCOPE_FFN):
                x = x + _wire_unpack(spec, pending)
        else:
            x, *kv = carry
        with jax.named_scope(SCOPE_LOGITS):
            x = rmsnorm(x, params["rms_final"])
            logits = _gather(matmul(params["wcls"], x))
        return logits, rebuild_paged_cache(tuple(kv), L)

    def wrap(params, cache, tokens, pos, table):
        in_specs = (param_specs(params, scheme), cache_spec, P(), P(),
                    P())
        out_specs = (P(), cache_spec)
        fn = _shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
        return fn(params, cache, tokens, pos, table)

    return jax.jit(named_program("serve_decode_step", wrap),
                   donate_argnums=1)


def make_sharded_verify(spec: TransformerSpec, mesh: Mesh, page_size: int,
                        scheme: str | None = None,
                        kv_quant: str = "f32"):
    """Tensor-parallel K-query speculative VERIFY step (ISSUE 7):
    make_sharded_forward_batch_paged's sibling scoring each row's current
    token plus K-1 drafts in ONE dispatch (models/llama.
    forward_batch_spec_paged semantics, per-shard over the LOCAL kv heads).

    Returns fn(params, cache, tokens (B, K), pos (B,), table (B, S/ps))
    -> (logits (B, K, vocab), cache). Works under BOTH collective schemes:
    the B*K query rows ride the layer tail as a flat activation batch, so
    the dispatch issues EXACTLY one decode step's per-layer collective
    schedule (the J001 verify census, contract_verify_collectives) with
    K-times the activation payload — per-collective launch latency, the
    dominant multi-chip term, is paid once for K scored positions. sp > 1
    is rejected as in the paged decode factory.
    """
    n_slices = mesh.shape["tp"]
    n_sp = mesh.shape.get("sp", 1)
    if n_sp > 1:
        raise ValueError(f"speculative verify requires sp=1, got sp={n_sp} "
                         f"(page tables break contiguous sequence chunks)")
    scheme = _effective_scheme(scheme, n_slices)
    validate_sharding(spec, mesh, scheme)
    validate_kv_quant(spec, n_slices, kv_quant)
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    L, hs = spec.n_layers, spec.head_size
    overlap = scheme == "overlap"
    q8 = kv_quant == "q8"
    cache_spec = CACHE_SPEC_PAGED_Q8 if q8 else CACHE_SPEC_PAGED

    def local_step(params, cache, tokens, pos, table):
        B, K = tokens.shape
        with jax.named_scope(SCOPE_EMBED):
            x = params["tok_embedding"][
                tokens.reshape(-1)].astype(jnp.float32)       # (B*K, d)
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        positions = (pos_b[:, None]
                     + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(-1)
        planes, n_pages = paged_cache_planes(cache)
        stacked, scanned = split_layer_weights(params)

        def body(carry, per_layer):
            if overlap:
                x, *kv, pending = carry
            else:
                (x, *kv), pending = carry, None
            idx, lw_slice = per_layer
            with jax.named_scope(SCOPE_LAYER):
                if overlap:
                    x = _consume_deferred(spec, x, pending, idx)
                lw = layer_view(stacked, lw_slice, idx)
                with jax.named_scope(SCOPE_ATTN):
                    q, k, v = _tp_qkv(spec, n_slices, lw, x, positions)
                    attend = paged_attention_q8 if q8 \
                        else spec_verify_attention
                    ao, *kv = attend(
                        hs, spec.kv_mul, page_size, n_pages,
                        q.reshape(B, K, -1), k.reshape(B, K, -1),
                        v.reshape(B, K, -1), *kv, idx, pos_b, table)
                if overlap:
                    x, pending = _tp_tail(spec, x, lw,
                                          ao.reshape(B * K, -1),
                                          scheme=scheme, n_slices=n_slices)
                    return (x, *kv, pending), None
                x = _tp_tail(spec, x, lw, ao.reshape(B * K, -1),
                             scheme=scheme)
            return (x, *kv), None

        idxs = jnp.arange(L, dtype=jnp.int32)
        init = (x, *planes)
        if overlap:
            init += (_deferred_init(spec, B * K),)
        carry, _ = jax.lax.scan(body, init, (idxs, scanned))
        if overlap:
            x, *kv, pending = carry
            with jax.named_scope(SCOPE_FFN):
                x = x + _wire_unpack(spec, pending)
        else:
            x, *kv = carry
        with jax.named_scope(SCOPE_LOGITS):
            x = rmsnorm(x, params["rms_final"])
            logits = _gather(matmul(params["wcls"], x))       # (B*K, V)
        return (logits.reshape(B, K, -1),
                rebuild_paged_cache(tuple(kv), L))

    def wrap(params, cache, tokens, pos, table):
        in_specs = (param_specs(params, scheme), cache_spec, P(), P(),
                    P())
        out_specs = (P(), cache_spec)
        fn = _shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
        return fn(params, cache, tokens, pos, table)

    return jax.jit(wrap, donate_argnums=1)


def make_sharded_mixed(spec: TransformerSpec, mesh: Mesh, page_size: int,
                       scheme: str | None = None,
                       kv_quant: str = "f32"):
    """Tensor-parallel token-budget MIXED dispatch (ISSUE 18):
    make_sharded_verify's sibling for per-row ARBITRARY spans
    (models/llama.forward_batch_mixed_paged semantics, per-shard over the
    LOCAL kv heads) — all active decode rows (span 1) plus one prefill
    slice (span up to the remaining budget) in ONE fused forward.

    Returns fn(params, cache, tokens (B, T), pos (B,), span (B,),
    table (B, S/ps)) -> (logits (B, T, vocab), cache). Works under all
    three collective schemes: the B*T query rows ride the layer tail as a
    flat activation batch, so the dispatch issues EXACTLY one decode
    step's per-layer collective schedule (contract_mixed_collectives;
    comm_stats.tp_collective_budget at t_len=budget) with T-times the
    activation payload — per-collective launch latency, the dominant
    multi-chip term, is paid once per token budget. sp > 1 is rejected as
    in the paged decode factory.
    """
    n_slices = mesh.shape["tp"]
    n_sp = mesh.shape.get("sp", 1)
    if n_sp > 1:
        raise ValueError(f"mixed dispatch requires sp=1, got sp={n_sp} "
                         f"(page tables break contiguous sequence chunks)")
    scheme = _effective_scheme(scheme, n_slices)
    validate_sharding(spec, mesh, scheme)
    validate_kv_quant(spec, n_slices, kv_quant)
    if spec.seq_len % page_size:
        raise ValueError(f"page_size={page_size} must divide "
                         f"seq_len={spec.seq_len}")
    L, hs = spec.n_layers, spec.head_size
    overlap = scheme == "overlap"
    q8 = kv_quant == "q8"
    cache_spec = CACHE_SPEC_PAGED_Q8 if q8 else CACHE_SPEC_PAGED

    def local_step(params, cache, tokens, pos, span, table):
        B, T = tokens.shape
        with jax.named_scope(SCOPE_EMBED):
            x = params["tok_embedding"][
                tokens.reshape(-1)].astype(jnp.float32)       # (B*T, d)
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        span_b = jnp.broadcast_to(jnp.asarray(span, jnp.int32), (B,))
        positions = (pos_b[:, None]
                     + jnp.arange(T, dtype=jnp.int32)[None, :]).reshape(-1)
        planes, n_pages = paged_cache_planes(cache)
        stacked, scanned = split_layer_weights(params)

        def body(carry, per_layer):
            if overlap:
                x, *kv, pending = carry
            else:
                (x, *kv), pending = carry, None
            idx, lw_slice = per_layer
            with jax.named_scope(SCOPE_LAYER):
                if overlap:
                    x = _consume_deferred(spec, x, pending, idx)
                lw = layer_view(stacked, lw_slice, idx)
                with jax.named_scope(SCOPE_ATTN):
                    q, k, v = _tp_qkv(spec, n_slices, lw, x, positions)
                    if q8:
                        ao, *kv = paged_attention_q8(
                            hs, spec.kv_mul, page_size, n_pages,
                            q.reshape(B, T, -1), k.reshape(B, T, -1),
                            v.reshape(B, T, -1), *kv, idx, pos_b, table,
                            span=span_b)
                    else:
                        ao, *kv = mixed_attention(
                            hs, spec.kv_mul, page_size, n_pages,
                            q.reshape(B, T, -1), k.reshape(B, T, -1),
                            v.reshape(B, T, -1), *kv, idx, pos_b, table,
                            span_b)
                if overlap:
                    x, pending = _tp_tail(spec, x, lw,
                                          ao.reshape(B * T, -1),
                                          scheme=scheme, n_slices=n_slices)
                    return (x, *kv, pending), None
                x = _tp_tail(spec, x, lw, ao.reshape(B * T, -1),
                             scheme=scheme)
            return (x, *kv), None

        idxs = jnp.arange(L, dtype=jnp.int32)
        init = (x, *planes)
        if overlap:
            init += (_deferred_init(spec, B * T),)
        carry, _ = jax.lax.scan(body, init, (idxs, scanned))
        if overlap:
            x, *kv, pending = carry
            with jax.named_scope(SCOPE_FFN):
                x = x + _wire_unpack(spec, pending)
        else:
            x, *kv = carry
        with jax.named_scope(SCOPE_LOGITS):
            x = rmsnorm(x, params["rms_final"])
            logits = _gather(matmul(params["wcls"], x))       # (B*T, V)
        return (logits.reshape(B, T, -1),
                rebuild_paged_cache(tuple(kv), L))

    def wrap(params, cache, tokens, pos, span, table):
        in_specs = (param_specs(params, scheme), cache_spec, P(), P(),
                    P(), P())
        out_specs = (P(), cache_spec)
        fn = _shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
        return fn(params, cache, tokens, pos, span, table)

    return jax.jit(wrap, donate_argnums=1)


def make_sharded_forward_batch(spec: TransformerSpec, mesh: Mesh,
                               scheme: str | None = None):
    """Tensor/sequence-parallel lockstep batch decode step (forward_batch
    over the mesh).

    Returns fn(params, cache, tokens (B,), pos) -> (logits (B, vocab), cache)
    with cache (L, B, S, n_kv, hs) sequence-chunked over sp and
    kv-head-sharded over tp. Per-row math == models/llama.forward_batch
    (same kernels; pos is a shared scalar clock for the lockstep loop or a
    (B,) vector for continuous batching, exactly as in forward_batch);
    per-layer collectives == make_sharded_forward's for the same ``scheme``
    (ref: four all_gathers, fused: two block combines — now carrying B rows
    each, plus the per-row LSE combine over sp). Gates: tp ∈ {2, 4} and
    sp ∈ {2, 4} logits/tokens match the single-chip batch path
    (tests/test_batch_tp.py) and the single-chip continuous scheduler
    (tests/test_continuous.py).
    """
    n_slices = mesh.shape["tp"]
    n_sp = mesh.shape.get("sp", 1)
    scheme = _effective_scheme(scheme, n_slices)
    validate_sharding(spec, mesh, scheme)
    kv_loc = spec.n_kv_heads // n_slices
    L, S, hs = spec.n_layers, spec.seq_len, spec.head_size
    C = S // n_sp  # sp-local sequence chunk
    overlap = scheme == "overlap"

    def local_step(params, cache, tokens, pos):
        B = tokens.shape[0]
        with jax.named_scope(SCOPE_EMBED):
            x = params["tok_embedding"][tokens].astype(jnp.float32)  # (B, d)
        positions = pos if jnp.ndim(pos) == 1 else jnp.full((B,), pos)
        # rank-4 (L*B, C, kv_loc, hs) carry view — same layout rationale as
        # forward_batch (row layer*B+b is a single-sequence cache plane)
        k4 = cache.k.reshape(L * B, C, kv_loc, hs)
        v4 = cache.v.reshape(L * B, C, kv_loc, hs)
        stacked, scanned = split_layer_weights(params)

        def body(carry, per_layer):
            if overlap:
                x, k_all, v_all, pending = carry
            else:
                (x, k_all, v_all), pending = carry, None
            idx, lw_slice = per_layer
            with jax.named_scope(SCOPE_LAYER):
                if overlap:
                    x = _consume_deferred(spec, x, pending, idx)
                lw = layer_view(stacked, lw_slice, idx)
                with jax.named_scope(SCOPE_ATTN):
                    q, k, v = _tp_qkv(spec, n_slices, lw, x, positions)
                    if n_sp == 1:
                        # shared with the single-chip batch path; the
                        # shard's cache holds kv_loc heads, off the carry
                        ao, k_all, v_all = batch_decode_attention(
                            hs, spec.kv_mul, S, q, k, v, k_all, v_all, idx,
                            pos)
                    else:
                        ao, k_all, v_all = _batch_sp_attention(
                            spec, C, q, k, v, k_all, v_all, idx, pos,
                            kv_loc, hs)
                if overlap:
                    x, pending = _tp_tail(spec, x, lw, ao, scheme=scheme,
                                          n_slices=n_slices)
                    return (x, k_all, v_all, pending), None
                x = _tp_tail(spec, x, lw, ao, scheme=scheme)
            return (x, k_all, v_all), None

        idxs = jnp.arange(L, dtype=jnp.int32)
        init = (x, k4, v4)
        if overlap:
            init += (_deferred_init(spec, B),)
        carry, _ = jax.lax.scan(body, init, (idxs, scanned))
        if overlap:
            x, k4, v4, pending = carry
            with jax.named_scope(SCOPE_FFN):
                x = x + _wire_unpack(spec, pending)
        else:
            x, k4, v4 = carry
        with jax.named_scope(SCOPE_LOGITS):
            x = rmsnorm(x, params["rms_final"])
            logits = _gather(matmul(params["wcls"], x))
        return logits, KVCache(k4.reshape(L, B, C, kv_loc, hs),
                               v4.reshape(L, B, C, kv_loc, hs))

    def wrap(params, cache, tokens, pos):
        in_specs = (param_specs(params, scheme), CACHE_SPEC_BATCH, P(), P())
        out_specs = (P(), CACHE_SPEC_BATCH)
        fn = _shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
        return fn(params, cache, tokens, pos)

    return jax.jit(named_program("serve_decode_step", wrap),
                   donate_argnums=1)
