"""CLI entrypoints: ``inference``/``worker`` (reference src/main.cpp), plus
``serve`` (HTTP API over continuous batching) and ``convert`` modes.

Flag surface parity (main.cpp:94-160): --model, --tokenizer, --prompt,
--weights-float-type, --buffer-float-type, --workers, --port, --nthreads,
--steps, --temperature, --topp; defaults port=9990, temperature=0.8, topp=0.9,
steps=64 (nthreads is accepted for compatibility; XLA owns intra-chip
threading).

Role mapping on TPU: the reference's 2^n socket-connected worker processes
become the chips of a tp mesh driven by ONE process — ``--tp N`` (default: all
local devices). ``worker`` mode exists for multi-HOST meshes and follows JAX's
multi-controller SPMD model (the DCN analog of the reference's socket star):
every host executes the SAME program over the global mesh, so ``worker`` takes
the same --model/--tokenizer/... flags as ``inference`` plus
``--coordinator host:port --num-hosts H --host-id i``, joins via
jax.distributed, runs the identical generation loop (identical --seed makes
every host sample the same token chain), and suppresses output — only the
root host (``inference`` with --host-id 0) prints. Each host reads its
shards from the model file (the scatter onto chips is the sharded
device_put); a host WITHOUT the file streams it from the root first —
``--serve-weights PORT`` on the root, ``--model-from-root HOST:PORT`` on
the worker (io/stream.py; the reference's wire transfer,
transformer.cpp:354-380).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from ..ops.quants import FloatType

_FT = {"f32": FloatType.F32, "f16": FloatType.F16, "q40": FloatType.Q40,
       "q80": FloatType.Q80}


# --model help shared by the modes that take the sidecar-cached load path
# (satellite: the GB-scale .kcache write must not be a disk-space surprise)
_MODEL_HELP = ("path to the reference-format .bin model. Single-chip Q40 "
               "runs write a pre-tiled <model>.kcache sidecar next to it "
               "(roughly the packed weight size on disk) so later loads "
               "mmap it instead of re-tiling for minutes; set "
               "DLLAMA_TILED_CACHE=0 to disable the sidecar read AND write")

def _refuses(spec, args, tp: int, serve: bool) -> bool:
    """Print the lines ``runtime/continuous.cache_refusals`` has for what a
    sequence of ``spec`` caches and the flags of this run (``inference`` or
    ``serve``; a flag the mode does not have reads as off); True if there
    was one."""
    from ..runtime.continuous import cache_refusals, sequence_caches

    get = lambda name, off=0: getattr(args, name, off) or off  # noqa: E731
    refused = cache_refusals(
        sequence_caches(spec),
        tp=max(tp, get("sp", 1)), page_size=get("kv_page_size"),
        kv_pages=get("kv_pages"), spec_k=get("spec_k"),
        dispatch_tokens=get("dispatch_tokens"),
        kv_quant=get("kv_quant", "f32"),
        kv_host_pages=get("kv_host_pages"),
        kv_disk_dir=get("kv_disk_dir", None), journal=bool(get("journal")),
        disagg=bool(get("disagg_role")), block_steps=get("block_steps", 1),
        kv_cache_dtype=get("kv_cache_dtype", "f32"), serve=serve)
    for line in refused:
        print(f"refused: {line}", file=sys.stderr)
    return bool(refused)


def _hybrid_line(spec, slots: int) -> str:
    """What a hybrid spec keeps a sequence: a startup line."""
    hy = spec.hybrid
    state = hy.count("mamba") * hy.d_inner * (hy.d_state + hy.d_conv - 1) * 4
    ring = hy.count("swa") * hy.window * 2 * spec.kv_dim * 4
    return (f"💡 layers: {hy.count('mamba')} mamba, {hy.count('swa')} window "
            f"({hy.window}), 1 full, {hy.count('gmu')} gmu, "
            f"{hy.count('xattn')} cross (differential attention); a "
            f"sequence keeps {state / 2**20:.1f} MiB of state and "
            f"{ring / 2**20:.1f} MiB of window ring ({slots} "
            f"slot{'s' if slots != 1 else ''}, fixed) and ONE layer's K / V: "
            f"{2 * spec.kv_dim * 4} B a position")


def _ssd_line(spec, slots: int) -> str:
    """What an ssd spec keeps a sequence: a startup line."""
    from ..analysis.memory_model import kv_position_bytes, state_slot_bytes

    sd = spec.ssd
    return (f"💡 layers, ONE mixer each: {sd.count('mamba2')} mamba-2 "
            f"({sd.heads} heads of {sd.head_dim}, state {sd.d_state}, "
            f"{sd.groups} groups), {sd.count('full')} attention "
            f"({spec.n_heads} heads over {spec.n_kv_heads} KV heads, no "
            f"positional encoding), {sd.count('experts')} expert "
            f"({spec.n_experts_held} of {spec.n_experts} experts held, "
            f"{spec.n_active_experts} a token, {spec.activation.kind}"
            f"{'' if spec.activation.gated else ', not gated'}); a sequence "
            f"keeps {state_slot_bytes(spec) / 2**20:.1f} MiB of state "
            f"({slots} slot{'s' if slots != 1 else ''}, fixed) and the "
            f"attention layers' K / V: {kv_position_bytes(spec, 1)} B a "
            f"position")


def _kda_line(spec, slots: int) -> str:
    """What a kda spec keeps a sequence: a startup line."""
    from ..analysis.memory_model import kv_position_bytes, state_slot_bytes

    kd, la = spec.kda, spec.latent
    return (f"💡 mixers: {la.count('kda')} delta-rule (KDA: {kd.heads} heads "
            f"of {kd.head_dim}, a decay a key channel down to "
            f"exp({kd.lower_bound:g}), conv {kd.d_conv}) beside "
            f"{la.count('full')} latent; a sequence keeps "
            f"{state_slot_bytes(spec) / 2**20:.1f} MiB of state and conv "
            f"rows ({slots} slot{'s' if slots != 1 else ''}, fixed) and the "
            f"latent layers' plane: {kv_position_bytes(spec, 1)} B a "
            f"position")


def _mixers_line(spec, slots: int) -> str:
    """What a mixer-kinds spec keeps a sequence: a startup line."""
    mx, lay = spec.mixers, spec.layout
    ring = mx.count("sliding") * mx.window * spec.kv_cached("sliding") * 4
    (n_f, hk, hv), n_s = spec.kv_shape("full"), spec.kv_shape("sliding")[0]
    kv = (f"{n_f} KV heads" if n_f == n_s
          else f"{n_f} and {n_s} KV heads") + (
        f" (K {hk}, V {hv})" if hk != hv else "")
    sink = "".join(f", a softmax sink a {k} head" for k in ("full", "sliding")
                   if mx.of(k).sink)
    scale = (f", output scaled by {mx.value_scale:g}"
             if mx.value_scale != 1.0 else "")
    ffn = (f"{lay.dense_layers} dense + {spec.n_expert_layers} expert "
           f"FFNs ({spec.n_experts_held} experts held, "
           f"{spec.n_active_experts} a token, {lay.shared} shared)"
           if spec.n_experts else "dense FFNs")
    return (f"💡 layers: {mx.count('full')} full ({mx.full.heads} heads, "
            f"RoPE {mx.rotary('full')} of {mx.head_size} at theta "
            f"{mx.full.rope_theta:g}"
            f"{', YaRN' if mx.full.rope_scaling else ''}), "
            f"{mx.count('sliding')} sliding ({mx.sliding.heads} heads, "
            f"window {mx.window}, theta {mx.sliding.rope_theta:g}) over "
            f"{kv}{sink}{scale}"
            f"{', per-head output gate' if mx.gate else ''}; {ffn}; a "
            f"sequence keeps {ring / 2**20:.1f} MiB of window ring ({slots} "
            f"slot{'s' if slots != 1 else ''}, fixed) and its full layers' "
            f"K / V: {mx.count('full') * spec.kv_cached('full') * 4} B a "
            f"position")


def _latent_line(spec) -> str:
    """What a latent-attention spec caches and holds: a startup line."""
    la, lay = spec.latent, spec.layout
    streams = (f"; {spec.hyper.streams} residual streams mixed around every "
               f"sub-layer ({spec.hyper.sinkhorn_iters} Sinkhorn rounds)"
               if spec.hyper else "")
    return (f"💡 attention: latent (q rank {la.q_rank}, cache "
            f"{la.width} values a position and layer: c_kv {la.kv_rank} + "
            f"k_rope {la.rope_dim}); layers: {lay.dense_layers} dense + "
            f"{spec.n_expert_layers} expert; experts held: "
            f"{spec.n_experts_held} of {spec.n_experts} from {lay.offset}, "
            f"{lay.shared} shared{streams}")


def _retention_line(spec, slots: int) -> str:
    """What a retention spec keeps a sequence: a startup line."""
    from ..ops.retention import state_bytes

    per = spec.n_layers * state_bytes(spec.n_kv_heads, spec.head_size)
    return (f"💡 attention: power retention (degree 2), no KV cache; "
            f"state: {slots} "
            f"slot{'s' if slots != 1 else ''} x {per / 2**20:.0f} MiB "
            f"(fixed, whatever the context)")


# --weights-float-type help of the modes that pack Q40 for one chip: how the
# layout is picked, and where the pick is recorded
_WFT_HELP = ("weight float type of the .bin. q40 on one chip: each weight's "
             "kernel layout (nb-major, d-major) and the decode chain's body "
             "(int4 planes or u8) are picked from the model's shapes, its "
             "packed size and the width of a decode dispatch (--slots, else "
             "the batch, else 1), with no knob; the pick is the "
             "'Q40 body policy' line on stderr. With --tp > 1 each shard "
             "is judged on its local shape ('Q40 sharded layout' line)")


def _load_one_chip(model: str, wft, bft, rows: int):
    """The single-chip load: sidecar-cached and pre-tiled (VERDICT r4 #7: a
    warm <model>.kcache makes host prep an mmap, like the reference's
    loader, transformer.cpp:280-296). The Q40 layout (bench-winning
    i4-plane + nb-major where the device, the shapes and the dispatch width
    ``rows`` support it) is resolved HERE, once, from the file's header and
    announced on stderr; the same value keys the sidecar, packs the tree
    and is returned for the engine. -> (spec, params, layout or None)."""
    from ..io.kernel_cache import load_model_packed
    from ..io.loader import read_spec
    from ..ops.linear import announce_q40_layout, q40_body_policy

    layout = None  # other float types: the engine's own, and moot
    if wft == FloatType.Q40:
        spec = read_spec(model, weights_float_type=wft)
        layout = q40_body_policy(spec, rows)
        announce_q40_layout(layout, spec, rows)
    spec, params = load_model_packed(model, weights_float_type=wft,
                                     buffer_float_type=bft, layout=layout)
    return spec, params, layout


def _obs_flags(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--log-json", action="store_true",
                    help="emit runtime narration (🌐/⏩/🔶 lines) as "
                         "newline-delimited JSON events instead of emoji "
                         "text (same as DLLAMA_LOG_JSON=1)")


def _apply_log_json(args) -> None:
    if getattr(args, "log_json", False):
        os.environ["DLLAMA_LOG_JSON"] = "1"


def _add_kv_tier_flags(ap: argparse.ArgumentParser) -> None:
    """Hierarchical KV-tiering knobs (ISSUE 12), shared by inference
    --continuous and serve. All need --kv-page-size: tiering spills
    PAGES."""
    ap.add_argument("--kv-host-pages", type=int, default=0, metavar="N",
                    help="KV tiering (needs --kv-page-size): pinned "
                         "host-RAM pool of N pages — cold radix-tree "
                         "prefix pages demote here (write-behind) "
                         "instead of dropping, and promote back on a "
                         "prefix hit via an async upload hidden behind "
                         "decode steps (0 = no host tier)")
    ap.add_argument("--kv-disk-dir", default=None, metavar="DIR",
                    help="KV tiering: spill directory for the disk tier "
                         "— host-pressure-cold pages land in append-only "
                         "segment files with per-page read-back CRC32 "
                         "sidecars (a damaged page re-derives via "
                         "prefill, never serves wrong bytes)")
    ap.add_argument("--kv-disk-gb", type=float, default=0.0, metavar="G",
                    help="live-byte budget of the disk tier in GiB "
                         "(needs --kv-disk-dir; 0 = uncapped)")


def _check_kv_tier_args(args, where: str) -> str | None:
    """Argparse-time validation (before the multi-GB model load), the
    --spec-k/--kv-quant contract: returns an error string or None."""
    if (args.kv_host_pages or args.kv_disk_dir) and args.kv_page_size <= 0:
        return (f"--kv-host-pages/--kv-disk-dir spill paged KV: add "
                f"--kv-page-size P{where}")
    if args.kv_disk_gb and not args.kv_disk_dir:
        return "--kv-disk-gb needs --kv-disk-dir (where else would it go?)"
    if args.kv_host_pages < 0 or args.kv_disk_gb < 0:
        return "--kv-host-pages/--kv-disk-gb must be >= 0"
    return None


def _add_common(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--nthreads", type=int, default=4,
                    help="accepted for reference-CLI compatibility; XLA "
                         "manages device threading")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of the jax.distributed coordinator "
                         "(multi-host only)")
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=None)
    ap.add_argument("--serve-weights", type=int, default=None, metavar="PORT",
                    help="(root) serve the model file's bytes on PORT so "
                         "hosts without a local copy can fetch it — the "
                         "reference's root->worker weight streaming "
                         "(transformer.cpp:250-273). UNAUTHENTICATED, like "
                         "the reference's socket protocol: run it on a "
                         "trusted LAN only, and restrict the interface with "
                         "--serve-weights-bind")
    ap.add_argument("--serve-weights-bind", default="0.0.0.0", metavar="ADDR",
                    help="interface the weight server listens on (default "
                         "all; bind a cluster-internal address to keep the "
                         "unauthenticated byte service off public networks)")
    ap.add_argument("--model-from-root", default=None, metavar="HOST:PORT",
                    help="(worker) fetch the model from the root's "
                         "--serve-weights endpoint into the --model path "
                         "when that file is absent (zero local model files, "
                         "like reference workers, transformer.cpp:354-380)")
    ap.add_argument("--stream-slices", action="store_true",
                    help="with --model-from-root: fetch ONLY this host's "
                         "tp weight bands (~1/tp of the matmul bytes, like "
                         "the reference's per-worker slice scatter, "
                         "transformer.cpp:250-273) instead of the whole "
                         "file. Needs an explicit --tp and equal devices "
                         "per host; the run cross-checks the assumed ranks "
                         "against the actual mesh and aborts on mismatch")


def _assumed_tp_ranks(args) -> set[int]:
    """The tp ranks this host's devices will hold, derived from CLI args
    alone (the fetch runs BEFORE jax.distributed, so the mesh is not yet
    buildable): make_mesh reshapes the global device list row-major into
    (dp, sp, tp), and with H equal hosts, host i owns global devices
    [i*D, (i+1)*D) for D = dp*sp*tp/H — so its tp coordinates are
    {g % tp}. The run re-derives the REAL coordinates from the mesh later
    and aborts on mismatch (fail loud, never compute on unfetched zeros)."""
    tp = args.tp
    if not tp or tp <= 1:
        raise SystemExit("--stream-slices needs an explicit --tp > 1 (the "
                         "slice layout is the tp weight sharding)")
    sp = getattr(args, "sp", 1) or 1
    dp = getattr(args, "dp", 1) or 1
    need = dp * sp * tp
    n_hosts = args.num_hosts
    if need % n_hosts:
        raise SystemExit(f"--stream-slices assumes equal devices/host; mesh "
                         f"of {need} devices does not divide over "
                         f"{n_hosts} hosts")
    per_host = need // n_hosts
    i = args.host_id or 0
    return {g % tp for g in range(i * per_host, (i + 1) * per_host)}


def _weight_streaming(args, quiet: bool, allow_slices: bool = True):
    """Start the root-side weight server / run the worker-side fetch (both
    BEFORE jax.distributed's barrier, so fetching overlaps nothing and a
    dead transfer fails fast). Returns the server (or None) so it outlives
    the load. With --stream-slices the fetch pulls only this host's tp
    bands (io/stream.fetch_model_slices) and records the assumed rank set
    on ``args`` for the post-mesh cross-check."""
    server = None
    if args.serve_weights is not None:
        from ..io.stream import WeightServer

        server = WeightServer(args.model, host=args.serve_weights_bind,
                              port=args.serve_weights)
        if not quiet:
            print(f"⏩ serving weights on port {server.port}")
    if args.model_from_root:
        if getattr(args, "stream_slices", False):
            if not allow_slices:
                raise SystemExit("--stream-slices is an inference/worker "
                                 "feature (training re-shards densified "
                                 "weights); fetch the whole file instead")
            from ..io.stream import fetch_model_slices

            ranks = _assumed_tp_ranks(args)
            fetch_model_slices(args.model_from_root, args.model,
                               _FT[args.weights_float_type], args.tp, ranks,
                               quiet=quiet)
            args._slice_tp_ranks = ranks
        else:
            from ..io.stream import fetch_model

            # unconditional: fetch_model owns the staleness decision (skips
            # only when the local size matches the server's; a truncated or
            # wrong-size local file is repaired, not trusted)
            fetch_model(args.model_from_root, args.model, quiet=quiet)
    elif getattr(args, "stream_slices", False):
        raise SystemExit("--stream-slices only applies with "
                         "--model-from-root")
    return server


def _print_device_memory(when: str) -> None:
    from ..utils.chip import memory_line

    line = memory_line(when)
    if line:
        print(line, file=sys.stderr, flush=True)


def _maybe_distributed(args) -> None:
    if args.coordinator:
        import jax

        # generous barrier timeout on EVERY host: any peer may be doing a
        # multi-GB --model-from-root fetch before it joins (e.g. ~40 GB of
        # 70B over 1 GbE takes ~6 min), and a host that already has its
        # file cannot know that — the default ~300 s would kill the job
        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_hosts,
            process_id=args.host_id if args.host_id is not None else 0,
            initialization_timeout=3600)


def cmd_inference(argv: list[str], quiet: bool = False) -> int:
    ap = argparse.ArgumentParser(
        prog="dllama-tpu inference",
        description="One prompt, token by token. At --temperature 0 the "
                    "per-token loop runs one step ahead of the host: the "
                    "step program returns the argmax of its logits and step "
                    "n+1 is enqueued on it before step n's token is back "
                    "(with a temperature the host samples from the logits). "
                    "The summary's 'Steps run ahead: U used, D dropped' "
                    "counts steps found already in flight (tokens - 1 after "
                    "the prompt) and steps enqueued and thrown away (one, "
                    "after a BOS stop).")
    ap.add_argument("--model", required=True, help=_MODEL_HELP)
    ap.add_argument("--tokenizer", required=True)
    ap.add_argument("--prompt", default=None)
    ap.add_argument("--weights-float-type", default="q40", choices=sorted(_FT),
                    help=_WFT_HELP)
    ap.add_argument("--buffer-float-type", default="f32", choices=sorted(_FT))
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--topp", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel ways (default: all local devices). "
                         "An expert (mixture-of-experts) model, one whose "
                         "header carries nExperts / nActiveExperts / qkNorm, "
                         "runs on one chip only: pass --tp 1 for it (any "
                         "other mesh is refused)")
    ap.add_argument("--tp-scheme", default=None,
                    choices=("ref", "fused", "overlap"),
                    help="tp collective schedule (= DLLAMA_TP_SCHEME): "
                         "'fused' (default) pairs column/row-parallel "
                         "matmuls Megatron-style — 2 collectives/layer; "
                         "'overlap' ring-decomposes the fused combines "
                         "into ppermute hops hidden behind compute "
                         "(bitwise equal to fused; requires --sp 1); "
                         "'ref' keeps the reference's 4-gather MatmulSlice "
                         "schedule, the bit-parity anchor")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel ways (sp-sharded KV cache + "
                         "distributed flash attention; reference has none)")
    ap.add_argument("--workers", nargs="*", default=None,
                    help="accepted for reference-CLI compatibility; on TPU "
                         "the workers are the chips of the mesh (see module "
                         "docstring for multi-host)")
    ap.add_argument("--fast", action="store_true",
                    help="fused on-device generation loop (one device "
                         "program for the whole chain; no per-token stats "
                         "lines)")
    ap.add_argument("--save-state", default=None, metavar="PATH",
                    help="write a resumable generation checkpoint (cache + "
                         "position + RNG) after the run")
    ap.add_argument("--resume-state", default=None, metavar="PATH",
                    help="resume a checkpointed generation (--prompt is "
                         "ignored; --steps more positions run)")
    ap.add_argument("--prompts-file", default=None, metavar="PATH",
                    help="batch mode: one prompt per line, decoded in one "
                         "fused lockstep batch (composes with --tp and "
                         "--sp; a capability the reference lacks). Ignores "
                         "--prompt/--fast/checkpoint flags")
    ap.add_argument("--continuous", action="store_true",
                    help="with --prompts-file: continuous batching — a pool "
                         "of --slots cache slots with per-slot position "
                         "clocks; finished rows are replaced mid-flight by "
                         "queued prompts (single chip)")
    ap.add_argument("--slots", type=int, default=0,
                    help="continuous-batching slot count (default: "
                         "min(#prompts, 8))")
    ap.add_argument("--block-steps", type=int, default=1, metavar="K",
                    help="with --continuous: fuse K decode steps into one "
                         "device dispatch (admission/retirement at chain "
                         "boundaries; cuts host round-trips Kx)")
    ap.add_argument("--kv-page-size", type=int, default=0, metavar="P",
                    help="with --continuous: paged KV cache — slots map "
                         "P-position pages from a shared pool through page "
                         "tables, with radix-tree prefix sharing of common "
                         "prompt prefixes (0 = contiguous per-slot cache)")
    ap.add_argument("--kv-pages", type=int, default=0, metavar="N",
                    help="paged-KV pool size in pages (default: "
                         "slots * seq_len / page-size, byte-parity with "
                         "the contiguous cache; fewer pages serve more "
                         "slots at equal HBM)")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="with --continuous and --kv-page-size: "
                         "self-speculative decoding — draft up to K-1 "
                         "tokens per row (n-gram prompt lookup, no second "
                         "model) and verify them with the current token "
                         "in ONE K-query dispatch; lossless (greedy "
                         "streams bitwise identical, sampled rows keep "
                         "the sampler's distribution via rejection "
                         "sampling). Supersedes --block-steps (0 = off)")
    ap.add_argument("--spec-ngram", type=int, default=3, metavar="N",
                    help="longest n-gram the speculative drafter matches "
                         "against the emitted stream (falls back to "
                         "shorter n-grams down to 1)")
    ap.add_argument("--dispatch-tokens", type=int, default=0, metavar="T",
                    help="with --continuous and --kv-page-size: "
                         "token-budget mixed dispatches — every device "
                         "step carries all active decode rows (1 token "
                         "each) plus ONE prefill slice cut to the "
                         "remaining budget of T tokens, in a single "
                         "fused forward (prefill no longer stalls "
                         "in-flight decodes behind a separate chunk "
                         "dispatch). -1 sizes from --prefill-chunk; "
                         "0 = off. Incompatible with --spec-k")
    ap.add_argument("--kv-cache-dtype", default="f32",
                    choices=("f32", "bf16"),
                    help="KV cache precision: f32 = reference parity "
                         "(transformer.cpp:198-199), bf16 halves cache "
                         "memory and attention HBM traffic")
    ap.add_argument("--kv-quant", default=None, choices=("f32", "q8"),
                    help="KV PAGE quantization (= DLLAMA_KV_QUANT; needs "
                         "--kv-page-size): q8 stores pool pages in the "
                         "Q80 int8+scale wire layout — ~1/3.8 of f32 "
                         "page bytes, so the same HBM holds ~3.8x pages "
                         "(~1.9x vs bf16); decode quantizes on write, "
                         "attention dequantizes on read. Greedy streams "
                         "stay deterministic; logits move to the "
                         "documented quantization tolerance (f32 = "
                         "exact parity)")
    _add_kv_tier_flags(ap)
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="N",
                    help="process the prompt prefix in T=N chunked forward "
                         "passes instead of one token at a time (same "
                         "output stream; ~20x prompt tokens/s on TPU; no "
                         "per-prompt-token stats lines)")
    ap.add_argument("--fast-prefill", action="store_true",
                    help="bf16 matmul precision for T>8 prefill chunks "
                         "(documented tolerance; decode keeps the parity "
                         "program). Needs --prefill-chunk > 1")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace of the "
                         "generation into DIR (tensorboard's format; "
                         "benchmark/harness/reduce_trace.py reads it). "
                         "DLLAMA_PROFILE_DIR sets the same thing without "
                         "flag plumbing")
    ap.add_argument("--metrics", action="store_true",
                    help="collect run telemetry (obs registry: per-token "
                         "latency histogram, generated-token counters) and "
                         "dump the Prometheus text exposition to stderr at "
                         "exit; 'serve' exposes GET /metrics instead")
    _obs_flags(ap)
    _add_common(ap)
    args = ap.parse_args(argv)
    _apply_log_json(args)
    if args.tp_scheme:
        os.environ["DLLAMA_TP_SCHEME"] = args.tp_scheme
    if args.kv_quant:
        os.environ["DLLAMA_KV_QUANT"] = args.kv_quant
    from ..ops.pallas_paged_attention import kv_quant_mode
    from ..parallel.comm_stats import tp_scheme

    scheme = tp_scheme()  # validate (env or flag) at argparse time
    args.kv_quant = kv_quant_mode()  # same pattern for DLLAMA_KV_QUANT
    if args.spec_k and args.kv_page_size <= 0:
        # fail HERE, not deep in ContinuousEngine construction after a
        # multi-GB model load: rollback truncates page tables
        print("--spec-k needs the paged KV cache: add --kv-page-size P "
              "(with --continuous)", file=sys.stderr)
        return 2
    if args.spec_k and args.dispatch_tokens:
        # the verify window and the prefill slice both claim the per-row
        # span; unifying them is follow-up work — refuse at argparse time
        print("--spec-k is incompatible with --dispatch-tokens: the "
              "verify window and the prefill slice both claim the "
              "per-row span (drop one)", file=sys.stderr)
        return 2
    if args.dispatch_tokens and args.kv_page_size <= 0:
        print("--dispatch-tokens needs the paged KV cache: add "
              "--kv-page-size P (with --continuous)", file=sys.stderr)
        return 2
    if args.kv_quant == "q8" and args.kv_page_size <= 0:
        # same argparse-time contract as --spec-k: q8 quantizes PAGE
        # planes, so it is meaningless without the paged pool — refuse
        # before the multi-GB model load
        print("--kv-quant q8 quantizes paged KV pages: add "
              "--kv-page-size P (with --continuous)", file=sys.stderr)
        return 2
    tier_err = _check_kv_tier_args(args, " (with --continuous)")
    if tier_err:
        print(tier_err, file=sys.stderr)
        return 2
    if scheme == "overlap" and args.sp > 1:
        print("--tp-scheme overlap needs --sp 1: the ring-decomposed "
              "combines assume un-chunked sequences (use --tp-scheme "
              "fused with sp>1)", file=sys.stderr)
        return 2
    if args.profile is None:  # one-shot env hook (obs/profiler.py)
        from ..obs.profiler import env_profile_dir

        args.profile = env_profile_dir()
    if args.coordinator and args.seed is None:
        # every host (root included) must sample the same chain, or hosts
        # hit the BOS early-stop at different steps and deadlock in the
        # collectives — refuse BEFORE joining the distributed barrier
        print("multi-host runs need an explicit --seed so every host "
              "samples the same chain", file=sys.stderr)
        return 2
    if args.host_id:  # non-root hosts run silently in SPMD lockstep
        quiet = True
    _ws = _weight_streaming(args, quiet)  # before the distributed barrier
    _maybe_distributed(args)

    import jax

    from ..io.loader import load_model
    from ..io.tokenizer import Tokenizer
    from ..parallel import make_mesh
    from ..runtime.generate import Engine, generate, generate_fast
    from ..runtime.sampling import Sampler

    prompts = None
    if (args.continuous or args.slots) and not args.prompts_file:
        print("--continuous/--slots need --prompts-file (the request "
              "queue)", file=sys.stderr)
        return 2
    if args.slots < 0:
        print(f"--slots must be non-negative (0 = auto: min(#prompts, 8)), "
              f"got {args.slots}", file=sys.stderr)
        return 2
    if args.fast_prefill and args.prefill_chunk <= 1:
        print("--fast-prefill only affects chunked prefill; pass "
              "--prefill-chunk N (N > 1)", file=sys.stderr)
        return 2
    if args.prompts_file:  # validate before the multi-GB model load
        if args.prefill_chunk > 1 and not args.continuous:
            # lockstep rows share one position clock: per-row prompt
            # prefill would desync them — only --continuous prefills
            print("--prefill-chunk with --prompts-file needs --continuous "
                  "(lockstep rows share the position clock)",
                  file=sys.stderr)
            return 2
        with open(args.prompts_file) as fh:
            prompts = [ln.rstrip("\n") for ln in fh if ln.strip()]
        if not prompts:
            print("prompts file is empty", file=sys.stderr)
            return 2

    wft = _FT[args.weights_float_type]
    bft = _FT[args.buffer_float_type]
    n_dev = len(jax.devices())
    if prompts is not None:
        # batch mode: single-chip unless --tp/--sp ask for a sharded step
        tp = args.tp or 1
    else:
        tp = args.tp or max(1, n_dev // args.sp)
    q40_layout = None  # mesh runs: the engine's own (the stock value)
    if tp > 1 or args.sp > 1:
        # mesh runs keep the codec tree: tp-aware packing happens in
        # parallel/tp.shard_params, which picks each leaf's layout on its
        # shard-local shape and says so on stderr
        spec, params = load_model(args.model, weights_float_type=wft,
                                  buffer_float_type=bft)
    else:
        # rows per decode dispatch: the slot pool's width, the lockstep
        # batch's, or one
        rows = (1 if prompts is None else
                (args.slots or min(len(prompts), 8)) if args.continuous
                else len(prompts))
        spec, params, q40_layout = _load_one_chip(args.model, wft, bft, rows)
    if not quiet:
        print(f"💡 dim: {spec.dim}\n💡 hiddenDim: {spec.hidden_dim}\n"
              f"💡 nLayers: {spec.n_layers}\n💡 nHeads: {spec.n_heads}\n"
              f"💡 nKvHeads: {spec.n_kv_heads}\n"
              f"💡 vocabSize: {spec.vocab_size}\n💡 seqLen: {spec.seq_len}\n"
              + (f"💡 nExperts: {spec.n_experts}\n💡 nActiveExperts: "
                 f"{spec.n_active_experts}\n💡 qkNorm: "
                 f"{int(spec.qk_norm) + spec.qk_norm_per_head}\n"
                 if spec.extended else "")
              + (f"💡 attnKind: {spec.attn_kind}\n💡 ropeTheta: "
                 f"{spec.rope_theta:g}\n💡 normEps: {spec.norm_eps:g}\n"
                 if spec.header_version >= 3 else "")
              + f"💡 nSlices: {tp} sp: {args.sp} scheme: "
              f"{scheme if tp > 1 else '-'} ({n_dev} devices, "
              f"{jax.devices()[0].platform})")
    if spec.n_experts and (tp > 1 or args.sp > 1):
        from ..ops.linear import MOE_TP_REFUSAL

        print(f"{MOE_TP_REFUSAL} (this run: tp={tp} sp={args.sp}; pass "
              f"--tp 1)", file=sys.stderr)
        return 2
    # batch prompts without --continuous run the lockstep batch, which has
    # no latent cache: only one sequence or the paged pool
    if _refuses(spec, args, tp, serve=prompts is not None):
        return 2
    if spec.retention and not quiet:
        print(_retention_line(spec, rows))   # one chip: rows is set
    if spec.latent and not quiet:
        print(_latent_line(spec))
    if spec.hybrid and not quiet:
        print(_hybrid_line(spec, rows))
    if spec.mixers and not quiet:
        print(_mixers_line(spec, rows))
    if spec.ssd and not quiet:
        print(_ssd_line(spec, rows))
    if spec.kda and not quiet:
        print(_kda_line(spec, rows))
    mesh = (make_mesh(sp=args.sp, tp=tp)
            if tp > 1 or args.sp > 1 else None)
    assumed = getattr(args, "_slice_tp_ranks", None)
    if assumed is not None:
        # slice-streamed weights: every band this host's devices will read
        # must have been fetched — verify the pre-mesh rank arithmetic
        # against the REAL mesh before any forward touches the params
        from ..parallel.mesh import local_axis_indices

        actual = local_axis_indices(mesh, "tp") if mesh is not None else {0}
        if not actual <= assumed:
            print(f"--stream-slices fetched tp ranks {sorted(assumed)} but "
                  f"this host's devices hold ranks {sorted(actual)} — the "
                  f"host->rank assumption does not match this topology; "
                  f"re-run without --stream-slices", file=sys.stderr)
            return 2
    import jax.numpy as jnp

    cache_dtype = jnp.bfloat16 if args.kv_cache_dtype == "bf16" else None
    if prompts is not None:  # batch mode: no Engine (its own device path)
        tokenizer = Tokenizer(args.tokenizer, spec.vocab_size)
        seed = args.seed if args.seed is not None else int(time.time())
        if args.continuous:
            from ..runtime.continuous import generate_continuous

            reg = None
            if args.metrics:
                from ..obs.metrics import Registry

                reg = Registry()
            generate_continuous(spec, params, tokenizer, prompts, args.steps,
                                args.temperature, args.topp, seed,
                                slots=args.slots, cache_dtype=cache_dtype,
                                mesh=mesh, quiet=quiet,
                                prefill_chunk=args.prefill_chunk,
                                block_steps=args.block_steps,
                                # multi-host: every host must sample the
                                # identical stream — pin the numpy sampler
                                # (see sampling.Sampler docstring)
                                use_native_sampler=not args.coordinator,
                                fast_prefill=args.fast_prefill,
                                page_size=args.kv_page_size,
                                kv_pages=args.kv_pages,
                                spec_k=args.spec_k,
                                spec_ngram=args.spec_ngram,
                                dispatch_tokens=args.dispatch_tokens,
                                kv_quant=args.kv_quant,
                                kv_host_pages=args.kv_host_pages,
                                kv_disk_dir=args.kv_disk_dir,
                                kv_disk_bytes=int(args.kv_disk_gb
                                                  * (1 << 30)),
                                metrics=reg, q40_layout=q40_layout)
            if reg is not None:
                print(reg.expose(), file=sys.stderr, end="")
            return 0
        from ..runtime.generate import generate_batch

        if args.metrics:
            # lockstep batch: one fused device program, no per-request
            # lifecycle to trace — say so instead of silently dropping
            # the flag (the continuous engine has the instruments)
            print("--metrics has nothing to collect on the lockstep batch "
                  "path; use --continuous for request-lifecycle metrics",
                  file=sys.stderr)
        if args.spec_k:
            # same precedent: speculative decoding is a continuous-engine
            # mode — a silently-dropped flag would read as "no speedup"
            print("--spec-k only applies to the continuous engine; use "
                  "--continuous (with --kv-page-size) for speculative "
                  "decoding", file=sys.stderr)
        if args.kv_page_size or args.kv_quant != "f32":
            # paged KV (and therefore q8 pages) is a continuous-engine
            # mode too — the lockstep batch runs the contiguous f32
            # cache, and a silently-dropped --kv-quant q8 would read as
            # "no capacity win"
            print("--kv-page-size/--kv-quant only apply to the "
                  "continuous engine; add --continuous for the paged "
                  "(and quantized) KV pool", file=sys.stderr)
        generate_batch(spec, params, tokenizer, prompts, args.steps,
                       args.temperature, args.topp, seed,
                       cache_dtype=cache_dtype, mesh=mesh, quiet=quiet,
                       q40_layout=q40_layout)
        return 0
    engine = Engine(spec, params, mesh=mesh, cache_dtype=cache_dtype,
                    fast_prefill=args.fast_prefill, q40_layout=q40_layout)
    if not quiet:
        from ..obs.spans import log_startup

        # where the seconds since the load began went, by phase, and every
        # program made so far by name; the programs made from here on (the
        # step's, at the first token) are logged as they are made
        log_startup()
        _print_device_memory("loaded")

    tokenizer = Tokenizer(args.tokenizer, spec.vocab_size)
    seed = args.seed if args.seed is not None else int(time.time())
    # multi-host: every host must sample the IDENTICAL chain or the SPMD
    # collectives deadlock — pin the numpy sampler (the native one can
    # differ by ulps across libm builds, and a host without a toolchain
    # falls back to numpy anyway)
    sampler = Sampler(spec.vocab_size, args.temperature, args.topp, seed,
                      use_native=not args.coordinator)
    # pieces print inside the per-token stats lines (reference behavior:
    # tokenizer.cpp prints each piece once, at the end of the 🔶 line)
    resume = None
    if args.resume_state:
        from ..runtime.checkpoint import load_generation_state

        pos0, tok0, prev0, rest0 = load_generation_state(
            args.resume_state, engine, sampler)
        resume = (pos0, tok0)
        if not quiet:
            print(f"⏩ Resumed at pos {pos0} ({len(prev0)} tokens so far)")
    import contextlib

    from ..obs.profiler import capture_options

    prof = (jax.profiler.trace(args.profile,
                               profiler_options=capture_options())
            if args.profile else contextlib.nullcontext())
    prev = prev0 if args.resume_state else []
    with prof:
        if args.fast:
            out, stats = generate_fast(engine, tokenizer, sampler,
                                       args.prompt or "", args.steps,
                                       quiet=quiet, resume=resume,
                                       resume_prompt=(rest0 if resume
                                                      else None),
                                       prefill_chunk=args.prefill_chunk)
        else:
            out, stats = generate(engine, tokenizer, sampler,
                                  args.prompt or "", args.steps, quiet=quiet,
                                  resume=resume,
                                  resume_prompt=(rest0 if resume else None),
                                  prefill_chunk=args.prefill_chunk)
    if not quiet:
        _print_device_memory("end")
    if args.profile and not quiet:
        print(f"⏩ Profiler trace written to {args.profile}")
    if args.metrics:
        # one-shot runs have no /metrics endpoint: expose the run's
        # telemetry as a Prometheus text dump on stderr (same metric
        # names as the server's scrape)
        from ..obs.metrics import Registry
        from ..obs.trace import STEP_BUCKETS

        reg = Registry()
        h = reg.histogram("dllama_request_decode_token_seconds",
                          "Per-token decode latency", buckets=STEP_BUCKETS)
        for ms in stats.token_ms:
            h.observe(ms / 1000.0)
        reg.counter("dllama_generated_tokens_total",
                    "Tokens generated this run").inc(stats.tokens)
        print(reg.expose(), file=sys.stderr, end="")
    if args.save_state:
        from ..io.tokenizer import BOS
        from ..runtime.checkpoint import save_generation_state

        if stats.final_pos > 0 and stats.final_token != BOS:
            save_generation_state(args.save_state, engine, sampler,
                                  stats.final_pos, stats.final_token,
                                  prev + out, stats.prompt_rest)
            if not quiet:
                print(f"⏩ Saved generation state to {args.save_state}")
        elif not quiet:
            print("💡 Generation ended (BOS or zero steps); nothing "
                  "resumable to save")
    return 0


def cmd_worker(argv: list[str]) -> int:
    """Multi-host worker = the same SPMD program as inference, silenced.

    JAX's multi-controller model requires every process to execute the jitted
    computations itself (there is no passive participant); ``worker`` exists
    so launch scripts keep the reference's root/worker vocabulary.
    """
    if "--port" in argv:  # accepted for reference-CLI compatibility
        i = argv.index("--port")
        argv = argv[:i] + argv[i + 2:]
    if "--coordinator" not in argv:
        print("💡 On TPU, single-host workers are chips of the mesh — run "
              "'inference --tp N' instead. For multi-host, pass the same "
              "flags as inference plus --coordinator host:port "
              "--num-hosts H --host-id I (I >= 1).", file=sys.stderr)
        return 2
    return cmd_inference(argv, quiet=True)


def cmd_serve(argv: list[str]) -> int:
    """HTTP inference server over the continuous-batching engine
    (runtime/server.py) — concurrent clients stream through the slot pool."""
    ap = argparse.ArgumentParser(prog="dllama-tpu serve")
    ap.add_argument("--model", required=True, help=_MODEL_HELP)
    ap.add_argument("--tokenizer", required=True)
    ap.add_argument("--weights-float-type", default="q40", choices=sorted(_FT),
                    help=_WFT_HELP)
    ap.add_argument("--buffer-float-type", default="f32", choices=sorted(_FT))
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9990)
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent sequences (cache slots)")
    ap.add_argument("--steps", type=int, default=64,
                    help="default max new positions per request")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--topp", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel ways (default: single chip; an "
                         "expert model is refused under --tp > 1)")
    ap.add_argument("--tp-scheme", default=None,
                    choices=("ref", "fused", "overlap"),
                    help="tp collective schedule (= DLLAMA_TP_SCHEME; see "
                         "'inference --help')")
    ap.add_argument("--kv-cache-dtype", default="f32",
                    choices=("f32", "bf16"))
    ap.add_argument("--prefill-chunk", type=int, default=128, metavar="N",
                    help="admission prefill: fill a new request's prompt "
                         "in T=N chunked passes (0/1 disables)")
    ap.add_argument("--block-steps", type=int, default=1, metavar="K",
                    help="fuse K decode steps into one device dispatch "
                         "(admission + per-token streaming at chain "
                         "boundaries; cuts host round-trips Kx — set 8-16 "
                         "on remote/high-latency runtimes)")
    ap.add_argument("--kv-page-size", type=int, default=0, metavar="P",
                    help="paged KV cache: slots map P-position pages from "
                         "a shared pool through page tables, with radix "
                         "prefix sharing of common prompt prefixes — the "
                         "shared-system-prompt serving win (0 = contiguous "
                         "per-slot cache)")
    ap.add_argument("--kv-pages", type=int, default=0, metavar="N",
                    help="paged-KV pool size in pages (default: "
                         "slots * seq_len / page-size; fewer pages serve "
                         "more slots at equal HBM)")
    ap.add_argument("--kv-quant", default=None, choices=("f32", "q8"),
                    help="KV page quantization (= DLLAMA_KV_QUANT; needs "
                         "--kv-page-size): q8 halves-and-more the page "
                         "bytes (Q80 int8+scale planes, ~1/3.8 of f32) "
                         "so the same HBM serves ~3.8x pool pages; "
                         "surfaces in /health paged_kv and "
                         "dllama_kv_quant_info")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="self-speculative decoding (needs "
                         "--kv-page-size): n-gram drafts verified K "
                         "positions per dispatch, lossless; accept rate "
                         "surfaces in /health and /metrics (0 = off)")
    _add_kv_tier_flags(ap)
    ap.add_argument("--spec-ngram", type=int, default=3, metavar="N",
                    help="longest drafter n-gram (falls back to 1)")
    ap.add_argument("--dispatch-tokens", type=int, default=0, metavar="T",
                    help="token-budget mixed dispatches (needs "
                         "--kv-page-size): decode rows + ONE prefill "
                         "slice share each fused dispatch under a T-token "
                         "budget — single-pool serving without prefill "
                         "stalls (-1 sizes from --prefill-chunk; 0 = "
                         "off; incompatible with --spec-k)")
    ap.add_argument("--fast-prefill", action="store_true",
                    help="bf16 matmul precision for admission prefill "
                         "(documented tolerance; decode untouched)")
    ap.add_argument("--metrics", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve GET /metrics (Prometheus text) and collect "
                         "request-lifecycle histograms (queue wait, TTFT, "
                         "per-token latency) + engine step metrics; "
                         "--no-metrics turns collection fully off the "
                         "decode hot path")
    ap.add_argument("--slo", default=None, metavar="SPEC",
                    help="SLO policy: name:ttft_ms:token_ms[,...], first "
                         "class is the default (e.g. "
                         "'interactive:1000:100,batch:60000:5000'); "
                         "requests pick a class with the \"class\" field, "
                         "verdicts land in /health's \"slo\" block and "
                         "dllama_slo_requests_total{class,verdict}. "
                         "Default: the built-in interactive/batch policy; "
                         "--slo off disables tracking")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="ARM DETERMINISTIC FAULT INJECTION (drills only — "
                         "never in front of real traffic): "
                         "key=value[,...] with step_delay_every, "
                         "step_delay_ms, deny_pages, leak_on_cancel "
                         "(runtime/chaos.ChaosMonkey)")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal (runtime/journal.py): "
                         "every admission/sampled token/retirement appends "
                         "a record; on restart the server re-admits "
                         "incomplete requests and their continued streams "
                         "are bitwise the uninterrupted run's (journaled "
                         "per-request seeds + coin cursors)")
    ap.add_argument("--journal-fsync", default="batch",
                    choices=("always", "batch", "off"),
                    help="journal durability: 'always' fsyncs every record "
                         "(power-loss safe, slowest), 'batch' fsyncs once "
                         "per scheduler step (default; at most one "
                         "dispatch's tokens at risk), 'off' leaves "
                         "flushing to the OS (process-crash safe only)")
    ap.add_argument("--watchdog-ms", type=float, default=0.0, metavar="MS",
                    help="step watchdog (runtime/supervisor.py): a device "
                         "dispatch exceeding this deadline marks /health "
                         "degraded and logs — hung-device detection "
                         "(0 = off)")
    ap.add_argument("--drain-s", type=float, default=10.0, metavar="S",
                    help="graceful-drain budget on SIGTERM: stop admission "
                         "(503), let in-flight requests finish for up to S "
                         "seconds, journal the remainder, exit 0")
    ap.add_argument("--supervise", action="store_true",
                    help="run serve under the crash-loop supervisor: "
                         "respawn on non-zero exits with exponential "
                         "backoff, forward SIGTERM for exactly-once "
                         "graceful drain (pair with --journal so the "
                         "respawned child recovers in-flight work)")
    ap.add_argument("--max-restarts", type=int, default=None, metavar="N",
                    help="(--supervise) give up after N respawns "
                         "(default: unbounded)")
    ap.add_argument("--disagg-role", default=None,
                    choices=("prefill", "decode"),
                    help="prefill/decode disaggregation (ISSUE 14): "
                         "'prefill' serves POST /prefill + the DCN page "
                         "channel (fills KV pages, samples the first "
                         "token, ships full prompt pages); 'decode' "
                         "fronts clients and forwards long prompts to "
                         "--disagg-peer, resuming the stream bitwise "
                         "from the returned journal record. Needs "
                         "--kv-page-size (pages are the transfer unit)")
    ap.add_argument("--disagg-peer", default=None, metavar="HOST:PORT",
                    help="(--disagg-role decode) the prefill server")
    ap.add_argument("--page-channel-port", type=int, default=0,
                    metavar="PORT",
                    help="(--disagg-role prefill) page-channel listen "
                         "port (0 = pick a free one; exposed in "
                         "/health's disagg block)")
    ap.add_argument("--handoff-min-pages", type=int, default=2,
                    metavar="N",
                    help="(--disagg-role decode) forward only prompts "
                         "spanning >= N full KV pages; shorter prompts "
                         "prefill locally — handing them off would ship "
                         "nothing and re-derive everything")
    ap.add_argument("--watch-interval", type=float, default=0.0,
                    metavar="S",
                    help="watchtower incident detection (ISSUE 20, "
                         "obs/watch.py): sample the engine's signal "
                         "plane every S seconds and run the detector "
                         "suite (SLO burn rate, page leak, stall shift, "
                         "goodput/spec collapse, recovery storm, "
                         "handoff spike); incidents surface on "
                         "/debug/incidents + /health's watch block and "
                         "dump a flight-recorder bundle when --flightrec "
                         "is set (0 = off; detectors still run on "
                         "manual watch_tick() calls)")
    ap.add_argument("--flightrec", default=None, metavar="DIR",
                    help="crash-forensics flight recorder (ISSUE 15, "
                         "obs/flightrec.py): drop a postmortem bundle "
                         "(recent spans + metrics snapshot + journal "
                         "tail + config fingerprint) into DIR when the "
                         "step watchdog fires, on the SIGTERM drain, "
                         "and on each --supervise crash-loop respawn; "
                         "validate bundles with tools/tracecheck.py "
                         "(the ring records either way; DIR enables "
                         "the files)")
    _obs_flags(ap)
    args = ap.parse_args(argv)
    if args.supervise:
        # re-exec THIS serve command (supervision flags stripped) under the
        # crash-loop wrapper — before any model load: the supervisor
        # process must stay tiny and device-free
        from ..runtime.supervisor import serve_child_cmd, supervise

        return supervise(serve_child_cmd(argv),
                         max_restarts=args.max_restarts,
                         flightrec_dir=args.flightrec)
    _apply_log_json(args)
    if args.kv_quant:
        os.environ["DLLAMA_KV_QUANT"] = args.kv_quant
    from ..ops.pallas_paged_attention import kv_quant_mode

    args.kv_quant = kv_quant_mode()  # env or flag, validated HERE —
    #                                  before any gate or model load
    if args.slots < 1:
        print(f"--slots must be positive, got {args.slots}", file=sys.stderr)
        return 2
    if args.fast_prefill and args.prefill_chunk <= 1:
        print("--fast-prefill only affects admission prefill; pass "
              "--prefill-chunk N (N > 1)", file=sys.stderr)
        return 2
    if args.spec_k and args.kv_page_size <= 0:
        # same argparse-time gate as inference: never surface this from
        # engine construction after the model load
        print("--spec-k needs the paged KV cache: add --kv-page-size P",
              file=sys.stderr)
        return 2
    if args.spec_k and args.dispatch_tokens:
        # same argparse-time gate as inference mode: the verify window
        # and the prefill slice both claim the per-row span
        print("--spec-k is incompatible with --dispatch-tokens: the "
              "verify window and the prefill slice both claim the "
              "per-row span (drop one)", file=sys.stderr)
        return 2
    if args.dispatch_tokens and args.kv_page_size <= 0:
        print("--dispatch-tokens needs the paged KV cache: add "
              "--kv-page-size P", file=sys.stderr)
        return 2
    if args.kv_quant == "q8" and args.kv_page_size <= 0:
        # q8 quantizes PAGE planes — meaningless without the pool; fail
        # before the model load, exactly like the inference-mode gate
        print("--kv-quant q8 quantizes paged KV pages: add "
              "--kv-page-size P", file=sys.stderr)
        return 2
    tier_err = _check_kv_tier_args(args, "")
    if tier_err:
        print(tier_err, file=sys.stderr)
        return 2
    if args.disagg_role and args.kv_page_size <= 0:
        # pages are the handoff transfer unit — same argparse-time gate
        # discipline as --spec-k / --kv-quant
        print("--disagg-role ships KV PAGES between pools: add "
              "--kv-page-size P", file=sys.stderr)
        return 2
    if args.disagg_role == "decode" and not args.disagg_peer:
        print("--disagg-role decode needs --disagg-peer HOST:PORT (the "
              "prefill server)", file=sys.stderr)
        return 2
    if args.disagg_peer and args.disagg_role != "decode":
        print("--disagg-peer only means something with --disagg-role "
              "decode", file=sys.stderr)
        return 2
    if args.handoff_min_pages < 1:
        print(f"--handoff-min-pages must be >= 1, got "
              f"{args.handoff_min_pages}", file=sys.stderr)
        return 2
    from ..obs.slo import SLOPolicy
    from ..runtime.chaos import ChaosMonkey

    try:
        slo = (None if args.slo == "off"
               else SLOPolicy.parse(args.slo) if args.slo
               else SLOPolicy.serving_default())
        chaos = ChaosMonkey.parse(args.chaos) if args.chaos else None
    except ValueError as e:
        print(f"serve: {e}", file=sys.stderr)
        return 2
    if chaos is not None:
        print("🔶 CHAOS ARMED: deterministic fault injection is live "
              f"({args.chaos}) — drill traffic only", file=sys.stderr)
    journal = None
    if args.journal:
        from ..runtime.journal import JournalCorruption, RequestJournal

        try:
            # open BEFORE the model load: non-tail damage must refuse in
            # milliseconds, not after minutes of weight streaming; the
            # config fingerprint (which needs the loaded spec) attaches
            # below via set_config
            journal = RequestJournal(args.journal,
                                     fsync=args.journal_fsync)
        except JournalCorruption as e:
            # recovering from an untrusted history would serve wrong
            # bytes — refuse to start, operator decides
            print(f"serve: journal {args.journal} is corrupt: {e}\n"
                  f"       (move it aside to start fresh, or restore a "
                  f"good copy to recover)", file=sys.stderr)
            return 1

    import jax.numpy as jnp

    from ..io.loader import load_model
    from ..io.tokenizer import Tokenizer
    from ..parallel import make_mesh
    from ..parallel.comm_stats import tp_scheme
    from ..runtime.server import InferenceServer

    if args.tp_scheme:
        os.environ["DLLAMA_TP_SCHEME"] = args.tp_scheme
    tp_scheme()  # validate before the model load
    sharded = bool(args.tp and args.tp > 1)
    wft = _FT[args.weights_float_type]
    bft = _FT[args.buffer_float_type]
    if sharded:  # mesh: tp-aware packing in shard_params
        spec, params = load_model(args.model, weights_float_type=wft,
                                  buffer_float_type=bft)
        q40_layout = None  # the engine's own (the stock value)
    else:
        spec, params, q40_layout = _load_one_chip(args.model, wft, bft,
                                                  rows=args.slots)
    tokenizer = Tokenizer(args.tokenizer, spec.vocab_size)
    if spec.n_experts and sharded:
        from ..ops.linear import MOE_TP_REFUSAL

        print(f"{MOE_TP_REFUSAL} (this run: --tp {args.tp})",
              file=sys.stderr)
        return 2
    if _refuses(spec, args, args.tp or 1, serve=True):
        return 2
    if spec.retention:
        print(_retention_line(spec, args.slots))
    if spec.latent:
        print(_latent_line(spec))
    if spec.hybrid:
        print(_hybrid_line(spec, args.slots))
    if spec.mixers:
        print(_mixers_line(spec, args.slots))
    if spec.ssd:
        print(_ssd_line(spec, args.slots))
    if spec.kda:
        print(_kda_line(spec, args.slots))
    mesh = make_mesh(tp=args.tp) if args.tp and args.tp > 1 else None
    seed = args.seed if args.seed is not None else int(time.time())
    if journal is not None:
        from ..runtime.journal import config_fingerprint, weight_file_digest

        # the WAL header records what a bitwise replay depends on: model
        # dims + quant types (spec), the tp collective scheme (tp=1 runs
        # one scheme-independent program — recorded as 'single' so a
        # scheme-env change cannot strand single-chip journals), the
        # sampler SEED POLICY ('explicit:<seed>' only when --seed is
        # pinned — the time-derived default passes across restarts:
        # replay reads journaled per-request seeds, never the base), and
        # a weight-file digest prefix. ContinuousEngine.recover refuses
        # on mismatch when the journal holds live work.
        seed_policy = (f"explicit:{args.seed}" if args.seed is not None
                       else "time")
        journal.set_config(config_fingerprint(
            spec, tp_scheme() if sharded else "single", seed_policy,
            weights_digest=weight_file_digest(args.model),
            kv_quant=args.kv_quant,
            kv_cache_dtype=args.kv_cache_dtype,
            kv_host_pages=args.kv_host_pages,
            kv_disk=bool(args.kv_disk_dir)))
    cache_dtype = jnp.bfloat16 if args.kv_cache_dtype == "bf16" else None
    try:
        server = InferenceServer(spec, params, tokenizer, args.host,
                                 args.port, args.slots, args.steps,
                                 args.temperature, args.topp, seed,
                                 cache_dtype=cache_dtype, mesh=mesh,
                                 prefill_chunk=args.prefill_chunk,
                                 block_steps=args.block_steps,
                                 fast_prefill=args.fast_prefill,
                                 metrics=args.metrics,
                                 page_size=args.kv_page_size,
                                 kv_pages=args.kv_pages,
                                 spec_k=args.spec_k,
                                 spec_ngram=args.spec_ngram,
                                 dispatch_tokens=args.dispatch_tokens,
                                 slo=slo,
                                 chaos=chaos, journal=journal,
                                 watchdog_s=args.watchdog_ms / 1e3,
                                 drain_s=args.drain_s,
                                 kv_quant=args.kv_quant,
                                 kv_host_pages=args.kv_host_pages,
                                 kv_disk_dir=args.kv_disk_dir,
                                 kv_disk_bytes=int(args.kv_disk_gb
                                                   * (1 << 30)),
                                 disagg_role=args.disagg_role,
                                 disagg_peer=args.disagg_peer,
                                 page_channel_port=args.page_channel_port,
                                 handoff_min_pages=args.handoff_min_pages,
                                 flightrec_dir=args.flightrec,
                                 watch_interval_s=args.watch_interval,
                                 q40_layout=q40_layout)
    except Exception as e:
        from ..runtime.journal import JournalConfigMismatch

        if not isinstance(e, JournalConfigMismatch):
            raise
        # recovery refused: the journal's recorded config fingerprint does
        # not match this serving config — never silently replay wrong
        # bytes; the operator restores the original config or moves the
        # journal aside
        print(f"serve: {e}", file=sys.stderr)
        return 1
    endpoints = "POST /generate, GET /health" + (
        ", GET /metrics, GET /debug/timeline, POST /profile"
        if args.metrics else "")
    print(f"🌐 serving on http://{args.host}:{server.port} "
          f"({args.slots} slots, {endpoints})")
    if args.disagg_role == "prefill":
        print(f"🌐 disagg role: prefill (POST /prefill; page channel on "
              f"port {server._page_channel.port})")
    elif args.disagg_role == "decode":
        print(f"🌐 disagg role: decode (peer {args.disagg_peer}, handoff "
              f"at >= {args.handoff_min_pages} full pages)")
    if server.recovered:
        print(f"🌐 recovered {server.recovered} journaled requests "
              f"from {args.journal}")
    _print_device_memory("loaded")
    server.serve_forever()
    _print_device_memory("end")
    return 0


def cmd_train(argv: list[str]) -> int:
    """Next-token training on a text corpus (capability extension; the
    reference is inference-only). Weights densify to f32, the batch is
    dp-sharded and the weights tp-sharded like inference (parallel/train.py),
    and --save/resume-state give exact-resume checkpoints: a split run
    reproduces the unsplit run's losses step for step (the data schedule is
    a pure function of --seed and the step counter).
    """
    ap = argparse.ArgumentParser(prog="dllama-tpu train")
    ap.add_argument("--model", required=True)
    ap.add_argument("--tokenizer", required=True)
    ap.add_argument("--data", required=True,
                    help="UTF-8 text corpus; tokenized once, windows "
                         "sampled per step")
    ap.add_argument("--weights-float-type", default="f32", choices=sorted(_FT))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128,
                    help="training window length (tokens per row)")
    ap.add_argument("--learning-rate", type=float, default=1e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--save-state", default=None, metavar="PATH")
    ap.add_argument("--resume-state", default=None, metavar="PATH")
    _add_common(ap)
    args = ap.parse_args(argv)
    # multi-host training: every host joins the global dp x tp mesh and runs
    # the identical program — the data schedule is already a pure function
    # of (--seed, step), so all hosts feed the same global windows and jit
    # shards them (dp can cross the host boundary); only host 0 prints
    quiet = bool(args.host_id)
    # before the distributed barrier; slice streaming is inference-only
    # (training densifies + re-shards, so a host needs the full tensors)
    _ws = _weight_streaming(args, quiet, allow_slices=False)
    _maybe_distributed(args)

    import numpy as np

    import jax.numpy as jnp

    from ..io.loader import densify_params, load_model, read_spec
    from ..io.tokenizer import Tokenizer
    from ..parallel import make_mesh
    from ..parallel.train import (load_train_state, make_train_step,
                                  read_train_meta, save_train_state,
                                  template_params)

    # header-only read: validate flags before streaming multi-GB weights
    spec = read_spec(args.model,
                     weights_float_type=_FT[args.weights_float_type])
    if args.seq + 1 > spec.seq_len:
        print(f"--seq must be < seq_len ({spec.seq_len}), got {args.seq}",
              file=sys.stderr)
        return 2
    tokenizer = Tokenizer(args.tokenizer, spec.vocab_size)
    with open(args.data, "rb") as fh:
        text = fh.read().decode("utf-8", errors="replace")
    corpus = np.asarray(tokenizer.encode(text, bos=True, eos=False),
                        dtype=np.int32)
    if len(corpus) < args.seq + 1:  # one (seq+1)-token window minimum
        print(f"corpus has {len(corpus)} tokens; need >= {args.seq + 1}",
              file=sys.stderr)
        return 2
    mesh = make_mesh(dp=args.dp, tp=args.tp)
    init_fn, step_fn = make_train_step(spec, mesh,
                                       learning_rate=args.learning_rate)
    start = 0
    if args.resume_state:
        meta = read_train_meta(args.resume_state)
        if meta.get("data_seed", args.seed) != args.seed:
            # the data schedule is a pure function of (seed, step): a
            # different seed silently breaks split == unsplit
            print(f"--resume-state was trained with --seed "
                  f"{meta['data_seed']}; pass the same seed (got "
                  f"{args.seed})", file=sys.stderr)
            return 2
        # the checkpoint overwrites every value: a zero template gives the
        # tree structure/shardings without streaming the model weights
        p, o = init_fn(template_params(spec))
        p, o, start = load_train_state(args.resume_state, spec, p, o,
                                       return_step=True)
        if not quiet:
            print(f"⏩ Resumed training at step {start}")
    else:
        _, params = load_model(args.model, spec=spec)
        p, o = init_fn(densify_params(params))

    def windows(step: int) -> np.ndarray:
        """(batch, seq+1) token windows — a pure function of (seed, step),
        so a resumed run continues the identical schedule. The exclusive
        high bound len - seq keeps the LAST corpus token reachable as a
        target (start len - seq - 1 is the final valid window)."""
        rng = np.random.default_rng((args.seed, step))
        starts = rng.integers(0, len(corpus) - args.seq, args.batch)
        return np.stack([corpus[s:s + args.seq + 1] for s in starts])

    for step in range(start, start + args.steps):
        t0 = time.perf_counter()
        p, o, loss = step_fn(p, o, jnp.asarray(windows(step)))
        loss = float(loss)
        if not quiet:
            print(f"🔶 step {step:5d}  loss {loss:8.4f}  "
                  f"{(time.perf_counter() - t0) * 1000:7.1f} ms")
    if args.save_state and not args.host_id:  # one writer: the root host
        save_train_state(args.save_state, spec, p, o,
                         step=start + args.steps, data_seed=args.seed)
        print(f"⏩ Saved training state to {args.save_state} "
              f"(step {start + args.steps})")
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: dllama-tpu {inference|worker|serve|train|convert} "
              f"[options]\n{__doc__}")
        return 0 if argv else 1
    mode, rest = argv[0], argv[1:]
    if mode in ("inference", "worker", "serve", "train"):
        # on-disk XLA compile cache: the first process pays the minutes-long
        # chain compile, every later invocation deserializes it (cold-start
        # attack — utils/compile_cache.py). Only for the jax-running modes:
        # convert (and the error path) stays numpy-only and side-effect-free.
        from ..utils.compile_cache import enable_persistent_cache

        enable_persistent_cache()
    if mode == "inference":
        return cmd_inference(rest)
    if mode == "worker":
        return cmd_worker(rest)
    if mode == "serve":
        return cmd_serve(rest)
    if mode == "train":
        return cmd_train(rest)
    if mode == "convert":
        from ..convert import main as convert_main

        convert_main(rest)
        return 0
    print(f"unknown mode {mode!r} (expected "
          f"inference|worker|serve|train|convert)", file=sys.stderr)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
