"""Rehearsal 3 of the ``on-chip-measurement`` guide: compile, for a
DESCRIBED v5e:2x2 and with no chip attached, the main-path programs of a
configuration at its published widths, from shape trees. What the chip's
compiler refuses shows here at no chip time, and ``memory_analysis()`` says
what each program needs beside its arguments (``kv_pages`` is sized from it).

  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_compile.py \
      --config mistral-7b-q40 --entry inference|serve
  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_compile.py \
      --config yi-34b-q40-tp4 --entry inference

One entry a process: the program's layout policy sets process-wide
environment knobs from the dispatch width. Nothing runs; a compile that
passes is not a chip run and is never reported as one.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = 1 << 30


def shape_tree(packed1: dict, n_layers: int, sharding_of):
    """ShapeDtypeStructs of a packed ONE-layer tree, stacked leaves widened
    to ``n_layers`` (every leaf but the embedding, the final norm and the
    classifier carries a leading layer axis)."""
    import jax

    flat = ("tok_embedding", "rms_final", "wcls")

    def widen(key, leaf_path, a):
        shape = tuple(a.shape)
        if key not in flat:
            assert shape[0] == 1, (key, shape)
            shape = (n_layers, *shape[1:])
        return jax.ShapeDtypeStruct(shape, a.dtype,
                                    sharding=sharding_of(key, leaf_path))

    out = {}
    for k, v in packed1.items():
        if isinstance(v, tuple):
            out[k] = type(v)(*(widen(k, i, a) for i, a in enumerate(v)))
        else:
            out[k] = widen(k, None, v)
    return out


def report(name: str, lowered, rows: list) -> None:
    t0 = time.time()
    try:
        compiled = lowered.compile()
    except Exception as e:          # the compiler's refusal is the finding
        rows.append({"program": name, "refused": f"{type(e).__name__}: "
                                                 f"{str(e)[:600]}"})
        print(json.dumps(rows[-1]), flush=True)
        return
    m = compiled.memory_analysis()
    text = compiled.as_text()
    rows.append({
        "program": name, "compile_s": round(time.time() - t0, 1),
        "argument_gib": round(m.argument_size_in_bytes / GIB, 3),
        "output_gib": round(m.output_size_in_bytes / GIB, 3),
        "alias_gib": round(m.alias_size_in_bytes / GIB, 3),
        "temp_gib": round(m.temp_size_in_bytes / GIB, 3),
        "total_gib": round((m.argument_size_in_bytes
                            + m.output_size_in_bytes
                            - m.alias_size_in_bytes
                            + m.temp_size_in_bytes) / GIB, 3),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "collectives": {k: text.count(k + "(") + text.count(k + "-start(")
                        for k in ("all-reduce", "all-gather",
                                  "reduce-scatter", "collective-permute")},
    })
    print(json.dumps(rows[-1]), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--entry", required=True, choices=("inference", "serve"))
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="override entries.serve.kv_pages")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

    # code that asks for the backend must take its chip branch: Pallas
    # kernels (not interpret mode) and the 'auto' kernel modes
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)

    from benchmark.harness import cells, model
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.ops.linear import (apply_q40_body_policy,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params)

    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    flags = config["entries"][args.entry]
    sizes = model.sizes_of(config)
    spec = model.program_spec(sizes)
    one = model.program_spec(dict(sizes, n_layers=1))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    tree1 = model.codec_tree(dict(sizes, n_layers=1), 0)
    rows: list = []
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    L, S = sizes["n_layers"], sizes["seq_len"]
    kvh, hs = sizes["n_kv_heads"], sizes["dim"] // sizes["n_heads"]
    tp = int(flags.get("tp", 1))

    if tp > 1:
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.parallel import tp as tpmod
        from distributed_llama_tpu.parallel.comm_stats import tp_scheme

        scheme = tp_scheme()
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, 1, tp),
                    ("dp", "sp", "tp"))
        packed1 = pack_q40_params(
            tree1, tp=tp, input_sharded=(
                tpmod.FUSED_INPUT_SHARDED
                if scheme in tpmod._INPUT_SHARDED_SCHEMES else ()))
        specs = tpmod.param_specs(packed1, scheme)

        def sharding_of(key, i):
            s = specs[key]
            return NamedSharding(mesh, s if i is None else s[i])

        params = shape_tree(packed1, L, sharding_of)
        kinds = {k: type(v).__name__ for k, v in packed1.items()}
        print(json.dumps({"tp": tp, "scheme": scheme, "leaf_kinds": kinds}),
              flush=True)
        cache_sh = NamedSharding(mesh, tpmod.CACHE_SPEC.k)
        cache = llama.KVCache(*(jax.ShapeDtypeStruct(
            (L, S, kvh, hs), jnp.float32, sharding=cache_sh)
            for _ in range(2)))
        rep = NamedSharding(mesh, P())
        fwd = tpmod.make_sharded_forward(spec, mesh, scheme=scheme)
        for name, t in (("tp decode step T=1", 1),
                        (f"tp prefill chunk T={flags['prefill_chunk']}",
                         int(flags["prefill_chunk"]))):
            report(name, fwd.lower(
                params, cache,
                jax.ShapeDtypeStruct((t,), jnp.int32, sharding=rep),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)), rows)
    else:
        from distributed_llama_tpu.ops.pallas_layer import prepare_mega_params

        width = 1 if args.entry == "inference" else int(flags["slots"])
        policy = apply_q40_body_policy(spec, rows=width)
        packed1 = fuse_q40_layer_matmuls(
            pack_q40_params(tree1, allow_nb_major=True))
        if args.entry == "inference":
            packed1 = prepare_mega_params(one, packed1)
        params = shape_tree(packed1, L, lambda k, i: chip)
        kinds = {k: type(v).__name__ for k, v in packed1.items()}
        print(json.dumps({"policy": policy, "leaf_kinds": kinds}),
              flush=True)
        seq_cache = llama.KVCache(sds((L, S, kvh, hs), jnp.float32),
                                  sds((L, S, kvh, hs), jnp.float32))
        fwd = jax.jit(functools.partial(llama.forward, spec),
                      donate_argnums=1)
        chunk = int(flags["prefill_chunk"])
        if args.entry == "inference":
            report("decode step T=1", fwd.lower(
                params, seq_cache, sds((1,), jnp.int32),
                sds((), jnp.int32)), rows)
            report(f"prefill chunk T={chunk}", fwd.lower(
                params, seq_cache, sds((chunk,), jnp.int32),
                sds((), jnp.int32)), rows)
        else:
            B, ps = int(flags["slots"]), int(flags["kv_page_size"])
            n_pages = (args.kv_pages or int(flags["kv_pages"])) + 1
            pool = llama.KVCache(
                sds((L, n_pages, ps, kvh, hs), jnp.float32),
                sds((L, n_pages, ps, kvh, hs), jnp.float32))
            step = jax.jit(functools.partial(llama.forward_batch_paged,
                                             spec, ps, kv_quant="f32"),
                           donate_argnums=1)
            report(f"paged decode step B={B}, pool {n_pages} pages",
                   step.lower(params, pool, sds((B,), jnp.int32),
                              sds((B,), jnp.int32),
                              sds((B, S // ps), jnp.int32)), rows)
            report(f"admission prefill chunk T={chunk}", fwd.lower(
                params, seq_cache, sds((chunk,), jnp.int32),
                sds((), jnp.int32)), rows)
            report("gather pages", jax.jit(
                lambda c, t: llama.gather_pages(c, t, ps)).lower(
                    pool, sds((S // ps,), jnp.int32)), rows)
            report("scatter pages", jax.jit(
                lambda c, s, t: llama.scatter_pages(c, s, t, ps),
                donate_argnums=0).lower(
                    pool, seq_cache, sds((S // ps,), jnp.int32)), rows)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"rehearse_{args.config}_{args.entry}.json"),
              "w", encoding="utf-8") as fh:
        json.dump(rows, fh, indent=1)
    return 1 if any("refused" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
