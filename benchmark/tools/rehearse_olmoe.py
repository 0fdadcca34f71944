"""``rehearse_compile.py`` for an expert configuration: compile, for a
DESCRIBED v5e:2x2 and with no chip attached, the ``serve`` programs of
``olmoe-1b-7b-q40`` at its published widths from shape trees (paged decode
step at the configuration's slots, admission prefill chunk, gather and
scatter of pages) and the ``inference`` step, and print what each needs
beside its arguments. ``rehearse_compile.py`` builds its tree from
``harness/model.py``, which knows the dense block only; its ``shape_tree``
and ``report`` are imported.

  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_olmoe.py [--kv-pages N]

Nothing runs; a compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="olmoe-1b-7b-q40")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="override entries.serve.kv_pages")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"     # kernels, not interpret mode
    jax.config.update("jax_enable_compilation_cache", False)

    from benchmark.harness import cells, olmoe
    from benchmark.tools.rehearse_compile import report, shape_tree
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.ops.linear import (apply_q40_body_policy,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params)

    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    flags = config["entries"]["serve"]
    sizes = olmoe.sizes_of(config)
    spec = olmoe.program_spec(sizes)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    tree1 = olmoe.codec_tree(dict(sizes, n_layers=1), 0)
    B, ps = int(flags["slots"]), int(flags["kv_page_size"])
    policy = apply_q40_body_policy(spec, rows=B)
    packed1 = fuse_q40_layer_matmuls(
        pack_q40_params(tree1, allow_nb_major=True))
    L, S = sizes["n_layers"], sizes["seq_len"]
    kvh, hs = sizes["n_kv_heads"], sizes["dim"] // sizes["n_heads"]
    params = shape_tree(packed1, L, lambda k, i: chip)
    print(json.dumps({"policy": policy, "leaf_kinds": {
        k: type(v).__name__ for k, v in packed1.items()}}), flush=True)
    rows: list = []
    seq_cache = llama.KVCache(sds((L, S, kvh, hs), jnp.float32),
                              sds((L, S, kvh, hs), jnp.float32))
    n_pages = (args.kv_pages or int(flags["kv_pages"])) + 1
    pool = llama.KVCache(sds((L, n_pages, ps, kvh, hs), jnp.float32),
                         sds((L, n_pages, ps, kvh, hs), jnp.float32))
    step = jax.jit(functools.partial(llama.forward_batch_paged, spec, ps,
                                     kv_quant="f32", moe_counts=True),
                   donate_argnums=1)
    report(f"paged decode step B={B}, pool {n_pages} pages",
           step.lower(params, pool, sds((B,), jnp.int32),
                      sds((B,), jnp.int32), sds((B, S // ps), jnp.int32)),
           rows)
    chunk = int(flags["prefill_chunk"])
    fwd = jax.jit(functools.partial(llama.forward, spec), donate_argnums=1)
    report(f"admission prefill chunk T={chunk}", fwd.lower(
        params, seq_cache, sds((chunk,), jnp.int32), sds((), jnp.int32)),
        rows)
    report("inference step T=1", jax.jit(
        functools.partial(llama.forward, spec, moe_counts=True),
        donate_argnums=1).lower(
            params, seq_cache, sds((1,), jnp.int32), sds((), jnp.int32)),
        rows)
    report("gather pages", jax.jit(
        lambda c, t: llama.gather_pages(c, t, ps)).lower(
            pool, sds((S // ps,), jnp.int32)), rows)
    report("scatter pages", jax.jit(
        lambda c, s, t: llama.scatter_pages(c, s, t, ps),
        donate_argnums=0).lower(
            pool, seq_cache, sds((S // ps,), jnp.int32)), rows)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"rehearse_{args.config}_serve.json"), "w",
              encoding="utf-8") as fh:
        json.dump(rows, fh, indent=1)
    return 1 if any("refused" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
