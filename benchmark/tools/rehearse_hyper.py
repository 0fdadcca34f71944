"""``rehearse_latent.py`` for a configuration whose residual path is several
streams (``harness/hyper.py``): compile, for a DESCRIBED v5e:2x2 and with no
chip attached, the ``serve`` programs of ``xing4-29b-a4b-q40`` at its
published widths from shape trees (paged decode step at the configuration's
slots with the pool aliased, admission prefill chunk, gather and scatter of
latent pages) and the ``inference`` step, print what each needs beside its
arguments, and count the decode step's instructions that the compiler made
of the residual path (those whose ``op_name`` lies under the ``hc.coef`` /
``hc.mix`` scopes, outside fused computations: ``harness/hyper.
path_instructions``, the rule the cell's trace readers tell the path's ops
by): what ``hc_ops_per_sublayer`` will read on the chip, before a chip is
asked.

  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_hyper.py [--kv-pages N]
      [--config-file benchmark/tests/tiny-hyper.json]

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


def path_instructions(hlo_text: str) -> dict:
    """{opcode: count} of ``harness/hyper.path_instructions``: a compiled
    module's instructions under a residual-path scope."""
    import collections

    from benchmark.harness import hyper

    return dict(collections.Counter(
        hyper.path_instructions(hlo_text).values()))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="xing4-29b-a4b-q40")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--dump-hlo", default=None, metavar="PATH",
                    help="write the decode step's compiled text there, "
                         "and compile nothing else")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="override entries.serve.kv_pages")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"     # kernels, not interpret mode
    jax.config.update("jax_enable_compilation_cache", False)

    from benchmark.harness import cells
    from benchmark.harness import hyper as latent
    from benchmark.tools.rehearse_compile import report, shape_tree
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.models.latent import (LatentCache,
                                                     plane_width,
                                                     prepare_latent_params)
    from distributed_llama_tpu.ops.linear import (announce_q40_layout,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params,
                                                  q40_body_policy)

    config = cells.load_json(args.config_file or os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    flags = config["entries"]["serve"]
    sizes = latent.sizes_of(config)
    spec = latent.program_spec(sizes)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    # one layer of each kind, packed as the engine packs, widened by shape
    one = dict(sizes, n_layers=2, dense_layers=1)
    tree1 = latent.codec_tree(one, 0)
    B, ps = int(flags["slots"]), int(flags["kv_page_size"])
    layout = q40_body_policy(spec, rows=B)
    announce_q40_layout(layout)
    packed1 = fuse_q40_layer_matmuls(pack_q40_params(
        prepare_latent_params(spec, tree1), allow_nb_major=True,
        layout=layout))
    k = sizes["dense_layers"]
    dense1 = packed1.pop("dense")
    params = shape_tree(packed1, sizes["n_layers"] - k, lambda key, i: chip)
    if k:
        params["dense"] = shape_tree(dense1, k, lambda key, i: chip)
    print(json.dumps({"policy": layout.label, "leaf_kinds": {
        key: type(v).__name__ for key, v in {
            **packed1, **{"dense." + a: b for a, b in dense1.items()}
        }.items()}}), flush=True)
    rows: list = []
    L, S, W = sizes["n_layers"], sizes["seq_len"], plane_width(spec)
    seq_cache = LatentCache(sds((L, S, W), jnp.float32))
    n_pages = (args.kv_pages or int(flags["kv_pages"])) + 1
    pool = LatentCache(sds((L, n_pages, ps, W), jnp.float32))
    step = jax.jit(functools.partial(llama.forward_batch_paged, spec, ps,
                                     kv_quant="f32", moe_counts=True),
                   donate_argnums=1)
    lowered = step.lower(params, pool, sds((B,), jnp.int32),
                         sds((B,), jnp.int32), sds((B, S // ps), jnp.int32))
    report(f"paged decode step B={B}, pool {n_pages} pages", lowered, rows)
    if "refused" not in rows[-1]:
        rows[-1]["residual_path_instructions"] = path_instructions(
            lowered.compile().as_text())
        print(json.dumps({"residual_path_instructions":
                          rows[-1]["residual_path_instructions"]}),
              flush=True)
    if args.dump_hlo:
        with open(args.dump_hlo, "w", encoding="utf-8") as fh:
            fh.write(lowered.compile().as_text())
        return 0
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
