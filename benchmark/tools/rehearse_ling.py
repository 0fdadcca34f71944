"""``rehearse_nemotron.py`` for ``harness/ling.py``'s configuration:
compile, for a DESCRIBED v5e:2x2 and with no chip attached, the ``serve``
programs of ``ling-3-flash-q40-ep8`` at its published widths from shape
trees (the paged decode step at the configuration's slots with the
delta-rule states, the conv rows and the four latent layers' pools aliased:
the state kernel, the slot kernel at 80 and 24 blocks a row, the latent
page kernel; the admission prefill chunk with the chunk form as XLA matrix
products and a forward substitution; the insert of a prefilled sequence into
a row and its pages) and the ``inference`` step, and print what each needs
beside its arguments (peak and temporaries): no copy of an expert stack, a
state or a pool may stand around a kernel call, and every Q40 leaf's blocks a
row (80, 24, 192, 128) are on the 8 grid, so the layout pads none. Run
before the first chip call: what the chip's compiler refuses here costs no
chip time.

  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_ling.py
      [--kv-pages N] [--prefill-chunk T]
      [--config-file benchmark/tests/tiny-ling.json] [--dump-hlo PATH]

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


def shape_params(sizes: dict, spec, rows: int, chip):
    """The packed param tree of ``spec`` as shapes: one layer of each stack
    (a KDA mixer, a latent mixer, the dense FFN, an expert FFN) prepared and
    packed as the engine packs, then widened to the stack's depth."""
    from benchmark.harness import ling
    from benchmark.tools.rehearse_compile import shape_tree
    from distributed_llama_tpu.models.latent import prepare_latent_params
    from distributed_llama_tpu.ops.linear import (announce_q40_layout,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params,
                                                  q40_body_policy)

    one = dict(sizes, n_layers=2, period=2, limits=(0.0, 0.0),
               shared_limits=(0.0, 0.0))
    layout = q40_body_policy(spec, rows=rows)
    announce_q40_layout(layout)
    packed1 = fuse_q40_layer_matmuls(pack_q40_params(
        prepare_latent_params(ling.program_spec(one),
                              ling.codec_tree(one, 0)),
        allow_nb_major=True, layout=layout))
    kinds = ling.kinds_of(sizes)
    depth = {"kda": kinds.count("kda"), "full": kinds.count("full"),
             "dense": sizes["dense_layers"]}
    stacks = {name: packed1.pop(name) for name in depth}
    params = shape_tree(packed1, sizes["n_layers"] - sizes["dense_layers"],
                        lambda key, i: chip)
    for name, stack in stacks.items():
        params[name] = shape_tree(stack, depth[name], lambda key, i: chip)
    kinds_of_leaf = {(name + "." if name else "") + key: (
        type(v).__name__, [list(a.shape) for a in v] if isinstance(v, tuple)
        else list(v.shape))
        for name, stack in {**stacks, "": packed1}.items()
        for key, v in stack.items()}
    return params, layout, kinds_of_leaf


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ling-3-flash-q40-ep8")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="override entries.serve.kv_pages")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="override entries.serve.prefill_chunk")
    ap.add_argument("--dump-hlo", default=None, metavar="PATH",
                    help="write the compiled decode step's text there")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"     # kernels, not interpret mode
    jax.config.update("jax_enable_compilation_cache", False)

    from benchmark.harness import cells
    from benchmark.harness import ling
    from benchmark.tools.rehearse_compile import report
    from distributed_llama_tpu.models import kda as program

    config = cells.load_json(args.config_file or os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    flags = config["entries"]["serve"]
    sizes = ling.sizes_of(config)
    spec = ling.program_spec(sizes)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    B, ps = int(flags["slots"]), int(flags["kv_page_size"])
    params, layout, leaf_kinds = shape_params(sizes, spec, B, chip)
    print(json.dumps({"policy": layout.label, "leaf_kinds": leaf_kinds}),
          flush=True)
    rows: list = []
    S = sizes["seq_len"]
    n_pages = (args.kv_pages or int(flags["kv_pages"])) + 1
    as_shapes = lambda c: jax.tree_util.tree_map(      # noqa: E731
        lambda a: sds(a.shape, a.dtype), c)
    seq_cache = as_shapes(jax.eval_shape(
        lambda: program.init_cache(spec)))
    pool = as_shapes(jax.eval_shape(
        lambda: program.init_cache_paged(spec, B, n_pages, ps)))
    step = jax.jit(functools.partial(
        program.forward_batch, spec, page_size=ps, health=True,
        moe_counts=True), donate_argnums=1)
    lowered = step.lower(params, pool, sds((B,), jnp.int32),
                         sds((B,), jnp.int32), sds((B, S // ps), jnp.int32),
                         sds((B,), jnp.int32))
    report(f"paged decode step B={B}, pool {n_pages} pages a latent layer",
           lowered, rows)
    if args.dump_hlo:
        with open(args.dump_hlo, "w", encoding="utf-8") as fh:
            fh.write(lowered.compile().as_text())
        return 0
    chunk = args.prefill_chunk or int(flags["prefill_chunk"])
    fwd = jax.jit(functools.partial(program.forward_chunk, spec, xdec=False,
                                    moe_counts=True), donate_argnums=1)
    report(f"admission prefill chunk T={chunk}", fwd.lower(
        params, seq_cache, sds((chunk,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32)), rows)
    report("inference step T=1", jax.jit(
        functools.partial(program.forward_chunk, spec, health=True,
                          moe_counts=True), donate_argnums=1).lower(
            params, seq_cache, sds((1,), jnp.int32), sds((), jnp.int32)),
        rows)
    report("insert a sequence into a row and its pages", jax.jit(
        functools.partial(program.insert_sequence, page_size=ps),
        donate_argnums=0).lower(pool, seq_cache, sds((), jnp.int32),
                                sds((S // ps,), jnp.int32)), rows)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"rehearse_{args.config}_serve.json"), "w",
              encoding="utf-8") as fh:
        json.dump(rows, fh, indent=1)
    return 1 if any("refused" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
