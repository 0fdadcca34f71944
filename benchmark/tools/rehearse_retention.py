"""``rehearse_compile.py`` for a retention configuration: compile, for a
DESCRIBED v5e:2x2 and with no chip attached, the ``serve`` programs of
``brumby-14b-q40`` at its published widths from shape trees (the decode step
at the configuration's slots with the state donated, the admission prefill
chunk on one sequence's scratch state and the state insert)
and the ``inference`` step, and print what each needs beside its arguments:
the state must come out ALIASED (``alias_gib`` near the state's size: a
second copy of 5 GiB does not fit) and no temporary may be weight-sized
(this model's Q40 leaves have 160 and 544 blocks a row, both off the 128
grid where PR 21 and PR 25 found per-step weight copies).
``rehearse_compile.py``'s ``shape_tree`` and ``report`` are imported.

  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_retention.py [--layers N]

Nothing runs; a compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="brumby-14b-q40")
    ap.add_argument("--layers", type=int, default=0,
                    help="override num_hidden_layers (the one fallback)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"     # kernels, not interpret mode
    jax.config.update("jax_enable_compilation_cache", False)

    from benchmark.harness import cells, retention
    from benchmark.tools.rehearse_compile import report, shape_tree
    from distributed_llama_tpu.models import llama
    from distributed_llama_tpu.ops.linear import (apply_q40_body_policy,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params)

    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    if args.layers:
        config["num_hidden_layers"] = args.layers
    flags = config["entries"]["serve"]
    sizes = retention.sizes_of(config)
    spec = retention.program_spec(sizes)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    tree1 = retention.codec_tree(dict(sizes, n_layers=1, vocab_size=1024), 0)
    B, chunk = int(flags["slots"]), int(flags["prefill_chunk"])
    L = sizes["n_layers"]

    def shapes_for(rows: int):
        """The packed tree's shapes as an engine ``rows`` wide lays it out
        (``serve`` at the slots, ``inference`` at one row)."""
        policy = apply_q40_body_policy(spec, rows=rows)
        packed1 = fuse_q40_layer_matmuls(
            pack_q40_params(tree1, allow_nb_major=True))
        params = shape_tree(packed1, L, lambda k, i: chip)
        # the vocabulary whole (the tree is built at a small one: only its
        # leaf kinds and per-layer shapes are read)
        for key in ("tok_embedding", "wcls"):
            params[key] = jax.tree_util.tree_map(
                lambda a: sds(tuple(spec.vocab_size if n == 1024 else n
                                    for n in a.shape), a.dtype), params[key])
        print(json.dumps({"rows": rows, "policy": policy, "leaf_kinds": {
            k: type(v).__name__ for k, v in packed1.items()}}), flush=True)
        return params

    params = shapes_for(B)

    def state(batch=None):
        return jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: llama.init_state(spec, batch)))

    rows: list = []
    step = jax.jit(functools.partial(llama.forward_batch_retention, spec,
                                     norm_min=True), donate_argnums=1)
    report(f"decode step B={B}, state donated", step.lower(
        params, state(B), sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32)), rows)
    fwd = jax.jit(functools.partial(llama.forward_retention, spec),
                  donate_argnums=1)
    report(f"admission prefill chunk T={chunk}", fwd.lower(
        params, state(), sds((chunk,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32)), rows)
    one = shapes_for(1)
    report("inference step T=1", jax.jit(
        functools.partial(llama.forward_retention, spec, norm_min=True),
        donate_argnums=1).lower(
            one, state(), sds((1,), jnp.int32), sds((), jnp.int32)), rows)
    report(f"inference prefill chunk T={chunk}", fwd.lower(
        one, state(), sds((chunk,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32)), rows)

    def insert(whole, one, b):
        return type(whole)(*(jax.lax.dynamic_update_slice(
            w, o[:, None], (0, b) + (0,) * (o.ndim - 1))
            for w, o in zip(whole, one)))

    report("state insert", jax.jit(insert, donate_argnums=0).lower(
        state(B), state(), sds((), jnp.int32)), rows)
    def gib(tree):
        return sum(jnp.dtype(a.dtype).itemsize * math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(tree)) / 2**30

    weights_gib, state_gib = gib(params), gib(state(B))
    rows.append({"weights_gib": round(weights_gib, 3),
                 "state_gib": round(state_gib, 3),
                 "scratch_state_gib": round(state_gib / B, 3),
                 "resident_gib": round(
                     weights_gib + state_gib * (1 + 1 / B), 3)})
    print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"rehearse_{args.config}_serve.json"), "w",
              encoding="utf-8") as fh:
        json.dump(rows, fh, indent=1)
    return 1 if any("refused" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
