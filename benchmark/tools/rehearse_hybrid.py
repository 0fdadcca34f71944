"""``rehearse_compile.py`` for a hybrid configuration: compile, for a
DESCRIBED v5e:2x2 and with no chip attached, the ``serve`` programs of
``phi4-mini-flash-q40`` at its published widths from shape trees (the decode
step at the configuration's slots and pages with the cache donated, the
admission prefill chunk on one sequence's scratch cache, and the insert of
that sequence's state, rings and pages) and the ``inference`` step and chunk,
and print what each needs beside its arguments: the cache must come out
ALIASED (``alias_gib`` near its size) and no temporary may be weight-sized
(this model's Q40 leaves have 80, 160 and 320 blocks a row, all off the 128
grid where PR 21 and PR 25 found per-step weight copies).
``rehearse_compile.py``'s ``report`` is imported.

  JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_hybrid.py

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
    ap.add_argument("--config", default="phi4-mini-flash-q40")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"     # kernels, not interpret mode
    jax.config.update("jax_enable_compilation_cache", False)

    from benchmark.harness import cells, hybrid
    from benchmark.tools.rehearse_compile import report
    from distributed_llama_tpu.models import sambay
    from distributed_llama_tpu.ops.linear import (apply_q40_body_policy,
                                                  fuse_q40_layer_matmuls,
                                                  pack_q40_params)

    config = cells.load_json(os.path.join(
        cells.BENCH_DIR, "configs", args.config + ".json"))
    flags = config["entries"]["serve"]
    sizes = hybrid.sizes_of(config)
    spec = hybrid.program_spec(sizes)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=chip)
    # the smallest depth with every kind, a small vocabulary: only the leaf
    # kinds and per-layer shapes are read, then widened to the real depth
    small = dict(sizes, n_layers=8, vocab_size=1024)
    tree8 = hybrid.codec_tree(small, 0)
    depth8 = {k: hybrid.kinds_of(8).count(k) for k in hybrid.KINDS}
    depth = {k: hybrid.kinds_of(sizes["n_layers"]).count(k)
             for k in hybrid.KINDS}
    B, chunk = int(flags["slots"]), int(flags["prefill_chunk"])
    ps, pages = int(flags["kv_page_size"]), int(flags["kv_pages"])

    def shapes_for(rows: int):
        policy = apply_q40_body_policy(spec, rows=rows)
        packed = fuse_q40_layer_matmuls(
            pack_q40_params(tree8, allow_nb_major=True))

        def widen(kind):
            def one(a):
                shape = tuple(a.shape)
                if kind is None:
                    shape = tuple(spec.vocab_size if n == 1024 else n
                                  for n in shape)
                else:
                    assert shape[0] == depth8[kind], (kind, shape)
                    shape = (depth[kind], *shape[1:])
                return sds(shape, a.dtype)
            return one

        params = {k: ({n: jax.tree_util.tree_map(widen(k), leaf)
                       for n, leaf in v.items()} if isinstance(v, dict)
                      else jax.tree_util.tree_map(widen(None), v))
                  for k, v in packed.items()}
        print(json.dumps({"rows": rows, "policy": policy, "leaf_kinds": {
            f"{k}.{n}": type(leaf).__name__ for k, v in packed.items()
            if isinstance(v, dict) for n, leaf in v.items()
            if isinstance(leaf, tuple)}}), flush=True)
        return params

    def like(fn):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                      jax.eval_shape(fn))

    params = shapes_for(B)
    pool = like(lambda: sambay.init_cache_paged(spec, B, pages + 1, ps))
    one = like(lambda: sambay.init_cache(spec))
    i32 = functools.partial(sds, dtype=jnp.int32)
    rows: list = []
    step = jax.jit(functools.partial(sambay.forward_batch_sambay, spec,
                                     page_size=ps, health=True),
                   donate_argnums=1)
    report(f"decode step B={B} over {pages} pages, cache donated",
           step.lower(params, pool, i32((B,)), i32((B,)),
                      i32((B, spec.seq_len // ps)), i32((B,))), rows)
    fwd = jax.jit(functools.partial(sambay.forward_sambay, spec, xdec=False),
                  donate_argnums=1)
    report(f"admission prefill chunk T={chunk} (self-decoder alone)",
           fwd.lower(params, one, i32((chunk,)), i32(()), i32(())), rows)
    report("state, ring and page insert", jax.jit(
        functools.partial(sambay.insert_sequence, page_size=ps),
        donate_argnums=0).lower(pool, one, i32(()),
                                i32((spec.seq_len // ps,))), rows)
    solo = shapes_for(1)
    report("inference step T=1", jax.jit(
        functools.partial(sambay.forward_sambay, spec, health=True),
        donate_argnums=1).lower(solo, one, i32((1,)), i32(())), rows)
    report(f"inference prefill chunk T={chunk}", fwd.lower(
        solo, one, i32((chunk,)), i32(()), i32(())), rows)

    def gib(tree):
        return sum(jnp.dtype(a.dtype).itemsize * math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(tree)) / 2**30

    rows.append({"weights_gib": round(gib(params), 3),
                 "state_gib": round(gib(pool[:2]), 3),
                 "rings_gib": round(gib(pool[2:4]), 3),
                 "pages_gib": round(gib(pool[4:]), 3),
                 "scratch_sequence_gib": round(gib(one), 3),
                 "resident_gib": round(gib(params) + gib(pool) + gib(one),
                                       3)})
    print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"rehearse_{args.config}_serve.json"), "w",
              encoding="utf-8") as fh:
        json.dump(rows, fh, indent=1)
    return 1 if any("refused" in r for r in rows) else 0


if __name__ == "__main__":
    sys.exit(main())
