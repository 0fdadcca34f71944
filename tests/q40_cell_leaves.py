"""The dense Q40 leaves of the nine benchmark configurations and the
dispatch widths their cells run, and one tile's check against float64, for
the tests of the T > 1 tile (test_q40_planes.py and its ``_stacked`` twin:
two files so that two workers share the 150 interpret-mode cases;
test_chip_compile.py). Nothing here touches a device while it is
imported."""

import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# blocks a row of the dense leaves of the nine benchmark configurations
# (Yi-34B's as its tp-4 shards hold them: 56 and 160), and the rows of a
# decode step (8, 16, 32) and of an admission or prefill chunk (128)
CELL_NB = (16, 24, 48, 56, 64, 80, 112, 128, 160, 192, 224, 288, 320, 448,
           544)
CELL_ROWS = (8, 16, 32, 128)

# configuration file -> (the harness module whose ``sizes_of`` /
# ``program_spec`` make its TransformerSpec, the tp degree it runs at, the
# rows of its cells' T > 1 dispatches: a decode step's slots where it has
# them, an admission or prefill chunk's)
CONFIGS = {"mistral-7b-q40": ("model", 1, (8, 128)),
           "yi-34b-q40-tp4": ("model", 4, (128,)),
           "olmoe-1b-7b-q40": ("olmoe", 1, (16, 128)),
           "brumby-14b-q40": ("retention", 1, (16, 128)),
           "deepseek-v3-q40-ep8": ("latent", 1, (32, 128)),
           "phi4-mini-flash-q40": ("hybrid", 1, (32, 128)),
           "xing4-29b-a4b-q40": ("hyper", 1, (32, 128)),
           "laguna-xs2-q40": ("laguna", 1, (32, 512)),
           "mimo-v2-flash-q40-ep8": ("mimo", 1, (32, 128))}


def dense_leaves(config: str):
    """[(d, blocks a row)] of a configuration's dense matmul tensors as a
    chip holds them: a layer's (every kind's), a leading dense layer's and
    the classifier; a tp-4 shard cuts ``wo`` / ``w2`` along the input and
    every other tensor along the output."""
    module, tp, _ = CONFIGS[config]
    harness = importlib.import_module(f"benchmark.harness.{module}")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{config}.json")) as f:
        spec = harness.program_spec(harness.sizes_of(json.load(f)))
    named = (spec.layer_matmul_shapes() + spec.dense_layer_matmul_shapes()
             + [("wcls", (spec.vocab_size, spec.dim))])
    out = set()
    for name, (d, n) in named:
        if tp > 1 and name in ("wo", "w2"):
            n //= tp
        elif tp > 1:
            d //= tp
        out.add((d, n // 32))
    return sorted(out)


def rule_triples():
    """{(blocks a row, rows, planes a dot): rows of the smallest leaf} over
    every (leaf, width) the cells run that the row tiler places: what
    ``ops/pallas_q40._pick_planes`` returns for the nine configurations."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    found = {}
    for config, (_, _, widths) in CONFIGS.items():
        for d, nb in dense_leaves(config):
            if pq._pick_rows_nb(d, nb) is None:
                continue
            for t in widths:
                key = (nb, t, pq._pick_planes(nb, pq._pick_block_t(t, nb)))
                found[key] = min(d, found.get(key, d))
    return found


def leaf(nb, d, layers, seed):
    """Seeded codes, float16-valued scales, and the float64 weights (d, n)
    of the LAST layer."""
    rng = np.random.default_rng(seed)
    lead = (layers,) if layers else ()
    qs = rng.integers(0, 256, (*lead, 16, nb, d), dtype=np.uint8)
    scale = ((rng.random((*lead, nb, d), dtype=np.float32) + 0.5)
             / (8 * np.sqrt(32 * nb))).astype(np.float16).astype(np.float32)
    q = (qs[-1] if layers else qs).astype(np.int32)
    codes = np.concatenate([(q & 0xF) - 8, (q >> 4) - 8], 0)
    s = (scale[-1] if layers else scale).astype(np.float64)
    w = np.transpose(codes * s[None], (2, 1, 0)).reshape(d, -1)
    return qs, scale, w


def tile(qs, scale, x, planes, stacked, bf16):
    """The jitted call a dispatch makes, under its own name, at ``planes``
    nibble planes a dot (interpret mode), on the last layer."""
    import jax.numpy as jnp

    from distributed_llama_tpu.ops import pallas_q40 as pq

    nb = qs.shape[-2]
    kw = dict(block_rows=128, block_t=pq._pick_block_t(x.shape[0], nb),
              interpret=True, bf16=bf16, planes=planes)
    if stacked:
        return np.asarray(pq._q40_mxu_nb_stacked(
            jnp.asarray([qs.shape[0] - 1], jnp.int32), jnp.asarray(qs),
            jnp.asarray(scale), jnp.asarray(x), **kw))
    return np.asarray(pq._q40_mxu_nb_2d(jnp.asarray(qs), jnp.asarray(scale),
                                        jnp.asarray(x), **kw))


# parity at every row count; the bf16 arm where a dispatch reaches the tile
# under it (up to MULTI_T_MAX rows: a wider one dequantizes and dots)
ARMS = [(rows, False) for rows in CELL_ROWS] + [(8, True)]
ARM_IDS = [f"T{r}-{'bf16' if b else 'parity'}" for r, b in ARMS]


def check_tile_near_float64(nb, rows, bf16, stacked):
    """At one (blocks a row, rows) a cell runs, through one call and one
    arm: the tile at the rule's planes a dot lies no farther from the
    float64 product than a dot a plane (today's) does, plus the float32
    rounding of two summation orders, and the two give the same array to
    that rounding. One dot adds G nb products inside the MXU where a dot a
    plane adds G partial sums on the vector unit."""
    from distributed_llama_tpu.ops import pallas_q40 as pq

    d = 128
    qs, scale, w = leaf(nb, d, 2 if stacked else 0, 7 * nb + rows)
    x = np.random.default_rng(nb + rows).standard_normal(
        (rows, 32 * nb)).astype(np.float32)
    want = x.astype(np.float64) @ w.T
    size = np.abs(want).max()
    g = pq._pick_planes(nb, pq._pick_block_t(rows, nb))
    one = tile(qs, scale, x, 1, stacked, bf16)
    got = one if g == 1 else tile(qs, scale, x, g, stacked, bf16)
    assert got.shape == (rows, d)
    near = np.abs(one - want).max() / size
    # parity: 7e-6 of 1e-4 in the cells; one bf16 pass a side: 2e-3
    assert near <= (2e-2 if bf16 else 1e-6)
    assert np.abs(got - want).max() / size <= near + 2e-7
    np.testing.assert_allclose(got, one, rtol=0, atol=1e-6 * size)
