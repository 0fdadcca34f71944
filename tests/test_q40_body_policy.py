"""The Q40 layout of a model (ISSUE 29): ONE value, ``Q40Layout``, resolved
by the pure ``q40_body_policy(spec, rows)`` and handed down as an argument;
ONE per-leaf rule, ``q40_leaf_layout``, for one chip and for shards. No
process environment carries any of it. Decision logic and plumbing only —
the kernels themselves are pinned by tests/test_pallas_q40.py."""

from __future__ import annotations

import functools
import os
import re

import numpy as np
import pytest

from distributed_llama_tpu.io.loader import Q40Kernel, Q40KernelNb, Q40Weight
from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import (llama2_7b_spec,
                                                llama2_13b_spec)
from distributed_llama_tpu.ops import linear
from distributed_llama_tpu.ops.linear import (Q40_STOCK, Q40Layout,
                                              apply_q40_body_policy,
                                              pack_q40_params,
                                              q40_body_policy,
                                              q40_leaf_layout)
from distributed_llama_tpu.ops.quants import FloatType

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """The kernel mode is each test's own; what ``apply_q40_body_policy``
    records is put back afterwards (monkeypatch restores the attribute)."""
    monkeypatch.delenv("DLLAMA_Q40_KERNEL", raising=False)
    monkeypatch.setattr(linear, "_APPLIED_LAYOUT", None)


def test_auto_picks_i4_nb_for_7b_on_pallas(monkeypatch):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    layout = q40_body_policy(llama2_7b_spec())
    policy, reason = layout                  # unpacks as (label, reason)
    assert policy == "i4-nb" and "auto" in reason
    assert layout.force_nb_major and layout.i4_chain
    # a mesh engine: leaves are judged shard-locally, the i4 body stays off
    assert q40_body_policy(llama2_7b_spec(), sharded=True) is Q40_STOCK
    assert not (Q40_STOCK.force_nb_major or Q40_STOCK.i4_chain)


@pytest.mark.parametrize("rows", [1, 4, 5, 8, 16])
def test_auto_packs_every_dispatch_width_nb_major(monkeypatch, rows):
    """Every width has an nb-major kernel since PR 32 (the matvec at one
    row, the MXU body beyond: a 2..8-row dispatch is one 8-row tile), so the
    width no longer keeps a tree d-major. Until then 5 and 8 rows (``serve``
    at its default 8 slots) resolved ``d-major``: 36 ms a step against the
    MXU body's 18.7 on the chip (PERF.md, PR 32)."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    layout = q40_body_policy(llama2_7b_spec(), rows=rows)
    assert layout.label == "i4-nb", layout.reason
    assert layout.force_nb_major and layout.i4_chain
    assert "row" not in layout.reason
    assert layout == q40_body_policy(llama2_7b_spec())


def test_auto_declines_13b_on_memory_headroom(monkeypatch):
    # the 13B i4 conversion OOMed a 16 GB chip (BASELINE.md r5): auto must
    # keep d-major there, and say why
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    policy, reason = q40_body_policy(llama2_13b_spec())
    assert policy == "d-major"
    assert "headroom" in reason
    # ... but a raised gate flips it (the gate is the module's constant)
    monkeypatch.setattr(linear, "Q40_I4_MAX_PACKED_GB", 12.0)
    policy, _ = q40_body_policy(llama2_13b_spec())
    assert policy == "i4-nb"


def test_auto_declines_off_pallas():
    # CPU / xla mode: layouts are moot, keep the stock picks
    layout = q40_body_policy(llama2_7b_spec())
    assert layout.label == "d-major"
    assert "Pallas" in layout.reason or "XLA" in layout.reason
    assert not layout.force_nb_major and not layout.i4_chain


# ---- what replaced the environment's precedence ---------------------------

# a width at which the stock picks and the forced layout differ (nb 128
# pads nothing, so only a layout that forces it packs nb-major) and every
# leaf places on the nb-major row tiler: q40_body_policy gives i4-nb at
# every dispatch width
WIDE = dict(dim=4096, hidden_dim=4096, n_layers=1, n_heads=32,
            n_kv_heads=32, vocab_size=256, seq_len=32,
            weights_float_type=FloatType.Q40)


def _wide():
    from distributed_llama_tpu.models.synth import synth_q40_fast

    spec = TransformerSpec(**WIDE)
    return spec, synth_q40_fast(spec, seed=3)


def _kinds(params) -> set:
    return {type(v) for v in params.values()
            if isinstance(v, (Q40Weight, Q40Kernel, Q40KernelNb))}


def test_explicit_layout_wins_over_the_engines_own(monkeypatch):
    """An engine resolves its layout itself, and one that is handed a
    layout takes that: the value is an argument, nothing overrides it."""
    from distributed_llama_tpu.runtime.generate import Engine

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    spec, tree = _wide()
    own = Engine(spec, tree)
    assert own.q40_layout.label == "i4-nb"
    assert _kinds(own.params) == {Q40KernelNb}
    stock = Q40Layout("d-major", "handed down")
    handed = Engine(spec, tree, q40_layout=stock)
    assert handed.q40_layout is stock
    assert _kinds(handed.params) == {Q40Kernel}
    # ... down to the chain: the loop converts to int4 planes iff the
    # engine's layout says so
    from distributed_llama_tpu.runtime import decode

    seen = []
    monkeypatch.setattr(decode, "make_decode_loop",
                        lambda *a, i4=False: seen.append(i4))
    own.decode_loop(0.0, 0.9)
    handed.decode_loop(0.0, 0.9)
    assert seen == [True, False]


def test_apply_returns_label_notes_and_leaves_env_alone(monkeypatch, capsys):
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    before = dict(os.environ)
    assert apply_q40_body_policy(llama2_7b_spec()) == "i4-nb"
    assert dict(os.environ) == before
    err = capsys.readouterr().err
    # chip_smoke.py parses this line
    m = re.search(r"💡 Q40 body policy: (.*)", err)
    assert m and m.group(1).startswith("i4-nb (auto: ")
    assert err.count("Q40 body policy") == 1
    assert linear._APPLIED_LAYOUT == q40_body_policy(llama2_7b_spec())


@pytest.mark.parametrize("model,want", [("7b", "i4-nb t1 mxu 8/8"),
                                        ("13b", "d-major t1 mxu 7/8")])
def test_policy_line_and_stamp_count_the_t1_mxu_leaves(model, want,
                                                       monkeypatch, capsys):
    """The one-row body is picked at trace time from shapes, so its record
    is static: the policy line and every log record's ``q40_body`` count
    the dense tensors whose T = 1 dispatch takes the MXU matvec (an
    nb-major leaf, block count a multiple of 8). 13B's ``w2`` (432 blocks a
    row) stays d-major under the stock picks: a vector body."""
    from distributed_llama_tpu.ops.linear import t1_bodies
    from distributed_llama_tpu.utils import fingerprint

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setattr(fingerprint, "_Q40_BODY", "unresolved")
    spec = llama2_7b_spec() if model == "7b" else llama2_13b_spec()
    label, count = want.split(" ", 1)
    assert t1_bodies(spec, q40_body_policy(spec)) == count
    assert apply_q40_body_policy(spec) == label
    assert f"; {count}; the i4 body" in capsys.readouterr().err
    assert fingerprint.run_stamp()["q40_body"] == want
    # off the kernel path there is no body to count
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "xla")
    apply_q40_body_policy(spec)
    assert "t1 mxu" not in capsys.readouterr().err
    assert fingerprint.run_stamp()["q40_body"] == "d-major"


@pytest.mark.parametrize("rows,want", [
    (8, "tile planes-a-dot: 1 x 8 leaves"),
    (32, "tile planes-a-dot: 1 x 8 leaves"),
    (1, None)])
def test_policy_line_and_stamp_carry_the_tile_planes_histogram(
        rows, want, monkeypatch, capsys):
    """For a decode dispatch of more than one row the policy line and every
    log record's ``q40_body`` count the nb-major dense tensors by the
    nibble planes one dot of the T > 1 tile contracts over
    (ops/pallas_q40._pick_planes), from the rule and nothing else; a
    one-row engine's steps never run the tile and its line says nothing.
    7B: seven tensors on the 128 grid, and ``w2`` (344 blocks a row) whose
    16-plane group would not fit a 256-row tile: a dot a plane all."""
    from distributed_llama_tpu.utils import fingerprint

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    monkeypatch.setattr(fingerprint, "_Q40_BODY", "unresolved")
    apply_q40_body_policy(llama2_7b_spec(), rows=rows)
    err = capsys.readouterr().err
    stamp = fingerprint.run_stamp()["q40_body"]
    if want is None:
        assert "planes-a-dot" not in err and "planes-a-dot" not in stamp
        return
    assert f"t1 mxu 8/8; {want}; the i4 body" in err
    assert stamp == f"i4-nb t1 mxu 8/8; {want}"


def test_tile_planes_counts_leaves_by_the_rule():
    """``tile_planes`` is a pure function of (spec, layout, rows): a spec
    with leaves off the 128 grid (dim 2560: 80 blocks a row; FFN 10240:
    320) merges 8 and 2 planes a dot at 32 rows and none at 8."""
    from distributed_llama_tpu.ops.linear import Q40Layout, tile_planes

    spec = TransformerSpec(dim=2560, hidden_dim=10240, n_layers=2,
                           n_heads=20, n_kv_heads=20, vocab_size=32000,
                           seq_len=64, weights_float_type=FloatType.Q40)
    layout = Q40Layout("nb-major", "test")
    assert tile_planes(spec, layout, 32) == (
        "tile planes-a-dot: 8 x 7 leaves, 2 x 1, 1 x 0")
    assert tile_planes(spec, layout, 8) == "tile planes-a-dot: 1 x 8 leaves"
    assert tile_planes(spec, layout, 5) == tile_planes(spec, layout, 8)


def test_apply_twice_the_second_stands(monkeypatch, capsys):
    """Overwritten, not first-wins: a by-hand packer after the second call
    packs the second call's layout (here a model past the headroom gate
    after one under it)."""
    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    spec, tree = _wide()
    assert apply_q40_body_policy(spec, rows=1) == "i4-nb"
    assert _kinds(pack_q40_params(tree, allow_nb_major=True)) == {Q40KernelNb}
    assert apply_q40_body_policy(llama2_13b_spec(), rows=8) == "d-major"
    assert linear._APPLIED_LAYOUT.label == "d-major"
    assert _kinds(pack_q40_params(tree, allow_nb_major=True)) == {Q40Kernel}
    assert "headroom" in capsys.readouterr().err


# ---- new with ISSUE 29 ----------------------------------------------------

def test_two_engines_of_two_widths_each_get_their_own_layout(monkeypatch):
    """One process, ``inference`` then ``serve`` at 8 slots, each preceded
    by ``apply_q40_body_policy`` as the benchmark's drivers call it: each
    engine resolves its own value from its own spec and width, and since
    PR 32 (an nb-major kernel at every width) both pack forced nb-major.
    A layout handed to the eight-row engine still wins over its own."""
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine
    from distributed_llama_tpu.runtime.generate import Engine

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    spec, tree = _wide()
    apply_q40_body_policy(spec, rows=1)
    one = Engine(spec, tree)
    apply_q40_body_policy(spec, rows=8)
    eight = ContinuousEngine(spec, tree, 8, 0.0, 0.9, seed=1)
    assert _kinds(one.params) == {Q40KernelNb}
    assert _kinds(eight.params) == {Q40KernelNb}
    # and without the calls: engines resolve the same values themselves
    assert Engine(spec, tree).q40_layout == one.q40_layout
    sixteen = ContinuousEngine(spec, tree, 16, 0.0, 0.9, seed=1)
    assert sixteen.q40_layout.label == eight.q40_layout.label == "i4-nb"
    assert _kinds(sixteen.params) == {Q40KernelNb}
    stock = ContinuousEngine(spec, tree, 8, 0.0, 0.9, seed=1,
                             q40_layout=Q40_STOCK)
    assert _kinds(stock.params) == {Q40Kernel}


def test_engine_ignores_the_shim_a_by_hand_packer_reads_it(monkeypatch):
    from distributed_llama_tpu.runtime.generate import Engine

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    spec, tree = _wide()
    apply_q40_body_policy(llama2_13b_spec(), rows=8)  # records d-major
    assert linear._APPLIED_LAYOUT.label == "d-major"
    assert _kinds(Engine(spec, tree).params) == {Q40KernelNb}
    # the tools' contract (benchmark/tools/rehearse_compile.py and two
    # more): apply, then pack by hand with no layout
    assert _kinds(pack_q40_params(tree, allow_nb_major=True)) == {Q40Kernel}
    apply_q40_body_policy(spec, rows=1)
    assert _kinds(pack_q40_params(tree, allow_nb_major=True)) == {Q40KernelNb}
    # a passed layout beats the shim
    assert _kinds(pack_q40_params(tree, allow_nb_major=True,
                                  layout=Q40_STOCK)) == {Q40Kernel}


# Real leaf shapes (benchmark/configs/*.json) and what is packed for them.
# At 1 and 16 rows these are what PR 27's tree packed (read off its own
# pack_q40_params with the re-tilers stubbed); at 8 rows that tree packed
# the stock picks (no nb-major kernel served 5..8 rows before PR 32) and
# the rows now read as their neighbours do.
def _dense(dim, hidden, heads, kv, vocab):
    hs = dim // heads
    return {"wq": (dim, dim), "wk": (kv * hs, dim), "wv": (kv * hs, dim),
            "wo": (dim, dim), "w1": (hidden, dim), "w2": (dim, hidden),
            "w3": (hidden, dim), "wcls": (vocab, dim)}


MISTRAL = _dense(4096, 14336, 32, 8, 32000)
YI = _dense(7168, 20480, 56, 8, 64000)
OLMOE = {"wq": (2048, 2048), "wk": (2048, 2048), "wv": (2048, 2048),
         "wo": (2048, 2048), "wcls": (50304, 2048), "moe_w1": (1024, 2048),
         "moe_w2": (2048, 1024), "moe_w3": (1024, 2048)}
MISTRAL_SPEC = dict(dim=4096, hidden_dim=14336, n_layers=32, n_heads=32,
                    n_kv_heads=8, vocab_size=32000, seq_len=4096,
                    weights_float_type=FloatType.Q40)
OLMOE_SPEC = dict(dim=2048, hidden_dim=1024, n_layers=16, n_heads=16,
                  n_kv_heads=16, vocab_size=50304, seq_len=4096,
                  weights_float_type=FloatType.Q40, n_experts=64,
                  n_active_experts=8, qk_norm=True)
_ALL_NB = dict.fromkeys(MISTRAL, "nb-major")
_ALL_D = dict.fromkeys(MISTRAL, "d-major")
# (shapes, spec or None for a sharded tree, tp, rows) -> the packed kinds
PARENT_PACKED = {
    # mistral7b.decode1: i4-nb forces every leaf
    "mistral-tp1-rows1": (MISTRAL, MISTRAL_SPEC, 1, 1, _ALL_NB),
    # mistral7b.serve-chat / serve-sat (8 slots): as at 1 and 16 rows
    "mistral-tp1-rows8": (MISTRAL, MISTRAL_SPEC, 1, 8, _ALL_NB),
    "mistral-tp1-rows16": (MISTRAL, MISTRAL_SPEC, 1, 16, _ALL_NB),
    # olmoe7b.gen-sat16: an expert spec keeps the stock picks at any width;
    # nb 64 pads 2x d-major, so the dense leaves pack nb-major too
    **{f"olmoe-tp1-rows{r}": (OLMOE, OLMOE_SPEC, 1, r,
                              dict.fromkeys(OLMOE, "nb-major"))
       for r in (1, 8, 16)},
    # yi34b-tp4.decode1: shard-local nb 224 / 56 / 160, all off the grid
    **{f"yi-tp4-rows{r}": (YI, None, 4, r, _ALL_NB) for r in (1, 8, 16)},
    # Mistral sharded: nb 128 is on the grid, the input-sharded 32 / 112 not
    **{f"mistral-tp4-rows{r}": (MISTRAL, None, 4, r,
                                {**_ALL_D, "wo": "nb-major",
                                 "w2": "nb-major"})
       for r in (1, 8, 16)},
}


@pytest.mark.parametrize("case", sorted(PARENT_PACKED))
def test_leaf_rule_packs_the_cells_as_the_parent_did(case, monkeypatch):
    """``q40_leaf_layout`` on each leaf's shard-local shape, under the
    layout an engine of that width resolves, and ``pack_q40_params`` on
    the abstract tree: both give the table's kinds, whatever the width."""
    import jax

    from distributed_llama_tpu.parallel.tp import FUSED_INPUT_SHARDED

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    shapes, spec_kw, tp, rows, want = PARENT_PACKED[case]
    layout = (q40_body_policy(TransformerSpec(**spec_kw), rows)
              if spec_kw else Q40_STOCK)

    def local(k, d, n):
        if tp > 1 and k in FUSED_INPUT_SHARDED:
            return d, n // tp // 32
        return d // tp, n // 32

    got = {k: q40_leaf_layout(*local(k, d, n), tp=tp, layout=layout, key=k)
           for k, (d, n) in shapes.items()}
    assert got == want

    lead = {k: (1, 64) if k.startswith("moe_") else
            () if k == "wcls" else (1,) for k in shapes}
    tree = {k: Q40Weight(
        jax.ShapeDtypeStruct((*lead[k], d, n // 32, 16), np.uint8),
        jax.ShapeDtypeStruct((*lead[k], d, n // 32), np.float16))
        for k, (d, n) in shapes.items()}
    packed = jax.eval_shape(lambda t: pack_q40_params(
        t, tp=tp, allow_nb_major=True, layout=layout,
        input_sharded=FUSED_INPUT_SHARDED if tp > 1 else ()), tree)
    names = {Q40KernelNb: "nb-major", Q40Kernel: "d-major",
             Q40Weight: "codec"}
    assert {k: names[type(v)] for k, v in packed.items()} == want


# The ten cells of BENCHMARK.json (``laguna.mix-sat32`` joined with PR 44): (policy label, kinds) an engine of the
# cell's width and tp resolves for the cell's own configuration file. PR 32
# moved the two 8-row cells (were "d-major", every leaf d-major) and nothing
# else: rows 1 and 16 and tp 4 read what PR 31's tree read. The three 32-row
# cells (PR 33, 37, 39) joined with PR 43; their kinds are "nb-major" for
# every leaf the spec names, a leading dense layer's included.
CELLS_PACKED = {
    "mistral7b.decode1": ("i4-nb", _ALL_NB),
    "mistral7b.serve-chat": ("i4-nb", _ALL_NB),
    "mistral7b.serve-sat": ("i4-nb", _ALL_NB),
    "yi34b-tp4.decode1": ("d-major", _ALL_NB),
    "olmoe7b.gen-sat16": ("d-major", dict.fromkeys(OLMOE, "nb-major")),
    "brumby14b.gen-sat16": ("i4-nb", _ALL_NB),
    "deepseekv3.gen-sat32": ("nb-major", None),
    "phi4flash.reason-sat32": ("nb-major", None),
    "xing4.gen-sat32": ("nb-major", None),
    "laguna.mix-sat32": ("nb-major", None),
}
MOVED_BY_PR32 = {"mistral7b.serve-chat", "mistral7b.serve-sat"}
SINCE_PR31 = {"deepseekv3.gen-sat32", "phi4flash.reason-sat32",
              "xing4.gen-sat32", "laguna.mix-sat32"}
PR31_PACKED = {**CELLS_PACKED,
               **dict.fromkeys(MOVED_BY_PR32, ("d-major", _ALL_D))}
_HARNESS = {"olmoe": "olmoe", "brumby": "retention", "deepseek_v3": "latent",
            "phi4flash": "hybrid", "xing4_0": "hyper", "laguna": "laguna"}


@pytest.mark.parametrize("cell_name", sorted(CELLS_PACKED))
def test_the_nine_cells_policy_and_leaf_kinds(cell_name, monkeypatch):
    """Each cell's configuration through its own harness module to the
    program's spec, at the cell's dispatch width (1 / 8 / 16 / 32 rows) and
    tp (1 / 4): the policy label and every leaf's packed kind. NO cell packs
    a d-major leaf: the ledger cannot say what the d-major bodies cost
    (ROADMAP D19 rests on this)."""
    import importlib

    import jax

    from benchmark.harness import cells
    from distributed_llama_tpu.parallel.tp import FUSED_INPUT_SHARDED

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    cell = cells.load_cell(cell_name)
    harness = importlib.import_module("benchmark.harness." + _HARNESS.get(
        cell.config.get("model_type"), "model"))
    flags = cell.config["entries"][
        "inference" if cell.traffic["entry"] == "inference" else "serve"]
    rows, tp = int(flags.get("slots", 1)), int(flags.get("tp", 1))
    assert (rows, tp) == {"mistral7b.decode1": (1, 1),
                          "yi34b-tp4.decode1": (1, 4)}.get(
        cell_name, (8 if cell_name in MOVED_BY_PR32 else
                    32 if cell_name in SINCE_PR31 else 16, 1))
    spec = harness.program_spec(harness.sizes_of(cell.config))
    layout = q40_body_policy(spec, rows=rows, sharded=tp > 1)

    def leaves(shapes, lead):
        shapes = dict(shapes)
        if spec.latent:     # as models/latent.prepare_latent_params leaves it
            from distributed_llama_tpu.models.latent import plane_width

            del shapes["wkv_b"]     # float32 w_uk / w_uv from here on
            shapes["wkv_a"] = (plane_width(spec), spec.dim)
        return {k: Q40Weight(
            jax.ShapeDtypeStruct((*lead(k), d, n // 32, 16), np.uint8),
            jax.ShapeDtypeStruct((*lead(k), d, n // 32), np.float16))
            for k, (d, n) in shapes.items()}

    tree = leaves(spec.layer_matmul_shapes() + spec.expert_matmul_shapes(),
                  lambda k: (1, spec.n_experts_held)
                  if k.startswith("moe_") else (1,))
    tree["wcls"] = Q40Weight(
        jax.ShapeDtypeStruct((spec.vocab_size, spec.dim // 32, 16), np.uint8),
        jax.ShapeDtypeStruct((spec.vocab_size, spec.dim // 32), np.float16))
    if spec.dense_layer_matmul_shapes():
        tree["dense"] = leaves(spec.dense_layer_matmul_shapes(),
                               lambda k: (1,))
    packed = jax.eval_shape(lambda t: pack_q40_params(
        t, tp=tp, allow_nb_major=tp == 1, layout=layout,
        input_sharded=FUSED_INPUT_SHARDED if tp > 1 else ()), tree)
    names = {Q40KernelNb: "nb-major", Q40Kernel: "d-major",
             Q40Weight: "codec"}
    dense = packed.pop("dense", {})
    kinds = {k: names[type(v)] for k, v in packed.items()}
    label, want = CELLS_PACKED[cell_name]
    if want is None:
        want = dict.fromkeys(kinds, "nb-major")
    assert (layout.label, kinds) == (label, want)
    assert {*kinds.values(), *(names[type(v)] for v in dense.values())} \
        == {"nb-major"}
    if cell_name not in SINCE_PR31:
        assert ((label, kinds) == PR31_PACKED[cell_name]) == (
            cell_name not in MOVED_BY_PR32)


@pytest.mark.parametrize("dim,label", [(2048, "nb-major"),
                                       (4096, "d-major")])
def test_a_slotted_spec_is_judged_by_its_block_counts(dim, label,
                                                      monkeypatch):
    """A slot-and-pages spec packs ONE layout, and which follows from its
    leaves' block counts, not from the record it carries: a leaf off the
    128 grid (dim 2048: 64 blocks a row) makes it nb-major, and with every
    count on the grid (dim, heads x head and hidden all multiples of 4096)
    the stock picks stand; never the i4 body, which a slot's state
    refuses."""
    from distributed_llama_tpu.models.spec import (MixerKind, MixerKinds,
                                                   TransformerSpec)

    monkeypatch.setenv("DLLAMA_Q40_KERNEL", "pallas")
    kind = MixerKind(32, 1e4, 128, None)
    spec = TransformerSpec(
        dim=dim, hidden_dim=8192, n_layers=4, n_heads=32, n_kv_heads=8,
        vocab_size=32768, seq_len=1024, mixers=MixerKinds(
            ("full", "sliding") * 2, 512, 128, kind, kind, True))
    assert spec.slotted
    layout = q40_body_policy(spec, rows=32)
    assert layout.label == label and ("off the 128 grid" in layout.reason) \
        == (label == "nb-major")


def test_leaf_rule_corners():
    forced = Q40Layout("i4-nb", "test")
    # one chip: the pad pick (13B's nb 160 pads 1.6x), opted in or not
    assert q40_leaf_layout(5120, 160) == "nb-major"
    assert q40_leaf_layout(5120, 160, allow_nb_major=False) == "d-major"
    assert q40_leaf_layout(5120, 432) == "d-major"            # pads 1.19x
    assert q40_leaf_layout(5120, 432, layout=forced) == "nb-major"
    # a d the nb-major row tiler cannot place stays d-major even forced
    assert q40_leaf_layout(1376, 160, layout=forced) == "d-major"
    # sharded: the layout's force is not consulted, the grid and width are
    assert q40_leaf_layout(4096, 128, tp=4, layout=forced) == "d-major"
    assert q40_leaf_layout(1792, 224, tp=4) == "nb-major"
    assert q40_leaf_layout(1792, 256, tp=4) == "d-major"
    # what neither tiler places stays codec
    assert q40_leaf_layout(1000003, 128) == "codec"
    # an expert stack: nb-major where the grouped kernels place it
    assert q40_leaf_layout(1024, 64, key="moe_w1") == "nb-major"
    assert q40_leaf_layout(100, 64, key="moe_w2") == "codec"


# retired names, spelled in halves so that this file holds none of them:
# three layout variables (ISSUE 29), then four Q40 tile and body knobs and
# the d-major int4 leaf they could reach (ISSUE 43)
_NAMES = ("DLLAMA_Q40_" + "BODY", "DLLAMA_Q40_" + "I4",
          "DLLAMA_NB_" + "MAJOR", "DLLAMA_PREFILL_" + "MATMUL",
          "DLLAMA_MULTI_T_" + "BODY", "DLLAMA_MULTI_" + "CAP",
          "DLLAMA_MATVEC_" + "CAP", "Q40Kernel" + "I4")


def _sources(*roots):
    for root in roots:
        path = os.path.join(_ROOT, root)
        if os.path.isfile(path):
            yield path
            continue
        for base, _, files in os.walk(path):
            for f in files:
                if f.endswith((".py", ".md")):
                    yield os.path.join(base, f)


def test_library_writes_no_environment():
    """Outside frontend/ and analysis/ the package writes ``os.environ``
    nowhere."""
    write = re.compile(r"os\.environ\[[^\]]*\]\s*=[^=]|os\.environ\."
                       r"(setdefault|update|pop)\(|os\.putenv\(|"
                       r"del os\.environ")
    offenders = []
    for path in _sources("distributed_llama_tpu"):
        rel = os.path.relpath(path, _ROOT)
        if rel.split(os.sep)[1] in ("frontend", "analysis"):
            continue
        with open(path, encoding="utf-8") as fh:
            for i, line in enumerate(fh, 1):
                if write.search(line):
                    offenders.append(f"{rel}:{i}: {line.strip()}")
    assert offenders == []


@functools.lru_cache(maxsize=None)
def _shipped_text() -> dict:
    """Relative path -> text of everything that ships, read once."""
    out = {}
    for path in _sources("distributed_llama_tpu", "bench.py", "tools",
                         "chip_smoke.py", "README.md"):
        with open(path, encoding="utf-8") as fh:
            out[os.path.relpath(path, _ROOT)] = fh.read()
    return out


@pytest.mark.parametrize("name", _NAMES)
def test_nothing_that_ships_names_a_retired_variable(name):
    assert [p for p, text in _shipped_text().items() if name in text] == []


def test_the_package_names_eleven_variables():
    """ROADMAP D5 counts them; a new ``DLLAMA_*`` name is a decision, not a
    side effect."""
    found = set()
    for path, text in _shipped_text().items():
        if path.startswith("distributed_llama_tpu" + os.sep):
            found.update(re.findall(r"DLLAMA_[A-Z0-9_]+", text))
    assert len(found) == 11, sorted(found)
