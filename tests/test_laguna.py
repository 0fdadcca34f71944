"""A mixer-kinds spec (Laguna-XS.2's layout: full and sliding grouped-query
attention layers, each kind with its own head count and RoPE, a per-head
output gate, a dense layer before expert layers) against
``models/reference_laguna.py`` on LOGITS, at a toy size with the published
pattern (L = 9: full at 0, 4, 8 with 6 heads, sliding between with 8, over 2
KV heads of 16; window 8, so the rings wrap; layer 0 a dense SwiGLU, then 8
experts of which 2 a token and a shared one; the full layers rotate half a
head under YaRN, the sliding ones the whole head at another base).

TOL: float32 against float32 at highest precision differs by op order alone
(the largest reading here is 2e-6 on logits of std 0.5); the same forward
with bfloat16 products reads 1e-2 and more, which
``test_bfloat16_fails_the_tolerance`` holds. A router decision that the two
take differently would read far over it: the seeded tokens' smallest margin
is 1e-4, a hundred times what the scores differ by.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_llama_tpu.io.loader import (load_model, read_spec,
                                             tensor_byte_ranges, write_model)
from distributed_llama_tpu.models import kindscan, laguna
from distributed_llama_tpu.models import reference_laguna as ref
from distributed_llama_tpu.models.llama import (forward, init_cache,
                                                params_to_device)
from distributed_llama_tpu.models.spec import (ExpertLayout, MixerKind,
                                               MixerKinds, RopeScaling,
                                               Router, TransformerSpec)
from distributed_llama_tpu.models.synth import (synth_params,
                                                write_synth_q40_model)
from distributed_llama_tpu.ops.quants import FloatType

TOL = 2e-4
SEQ = 64
PATTERN = ("full", "sliding", "sliding", "sliding")
YARN = RopeScaling(64.0, 16, 64.0, 1.0, 1.0, 0.0)


def tiny(n_layers=9, wft=FloatType.F32, experts=True, head=16, **kw):
    kinds = (PATTERN * 10)[:n_layers]
    mixers = MixerKinds(kinds, 8, head,
                        MixerKind(6, 500000.0, head // 2, YARN),
                        MixerKind(8, 10000.0), True)
    moe = dict(n_experts=8, n_active_experts=2,
               layout=ExpertLayout(1, 96, 1),
               router=Router("sigmoid", 1, 1, True, 2.5)) if experts else {}
    return TransformerSpec(
        dim=64, hidden_dim=32, n_layers=n_layers, n_heads=6, n_kv_heads=2,
        vocab_size=128, seq_len=SEQ, weights_float_type=wft, norm_eps=1e-6,
        mixers=mixers, **moe, **kw)


SPEC = tiny()


@pytest.fixture(scope="module")
def tokens():
    return [int(t) for t in np.random.default_rng(0).integers(3, 128, SEQ)]


@pytest.fixture(scope="module", params=[(9, True), (6, False)],
                ids=["experts", "dense"])
def model(request, tokens):
    spec = tiny(request.param[0], experts=request.param[1])
    tree = synth_params(spec, q40=False, seed=3)
    return spec, tree, ref.forward(tree, spec, tokens)[0]


@pytest.fixture(scope="module")
def tree():
    return synth_params(SPEC, q40=False, seed=3)


@pytest.fixture(scope="module")
def want(tree, tokens):
    logits, margins, _ = ref.forward(tree, SPEC, tokens)
    assert margins.min() > 5e-5     # no near-tie in the seeded stream
    return logits


# -- the spec and its file --------------------------------------------------------

def test_the_pattern_and_its_scans():
    assert SPEC.header_version == 7 and SPEC.planned and SPEC.slotted
    assert SPEC.head_size == 16 and SPEC.kv_dim == 32
    assert (SPEC.mixers.count("full"), SPEC.mixers.count("sliding")) == (3, 6)
    sigs = laguna.layer_stacks(SPEC)
    assert sigs[0] == ("full", "dense") and sigs[4] == ("full", "")
    # layer 0 alone (its FFN is dense), then sliding x 3 + full twice over
    assert kindscan.segments(sigs) == [
        (0, (("full", "dense"),), 1),
        (1, (("sliding", ""),) * 3 + (("full", ""),), 2)]
    # the published depth: the unit nine times, then the last three layers
    deep = kindscan.segments(laguna.layer_stacks(tiny(40)))
    assert [(f, len(u), r) for f, u, r in deep] == [(0, 1, 1), (1, 4, 9),
                                                    (37, 1, 3)]


def test_header_round_trip():
    for spec in (SPEC, tiny(6, FloatType.Q40, experts=False),
                 dataclasses.replace(SPEC, mixers=dataclasses.replace(
                     SPEC.mixers, gate=False,
                     sliding=MixerKind(4, 1e6, 8, YARN)))):
        raw = spec.header()
        assert len(raw) == spec.header_bytes == 468
        assert TransformerSpec.from_header(
            raw, spec.weights_float_type) == spec
    # every earlier version still reads and writes byte for byte
    old = TransformerSpec(64, 128, 2, 4, 2, 128, 64)
    assert old.header_version == 0 and len(old.header()) == 28


@pytest.mark.parametrize("change,match", [
    (dict(n_heads=8), "n_heads the full kind's"),
    (dict(qk_norm=True), "without q/k-norm"),
    (dict(rope_scaling=YARN), "a kind's"),
    (dict(n_layers=8), "for each of n_layers"),
])
def test_the_spec_refuses(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(SPEC, **change)


def test_a_kind_refuses_heads_off_the_kv_heads():
    with pytest.raises(ValueError, match="multiple of n_kv_heads"):
        dataclasses.replace(SPEC, mixers=dataclasses.replace(
            SPEC.mixers, sliding=MixerKind(7)))
    with pytest.raises(ValueError, match="set latent or mixers"):
        TransformerSpec(64, 32, 2, 4, 2, 128, 64, n_experts=4,
                        n_active_experts=2, layout=ExpertLayout(1, 96))


def test_file_round_trip_and_byte_ranges(tmp_path, tree):
    path = str(tmp_path / "m.bin")
    write_model(path, SPEC, tree)
    assert read_spec(path) == SPEC
    spec, back = load_model(path)
    assert set(back) == set(tree)
    for stack in ("full", "sliding", "dense"):
        assert set(back[stack]) == set(tree[stack])
        for k, v in tree[stack].items():
            assert np.array_equal(back[stack][k], v), (stack, k)
    assert np.array_equal(back["moe_w2"], tree["moe_w2"])
    assert back["full"]["wq"].shape == (3, 96, 64)
    assert back["sliding"]["wq"].shape == (6, 128, 64)
    assert back["sliding"]["w_hgate"].shape == (6, 8, 64)
    ranges = tensor_byte_ranges(SPEC)
    assert ranges[-1].offset + ranges[-1].nbytes == SPEC.file_size()
    layers = [r.layer for r in ranges if r.name == "rms_ffn"]
    assert layers == list(range(9))
    assert [r.layer for r in ranges if r.name == "w_hgate"] == list(range(9))


def test_synth_q40_file_is_byte_exact(tmp_path):
    spec = tiny(6, FloatType.Q40)
    path = str(tmp_path / "q.bin")
    assert write_synth_q40_model(path, spec, seed=1) == spec.file_size()
    assert read_spec(path, FloatType.Q40) == spec


# -- the forward against the reference ------------------------------------------

def test_full_forward_matches_the_reference(model, tokens):
    spec, tree, want = model
    got, _ = forward(spec, params_to_device(tree), init_cache(spec),
                     jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[:45]).max() < TOL


def test_chunked_prefill_then_decode(model, tokens):
    """Chunks of 8 with a ragged last one (21 = 2 x 8 + 5) through the
    caches, then decode: the rings have wrapped twice by then, and both
    head counts ran."""
    spec, tree, want = model
    params = params_to_device(tree)
    pre = jax.jit(lambda p, c, t, pos, n: laguna.forward_chunk(
        spec, p, c, t, pos, n, xdec=False))
    step = jax.jit(lambda p, c, t, pos: forward(spec, p, c, t, pos))
    cache = init_cache(spec)
    for lo in range(0, 21, 8):
        part = tokens[lo:min(lo + 8, 21)]
        logits, cache = pre(params, cache,
                            jnp.asarray(part + [0] * (8 - len(part))),
                            jnp.int32(lo), jnp.int32(len(part)))
        assert logits.shape == (0, spec.vocab_size)   # no classifier
    worst = 0.0
    for pos in range(21, 40):
        logits, cache = step(params, cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos))
        worst = max(worst, float(np.abs(np.asarray(logits)[0]
                                        - want[pos]).max()))
    assert worst < TOL


def test_a_padded_position_reaches_nothing(tree, tokens):
    """A chunk of 8 of which 5 count leaves rings and K / V as the 5 alone
    do (another pad token, the same cache)."""
    params = params_to_device(tree)
    pre = jax.jit(lambda t, n: laguna.forward_chunk(
        SPEC, params, init_cache(SPEC), t, jnp.int32(0), n, xdec=False)[1])
    a = pre(jnp.asarray(tokens[:5] + [7, 8, 9]), jnp.int32(5))
    b = pre(jnp.asarray(tokens[:5] + [0, 0, 0]), jnp.int32(5))
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_a_chunk_walks_its_live_prefix(tree, tokens, want):
    """Chunks of 16 take the walk over the blocks up to pos + T (16 divides
    the 64 positions); a chunk of 24 does not divide them and takes the
    whole masked plane: both are the reference's."""
    params = params_to_device(tree)
    for t_len in (16, 24):
        cache, worst = init_cache(SPEC), 0.0
        for lo in range(0, 48, t_len):
            logits, cache = forward(SPEC, params, cache,
                                    jnp.asarray(tokens[lo:lo + t_len]),
                                    jnp.int32(lo))
            worst = max(worst, float(np.abs(
                np.asarray(logits) - want[lo:lo + t_len]).max()))
        assert worst < TOL, t_len


@pytest.mark.parametrize("dropped", ["gate", "rope"])
def test_the_gate_and_the_kinds_rope_each_matter(tree, tokens, want, dropped):
    """The reference with the per-head gate left out, or with plain RoPE
    over the whole head at base 10,000 in place of each kind's own (so the
    full layers lose their partial rotation, YaRN's frequencies and the
    attention factor), is NOT what the program computes: a forward that
    dropped either would fail the tests above."""
    other = ref.forward(tree, SPEC, tokens[:45], **{dropped: False})[0]
    assert np.abs(other - want[:45]).max() > 100 * TOL
    got, _ = forward(SPEC, params_to_device(tree), init_cache(SPEC),
                     jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - other).max() > 100 * TOL


def test_the_kinds_rope_tables_are_the_references():
    """The program's and the reference's tables are written apart; the
    published model's numbers by hand: a full layer rotates 64 of 128
    dimensions, its slowest pairs at 1 / 64 of their frequency, cos and sin
    times 0.1 ln 64 + 1."""
    for spec in (SPEC, tiny(head=128, n_layers=4)):
        tables = laguna.rope_tables(spec)
        for kind in ("full", "sliding"):
            freq, factor = ref.rope_table(spec.mixers.of(kind),
                                          spec.head_size)
            assert np.allclose(tables[kind][0], freq, rtol=1e-6)
            assert tables[kind][1] == pytest.approx(factor)
    mk = MixerKind(48, 500000.0, 64, RopeScaling(64.0, 4096, 64.0, 1.0))
    freq, factor = ref.rope_table(mk, 128)
    assert freq.shape == (32,) and factor == pytest.approx(1.4158883, 1e-7)
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert freq[0] == pytest.approx(1.0) and freq[-1] == pytest.approx(
        plain[-1] / 64, rel=1e-5)


def test_last_position_only_prompt(tree, tokens, want):
    """``Engine.prefill`` fills the caches; the prompt's last token takes
    the decode step: the reference's last row."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng = Engine(SPEC, tree)
    for round_ in range(2):      # the second on the first's stale caches
        eng.prefill(tokens[:30], chunk=8)      # 30 = 3 x 8 + 6
        got = eng.infer(tokens[30], 30)
        assert np.abs(got - want[30]).max() < TOL, round_
    assert 0 < eng.gate_min < 0.5 and eng.moe_pairs == 2 * 8 * 2
    with pytest.raises(ValueError, match="cannot be rewound"):
        eng.infer(tokens[5], 5)


def test_bfloat16_fails_the_tolerance(tree, tokens, want):
    from distributed_llama_tpu.ops.linear import matmul_precision

    with matmul_precision("bf16"):
        got, _ = forward(SPEC, params_to_device(tree), init_cache(SPEC),
                         jnp.asarray(tokens[:45]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want[:45]).max() > 5 * TOL


def test_q40_tree_matches_the_reference(tokens):
    spec = tiny(9, FloatType.Q40)
    tree = synth_params(spec, q40=True, seed=5)
    want = ref.forward(tree, spec, tokens[:24])[0]
    got, _ = forward(spec, params_to_device(tree), init_cache(spec),
                     jnp.asarray(tokens[:24]), jnp.int32(0))
    assert np.abs(np.asarray(got) - want).max() < TOL


def test_decode_through_the_kernels_matches_the_einsum_route(tokens,
                                                             monkeypatch):
    """The decode step with the head-major attention kernels on (interpret
    mode; head size 128, 2 KV heads, groups of 3 and 4 heads in one
    program) against the reference: rings through ``rows_decode_attention``,
    the full layers' planes through the same kernel at their own depth."""
    spec = tiny(5, head=128)
    tree = synth_params(spec, q40=False, seed=3)
    want = ref.forward(tree, spec, tokens[:20])[0]
    monkeypatch.setenv("DLLAMA_ATTN_KERNEL", "pallas")
    params = params_to_device(tree)
    step = jax.jit(lambda c, t, pos: forward(spec, params, c, t, pos))
    cache, worst = init_cache(spec), 0.0
    for pos in range(20):
        logits, cache = step(cache, jnp.asarray(tokens[pos:pos + 1]),
                             jnp.int32(pos))
        worst = max(worst, float(np.abs(np.asarray(logits)[0]
                                        - want[pos]).max()))
    assert worst < TOL


# -- serve -------------------------------------------------------------------------

def _greedy(spec, tree, prompt, steps):
    """What single-sequence ``inference`` gives at temperature 0."""
    from distributed_llama_tpu.runtime.generate import Engine

    eng, out, tok = Engine(spec, tree), [], prompt[0]
    for pos in range(steps):
        forced = pos + 1 < len(prompt)
        nxt = eng.infer(tok, pos, pick=not forced, last=True)
        tok = prompt[pos + 1] if forced else nxt
        out.append(tok)
    return out


def _engine(tree, spec=SPEC, **kw):
    from distributed_llama_tpu.runtime.continuous import ContinuousEngine

    kw = dict(dict(slots=2, temperature=0.0, topp=0.9, seed=3,
                   prefill_chunk=8, page_size=4, kv_pages=40), **kw)
    return ContinuousEngine(spec, tree, **kw)


def test_serve_more_requests_than_slots(tree, tokens, want):
    """Five requests on two slots: a slot is reused over another sequence's
    rings and pages, prompts longer than twice the window among them. Every
    stream is ``inference``'s, and every served position's logit lies at
    the reference's maximum."""
    from distributed_llama_tpu.runtime.continuous import Request

    prompts = [tokens[:9], tokens[5:30], tokens[20:22], tokens[10:37],
               tokens[40:52]]
    budgets = [24, 40, 20, 44, 30]
    eng = _engine(tree)
    assert eng._insert.__name__ == "serve_admit_state_insert"
    assert eng._decode.__name__ == "serve_decode_step"
    mx = SPEC.mixers
    assert eng.stats.state_bytes == 0
    assert eng.stats.window_bytes == 2 * 6 * mx.window * 2 * SPEC.kv_dim * 4
    # a page covers the full layers only: 3 pools of 40 + 1 pages
    assert eng.cache.k.shape == (3, 41, 2, 4, 16)
    reqs = [eng.submit(Request(tokens=list(p), steps=b))
            for p, b in zip(prompts, budgets)]
    while eng.step_once():
        pass
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.error is None and r.out == _greedy(SPEC, tree, p, b)
        served = r.out[len(p) - 1:]
        logits = ref.forward(tree, SPEC, list(p) + served[:-1])[0][
            len(p) - 1:]
        short = logits.max(-1) - logits[np.arange(len(served)), served]
        assert short.max() < TOL
    st = eng.stats
    assert st.steps_ahead > 0 and st.admit_prefills == 4
    assert 0 < st.gate_min < st.gate_mean < 1 and st.gate_steps == st.steps
    # every step routes 2 rows x 2 experts in each of the 8 expert layers
    assert st.moe_pairs == st.steps * 2 * 2 * 8 == st.moe_local_pairs
    assert st.moe_load.shape == (8,) and st.moe_chunk_pairs > 0
    assert st.shared_kv_positions > st.window_kv_positions > 0
    assert st.paged_kv_positions == 0     # a plain KV pool's counter
    assert st.shared_kv_pages >= 0


def test_a_stale_row_decodes_as_an_empty_one(tree):
    """Nothing resets a retired row: the step program, run from position 0
    on rows that hold other sequences' rings and pages, gives bit for bit
    what it gives on an engine that has served nothing."""
    def from_zero(dirty):
        eng = _engine(tree)
        table = np.arange(1, 1 + 2 * 16, dtype=np.int32).reshape(2, 16)

        def decode(first, steps):
            out, tok = [], np.asarray(first, np.int32)
            picked = jnp.zeros((2,), jnp.int32)
            for pos in range(steps):
                blk = np.concatenate(
                    [tok[:, None], np.full((2, 1), pos, np.int32), table,
                     np.ones((2, 1), np.int32)], axis=1)
                lg, picked, eng.cache, _, _ = eng._decode(
                    eng.params, eng.cache, picked, jnp.asarray(blk))
                out.append(np.asarray(lg))
                tok = np.asarray(picked)
            return np.stack(out, 1)

        if dirty:
            decode([5, 9], 12)
        return decode([1, 1], 6)

    assert np.array_equal(from_zero(False), from_zero(True))


REFUSED = {
    "tp": (dict(tp=2, page_size=16), "--tp 2"),
    "no pages": (dict(), "serve without --kv-page-size"),
    "prefix sharing": (dict(page_size=16, prefix_share=True),
                       "prefix sharing"),
    "spec_k": (dict(page_size=16, spec_k=4), "--spec-k 4"),
    "dispatch_tokens": (dict(page_size=16, dispatch_tokens=32),
                        "--dispatch-tokens 32"),
    "kv_quant": (dict(page_size=16, kv_quant="q8"), "--kv-quant q8"),
    "tiers": (dict(page_size=16, kv_host_pages=4), "--kv-host-pages"),
    "journal": (dict(page_size=16, journal=True), "--journal"),
    "disagg": (dict(page_size=16, disagg=True), "--disagg-role"),
    "block_steps": (dict(page_size=16, block_steps=4), "--block-steps 4"),
    "cache dtype": (dict(page_size=16, kv_cache_dtype="bf16"),
                    "--kv-cache-dtype bf16"),
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_each_refusal_by_name(flag):
    """One list (``cache_refusals``), read by what a sequence caches: a
    mixer-kinds spec's lines name the flag and say why."""
    from distributed_llama_tpu.runtime.continuous import (cache_refusals,
                                                          sequence_caches)

    caches = sequence_caches(SPEC)
    assert caches == {"state", "pages", "rings"}
    kw, names = REFUSED[flag]
    lines = cache_refusals(caches, **kw)
    assert len(lines) == 1 and lines[0].startswith(names)
    assert "window ring" in lines[0]
    assert cache_refusals(caches, page_size=16) == []
    assert cache_refusals(caches, serve=False) == []     # inference


@pytest.mark.parametrize("kw,match", [
    (dict(page_size=0, kv_pages=0), "serve without --kv-page-size"),
    (dict(prefix_share=True), "prefix sharing"),
    (dict(spec_k=4), "--spec-k 4"),
    (dict(dispatch_tokens=32), "--dispatch-tokens"),
    (dict(kv_quant="q8"), "--kv-quant q8"),
    (dict(kv_host_pages=4), "--kv-host-pages"),
    (dict(remote_pages=True), "--disagg-role"),
    (dict(block_steps=4), "--block-steps 4"),
    (dict(cache_dtype=jnp.bfloat16), "--kv-cache-dtype"),
])
def test_the_engine_refuses(tree, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(tree, **kw)


def test_tp_refuses_a_mixer_kinds_spec(tree):
    from distributed_llama_tpu.analysis import memory_model as mm
    from distributed_llama_tpu.parallel import make_mesh
    from distributed_llama_tpu.parallel.tp import (param_specs,
                                                   validate_sharding)
    from distributed_llama_tpu.runtime.generate import Engine

    mesh = make_mesh(tp=2)
    dense = tiny(6, experts=False)
    dense_tree = synth_params(dense, q40=False, seed=3)
    for raises in (lambda: validate_sharding(SPEC, mesh),
                   lambda: validate_sharding(dense, mesh),
                   lambda: param_specs(dense_tree),
                   lambda: Engine(dense, dense_tree, mesh=mesh),
                   lambda: mm.weight_values_per_device(SPEC, 2),
                   lambda: mm.weight_values_per_device(dense, 2),
                   lambda: mm.kv_position_bytes(dense, 2)):
        with pytest.raises(ValueError, match="one chip only"):
            raises()


def test_memory_model_counts_rings_pages_and_whole_experts():
    """One chip: rings for the sliding layers, pages over the full layers
    ONLY, every expert held; the sizes by hand from the shapes."""
    from distributed_llama_tpu.analysis import memory_model as mm

    spec = tiny(9, FloatType.Q40)
    mx = spec.mixers
    assert mm.state_slot_bytes(spec) == 6 * mx.window * 2 * spec.kv_dim * 4
    assert mm.kv_position_bytes(spec, 1) == 3 * 2 * spec.kv_dim * 4
    assert mm.kv_page_bytes(spec, 1, 4) == 4 * 3 * 2 * spec.kv_dim * 4
    values = 128 * 64 + sum(
        e[2][0] * e[2][1] for _, _, entries in spec.layer_plans()
        for e in entries if e[0] == "mm")
    assert mm.weight_values_per_device(spec, 1) == values
    # 8 experts of three (32 x 64) leaves in each of 8 layers are in it
    assert values > 8 * 8 * 3 * 32 * 64
    rep = mm.device_footprint(spec, 1, "ref", batch=2, kv_page_size=4,
                              kv_pages=40)    # and the scrap page
    assert rep.kv_cache_bytes == (41 * mm.kv_page_bytes(spec, 1, 4)
                                  + 2 * mm.state_slot_bytes(spec))
    eng = _engine(synth_params(spec, q40=True, seed=3), spec)
    assert rep.kv_cache_bytes == sum(int(a.nbytes) for a in eng.cache)


@pytest.mark.parametrize("mode,flags,match", [
    ("inference", ["--tp", "2"], "one chip only"),
    ("serve", [], "refused: serve without --kv-page-size"),
    ("serve", ["--kv-page-size", "4", "--journal", "J"],
     "refused: --journal"),
    ("serve", ["--kv-page-size", "4", "--spec-k", "3"],
     "refused: --spec-k 3"),
])
def test_the_cli_refuses(tmp_path, capsys, mode, flags, match):
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    spec = dataclasses.replace(tiny(5, FloatType.Q40), vocab_size=512)
    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, spec, seed=1)
    write_synth_tokenizer(tok, spec.vocab_size)
    flags = [f.replace("J", str(tmp_path / "j.wal")) for f in flags]
    rc = cli.main([mode, "--model", model, "--tokenizer", tok,
                   "--weights-float-type", "q40", *flags,
                   *(["--prompt", "hi", "--steps", "4"]
                     if mode == "inference" else ["--port", "0"])])
    err = capsys.readouterr().err
    assert rc == 2 and match in err


def test_synth_model_file_runs_through_the_cli(tmp_path, capsys):
    """``inference`` from a file alone: the header says what the model is."""
    from distributed_llama_tpu.frontend import cli
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    spec = dataclasses.replace(tiny(5, FloatType.Q40), vocab_size=512)
    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    assert write_synth_q40_model(model, spec, seed=1) == spec.file_size()
    write_synth_tokenizer(tok, spec.vocab_size)
    rc = cli.main(["inference", "--model", model, "--tokenizer", tok,
                   "--weights-float-type", "q40", "--prompt", "hello there",
                   "--steps", "12", "--temperature", "0", "--tp", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2 full (6 heads" in out and "3 sliding (8 heads" in out
    assert "per-head output gate" in out and "8 experts held" in out


def test_convert_reads_the_published_config():
    """``laguna_spec`` on the catalog's keys: the kinds, a kind's head count
    and RoPE, the gate, the expert layout; the q / k rows' permutation
    turns a kind's ROTATED dimensions only."""
    import types

    from distributed_llama_tpu.convert import laguna_spec, unpermute_rotary

    c = types.SimpleNamespace(
        model_type="laguna", vocab_size=100352, hidden_size=2048,
        intermediate_size=8192, num_hidden_layers=8, num_attention_heads=48,
        num_key_value_heads=8, head_dim=128, rms_norm_eps=1e-6,
        num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, gating=True, sliding_window=512,
        rope_parameters={
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        layer_types=["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"] + ["sliding_attention"] * 3,
        mlp_layer_types=["dense"] + ["sparse"] * 7,
        moe_routed_scaling_factor=2.5,
        num_attention_heads_per_layer=[48, 64, 64, 64] * 2)
    spec = laguna_spec(c, FloatType.Q40, 4096)
    mx = spec.mixers
    assert mx.kinds == PATTERN * 2 and (mx.window, mx.head_size) == (512, 128)
    assert mx.full == MixerKind(48, 500000.0, 64,
                                RopeScaling(64.0, 4096, 64.0, 1.0, 1.0, 0.0))
    assert mx.sliding == MixerKind(64, 10000.0) and mx.gate
    assert spec.layout == ExpertLayout(1, 8192, 1)
    assert spec.router == Router("sigmoid", 1, 1, True, 2.5)
    assert (spec.n_experts, spec.n_active_experts, spec.hidden_dim) == (
        256, 8, 512)
    w = np.arange(2 * 8, dtype=np.float32).reshape(16, 1)   # 2 heads of 8
    got = unpermute_rotary(w, 8, 4)[:, 0]
    assert list(got[:8]) == [0, 2, 1, 3, 4, 5, 6, 7]
    assert list(unpermute_rotary(w, 8, 8)[:8, 0]) == [0, 4, 1, 5, 2, 6, 3, 7]
