"""At temperature 0 ``generate``'s per-token loop takes the token on the
device and runs one step ahead of the host (``Engine.infer(..., pick=True)``,
PR 27); with a temperature the host samples as it always did.

The reference here is the loop ``generate`` was before: ``Engine.infer``
WITHOUT the pick (logits back) and the host ``Sampler`` on them. ``generate``
must give that loop's tokens and leave the sampler's xorshift stream where
that loop leaves it, whatever ends the generation; the counters
``ahead_used`` / ``ahead_dropped`` say how often the step enqueued ahead was
the one the caller asked for next.
"""

import numpy as np
import pytest

from distributed_llama_tpu.io.tokenizer import BOS
from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import synth_params
from distributed_llama_tpu.ops.quants import FloatType
from distributed_llama_tpu.runtime.generate import Engine, generate
from distributed_llama_tpu.runtime.sampling import Sampler

DENSE = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=128, seq_len=32)
EXPERT = TransformerSpec(dim=128, hidden_dim=64, n_layers=2, n_heads=2,
                         n_kv_heads=2, vocab_size=256, seq_len=32,
                         weights_float_type=FloatType.Q40, n_experts=4,
                         n_active_experts=2, qk_norm=True)
SAMPLERS = [(0.0, 0.9), (0.8, 0.9)]


class _IdTokenizer:
    def encode(self, text, bos=True, eos=False):
        return [1] + [3 + b for b in text.encode()]

    def decode_piece(self, prev, tok):
        return b"?"


TOK = _IdTokenizer()


def _sampler(spec, temperature, topp, seed=7):
    # the numpy sampler: the semantics of record (sampling.Sampler)
    return Sampler(spec.vocab_size, temperature, topp, seed,
                   use_native=False)


def host_loop(engine, sampler, prompt, steps):
    """The per-token loop with the HOST sampling: forced prompt tokens, then
    ``sampler.sample`` on the logits ``infer`` brings back; stop on BOS."""
    prompt_tokens = TOK.encode(prompt)
    out, token = [], prompt_tokens[0]
    for pos in range(min(steps, engine.spec.seq_len)):
        logits = engine.infer(token, pos)
        if pos + 1 < len(prompt_tokens):
            token = prompt_tokens[pos + 1]
        else:
            token = sampler.sample(logits)
        if token == BOS:
            break
        out.append(token)
    return out


@pytest.fixture(scope="module")
def engines():
    """kind -> a function that builds a new Engine of that kind."""
    dense = synth_params(DENSE, q40=False, seed=4, scale=0.3)
    expert = synth_params(EXPERT, q40=True, seed=11)

    def sharded():
        from distributed_llama_tpu.parallel import make_mesh

        return Engine(DENSE, dense, mesh=make_mesh(sp=1, tp=2))

    return {"dense": lambda: Engine(DENSE, dense), "tp2": sharded,
            "expert": lambda: Engine(EXPERT, expert)}


def _ahead(temperature, sampled):
    """(used, dropped) of a generation of ``sampled`` sampled tokens that
    ends on its budget: every sampled step but the first is found in
    flight and the last enqueues none; nothing runs ahead of a host
    sampler."""
    return (sampled - 1 if temperature == 0.0 else 0, 0)


@pytest.mark.parametrize("temperature,topp", SAMPLERS)
@pytest.mark.parametrize("kind", ["dense", "tp2", "expert"])
def test_generate_gives_the_host_sampled_stream(engines, kind, temperature,
                                                topp):
    ref_eng, eng = engines[kind](), engines[kind]()
    s_ref = _sampler(eng.spec, temperature, topp)
    s = _sampler(eng.spec, temperature, topp)
    steps = 20
    want = host_loop(ref_eng, s_ref, "abcd", steps)
    got, stats = generate(eng, TOK, s, "abcd", steps, quiet=True)
    assert got == want and len(got) == steps
    assert s.rng.state == s_ref.rng.state and s.rng.draws == s_ref.rng.draws
    assert stats.tokens == steps          # 4 forced positions, 16 sampled
    assert (stats.ahead_used, stats.ahead_dropped) == _ahead(temperature, 16)
    assert eng._ahead is None
    if kind == "expert":  # counted when a step is consumed: one a token
        k, L = EXPERT.n_active_experts, EXPERT.n_layers
        assert stats.moe_pairs == stats.moe_active == steps * k * L


def test_greedy_bos_stop_drops_the_one_step_enqueued_on_it(engines):
    steps = 18
    want = host_loop(engines["dense"](), _sampler(DENSE, 0.0, 0.9), "we",
                     steps)
    eng = engines["dense"]()
    got, stats = generate(eng, TOK, _sampler(DENSE, 0.0, 0.9), "we", steps,
                          quiet=True)
    assert got == want and 4 <= len(got) < steps - 1   # it did stop early
    assert stats.tokens == len(got) + 1 and stats.final_token == BOS
    assert stats.ahead_dropped == 1
    assert stats.ahead_used == stats.tokens - 2 - 1    # 2 forced, 1 first
    assert eng._ahead is None
    # the engine goes on as one that never ran ahead does
    ref = engines["dense"]()
    host_loop(ref, _sampler(DENSE, 0.0, 0.9), "we", steps)
    np.testing.assert_array_equal(eng.infer(9, stats.final_pos),
                                  ref.infer(9, stats.final_pos))


def test_sampled_bos_stop_leaves_the_rng_where_the_host_loop_does():
    """All-zero classifier: uniform probabilities, so BOS comes up when a
    coin lands in its 1 / vocab bucket (test_decode_loop's recipe)."""
    from distributed_llama_tpu.utils.rng import Xorshift64

    params = synth_params(DENSE, q40=False, seed=3, scale=0.0)
    params["wcls"] = np.zeros_like(params["wcls"])
    params["tok_embedding"] = np.zeros_like(params["tok_embedding"])
    steps = 14
    # multinomial over a uniform cdf: index = floor(coin * vocab)
    seed = next(
        s for s in range(1, 4000)
        if 3 <= next((i for i, c in enumerate(
            Xorshift64(s).f32_array(steps - 2))
            if int(c * DENSE.vocab_size) == BOS), 0) < steps - 4)
    s_ref = Sampler(DENSE.vocab_size, 0.7, 0.0, seed, use_native=False)
    want = host_loop(Engine(DENSE, params), s_ref, "a", steps)
    s = Sampler(DENSE.vocab_size, 0.7, 0.0, seed, use_native=False)
    got, stats = generate(Engine(DENSE, params), TOK, s, "a", steps,
                          quiet=True)
    assert got == want and 3 <= len(got) < steps - 1
    assert (stats.ahead_used, stats.ahead_dropped) == (0, 0)
    assert (s.rng.state, s.rng.draws) == (s_ref.rng.state, s_ref.rng.draws)


@pytest.mark.parametrize("temperature,topp", SAMPLERS)
def test_forced_prompt_tail_and_the_last_position(engines, temperature,
                                                  topp):
    """``prefill_chunk=0``: a long prompt is forced through ``infer`` token
    by token. Nothing is enqueued ahead on a forced token (so nothing is
    dropped), and none at the last position: the step program runs once a
    token, not once more."""
    eng = engines["dense"]()
    calls = []
    fwd = eng._fwd
    eng._fwd = lambda *a: calls.append(1) or fwd(*a)
    prompt, steps = "0123456789", 18   # no BOS within the budget
    s_ref = _sampler(DENSE, temperature, topp)
    want = host_loop(engines["dense"](), s_ref, prompt, steps)
    s = _sampler(DENSE, temperature, topp)
    got, stats = generate(eng, TOK, s, prompt, steps, quiet=True,
                          prefill_chunk=0)
    assert got == want and got[:10] == TOK.encode(prompt)[1:]
    assert stats.tokens == steps == len(calls)
    assert (stats.ahead_used, stats.ahead_dropped) == _ahead(temperature, 8)
    assert s.rng.state == s_ref.rng.state


def test_prompt_longer_than_the_budget_enqueues_nothing(engines):
    eng = engines["dense"]()
    got, stats = generate(eng, TOK, _sampler(DENSE, 0.0, 0.9), "abcdefgh",
                          5, quiet=True)
    assert got == TOK.encode("abcdefgh")[1:6]
    assert (stats.ahead_used, stats.ahead_dropped) == (0, 0)
    assert eng._ahead is None
    assert stats.prompt_rest == TOK.encode("abcdefgh")[6:]


@pytest.mark.parametrize("temperature,topp", [(0.0, 0.9), (0.9, 0.9)])
def test_checkpoint_resume_gives_the_uninterrupted_host_stream(
        engines, tmp_path, temperature, topp):
    from distributed_llama_tpu.runtime.checkpoint import (
        load_generation_state, save_generation_state)

    s_ref = _sampler(DENSE, temperature, topp, seed=77)
    want = host_loop(engines["dense"](), s_ref, "ab", 16)

    eng1, s1 = engines["dense"](), _sampler(DENSE, temperature, topp, seed=77)
    part1, st1 = generate(eng1, TOK, s1, "ab", 7, quiet=True)
    assert eng1._ahead is None     # the budget's end enqueued nothing
    ckpt = str(tmp_path / "gen.npz")
    save_generation_state(ckpt, eng1, s1, st1.final_pos, st1.final_token,
                          part1)
    eng2, s2 = engines["dense"](), _sampler(DENSE, temperature, topp, seed=1)
    pos, token, _, rest = load_generation_state(ckpt, eng2, s2)
    part2, st2 = generate(eng2, TOK, s2, "IGNORED", 16 - pos, quiet=True,
                          resume=(pos, token), resume_prompt=rest)
    assert part1 + part2 == want and len(want) == 16
    assert s2.rng.state == s_ref.rng.state
    assert (st1.ahead_used, st1.ahead_dropped) == _ahead(temperature, 5)
    assert (st2.ahead_used, st2.ahead_dropped) == _ahead(temperature, 9)


def test_infer_without_pick_is_unchanged_and_a_jump_drops_the_step(engines):
    eng, ref = engines["dense"](), engines["dense"]()
    first = eng.infer(5, 0, pick=True)
    assert isinstance(first, int) and first == int(np.argmax(ref.infer(5, 0)))
    assert eng._ahead is not None and eng._ahead.token == first

    # a caller that does not go where infer assumed: the step is dropped
    # and the call runs as it always did
    other = (first + 1) % DENSE.vocab_size
    logits = eng.infer(other, 1)
    assert (eng.ahead_used, eng.ahead_dropped) == (0, 1)
    assert eng._ahead is None
    assert isinstance(logits, np.ndarray) and logits.dtype == np.float32
    assert logits.shape == (DENSE.vocab_size,)
    np.testing.assert_array_equal(logits, ref.infer(other, 1))

    # the token it returned at the next position: found in flight
    second = eng.infer(int(np.argmax(logits)), 2, pick=True)
    third = eng.infer(second, 3, pick=True, last=True)
    assert (eng.ahead_used, eng.ahead_dropped) == (1, 1)
    assert eng._ahead is None
    assert second == int(np.argmax(ref.infer(int(np.argmax(logits)), 2)))
    assert third == int(np.argmax(ref.infer(second, 3)))
    # the step in flight handed to a caller who wants logits: dropped too
    eng.infer(third, 4, pick=True)
    ref.infer(third, 4)
    np.testing.assert_array_equal(eng.infer(7, 5), ref.infer(7, 5))
    assert (eng.ahead_used, eng.ahead_dropped) == (1, 2)


def test_reset_and_prefill_drop_the_step_in_flight(engines):
    eng = engines["dense"]()
    eng.infer(5, 0, pick=True)
    eng.reset()
    assert eng._ahead is None and eng.ahead_dropped == 1
    eng.infer(5, 0, pick=True)
    eng.prefill([1, 7, 9], 0, chunk=2)
    assert eng._ahead is None and eng.ahead_dropped == 2
    # and the engine goes on as a new one does
    ref = engines["dense"]()
    ref.prefill([1, 7, 9], 0, chunk=2)
    np.testing.assert_array_equal(eng.infer(11, 3), ref.infer(11, 3))


def test_no_step_ahead_past_the_context(engines):
    """At the cache's last slot there is no next position to run: a step
    at ``seq_len`` would clamp its write back over the last real slot."""
    eng = engines["dense"]()
    eng.infer(5, DENSE.seq_len - 1, pick=True)
    assert eng._ahead is None


def test_greedy_pick_takes_the_lowest_index_on_a_tie():
    """``np.argmax`` (the host's ``sample_argmax``) and the step program's
    pick agree on ties: an all-zero classifier makes every logit equal."""
    params = synth_params(DENSE, q40=False, seed=3, scale=0.3)
    params["wcls"] = np.zeros_like(params["wcls"])
    assert Engine(DENSE, params).infer(5, 0, pick=True) == 0


def test_summary_prints_and_logs_the_counters(engines, capsys, monkeypatch):
    import sys

    gen_mod = sys.modules[generate.__module__]
    events = []
    monkeypatch.setattr(
        gen_mod, "log_event",
        lambda name, text, **kw: events.append((name, kw)))
    _, stats = generate(engines["dense"](), TOK, _sampler(DENSE, 0.0, 0.9),
                        "ab", 9)
    assert "Steps run ahead:     6 used, 0 dropped" in capsys.readouterr().out
    summary = dict(events)["run.summary"]
    assert (summary["ahead_used"], summary["ahead_dropped"]) == (6, 0)
    assert (stats.ahead_used, stats.ahead_dropped) == (6, 0)
