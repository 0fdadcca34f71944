"""Where the persistent compile cache is on and where it is not:
``utils/compile_cache.enable_persistent_cache`` is the CLI's (and
``bench.py``'s) process-wide switch, and a test process must not inherit it
from a ``cli.main`` it ran (``conftest.persistent_cache_off``, ROADMAP D22).
The function itself stays what the benchmark's warm ``setup_s`` depends on.
"""

import contextlib
import io
import os

import jax

from distributed_llama_tpu.models.spec import TransformerSpec
from distributed_llama_tpu.models.synth import (write_synth_q40_model,
                                                write_synth_tokenizer)
from distributed_llama_tpu.ops.quants import FloatType

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = TransformerSpec(dim=64, hidden_dim=160, n_layers=2, n_heads=4,
                       n_kv_heads=2, vocab_size=384, seq_len=32,
                       weights_float_type=FloatType.Q40)


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_cli_main_in_process_leaves_the_cache_off(tmp_path):
    from distributed_llama_tpu.frontend.cli import main

    model, tok = str(tmp_path / "m.bin"), str(tmp_path / "t.bin")
    write_synth_q40_model(model, SPEC, seed=2)
    write_synth_tokenizer(tok, SPEC.vocab_size)
    checkout_cache = os.path.join(_ROOT, ".jax_cache")
    configured = jax.config.jax_compilation_cache_dir
    before = _entries(checkout_cache)
    with contextlib.redirect_stdout(io.StringIO()) as out:
        assert main(["inference", "--model", model, "--tokenizer", tok,
                     "--prompt", "hi", "--steps", "4", "--temperature", "0",
                     "--weights-float-type", "q40", "--tp", "1"]) == 0
    assert "Avg generation time" in out.getvalue()   # programs were made
    assert jax.config.jax_compilation_cache_dir == configured
    assert configured != checkout_cache
    assert not _entries(checkout_cache) - before


def test_enable_persistent_cache_itself_is_whole(persistent_cache_off,
                                                 tmp_path, monkeypatch):
    """The real function, handed over by the fixture that hides it: placed
    from outside it sets no directory in code, returns the one it was given
    and zeroes both thresholds; the fixture then puts all three back."""
    enable_persistent_cache = persistent_cache_off
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    configured = jax.config.jax_compilation_cache_dir
    assert enable_persistent_cache() == placed and os.path.isdir(placed)
    assert jax.config.jax_compilation_cache_dir == configured
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
