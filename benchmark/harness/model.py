"""From a configuration file to what the program is handed: the seven header
sizes, the program's ``TransformerSpec``, the seeded codec tree, and the
synthetic tokenizer. Shared by the drivers."""

from __future__ import annotations

import os
import tempfile


def sizes_of(config: dict) -> dict:
    """The reference header's seven sizes from the published ``config.json``
    keys the configuration file holds at its top level."""
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    if dim % heads:
        raise ValueError("hidden_size must divide by num_attention_heads")
    if "head_dim" in config and config["head_dim"] != dim // heads:
        raise ValueError("the program fixes head size at hidden/heads")
    return {"dim": dim, "hidden_dim": config["intermediate_size"],
            "n_layers": config["num_hidden_layers"], "n_heads": heads,
            "n_kv_heads": config["num_key_value_heads"],
            "vocab_size": config["vocab_size"],
            "seq_len": config["max_position_embeddings"]}


def check_runnable(config: dict) -> None:
    """What the program fixes in code must be what the file says is run."""
    from distributed_llama_tpu.ops import linear

    if config.get("rope_theta") != 10000.0:
        raise ValueError("the program fixes the RoPE base at 10000 "
                         "(models/llama.py); the file must say so and list "
                         "rope_theta under reduced if the source differs")
    if abs(config.get("rms_norm_eps") - linear.RMS_EPS) > 1e-12:
        raise ValueError(f"the program fixes RMSNorm eps at "
                         f"{linear.RMS_EPS}")
    win = config.get("sliding_window")
    if win is not None and win < config["max_position_embeddings"]:
        raise ValueError("a sliding window shorter than the context would "
                         "cut attention; the program has no window")
    if (config.get("weights"), config.get("buffers"),
            config.get("kv_cache")) != ("q40", "f32", "f32"):
        raise ValueError("drivers run Q40 weights with f32 buffers and KV")


def program_spec(sizes: dict):
    from distributed_llama_tpu.models.spec import TransformerSpec
    from distributed_llama_tpu.ops.quants import FloatType

    return TransformerSpec(**sizes, weights_float_type=FloatType.Q40,
                           buffer_float_type=FloatType.F32)


def codec_tree(sizes: dict, seed: int):
    from distributed_llama_tpu.io.loader import Q40Weight

    from . import weights

    return weights.build_codec_tree(sizes, seed, Q40Weight)


def tokenizer(vocab_size: int):
    """The program's synthetic tokenizer (one token a character, plus BOS
    and the leading space), written under ``TMPDIR`` and loaded."""
    from distributed_llama_tpu.io.tokenizer import Tokenizer
    from distributed_llama_tpu.models.synth import write_synth_tokenizer

    fd, path = tempfile.mkstemp(prefix="bench_tok_", suffix=".bin")
    os.close(fd)
    try:
        write_synth_tokenizer(path, vocab_size)
        return Tokenizer(path, vocab_size)
    finally:
        os.unlink(path)
