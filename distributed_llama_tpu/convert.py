"""Weight converter: Meta/HF Llama checkpoints -> reference-format .bin.

Capability parity with the reference converter (converter/converter.py): reads
Meta ``consolidated.*.pth`` shards + ``params.json``, re-concatenates Meta's
tensor-parallel shards (dim=1 for tok_embeddings/wo/w2, dim=0 otherwise,
converter.py:131-148), and writes the header + tensors in the fixed reference
order with norms/embeddings always F32 and the legacy rope.freqs gap
(converter.py:85-151). Target float types: q40 | float16 | float32.

Extensions beyond the reference:
* ``--source hf``: convert a HuggingFace LlamaForCausalLM checkpoint
  (safetensors/pytorch), mapping q/k heads back from HF's permuted layout to
  Meta's interleaved RoPE layout.
* ``--source hf`` on ``model_type: olmoe`` (OlmoeForCausalLM): the routed
  experts (``mlp.gate.weight`` -> router, ``mlp.experts.{e}.gate_proj`` /
  ``down_proj`` / ``up_proj`` -> w1 / w2 / w3 of expert e) and the q/k-norm
  gains (``self_attn.q_norm`` / ``k_norm``), written with the extended
  header (models/spec.py). The row permutation applied to wq / wk for
  interleaved-pair RoPE is applied to the q/k-norm gains as well: the gains
  act on the projection's outputs, so they move with its rows. That is the
  one departure from the published rotate-half form, and it computes the
  same attention scores (a fixed permutation inside each head of both q
  and k leaves q.k unchanged; RMS is permutation-invariant).
* ``--source hf`` on ``model_type: brumby`` (Brumby-14B: Qwen3's block with
  power-retention layers): ``self_attn.q_norm`` / ``k_norm`` (ONE gain of
  head size each, permuted inside the head as the rows of wq / wk are) ->
  ``rms_q`` / ``rms_k``, and the retention gate ``self_attn.gate_proj.weight``
  -> ``w_gate``, float32, written after ``wo`` under header extension 3
  with ``rope_theta`` and ``rms_norm_eps`` from the config. The gate's
  tensor name and its shape (n_kv_heads, hidden_size: one gate a KV head)
  are this repo's reading of the publication, not of the checkpoint: a
  checkpoint whose gate has another shape is REFUSED rather than guessed at
  (``GATE_TENSOR`` names the tensor; there is no fallback).
* ``--source hf`` on ``model_type: deepseek_v3`` (DeepSeek-V3's block:
  latent attention, leading dense layers, sigmoid group-limited routing with
  a shared expert), from a BFLOAT16 checkpoint: ``self_attn.q_a_proj`` /
  ``q_a_layernorm`` / ``q_b_proj`` / ``kv_a_proj_with_mqa`` /
  ``kv_a_layernorm`` / ``kv_b_proj`` / ``o_proj``, ``mlp.gate.weight`` and
  ``mlp.gate.e_score_correction_bias``, ``mlp.shared_experts.*`` and
  ``mlp.experts.{e}.*`` (``LATENT_TENSORS``), written under header
  extension 4 in ``TransformerSpec.layer_plans`` order. The rows are taken
  as they are: the published weights give interleaved (pair) rotary
  features, which the published code de-interleaves at run time and this
  program rotates in place. The whole model is written: every layer and
  every routed expert (a file that holds a share of the experts is a
  benchmark's seeded tree, not a conversion). The released FP8 block-scaled
  checkpoint (``quantization_config`` in its config) is NOT read: cast it
  to bfloat16 with the publisher's script first. The multi-token-prediction
  module (the checkpoint's last layer) is left out.
* ``model_type: xing4_0`` (Xing4.0-29B-A4B: the same block with a residual
  path of ``hc_mult`` streams): as ``deepseek_v3``, under header extension
  6, with six float32 tensors a layer (``HYPER_TENSORS``). Their names in
  the checkpoint are a GUESS (``HYPER_TENSORS_NOTE``): no checkpoint was
  seen, and one that names them otherwise is refused by a ``KeyError``.
* ``model_type: Motif`` (Motif-3-Beta, ``attention_cls: gdla``: grouped
  differential attention on a latent plane, sliding and full layers,
  PolyNorm, ``mhc_expansion_rate`` streams): ``motif_spec`` reads its
  ``config.json`` into header extension 9; the tensors are
  ``LATENT_TENSORS`` and ``HYPER_TENSORS`` with ``MOTIF_TENSORS``' three,
  their names a GUESS as those (``MOTIF_TENSORS_NOTE``).
* ``model_type: phi4flash`` (Phi-4-mini-flash-reasoning, SambaY): its
  ``config.json`` gives the spec (``hybrid_spec``: the per-layer list of
  kinds from ``mb_per_layer`` and the depth, header extension 5; the
  state-space sizes, which the config has no key for, are the family's
  defaults), and the tensors are REFUSED: which checkpoint tensors are the
  lambdas and the sub-norm, and which heads the checkpoint pairs, was not
  checked against the checkpoint (no network here), and a converter that
  guessed would write a file that runs and is wrong.
* ``model_type: laguna`` (Laguna-XS.2: window and full grouped-query
  attention layers, each kind with its own head count and RoPE, a per-head
  output gate, a dense layer before expert layers): ``laguna_spec`` reads
  the config (``layer_types``, ``num_attention_heads_per_layer``, the two
  ``rope_parameters``, ``gating``, the expert keys; header extension 7). The
  tensor names (``LAGUNA_TENSORS``) are a GUESS (``LAGUNA_TENSORS_NOTE``):
  no checkpoint was seen, and one that names them otherwise is refused by a
  ``KeyError``. ``q_proj`` / ``k_proj`` rows go from rotate-half order to
  interleaved pairs over a kind's ROTATED dimensions only.
* ``model_type: mimo_v2_flash`` (MiMo-V2-Flash: window layers with a
  learned softmax sink beside full layers, a KV head count a layer kind, K
  heads of 192 beside V heads of 128, the attention output scaled):
  ``mimo_spec`` reads the config (``hybrid_layer_pattern``, the ``swa_*``
  keys, ``v_head_dim``, ``attention_value_scale``, the two sink flags,
  ``moe_layer_freq`` and the router's keys; header extension 8). The tensor
  names are ``LAGUNA_TENSORS`` with ``MIMO_TENSORS``' two, a GUESS as
  theirs; the multi-token-prediction layers are not read.
* ``model_type: nemotron_h`` (NVIDIA-Nemotron-3-Nano-30B-A3B: a layer is ONE
  mixer, Mamba-2, attention without positional encoding or non-gated relu2
  experts): ``nemotron_spec`` reads the config (``hybrid_override_pattern``,
  the ``mamba_*`` / ``ssm_state_size`` / ``n_groups`` / ``conv_kernel`` /
  ``chunk_size`` keys, the router's; header extension 10) and
  ``nemotron_tensor`` the published names (``NEMOTRON_TENSORS``):
  ``in_proj``'s rows [z | xBC | dt] are cut into ``in_zx`` and ``in_dt``,
  ``conv1d.weight`` (channels, 1, taps) is laid (taps, channels); q / k rows
  stay as they are (there is no rotary embedding to permute for). Tested on
  seeded tensors of those names; the published weights were not run.
* ``model_type: bailing_hybrid`` (Ling-3.0-flash: Kimi-Delta-Attention
  layers beside latent attention with no query rank and a head-wise gate,
  DeepSeek-V3's router, a SwiGLU clamp a layer): ``ling_spec`` reads the
  config (``layer_group_size``: every such layer is the latent one;
  ``kda_lower_bound``, ``short_conv_kernel_size``, the two
  ``*_swiglu_limit_list``; header extension 11) and ``ling_tensor`` the
  names (``LING_TENSORS``: a GUESS from the family's published hybrid code
  and Kimi Linear's, not read off a checkpoint): a KDA layer's q / k / v /
  f (the decay) / g projections are stacked into ``in_qkvag``, its three
  ``*_conv1d.weight`` (channels, 1, taps) laid (taps, 3 channels), and a
  layer's two limits, which are the CONFIG's, written as the tensor
  ``ffn_limit``. ``rope_interleave`` true: q / k rows stay as they are. The
  multi-token-prediction layer is not read. Tested on seeded tensors of
  those names; the published weights were not run.
* tokenizer export: ``--export-tokenizer`` writes the llama2.c tokenizer.bin
  from a sentencepiece tokenizer.model.

Usage: python -m distributed_llama_tpu.convert <modelPath> <q40|float16|float32>
       [--out FILE] [--seq-len N] [--source meta|hf]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
from pathlib import Path

import numpy as np

from .io.loader import _write_matmul  # same packers as the file writer
from .models.spec import TransformerSpec
from .ops.quants import FloatType

_FT = {"float32": FloatType.F32, "float16": FloatType.F16,
       "q40": FloatType.Q40}

# file-order tensor names per layer, and their Meta checkpoint keys
_LAYER_TENSORS = [
    ("rms_att", "layers.{i}.attention_norm.weight"),
    ("rms_ffn", "layers.{i}.ffn_norm.weight"),
    ("wq", "layers.{i}.attention.wq.weight"),
    ("wk", "layers.{i}.attention.wk.weight"),
    ("wv", "layers.{i}.attention.wv.weight"),
    ("wo", "layers.{i}.attention.wo.weight"),
    ("w1", "layers.{i}.feed_forward.w1.weight"),
    ("w2", "layers.{i}.feed_forward.w2.weight"),
    ("w3", "layers.{i}.feed_forward.w3.weight"),
]
# Meta shards concatenate along dim=1 for these (converter.py:131-136)
_AXIS1 = {"tok_embedding", "wo", "w2"}
_ALWAYS_F32 = {"tok_embedding", "rms_att", "rms_ffn", "rms_final", "rms_q",
               "rms_k", "moe_gate", "w_gate"}


def _is_f32(name: str) -> bool:
    return name in _ALWAYS_F32


class MetaCheckpoint:
    """Streams tensors from Meta consolidated.*.pth shards, one key at a time."""

    def __init__(self, model_path: str):
        import torch

        self.torch = torch
        self.paths = sorted(Path(model_path).glob("consolidated.*.pth"))
        if not self.paths:
            raise FileNotFoundError(
                f"no consolidated.*.pth under {model_path}")
        with open(os.path.join(model_path, "params.json")) as f:
            self.params = json.load(f)
        # mmap'd lazy loads: tensors materialize per-key, not per-file
        self.shards = [torch.load(p, map_location="cpu", mmap=True,
                                  weights_only=True) for p in self.paths]

    def tensor(self, key: str, axis1: bool) -> np.ndarray:
        parts = [s[key] for s in self.shards]
        t = (parts[0] if len(parts) == 1 or parts[0].dim() == 1
             else self.torch.cat(parts, dim=1 if axis1 else 0))
        return t.to(self.torch.float32).numpy()

    def spec(self, target: FloatType, seq_len: int) -> TransformerSpec:
        p = self.params
        vocab = p["vocab_size"]
        if vocab < 1:
            # Meta ships vocab_size=-1 as a sentinel; derive the real count
            # from the embedding table (the reference refuses outright,
            # converter.py:76-77 'Invalid vocab size')
            # tok_embeddings shards along dim=1, so shape[0] is the full vocab
            vocab = self.shards[0]["tok_embeddings.weight"].shape[0]
            if vocab < 1:
                raise ValueError("Invalid vocab size")
        w1 = self.shards[0]["layers.0.feed_forward.w1.weight"]
        hidden = w1.shape[0] * len(self.shards)
        return TransformerSpec(
            dim=p["dim"], hidden_dim=hidden, n_layers=p["n_layers"],
            n_heads=p["n_heads"],
            n_kv_heads=p.get("n_kv_heads") or p["n_heads"],
            vocab_size=vocab, seq_len=seq_len,
            weights_float_type=target)

    def keys(self):
        return {"tok_embedding": "tok_embeddings.weight",
                "rms_final": "norm.weight", "wcls": "output.weight"}


GATE_TENSOR = "model.layers.{layer}.self_attn.gate_proj.weight"
"""A ``brumby`` checkpoint's retention gate (assumed name; see the module
docstring)."""


_L = "model.layers.{layer}."
LATENT_TENSORS = {
    "rms_att": _L + "input_layernorm.weight",
    "rms_ffn": _L + "post_attention_layernorm.weight",
    "rms_q_a": _L + "self_attn.q_a_layernorm.weight",
    "rms_kv_a": _L + "self_attn.kv_a_layernorm.weight",
    "wq_a": _L + "self_attn.q_a_proj.weight",
    "wq_b": _L + "self_attn.q_b_proj.weight",
    "wkv_a": _L + "self_attn.kv_a_proj_with_mqa.weight",
    "wkv_b": _L + "self_attn.kv_b_proj.weight",
    "wo": _L + "self_attn.o_proj.weight",
    "w1": _L + "mlp.gate_proj.weight",
    "w2": _L + "mlp.down_proj.weight",
    "w3": _L + "mlp.up_proj.weight",
    "moe_gate": _L + "mlp.gate.weight",
    "moe_bias": _L + "mlp.gate.e_score_correction_bias",
    "sh_w1": _L + "mlp.shared_experts.gate_proj.weight",
    "sh_w2": _L + "mlp.shared_experts.down_proj.weight",
    "sh_w3": _L + "mlp.shared_experts.up_proj.weight",
    "moe_w1": _L + "mlp.experts.{expert}.gate_proj.weight",
    "moe_w2": _L + "mlp.experts.{expert}.down_proj.weight",
    "moe_w3": _L + "mlp.experts.{expert}.up_proj.weight",
}
LAGUNA_TENSORS = dict(
    {k: LATENT_TENSORS[k] for k in (
        "rms_att", "rms_ffn", "wo", "w1", "w2", "w3", "moe_gate",
        "moe_w1", "moe_w2", "moe_w3")},
    wq=_L + "self_attn.q_proj.weight", wk=_L + "self_attn.k_proj.weight",
    wv=_L + "self_attn.v_proj.weight",
    w_hgate=_L + "self_attn.g_proj.weight",
    sh_w1=_L + "mlp.shared_expert.gate_proj.weight",
    sh_w2=_L + "mlp.shared_expert.down_proj.weight",
    sh_w3=_L + "mlp.shared_expert.up_proj.weight")
MIMO_TENSORS = {"sink": _L + "self_attn.attention_sink_bias",
                "moe_bias": LATENT_TENSORS["moe_bias"]}
"""What a ``mimo_v2_flash`` checkpoint has beside ``LAGUNA_TENSORS`` (a
guess, as those)."""
LAGUNA_TENSORS_NOTE = (
    "the names of a laguna checkpoint's tensors (self_attn.g_proj for the "
    "per-head gate, mlp.shared_expert.*, mlp.experts.{e}.*) are a guess: "
    "no checkpoint was seen; one that names them otherwise fails with a "
    "KeyError and nothing is written wrong")
"""A ``deepseek_v3`` checkpoint's tensors by this repo's names, as the
published bfloat16 state dict names them."""


HYPER_TENSORS = {
    f"hc_{sub}_{leaf}": _L + f"{module}.{name}"
    for sub, module in (("att", "attn_hc"), ("ffn", "mlp_hc"))
    for leaf, name in (("phi", "phi.weight"), ("gate", "alpha"),
                       ("bias", "bias"))}
HYPER_TENSORS_NOTE = (
    "a guess: no xing4_0 checkpoint was seen. The map takes one module a "
    "sub-layer (attn_hc, mlp_hc) whose phi.weight is (2 n + n^2, n hidden) "
    "with rows [pre | post | res row-major], alpha the three gates and "
    "bias b_pre | b_post | B_res; a checkpoint that names or splits them "
    "otherwise fails with a KeyError on the first such tensor, and nothing "
    "is guessed further")
"""A ``xing4_0`` checkpoint's hyper-connection tensors by this repo's names
(``TransformerSpec.hyper_shapes``); the rest are ``LATENT_TENSORS``."""


MOTIF_TENSORS = {"w_lambda": _L + "self_attn.lambda_proj.weight",
                 "wg": _L + "self_attn.g_proj.weight",
                 "pn_w": _L + "mlp.act_fn.weight"}
MOTIF_TENSORS_NOTE = (
    "a guess: no Motif checkpoint was seen. The map takes self_attn."
    "lambda_proj.weight (signal heads x hidden) for the per-token lambda, "
    "self_attn.g_proj.weight for the elementwise gate and mlp.act_fn.weight "
    "as PolyNorm's (w0, w1, w2, b) of a layer; the hyper-connection modules "
    "as xing4_0's. A checkpoint that names or splits them otherwise fails "
    "with a KeyError on the first such tensor, and nothing is guessed "
    "further")
"""What a ``Motif`` checkpoint has beside ``LATENT_TENSORS`` and
``HYPER_TENSORS``."""


def motif_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``Motif`` config object ``c`` (``attention_cls``
    "gdla"): every layer and every routed expert, header extension 9. The
    readings the config does not settle are stated in
    ``models/reference_motif.py``."""
    import math

    from .models.spec import (Activation, ExpertLayout, HyperConnections,
                              LatentAttn, Router)

    if (c.attention_cls, c.score_func, c.hidden_act, bool(c.diff_v2),
            bool(c.headwise_attn_output_gate),
            getattr(c, "interleave_moe_layer_step", 1),
            bool(c.score_before_experts)) != (
            "gdla", "sigmoid", "poly_norm", True, False, 1, False):
        raise ValueError("Motif: grouped differential latent attention "
                         "(gdla, diff_v2), sigmoid scores applied after the "
                         "experts, an expert layer after every leading dense "
                         "one and PolyNorm are what the program runs")
    rs = getattr(c, "rope_scaling", None) or {}
    if rs.get("apply_yarn_scaling", False) or c.swa_rope_theta != c.rope_theta:
        raise ValueError("Motif: plain RoPE at one base in both layer kinds "
                         "is what the program runs (apply_yarn_scaling "
                         "false)")
    n_layers, groups = c.num_hidden_layers, c.num_key_value_heads
    if c.num_noise_heads not in (0, groups):
        raise ValueError("Motif: one noise head a KV group, or none")
    period = c.sliding_window_period
    sliding = bool(c.use_sliding_window)
    if sliding and c.sliding_window_pattern != "interleave":
        raise ValueError("Motif: the interleaved window pattern alone")
    kinds = tuple("full" if (i + 1) % period == 0 else "sliding"
                  for i in range(n_layers)) if sliding else ()
    streams = c.mhc_expansion_rate if c.mhc_enabled else 0
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.moe_intermediate_size,
        n_layers=n_layers, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_attention_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        n_experts=c.num_experts, n_active_experts=c.experts_top_k,
        rope_theta=float(c.rope_theta), norm_eps=float(c.rms_norm_eps),
        latent=LatentAttn(
            c.q_lora_rank, c.kv_lora_rank, c.head_dim - c.qk_rope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, kv_groups=groups,
            noise_heads=c.num_noise_heads // groups,
            gate=bool(c.elementwise_attn_output_gate), kinds=kinds,
            window=c.sliding_window if sliding else 0),
        layout=ExpertLayout(min(c.n_dense_first_layers, n_layers - 1),
                            c.intermediate_size, c.num_shared_experts),
        router=Router("sigmoid", 1, 1, bool(c.route_norm),
                      float(c.route_scale), False),
        hyper=HyperConnections(streams, int(c.mhc_sinkhorn_iters), 1e-6,
                               -math.inf, math.inf,
                               float(getattr(c, "hidden_clamp", 0) or 0))
        if streams else None,
        activation=Activation("polynorm", float(c.polynorm_output_scale),
                              float(c.polynorm_bias_clamp)))


def latent_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``deepseek_v3`` config object ``c``: every layer and
    every routed expert."""
    from .models.spec import ExpertLayout, LatentAttn, RopeScaling, Router

    if getattr(c, "quantization_config", None):
        raise ValueError(
            "an FP8 block-scaled checkpoint (quantization_config) is not "
            "read: cast it to bfloat16 first (the publisher's "
            "fp8_cast_bf16.py), then convert that")
    if (c.scoring_func, c.topk_method, getattr(c, "moe_layer_freq", 1)) != (
            "sigmoid", "noaux_tc", 1) or c.hidden_act != "silu":
        raise ValueError("deepseek_v3: sigmoid scores with the noaux_tc "
                         "choice, an expert layer after every leading "
                         "dense one and SwiGLU are what the program runs")
    rs = c.rope_scaling
    if rs and rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {rs}: only yarn is implemented")
    n_layers = c.num_hidden_layers
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.moe_intermediate_size,
        n_layers=n_layers, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_key_value_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        n_experts=c.n_routed_experts,
        n_active_experts=c.num_experts_per_tok,
        rope_theta=float(c.rope_theta), norm_eps=float(c.rms_norm_eps),
        latent=LatentAttn(c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
                          c.qk_rope_head_dim, c.v_head_dim),
        layout=ExpertLayout(min(c.first_k_dense_replace, n_layers - 1),
                            c.intermediate_size, c.n_shared_experts),
        router=Router("sigmoid", c.n_group, c.topk_group,
                      bool(c.norm_topk_prob),
                      float(c.routed_scaling_factor), True),
        rope_scaling=RopeScaling(
            float(rs["factor"]), int(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
            float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))
        if rs else None)


class HFCheckpoint:
    """HuggingFace LlamaForCausalLM -> reference tensor layout.

    HF stores wq/wk with rotary halves separated
    (permute: [h, 2, hs/2] view); Meta/reference RoPE expects interleaved
    pairs, so we invert the permutation.
    """

    def __init__(self, model_path: str):
        import torch

        self.torch = torch
        from transformers import AutoConfig

        self.config = AutoConfig.from_pretrained(model_path)
        self.path = model_path
        self._state = None

    @property
    def state(self):
        if self._state is None:
            from transformers import AutoModelForCausalLM

            model = AutoModelForCausalLM.from_pretrained(
                self.path, torch_dtype=self.torch.float32,
                low_cpu_mem_usage=True)
            self._state = model.state_dict()
        return self._state

    def _unpermute(self, w: "np.ndarray", n_heads: int) -> np.ndarray:
        """Rows of a (d, n) projection, or the d gains that act on its
        outputs, from rotate-half order to interleaved pairs."""
        d = w.shape[0]
        hs = d // n_heads
        return (w.reshape(n_heads, 2, hs // 2, *w.shape[1:])
                .swapaxes(1, 2).reshape(w.shape))

    def spec(self, target: FloatType, seq_len: int) -> TransformerSpec:
        c = self.config
        moe = {}
        if getattr(c, "model_type", "") == "deepseek_v3":
            return latent_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "xing4_0":
            import dataclasses

            from .models.spec import HyperConnections

            print(f"🔶 xing4_0 hyper-connection tensors: "
                  f"{HYPER_TENSORS_NOTE}")
            return dataclasses.replace(
                latent_spec(c, target, seq_len), hyper=HyperConnections(
                    int(c.hc_mult), int(c.hc_sinkhorn_iters),
                    float(c.hc_eps), float(c.mhc_h_res_clamp_min),
                    float(c.mhc_h_res_clamp_max)))
        if getattr(c, "model_type", "") == "Motif":
            print(f"🔶 Motif tensors: {MOTIF_TENSORS_NOTE}")
            return motif_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "phi4flash":
            return hybrid_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "nemotron_h":
            return nemotron_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "bailing_hybrid":
            print(f"🔶 bailing_hybrid tensors: {LING_TENSORS_NOTE}")
            return ling_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "laguna":
            print(f"🔶 laguna tensors: {LAGUNA_TENSORS_NOTE}")
            return laguna_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "mimo_v2_flash":
            print(f"🔶 mimo_v2_flash tensors, likewise: "
                  f"{LAGUNA_TENSORS_NOTE}")
            return mimo_spec(c, target, seq_len)
        if getattr(c, "model_type", "") == "olmoe":
            if getattr(c, "norm_topk_prob", False):
                raise ValueError("olmoe with norm_topk_prob: the program "
                                 "keeps the top-k probabilities as they are")
            if getattr(c, "clip_qkv", None) is not None:
                raise ValueError("olmoe with clip_qkv: not implemented")
            moe = dict(n_experts=c.num_experts,
                       n_active_experts=c.num_experts_per_tok, qk_norm=True)
        if getattr(c, "model_type", "") == "brumby":
            moe = dict(qk_norm=True, qk_norm_per_head=True,
                       attn_kind="retention",
                       rope_theta=float(c.rope_theta),
                       norm_eps=float(c.rms_norm_eps))
        return TransformerSpec(**moe, **self._base_sizes(target, seq_len))

    def _base_sizes(self, target: FloatType, seq_len: int) -> dict:
        c = self.config
        return dict(
            dim=c.hidden_size, hidden_dim=c.intermediate_size,
            n_layers=c.num_hidden_layers, n_heads=c.num_attention_heads,
            n_kv_heads=getattr(c, "num_key_value_heads",
                               c.num_attention_heads),
            vocab_size=c.vocab_size, seq_len=seq_len,
            weights_float_type=target)

    def tensor_by_name(self, name: str, layer: int | None,
                       spec: TransformerSpec,
                       expert: int | None = None) -> np.ndarray:
        if spec.hybrid:
            raise ValueError(
                "phi4flash: the checkpoint's tensor names (lambdas, "
                "sub-norm) and its pairing of heads were not checked "
                "against the checkpoint, so no tensor is converted (the "
                "module docstring says why); models/synth.py writes a "
                "seeded file of this spec")
        if spec.ssd:
            return nemotron_tensor(
                lambda key: self.state[key].to(self.torch.float32).numpy(),
                name, layer, spec, expert)
        if spec.kda:
            c = self.config
            return ling_tensor(
                lambda key: self.state[key].to(self.torch.float32).numpy(),
                name, layer, spec, expert,
                (c.expert_swiglu_limit_list,
                 c.share_expert_swiglu_limit_list))
        if spec.mixers:
            key = LAGUNA_TENSORS.get(name) or MIMO_TENSORS.get(name) or {
                "tok_embedding": "model.embed_tokens.weight",
                "rms_final": "model.norm.weight",
                "wcls": "lm_head.weight"}[name]
            w = self.state[key.format(layer=layer, expert=expert)].to(
                self.torch.float32).numpy()
            if name in ("wq", "wk"):
                kind = spec.mixers.kinds[layer]
                w = unpermute_rotary(w, spec.head_size,
                                     spec.mixers.rotary(kind))
            return w
        if spec.latent:     # rows as they are: see the module docstring
            key = (LATENT_TENSORS.get(name) or HYPER_TENSORS.get(name)
                   or MOTIF_TENSORS.get(name)) or {
                "tok_embedding": "model.embed_tokens.weight",
                "rms_final": "model.norm.weight",
                "wcls": "lm_head.weight"}[name]
            return self.state[key.format(layer=layer, expert=expert)].to(
                self.torch.float32).numpy()
        experts = f"model.layers.{layer}.mlp.experts.{expert}"
        hf = {
            "tok_embedding": "model.embed_tokens.weight",
            "rms_final": "model.norm.weight",
            "wcls": "lm_head.weight",
            "rms_att": f"model.layers.{layer}.input_layernorm.weight",
            "rms_ffn": f"model.layers.{layer}.post_attention_layernorm.weight",
            "wq": f"model.layers.{layer}.self_attn.q_proj.weight",
            "wk": f"model.layers.{layer}.self_attn.k_proj.weight",
            "wv": f"model.layers.{layer}.self_attn.v_proj.weight",
            "wo": f"model.layers.{layer}.self_attn.o_proj.weight",
            "w1": f"model.layers.{layer}.mlp.gate_proj.weight",
            "w2": f"model.layers.{layer}.mlp.down_proj.weight",
            "w3": f"model.layers.{layer}.mlp.up_proj.weight",
            "rms_q": f"model.layers.{layer}.self_attn.q_norm.weight",
            "rms_k": f"model.layers.{layer}.self_attn.k_norm.weight",
            "w_gate": GATE_TENSOR.format(layer=layer),
            "moe_gate": f"model.layers.{layer}.mlp.gate.weight",
            "moe_w1": f"{experts}.gate_proj.weight",
            "moe_w2": f"{experts}.down_proj.weight",
            "moe_w3": f"{experts}.up_proj.weight",
        }[name]
        w = self.state[hf].to(self.torch.float32).numpy()
        if name == "w_gate" and w.shape != spec.gate_shape:
            raise ValueError(
                f"{hf}: shape {w.shape}, expected (n_kv_heads, dim) = "
                f"{spec.gate_shape}: one sigmoid gate a KV head is what the "
                f"program runs; another gate form is not guessed at")
        per_head = spec.qk_norm_per_head and name in ("rms_q", "rms_k")
        if per_head:                      # one head's gains: permute inside
            w = self._unpermute(w, 1)
        elif name in ("wq", "rms_q"):
            w = self._unpermute(w, spec.n_heads)
        elif name in ("wk", "rms_k"):
            w = self._unpermute(w, spec.n_kv_heads)
        return w


def convert_meta(model_path: str, target: str, out: str | None = None,
                 seq_len: int = 2048) -> str:
    ckpt = MetaCheckpoint(model_path)
    spec = ckpt.spec(_FT[target], seq_len)
    name = os.path.basename(os.path.normpath(model_path))
    out = out or f"dllama_{name}_{target}.bin"
    top = ckpt.keys()

    with open(out, "wb") as f:
        f.write(spec.header())
        _write_tensor(f, spec, "tok_embedding",
                      ckpt.tensor(top["tok_embedding"], True))
        for i in range(spec.n_layers):
            for name_, key in _LAYER_TENSORS:
                arr = ckpt.tensor(key.format(i=i), name_ in _AXIS1)
                _write_tensor(f, spec, name_, arr)
                del arr
            gc.collect()
            print(f"🔶 wrote layer {i + 1}/{spec.n_layers}")
        _write_tensor(f, spec, "rms_final", ckpt.tensor(top["rms_final"], False))
        f.write(b"\x00" * spec.rope_gap_bytes)
        _write_tensor(f, spec, "wcls", ckpt.tensor(top["wcls"], False))
    assert os.path.getsize(out) == spec.file_size()
    print(f"✅ {out}: {spec.file_size()} bytes")
    return out


def unpermute_rotary(w: np.ndarray, head_size: int, rotary: int):
    """Rows of a (heads x head_size, n) projection: each head's first
    ``rotary`` rows from rotate-half order (i pairs with i + rotary / 2) to
    interleaved pairs, the other rows as they are."""
    heads = w.reshape(-1, head_size, *w.shape[1:])
    turned = heads[:, :rotary].reshape(
        heads.shape[0], 2, rotary // 2, *w.shape[1:]).swapaxes(1, 2)
    return np.concatenate(
        [turned.reshape(heads.shape[0], rotary, *w.shape[1:]),
         heads[:, rotary:]], axis=1).reshape(w.shape)


def laguna_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``laguna`` config: the per-layer list of kinds and head
    counts, each kind's RoPE from ``rope_parameters``, the gate flag, and
    the expert layout (leading dense layers from ``mlp_layer_types``).
    What the config has no key for is ``assumed`` (models/reference_laguna
    .py): sigmoid scores, renormalised top-k, the gate's form."""
    from .models.spec import (ExpertLayout, MixerKind, MixerKinds,
                              RopeScaling, Router)

    kinds = tuple("full" if t == "full_attention" else "sliding"
                  for t in c.layer_types)
    heads = {k: {h for h, kk in zip(c.num_attention_heads_per_layer, kinds)
                 if kk == k} for k in ("full", "sliding")}
    if any(len(h) > 1 for h in heads.values()):
        raise ValueError("laguna: one head count a layer KIND is what the "
                         f"spec holds, the config gives {heads}")
    mlp = list(getattr(c, "mlp_layer_types", []))
    dense = len(mlp) - len([m for m in mlp if m == "sparse"])
    if mlp[:dense] != ["dense"] * dense:
        raise ValueError("laguna: dense layers lead, expert layers follow")

    def kind_of(name, key):
        rp = c.rope_parameters[key]
        n_heads = (heads[name] or {c.num_attention_heads}).pop()
        rot = int(round(c.head_dim * float(
            rp.get("partial_rotary_factor", 1.0))))
        yarn = None
        if rp.get("rope_type", "default") == "yarn":
            import math

            m = 0.1 * math.log(rp["factor"]) + 1.0
            if abs(float(rp.get("attention_factor", m)) - m) > 1e-4:
                raise ValueError("laguna: an attention_factor other than "
                                 "0.1 ln(factor) + 1 has no field")
            yarn = RopeScaling(
                float(rp["factor"]),
                int(rp["original_max_position_embeddings"]),
                float(rp.get("beta_fast", 32.0)),
                float(rp.get("beta_slow", 1.0)), 1.0, 0.0)
        return MixerKind(n_heads, float(rp["rope_theta"]),
                         0 if rot == c.head_dim else rot, yarn)

    full = kind_of("full", "full_attention")
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.moe_intermediate_size,
        n_layers=c.num_hidden_layers, n_heads=full.heads,
        n_kv_heads=c.num_key_value_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        n_experts=c.num_experts, n_active_experts=c.num_experts_per_tok,
        norm_eps=float(c.rms_norm_eps),
        layout=ExpertLayout(dense, c.intermediate_size if dense else 0,
                            c.shared_expert_intermediate_size
                            // c.moe_intermediate_size),
        router=Router("sigmoid", 1, 1, True,
                      float(c.moe_routed_scaling_factor)),
        mixers=MixerKinds(kinds, int(c.sliding_window), int(c.head_dim),
                          full, kind_of("sliding", "sliding_attention"),
                          bool(getattr(c, "gating", False))))


def mimo_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``mimo_v2_flash`` config: the kinds from
    ``hybrid_layer_pattern`` (0 full, 1 sliding), each kind's KV heads, RoPE
    base and sink flag, K's and V's head sizes, the value scale, and the
    expert layout (leading dense layers from ``moe_layer_freq``; no shared
    expert). The rotated dimensions are ``int(head_dim x
    partial_rotary_factor)`` as published (64 of 192), the same in both
    kinds."""
    from .models.spec import ExpertLayout, MixerKind, MixerKinds, Router

    kinds = tuple("sliding" if k else "full" for k in c.hybrid_layer_pattern)
    freq = list(c.moe_layer_freq)
    dense = freq.index(1) if 1 in freq else len(freq)
    if any(f != 1 for f in freq[dense:]) or len(freq) != len(kinds):
        raise ValueError("mimo_v2_flash: dense layers lead, expert layers "
                         "follow, one entry a layer")
    if (c.swa_num_attention_heads != c.num_attention_heads
            or c.swa_head_dim != c.head_dim
            or c.swa_v_head_dim != c.v_head_dim
            or c.scoring_func != "sigmoid" or c.topk_method != "noaux_tc"
            or getattr(c, "n_shared_experts", None)
            or getattr(c, "attention_bias", False)):
        raise ValueError("mimo_v2_flash: one head count and one head size "
                         "for both kinds, sigmoid scores with a choice bias, "
                         "no shared expert and no attention bias are what "
                         "the spec holds")
    rot = int(c.head_dim * float(c.partial_rotary_factor))
    rot -= rot % 2
    rot = 0 if rot == c.head_dim else rot
    heads = c.num_attention_heads
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.moe_intermediate_size,
        n_layers=c.num_hidden_layers, n_heads=heads,
        n_kv_heads=c.num_key_value_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        n_experts=c.n_routed_experts,
        n_active_experts=c.num_experts_per_tok,
        norm_eps=float(c.layernorm_epsilon),
        layout=ExpertLayout(dense, c.intermediate_size if dense else 0, 0),
        router=Router("sigmoid", int(c.n_group), int(c.topk_group),
                      bool(c.norm_topk_prob),
                      float(c.routed_scaling_factor or 1.0), True),
        mixers=MixerKinds(
            kinds, int(c.sliding_window), int(c.head_dim),
            MixerKind(heads, float(c.rope_theta), rot, None, 0,
                      bool(c.add_full_attention_sink_bias)),
            MixerKind(heads, float(c.swa_rope_theta), rot, None,
                      int(c.swa_num_key_value_heads),
                      bool(c.add_swa_attention_sink_bias)),
            False, 0 if c.v_head_dim == c.head_dim else int(c.v_head_dim),
            float(c.attention_value_scale)))


NEMOTRON_TENSORS = {
    "tok_embedding": "backbone.embeddings.weight",
    "rms_final": "backbone.norm_f.weight",
    "wcls": "lm_head.weight",
    "rms_att": "backbone.layers.{layer}.norm.weight",
    "in_zx": "backbone.layers.{layer}.mixer.in_proj.weight",
    "in_dt": "backbone.layers.{layer}.mixer.in_proj.weight",
    "conv_w": "backbone.layers.{layer}.mixer.conv1d.weight",
    "conv_b": "backbone.layers.{layer}.mixer.conv1d.bias",
    "dt_bias": "backbone.layers.{layer}.mixer.dt_bias",
    "a_log": "backbone.layers.{layer}.mixer.A_log",
    "d_skip": "backbone.layers.{layer}.mixer.D",
    "norm_g": "backbone.layers.{layer}.mixer.norm.weight",
    "out_proj": "backbone.layers.{layer}.mixer.out_proj.weight",
    "wq": "backbone.layers.{layer}.mixer.q_proj.weight",
    "wk": "backbone.layers.{layer}.mixer.k_proj.weight",
    "wv": "backbone.layers.{layer}.mixer.v_proj.weight",
    "wo": "backbone.layers.{layer}.mixer.o_proj.weight",
    "moe_gate": "backbone.layers.{layer}.mixer.gate.weight",
    "moe_bias":
        "backbone.layers.{layer}.mixer.gate.e_score_correction_bias",
    "moe_w1": "backbone.layers.{layer}.mixer.experts.{expert}.up_proj.weight",
    "moe_w2":
        "backbone.layers.{layer}.mixer.experts.{expert}.down_proj.weight",
    "sh_w1": "backbone.layers.{layer}.mixer.shared_experts.up_proj.weight",
    "sh_w2": "backbone.layers.{layer}.mixer.shared_experts.down_proj.weight",
}
NEMOTRON_LETTERS = {"M": "mamba2", "*": "full", "E": "experts"}


def nemotron_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``nemotron_h`` config. ``d_inner`` is
    ``mamba_num_heads x mamba_head_dim`` (not ``expand x hidden_size``: the
    published parameter count says which), the router's groups are
    ``n_group`` (1) and Mamba-2's ``n_groups``; a pattern with a dense-MLP
    layer ('-'), a bias, a gated or another activation, or routing groups
    is refused."""
    from .models.spec import Activation, ExpertLayout, Router, SsdLayers

    pattern = c.hybrid_override_pattern
    if len(pattern) != c.num_hidden_layers or set(pattern) - set(
            NEMOTRON_LETTERS):
        raise ValueError("nemotron_h: hybrid_override_pattern has one of "
                         "M, * and E a layer (a dense-MLP layer '-' is not "
                         "run)")
    if (getattr(c, "mlp_hidden_act", "relu2") != "relu2"
            or getattr(c, "mamba_hidden_act", "silu") != "silu"
            or (getattr(c, "n_group", 1), getattr(c, "topk_group", 1))
            != (1, 1) or getattr(c, "n_shared_experts", 1) != 1
            or any(getattr(c, k, False) for k in (
                "attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias",
                "tie_word_embeddings"))
            or not getattr(c, "use_conv_bias", True)):
        raise ValueError("nemotron_h: relu2 experts with one shared, silu "
                         "in the mixer, no routing groups, no projection "
                         "bias, a conv bias and an untied head are what "
                         "the program runs")
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.moe_intermediate_size,
        n_layers=c.num_hidden_layers, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_key_value_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        n_experts=c.n_routed_experts,
        n_active_experts=c.num_experts_per_tok,
        norm_eps=float(getattr(c, "norm_eps", 1e-5)),
        layout=ExpertLayout(shared=1),
        router=Router("sigmoid", 1, 1, bool(c.norm_topk_prob),
                      float(c.routed_scaling_factor), bias=True),
        activation=Activation("relu2", gated=False),
        ssd=SsdLayers(tuple(NEMOTRON_LETTERS[x] for x in pattern),
                      int(c.mamba_num_heads), int(c.mamba_head_dim),
                      int(c.n_groups), int(c.ssm_state_size),
                      int(c.head_dim), int(c.conv_kernel),
                      int(c.chunk_size),
                      int(c.moe_shared_expert_intermediate_size)))


def nemotron_tensor(read, name: str, layer: int | None,
                    spec: TransformerSpec, expert: int | None = None):
    """The loader's tensor ``name`` of layer ``layer`` (held expert
    ``expert``) from a ``nemotron_h`` checkpoint, ``read(key)`` giving a
    published tensor as float32 numpy."""
    if expert is not None:
        expert += spec.layout.offset
    w = read(NEMOTRON_TENSORS[name].format(layer=layer, expert=expert))
    cut = spec.ssd.d_inner + spec.ssd.conv_dim
    if name == "in_zx":
        return w[:cut]
    if name == "in_dt":
        return w[cut:]
    if name == "conv_w":
        return np.ascontiguousarray(w.reshape(w.shape[0], -1).T)
    return w


LING_TENSORS_NOTE = (
    "the names are a guess from the family's published hybrid code and Kimi "
    "Linear's (no checkpoint was read): check them against the checkpoint's "
    "index before trusting a converted file")
_LING_ATT = "model.layers.{layer}.attention."
_LING_MLP = "model.layers.{layer}.mlp."
LING_TENSORS = {
    "tok_embedding": "model.word_embeddings.weight",
    "rms_final": "model.norm.weight",
    "wcls": "lm_head.weight",
    "rms_att": "model.layers.{layer}.input_layernorm.weight",
    "rms_ffn": "model.layers.{layer}.post_attention_layernorm.weight",
    # a KDA layer: in_qkvag stacks the five projections in this order
    "in_qkvag": tuple(_LING_ATT + n + "_proj.weight" for n in "qkvfg"),
    "conv_w": tuple(_LING_ATT + n + "_conv1d.weight" for n in "qkv"),
    "a_log": _LING_ATT + "A_log",
    "dt_bias": _LING_ATT + "dt_bias",
    "w_beta": _LING_ATT + "b_proj.weight",
    "norm_g": _LING_ATT + "o_norm.weight",
    "wo": _LING_ATT + "o_proj.weight",
    # a latent layer
    "wq": _LING_ATT + "q_proj.weight",
    "rms_kv_a": _LING_ATT + "kv_a_layernorm.weight",
    "wkv_a": _LING_ATT + "kv_a_proj_with_mqa.weight",
    "wkv_b": _LING_ATT + "kv_b_proj.weight",
    "w_hgate": _LING_ATT + "g_proj.weight",
    # the FFN
    "w1": _LING_MLP + "gate_proj.weight",
    "w2": _LING_MLP + "down_proj.weight",
    "w3": _LING_MLP + "up_proj.weight",
    "moe_gate": _LING_MLP + "gate.weight",
    "moe_bias": _LING_MLP + "gate.expert_bias",
    "sh_w1": _LING_MLP + "shared_experts.gate_proj.weight",
    "sh_w2": _LING_MLP + "shared_experts.down_proj.weight",
    "sh_w3": _LING_MLP + "shared_experts.up_proj.weight",
    "moe_w1": _LING_MLP + "experts.{expert}.gate_proj.weight",
    "moe_w2": _LING_MLP + "experts.{expert}.down_proj.weight",
    "moe_w3": _LING_MLP + "experts.{expert}.up_proj.weight",
}


def ling_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``bailing_hybrid`` config: every ``layer_group_size``-th
    layer is the latent one and the others are KDA layers; the decay and
    the output gate of a KDA layer full rank (``no_kda_lora``), the
    head-wise gate the latent layers'. A query rank, a RoPE scaling, a
    bias, another activation, a norm this program does not compute
    (``value_norm``, ``up_proj_norm``, ``use_nGPT``, ``scale_router_input``)
    or a KDA gate that is not the lower-bound ("safe") one is refused."""
    from .models.spec import (Activation, ExpertLayout, KdaLayers,
                              LatentAttn, Router)

    period = int(c.layer_group_size)
    if (getattr(c, "q_lora_rank", None) or getattr(c, "rope_scaling", None)
            or getattr(c, "hidden_act", "silu") != "silu"
            or not getattr(c, "kda_safe_gate", True)
            or not getattr(c, "no_kda_lora", True)
            or not getattr(c, "linear_silu", True)
            or not getattr(c, "rope_interleave", True)
            or not getattr(c, "use_qk_norm", True)
            or getattr(c, "group_norm_size", 1) != 1
            or getattr(c, "num_shared_experts", 1) != 1
            or getattr(c, "moe_shared_expert_intermediate_size",
                       c.moe_intermediate_size) != c.moe_intermediate_size
            or getattr(c, "gated_attention_proj_granularity_type",
                       "head_wise") != "head_wise"
            or any(getattr(c, k, False) for k in (
                "use_bias", "use_qkv_bias", "tie_word_embeddings",
                "value_norm", "up_proj_norm", "use_nGPT",
                "scale_router_input", "use_kda_lora", "use_mla_nope"))):
        raise ValueError(
            "bailing_hybrid: KDA layers with the lower-bound gate, full-rank "
            "decay and gate projections, SiLU after the convolutions and an "
            "L2 q / k norm, one output norm group; latent attention with no "
            "query rank, plain interleaved RoPE and a head-wise gate; SiLU "
            "experts with one shared expert of their width, no bias and an "
            "untied head are what the program runs")
    kinds = tuple("full" if (i + 1) % period == 0 else "kda"
                  for i in range(c.num_hidden_layers))
    limits = any(c.expert_swiglu_limit_list) or any(
        c.share_expert_swiglu_limit_list)
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.moe_intermediate_size,
        n_layers=c.num_hidden_layers, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_key_value_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        n_experts=c.num_experts, n_active_experts=c.num_experts_per_tok,
        rope_theta=float(c.rope_theta), norm_eps=float(c.rms_norm_eps),
        latent=LatentAttn(0, int(c.kv_lora_rank), int(c.qk_nope_head_dim),
                          int(c.qk_rope_head_dim), int(c.v_head_dim),
                          kinds=kinds, head_gate=True),
        layout=ExpertLayout(int(c.first_k_dense_replace),
                            int(c.intermediate_size), 1),
        router=Router(getattr(c, "score_function", "sigmoid"),
                      int(c.n_group), int(c.topk_group),
                      bool(c.norm_topk_prob),
                      float(c.routed_scaling_factor),
                      bool(c.moe_router_enable_expert_bias)),
        activation=Activation(limits=bool(limits)),
        kda=KdaLayers(int(c.num_attention_heads), int(c.head_dim),
                      int(c.short_conv_kernel_size),
                      lower_bound=float(c.kda_lower_bound)))


def ling_tensor(read, name: str, layer: int | None, spec: TransformerSpec,
                expert: int | None = None, limits=((), ())):
    """The loader's tensor ``name`` of layer ``layer`` (held expert
    ``expert``) from a ``bailing_hybrid`` checkpoint, ``read(key)`` giving
    a tensor as float32 numpy; ``limits``: the config's two per-layer
    lists, whose entries of ``layer`` are the tensor ``ffn_limit``."""
    if name == "ffn_limit":
        return np.asarray([lst[layer] if layer < len(lst) else 0.0
                           for lst in limits], np.float32)
    if expert is not None:
        expert += spec.layout.offset
    key = LING_TENSORS[name]
    if name == "conv_w":    # (channels, 1, taps) each -> (taps, 3 channels)
        return np.ascontiguousarray(np.concatenate(
            [read(k.format(layer=layer)).reshape(spec.kda.width, -1)
             for k in key]).T)
    if isinstance(key, tuple):
        return np.concatenate([read(k.format(layer=layer)) for k in key])
    return read(key.format(layer=layer, expert=expert))


def hybrid_spec(c, target: FloatType, seq_len: int) -> TransformerSpec:
    """The spec of a ``phi4flash`` config: Mamba at every ``mb_per_layer``-th
    layer up to the middle, window attention between, the full layer after
    the middle one, then GMUs and cross-attention (``sambay_kinds``). The
    config has no key for the state-space sizes: d_state 16, d_conv 4,
    expand 2 and dt_rank = ceil(hidden / 16), the family's defaults."""
    from .models.spec import HybridLayers, sambay_kinds

    if getattr(c, "mb_per_layer", 2) != 2 or c.num_hidden_layers % 2 \
            or not getattr(c, "tie_word_embeddings", True):
        raise ValueError("phi4flash: mb_per_layer 2, an even depth and a "
                         "tied embedding are what the list of kinds and "
                         "the file lay out")
    return TransformerSpec(
        dim=c.hidden_size, hidden_dim=c.intermediate_size,
        n_layers=c.num_hidden_layers, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_key_value_heads, vocab_size=c.vocab_size,
        seq_len=seq_len, weights_float_type=target,
        norm_eps=float(getattr(c, "layer_norm_eps", 1e-5)),
        hybrid=HybridLayers(sambay_kinds(c.num_hidden_layers),
                            int(c.sliding_window), 2 * c.hidden_size, 16, 4,
                            -(-c.hidden_size // 16)))


def convert_hf(model_path: str, target: str, out: str | None = None,
               seq_len: int = 2048, ckpt=None) -> str:
    ckpt = ckpt or HFCheckpoint(model_path)
    spec = ckpt.spec(_FT[target], seq_len)
    name = os.path.basename(os.path.normpath(model_path))
    out = out or f"dllama_{name}_{target}.bin"
    with open(out, "wb") as f:
        f.write(spec.header())
        _write_tensor(f, spec, "tok_embedding",
                      ckpt.tensor_by_name("tok_embedding", None, spec))
        # a latent spec's layers, of two kinds, in the file's own order
        i = -1
        for stack, _, entries in (spec.layer_plans() if spec.planned
                                  else ()):
            # a mixer-kinds spec has two runs a layer: its FFN's follows
            i += not ((spec.mixers or spec.kda) and stack in ("", "dense"))
            for kind, name_, _, *e in entries:
                arr = ckpt.tensor_by_name(name_, i, spec,
                                          e[0] if e else None)
                if kind == "f32":
                    f.write(np.ascontiguousarray(
                        arr, dtype=np.float32).tobytes())
                else:
                    _write_matmul(f, spec, arr)
            print(f"🔶 wrote layer {i + 1}/{spec.n_layers}")
        for i in range(0 if spec.planned else spec.n_layers):
            # file order (models/spec.py): norms, attention, router, experts
            names = ([n for n, _ in spec.layer_norm_shapes()]
                     + [n for n, _ in spec.layer_matmul_shapes()]
                     + (["moe_gate"] if spec.n_experts else []))
            if spec.retention:            # the gate follows wo
                names.insert(names.index("wo") + 1, "w_gate")
            for name_ in names:
                _write_tensor(f, spec, name_,
                              ckpt.tensor_by_name(name_, i, spec))
            for e in range(spec.n_experts):
                for name_, _ in spec.expert_matmul_shapes():
                    _write_tensor(f, spec, name_,
                                  ckpt.tensor_by_name(name_, i, spec, e))
            print(f"🔶 wrote layer {i + 1}/{spec.n_layers}")
        _write_tensor(f, spec, "rms_final",
                      ckpt.tensor_by_name("rms_final", None, spec))
        f.write(b"\x00" * spec.rope_gap_bytes)
        _write_tensor(f, spec, "wcls", ckpt.tensor_by_name("wcls", None, spec))
    assert os.path.getsize(out) == spec.file_size()
    print(f"✅ {out}: {spec.file_size()} bytes")
    return out


def _write_tensor(f, spec: TransformerSpec, name: str, arr: np.ndarray) -> None:
    if _is_f32(name):
        f.write(np.ascontiguousarray(arr, dtype=np.float32).tobytes())
    else:
        _write_matmul(f, spec, arr)


def export_tokenizer(model_file: str, out: str = "tokenizer.bin") -> str:
    """sentencepiece tokenizer.model -> llama2.c tokenizer.bin."""
    from sentencepiece import SentencePieceProcessor  # optional dep

    from .io.tokenizer import write_tokenizer

    sp = SentencePieceProcessor(model_file=model_file)
    pieces, scores = [], []
    for i in range(sp.vocab_size()):
        piece = sp.id_to_piece(i).replace("▁", " ").encode("utf-8")
        pieces.append(piece)
        scores.append(float(sp.get_score(i)))
    write_tokenizer(out, pieces, scores)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("model_path")
    ap.add_argument("target", choices=sorted(_FT))
    ap.add_argument("--out")
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--source", choices=["meta", "hf"], default="meta",
                    help="hf: a HuggingFace checkpoint by its model_type "
                         "(deepseek_v3: bfloat16 checkpoints only; the FP8 "
                         "block-scaled release is not read)")
    ap.add_argument("--export-tokenizer", metavar="SP_MODEL",
                    help="also write tokenizer.bin from a sentencepiece model")
    args = ap.parse_args(argv)
    if args.source == "hf":
        convert_hf(args.model_path, args.target, args.out, args.seq_len)
    else:
        convert_meta(args.model_path, args.target, args.out, args.seq_len)
    if args.export_tokenizer:
        export_tokenizer(args.export_tokenizer)


if __name__ == "__main__":
    main()
