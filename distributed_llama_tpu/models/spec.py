"""Model spec + .bin file layout accounting.

File format parity with the reference: 28-byte header of 7 little-endian int32
{dim, hiddenDim, nLayers, nHeads, nKvHeads, vocabSize, seqLen} (reference
src/transformer.hpp:23-31, src/transformer.cpp:52-95), then tensors in the
fixed order written by converter/converter.py:85-151 and read by
src/transformer.cpp:298-352:

  tok_embeddings (F32, vocab x dim)
  per layer: attention_norm (F32 dim), ffn_norm (F32 dim),
             wq (dim x dim), wk (kvDim x dim), wv (kvDim x dim), wo (dim x dim),
             w1 (hidden x dim), w2 (dim x hidden), w3 (hidden x dim)
             [all in weightsFloatType]
  norm (F32 dim)
  <gap: 2 * seqLen * headSize/2 f32 — the legacy freq_cis region, skipped>
  output/wcls (vocab x dim, weightsFloatType)

Matmul weights are stored row-major (d, n): out[i] = sum_j w[i, j] * x[j]
(reference src/funcs.cpp:269-299 semantics).

Expert models (``n_experts > 0``; OLMoE-style routed FFN, optional q/k-norm)
carry a VERSIONED header extension that no 28-byte-header file has: three
int32 {EXT_MAGIC (negative, where a 28-byte file holds its positive dim),
EXT_VERSION, number of ints that follow}, then the seven base ints and
{nExperts, nActiveExperts, qkNorm}: 52 bytes. A spec with all three at
their defaults writes and reads the 28-byte header byte for byte as before.
``hidden_dim`` is then the width of ONE expert, and the per-layer order is:

  attention_norm, ffn_norm (F32 dim),
  [q_norm (F32 dim), k_norm (F32 kvDim)   -- only if qkNorm]
  wq, wk, wv, wo                          [weightsFloatType]
  router (F32, nExperts x dim)
  per expert e: w1_e (hidden x dim), w2_e (dim x hidden), w3_e (hidden x dim)
                                          [weightsFloatType]

In the param tree the expert tensors are stacked as ``moe_w1`` / ``moe_w2`` /
``moe_w3`` (L, E, d, n), the router as ``moe_gate`` (L, E, dim) float32, the
q/k-norm gains as ``rms_q`` (L, dim) and ``rms_k`` (L, kvDim).

Extension VERSION 3 (72 bytes: the thirteen ints of version 2, one more
and two float64) is written only by a spec that needs one of its fields, so every file
of versions 0 (28 bytes) and 2 still reads and writes byte for byte:
{attnKind (0 softmax, 1 power retention of degree 2), ropeTheta,
normEps}, and ``qkNorm`` may be 2: ONE
gain of head size shared by all heads (Qwen3's per-head q/k-norm) where 1
is a gain over the whole projection (OLMoE's). A retention spec (every
layer the same kind; a per-layer list of kinds can take this field's
place) keeps a recurrent state of fixed size in place of a KV cache
(``ops/retention.py``) and carries one more tensor a layer, the gate:

  attention_norm, ffn_norm, q_norm (F32 headSize), k_norm (F32 headSize),
  wq, wk, wv, wo                          [weightsFloatType]
  w_gate (F32, nKvHeads x dim)
  w1, w2, w3                              [weightsFloatType]

``w_gate`` is (L, nKvHeads, dim) float32 in the param tree.
"""

from __future__ import annotations

import dataclasses
import struct

from ..ops.quants import FloatType, batch_bytes

HEADER_STRUCT = struct.Struct("<7i")
HEADER_BYTES = HEADER_STRUCT.size  # 28
# extended header: magic, version, count of the ints that follow
EXT_MAGIC = -0x444C4D58   # "DLMX"; a 28-byte header starts with dim > 0
EXT_VERSION = 2
EXT_STRUCT = struct.Struct("<13i")
EXT3_VERSION = 3
EXT3_STRUCT = struct.Struct("<14i2d")   # ... attnKind, theta, eps
MAX_HEADER_BYTES = EXT3_STRUCT.size
ATTN_KINDS = ("softmax", "retention")


@dataclasses.dataclass(frozen=True)
class TransformerSpec:
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    weights_float_type: FloatType = FloatType.F32
    buffer_float_type: FloatType = FloatType.F32
    # routed experts (0 = a dense SwiGLU FFN), experts kept per token, and
    # RMSNorm gains over the whole q / k projection before RoPE
    n_experts: int = 0
    n_active_experts: int = 0
    qk_norm: bool = False
    # ONE q/k-norm gain of head size shared by all heads (Qwen3) instead of
    # a gain over the whole projection (OLMoE); implies qk_norm
    qk_norm_per_head: bool = False
    # what every layer's attention is: "softmax" over a KV cache, or power
    # "retention" (degree 2: ops/retention.py) over a recurrent state
    attn_kind: str = "softmax"
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5

    def __post_init__(self):
        if self.attn_kind not in ATTN_KINDS:
            raise ValueError(f"attn_kind={self.attn_kind!r}: expected one "
                             f"of {ATTN_KINDS}")
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError("qk_norm_per_head says how qk_norm's gains "
                             "lie: set qk_norm too")
        if self.retention and (self.n_experts or self.head_size % 2):
            raise ValueError("a retention spec has a dense FFN and an even "
                             "head size")
        if bool(self.n_experts) != bool(self.n_active_experts) or not (
                0 <= self.n_active_experts <= self.n_experts):
            raise ValueError(
                f"n_experts={self.n_experts} / n_active_experts="
                f"{self.n_active_experts}: both 0 (dense FFN) or "
                f"0 < active <= experts")

    @property
    def retention(self) -> bool:
        """Whether the layers keep a recurrent state in place of a KV cache."""
        return self.attn_kind == "retention"

    @property
    def header_version(self) -> int:
        """0 (the 28-byte header), 2 or 3: the lowest that holds the spec."""
        if (self.retention or self.qk_norm_per_head
                or self.rope_theta != 10000.0 or self.norm_eps != 1e-5):
            return EXT3_VERSION
        return EXT_VERSION if (self.n_experts or self.qk_norm) else 0

    @property
    def extended(self) -> bool:
        """True when the file carries a header extension."""
        return self.header_version > 0

    @property
    def header_bytes(self) -> int:
        return {0: HEADER_BYTES, EXT_VERSION: EXT_STRUCT.size,
                EXT3_VERSION: EXT3_STRUCT.size}[self.header_version]

    @property
    def head_size(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_dim(self) -> int:
        return (self.dim * self.n_kv_heads) // self.n_heads

    @property
    def kv_mul(self) -> int:
        """GQA group size: queries per kv head (reference transformer-tasks.cpp:214)."""
        return self.n_heads // self.n_kv_heads

    # -- header ------------------------------------------------------------

    @classmethod
    def from_header(cls, raw: bytes, weights_float_type=FloatType.F32,
                    buffer_float_type=FloatType.F32) -> "TransformerSpec":
        ext, more = (0, 0, 0), {}
        if struct.unpack_from("<i", raw)[0] == EXT_MAGIC:
            version, count = struct.unpack_from("<2i", raw, 4)
            layout = {(EXT_VERSION, 10): EXT_STRUCT,
                      (EXT3_VERSION, 13): EXT3_STRUCT}.get((version, count))
            if layout is None:
                raise ValueError(f"unknown header extension version "
                                 f"{version} ({count} ints)")
            if len(raw) < layout.size:
                raise ValueError("extended header truncated")
            _, _, _, *ints = layout.unpack(raw[:layout.size])
            base, ext = ints[:7], ints[7:10]
            if version == EXT3_VERSION:
                kind, theta, eps = ints[10:]
                if not 0 <= kind < len(ATTN_KINDS):
                    raise ValueError(f"unknown attention kind {kind}")
                more = dict(attn_kind=ATTN_KINDS[kind],
                            qk_norm_per_head=ext[2] == 2,
                            rope_theta=float(theta), norm_eps=float(eps))
        else:
            base = HEADER_STRUCT.unpack(raw[:HEADER_BYTES])
        dim, hidden, n_layers, n_heads, n_kv, vocab, seq = base
        # llama2.c-style exports flag a shared classifier with a negative
        # vocab size; the reference takes abs() (transformer.cpp:73)
        return cls(dim, hidden, n_layers, n_heads, n_kv, abs(vocab), seq,
                   FloatType(weights_float_type), FloatType(buffer_float_type),
                   n_experts=ext[0], n_active_experts=ext[1],
                   qk_norm=bool(ext[2]), **more)

    def header(self) -> bytes:
        base = (self.dim, self.hidden_dim, self.n_layers, self.n_heads,
                self.n_kv_heads, self.vocab_size, self.seq_len)
        if not self.extended:
            return HEADER_STRUCT.pack(*base)
        if self.header_version == EXT_VERSION:
            return EXT_STRUCT.pack(EXT_MAGIC, EXT_VERSION, 10, *base,
                                   self.n_experts, self.n_active_experts,
                                   int(self.qk_norm))
        return EXT3_STRUCT.pack(
            EXT_MAGIC, EXT3_VERSION, 13, *base, self.n_experts,
            self.n_active_experts, int(self.qk_norm) + self.qk_norm_per_head,
            ATTN_KINDS.index(self.attn_kind), self.rope_theta, self.norm_eps)

    # -- per-tensor shapes (d, n) in file order ----------------------------

    def layer_matmul_shapes(self) -> list[tuple[str, tuple[int, int]]]:
        """One layer's matmul tensors that exist ONCE a layer, in file
        order: an expert spec has the four attention tensors here and its
        FFN under ``expert_matmul_shapes``."""
        d, h, kv = self.dim, self.hidden_dim, self.kv_dim
        attn = [("wq", (d, d)), ("wk", (kv, d)), ("wv", (kv, d)),
                ("wo", (d, d))]
        if self.n_experts:
            return attn
        return attn + [("w1", (h, d)), ("w2", (d, h)), ("w3", (h, d))]

    def expert_matmul_shapes(self) -> list[tuple[str, tuple[int, int]]]:
        """The tensors of ONE routed expert, in file order; a layer holds
        ``n_experts`` of each, stacked (L, E, d, n) in the param tree.
        Empty for a dense spec."""
        if not self.n_experts:
            return []
        d, h = self.dim, self.hidden_dim
        return [("moe_w1", (h, d)), ("moe_w2", (d, h)), ("moe_w3", (h, d))]

    def matmul_shape_counts(self) -> list[tuple[tuple[int, int], int]]:
        """((d, n), copies per layer) of every per-layer matmul tensor:
        what a size or layout gate walks."""
        return ([(shape, 1) for _, shape in self.layer_matmul_shapes()]
                + [(shape, self.n_experts)
                   for _, shape in self.expert_matmul_shapes()])

    def layer_norm_shapes(self) -> list[tuple[str, int]]:
        """One layer's float32 gain vectors, in file order."""
        norms = [("rms_att", self.dim), ("rms_ffn", self.dim)]
        if self.qk_norm_per_head:
            norms += [("rms_q", self.head_size), ("rms_k", self.head_size)]
        elif self.qk_norm:
            norms += [("rms_q", self.dim), ("rms_k", self.kv_dim)]
        return norms

    @property
    def gate_shape(self) -> tuple[int, int] | None:
        """A retention layer's ``w_gate`` (float32, after ``wo`` in the
        file): one row a KV head. None for a softmax spec."""
        return (self.n_kv_heads, self.dim) if self.retention else None

    def matmul_bytes(self, shape: tuple[int, int]) -> int:
        dd, nn = shape
        return batch_bytes(self.weights_float_type, nn, dd)

    @property
    def rope_gap_bytes(self) -> int:
        """Legacy freq_cis_real+imag region (transformer.cpp:338-339)."""
        return 2 * (self.seq_len * self.head_size // 2) * 4

    def block_bytes(self) -> int:
        b = sum(n * 4 for _, n in self.layer_norm_shapes())  # always F32
        b += self.n_experts * self.dim * 4                   # router, F32
        if self.retention:
            b += self.n_kv_heads * self.dim * 4              # w_gate, F32
        for shape, copies in self.matmul_shape_counts():
            b += copies * self.matmul_bytes(shape)
        return b

    def file_size(self) -> int:
        """Byte-exact total, mirroring the check at transformer.cpp:344-348."""
        b = self.header_bytes
        b += self.vocab_size * self.dim * 4          # tok_embeddings, F32
        b += self.n_layers * self.block_bytes()
        b += self.dim * 4                            # rmsFinal, F32
        b += self.rope_gap_bytes
        b += self.matmul_bytes((self.vocab_size, self.dim))  # wcls
        return b
