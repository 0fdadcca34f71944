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

Extension VERSION 4 (the fourteen ints and two float64 of version 3, then
sixteen ints and seven float64: ``EXT4_STRUCT``) is written only by a spec
that sets one of the grouped records below, so files of versions 0, 2 and 3
read and write byte for byte: {qRank, kvRank, nopeDim, ropeDim, vDim}
(``LatentAttn``: low-rank q, and a latent KV plane of kvRank + ropeDim values
a position in place of K and V), {denseLayers, denseHidden, sharedExperts,
held, offset} (``ExpertLayout``: leading dense layers and their width,
shared experts, and WHICH routed experts this file holds: a count and an
offset into the router's width ``nExperts``), {scoring, groups, groupsKept,
renormalise, bias; scale} (``Router``), {scaled; factor, originalPositions,
betaFast, betaSlow, mscale, mscaleAllDim} (``RopeScaling``: YaRN). Such a
file has no legacy rope gap, and its layers are of two kinds, dense first
(``layer_plans`` is the file order, the one place that says it):

  attention_norm, ffn_norm, q_a_norm (F32 qRank), kv_a_norm (F32 kvRank),
  wq_a (qRank x dim), wq_b (nHeads (nope + rope) x qRank),
  wkv_a ((kvRank + rope) x dim), wkv_b (nHeads (nope + v) x kvRank),
  wo (dim x nHeads v)                     [weightsFloatType]
  a dense layer:   w1, w2, w3 at denseHidden
  an expert layer: router (F32, nExperts x dim), [router_bias (F32 nExperts)],
                   sh_w1, sh_w2, sh_w3 at sharedExperts x hidden,
                   per HELD expert e: w1_e, w2_e, w3_e

In the param tree the expert layers' tensors are the top-level stacks (their
leading axis counts expert layers only; ``moe_w*`` are (L_e, held, d, n),
``moe_bias`` (L_e, nExperts)) and the leading dense layers' are the same
keys under ``params["dense"]``.

Extension VERSION 5 (version 4's values, then eight ints {window, dInner,
dState, dConv, dtRank, three reserved} and 128 bytes, one a layer: its index
into ``LAYER_KINDS``, 255 past the last layer) is written only by a spec
that sets ``hybrid`` (``HybridLayers``: a per-layer list of kinds in place
of the one ``attnKind``), so files of versions 0, 2, 3 and 4 read and write
byte for byte. Such a file has no rope gap (the model has no positional
encoding), LayerNorm with gain AND bias where the others have RMSNorm, and
``layer_plans`` says each kind's tensors in file order; in the param tree
each kind is a stack of its own under ``params[kind]`` (its leading axis
counts the layers of that kind), the final norm's gain is followed by its
bias (``rms_final_b``), and the classifier is a copy of the embedding in
the weights' float type (tied).

Extension VERSION 6 (version 4's values, then four ints {streams,
sinkhornIters, two reserved} and three float64 {eps, clampMin, clampMax}:
``HyperConnections``) is written only by a spec that sets ``hyper``, so
files of versions 0, 2, 3, 4 and 5 read and write byte for byte. A layer's
residual path is then ``streams`` parallel streams mixed by per-token
coefficients (``ops/hyper.py``), and each layer carries, after its norms and
before its matmul tensors, six float32 tensors, three a sub-layer
(attention's, then the feed-forward's):

  hc_att_phi ((2 n + n^2) x (n dim): rows [pre (n) | post (n) | res (n^2,
             row-major)], one row an output as every matmul weight here),
  hc_att_gate (3: a_pre, a_post, a_res), hc_att_bias (2 n + n^2: b_pre,
             b_post, B_res row-major), hc_ffn_phi, hc_ffn_gate, hc_ffn_bias

Extension VERSION 7 (version 4's values, then nine ints {window, headSize,
gate, fullHeads, slidingHeads, fullRotary, slidingRotary, fullScaled,
slidingScaled}, fourteen float64 {fullTheta, slidingTheta,
then each kind's YaRN six: factor, originalPositions, betaFast, betaSlow,
mscale, mscaleAllDim} and 128 bytes, one a layer: its index into
``MIXER_KINDS``, 255 past the last layer) is written only by a spec that
sets ``mixers`` (``MixerKinds``: a per-layer list of grouped-query attention
kinds, each with a head count and a RoPE of its own, COMBINED with version
4's ``ExpertLayout`` / ``Router``), so files of every earlier version read
and write byte for byte. Such a file has no rope gap, and a layer is two
runs of tensors, its mixer's (``params[kind]``: a stack a kind, so ``wq`` /
``wo`` may differ in shape by kind) and then its FFN's (the leading dense
layers' under ``params["dense"]``, the others' the top-level stacks, as in
version 4):

  attention_norm (F32 dim), wq (heads_k head x dim), wk, wv (kvHeads head x
  dim), wo (dim x heads_k head)          [weightsFloatType]
  [w_hgate (F32, heads_k x dim)           -- only if gate]
  ffn_norm (F32 dim), then a dense layer's w1, w2, w3 at denseHidden, or an
  expert layer's router ... experts as in version 4 (or, with no experts,
  w1, w2, w3 at hiddenDim)

Extension VERSION 8 (version 7's values, then five ints {valueHeadSize,
fullKvHeads, slidingKvHeads, fullSink, slidingSink} and one float64
{valueScale}) is written only by a mixer-kinds spec that sets one of them
(MiMo-V2-Flash's layout: V heads narrower than K heads, a KV head count a
layer kind, a learned softmax sink a query head, the attention output
scaled), so a version-7 file reads and writes byte for byte. A kind's ``wk``
is (kvHeads_k head x dim), ``wv`` (kvHeads_k valueHead x dim), ``wo`` (dim x
heads_k valueHead), and a kind with a sink has, after ``wo`` (and
``w_hgate``), ``sink`` (F32, heads_k).

Extension VERSION 9 (version 6's values, ``HyperConnections`` all zero
where the spec has one stream, then eight ints {kvGroups, noiseHeads, gate,
window, activation (0 silu, 1 polynorm), three reserved}, three float64
{activationScale, activationClamp, streamClamp} and 128 bytes, a layer's
kind each (``MIXER_KINDS``; all 255: every layer "full")) is written only
by a latent spec that sets one of them (Motif-3-Beta's layout: ``wkv_b``
expands the plane to ``kvGroups`` heads that ``nHeads / kvGroups`` query
heads share, the last ``noiseHeads`` of a group are subtracted from its
others after the softmax, an elementwise gate on the attention output, a
ring of ``window`` latent rows a "sliding" layer, PolyNorm in the FFN), so
files of every earlier version read and write byte for byte. A layer then
carries, after its norms (and the residual path's tensors):

  w_lambda (F32, signalHeads x dim)   where noiseHeads > 0
  pn_w     (F32, 4: w0, w1, w2, b)    where the activation is PolyNorm
  wq_a, wq_b, wkv_a, wkv_b (kvGroups (nope + v) x kvRank),
  wg (signalHeads v x dim)            where gate, wo (dim x signalHeads v)

with signalHeads = nHeads - kvGroups noiseHeads.

Extension VERSION 10 (version 4's values, then twelve ints {ssmHeads,
ssmHeadDim, ssmGroups, ssmState, ssmConv, ssmChunk, headSize, sharedHidden,
activation (``ACTIVATIONS``), gated, two reserved} and 128 bytes, a layer's
kind each: its index into ``SSD_KINDS``, 255 past the last layer) is written
only by a spec that sets ``ssd`` (``SsdLayers``: a layer is ONE mixer under
one norm and one residual add: a Mamba-2 (SSD) mixer, grouped-query
attention with no positional encoding, or routed experts with version 4's
``ExpertLayout`` / ``Router``), so files of every earlier version read and
write byte for byte. Such a file has no rope gap, and a layer is one run of
tensors in its kind's stack (``params[kind]``):

  "mamba2":  norm (F32 dim), in_zx ((dInner + convDim) x dim: rows [z | x |
             B | C]) [weightsFloatType], in_dt (F32, ssmHeads x dim),
             conv_w (F32, ssmConv x convDim), conv_b (F32 convDim),
             dt_bias, a_log, d_skip (F32 ssmHeads), norm_g (F32 dInner),
             out_proj (dim x dInner) [weightsFloatType]
  "full":    norm, wq (nHeads headSize x dim), wk, wv (nKvHeads headSize x
             dim), wo (dim x nHeads headSize)   [weightsFloatType]
  "experts": norm, router (F32, nExperts x dim), [router_bias (F32
             nExperts)], sh_w1 (sharedHidden x dim), sh_w2 (dim x
             sharedHidden) where sharedHidden > 0, per HELD expert e: w1_e
             (hidden x dim), w2_e (dim x hidden)   [weightsFloatType]: an
             expert that is not gated has no w3

with dInner = ssmHeads ssmHeadDim and convDim = dInner + 2 ssmGroups
ssmState; the norm's gain is ``rms_att`` in every kind's stack.

Extension VERSION 11 (version 9's values, its 128 bytes now an index into
``LATENT_KINDS``, then eight ints {kdaHeads, kdaHeadDim, kdaConv, headGate,
ffnLimits, three reserved} and one float64 {kdaLowerBound}) is
written only by a latent spec that sets ``kda`` (``KdaLayers``:
Ling-3.0-flash's layout, Kimi-Delta-Attention layers beside latent
attention), so files of every earlier version read and write byte for byte.
A layer's kind is then "kda" (a delta-rule state of kdaHeads x kdaHeadDim x
kdaHeadDim float32 a sequence, ``ops/kda.py``) or "full" (latent attention,
where ``qRank`` may be 0: ONE matrix ``wq`` and no ``q_a_norm``, and with
``headGate`` a sigmoid gate a HEAD on the heads' outputs). Such a file lays
a layer as two runs of tensors, as version 7 does: its mixer's
(``params[kind]``) and then its FFN's (``params["dense"]`` or the top-level
stacks), with kd = kdaHeads kdaHeadDim:

  "kda":   attention_norm (F32 dim), in_qkvag (5 kd x dim: rows [q | k | v |
           a | g]) [weightsFloatType], conv_w (F32, kdaConv x 3 kd: the taps
           of q, k and v's depthwise causal convolutions), a_log (F32
           kdaHeads), dt_bias (F32 kd), w_beta (F32, kdaHeads x dim), norm_g
           (F32 kd), wo (dim x kd) [weightsFloatType]
  "full":  attention_norm, kv_a_norm (F32 kvRank), [q_a_norm (F32 qRank),
           wq_a, wq_b | wq (nHeads (nope + rope) x dim) where qRank is 0],
           wkv_a, wkv_b, [wg], wo [weightsFloatType], [w_hgate (F32, nHeads x
           dim) where headGate]
  FFN:     ffn_norm (F32 dim), then a dense layer's w1, w2, w3 at
           denseHidden, or an expert layer's [ffn_limit (F32, 2: the clamp
           of the routed experts' and of the shared expert's gate and up
           projections, 0: none) where ffnLimits], router ... experts as in
           version 4
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
EXT4_VERSION = 4
EXT4_STRUCT = struct.Struct("<14i2d16i7d")
EXT5_VERSION = 5
EXT5_STRUCT = struct.Struct("<14i2d16i7d8i128B")
EXT6_VERSION = 6
EXT6_STRUCT = struct.Struct("<14i2d16i7d4i3d")
EXT7_VERSION = 7
EXT7_STRUCT = struct.Struct("<14i2d16i7d9i14d128B")
EXT8_VERSION = 8
EXT8_STRUCT = struct.Struct("<14i2d16i7d9i14d128B5i1d")
EXT9_VERSION = 9
EXT9_STRUCT = struct.Struct("<14i2d16i7d4i3d8i3d128B")
EXT10_VERSION = 10
EXT10_STRUCT = struct.Struct("<14i2d16i7d12i128B")
EXT11_VERSION = 11
EXT11_STRUCT = struct.Struct("<14i2d16i7d4i3d8i3d128B8i1d")
MAX_HEADER_BYTES = max(EXT8_STRUCT.size, EXT9_STRUCT.size,
                       EXT10_STRUCT.size, EXT11_STRUCT.size)
ACTIVATIONS = ("silu", "polynorm", "relu2")
HC_SUBLAYERS = ("att", "ffn")
ATTN_KINDS = ("softmax", "retention")
# what a layer of a ``HybridLayers`` spec mixes with, and what it caches for
# one sequence: a recurrent state of fixed size, a ring of the last
# ``window`` positions' K / V, every position's K / V (the ONE growing
# cache, in pages under ``serve``), or nothing of its own
LAYER_KINDS = ("mamba", "swa", "full", "gmu", "xattn")
CACHE_OF_KIND = {"mamba": "state", "swa": "window", "full": "pages",
                 "gmu": None, "xattn": None}
ROUTER_SCORINGS = ("softmax", "sigmoid")
# what a layer of a ``MixerKinds`` spec attends over: every position (K / V
# of its own, in pages under ``serve``) or the last ``window`` (a ring)
MIXER_KINDS = ("full", "sliding")
# ... of a latent spec: those two, or a Kimi-Delta-Attention layer (a
# recurrent state of fixed size; header version 11)
LATENT_KINDS = (*MIXER_KINDS, "kda")
# what a layer of an ``SsdLayers`` spec IS (one mixer, no FFN beside it),
# and what it caches for one sequence
SSD_KINDS = ("mamba2", "full", "experts")


@dataclasses.dataclass(frozen=True)
class LatentAttn:
    """Latent attention: q through a low rank, and ONE cached plane of
    ``kv_rank + rope_dim`` values a position that all heads read (its first
    ``kv_rank`` columns are also the values). Head sizes are stated, not
    derived from ``dim // n_heads``."""
    q_rank: int
    kv_rank: int
    nope_dim: int    # a head's q / k part that carries no position
    rope_dim: int    # ... and the part RoPE rotates (k's is shared by heads)
    v_dim: int
    # header version 9 (the defaults: versions 4 and 6 as they were).
    # ``wkv_b`` expands the plane to ``kv_groups`` heads of [k_nope | v]
    # that n_heads / kv_groups query heads share (0: a head its own); of a
    # group's heads the LAST ``noise_heads`` are noise heads: finished
    # softmax heads that are subtracted, times a per-token lambda
    # (``w_lambda``), from each of the group's other (signal) heads, which
    # alone reach ``wo``; ``gate``: the signal heads' output times an
    # elementwise sigmoid of the normed layer input (``wg``) before ``wo``;
    # ``kinds[i]`` is layer i's kind, "full" or "sliding" (``MIXER_KINDS``;
    # empty: every layer "full"): a sliding layer sees the last ``window``
    # positions and keeps a RING of their latent rows in place of a plane
    kv_groups: int = 0
    noise_heads: int = 0
    gate: bool = False
    kinds: tuple = ()
    window: int = 0
    # header version 11 (a spec with ``kda``): ``q_rank`` may be 0 (q is ONE
    # matrix ``wq``, no rank and no norm between), a kind may be "kda"
    # (``LATENT_KINDS``), and ``head_gate``: the heads' outputs times a
    # sigmoid of the normed layer input a HEAD (``w_hgate``, float32)
    head_gate: bool = False

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def width(self) -> int:
        """Values cached a position and layer: [c_kv | k_rope]."""
        return self.kv_rank + self.rope_dim

    @property
    def widened(self) -> bool:
        """Whether the record states what a version-4 header has no field
        for."""
        return bool(self.kv_groups or self.noise_heads or self.gate
                    or self.kinds or self.window or self.head_gate)

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)


@dataclasses.dataclass(frozen=True)
class Activation:
    """What an FFN applies between its input and output matrices. Gated
    (the default): ``w2(act(w1 x) * w3 x)``, ``act`` on the gate projection
    before the product with the up projection: "silu", or "polynorm"
    (arXiv:2411.03884):
    ``scale * (w0 n(z^3) + w1 n(z^2) + w2 n(z) + clip(b, -clamp, clamp))``
    with ``n(u) = u / sqrt(mean(u^2) + eps)`` over the FFN's own width and
    the four numbers a layer's ``pn_w`` (``clamp`` 0: the bias as it is).
    ``gated=False`` (header version 10): ONE up matrix and no product,
    ``w2(act(w1 x))``, with "relu2": ``relu(z)^2``. ``limits`` (header
    version 11): an expert layer carries ``ffn_limit`` (2,), the clamp L of
    its routed experts and of its shared expert, ``w2(act(min(w1 x, L)) *
    clip(w3 x, -L, L))``; L = 0 is no clamp (ops/linear.gated_product)."""
    kind: str = "silu"
    scale: float = 1.0
    clamp: float = 0.0
    gated: bool = True
    limits: bool = False


@dataclasses.dataclass(frozen=True)
class ExpertLayout:
    """Which layers are dense and which experts live here: ``dense_layers``
    leading layers have a SwiGLU of ``dense_hidden``; the rest route over
    ``n_experts`` and add ``shared`` always-on experts (one FFN of
    ``shared * hidden_dim``, of the spec's activation: a SwiGLU unless it
    says otherwise; an ``SsdLayers`` spec states that width itself,
    ``shared_hidden``); this file holds routed experts
    ``offset .. offset + held - 1`` of the router's width (held 0 = all)."""
    dense_layers: int = 0
    dense_hidden: int = 0
    shared: int = 0
    held: int = 0
    offset: int = 0


@dataclasses.dataclass(frozen=True)
class Router:
    """How an expert layer scores and chooses (ops/pallas_moe.route)."""
    scoring: str = "softmax"
    groups: int = 1          # experts are split into this many groups ...
    groups_kept: int = 1     # ... and chosen among the best so many
    renormalise: bool = False
    scale: float = 1.0
    bias: bool = False       # a (n_experts,) bias added for the CHOICE only


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN: frequencies blended between f and f / factor over the
    correction range, and the attention scale's m^2."""
    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class HybridLayers:
    """A per-layer list of kinds (SambaY's decoder-hybrid-decoder,
    arXiv:2507.06607; models/sambay.py runs it, models/reference_sambay.py
    states it). ``kinds[i]`` is layer i's mixer: "mamba" (selective state
    space, Mamba-1), "swa" (differential attention over the last ``window``
    positions, the current one included), "full" (differential attention,
    causal over every position: its K / V are the model's only growing
    cache), "gmu" (a gate on the memory ``m``: the scan output of the last
    Mamba layer before the first GMU) and "xattn" (differential
    cross-attention, own queries over the "full" layer's K / V). Every
    layer after the "full" one is a "gmu" or an "xattn": they hold no state
    of their own, so a prompt runs them at its last position alone."""
    kinds: tuple
    window: int
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    def layers_of(self, kind: str) -> tuple:
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    @property
    def full_layer(self) -> int:
        return self.kinds.index("full")

    @property
    def memory_layer(self) -> int | None:
        """The Mamba layer whose scan output the GMUs gate (None: no GMU)."""
        if "gmu" not in self.kinds:
            return None
        return max(i for i in self.layers_of("mamba")
                   if i < self.kinds.index("gmu"))


@dataclasses.dataclass(frozen=True)
class HyperConnections:
    """A residual path of ``streams`` parallel streams (manifold-constrained
    hyper-connections, arXiv:2512.24880; ops/hyper.py computes it,
    models/reference_hyper.py states it): each sub-layer reads a per-token
    mix of the streams and writes back through a per-token matrix that
    ``sinkhorn_iters`` alternating normalisations (each sum taken with
    ``eps`` added) project onto the doubly stochastic ones, from logits
    clamped to [clamp_min, clamp_max] (both infinite: no clamp, and none is
    computed). ``stream_clamp`` (header version 9; 0: none): the streams a
    sub-layer writes back are clipped to +- that."""
    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    stream_clamp: float = 0.0

    @property
    def clamped(self) -> bool:
        import math

        return not (math.isinf(self.clamp_min) and math.isinf(self.clamp_max))

    @property
    def coefficients(self) -> int:
        """Per-token coefficients of one sub-layer: n + n + n^2."""
        return self.streams * (2 + self.streams)


@dataclasses.dataclass(frozen=True)
class MixerKind:
    """One kind of grouped-query attention layer: its query heads, its KV
    heads (0: the spec's ``n_kv_heads``), its RoPE: base, how many leading
    dimensions of a head it rotates (0: all of them), and YaRN or none; and
    whether each query head has a learned ``sink``: one more column of its
    softmax that carries no value, so a row's weights sum to less than 1."""
    heads: int
    rope_theta: float = 10000.0
    rotary_dim: int = 0
    rope_scaling: RopeScaling | None = None
    kv_heads: int = 0
    sink: bool = False


@dataclasses.dataclass(frozen=True)
class MixerKinds:
    """A per-layer list of grouped-query softmax attention kinds (Laguna's
    layout; models/laguna.py runs it, models/reference_laguna.py states
    it). ``kinds[i]`` is layer i's mixer: "full" (causal over every
    position, K / V of ITS OWN) or "sliding" (the last ``window``
    positions, the current one included). A kind has its own head count
    and RoPE; the head SIZE is one, stated here (``dim // n_heads`` says
    nothing where the head count is a kind's). ``gate``: each head's output
    is multiplied by a sigmoid of the normed layer input (``w_hgate``)
    before ``wo``. ``v_head_size``: a V head's size where it is not a K
    head's (0: ``head_size``); ``value_scale``: what the attention output is
    multiplied by before ``wo``. The FFN of a layer is
    ``TransformerSpec.layout``'s and ``router``'s, as for a latent spec."""
    kinds: tuple
    window: int
    head_size: int
    full: MixerKind
    sliding: MixerKind
    gate: bool = False
    v_head_size: int = 0
    value_scale: float = 1.0

    @property
    def v_size(self) -> int:
        return self.v_head_size or self.head_size

    @property
    def widened(self) -> bool:
        """Whether the spec states what a version-7 header has no field
        for."""
        return bool(self.v_head_size or self.value_scale != 1.0
                    or any(k.kv_heads or k.sink
                           for k in (self.full, self.sliding)))

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    def of(self, kind: str) -> MixerKind:
        return self.full if kind == "full" else self.sliding

    def rotary(self, kind: str) -> int:
        return self.of(kind).rotary_dim or self.head_size


@dataclasses.dataclass(frozen=True)
class SsdLayers:
    """A per-layer list in which a layer is ONE mixer under one pre-norm
    and one residual add (Nemotron-H's layout; models/nemotron.py runs it,
    models/reference_nemotron.py states it). ``kinds[i]`` is layer i:
    "mamba2" (a Mamba-2 / SSD mixer, arXiv:2405.21060: ``heads`` heads of
    ``head_dim`` channels, a state (heads, head_dim, d_state) float32 a
    sequence, a SCALAR decay a head, B and C shared by the heads of one of
    ``groups`` groups, a causal depthwise convolution ``d_conv`` wide over
    [x | B | C] together, a gated RMSNorm in groups of d_inner / groups;
    a prompt runs the chunked form in chunks of ``chunk``), "full"
    (grouped-query softmax attention, causal, NO positional encoding, K / V
    of its own: ``n_heads`` heads of ``head_size`` over ``n_kv_heads``) or
    "experts" (``TransformerSpec.layout``'s and ``router``'s routed
    experts of ``hidden_dim`` and one shared expert of ``shared_hidden``,
    0: none). The list need not be periodic."""
    kinds: tuple
    heads: int
    head_dim: int
    groups: int
    d_state: int
    head_size: int
    d_conv: int = 4
    chunk: int = 128
    shared_hidden: int = 0

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: [x | B | C]."""
        return self.d_inner + 2 * self.groups * self.d_state

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)


@dataclasses.dataclass(frozen=True)
class KdaLayers:
    """The Kimi-Delta-Attention layers of a latent spec whose
    ``latent.kinds`` name some "kda" (arXiv:2510.26692; ops/kda.py has the
    recurrence, models/kda.py runs it, models/reference_kda.py states it):
    ``heads`` heads of ``head_dim`` key AND value channels, a state (heads,
    head_dim, head_dim) float32 a sequence and layer, depthwise causal
    convolutions ``d_conv`` wide over q, k and v (their last d_conv - 1
    inputs are state too), a per-channel decay exp(g) with ``g =
    lower_bound * sigmoid(exp(a_log) * (W_a h + dt_bias))`` in
    [exp(lower_bound), 1). How a prompt's chunked form is tiled
    (``ops/kda.CHUNK``) is the program's business and no field here."""
    heads: int
    head_dim: int
    d_conv: int = 4
    lower_bound: float = -5.0

    @property
    def width(self) -> int:
        """heads x head_dim: what each of q, k, v, the decay and the
        output gate is wide."""
        return self.heads * self.head_dim


def cache_lanes(head: int) -> int:
    """The last dim a mixer-kinds spec's cache gives a K or V head of
    ``head`` values: itself up to one 128-lane tile, whole tiles past it
    (192 lies in 256, the rest zeros). The chip tiles a last dim in 128
    lanes whatever the shape says, and the decode kernels copy whole tiles
    only (ops/pallas_head_major_attention.py)."""
    return head if head <= 128 else -(-head // 128) * 128


def sambay_kinds(n_layers: int) -> tuple:
    """The published pattern at ``n_layers`` (even, >= 8), L/2 = h: Mamba at
    even i <= h, window attention at odd i < h, the full layer at h + 1,
    then GMUs at even and cross-attention at odd i."""
    h = n_layers // 2
    return tuple("mamba" if i <= h and i % 2 == 0 else "swa" if i < h
                 else "full" if i == h + 1 else "gmu" if i % 2 == 0
                 else "xattn" for i in range(n_layers))


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
    # grouped records of header version 4 (None / defaults: as before)
    latent: LatentAttn | None = None
    layout: ExpertLayout = ExpertLayout()
    router: Router = Router()
    rope_scaling: RopeScaling | None = None
    # header version 5: a per-layer list of kinds (None: every layer is
    # what ``attn_kind`` / ``latent`` / ``layout`` say, the list's trivial
    # cases)
    hybrid: HybridLayers | None = None
    # header version 6: a residual path of more than one stream (None: the
    # plain ``x + F(x)``)
    hyper: HyperConnections | None = None
    # header version 7: a per-layer list of grouped-query attention kinds,
    # each with a head count and RoPE of its own, beside ``layout`` and
    # ``router`` (None: every layer is what the fields above say)
    mixers: MixerKinds | None = None
    # header version 9: what an FFN applies to its gate projection
    activation: Activation = Activation()
    # header version 10: a per-layer list whose layer is ONE mixer (Mamba-2,
    # attention or experts), beside ``layout`` and ``router``
    ssd: SsdLayers | None = None
    # header version 11: the Kimi-Delta-Attention layers of a latent spec
    # (``latent.kinds`` says which layers they are)
    kda: KdaLayers | None = None

    def __post_init__(self):
        if self.ssd is not None:
            self._check_ssd()
        elif self.kda is not None:
            self._check_kda()
        elif self.latent is not None and (
                self.latent.widened or self.activation != Activation()):
            self._check_latent_kinds()
        elif self.activation != Activation():
            raise ValueError("an activation other than SiLU is carried by "
                             "header version 9, a latent spec's, or 10, an "
                             "ssd spec's: set latent or ssd")
        if self.hybrid is not None:
            self._check_hybrid()
        if self.mixers is not None:
            self._check_mixers()
        if self.hyper is not None and (
                not self.latent or self.hyper.streams < 2
                or self.hyper.sinkhorn_iters < 1
                or not self.hyper.clamp_min < self.hyper.clamp_max):
            raise ValueError("a residual path of several streams is run by "
                             "the latent-attention forward (models/latent."
                             "py): set latent, at least 2 streams, at least "
                             "one Sinkhorn iteration and clamp_min < "
                             "clamp_max")
        if self.attn_kind not in ATTN_KINDS:
            raise ValueError(f"attn_kind={self.attn_kind!r}: expected one "
                             f"of {ATTN_KINDS}")
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError("qk_norm_per_head says how qk_norm's gains "
                             "lie: set qk_norm too")
        if self.retention and (self.n_experts or self.head_size % 2):
            raise ValueError("a retention spec has a dense FFN and an even "
                             "head size")
        lay = self.layout
        if self.router.scoring not in ROUTER_SCORINGS:
            raise ValueError(f"router scoring {self.router.scoring!r}: "
                             f"expected one of {ROUTER_SCORINGS}")
        if (lay != ExpertLayout() or self.router != Router()) and not (
                self.n_experts):
            raise ValueError("an expert layout or a router kind says how an "
                             "expert spec's layers lie: set n_experts too")
        if self.n_experts and (
                not 0 <= lay.dense_layers < self.n_layers
                or bool(lay.dense_layers) != bool(lay.dense_hidden)
                or lay.offset + (lay.held or self.n_experts) > self.n_experts
                or self.n_experts % self.router.groups
                or not 0 < self.router.groups_kept <= self.router.groups):
            raise ValueError(f"{lay} / {self.router} do not fit n_layers="
                             f"{self.n_layers}, n_experts={self.n_experts}")
        if self.latent and (self.retention or self.qk_norm
                            or self.latent.rope_dim % 2):
            raise ValueError("a latent-attention spec is softmax attention "
                             "without q/k-norm, with an even rope_dim")
        if not (self.latent or self.mixers or self.ssd) and (
                lay != ExpertLayout() or self.rope_scaling
                or self.router != Router()):
            raise ValueError("an expert layout, a router kind and a RoPE "
                             "scaling are run by the latent-attention "
                             "forward (models/latent.py), the mixer-kinds "
                             "forward (models/laguna.py) and the ssd "
                             "forward (models/nemotron.py) only: set "
                             "latent or mixers (or ssd)")
        if self.latent and not self.n_experts:
            raise ValueError("a latent-attention spec has expert layers "
                             "after its leading dense ones: set n_experts")
        if bool(self.n_experts) != bool(self.n_active_experts) or not (
                0 <= self.n_active_experts <= self.n_experts):
            raise ValueError(
                f"n_experts={self.n_experts} / n_active_experts="
                f"{self.n_active_experts}: both 0 (dense FFN) or "
                f"0 < active <= experts")

    def _check_kda(self) -> None:
        kd, la, act = self.kda, self.latent, self.activation
        kinds = tuple(la.kinds) if la else ()
        if (la is None or len(kinds) != self.n_layers or len(kinds) > 128
                or set(kinds) != {"kda", "full"}):
            raise ValueError("kda: a latent spec whose latent.kinds name "
                             "\"kda\" and \"full\" layers (some of each, no "
                             "\"sliding\"), one for each of n_layers (at most "
                             "128)")
        if (self.hybrid or self.hyper or self.mixers or la.kv_groups
                or la.noise_heads or la.window or la.q_rank < 0
                or min(kd.heads, kd.head_dim, kd.d_conv - 1) < 1
                or not kd.lower_bound < 0):
            raise ValueError("kda: positive sizes and a negative lower "
                             "bound, beside plain latent attention (a head "
                             "its own KV group, no noise heads, no rings, "
                             "one residual stream)")
        if act != Activation(limits=act.limits):
            raise ValueError(f"activation {act}: a kda spec's FFN is a gated "
                             f"SiLU, with per-layer limits or without")

    def _check_latent_kinds(self) -> None:
        la, act = self.latent, self.activation
        if la.head_gate or la.q_rank < 1 or act.limits:
            raise ValueError("latent: a head-wise gate, q_rank 0 and "
                             "per-layer FFN limits are header version 11's: "
                             "set kda")
        groups = la.kv_groups or self.n_heads
        kinds = tuple(la.kinds)
        if (self.n_heads % groups or la.noise_heads not in (0, 1)
                or la.noise_heads >= self.n_heads // groups
                or min(la.kv_groups, la.window) < 0):
            raise ValueError(
                f"latent: n_heads={self.n_heads} in kv_groups={groups} "
                f"groups of more than one head, of which the last may be a "
                f"noise head (noise_heads={la.noise_heads}: 0 or 1; which "
                f"signal heads a second one would be subtracted from is "
                f"not stated)")
        if kinds and (len(kinds) != self.n_layers or len(kinds) > 128
                      or any(k not in MIXER_KINDS for k in kinds)):
            raise ValueError(f"latent.kinds: one of {MIXER_KINDS} for each "
                             f"of n_layers={self.n_layers} (at most 128)")
        if ("sliding" in kinds) != bool(la.window):
            raise ValueError("latent: a window where a layer is \"sliding\", "
                             "and there alone")
        if (act.kind not in ACTIVATIONS or act.clamp < 0
                or (act.kind == "silu" and act != Activation())):
            raise ValueError(f"activation {act}: one of {ACTIVATIONS}, a "
                             f"scale and a clamp >= 0 a PolyNorm's alone")

    def _check_ssd(self) -> None:
        sd, act = self.ssd, self.activation
        kinds = tuple(sd.kinds)
        if (len(kinds) != self.n_layers or len(kinds) > 128
                or any(k not in SSD_KINDS for k in kinds)):
            raise ValueError(f"ssd.kinds: one of {SSD_KINDS} for each of "
                             f"n_layers={self.n_layers} (at most 128)")
        if (self.hybrid or self.latent or self.hyper or self.mixers
                or self.retention or self.qk_norm or self.rope_scaling
                or self.layout.dense_layers):
            raise ValueError("an ssd spec's layer is one mixer (Mamba-2, "
                             "attention without positional encoding, or "
                             "experts): it combines with layout (no leading "
                             "dense layers) and router only")
        if (min(sd.heads, sd.head_dim, sd.groups, sd.d_state, sd.d_conv - 1,
                sd.chunk, sd.head_size) < 1 or sd.heads % sd.groups
                or sd.d_inner % sd.groups or sd.shared_hidden < 0
                or sd.shared_hidden % 32 or self.n_heads % self.n_kv_heads):
            raise ValueError(
                f"ssd: positive sizes, heads={sd.heads} and d_inner="
                f"{sd.d_inner} multiples of groups={sd.groups}, a shared "
                f"width of whole Q40 blocks, n_heads a multiple of "
                f"n_kv_heads")
        if ("experts" in kinds) != bool(self.n_experts) or (
                sd.shared_hidden and not self.layout.shared):
            raise ValueError("ssd: n_experts where a layer is \"experts\", "
                             "and there alone; layout.shared 1 with a "
                             "shared_hidden")
        if (act.kind not in ACTIVATIONS or act.kind == "polynorm"
                or act.gated != (act.kind == "silu")
                or (act.scale, act.clamp) != (1.0, 0.0)):
            raise ValueError(f"activation {act}: an ssd spec's experts are "
                             f"gated SiLU or non-gated relu2")

    def _check_mixers(self) -> None:
        mx = self.mixers
        kinds = tuple(mx.kinds)
        if (len(kinds) != self.n_layers or len(kinds) > 128
                or any(k not in MIXER_KINDS for k in kinds)):
            raise ValueError(f"mixers.kinds: one of {MIXER_KINDS} for each "
                             f"of n_layers={self.n_layers} (at most 128)")
        if (self.hybrid or self.latent or self.hyper or self.retention
                or self.qk_norm or self.rope_scaling):
            raise ValueError("a mixer-kinds spec is softmax grouped-query "
                             "attention without q/k-norm: its RoPE and "
                             "scaling are a kind's (MixerKind), and it "
                             "combines with layout / router only")
        for kind in MIXER_KINDS:
            k = mx.of(kind)
            rot = mx.rotary(kind)
            n_kv = self.kv_shape(kind)[0]
            if (k.heads < 1 or k.heads % n_kv or rot % 2
                    or not 0 < rot <= mx.head_size):
                raise ValueError(
                    f"mixers.{kind}: {k.heads} heads must be a multiple of "
                    f"n_kv_heads={n_kv} (the kind's), and its rotary_dim "
                    f"even and at most head_size={mx.head_size}")
        if (mx.window < 1 or mx.head_size < 2 or mx.v_head_size < 0
                or self.n_heads != mx.full.heads
                or mx.full.kv_heads not in (0, self.n_kv_heads)):
            raise ValueError("mixers: a positive window and head_size, and "
                             "n_heads the full kind's head count (n_kv_heads "
                             "its KV heads)")

    def _check_hybrid(self) -> None:
        hy = self.hybrid
        kinds = tuple(hy.kinds)
        if (len(kinds) != self.n_layers or len(kinds) > 128
                or any(k not in LAYER_KINDS for k in kinds)):
            raise ValueError(f"hybrid.kinds: one of {LAYER_KINDS} for each "
                             f"of n_layers={self.n_layers} (at most 128)")
        if kinds.count("full") != 1 or any(
                k not in ("gmu", "xattn")
                for k in kinds[kinds.index("full") + 1:]) or any(
                k in ("gmu", "xattn") for k in kinds[:kinds.index("full")]):
            raise ValueError("a hybrid spec has ONE full-attention layer, "
                             "state-bearing layers before it and only "
                             "gmu / xattn layers after it")
        if "gmu" in kinds and "mamba" not in kinds:
            raise ValueError("a gmu layer gates a Mamba layer's memory")
        if (self.retention or self.latent or self.n_experts or self.qk_norm
                or self.n_heads % 2 or self.n_kv_heads % 2
                or (self.n_heads // 2) % (self.n_kv_heads // 2)
                or hy.window < 1 or min(hy.d_inner, hy.d_state, hy.dt_rank,
                                        hy.d_conv - 1) < 1):
            raise ValueError("a hybrid spec has a dense FFN, differential "
                             "attention over PAIRS of heads (even head "
                             "counts), and positive state-space sizes")

    @property
    def planned(self) -> bool:
        """Whether ``layer_plans`` (and not the one-kind walk) says the
        file's layers."""
        return bool(self.latent or self.hybrid or self.mixers or self.ssd)

    @property
    def retention(self) -> bool:
        """Whether the layers keep a recurrent state in place of a KV cache."""
        return self.attn_kind == "retention"

    @property
    def stateful(self) -> bool:
        """Whether a sequence keeps something that a step rewrites and that
        cannot be rewound (a recurrent state, a window ring)."""
        return bool(self.retention or self.slotted)

    @property
    def slotted(self) -> bool:
        """Whether a sequence keeps a slot of fixed size AND pages (a
        hybrid spec's, a mixer-kinds spec's, a latent spec's with sliding
        layers: rings of latent rows beside the full layers' plane): what
        ``models/llama.slot_model`` runs."""
        return bool(self.hybrid or self.mixers or self.ssd or self.kda
                    or (self.latent and self.latent.window))

    @property
    def window(self) -> int:
        """Positions a slotted spec's window layers see (0: it has none)."""
        rec = self.hybrid or self.mixers or self.latent
        return rec.window if rec else 0

    @property
    def latent_kinds(self) -> tuple:
        """A latent spec's kind a layer (``LatentAttn.kinds``; an empty
        list is every layer "full")."""
        return tuple(self.latent.kinds) or ("full",) * self.n_layers

    @property
    def latent_groups(self) -> int:
        """KV groups ``wkv_b`` expands a latent spec's plane to."""
        return self.latent.kv_groups or self.n_heads

    @property
    def latent_signal_heads(self) -> int:
        """A latent spec's heads that reach ``wo``: all but the noise
        heads."""
        return self.n_heads - self.latent_groups * self.latent.noise_heads

    @property
    def header_version(self) -> int:
        """0 (the 28-byte header), 2, 3, 4, 5, 6, 7, 8, 9, 10 or 11: the lowest
        that holds the spec."""
        if self.ssd:
            return EXT10_VERSION
        if self.kda:
            return EXT11_VERSION
        if self.mixers:
            return EXT8_VERSION if self.mixers.widened else EXT7_VERSION
        if self.latent and (self.latent.widened
                            or self.activation != Activation()
                            or (self.hyper and self.hyper.stream_clamp)):
            return EXT9_VERSION
        if self.hyper:
            return EXT6_VERSION
        if self.hybrid:
            return EXT5_VERSION
        if (self.latent or self.rope_scaling or self.layout != ExpertLayout()
                or self.router != Router()):
            return EXT4_VERSION
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
                EXT3_VERSION: EXT3_STRUCT.size,
                EXT4_VERSION: EXT4_STRUCT.size,
                EXT5_VERSION: EXT5_STRUCT.size,
                EXT6_VERSION: EXT6_STRUCT.size,
                EXT7_VERSION: EXT7_STRUCT.size,
                EXT8_VERSION: EXT8_STRUCT.size,
                EXT9_VERSION: EXT9_STRUCT.size,
                EXT10_VERSION: EXT10_STRUCT.size,
                EXT11_VERSION: EXT11_STRUCT.size}[self.header_version]

    @property
    def head_size(self) -> int:
        """A q / k head: derived, unless a latent or a mixer-kinds spec
        states it."""
        if self.mixers or self.ssd:
            return (self.mixers or self.ssd).head_size
        return self.latent.qk_dim if self.latent else self.dim // self.n_heads

    @property
    def n_experts_held(self) -> int:
        """Routed experts whose weights this spec holds (all, unless the
        layout says a share)."""
        return self.layout.held or self.n_experts

    @property
    def held_columns(self) -> slice:
        """The columns of an (L, E) routed-rows count (the router's full
        width: ops/pallas_moe.moe_ffn) that belong to experts held here."""
        off = self.layout.offset
        return slice(off, off + self.n_experts_held)

    @property
    def n_dense_layers(self) -> int:
        return self.layout.dense_layers

    @property
    def n_expert_layers(self) -> int:
        if self.ssd:
            return self.ssd.count("experts")
        return self.n_layers - self.layout.dense_layers if self.n_experts \
            else 0

    @property
    def kv_dim(self) -> int:
        if self.mixers or self.ssd:
            return self.n_kv_heads * self.head_size
        return (self.dim * self.n_kv_heads) // self.n_heads

    def kv_shape(self, kind: str) -> tuple[int, int, int]:
        """(KV heads, a K head's size, a V head's size) of a mixer-kinds
        spec's layers of ``kind``."""
        mx = self.mixers
        return (mx.of(kind).kv_heads or self.n_kv_heads, mx.head_size,
                mx.v_size)

    def kv_cached(self, kind: str) -> int:
        """Values a position takes in the cache of ONE layer of ``kind``:
        its K and V heads as held (``cache_lanes``)."""
        n_kv, k_size, v_size = self.kv_shape(kind)
        return n_kv * (cache_lanes(k_size) + cache_lanes(v_size))

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
                      (EXT3_VERSION, 13): EXT3_STRUCT,
                      (EXT4_VERSION, 29): EXT4_STRUCT,
                      (EXT5_VERSION, 165): EXT5_STRUCT,
                      (EXT6_VERSION, 36): EXT6_STRUCT,
                      (EXT7_VERSION, 187): EXT7_STRUCT,
                      (EXT8_VERSION, 193): EXT8_STRUCT,
                      (EXT9_VERSION, 175): EXT9_STRUCT,
                      (EXT10_VERSION, 176): EXT10_STRUCT,
                      (EXT11_VERSION, 184): EXT11_STRUCT}.get(
                          (version, count))
            if layout is None:
                raise ValueError(f"unknown header extension version "
                                 f"{version} ({count} ints)")
            if len(raw) < layout.size:
                raise ValueError("extended header truncated")
            _, _, _, *ints = layout.unpack(raw[:layout.size])
            base, ext = ints[:7], ints[7:10]
            if version == EXT5_VERSION:
                more = _read_ext5(ints[36:], base[2])
                ints = ints[:36]
            if version in (EXT7_VERSION, EXT8_VERSION):
                more = _read_ext7(ints[36:], base[2])
                ints = ints[:36]
            if version == EXT10_VERSION:
                more = _read_ext10(ints[36:], base[2])
                ints = ints[:36]
            nine = eleven = None
            if version == EXT11_VERSION:
                eleven, ints = ints[-9:], ints[:-9]
            if version in (EXT9_VERSION, EXT11_VERSION):
                nine, ints = ints[43:], ints[:43]
            if version in (EXT6_VERSION, EXT9_VERSION, EXT11_VERSION):
                streams, iters, _, _, eps, lo, hi = ints[36:]
                more = dict(hyper=HyperConnections(
                    streams, iters, float(eps), float(lo), float(hi),
                    float(nine[10]) if nine else 0.0)) if streams else {}
                ints = ints[:36]
            if version >= EXT4_VERSION:
                more = dict(_read_ext4(ints[13:]), **more)
            if nine:
                more.update(_read_ext9(nine, more["latent"], base[2]))
            if eleven:
                more.update(_read_ext11(eleven, more))
            if version >= EXT3_VERSION:
                kind, theta, eps = ints[10:13]
                if not 0 <= kind < len(ATTN_KINDS):
                    raise ValueError(f"unknown attention kind {kind}")
                more = dict(more, attn_kind=ATTN_KINDS[kind],
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
        v3 = (*base, self.n_experts, self.n_active_experts,
              int(self.qk_norm) + self.qk_norm_per_head,
              ATTN_KINDS.index(self.attn_kind), self.rope_theta,
              self.norm_eps)
        if self.header_version == EXT3_VERSION:
            return EXT3_STRUCT.pack(EXT_MAGIC, EXT3_VERSION, 13, *v3)
        la = self.latent or LatentAttn(0, 0, 0, 0, 0)
        lay, ro = self.layout, self.router
        rs = self.rope_scaling or RopeScaling(0.0, 0)
        v4 = (
            la.q_rank, la.kv_rank, la.nope_dim, la.rope_dim, la.v_dim,
            lay.dense_layers, lay.dense_hidden, lay.shared, lay.held,
            lay.offset, ROUTER_SCORINGS.index(ro.scoring), ro.groups,
            ro.groups_kept, int(ro.renormalise), int(ro.bias),
            int(self.rope_scaling is not None),
            ro.scale, rs.factor, float(rs.original_positions), rs.beta_fast,
            rs.beta_slow, rs.mscale, rs.mscale_all_dim)
        if self.header_version == EXT4_VERSION:
            return EXT4_STRUCT.pack(EXT_MAGIC, EXT4_VERSION, 29, *v3, *v4)
        if self.header_version in (EXT6_VERSION, EXT9_VERSION,
                                   EXT11_VERSION):
            hc = self.hyper or HyperConnections(0, 0, 0.0, 0.0, 0.0)
            v6 = (*v3, *v4, hc.streams, hc.sinkhorn_iters, 0, 0, hc.eps,
                  hc.clamp_min, hc.clamp_max)
            if self.header_version == EXT6_VERSION:
                return EXT6_STRUCT.pack(EXT_MAGIC, EXT6_VERSION, 36, *v6)
            act = self.activation
            kinds = [LATENT_KINDS.index(k) for k in la.kinds]
            v9 = (*v6, la.kv_groups,
                  la.noise_heads, int(la.gate), la.window,
                  ACTIVATIONS.index(act.kind), 0, 0, 0, act.scale, act.clamp,
                  hc.stream_clamp, *kinds, *([255] * (128 - len(kinds))))
            if self.header_version == EXT9_VERSION:
                return EXT9_STRUCT.pack(EXT_MAGIC, EXT9_VERSION, 175, *v9)
            kd = self.kda
            return EXT11_STRUCT.pack(
                EXT_MAGIC, EXT11_VERSION, 184, *v9, kd.heads, kd.head_dim,
                kd.d_conv, int(la.head_gate), int(act.limits), 0, 0, 0,
                kd.lower_bound)
        if self.ssd:
            sd, act = self.ssd, self.activation
            kinds = [SSD_KINDS.index(k) for k in sd.kinds]
            return EXT10_STRUCT.pack(
                EXT_MAGIC, EXT10_VERSION, 176, *v3, *v4, sd.heads,
                sd.head_dim, sd.groups, sd.d_state, sd.d_conv, sd.chunk,
                sd.head_size, sd.shared_hidden, ACTIVATIONS.index(act.kind),
                int(act.gated), 0, 0, *kinds, *([255] * (128 - len(kinds))))
        if self.mixers:
            mx = self.mixers
            kinds = [MIXER_KINDS.index(k) for k in mx.kinds]
            yarn = []
            for k in (mx.full, mx.sliding):
                r = k.rope_scaling or RopeScaling(0.0, 0)
                yarn += [r.factor, float(r.original_positions), r.beta_fast,
                         r.beta_slow, r.mscale, r.mscale_all_dim]
            v7 = (*v3, *v4, mx.window,
                  mx.head_size, int(mx.gate), mx.full.heads, mx.sliding.heads,
                  mx.full.rotary_dim, mx.sliding.rotary_dim,
                  int(mx.full.rope_scaling is not None),
                  int(mx.sliding.rope_scaling is not None),
                  mx.full.rope_theta, mx.sliding.rope_theta, *yarn,
                  *kinds, *([255] * (128 - len(kinds))))
            if self.header_version == EXT7_VERSION:
                return EXT7_STRUCT.pack(EXT_MAGIC, EXT7_VERSION, 187, *v7)
            return EXT8_STRUCT.pack(
                EXT_MAGIC, EXT8_VERSION, 193, *v7, mx.v_head_size,
                mx.full.kv_heads, mx.sliding.kv_heads, int(mx.full.sink),
                int(mx.sliding.sink), mx.value_scale)
        hy = self.hybrid
        kinds = [LAYER_KINDS.index(k) for k in hy.kinds]
        return EXT5_STRUCT.pack(
            EXT_MAGIC, EXT5_VERSION, 165, *v3, *v4, hy.window, hy.d_inner,
            hy.d_state, hy.d_conv, hy.dt_rank, 0, 0, 0,
            *kinds, *([255] * (128 - len(kinds))))

    # -- per-tensor shapes (d, n) in file order ----------------------------

    def layer_matmul_shapes(self) -> list[tuple[str, tuple[int, int]]]:
        """One layer's matmul tensors that exist ONCE a layer, in file
        order: an expert spec has the four attention tensors here and its
        FFN under ``expert_matmul_shapes``."""
        d, h, kv = self.dim, self.hidden_dim, self.kv_dim
        if self.hybrid or self.mixers or self.ssd or self.kda:
            # every distinct matmul tensor of any kind, once (a routed
            # expert's are ``expert_matmul_shapes``)
            seen = {}
            for _, _, entries in self.layer_plans():
                seen.update({(e[1], e[2]): None for e in entries
                             if e[0] == "mm" and len(e) == 3})
            return list(seen)
        attn = self.attn_matmul_shapes()
        if self.n_experts:
            sh = self.layout.shared * h   # the shared experts: ONE SwiGLU
            return attn + ([("sh_w1", (sh, d)), ("sh_w2", (d, sh)),
                            ("sh_w3", (sh, d))] if sh else [])
        return attn + [("w1", (h, d)), ("w2", (d, h)), ("w3", (h, d))]

    def attn_matmul_shapes(self) -> list[tuple[str, tuple[int, int]]]:
        """The attention's matmul tensors of a layer, in file order (a
        latent spec's: ``wkv_b`` a KV group's rows, ``wg`` and ``wo`` over
        the signal heads)."""
        d, kv = self.dim, self.kv_dim
        if not self.latent:
            return [("wq", (d, d)), ("wk", (kv, d)), ("wv", (kv, d)),
                    ("wo", (d, d))]
        la, out = self.latent, self.latent_signal_heads * self.latent.v_dim
        wq = ([("wq_a", (la.q_rank, d)),
               ("wq_b", (self.n_heads * la.qk_dim, la.q_rank))]
              if la.q_rank else [("wq", (self.n_heads * la.qk_dim, d))])
        return [*wq,
                ("wkv_a", (la.width, d)),
                ("wkv_b", (self.latent_groups * (la.nope_dim + la.v_dim),
                           la.kv_rank)),
                *([("wg", (out, d))] if la.gate else []),
                ("wo", (d, out))]

    def dense_layer_matmul_shapes(self) -> list[tuple[str, tuple[int, int]]]:
        """A LEADING DENSE layer's matmul tensors of an expert spec whose
        layout has some (empty otherwise): the attention tensors and a
        SwiGLU of ``layout.dense_hidden``."""
        if not self.layout.dense_layers or self.mixers or self.kda:
            return []   # a mixer-kinds spec's are among its distinct ones
        d, h = self.dim, self.layout.dense_hidden
        return self.attn_matmul_shapes() + [
            ("w1", (h, d)), ("w2", (d, h)), ("w3", (h, d))]

    def expert_matmul_shapes(self) -> list[tuple[str, tuple[int, int]]]:
        """The tensors of ONE routed expert, in file order; a layer holds
        ``n_experts`` of each, stacked (L, E, d, n) in the param tree.
        Empty for a dense spec."""
        if not self.n_experts:
            return []
        d, h = self.dim, self.hidden_dim
        if not self.activation.gated:       # one up matrix, no product
            return [("moe_w1", (h, d)), ("moe_w2", (d, h))]
        return [("moe_w1", (h, d)), ("moe_w2", (d, h)), ("moe_w3", (h, d))]

    def matmul_shape_counts(self) -> list[tuple[tuple[int, int], int]]:
        """((d, n), copies per layer) of every per-layer matmul tensor:
        what a size or layout gate walks."""
        return ([(shape, 1) for _, shape in self.layer_matmul_shapes()]
                + [(shape, self.n_experts_held)
                   for _, shape in self.expert_matmul_shapes()])

    def layer_norm_shapes(self) -> list[tuple[str, int]]:
        """One layer's float32 gain vectors, in file order."""
        norms = [("rms_att", self.dim), ("rms_ffn", self.dim)]
        if self.latent:
            norms += [("rms_q_a", self.latent.q_rank),
                      ("rms_kv_a", self.latent.kv_rank)]
        if self.qk_norm_per_head:
            norms += [("rms_q", self.head_size), ("rms_k", self.head_size)]
        elif self.qk_norm:
            norms += [("rms_q", self.dim), ("rms_k", self.kv_dim)]
        return norms

    def hyper_shapes(self) -> list[tuple[str, tuple]]:
        """One layer's float32 tensors of the residual path, in file order
        (empty without ``hyper``): three a sub-layer."""
        if not self.hyper:
            return []
        k, n = self.hyper.coefficients, self.hyper.streams
        return [(f"hc_{sub}_{leaf}", shape) for sub in HC_SUBLAYERS
                for leaf, shape in (("phi", (k, n * self.dim)),
                                    ("gate", (3,)), ("bias", (k,)))]

    @property
    def gate_shape(self) -> tuple[int, int] | None:
        """A retention layer's ``w_gate`` (float32, after ``wo`` in the
        file): one row a KV head. None for a softmax spec."""
        return (self.n_kv_heads, self.dim) if self.retention else None

    def layer_plans(self):
        """The file's layers in order, for a spec whose layers are of two
        kinds: ``(stack, index, entries)`` a layer, ``stack`` "dense" (a
        leading dense layer, in ``params["dense"]``) or "" (an expert
        layer, the top-level stacks), ``index`` the layer's place in its
        stack, and ``entries`` its tensors in file order: ("f32", name,
        shape) or ("mm", name, (d, n)[, expert]). Per-layer kinds of any
        pattern can take this list's place."""
        if self.hybrid:
            return self._hybrid_plans()
        if self.mixers:
            return self._mixer_plans()
        if self.ssd:
            return self._ssd_plans()
        if self.kda:
            return self._kda_plans()
        norms = [("f32", n, (w,)) for n, w in self.layer_norm_shapes()]
        norms += [("f32", n, s) for n, s in self.hyper_shapes()]
        if self.latent.noise_heads:
            norms.append(("f32", "w_lambda",
                          (self.latent_signal_heads, self.dim)))
        if self.activation.kind == "polynorm":
            norms.append(("f32", "pn_w", (4,)))
        dense = norms + [("mm", n, s)
                         for n, s in self.dense_layer_matmul_shapes()]
        shared = self.layer_matmul_shapes()
        n_attn = len(shared) - (3 if self.layout.shared else 0)
        expert = norms + [("mm", n, s) for n, s in shared[:n_attn]]
        expert.append(("f32", "moe_gate", (self.n_experts, self.dim)))
        if self.router.bias:
            expert.append(("f32", "moe_bias", (self.n_experts,)))
        expert += [("mm", n, s) for n, s in shared[n_attn:]]
        expert += [("mm", n, s, e) for e in range(self.n_experts_held)
                   for n, s in self.expert_matmul_shapes()]
        k = self.layout.dense_layers
        return ([("dense", i, dense) for i in range(k)]
                + [("", i, expert) for i in range(self.n_layers - k)])

    def _hybrid_plans(self):
        """``layer_plans`` of a hybrid spec: ``stack`` is the layer's kind,
        ``index`` its place among the layers of that kind. Every layer:
        LayerNorm gains and biases, the mixer's tensors, then the FFN
        (``w13`` = fc1, [gate | up] on its output rows; ``w2`` = fc2). Small
        leaves are float32 whatever the weights' type: the biases, a Mamba
        layer's conv taps (d_conv, d_inner), ``x_proj`` (dt_rank + 2
        d_state, d_inner), ``dt_proj`` (d_inner, dt_rank), ``a_log``
        (d_state, d_inner: the state index before the channel, as the state
        is held) and ``d_skip``; an attention layer's four lambda vectors
        ``lam`` (4, head) [lq1, lk1, lq2, lk2] and sub-norm gain ``subln``
        (2 head)."""
        hy, d, h = self.hybrid, self.dim, self.hidden_dim
        kv, hs = self.kv_dim, self.head_size
        di, ds, dr = hy.d_inner, hy.d_state, hy.dt_rank
        f, m = (lambda n, *s: ("f32", n, s)), (lambda n, *s: ("mm", n, s))
        norms = [f("ln1_g", d), f("ln1_b", d), f("ln2_g", d), f("ln2_b", d)]
        ffn = [m("w13", 2 * h, d), m("w2", d, h)]
        diff = [f("lam", 4, hs), f("subln", 2 * hs)]
        out = [m("wo", d, d), f("bo", d)]
        mixer = {
            "mamba": [m("in_proj", 2 * di, d), f("conv_w", hy.d_conv, di),
                      f("conv_b", di), f("x_proj", dr + 2 * ds, di),
                      f("dt_proj", di, dr), f("dt_b", di),
                      f("a_log", ds, di), f("d_skip", di),
                      m("out_proj", d, di)],
            "swa": [m("wqkv", d + 2 * kv, d), f("bqkv", d + 2 * kv)] + diff
            + out,
            "gmu": [m("in_proj", di, d), m("out_proj", d, di)],
            "xattn": [m("wq", d, d), f("bq", d)] + diff + out,
        }
        mixer["full"] = mixer["swa"]
        seen: dict = {}
        plans = []
        for kind in hy.kinds:
            plans.append((kind, seen.get(kind, 0),
                          norms + mixer[kind] + ffn))
            seen[kind] = seen.get(kind, 0) + 1
        return plans

    def _mixer_plans(self):
        """``layer_plans`` of a mixer-kinds spec: TWO entries a layer, its
        mixer's (``stack`` the kind, ``index`` its place among the layers
        of that kind) and then its FFN's (``stack`` "dense" or "", as for
        a latent spec's two kinds of FFN)."""
        mx, d, h = self.mixers, self.dim, self.hidden_dim
        lay = self.layout
        f, m = (lambda n, *s: ("f32", n, s)), (lambda n, *s: ("mm", n, s))
        ffn_dense = [f("rms_ffn", d), m("w1", lay.dense_hidden, d),
                     m("w2", d, lay.dense_hidden),
                     m("w3", lay.dense_hidden, d)]
        ffn = [f("rms_ffn", d)]
        if self.n_experts:
            ffn.append(f("moe_gate", self.n_experts, d))
            if self.router.bias:
                ffn.append(f("moe_bias", self.n_experts))
            sh = lay.shared * h
            if sh:
                ffn += [m("sh_w1", sh, d), m("sh_w2", d, sh),
                        m("sh_w3", sh, d)]
            ffn += [("mm", n, s, e) for e in range(self.n_experts_held)
                    for n, s in self.expert_matmul_shapes()]
        else:
            ffn += [m("w1", h, d), m("w2", d, h), m("w3", h, d)]
        seen = {k: 0 for k in MIXER_KINDS}
        plans = []
        for i, kind in enumerate(mx.kinds):
            heads = mx.of(kind).heads
            n_kv, k_size, v_size = self.kv_shape(kind)
            mixer = [f("rms_att", d), m("wq", heads * k_size, d),
                     m("wk", n_kv * k_size, d), m("wv", n_kv * v_size, d),
                     m("wo", d, heads * v_size)]
            if mx.gate:
                mixer.append(f("w_hgate", heads, d))
            if mx.of(kind).sink:
                mixer.append(f("sink", heads))
            plans.append((kind, seen[kind], mixer))
            seen[kind] += 1
            k = lay.dense_layers
            plans.append(("dense", i, ffn_dense) if i < k
                         else ("", i - k, ffn))
        return plans

    def _kda_plans(self):
        """``layer_plans`` of a kda spec: TWO entries a layer, as a
        mixer-kinds spec's: its mixer's (``stack`` "kda" or "full",
        ``index`` its place among the layers of that kind) and then its
        FFN's ("dense" or ""). The module docstring has each run's
        tensors."""
        kd, la, lay = self.kda, self.latent, self.layout
        d, h, w = self.dim, self.hidden_dim, self.kda.width
        f, m = (lambda n, *s: ("f32", n, s)), (lambda n, *s: ("mm", n, s))
        mixer = {
            "kda": [f("rms_att", d), m("in_qkvag", 5 * w, d),
                    f("conv_w", kd.d_conv, 3 * w), f("a_log", kd.heads),
                    f("dt_bias", w), f("w_beta", kd.heads, d),
                    f("norm_g", w), m("wo", d, w)],
            "full": [f("rms_att", d), f("rms_kv_a", la.kv_rank),
                     *([f("rms_q_a", la.q_rank)] if la.q_rank else []),
                     *(("mm", n, s) for n, s in self.attn_matmul_shapes()),
                     *([f("w_hgate", self.n_heads, d)] if la.head_gate
                       else [])],
        }
        ffn_dense = [f("rms_ffn", d), m("w1", lay.dense_hidden, d),
                     m("w2", d, lay.dense_hidden),
                     m("w3", lay.dense_hidden, d)]
        ffn = [f("rms_ffn", d)]
        if self.activation.limits:
            ffn.append(f("ffn_limit", 2))
        ffn.append(f("moe_gate", self.n_experts, d))
        if self.router.bias:
            ffn.append(f("moe_bias", self.n_experts))
        sh = lay.shared * h
        if sh:
            ffn += [m("sh_w1", sh, d), m("sh_w2", d, sh), m("sh_w3", sh, d)]
        ffn += [("mm", n, s, e) for e in range(self.n_experts_held)
                for n, s in self.expert_matmul_shapes()]
        seen = {"kda": 0, "full": 0}
        plans = []
        for i, kind in enumerate(la.kinds):
            plans.append((kind, seen[kind], mixer[kind]))
            seen[kind] += 1
            k = lay.dense_layers
            plans.append(("dense", i, ffn_dense) if i < k
                         else ("", i - k, ffn))
        return plans

    def _ssd_plans(self):
        """``layer_plans`` of an ssd spec: ONE entry a layer, ``stack`` its
        kind and ``index`` its place among the layers of that kind (the
        module docstring has each kind's tensors)."""
        sd, d, h = self.ssd, self.dim, self.hidden_dim
        f, m = (lambda n, *s: ("f32", n, s)), (lambda n, *s: ("mm", n, s))
        hs, di = sd.head_size, sd.d_inner
        experts = [f("rms_att", d), f("moe_gate", self.n_experts, d)]
        if self.router.bias:
            experts.append(f("moe_bias", self.n_experts))
        if sd.shared_hidden:
            experts += [m("sh_w1", sd.shared_hidden, d),
                        m("sh_w2", d, sd.shared_hidden)]
            if self.activation.gated:
                experts.append(m("sh_w3", sd.shared_hidden, d))
        experts += [("mm", n, s, e) for e in range(self.n_experts_held)
                    for n, s in self.expert_matmul_shapes()]
        mixer = {
            "mamba2": [f("rms_att", d), m("in_zx", di + sd.conv_dim, d),
                       f("in_dt", sd.heads, d),
                       f("conv_w", sd.d_conv, sd.conv_dim),
                       f("conv_b", sd.conv_dim), f("dt_bias", sd.heads),
                       f("a_log", sd.heads), f("d_skip", sd.heads),
                       f("norm_g", di), m("out_proj", d, di)],
            "full": [f("rms_att", d), m("wq", self.n_heads * hs, d),
                     m("wk", self.n_kv_heads * hs, d),
                     m("wv", self.n_kv_heads * hs, d),
                     m("wo", d, self.n_heads * hs)],
            "experts": experts,
        }
        seen = {k: 0 for k in SSD_KINDS}
        plans = []
        for kind in sd.kinds:
            plans.append((kind, seen[kind], mixer[kind]))
            seen[kind] += 1
        return plans

    def stack_leaves(self):
        """(stack, name, kind, stacked shape) of every leaf of the two layer
        stacks ``layer_plans`` walks, once each: the leading axis counts the
        stack's layers, an expert tensor's second axis the experts held."""
        depth = {"dense": self.n_dense_layers, "": self.n_expert_layers}
        if self.hybrid:
            depth = {k: self.hybrid.count(k) for k in LAYER_KINDS}
        if self.mixers:
            depth = {"dense": self.n_dense_layers,
                     "": self.n_layers - self.n_dense_layers,
                     **{k: self.mixers.count(k) for k in MIXER_KINDS}}
        if self.ssd:
            depth = {k: self.ssd.count(k) for k in SSD_KINDS}
        if self.kda:
            depth = {"dense": self.n_dense_layers,
                     "": self.n_layers - self.n_dense_layers,
                     **{k: self.latent.count(k) for k in ("kda", "full")}}
        seen, out = set(), []
        for stack, _, entries in self.layer_plans():
            for kind, name, shape, *e in entries:
                if (stack, name) not in seen:
                    seen.add((stack, name))
                    lead = (depth[stack],
                            *([self.n_experts_held] if e else []))
                    out.append((stack, name, kind, (*lead, *shape)))
        return out

    def matmul_bytes(self, shape: tuple[int, int]) -> int:
        dd, nn = shape
        return batch_bytes(self.weights_float_type, nn, dd)

    @property
    def rope_gap_bytes(self) -> int:
        """Legacy freq_cis_real+imag region (transformer.cpp:338-339); a
        version-4 file has none."""
        if self.header_version >= EXT4_VERSION:
            return 0
        return 2 * (self.seq_len * self.head_size // 2) * 4

    def block_bytes(self) -> int:
        """One layer's bytes in the file (an expert layer's, where a
        version-4 spec has two kinds: ``file_size`` walks both)."""
        if self.header_version >= EXT4_VERSION:
            return self._plan_bytes(self.layer_plans()[-1][2])
        b = sum(n * 4 for _, n in self.layer_norm_shapes())  # always F32
        b += self.n_experts * self.dim * 4                   # router, F32
        if self.retention:
            b += self.n_kv_heads * self.dim * 4              # w_gate, F32
        for shape, copies in self.matmul_shape_counts():
            b += copies * self.matmul_bytes(shape)
        return b

    def _plan_bytes(self, entries) -> int:
        import math

        return sum(4 * math.prod(e[2]) if e[0] == "f32"
                   else self.matmul_bytes(e[2]) for e in entries)

    def file_size(self) -> int:
        """Byte-exact total, mirroring the check at transformer.cpp:344-348."""
        b = self.header_bytes
        b += self.vocab_size * self.dim * 4          # tok_embeddings, F32
        if self.header_version >= EXT4_VERSION:
            b += sum(self._plan_bytes(e) for _, _, e in self.layer_plans())
        else:
            b += self.n_layers * self.block_bytes()
        b += self.dim * 4                            # rmsFinal, F32
        if self.hybrid:
            b += self.dim * 4                        # its bias (LayerNorm)
        b += self.rope_gap_bytes
        b += self.matmul_bytes((self.vocab_size, self.dim))  # wcls
        return b


def _read_ext7(vals, n_layers: int) -> dict:
    """``mixers`` from a version-7 header's nine ints, fourteen float64
    and 128 bytes, and a version-8 header's five ints and one float64
    after them."""
    (window, head, gate, f_heads, s_heads, f_rot, s_rot, f_scaled, s_scaled,
     f_theta, s_theta, *rest) = vals
    yarn, kinds = rest[:12], rest[12:][:n_layers]
    v_head, f_kv, s_kv, f_sink, s_sink, v_scale = rest[140:] or (
        0, 0, 0, 0, 0, 1.0)
    if not 0 < n_layers <= 128 or any(k >= len(MIXER_KINDS) for k in kinds):
        raise ValueError("unknown layer kind in a version-7 header")

    def scaling(on, six):
        factor, orig, *more = six
        return RopeScaling(float(factor), int(orig),
                           *(float(x) for x in more)) if on else None

    return dict(mixers=MixerKinds(
        tuple(MIXER_KINDS[k] for k in kinds), window, head,
        MixerKind(f_heads, float(f_theta), f_rot,
                  scaling(f_scaled, yarn[:6]), f_kv, bool(f_sink)),
        MixerKind(s_heads, float(s_theta), s_rot,
                  scaling(s_scaled, yarn[6:]), s_kv, bool(s_sink)),
        bool(gate), v_head, float(v_scale)))


def _read_ext10(vals, n_layers: int) -> dict:
    """``ssd`` and ``activation`` from a version-10 header's twelve ints
    and 128 bytes."""
    (heads, head_dim, groups, d_state, d_conv, chunk, head_size, shared,
     act, gated, _, _, *kinds) = vals
    kinds = kinds[:n_layers]
    if (not 0 < n_layers <= 128 or not 0 <= act < len(ACTIVATIONS)
            or any(k >= len(SSD_KINDS) for k in kinds)):
        raise ValueError("unknown activation or layer kind in a version-10 "
                         "header")
    return dict(
        ssd=SsdLayers(tuple(SSD_KINDS[k] for k in kinds), heads, head_dim,
                      groups, d_state, head_size, d_conv, chunk, shared),
        activation=Activation(ACTIVATIONS[act], gated=bool(gated)))


def _read_ext11(vals, more: dict) -> dict:
    """``kda`` from a version-11 header's eight ints and one float64, and
    what it adds to ``latent`` and ``activation`` (as ``_read_ext9`` left
    them)."""
    heads, head_dim, d_conv, head_gate, limits, _, _, _, bound = vals
    return dict(
        kda=KdaLayers(heads, head_dim, d_conv, float(bound)),
        latent=dataclasses.replace(more["latent"],
                                   head_gate=bool(head_gate)),
        activation=dataclasses.replace(more["activation"],
                                       limits=bool(limits)))


def _read_ext9(vals, la: LatentAttn, n_layers: int) -> dict:
    """What a version-9 header adds to ``latent`` (and ``activation``)
    from its eight ints, three float64 and 128 bytes."""
    groups, noise, gate, window, act, _, _, _, scale, clamp, _, *kinds = vals
    kinds = [k for k in kinds[:n_layers] if k != 255]
    if not 0 <= act < len(ACTIVATIONS) or any(
            k >= len(LATENT_KINDS) for k in kinds):
        raise ValueError("unknown activation or layer kind in a version-9 "
                         "header")
    return dict(
        latent=dataclasses.replace(
            la, kv_groups=groups, noise_heads=noise, gate=bool(gate),
            window=window, kinds=tuple(LATENT_KINDS[k] for k in kinds)),
        activation=Activation(ACTIVATIONS[act], float(scale), float(clamp)))


def _read_ext5(vals, n_layers: int) -> dict:
    """``hybrid`` from a version-5 header's eight ints and 128 bytes."""
    window, d_inner, d_state, d_conv, dt_rank, _, _, _, *kinds = vals
    kinds = kinds[:n_layers]
    if not 0 < n_layers <= 128 or any(k >= len(LAYER_KINDS) for k in kinds):
        raise ValueError("unknown layer kind in a version-5 header")
    return dict(hybrid=HybridLayers(
        tuple(LAYER_KINDS[k] for k in kinds), window, d_inner, d_state,
        d_conv, dt_rank))


def _read_ext4(vals) -> dict:
    """The grouped records of a version-4 header from its sixteen ints and
    seven float64 (``TransformerSpec.header`` has the order)."""
    (q_rank, kv_rank, nope, rope, v_dim, dense_layers, dense_hidden, shared,
     held, offset, scoring, groups, kept, renorm, bias, scaled,
     scale, factor, orig, b_fast, b_slow, mscale, mscale_all) = vals
    if not 0 <= scoring < len(ROUTER_SCORINGS):
        raise ValueError(f"unknown router scoring {scoring}")
    return dict(
        latent=LatentAttn(q_rank, kv_rank, nope, rope, v_dim)
        if kv_rank else None,
        layout=ExpertLayout(dense_layers, dense_hidden, shared, held, offset),
        router=Router(ROUTER_SCORINGS[scoring], groups, kept, bool(renorm),
                      float(scale), bool(bias)),
        rope_scaling=RopeScaling(float(factor), int(orig), float(b_fast),
                                 float(b_slow), float(mscale),
                                 float(mscale_all)) if scaled else None)
