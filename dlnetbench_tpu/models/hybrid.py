"""Decoder with a per-layer pattern of mixers and of FFNs.  Two families
run through it: the SambaY hybrid (arXiv:2507.06607: Mamba,
sliding-window, full and cross attention and gated memory units in one
stack, differential attention (arXiv:2410.05258) in every attention
layer, LayerNorm with bias around a SwiGLU MLP, no positional encoding,
the embedding tied to the head), the latent-attention expert models
of the DeepSeek-V3 family as Kimi-VL-A3B and Moonlight state them (an
``mla`` mixer in every layer, RMSNorm, a leading dense layer and then
routed experts beside shared ones, an untied head), the
linear-attention expert models of the Qwen3-Next family (``gdn`` layers
with one ``gated`` layer a period, a zero-centred RMSNorm, routed
experts beside a shared one behind a sigmoid gate) and the
short-convolution expert models of the LFM2 family (``conv`` layers
with a ``gated`` layer without its gate every third or fourth, leading
dense layers that are ``conv`` layers, sigmoid-routed experts and no
shared one, the head tied under RMSNorm) and the window-and-full
attention expert models of the SmallThinker family (``swa`` layers with
a ``nope`` layer every fourth, no norm a head, a router that reads the
layer's input before attention, ReLU-gated experts and no shared one,
an untied head under RMSNorm) and the head-gated window-and-full
attention expert models of the Laguna family (``swa`` layers with a
``gated`` layer every fourth, the two kinds at head counts, RoPE bases
and turned lanes of their own, YaRN on the full layers', one sigmoid
gate a head from a projection of its own, a leading dense layer, then
softmax-routed experts times ``routed_scale`` beside a plain shared
one) and the sparse-and-linear hybrids of the MiniCPM-SALA family
(``lightning`` layers with a ``sparse`` layer about every fourth, dense
SwiGLUs, an untied head, and MiniCPM's three scalars on the residual
path: below).

``HybridConfig.layer_kinds`` names each layer's mixer, one of ``KINDS``:

* ``mamba``  — selective state-space mixer (``ops.selective_scan``).  The
  LAST of them is the memory layer: its scan output ``s`` (after the
  skip, before the gate) is handed to every ``gmu`` layer.
* ``window`` / ``full`` — causal self-attention, ``window`` over the
  last ``attention_window`` keys.  The one ``full`` layer's keys and
  values are handed to every ``cross`` layer.
* ``gmu``    — gated memory unit: ``(m * silu(y W1)) W2``.
* ``cross``  — attention with queries of its own over the ``full``
  layer's keys and values (causal), so their gradients sum back there.
* ``mla``    — latent attention: keys without position and values are
  expanded from one normed low-rank row a token, one RoPE key head is
  shared by all heads, and the scores run over ``qk_nope + qk_rope``
  lanes beside values of ``v_head_dim`` (``ops.attention`` with two
  widths); plain softmax, no differential pairing.
* ``gdn``    — Gated DeltaNet linear attention (arXiv:2412.06464): one
  projection to queries, keys, values and an output gate, a depthwise
  causal convolution and SiLU over the first three, queries and keys
  normed to unit length a head, a matrix state a value head decayed by
  ``exp(-exp(a_log) softplus(a + dt_bias))`` and corrected by a delta
  of strength ``sigmoid(b)`` a token (``ops.gated_delta_rule``), an
  RMSNorm over each head's output times ``silu`` of the gate.
* ``gated``  — softmax attention with an output gate: the query
  projection carries a gate of the head's width beside each head's
  query, queries and keys are normed a head, RoPE turns the first
  ``rope_dim`` lanes, and ``sigmoid(gate)`` multiplies the heads'
  output before the output projection.  Where the card states no gate
  (``attn_gate`` false) the projection carries the queries alone and
  the heads' output goes to the output projection as it is: grouped
  softmax attention with a norm a head.  Where the gate is one scalar
  a head (``attn_gate`` "head") a projection of its own, ``wg`` [D, H],
  reads the layer's normed input and ``sigmoid`` of it multiplies each
  head's output.  ``rope_yarn`` blends the turned lanes' frequencies
  and scales cos and sin (``layers.rope_freqs``).
* ``swa`` / ``nope`` — the ``gated`` layer's grouped softmax attention
  (its weights, its function) with the layer's own mask and positions:
  ``swa`` sees the last ``attention_window`` keys and RoPE turns its
  heads, ``nope`` sees every earlier key and has no position at all.
  A ``swa`` layer's query heads, RoPE base and turned lanes are its own
  where the card states them (``window_heads``, ``window_rope_theta``,
  ``window_rope_dim``); with a head count of their own the ``swa``
  layers are a stack of their own, ``swa``, beside ``gated``.
* ``conv``   — gated short convolution: one projection to three streams
  ``[b | c | u]`` of the model's width, ``c * conv(b * u)`` with a
  depthwise causal convolution of ``short_conv`` taps over time, no
  activation and no bias, and an output projection.
* ``sparse`` — the ``gated`` layer's projections, norm a head and gate
  of a head's width with no position, over the key blocks each token
  chooses for itself (InfLLM-V2; ``sparse_sizes``,
  ``ops.sparse_attention``): a sequence longer than the sizes'
  ``dense_len`` selects (``select_blocks``, no gradient, once a step:
  the checkpoint keeps the lists) and attends its own blocks
  (``block_sparse_attention``); at or under it the layer is a ``nope``
  layer with a gate, bit for bit.
* ``lightning`` — linear attention with a constant decay a head
  (``ops.lightning_attention``): projections to queries, keys and
  values at ``gdn_key_heads`` heads, a norm a head on queries and keys,
  RoPE on every lane, ``S_t = lambda_h S_{t-1} + k_t^T v_t`` with
  ``lambda_h`` from the head's slope and the layer's place among
  ``lightning_depth`` layers (a constant, not a leaf), an RMSNorm over
  each head's output (``o_norm``) times ``sigmoid`` of a gate projected
  from the layer's normed input, and an output projection.

Every layer is ``x += c mixer(norm(x)); x += c ffn(norm(x))`` with
``c = residual_scale``; the embedding is times ``embed_scale`` and the
final normed stream times ``logit_scale`` before the head.  The three
are 1.0 (and then no operation of the program) everywhere but in the
MiniCPM family, whose ``scale_emb``, ``scale_depth / sqrt(published
depth)`` and ``dim_model_base / hidden_size`` they are.
``ffn_kinds`` names each layer's FFN: ``dense`` (SwiGLU at ``ff_dim``)
or ``moe``: ``sum_i w_i expert_i(y)`` over the token's top-k of
``num_experts`` routed experts, of which this chip holds
``held_experts`` (``models/moe.moe_held``: the router scores and
selects over all of them, no token is dropped), plus one shared SwiGLU
at ``shared_ff_dim`` that every token passes through (times
``sigmoid(y w_sg)`` a token where ``shared_gate``).  Where
``early_router`` the router reads the mixer's normed input, the tensor
attention reads, and the experts the normed stream after the mixer;
``expert_activation`` is the experts' gate's (``silu`` | ``relu``).  Parameters are
stacked by kind (``block`` holds both norms of every layer and the MLP
of every dense one; ``moe`` the expert layers' router, routed and shared
experts), layers are unrolled as the bench recipe unrolls them, and
``remat`` checkpoints each layer: the backward recomputes the layer from
its input, all but its attention kernel's forward call, whose output and
lse the checkpoint keeps (``_keeping``: ``[B, S, Hq, Dv]`` in the
model's dtype and ``[B, Hq, 8, S]`` float32 a call, where a head of
the output is one lane tile, ``ops.flash_attention._kept``), and all but
a ``gdn`` layer's rule, whose output, chunk states and chunk matrices it
keeps (``ops.gated_delta_rule.KEPT_NAMES``: ``[B, S, H, dv]`` and
``[B, H, S / C, dk, dv]`` in the model's dtype, ``[B, H, S / C, C, C]``
float32); a layer without such a kernel keeps nothing.

Differential attention runs over the kernels ``ops.attention`` already
has: heads are paired by adjacent index, ``(q1, k1)`` and ``(q2, k2)``
are each zero-padded from ``head_dim`` to the value's ``2 * head_dim``
and attend in two calls over ``V = [v1; v2]``; the query is scaled by
sqrt(2) at its projection (before it is rounded to the activation
dtype), so the kernels' ``1 / sqrt(2 * head_dim)`` is the published
``1 / sqrt(head_dim)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp

from dlnetbench_tpu import ops
from dlnetbench_tpu.core.model_card import ModelCard
from dlnetbench_tpu.metrics.spans import mark, scope
from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.models.moe import moe_held
from dlnetbench_tpu.ops import sparse_attention as sparse
from dlnetbench_tpu.ops.attention_mask import MaskSpec
from dlnetbench_tpu.ops.flash_attention import KEPT_NAMES
from dlnetbench_tpu.ops.gated_delta_rule import (
    KEPT_NAMES as RULE_KEPT_NAMES, gated_delta_rule)
from dlnetbench_tpu.ops.lightning_attention import (head_log_decay,
                                                    lightning_attention)
from dlnetbench_tpu.ops.selective_scan import selective_scan

_F32 = jnp.float32
KINDS = ("mamba", "window", "full", "gmu", "cross", "mla", "gdn", "gated",
         "conv", "sparse", "lightning", "swa", "nope")
FFN_KINDS = ("dense", "moe")
# what a layer's kind reads: the stack of parameters its mixer's weights
# lie in, and the inner scope (``spans.SCOPES``) its kernel call wears
# inside its layer's (None: the layer's alone).  What a configuration
# changes of this is in ``HybridConfig.group_of`` and ``attn_scope``
_READS = {"mamba": ("mamba", None), "window": ("attn", None),
          "full": ("attn", None), "gmu": ("gmu", None),
          "cross": ("cross", None), "mla": ("mla", None),
          "gdn": ("gdn", None), "gated": ("gated", None),
          "conv": ("conv", None), "swa": ("gated", "attn.window"),
          "nope": ("gated", "attn.full"), "sparse": ("gated", "attn.sparse"),
          "lightning": ("lightning", "linattn.rule")}
# the kinds ``gated_mixer`` computes, whichever stack holds their weights
_GATED_KINDS = frozenset(k for k, (g, _) in _READS.items() if g == "gated")
# what a step with expert layers returns beside its loss
# (``models/moe.moe_held``): three counters over its expert layers (the
# rows routed to held experts and the rows past the bound summed, the
# largest load of one expert) and every layer's selection
COUNTERS = ("routed", "max_load", "past_bound")
ROUTING = (*COUNTERS, "choices")
# what a step whose sparse layers select returns beside its loss: every
# such layer's lists stacked [sparse layers, B, S, Hkv, topk] and two
# counters summed over them (``ops.sparse_attention.counters``)
SELECTION = ("blocks", "selected", "visited")
# leaves kept in float32 whatever the model's dtype (the family's
# convention: the recurrence's own parameters, lambdas and norms)
F32_LEAVES = frozenset({
    "norm1", "norm1_b", "norm2", "norm2_b", "final_norm", "final_norm_b",
    "a_log", "d_skip", "b_dt", "conv_b", "sub_norm",
    "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
    "kv_norm", "router_bias", "dt_bias", "o_norm", "q_norm", "k_norm"})
_SPLASH_BLOCKS = (2048, 1024, 512, 256, 128)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    embed_dim: int
    num_heads: int
    num_kv_heads: int
    ff_dim: int
    layer_kinds: tuple
    seq_len: int
    ssm_inner: int
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0            # 0 = embed_dim / 16
    attention_window: int = 512
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False             # jax.checkpoint each layer: all
                                    # recomputed but the attention
                                    # kernel's output and lse, kept
    attention_impl: str = "auto"    # ops.attention: auto | flash | xla
    scan_impl: str = "auto"         # ops.selective_scan: auto|pallas|xla
    loss_row_block: int = 0         # head and loss in blocks of this
                                    # many rows, each block's gradients
                                    # taken with its loss, so that
                                    # [T, V] logits never lie whole in
                                    # HBM; 0 = whole
    rms_norm: bool = False          # RMSNorm without bias, else LayerNorm
    norm_plus_one: bool = False     # the RMSNorm scales by 1 + w (w zero
                                    # at the start), not by w
    tied_head: bool = True          # the head is the embedding table
    # latent attention ("mla" layers)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    # gated attention ("gated" layers) and linear attention ("gdn")
    attn_head_dim: int = 0          # 0 = embed_dim / num_heads
    rope_dim: int = 0               # leading lanes of a head RoPE turns
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0            # lanes of a key head
    gdn_value_dim: int = 0          # lanes of a value head
    gdn_conv: int = 4               # taps of the depthwise causal conv
    attn_gate: bool | str = True    # a "gated" layer's output gate: of
                                    # the head's width (``wq`` carries
                                    # it), "head": one scalar a head
                                    # (``wg``), False: none
    head_norm: bool = True          # ... and its norm a head on q and k
    # what a "swa" layer has of its own: 0 = the "gated" layer's
    window_heads: int = 0           # query heads (then a stack "swa")
    window_rope_theta: float = 0.0
    window_rope_dim: int = 0
    rope_yarn: tuple = ()           # YaRN on a "gated" layer's turned
                                    # lanes (``layers.rope_freqs``):
                                    # (factor, original positions,
                                    # beta_fast, beta_slow, the factor
                                    # on cos and sin); () = plain RoPE
    short_conv: int = 3             # taps of a "conv" layer's convolution
    rule_impl: str = "auto"         # ops.gated_delta_rule: auto|pallas|xla
    # the FFN of each layer; () = every layer dense
    ffn_kinds: tuple = ()
    num_experts: int = 0            # the router's outputs
    top_k: int = 0
    expert_ff_dim: int = 0
    shared_ff_dim: int = 0          # 0 = no shared expert
    shared_gate: bool = False       # the shared expert times sigmoid(y w)
    router_scoring: str = "softmax"  # layers.moe_router's gate
    early_router: bool = False      # the router reads the mixer's input
    expert_activation: str = "silu"  # the experts' gate: silu | relu
    routed_scale: float = 1.0
    held_experts: tuple = ()        # (first, count) of the routed experts
                                    # this chip holds; () = all of them
    moe_slots: int = 0              # rows a held expert's buffer has: a
                                    # bound the load is not to reach
    # MiniCPM's three scalars; 1.0 = none, and no operation
    embed_scale: float = 1.0        # the embedding's rows times this
    residual_scale: float = 1.0     # a branch times this before it is added
    logit_scale: float = 1.0        # the final normed stream times this
    # a "sparse" layer's selection (``ops.sparse_attention.SparseSizes``:
    # kernel_size, kernel_stride, block_size, topk, window_size,
    # init_blocks, dense_len)
    sparse_sizes: tuple = ()
    # a "lightning" layer's heads are ``gdn_key_heads`` of ``gdn_key_dim``
    # lanes (values ``gdn_value_dim``); its decay reads the layer's index
    # among this many layers (0 = the model's own depth)
    lightning_depth: int = 0

    def __post_init__(self):
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        if not kinds or set(kinds) - set(KINDS):
            raise ValueError(f"layer_kinds {kinds} must name {KINDS}")
        ffn = tuple(self.ffn_kinds) or ("dense",) * len(kinds)
        object.__setattr__(self, "ffn_kinds", ffn)
        if len(ffn) != len(kinds) or set(ffn) - set(FFN_KINDS):
            raise ValueError(f"ffn_kinds {ffn} must name {FFN_KINDS}, "
                             f"one a layer")
        if "moe" in ffn:
            held = tuple(self.held_experts) or (0, self.num_experts)
            object.__setattr__(self, "held_experts", held)
            if not (0 <= held[0] and held[1] > 0
                    and sum(held) <= self.num_experts
                    and 0 < self.top_k <= self.num_experts
                    and self.moe_slots > 0 and self.expert_ff_dim > 0):
                raise ValueError(
                    f"expert layers need num_experts, top_k, "
                    f"expert_ff_dim, moe_slots and held_experts "
                    f"{held} within the {self.num_experts} experts")
        if "mla" in kinds and not (self.kv_lora_rank
                                   and self.qk_nope_head_dim
                                   and self.qk_rope_head_dim % 2 == 0
                                   and self.v_head_dim):
            raise ValueError("mla layers need kv_lora_rank, "
                             "qk_nope_head_dim, an even qk_rope_head_dim "
                             "and v_head_dim")
        if "gdn" in kinds and not (
                self.gdn_key_heads and self.gdn_key_dim
                and self.gdn_value_dim and self.gdn_value_heads
                and self.gdn_value_heads % self.gdn_key_heads == 0):
            raise ValueError("gdn layers need gdn_key_heads dividing "
                             "gdn_value_heads, gdn_key_dim and "
                             "gdn_value_dim")
        if self.early_router and any(
                f == "moe" and k not in _GATED_KINDS
                for k, f in zip(kinds, ffn)):
            raise ValueError("early_router: only a gated, swa or nope "
                             "layer hands its router the mixer's input")
        if set(kinds) & _GATED_KINDS and (
                self.num_heads % self.num_kv_heads
                                 or self.rope_dim % 2
                                 or self.rope_dim > self.head_dim):
            raise ValueError("gated, swa and nope layers need "
                             "num_kv_heads dividing "
                             "num_heads and an even rope_dim within the "
                             "head")
        object.__setattr__(self, "rope_yarn", tuple(self.rope_yarn))
        object.__setattr__(self, "sparse_sizes", tuple(self.sparse_sizes))
        if "sparse" in kinds:
            if len(self.sparse_sizes) != 7:
                raise ValueError("sparse layers need the seven "
                                 "sparse_sizes")
            if self.has_selection:
                sparse.SparseSizes(*self.sparse_sizes).check(self.seq_len)
        if "lightning" in kinds and not (
                self.gdn_key_heads and self.gdn_key_dim
                and self.gdn_key_dim % 2 == 0 and self.gdn_value_dim
                and self.gdn_value_heads == self.gdn_key_heads):
            raise ValueError("lightning layers need as many gdn_key_heads "
                             "as gdn_value_heads, an even gdn_key_dim and "
                             "gdn_value_dim")
        if "swa" in kinds and (self.window_heads % self.num_kv_heads
                               or self.window_rope_dim % 2
                               or self.window_rope_dim > self.head_dim):
            raise ValueError("swa layers need num_kv_heads dividing "
                             "window_heads and an even window_rope_dim "
                             "within the head")
        if (self.attn_gate not in (False, True, "head")
                or len(self.rope_yarn) not in (0, 5)):
            raise ValueError("attn_gate is False, True or 'head'; "
                             "rope_yarn five numbers")
        if set(kinds) & {"window", "full", "cross"} and (
                self.num_heads % 2 or self.num_kv_heads % 2
                or self.num_heads % self.num_kv_heads):
            raise ValueError("differential attention pairs adjacent "
                             "heads: head counts must be even")
        first = {k: kinds.index(k) for k in set(kinds)}
        if "gmu" in first and not (
                "mamba" in first and self.memory_layer < first["gmu"]):
            raise ValueError("a gmu layer needs a mamba layer before it")
        if "cross" in first and not (
                kinds.count("full") == 1
                and first["full"] < first["cross"]):
            raise ValueError("cross layers need exactly one full layer "
                             "before them")

    @classmethod
    def from_card(cls, card: ModelCard, *, seq_len: int | None = None,
                  layer_kinds: tuple | None = None,
                  **over) -> "HybridConfig":
        if not card.layer_kinds:
            raise ValueError(f"{card.name} states no layer_kinds; use "
                             f"models.transformer")
        kinds = tuple(layer_kinds or card.layer_kinds)
        stated = {"rms_norm": card.rms_norm,
                  "norm_plus_one": card.norm_plus_one,
                  "tied_head": card.tied_embeddings,
                  "attn_head_dim": card.attn_head_dim,
                  "attn_gate": card.attn_output_gate,
                  "head_norm": card.attn_head_norm,
                  "rope_dim": card.rope_dim,
                  "window_heads": card.window_heads,
                  "window_rope_theta": card.window_rope_theta,
                  "window_rope_dim": card.window_rope_dim,
                  "rope_yarn": card.rope_yarn,
                  "embed_scale": card.embed_scale,
                  "residual_scale": card.residual_scale,
                  "logit_scale": card.logit_scale,
                  "sparse_sizes": card.sparse_attention,
                  "lightning_depth": card.published_layers,
                  "gdn_key_heads": card.linear_key_heads,
                  "gdn_value_heads": card.linear_value_heads,
                  "gdn_key_dim": card.linear_key_dim,
                  "gdn_value_dim": card.linear_value_dim,
                  "kv_lora_rank": card.kv_lora_rank,
                  "qk_nope_head_dim": card.qk_nope_head_dim,
                  "qk_rope_head_dim": card.qk_rope_head_dim,
                  "v_head_dim": card.v_head_dim}
        if card.rope_theta:
            stated["rope_theta"] = card.rope_theta
        if card.norm_eps:
            stated["norm_eps"] = card.norm_eps
        if card.linear_conv:
            stated["gdn_conv"] = card.linear_conv
        if card.short_conv:
            stated["short_conv"] = card.short_conv
        if (moe := card.moe_params) is not None:
            width = moe.expert_ff_dim or card.ff_dim
            stated.update(
                ffn_kinds=tuple(
                    "dense" if li < moe.first_dense_layers else "moe"
                    for li in range(len(kinds))),
                num_experts=moe.num_experts,
                top_k=moe.num_experts_per_tok, expert_ff_dim=width,
                shared_ff_dim=moe.shared_experts * width,
                shared_gate=moe.shared_gate,
                router_scoring=moe.scoring,
                routed_scale=moe.routed_scale,
                early_router=moe.early_router,
                expert_activation=moe.activation)
        return cls(vocab_size=card.vocab_size, embed_dim=card.embed_dim,
                   num_heads=card.num_heads, num_kv_heads=card.kv_heads,
                   ff_dim=card.ff_dim, layer_kinds=kinds,
                   seq_len=seq_len or card.seq_len,
                   ssm_inner=card.ssm_inner, ssm_state=card.ssm_state,
                   ssm_conv=card.ssm_conv, ssm_dt_rank=card.ssm_dt_rank,
                   attention_window=card.sliding_window,
                   **{**stated, **over})

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.embed_dim // self.num_heads

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or self.embed_dim // 16

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def memory_layer(self) -> int:
        """The last mamba layer: its scan output is the memory."""
        kinds = self.layer_kinds
        return len(kinds) - 1 - kinds[::-1].index("mamba")

    def heads_of(self, kind: str) -> int:
        """Query heads of a ``kind`` attention layer."""
        return (self.window_heads if kind == "swa" and self.window_heads
                else self.num_heads)

    def group_of(self, kind: str) -> str:
        """The stack a ``kind`` layer's mixer reads: ``_READS``'s, and
        ``swa`` where the window layers' head count is their own."""
        own = kind == "swa" and self.heads_of(kind) != self.num_heads
        return "swa" if own else _READS[kind][0]

    def attn_scope(self, kind: str):
        """The inner scope of a ``kind`` layer's kernel call: a
        ``gated`` layer beside ``swa`` layers wears ``attn.full``, so
        that the two kinds' time can be read apart."""
        if kind == "gated" and "swa" in self.layer_kinds:
            return "attn.full"
        return _READS[kind][1]

    def rope_of(self, kind: str) -> tuple:
        """(theta, turned lanes, YaRN's numbers or None) of a ``gated``
        or ``swa`` layer's RoPE."""
        if kind == "swa":
            return (self.window_rope_theta or self.rope_theta,
                    self.window_rope_dim or self.rope_dim or self.head_dim,
                    None)
        return (self.rope_theta, self.rope_dim or self.head_dim,
                self.rope_yarn or None)

    def index_in_group(self, li: int) -> int:
        group = self.group_of(self.layer_kinds[li])
        return sum(1 for k in self.layer_kinds[:li]
                   if self.group_of(k) == group)

    def index_in_ffn(self, li: int) -> int:
        """Layer ``li``'s place among the layers with its kind of FFN
        (``li`` itself where every layer is dense)."""
        return self.ffn_kinds[:li].count(self.ffn_kinds[li])

    @property
    def has_experts(self) -> bool:
        return "moe" in self.ffn_kinds

    @property
    def has_selection(self) -> bool:
        """A ``sparse`` layer selects: the sequence is longer than its
        ``dense_len``."""
        return ("sparse" in self.layer_kinds
                and self.seq_len > self.sparse_sizes[6])

    @property
    def returns_aux(self) -> bool:
        """The step returns ``ROUTING`` / ``SELECTION`` beside its loss."""
        return self.has_experts or self.has_selection

    def group_sizes(self) -> dict:
        out = {g: 0 for g, _ in _READS.values()}
        for k in self.layer_kinds:
            g = self.group_of(k)
            out[g] = out.get(g, 0) + 1
        return out


def lambda_init(li: int) -> float:
    """The differential attention's constant of layer ``li`` (its index
    in the model as it is run)."""
    return 0.8 - 0.6 * math.exp(-0.3 * li)


def param_shapes(cfg: HybridConfig) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}: the layout both
    ``init_params`` and the benchmark's seeded weights follow.  ``init``
    is a scale for normal draws, or one of "ones", "zeros", "a_log",
    "b_dt", "decay_log".  A norm that scales by ``1 + w`` starts at
    zero."""
    d, f, v = cfg.embed_dim, cfg.ff_dim, cfg.vocab_size
    e, n, r, w = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    dh = cfg.head_dim
    dq, dkv = cfg.num_heads * dh, cfg.num_kv_heads * dh
    nl = cfg.num_layers
    sizes = cfg.group_sizes()
    s_d = 1.0 / math.sqrt(d)
    nd = cfg.ffn_kinds.count("dense")
    unit = "zeros" if cfg.norm_plus_one else "ones"
    out = {
        "final_norm": ((d,), unit),
        "block/norm1": ((nl, d), unit),
        "block/norm2": ((nl, d), unit),
    }
    if cfg.tied_head:
        out["embed"] = ((v, d), s_d)  # tied: the table is the head too
    else:
        out.update({"embed": ((v, d), 1.0), "head": ((v, d), s_d)})
    if not cfg.rms_norm:
        out.update({"final_norm_b": ((d,), "zeros"),
                    "block/norm1_b": ((nl, d), "zeros"),
                    "block/norm2_b": ((nl, d), "zeros")})
    if nd:
        out.update({"block/w_gate": ((nd, d, f), s_d),
                    "block/w_up": ((nd, d, f), s_d),
                    "block/w_down": ((nd, f, d), 1.0 / math.sqrt(f))})
    if (m := nl - nd):
        x, fe, fs = cfg.num_experts, cfg.expert_ff_dim, cfg.shared_ff_dim
        held = cfg.held_experts[1]
        out.update({
            "moe/w_router": ((m, d, x), s_d),
            "moe/w_gate": ((m, held, d, fe), s_d),
            "moe/w_up": ((m, held, d, fe), s_d),
            "moe/w_down": ((m, held, fe, d), 1.0 / math.sqrt(fe))})
        if cfg.router_scoring == "sigmoid":
            out["moe/router_bias"] = ((m, x), "zeros")
        if fs:
            out.update({
                "moe/ws_gate": ((m, d, fs), s_d),
                "moe/ws_up": ((m, d, fs), s_d),
                "moe/ws_down": ((m, fs, d), 1.0 / math.sqrt(fs))})
            if cfg.shared_gate:
                out["moe/ws_sig"] = ((m, d), s_d)
    if (m := sizes["mamba"]):
        out.update({
            "mamba/w_in": ((m, d, 2 * e), s_d),
            "mamba/conv_w": ((m, w, e), 1.0 / math.sqrt(w)),
            "mamba/conv_b": ((m, e), "zeros"),
            "mamba/w_x": ((m, e, r + 2 * n), 1.0 / math.sqrt(e)),
            "mamba/w_dt": ((m, r, e), 1.0 / math.sqrt(r)),
            "mamba/b_dt": ((m, e), "b_dt"),
            "mamba/a_log": ((m, e, n), "a_log"),
            "mamba/d_skip": ((m, e), "ones"),
            "mamba/w_out": ((m, e, d), 1.0 / math.sqrt(e)),
        })
    for group, m in (("attn", sizes["attn"]), ("cross", sizes["cross"])):
        if not m:
            continue
        out.update({
            f"{group}/wq": ((m, d, dq), s_d),
            f"{group}/wo": ((m, dq, d), 1.0 / math.sqrt(dq)),
            f"{group}/sub_norm": ((m, 2 * dh), "ones"),
            **{f"{group}/lambda_{x}": ((m, dh), 0.1)
               for x in ("q1", "k1", "q2", "k2")},
        })
        if group == "attn":
            out.update({"attn/wk": ((m, d, dkv), s_d),
                        "attn/wv": ((m, d, dkv), s_d)})
    if (m := sizes["gmu"]):
        out.update({"gmu/w1": ((m, d, e), s_d),
                    "gmu/w2": ((m, e, d), 1.0 / math.sqrt(e))})
    if (m := sizes["mla"]):
        h, r = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        out.update({
            "mla/wq": ((m, d, h * (dn + dr)), s_d),
            "mla/w_kva": ((m, d, r + dr), s_d),
            "mla/kv_norm": ((m, r), "ones"),
            "mla/w_kvb": ((m, r, h * (dn + dv)), 1.0 / math.sqrt(r)),
            "mla/wo": ((m, h * dv, d), 1.0 / math.sqrt(h * dv))})
    if (m := sizes["gdn"]):
        hv, w = cfg.gdn_value_heads, cfg.gdn_conv
        qk = cfg.gdn_key_heads * cfg.gdn_key_dim
        vz = hv * cfg.gdn_value_dim
        out.update({
            "gdn/w_qkvz": ((m, d, 2 * qk + 2 * vz), s_d),
            "gdn/w_ba": ((m, d, 2 * hv), s_d),
            "gdn/conv_w": ((m, w, 2 * qk + vz), 1.0 / math.sqrt(w)),
            "gdn/a_log": ((m, hv), "decay_log"),
            "gdn/dt_bias": ((m, hv), "ones"),
            "gdn/o_norm": ((m, cfg.gdn_value_dim), "ones"),
            "gdn/w_out": ((m, vz, d), 1.0 / math.sqrt(vz))})
    for g, h in (("gated", cfg.num_heads), ("swa", cfg.window_heads)):
        if not (m := sizes.get(g, 0)):
            continue
        hkv, wide = cfg.num_kv_heads, cfg.attn_gate is True
        out.update({
            f"{g}/wq": ((m, d, (1 + wide) * h * dh), s_d),
            f"{g}/wk": ((m, d, hkv * dh), s_d),
            f"{g}/wv": ((m, d, hkv * dh), s_d),
            f"{g}/wo": ((m, h * dh, d), 1.0 / math.sqrt(h * dh))})
        if cfg.attn_gate == "head":
            out[f"{g}/wg"] = ((m, d, h), s_d)
        if cfg.head_norm:
            out.update({f"{g}/q_norm": ((m, dh), unit),
                        f"{g}/k_norm": ((m, dh), unit)})
    if (m := sizes["lightning"]):
        h = cfg.gdn_key_heads
        qk, vz = h * cfg.gdn_key_dim, h * cfg.gdn_value_dim
        out.update({
            "lightning/wq": ((m, d, qk), s_d),
            "lightning/wk": ((m, d, qk), s_d),
            "lightning/wv": ((m, d, vz), s_d),
            "lightning/wz": ((m, d, vz), s_d),
            "lightning/q_norm": ((m, cfg.gdn_key_dim), unit),
            "lightning/k_norm": ((m, cfg.gdn_key_dim), unit),
            "lightning/o_norm": ((m, cfg.gdn_value_dim), unit),
            "lightning/wo": ((m, vz, d), 1.0 / math.sqrt(vz))})
    if (m := sizes["conv"]):
        out.update({
            "conv/w_in": ((m, d, 3 * d), s_d),
            "conv/conv_w": ((m, cfg.short_conv, d),
                            1.0 / math.sqrt(cfg.short_conv)),
            "conv/w_out": ((m, d, d), s_d)})
    return out


def init_leaf(key, name: str, shape, init, dtype):
    """One leaf of ``param_shapes``.  ``a_log`` is log(1..N) a channel,
    ``b_dt`` the inverse softplus of a step drawn log-uniform in
    [1e-3, 1e-1], ``decay_log`` the log of a draw uniform in (0, 16);
    the float32 leaves stay float32."""
    dt = _F32 if name.rsplit("/", 1)[-1] in F32_LEAVES else dtype
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "zeros":
        return jnp.zeros(shape, dt)
    if init == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=_F32)), shape
        ).astype(dt)
    if init == "decay_log":
        return jnp.log(jax.random.uniform(key, shape, _F32, 1e-3, 16.0)
                       ).astype(dt)
    if init == "b_dt":
        step = jnp.exp(jax.random.uniform(
            key, shape, _F32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    return (jax.random.normal(key, shape, _F32) * init).astype(dt)


def init_params(key, cfg: HybridConfig) -> dict:
    spec = param_shapes(cfg)
    tree: dict = {}
    for k, (name, (shape, init)) in zip(
            jax.random.split(key, len(spec)), sorted(spec.items())):
        leaf = init_leaf(k, name, shape, init, cfg.jdtype)
        group, _, sub = name.rpartition("/")
        (tree.setdefault(group, {}) if group else tree)[sub] = leaf
    return tree


# ------------------------------------------------------------- mixers

def _norm_scale(cfg, w):
    """What an RMSNorm multiplies by: its weight, or one more."""
    return 1.0 + w if cfg.norm_plus_one else w


def _norm(cfg, x, p, name: str):
    """The model's norm with the weight ``p[name]`` (and, for LayerNorm,
    the bias ``p[name + "_b"]``)."""
    if cfg.rms_norm:
        return L.rmsnorm(x, _norm_scale(cfg, p[name]),
                         cfg.norm_eps).astype(x.dtype)
    return L.layernorm(x, p[name], p[name + "_b"],
                       cfg.norm_eps).astype(x.dtype)


def _silu(x):
    return jax.nn.silu(x.astype(_F32)).astype(x.dtype)


def _causal_conv(u, w, b=None):
    """Depthwise causal convolution along time: u [B, S, E], w [K, E]
    (tap K-1 is the current step), b [E] or None."""
    k, s = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0))).astype(_F32)
    out = 0.0 if b is None else b.astype(_F32)
    for i in range(k):
        out = out + up[:, i:i + s] * w[i].astype(_F32)
    return out.astype(u.dtype)


@jax.custom_vjp
def _conv_silu(u, w):
    """``silu(causal conv(u))`` without bias, u [B, S, E], w [K, E].
    The backward keeps ``u`` alone and makes the convolution again, so
    that no float32 copy of [B, S, E] lives from the forward to it."""
    return _silu(_causal_conv(u, w))


def _conv_silu_fwd(u, w):
    return _conv_silu(u, w), (u, w)


def _conv_silu_bwd(res, dy):
    u, w = res
    k, s = w.shape[0], u.shape[1]
    with scope("linattn"):
        up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
        wf = w.astype(_F32)
        c = sum(up[:, i:i + s].astype(_F32) * wf[i] for i in range(k))
        sig = jax.nn.sigmoid(c)
        dc = dy.astype(_F32) * sig * (1.0 + c * (1.0 - sig))
        dw = jnp.stack([jnp.sum(up[:, i:i + s].astype(_F32) * dc, (0, 1))
                        for i in range(k)])
        # tap i of step t reads u[t - (k - 1 - i)]
        dcp = jnp.pad(dc, ((0, 0), (0, k - 1), (0, 0)))
        du = sum(dcp[:, k - 1 - i:k - 1 - i + s] * wf[i] for i in range(k))
        return du.astype(u.dtype), dw.astype(w.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@jax.custom_vjp
def _gated_conv(bcu, w):
    """``c * conv(b * u)`` of the three streams ``bcu = [b | c | u]``
    [B, S, 3E] with the taps w [K, E]: a causal depthwise convolution,
    no activation and no bias.  The backward keeps ``bcu`` as the
    projection wrote it and makes the two products and the convolution
    again, so that no float32 copy of [B, S, E] lives from the forward
    to it."""
    b, c, u = (t.astype(_F32) for t in jnp.split(bcu, 3, axis=-1))
    return (c * _causal_conv(b * u, w)).astype(bcu.dtype)


def _gated_conv_fwd(bcu, w):
    return _gated_conv(bcu, w), (bcu, w)


def _gated_conv_bwd(res, dy):
    bcu, w = res
    k, s = w.shape[0], bcu.shape[1]
    with scope("conv.gate"):
        b, c, u = (t.astype(_F32) for t in jnp.split(bcu, 3, axis=-1))
        wf, dy, z = w.astype(_F32), dy.astype(_F32), b * u
        dh = dy * c
        zp = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
        dw = jnp.stack([jnp.sum(zp[:, i:i + s] * dh, (0, 1))
                        for i in range(k)])
        # tap i of step t reads z[t - (k - 1 - i)]
        dhp = jnp.pad(dh, ((0, 0), (0, k - 1), (0, 0)))
        dz = sum(dhp[:, k - 1 - i:k - 1 - i + s] * wf[i] for i in range(k))
        dbcu = jnp.concatenate(
            [dz * u, dy * _causal_conv(z, w), dz * b], axis=-1)
        return dbcu.astype(bcu.dtype), dw.astype(w.dtype)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def conv_mixer(y, p):
    """Gated short convolution: ``(c * conv(b * u)) W_out`` with
    ``[b | c | u] = y W_in``."""
    bcu = jnp.dot(y, p["w_in"])
    with scope("conv.gate"):
        g = _gated_conv(bcu, p["conv_w"])
    return jnp.dot(g, p["w_out"])


def mamba_mixer(cfg: HybridConfig, y, p):
    """(out [B, S, D], s [B, S, E]: the scan's output, the memory)."""
    e, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
    uz = jnp.dot(y, p["w_in"])
    u = _silu(_causal_conv(uz[..., :e], p["conv_w"], p["conv_b"]))
    xp = jnp.dot(u, p["w_x"])
    delta = jax.nn.softplus(
        jnp.dot(xp[..., :r], p["w_dt"], preferred_element_type=_F32)
        + p["b_dt"])
    with scope("ssm.scan"):
        s = selective_scan(u, delta, -jnp.exp(p["a_log"]),
                           xp[..., r:r + n], xp[..., r + n:],
                           p["d_skip"], cfg.scan_impl)
    return jnp.dot(s * _silu(uz[..., e:]), p["w_out"]), s


def gmu_mixer(y, memory, p):
    return jnp.dot(memory * _silu(jnp.dot(y, p["w1"])), p["w2"])


def _pairs(t, first: int):
    """[B, S, H, dh] -> [B, S, H / 2, 2 * dh]: head ``2p + first`` of
    each adjacent pair, zero-padded to the value's width."""
    b, s, h, dh = t.shape
    t = t.reshape(b, s, h // 2, 2, dh)[:, :, :, first]
    return jnp.concatenate([t, jnp.zeros_like(t)], axis=-1)


def project_kv(cfg: HybridConfig, y, p):
    """(k1, k2, V) as the attention kernels take them."""
    b, s, _ = y.shape
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    k = jnp.dot(y, p["wk"]).reshape(b, s, hkv, dh)
    v = jnp.dot(y, p["wv"]).reshape(b, s, hkv // 2, 2 * dh)
    return _pairs(k, 0), _pairs(k, 1), v


def _splash_block(cfg: HybridConfig, s: int):
    """Blocks no wider than the window, so that a row block's visit
    range is the band's two or three blocks and the tiles hold little
    that lies outside it.  The kernels' grid is as long as that range
    (``BlockMask.q_visits``), so a smaller block costs the steps of its
    own tiles and no longer ``S // block`` of them a row."""
    limit = max(cfg.attention_window, 128)
    return next((b for b in _SPLASH_BLOCKS if b <= limit and s % b == 0),
                None)


def diff_attention(cfg: HybridConfig, y, p, kv, li: int, window: bool):
    """Differential attention of layer ``li`` over ``kv`` = (k1, k2, V)."""
    b, s, d = y.shape
    hq, dh = cfg.num_heads, cfg.head_dim
    k1, k2, v = kv
    # sqrt(2): the kernels divide by sqrt(2 * dh), the model by sqrt(dh)
    q = (jnp.dot(y, p["wq"], preferred_element_type=_F32)
         * math.sqrt(2.0)).astype(y.dtype).reshape(b, s, hq, dh)
    mask, block = None, None
    if window:
        mask = MaskSpec(causal=True, window=cfg.attention_window)
        block = _splash_block(cfg, s)
    a1, a2 = (ops.attention(_pairs(q, i), k, v, causal=True,
                            impl=cfg.attention_impl, mask=mask,
                            block_q=block, block_k=block).astype(_F32)
              for i, k in ((0, k1), (1, k2)))
    lam0 = lambda_init(li)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    o = a1 - lam * a2                                 # [B, S, Hq/2, 2dh]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.norm_eps)
    o = (o * p["sub_norm"] * (1.0 - lam0)).astype(y.dtype)
    return jnp.dot(o.reshape(b, s, hq * dh), p["wo"])


def mla_mixer(cfg: HybridConfig, y, p):
    """Latent attention.  ``ckv = y W_kva`` is a token's low-rank row
    and its one RoPE key head; ``k_nope`` and ``v`` of every head are
    expanded from the normed row; the scores run over ``[nope | rope]``
    lanes, scaled by their width, the values keep theirs."""
    b, s, _ = y.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = jnp.dot(y, p["wq"]).reshape(b, s, h, dn + dr)
    ckv = jnp.dot(y, p["w_kva"])
    c = L.rmsnorm(ckv[..., :r], p["kv_norm"], cfg.norm_eps).astype(y.dtype)
    kv = jnp.dot(c, p["w_kvb"]).reshape(b, s, h, dn + dv)
    q_rope, k_rope = L.rope(q[..., dn:], ckv[..., None, r:],
                            jnp.arange(s), cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    o = ops.attention(q, k, kv[..., dn:], causal=True,
                      impl=cfg.attention_impl)
    return jnp.dot(o.reshape(b, s, h * dv), p["wo"])


def _unit_heads(t, eps: float):
    """Each head of t [..., H, d] over its length, in float32."""
    t = t.astype(_F32)
    return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + eps)


def gdn_mixer(cfg: HybridConfig, y, p):
    """Gated DeltaNet.  Key head ``h // (value heads / key heads)``
    serves value head ``h``; the decay and the delta's strength are a
    value head's, in float32."""
    b, s, _ = y.shape
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    nqk, nv = hk * dk, hv * dv
    qkvz = jnp.dot(y, p["w_qkvz"])
    ba = jnp.dot(y, p["w_ba"], preferred_element_type=_F32)
    qkv = _conv_silu(qkvz[..., :2 * nqk + nv], p["conv_w"])
    q = _unit_heads(qkv[..., :nqk].reshape(b, s, hk, dk), 1e-6) \
        * (1.0 / math.sqrt(dk))
    k = _unit_heads(qkv[..., nqk:2 * nqk].reshape(b, s, hk, dk), 1e-6)
    q, k = (jnp.repeat(t.astype(y.dtype), hv // hk, axis=2)
            for t in (q, k))
    v = qkv[..., 2 * nqk:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    with scope("linattn.rule"):
        o = gated_delta_rule(q, k, v, g, beta, cfg.rule_impl)
    o = L.rmsnorm(o, p["o_norm"], cfg.norm_eps).astype(y.dtype)
    z = qkvz[..., 2 * nqk + nv:].reshape(b, s, hv, dv)
    return jnp.dot((o * _silu(z)).reshape(b, s, nv), p["w_out"])


def _gated_qkv(cfg: HybridConfig, y, p, kind: str):
    """A ``gated_mixer`` layer's projections: (the query projection's
    output [B, S, H, dh or 2 dh] with the wide gate's lanes, q, k, v),
    queries and keys normed a head where the card says so."""
    b, s, _ = y.shape
    h, hkv, dh = cfg.heads_of(kind), cfg.num_kv_heads, cfg.head_dim
    wide = cfg.attn_gate is True
    qg = jnp.dot(y, p["wq"]).reshape(b, s, h, (1 + wide) * dh)
    q = qg[..., :dh]
    k = jnp.dot(y, p["wk"]).reshape(b, s, hkv, dh)
    v = jnp.dot(y, p["wv"]).reshape(b, s, hkv, dh)
    if cfg.head_norm:
        q = L.rmsnorm(q, _norm_scale(cfg, p["q_norm"]),
                      cfg.norm_eps).astype(y.dtype)
        k = L.rmsnorm(k, _norm_scale(cfg, p["k_norm"]),
                      cfg.norm_eps).astype(y.dtype)
    return qg, q, k, v


def _gated_out(cfg: HybridConfig, y, p, qg, o):
    """The heads' output ``o`` [B, S, H, dh] under the layer's gate,
    through the output projection."""
    b, s, h, dh = o.shape
    if cfg.attn_gate is True:
        o = o * jax.nn.sigmoid(qg[..., dh:].astype(_F32)).astype(y.dtype)
    elif cfg.attn_gate:
        with scope("attn.gate"):
            g = jax.nn.sigmoid(jnp.dot(y, p["wg"],
                                       preferred_element_type=_F32))
            o = o * g[..., None].astype(y.dtype)
    return jnp.dot(o.reshape(b, s, h * dh), p["wo"])


def gated_mixer(cfg: HybridConfig, y, p, kind: str = "gated"):
    """Softmax attention with grouped keys and values; a norm a head on
    queries and keys and an output gate where the card states them.
    The layer's ``kind`` gives its mask, its positions and its query
    heads (``cfg.heads_of``): ``gated``, ``nope`` and ``sparse`` (at a
    sequence that does not select: ``sparse_mixer``) see every earlier
    key, ``swa`` the last ``attention_window`` (the block-sparse
    kernels at ``_splash_block``'s blocks); RoPE turns the first lanes
    of ``gated`` and ``swa`` heads as ``cfg.rope_of`` says and none of
    ``nope``'s or ``sparse``'s."""
    s = y.shape[1]
    qg, q, k, v = _gated_qkv(cfg, y, p, kind)
    inner = cfg.attn_scope(kind)
    with scope(inner) if inner else contextlib.nullcontext():
        if kind not in ("nope", "sparse"):
            theta, dr, yarn = cfg.rope_of(kind)
            q_rope, k_rope = L.rope(q[..., :dr], k[..., :dr], jnp.arange(s),
                                    theta, yarn)
            q = jnp.concatenate([q_rope, q[..., dr:]], axis=-1)
            k = jnp.concatenate([k_rope, k[..., dr:]], axis=-1)
        mask, block = None, None
        if kind == "swa":
            mask = MaskSpec(causal=True, window=cfg.attention_window)
            block = _splash_block(cfg, s)
        o = ops.attention(q, k, v, causal=True, impl=cfg.attention_impl,
                          mask=mask, block_q=block, block_k=block)
    return _gated_out(cfg, y, p, qg, o)


def sparse_mixer(cfg: HybridConfig, y, p):
    """(out, the layer's ``SELECTION`` or None).  A sequence longer than
    ``dense_len``: each token's blocks from its own scores
    (``attn.select``; integers, no gradient), then attention over them
    (``attn.sparse``).  At or under it ``gated_mixer``'s every earlier
    key: nothing is selected."""
    if not cfg.has_selection:
        return gated_mixer(cfg, y, p, "sparse"), None
    sizes = sparse.SparseSizes(*cfg.sparse_sizes)
    qg, q, k, v = _gated_qkv(cfg, y, p, "sparse")
    with scope("attn.select"):
        blocks = sparse.select_blocks(q, k, sizes)
    with scope("attn.sparse"):
        visits = sparse.plan_visits(blocks, sizes.block_size, q.dtype)
        o = sparse.block_sparse_attention(q, k, v, visits)
    return _gated_out(cfg, y, p, qg, o), {
        "blocks": blocks, **sparse.counters(blocks, visits)}


def lightning_mixer(cfg: HybridConfig, y, p, li: int):
    """Lightning attention of layer ``li``: a norm a head on queries and
    keys, RoPE on every lane, the rule with the layer's constant decay
    (float32), a norm over each head's output, a sigmoid gate from a
    projection of its own."""
    b, s, _ = y.shape
    h, dk, dv = cfg.gdn_key_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    q = jnp.dot(y, p["wq"]).reshape(b, s, h, dk)
    k = jnp.dot(y, p["wk"]).reshape(b, s, h, dk)
    v = jnp.dot(y, p["wv"]).reshape(b, s, h, dv)
    q = L.rmsnorm(q, _norm_scale(cfg, p["q_norm"]),
                  cfg.norm_eps).astype(y.dtype)
    k = L.rmsnorm(k, _norm_scale(cfg, p["k_norm"]),
                  cfg.norm_eps).astype(y.dtype)
    q, k = L.rope(q, k, jnp.arange(s), cfg.rope_theta)
    decay = head_log_decay(h, li, cfg.lightning_depth or cfg.num_layers)
    with scope("linattn.rule"):
        o = lightning_attention(q, k, v, decay, 1.0 / math.sqrt(dk),
                                cfg.rule_impl)
    o = L.rmsnorm(o, _norm_scale(cfg, p["o_norm"]),
                  cfg.norm_eps).astype(y.dtype)
    z = jnp.dot(y, p["wz"]).reshape(b, s, h, dv)
    o = o * jax.nn.sigmoid(z.astype(_F32)).astype(y.dtype)
    return jnp.dot(o.reshape(b, s, h * dv), p["wo"])


def _add(cfg: HybridConfig, x, out):
    """The residual stream after a branch: ``x + c out``, ``c`` the
    configuration's ``residual_scale`` (1.0: the plain sum)."""
    if cfg.residual_scale == 1.0:
        return x + out
    return x + (out.astype(_F32) * cfg.residual_scale).astype(x.dtype)


def expert_ffn(cfg: HybridConfig, x, norm, fp, router_in=None):
    """``x + routed(y) + shared(y)``, ``y = norm(x)``, and the layer's
    routing (``moe_held``'s).  The router reads ``router_in`` [B, S, D]
    where the layer hands one (``early_router``: the mixer's normed
    input), else ``y``.  The norm lies in ``moe.router`` and the
    residual in ``moe.combine``, as in ``transformer._block``."""
    b, s, d = x.shape
    with scope("moe.router"):
        y = _norm(cfg, x, norm, "norm2")
    routed, routing = moe_held(
        y.reshape(b * s, d), fp["w_router"], fp["w_gate"], fp["w_up"],
        fp["w_down"], cfg.top_k, held=cfg.held_experts,
        slots=cfg.moe_slots, scoring=cfg.router_scoring,
        bias=fp.get("router_bias"), scale=cfg.routed_scale,
        router_x=(None if router_in is None
                  else router_in.reshape(b * s, d)),
        activation=cfg.expert_activation)
    out = routed.reshape(b, s, d)
    if cfg.shared_ff_dim:
        with scope("moe.shared"):
            shared = L.swiglu(y, fp["ws_gate"], fp["ws_up"], fp["ws_down"])
            if cfg.shared_gate:
                shared = shared * jax.nn.sigmoid(jnp.dot(
                    y, fp["ws_sig"], preferred_element_type=_F32)
                )[..., None].astype(y.dtype)
            out = out + shared
    with scope("moe.combine"):
        return _add(cfg, x, out), routing


def _layer(cfg: HybridConfig, li: int, x, bp, mp, fp, memory, kv):
    """Layer ``li``: ``bp`` its norms, ``mp`` its mixer's weights,
    ``fp`` its FFN's.  Returns (x, handed, aux): ``handed`` is the
    memory (the memory layer), (k1, k2, V) (the full layer) or None;
    ``aux`` an expert layer's ``ROUTING`` and a selecting sparse
    layer's ``SELECTION`` in one dict, else None."""
    kind = cfg.layer_kinds[li]
    handed = router_in = selection = None
    if kind == "mamba":
        with scope("ssm"):
            out, s = mamba_mixer(cfg, _norm(cfg, x, bp, "norm1"), mp)
            x = _add(cfg, x, out)
        if li == cfg.memory_layer:
            handed = s
    elif kind == "gmu":
        with scope("gmu"):
            x = _add(cfg, x, gmu_mixer(_norm(cfg, x, bp, "norm1"), memory,
                                       mp))
    elif kind == "mla":
        with scope("attn"):
            x = _add(cfg, x, mla_mixer(cfg, _norm(cfg, x, bp, "norm1"), mp))
    elif kind in _GATED_KINDS:
        with scope("attn"):
            y = _norm(cfg, x, bp, "norm1")
            # a "gated" layer keeps the call of three arguments that
            # the benchmark's planted fault wraps
            # (benchmarks/runners/train_conv_moe._no_qk_norm)
            if kind == "sparse":
                out, selection = sparse_mixer(cfg, y, mp)
            else:
                out = (gated_mixer(cfg, y, mp) if kind == "gated"
                       else gated_mixer(cfg, y, mp, kind))
            x = _add(cfg, x, out)
        if cfg.early_router:
            router_in = y
    elif kind == "gdn":
        with scope("linattn"):
            x = _add(cfg, x, gdn_mixer(cfg, _norm(cfg, x, bp, "norm1"), mp))
    elif kind == "lightning":
        with scope("linattn"):
            x = _add(cfg, x, lightning_mixer(
                cfg, _norm(cfg, x, bp, "norm1"), mp, li))
    elif kind == "conv":
        with scope("conv"):
            x = _add(cfg, x, conv_mixer(_norm(cfg, x, bp, "norm1"), mp))
    else:
        with scope("attn"):
            y = _norm(cfg, x, bp, "norm1")
            if kind != "cross":
                kv = project_kv(cfg, y, mp)
            if kind == "full":
                handed = kv
            x = _add(cfg, x, diff_attention(cfg, y, mp, kv, li,
                                            kind == "window"))
    if cfg.ffn_kinds[li] == "moe":
        x, routing = expert_ffn(cfg, x, bp, fp, router_in)
        return x, handed, {**routing, **(selection or {})}
    with scope("mlp"):
        y = _norm(cfg, x, bp, "norm2")
        x = _add(cfg, x, L.swiglu(y, fp["w_gate"], fp["w_up"], fp["w_down"]))
    return x, handed, selection


_MLP = ("w_gate", "w_up", "w_down")
# the names a checkpointed layer keeps: an attention kernel's output and
# lse, the gated delta rule's output, chunk states and chunk matrices,
# and a sparse layer's selection with what the kernels read of it
_KEPT = (*KEPT_NAMES, *RULE_KEPT_NAMES, sparse.BLOCKS_NAME)


def _keeping(li: int, kind: str):
    """The checkpoint policy of layer ``li``: the values its kernels
    have named (``_KEPT``) are saved for the backward and nothing else
    is, so the layer's recomputation has no use for the kernel's forward
    call, nor for the selection and its plan of visits, and drops them;
    a layer without such a kernel saves nothing.  Each value it saves is
    a fact of the traced program, marked ``remat.kept`` (``spans.mark``:
    on the build's ``compile`` span under a tracer, nothing without
    one)."""
    named = jax.checkpoint_policies.save_only_these_names(*_KEPT)

    def policy(prim, *avals, **params):
        keep = named(prim, *avals, **params)
        if keep:
            value, = avals
            mark("remat.kept", layer=li, kind=kind, value=params["name"],
                 bytes=value.size * value.dtype.itemsize)
        return keep
    return policy


def _forward(params: dict, tokens, cfg: HybridConfig):
    """tokens [B, S] -> (the last layer's output [B, S, D] before the
    final norm, which ``loss_and_routing`` goes on from; the expert
    layers' ``ROUTING``, ``choices`` stacked [expert layers, T, k], and
    the selecting sparse layers' ``SELECTION``, ``blocks`` stacked
    [sparse layers, B, S, Hkv, topk], or {} without either)."""
    with scope("embed"):
        x = params["embed"][tokens]
        if cfg.embed_scale != 1.0:
            x = (x.astype(_F32) * cfg.embed_scale).astype(x.dtype)
    memory = kv = None
    auxes = []
    for li, kind in enumerate(cfg.layer_kinds):
        layer = _layer
        if cfg.remat:
            layer = jax.checkpoint(_layer, static_argnums=(0, 1),
                                   policy=_keeping(li, kind))
        gi, fi = cfg.index_in_group(li), cfg.index_in_ffn(li)
        block = params["block"]
        bp = {k: a[li] for k, a in block.items() if k not in _MLP}
        mp = jax.tree.map(lambda a: a[gi], params[cfg.group_of(kind)])
        if cfg.ffn_kinds[li] == "moe":
            fp = jax.tree.map(lambda a: a[fi], params["moe"])
        else:
            fp = {k: block[k][fi] for k in _MLP}
        x, handed, aux = layer(cfg, li, x, bp, mp, fp, memory, kv)
        if kind == "mamba" and handed is not None:
            memory = handed
        elif kind == "full":
            kv = handed
        if aux:
            auxes.append(aux)
    out = {}
    routed = [a for a in auxes if "choices" in a]
    if routed:
        stacked = {k: jnp.stack([r[k] for r in routed]) for k in ROUTING}
        out = {**stacked, "routed": jnp.sum(stacked["routed"]),
               "max_load": jnp.max(stacked["max_load"]),
               "past_bound": jnp.sum(stacked["past_bound"])}
    selected = [a for a in auxes if "blocks" in a]
    if selected:
        out.update(
            blocks=jnp.stack([a["blocks"] for a in selected]),
            selected=sum(a["selected"] for a in selected),
            visited=sum(a["visited"] for a in selected))
    return x, out


def forward(params: dict, tokens, cfg: HybridConfig):
    """tokens [B, S] -> the last layer's output [B, S, D] (before the
    final norm)."""
    return _forward(params, tokens, cfg)[0]


def loss_and_routing(params: dict, tokens, cfg: HybridConfig):
    """(next-token cross-entropy on a [B, S+1] token batch, the expert
    layers' ``ROUTING`` and the sparse layers' ``SELECTION``).  Where the rows are a multiple of
    ``cfg.loss_row_block`` the head and the loss run block by block and
    each block leaves its gradients behind
    (``layers.blocked_head_cross_entropy``); the final norm is taken
    over all rows at once either way.  The head is the embedding table
    (``tied_head``) or a [V, D] table of its own."""
    x, routing = _forward(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    table = params["embed" if cfg.tied_head else "head"]
    with scope("head_loss"):
        x = _norm(cfg, x, params, "final_norm")
        if cfg.logit_scale != 1.0:
            x = (x.astype(_F32) * cfg.logit_scale).astype(x.dtype)
        rows, block = x.shape[0] * x.shape[1], cfg.loss_row_block
        if not block or rows <= block or rows % block:
            return L.cross_entropy(jnp.dot(x, table.T), targets), routing
        return L.blocked_head_cross_entropy(
            x.reshape(rows, -1), table, targets.reshape(rows),
            block), routing


def loss_fn(params: dict, tokens, cfg: HybridConfig):
    return loss_and_routing(params, tokens, cfg)[0]
