"""Architecture cards — the model database.

The reference keeps nine JSON architecture cards under ``models/*.json`` with
``embed_dim / num_heads / ff_dim / seq_len / num_encoder_blocks /
num_decoder_blocks`` and optional ``moe_params`` (reference
models/llama3_8b.json, models/mixtral_8x7b.json), consumed by
``count_layers`` (reference cpp/utils.hpp:279-294).

This rebuild keeps that JSON schema as the interop surface and extends it
with the fields a *real* TPU implementation of each model needs (vocab size,
KV heads for GQA, MLP family, ViT patching) — the reference never needs them
because it does no math.  Extended fields are optional in the parser so the
reference's own card files load unchanged.

Parameter counts are computed analytically from the card (the reference
instead downloads full HuggingFace weights just to count parameters,
reference python/model_stats.py:144-145 — an egress + 140 GB dependency this
rebuild deliberately drops).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

_CARD_DIR = Path(__file__).resolve().parent.parent / "data" / "models"


@dataclasses.dataclass(frozen=True)
class MoEParams:
    num_experts: int
    num_experts_per_tok: int
    # --- extended fields (defaults are the Mixtral family's) ---
    scoring: str = "softmax"        # the gate: softmax over the top-k
                                    # logits, or "sigmoid" scores with a
                                    # selection bias, normalised, scaled
    routed_scale: float = 1.0       # times the normalised weights
    shared_experts: int = 0         # experts every token passes through
    shared_gate: bool = False       # ... times sigmoid(y w) a token
    expert_ff_dim: int = 0          # an expert's width; 0 => ff_dim
    first_dense_layers: int = 0     # leading layers with a dense FFN
    early_router: bool = False      # the router reads the layer's normed
                                    # input, the tensor attention reads,
                                    # not the experts' (the normed stream
                                    # after attention)
    activation: str = "silu"        # the experts' gate: silu | relu


@dataclasses.dataclass(frozen=True)
class ModelCard:
    name: str
    embed_dim: int
    num_heads: int
    ff_dim: int
    seq_len: int
    num_encoder_blocks: int = 0
    num_decoder_blocks: int = 0
    moe_params: MoEParams | None = None
    # --- extended fields (rebuild only; defaults make reference cards load) ---
    vocab_size: int = 0             # 0 for patch-input models (ViT)
    num_kv_heads: int = 0           # 0 => MHA (kv heads == heads)
    gated_mlp: bool = False         # SwiGLU (llama family) vs GELU 2-matmul
    tied_embeddings: bool = False   # share input embedding with LM head
    max_position_embeddings: int = 0  # learned positions (gpt2); 0 => RoPE/none
    image_size: int = 0             # ViT
    patch_size: int = 0             # ViT
    num_classes: int = 0            # ViT head
    # a per-layer pattern of mixers (models/hybrid.py KINDS: mamba,
    # window, full, gmu, cross, mla, gdn, gated, conv, swa, nope, sparse,
    # lightning), one name a decoder block; () => every
    # block is the transformer's one kind (models/transformer.py)
    layer_kinds: tuple = ()
    sliding_window: int = 0         # keys a "window" or "swa" layer attends
    differential_attention: bool = False  # heads paired, two softmaxes
    ssm_inner: int = 0              # a mamba / gmu layer's channels E
    ssm_state: int = 0              # state size N a channel
    ssm_conv: int = 0               # depthwise causal conv width
    ssm_dt_rank: int = 0            # rank of the step projection
    # latent attention (an "mla" layer, models/hybrid.py): keys and
    # values expanded from one low-rank row a token, score heads of
    # qk_nope + qk_rope lanes beside value heads of v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 0.0         # 0 => layers.rope's default
    rms_norm: bool = False          # a layer_kinds model's norm: RMSNorm
                                    # without bias (else LayerNorm)
    norm_eps: float = 0.0           # 0 => the model family's default
    norm_plus_one: bool = False     # the RMSNorm scales by 1 + w
    # gated softmax attention (a "gated" layer): heads of attn_head_dim
    # lanes (0 => embed_dim / num_heads), RoPE on the first rope_dim
    attn_head_dim: int = 0
    rope_dim: int = 0
    attn_output_gate: bool | str = True  # false: the heads' output
                                    # ungated, the query projection
                                    # without lanes for a gate; "head":
                                    # one sigmoid gate a head from a
                                    # projection [D, H] of its own
    attn_head_norm: bool = True     # false: queries and keys not normed
                                    # a head ("swa" and "nope" layers are
                                    # "gated" ones with a window and RoPE,
                                    # or neither, of their own)
    # linear attention with a matrix state (a "gdn" layer)
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 0            # depthwise causal conv width
    # gated short convolution (a "conv" layer): taps of its depthwise
    # causal conv over the model's width
    short_conv: int = 0
    # what a "swa" layer has of its own, 0 => the "gated" layer's: its
    # query heads (over the same key/value heads), its RoPE base and the
    # leading lanes RoPE turns
    window_heads: int = 0
    window_rope_theta: float = 0.0
    window_rope_dim: int = 0
    # YaRN on a "gated" layer's turned lanes (models/layers.rope_freqs):
    # (factor, original positions, beta_fast, beta_slow, the factor on
    # cos and sin); () => plain RoPE
    rope_yarn: tuple = ()
    # MiniCPM's three scalars, 1.0 => none: the embedding times
    # embed_scale (scale_emb), each branch times residual_scale before
    # it is added (scale_depth / sqrt(published depth)), the final
    # normed stream times logit_scale (dim_model_base / hidden_size)
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # a "sparse" layer's selection (ops/sparse_attention.SparseSizes):
    # (kernel_size, kernel_stride, block_size, topk, window_size,
    # init_blocks, dense_len)
    sparse_attention: tuple = ()
    # a "lightning" layer's heads are linear_key_heads of linear_key_dim
    # lanes (values linear_value_dim); its decay reads the layer's index
    # among published_layers (0 => num_decoder_blocks: a cut of a model
    # keeps the published depth here)
    published_layers: int = 0

    # ------------------------------------------------------------------ #
    @property
    def num_layers(self) -> int:
        """Total block count (reference cpp/utils.hpp:279-294 semantics)."""
        return self.num_encoder_blocks + self.num_decoder_blocks

    @property
    def is_moe(self) -> bool:
        return self.moe_params is not None

    @property
    def is_vit(self) -> bool:
        return self.patch_size > 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def num_experts(self) -> int:
        return self.moe_params.num_experts if self.moe_params else 1

    @property
    def top_k(self) -> int:
        return self.moe_params.num_experts_per_tok if self.moe_params else 1

    # ------------------------------------------------------------------ #
    def attn_params_per_layer(self) -> int:
        d, dkv = self.embed_dim, self.kv_dim
        return d * d + 2 * d * dkv + d * d  # Wq, Wk, Wv, Wo

    def mlp_params_per_expert(self, width: int = 0) -> int:
        """One MLP of ``width`` (the dense ``ff_dim`` by default)."""
        n_mat = 3 if self.gated_mlp else 2
        return n_mat * self.embed_dim * (width or self.ff_dim)

    def routed_expert_params(self) -> int:
        moe = self.moe_params
        return self.mlp_params_per_expert(moe.expert_ff_dim)

    def mixer_params(self, kind: str) -> int:
        """Parameters of one ``layer_kinds`` mixer (norms left out)."""
        d, e, n, r = (self.embed_dim, self.ssm_inner, self.ssm_state,
                      self.ssm_dt_rank)
        if kind == "mamba":
            return (d * 2 * e + e * self.ssm_conv + e + e * (r + 2 * n)
                    + r * e + e + e * n + e + e * d)
        if kind == "gmu":
            return 2 * d * e
        if kind == "cross":
            return 2 * d * d            # queries and output only
        if kind == "gdn":
            hv = self.linear_value_heads
            qk = self.linear_key_heads * self.linear_key_dim
            vz = hv * self.linear_value_dim
            return (d * (2 * qk + 2 * vz) + d * 2 * hv
                    + self.linear_conv * (2 * qk + vz) + 2 * hv
                    + self.linear_value_dim + vz * d)
        if kind == "lightning":
            qk = self.linear_key_heads * self.linear_key_dim
            vz = self.linear_value_heads * self.linear_value_dim
            return (d * (2 * qk + 2 * vz) + 2 * self.linear_key_dim
                    + self.linear_value_dim + vz * d)
        if kind in ("gated", "swa", "nope", "sparse"):
            dh = self.attn_head_dim or self.head_dim
            heads = (self.window_heads if kind == "swa"
                     and self.window_heads else self.num_heads)
            dq, dkv = heads * dh, self.kv_heads * dh
            gate = {True: d * dq, "head": d * heads}.get(
                self.attn_output_gate, 0)
            return (d * dq + gate + 2 * d * dkv
                    + 2 * dh * self.attn_head_norm + dq * d)
        if kind == "conv":
            return d * 3 * d + self.short_conv * d + d * d
        if kind == "mla":
            h, r = self.num_heads, self.kv_lora_rank
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            return (d * h * qk + d * (r + self.qk_rope_head_dim) + r
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        return self.attn_params_per_layer()

    def ffn_params(self, li: int) -> int:
        """Parameters of layer ``li``'s FFN in a ``layer_kinds`` model:
        dense, or routed and shared experts with their router."""
        d, moe = self.embed_dim, self.moe_params
        if moe is None or li < moe.first_dense_layers:
            return self.mlp_params_per_expert()
        experts = moe.num_experts + moe.shared_experts
        bias = moe.num_experts if moe.scoring == "sigmoid" else 0
        bias += d if moe.shared_gate else 0
        return (experts * self.routed_expert_params()
                + d * moe.num_experts + bias)

    def num_params(self) -> int:
        """Analytic total parameter count (biases/norms included coarsely)."""
        d = self.embed_dim
        if self.layer_kinds:
            norms = 2 * d if self.rms_norm else 4 * d
            total = sum(self.mixer_params(k) + self.ffn_params(li) + norms
                        for li, k in enumerate(self.layer_kinds)) \
                + norms // 2
            return total + self.vocab_size * d * (
                1 if self.tied_embeddings else 2)
        per_layer = self.attn_params_per_layer() + 2 * d  # + two norms
        if self.is_moe:
            per_layer += self.num_experts * self.mlp_params_per_expert()
            per_layer += d * self.num_experts  # router
        else:
            per_layer += self.mlp_params_per_expert()
        total = self.num_layers * per_layer + d  # final norm
        if self.vocab_size:
            total += self.vocab_size * d  # input embedding
            if not self.tied_embeddings:
                total += self.vocab_size * d  # LM head
        if self.max_position_embeddings:
            total += self.max_position_embeddings * d
        if self.is_vit:
            total += 3 * self.patch_size ** 2 * d        # patch embed
            total += (self.seq_len + 1) * d              # cls + positions
            total += d * self.num_classes                # classifier head
        return total

    def non_expert_params(self) -> int:
        """Params NOT sharded by expert parallelism (reference
        hybrid_3d_moe.cpp:361-363 uses this to size the two-level grad sync).
        Zero for dense models, matching the reference stat files'
        ``Non_Expert_size:0`` convention."""
        if not self.is_moe:
            return 0
        layers = self.num_layers - self.moe_params.first_dense_layers
        return self.num_params() - layers * self.num_experts * \
            self.routed_expert_params()


# ---------------------------------------------------------------------- #
def _parse_card(name: str, raw: dict) -> ModelCard:
    moe = None
    if "moe_params" in raw:
        moe = MoEParams(**raw["moe_params"])
    known = {f.name for f in dataclasses.fields(ModelCard)}
    kwargs = {k: v for k, v in raw.items() if k in known and k != "moe_params"}
    for key in ("layer_kinds", "rope_yarn", "sparse_attention"):
        if key in kwargs:
            kwargs[key] = tuple(kwargs[key])
    return ModelCard(name=name, moe_params=moe, **kwargs)


def load_model_card(name: str, card_dir: Path | str | None = None) -> ModelCard:
    """Load ``<card_dir>/<name>.json``.  Accepts reference-format cards
    (base fields only) as well as extended rebuild cards."""
    d = Path(card_dir) if card_dir else _CARD_DIR
    path = d / f"{name}.json"
    with open(path) as f:
        raw = json.load(f)
    return _parse_card(name, raw)


def list_model_cards(card_dir: Path | str | None = None) -> list[str]:
    d = Path(card_dir) if card_dir else _CARD_DIR
    return sorted(p.stem for p in d.glob("*.json"))


def arch_name_from_stats_name(stats_name: str) -> str:
    """``llama3_8b_16_bfloat16`` → ``llama3_8b`` (the reference derives the
    arch-card path by stripping the trailing ``_<batch>_<dtype>`` suffixes,
    reference cpp/hybrid_parallel/hybrid_2d.cpp:214-216)."""
    parts = stats_name.split("_")
    if len(parts) < 3:
        raise ValueError(f"not a stats name: {stats_name!r}")
    return "_".join(parts[:-2])
