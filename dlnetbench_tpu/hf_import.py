"""HuggingFace-config import — architecture cards from HF model configs.

Rebuild of the reference's model-download layer (reference
python/download_models.py:21-36 registry, :41-109 download logic), rethought
for this framework: what every downstream layer consumes is the
*architecture card* (core/model_card.py), so the useful artifact of "import
a HF model" is a card, not a cache of safetensors.  This module maps a HF
config (``model_type`` gpt2 / llama / mistral / mixtral / phi4flash /
deepseek_v3 as Kimi-VL and Moonlight state it / qwen3_next / lfm2_moe /
smallthinker / laguna / minicpm_sala / vit) onto
``ModelCard`` fields and writes the card JSON.

Offline-first: hub access is attempted only when requested and is never
required — for the 9 registry models the committed cards double as the
fallback source, so ``--all`` works with zero egress (this box has none).
Weight downloads (the reference's non-``--config_only`` mode) are delegated
to ``transformers`` when explicitly asked for; stats generation here never
needs weights because parameter counts are analytic
(core/model_card.py::num_params, replacing the reference's
load-the-whole-model count at python/model_stats.py:63-83).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
from pathlib import Path
from typing import Any, Mapping

from dlnetbench_tpu.core.model_card import (
    ModelCard,
    MoEParams,
    load_model_card,
)

# Same 9 models as the reference registry (download_models.py:21-36),
# keyed by this repo's card names.
REGISTRY: dict[str, str] = {
    "gpt2_l": "gpt2-large",
    "gpt2_xl": "gpt2-xl",
    "llama3_8b": "meta-llama/Meta-Llama-3-8B",
    "llama3_70b": "meta-llama/Meta-Llama-3-70B",
    "minerva_7b": "sapienzanlp/Minerva-7B-instruct-v1.0",
    "mixtral_8x7b": "mistralai/Mixtral-8x7B-v0.1",
    "vit_b": "google/vit-base-patch16-224",
    "vit_l": "google/vit-large-patch16-224",
    "vit_h": "google/vit-huge-patch14-224-in21k",
}


def card_from_hf_config(name: str, cfg: Mapping[str, Any] | Any) -> ModelCard:
    """Map a HF config (a dict or a ``PretrainedConfig``) to a ModelCard.

    Dispatches on ``model_type``; covers the architecture families of the
    registry: gpt2 (learned positions, tied embeddings), llama/mistral
    (RoPE + SwiGLU + GQA), mixtral (adds MoE), vit (encoder + classifier).
    """
    if hasattr(cfg, "to_dict"):
        cfg = cfg.to_dict()
    if cfg.get("model_type") == "kimi_vl":   # the language model's part
        cfg = cfg["text_config"]
    mt = cfg.get("model_type", "")
    if mt == "deepseek_v3" or (not mt and "kv_lora_rank" in cfg):
        return _latent_moe_card(name, cfg)

    if mt == "gpt2":
        n_embd = int(cfg["n_embd"])
        n_positions = int(cfg.get("n_positions") or cfg.get("n_ctx") or 1024)
        return ModelCard(
            name=name,
            embed_dim=n_embd,
            num_heads=int(cfg["n_head"]),
            ff_dim=int(cfg.get("n_inner") or 4 * n_embd),
            seq_len=n_positions,
            num_decoder_blocks=int(cfg["n_layer"]),
            vocab_size=int(cfg["vocab_size"]),
            max_position_embeddings=n_positions,
            tied_embeddings=True,
        )

    if mt in ("llama", "mistral", "mixtral"):
        moe = None
        if mt == "mixtral":
            moe = MoEParams(
                num_experts=int(cfg["num_local_experts"]),
                num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            )
        heads = int(cfg["num_attention_heads"])
        return ModelCard(
            name=name,
            embed_dim=int(cfg["hidden_size"]),
            num_heads=heads,
            num_kv_heads=int(cfg.get("num_key_value_heads") or heads),
            ff_dim=int(cfg["intermediate_size"]),
            seq_len=int(cfg["max_position_embeddings"]),
            num_decoder_blocks=int(cfg["num_hidden_layers"]),
            vocab_size=int(cfg["vocab_size"]),
            gated_mlp=True,
            moe_params=moe,
        )

    if mt == "phi4flash":
        return _phi4flash_card(name, cfg)

    if mt == "qwen3_next":
        return _linear_moe_card(name, cfg)

    if mt == "lfm2_moe":
        return _conv_moe_card(name, cfg)

    if mt == "smallthinker" or (not mt and "moe_num_primary_experts" in cfg):
        return _swa_moe_card(name, cfg)

    if mt == "laguna":
        return _headgate_moe_card(name, cfg)

    if mt == "minicpm_sala":
        return _sparse_linear_card(name, cfg)

    if mt == "vit":
        image = int(cfg["image_size"])
        patch = int(cfg["patch_size"])
        return ModelCard(
            name=name,
            embed_dim=int(cfg["hidden_size"]),
            num_heads=int(cfg["num_attention_heads"]),
            ff_dim=int(cfg["intermediate_size"]),
            seq_len=(image // patch) ** 2 + 1,   # patches + [cls]
            num_encoder_blocks=int(cfg["num_hidden_layers"]),
            image_size=image,
            patch_size=patch,
            num_classes=int(cfg.get("num_labels") or 1000),
        )

    raise ValueError(f"unsupported HF model_type {mt!r} for {name}")


def phi4flash_layer_kinds(num_layers: int) -> tuple:
    """The SambaY layer map (arXiv:2507.06607; ``modeling_phi4flash.py``),
    which ``config.json`` does not state: the first half and one more
    layer alternate mamba and window attention, the layer after that is
    the one full-attention layer, the rest alternate gated memory units
    and cross attention over that layer's keys and values."""
    half = num_layers // 2
    kinds = []
    for li in range(num_layers):
        if li <= half:
            kinds.append("mamba" if li % 2 == 0 else "window")
        elif li == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if li % 2 == 0 else "cross")
    return tuple(kinds)


def _phi4flash_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "phi4flash"``.  The state-space sizes are not in
    the config: they are the Mamba family's defaults (E = 2 x hidden,
    N = 16, conv 4, dt rank hidden / 16) unless the config names them
    (``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``,
    ``mamba_dt_rank``)."""
    hidden = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    dt_rank = cfg.get("mamba_dt_rank", "auto")
    return ModelCard(
        name=name,
        embed_dim=hidden,
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads") or heads),
        ff_dim=int(cfg["intermediate_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=int(cfg["num_hidden_layers"]),
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_word_embeddings", True)),
        layer_kinds=phi4flash_layer_kinds(int(cfg["num_hidden_layers"])),
        sliding_window=int(cfg["sliding_window"]),
        differential_attention=True,
        ssm_inner=int(cfg.get("mamba_expand", 2)) * hidden,
        ssm_state=int(cfg.get("mamba_d_state", 16)),
        ssm_conv=int(cfg.get("mamba_d_conv", 4)),
        ssm_dt_rank=(hidden // 16 if dt_rank == "auto" else int(dt_rank)),
    )


def _latent_moe_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "deepseek_v3"`` as Kimi-VL-A3B and Moonlight state
    it (a ``config.json`` whose language model has ``kv_lora_rank``):
    latent attention in every layer, ``first_k_dense_replace`` dense
    layers and then routed experts beside shared ones, a sigmoid gate
    with a selection bias.  Refused, because no layer here computes
    them: a low-rank query (``q_lora_rank``), grouped selection
    (``n_group`` > 1), scaled RoPE and an expert layer every other
    layer (``moe_layer_freq`` > 1)."""
    unsupported = {k: cfg.get(k) for k, ok in (
        ("q_lora_rank", (None,)), ("n_group", (None, 1)),
        ("topk_group", (None, 1)), ("rope_scaling", (None,)),
        ("moe_layer_freq", (None, 1))) if cfg.get(k) not in ok}
    if unsupported:
        raise ValueError(f"{name}: latent-attention import has no "
                         f"{unsupported}")
    layers = int(cfg["num_hidden_layers"])
    return ModelCard(
        name=name,
        embed_dim=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg.get("num_key_value_heads")
                         or cfg["num_attention_heads"]),
        ff_dim=int(cfg["intermediate_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=layers,
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        layer_kinds=("mla",) * layers,
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rms_norm=True,
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        moe_params=MoEParams(
            num_experts=int(cfg["n_routed_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            scoring=str(cfg.get("scoring_func", "softmax")),
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            shared_experts=int(cfg.get("n_shared_experts") or 0),
            expert_ff_dim=int(cfg["moe_intermediate_size"]),
            first_dense_layers=int(cfg.get("first_k_dense_replace", 0)),
        ),
    )


def linear_layer_kinds(num_layers: int, full_interval: int) -> tuple:
    """``layer_types`` as the qwen3_next configuration derives it when
    ``config.json`` leaves it out: every ``full_interval``-th layer is
    gated softmax attention, the others Gated DeltaNet."""
    return tuple("gated" if (i + 1) % full_interval == 0 else "gdn"
                 for i in range(num_layers))


def _linear_moe_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "qwen3_next"``: Gated DeltaNet layers with one
    gated softmax attention layer a period, a zero-centred RMSNorm,
    softmax-routed experts in every layer beside a shared expert behind
    a sigmoid gate.  Refused, because no layer here computes them: a
    dense FFN in some layers (``mlp_only_layers``, ``decoder_sparse_step``
    > 1), unnormalised top-k weights, scaled RoPE, a sliding window, a
    shared expert whose width is no multiple of a routed one's.  The
    multi-token-prediction module is not in ``config.json``'s keys read
    here and is not built."""
    width = int(cfg["moe_intermediate_size"])
    shared = int(cfg.get("shared_expert_intermediate_size") or 0)
    unsupported = {k: cfg.get(k) for k, ok in (
        ("mlp_only_layers", (None, [], ())),
        ("decoder_sparse_step", (None, 1)),
        ("norm_topk_prob", (True,)), ("rope_scaling", (None,)),
        ("use_sliding_window", (None, False)),
        ("attention_bias", (None, False))) if cfg.get(k) not in ok}
    if shared % width:
        unsupported["shared_expert_intermediate_size"] = shared
    if unsupported:
        raise ValueError(f"{name}: linear-attention import has no "
                         f"{unsupported}")
    layers = int(cfg["num_hidden_layers"])
    heads = int(cfg["num_attention_heads"])
    head_dim = int(cfg.get("head_dim") or cfg["hidden_size"] // heads)
    kinds = tuple(
        {"linear_attention": "gdn", "full_attention": "gated"}[t]
        for t in cfg["layer_types"]) if cfg.get("layer_types") else \
        linear_layer_kinds(layers, int(cfg.get("full_attention_interval", 4)))
    return ModelCard(
        name=name,
        embed_dim=int(cfg["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads") or heads),
        ff_dim=int(cfg["intermediate_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=layers,
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        layer_kinds=kinds,
        attn_head_dim=head_dim,
        rope_dim=int(head_dim * float(cfg.get("partial_rotary_factor", 1.0))),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rms_norm=True,
        norm_plus_one=True,
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        linear_key_heads=int(cfg["linear_num_key_heads"]),
        linear_value_heads=int(cfg["linear_num_value_heads"]),
        linear_key_dim=int(cfg["linear_key_head_dim"]),
        linear_value_dim=int(cfg["linear_value_head_dim"]),
        linear_conv=int(cfg["linear_conv_kernel_dim"]),
        moe_params=MoEParams(
            num_experts=int(cfg["num_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            scoring="softmax",
            shared_experts=shared // width,
            shared_gate=bool(shared),
            expert_ff_dim=width,
        ),
    )


def _conv_moe_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "lfm2_moe"``: gated short convolutions with
    grouped-query softmax attention (a norm a head, RoPE on every lane,
    no output gate) where ``layer_types`` says ``full_attention``,
    ``num_dense_layers`` leading dense FFNs and then sigmoid-routed
    experts with a selection bias and no shared expert, the head tied
    to the embedding.  Refused, because no layer here computes them: a
    bias in the convolution, unnormalised top-k weights, a router
    without its selection bias, scaled RoPE."""
    unsupported = {k: cfg.get(k) for k, ok in (
        ("conv_bias", (None, False)), ("norm_topk_prob", (True,)),
        ("use_expert_bias", (True,)), ("rope_scaling", (None,)))
        if cfg.get(k) not in ok}
    if unsupported:
        raise ValueError(f"{name}: short-convolution import has no "
                         f"{unsupported}")
    heads = int(cfg["num_attention_heads"])
    kinds = tuple({"conv": "conv", "full_attention": "gated"}[t]
                  for t in cfg["layer_types"])
    return ModelCard(
        name=name,
        embed_dim=int(cfg["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads") or heads),
        ff_dim=int(cfg["intermediate_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=int(cfg["num_hidden_layers"]),
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_embedding", True)),
        layer_kinds=kinds,
        attn_output_gate=False,
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rms_norm=True,
        norm_eps=float(cfg.get("norm_eps", 1e-5)),
        short_conv=int(cfg["conv_L_cache"]),
        moe_params=MoEParams(
            num_experts=int(cfg["num_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            scoring="sigmoid",
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            expert_ff_dim=int(cfg["moe_intermediate_size"]),
            first_dense_layers=int(cfg.get("num_dense_layers", 0)),
        ),
    )


def _swa_moe_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "smallthinker"`` (or a ``config.json`` with its
    ``moe_num_primary_experts``): grouped-query softmax attention with
    no norm a head whose window and RoPE are a layer's
    (``sliding_window_layout`` and ``rope_layout``, 1 a layer with a
    window of ``sliding_window_size`` keys and RoPE, 0 a layer that
    sees every earlier key and has no position), a router that reads
    the layer's normed input before attention (softmax over the top-k
    logits), ReLU-gated experts in every layer and no shared one, an
    untied head.  Refused, because no layer here computes them: a layer
    whose two layouts differ (a window without RoPE, or RoPE over the
    whole sequence), a router without its softmax, unnormalised top-k
    weights, scaled RoPE."""
    unsupported = {k: cfg.get(k) for k, ok in (
        ("moe_primary_router_apply_softmax", (True,)),
        ("norm_topk_prob", (True,)), ("rope_scaling", (None,)))
        if cfg.get(k) not in ok}
    if unsupported:
        raise ValueError(f"{name}: window-and-full import has no "
                         f"{unsupported}")
    layers = int(cfg["num_hidden_layers"])
    window, turned = (list(cfg[k]) for k in ("sliding_window_layout",
                                             "rope_layout"))
    if not len(window) == len(turned) == layers:
        raise ValueError(f"{name}: {len(window)} sliding_window_layout and "
                         f"{len(turned)} rope_layout entries for {layers} "
                         f"layers")
    differ = [li for li, (w, r) in enumerate(zip(window, turned))
              if bool(w) != bool(r)]
    if differ:
        raise ValueError(f"{name}: layer {differ[0]} has "
                         f"sliding_window_layout {window[differ[0]]} and "
                         f"rope_layout {turned[differ[0]]}; no layer here "
                         f"has a window without RoPE or RoPE without one")
    heads = int(cfg["num_attention_heads"])
    return ModelCard(
        name=name,
        embed_dim=int(cfg["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads") or heads),
        ff_dim=int(cfg["moe_ffn_hidden_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=layers,
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        layer_kinds=tuple("swa" if w else "nope" for w in window),
        sliding_window=int(cfg["sliding_window_size"]),
        attn_head_dim=int(cfg.get("head_dim")
                          or cfg["hidden_size"] // heads),
        attn_output_gate=False,
        attn_head_norm=False,
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rms_norm=True,
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        moe_params=MoEParams(
            num_experts=int(cfg["moe_num_primary_experts"]),
            num_experts_per_tok=int(cfg["moe_num_active_primary_experts"]),
            scoring="softmax",
            expert_ff_dim=int(cfg["moe_ffn_hidden_size"]),
            early_router=True,
            activation="relu",
        ),
    )


_LAGUNA_KIND = {"full_attention": "gated", "sliding_attention": "swa"}


def _headgate_moe_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "laguna"``: grouped-query softmax attention with no
    norm a head, a window (``sliding_attention``: ``swa``) or every
    earlier key (``full_attention``: ``gated``) by ``layer_types``, the
    two kinds at query head counts of their own
    (``num_attention_heads_per_layer``) over the same key/value heads
    and with RoPE of their own (``rope_parameters`` by kind: base,
    ``partial_rotary_factor``, plain or YaRN), one sigmoid gate a head
    (``gating: "per-head"``), dense FFNs where ``mlp_layer_types`` /
    ``mlp_only_layers`` say so (leading layers only) and softmax-routed
    experts times ``moe_routed_scaling_factor`` beside one plain shared
    expert elsewhere, an untied head.  Refused, with the layer's number
    where it is a layer's: a per-layer list of another length than
    ``num_hidden_layers``, a head count the key/value heads do not
    divide or that differs within a kind, a dense layer after an expert
    layer, a gate that is not a head's, YaRN on a window layer or
    another scaling anywhere, a router with a soft cap, unnormalised
    weights, the router's weight on an expert's input."""
    layers = int(cfg["num_hidden_layers"])
    kv = int(cfg["num_key_value_heads"])
    kinds = list(cfg["layer_types"])
    per_layer = {"layer_types": kinds,
                 "num_attention_heads_per_layer":
                     list(cfg["num_attention_heads_per_layer"])}
    for key in ("mlp_layer_types", "gating_types"):
        if key in cfg:
            per_layer[key] = list(cfg[key])
    for key, values in per_layer.items():
        if len(values) != layers:
            raise ValueError(f"{name}: {len(values)} entries of {key} for "
                             f"{layers} layers")
    heads: dict = {}
    for li, (kind, h) in enumerate(zip(
            kinds, per_layer["num_attention_heads_per_layer"])):
        if kind not in _LAGUNA_KIND:
            raise ValueError(f"{name}: layer {li} is a {kind!r} layer; "
                             f"this import has {sorted(_LAGUNA_KIND)}")
        if h % kv:
            raise ValueError(f"{name}: layer {li} has {h} query heads, "
                             f"which {kv} key/value heads do not divide")
        if heads.setdefault(kind, h) != h:
            raise ValueError(f"{name}: layer {li} has {h} query heads "
                             f"where earlier {kind} layers have "
                             f"{heads[kind]}; a kind has one head count")
    dense = per_layer.get("mlp_layer_types") or [
        "dense" if li in cfg.get("mlp_only_layers", ()) else "sparse"
        for li in range(layers)]
    first_dense = dense.index("sparse") if "sparse" in dense else layers
    late = [li for li, f in enumerate(dense)
            if f != ("dense" if li < first_dense else "sparse")]
    if late:
        raise ValueError(f"{name}: layer {late[0]} has a {dense[late[0]]!r} "
                         f"FFN after an expert layer; dense layers lead")
    gates = set(per_layer.get("gating_types", ())) | {
        str(cfg.get("gating", "per-head")).replace("-", "_")}
    rope = cfg["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    unsupported = {k: v for k, v, ok in (
        ("gating", sorted(gates), gates == {"per_head"}),
        ("sliding_attention.rope_type", window.get("rope_type"),
         window.get("rope_type", "default") == "default"),
        ("full_attention.rope_type", full.get("rope_type"),
         full.get("rope_type", "default") in ("default", "yarn")),
        ("moe_router_logit_softcapping",
         cfg.get("moe_router_logit_softcapping"),
         not cfg.get("moe_router_logit_softcapping")),
        ("norm_topk_prob", cfg.get("norm_topk_prob"),
         cfg.get("norm_topk_prob", True)),
        ("moe_apply_router_weight_on_input",
         cfg.get("moe_apply_router_weight_on_input"),
         not cfg.get("moe_apply_router_weight_on_input")),
        ("attention_bias", cfg.get("attention_bias"),
         not cfg.get("attention_bias"))) if not ok}
    if unsupported:
        raise ValueError(f"{name}: head-gated import has no {unsupported}")
    dh = int(cfg.get("head_dim")
             or cfg["hidden_size"] // int(cfg["num_attention_heads"]))
    yarn = ()
    if full.get("rope_type") == "yarn":
        yarn = (float(full["factor"]),
                float(full["original_max_position_embeddings"]),
                float(full.get("beta_fast", 32)),
                float(full.get("beta_slow", 1)),
                float(full["attention_factor"]))
    full_heads = heads.get("full_attention",
                           int(cfg["num_attention_heads"]))
    width = int(cfg["moe_intermediate_size"])
    shared = int(cfg.get("shared_expert_intermediate_size", 0))
    if shared % width:
        raise ValueError(f"{name}: a shared expert of {shared} beside "
                         f"experts of {width}")
    return ModelCard(
        name=name,
        embed_dim=int(cfg["hidden_size"]),
        num_heads=full_heads,
        num_kv_heads=kv,
        ff_dim=int(cfg["intermediate_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=layers,
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        layer_kinds=tuple(_LAGUNA_KIND[k] for k in kinds),
        sliding_window=int(cfg["sliding_window"]),
        attn_head_dim=dh,
        attn_output_gate="head",
        attn_head_norm=False,
        rope_theta=float(full["rope_theta"]),
        rope_dim=int(dh * float(full.get("partial_rotary_factor", 1))),
        rope_yarn=yarn,
        window_heads=heads.get("sliding_attention", full_heads),
        window_rope_theta=float(window["rope_theta"]),
        window_rope_dim=int(
            dh * float(window.get("partial_rotary_factor", 1))),
        rms_norm=True,
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        moe_params=MoEParams(
            num_experts=int(cfg["num_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            scoring="softmax",
            routed_scale=float(cfg.get("moe_routed_scaling_factor", 1.0)),
            shared_experts=shared // width,
            expert_ff_dim=width,
            first_dense_layers=first_dense,
        ),
    )


_SALA_KIND = {"minicpm4": "sparse", "lightning-attn": "lightning"}
# MiniCPM4-8B's published ``sparse_config``, in
# ``ops/sparse_attention.SparseSizes``' order: the MiniCPM-SALA config
# names the same ``minicpm4`` mixer and does not repeat its sizes
_MINICPM4_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                    "topk": 64, "window_size": 2048, "init_blocks": 1,
                    "dense_len": 8192}


def _sparse_linear_card(name: str, cfg: Mapping[str, Any]) -> ModelCard:
    """``model_type: "minicpm_sala"``: by ``mixer_types`` a ``minicpm4``
    layer (``sparse``: grouped softmax attention without position over
    the key blocks a token selects, a norm a head, a gate of the head's
    width) or a ``lightning-attn`` layer (``lightning``: linear
    attention with a constant decay a head, RoPE, a norm a head on
    queries, keys and the output, a gate), dense SwiGLUs, an untied
    head, and MiniCPM's three scalars (``scale_emb``; ``scale_depth /
    sqrt(num_hidden_layers)``; ``dim_model_base / hidden_size``).  The
    selection's sizes are the config's ``sparse_config`` where it has
    one, else MiniCPM4's.  Refused: a list of another length than
    ``num_hidden_layers``, an unknown mixer (by its layer's number),
    lightning keys and values at another head count than its queries,
    another ``lightning_scale``, RoPE on a sparse layer, a layer without
    its norms or gates, a bias."""
    layers = int(cfg["num_hidden_layers"])
    mixers = list(cfg["mixer_types"])
    if len(mixers) != layers:
        raise ValueError(f"{name}: {len(mixers)} entries of mixer_types "
                         f"for {layers} layers")
    for li, m in enumerate(mixers):
        if m not in _SALA_KIND:
            raise ValueError(f"{name}: layer {li} is a {m!r} mixer; this "
                             f"import has {sorted(_SALA_KIND)}")
    nh = int(cfg["lightning_nh"])
    unsupported = {k: v for k, v, ok in (
        ("lightning_nkv", cfg.get("lightning_nkv"),
         int(cfg.get("lightning_nkv", nh)) == nh),
        ("lightning_scale", cfg.get("lightning_scale"),
         cfg.get("lightning_scale", "1/sqrt(d)") == "1/sqrt(d)"),
        ("lightning_use_rope", cfg.get("lightning_use_rope"),
         cfg.get("lightning_use_rope", True)),
        ("attn_use_rope", cfg.get("attn_use_rope"),
         not cfg.get("attn_use_rope", False)),
        ("qk_norm", cfg.get("qk_norm"), cfg.get("qk_norm", True)),
        ("use_output_norm", cfg.get("use_output_norm"),
         cfg.get("use_output_norm", True)),
        ("use_output_gate", cfg.get("use_output_gate"),
         cfg.get("use_output_gate", True)),
        ("attn_use_output_gate", cfg.get("attn_use_output_gate"),
         cfg.get("attn_use_output_gate", True)),
        ("hidden_act", cfg.get("hidden_act"),
         cfg.get("hidden_act", "silu") == "silu"),
        ("attention_bias", cfg.get("attention_bias"),
         not cfg.get("attention_bias"))) if not ok}
    if unsupported:
        raise ValueError(f"{name}: sparse-and-linear import has no "
                         f"{unsupported}")
    hidden = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    sizes = {**_MINICPM4_SPARSE, **cfg.get("sparse_config", {})}
    dh = int(cfg["lightning_head_dim"])
    return ModelCard(
        name=name,
        embed_dim=hidden,
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads") or heads),
        ff_dim=int(cfg["intermediate_size"]),
        seq_len=int(cfg["max_position_embeddings"]),
        num_decoder_blocks=layers,
        vocab_size=int(cfg["vocab_size"]),
        gated_mlp=True,
        tied_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        layer_kinds=tuple(_SALA_KIND[m] for m in mixers),
        attn_head_dim=int(cfg.get("head_dim") or hidden // heads),
        attn_output_gate=True,
        attn_head_norm=True,
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        rms_norm=True,
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
        linear_key_heads=nh,
        linear_value_heads=nh,
        linear_key_dim=dh,
        linear_value_dim=dh,
        embed_scale=float(cfg.get("scale_emb", 1.0)),
        residual_scale=float(cfg.get("scale_depth", 1.0))
        / math.sqrt(layers),
        logit_scale=float(cfg.get("dim_model_base", hidden)) / hidden,
        sparse_attention=tuple(int(sizes[k]) for k in _MINICPM4_SPARSE),
        published_layers=layers,
    )


def card_to_json(card: ModelCard) -> dict:
    """Card -> the on-disk JSON schema (reference models/*.json shape plus
    the rebuild's extended fields; fields at their default are elided)."""
    out: dict[str, Any] = {}
    for f in dataclasses.fields(ModelCard):
        if f.name in ("name", "moe_params"):
            continue
        v = getattr(card, f.name)
        if v != f.default:
            out[f.name] = list(v) if isinstance(v, tuple) else v
    if card.moe_params is not None:
        defaults = MoEParams(0, 0)
        out["moe_params"] = {
            f.name: getattr(card.moe_params, f.name)
            for f in dataclasses.fields(MoEParams)
            if f.name in ("num_experts", "num_experts_per_tok")
            or getattr(card.moe_params, f.name)
            != getattr(defaults, f.name)}
    return out


def fetch_card(name: str, *, allow_hub: bool = False) -> tuple[ModelCard, str]:
    """Return (card, source) for a registry model.

    source is "hub" when a live HF config was fetched and mapped,
    "fallback" when the committed card was used (no egress / no access —
    the gated-model case the reference handles with login, :33-35).
    """
    if name not in REGISTRY:
        raise KeyError(f"unknown model {name!r}; registry: {sorted(REGISTRY)}")
    if allow_hub:
        try:
            from transformers import AutoConfig
            cfg = AutoConfig.from_pretrained(REGISTRY[name])
            return card_from_hf_config(name, cfg), "hub"
        except Exception as e:  # no net, gated repo, missing transformers
            print(f"[hf_import] hub fetch failed for {name} ({e!r}); "
                  f"using committed card", file=sys.stderr)
    return load_model_card(name), "fallback"


def import_model(name: str, out_dir: Path, *, allow_hub: bool = False,
                 weights: bool = False) -> Path:
    card, source = fetch_card(name, allow_hub=allow_hub)
    if weights and allow_hub:
        try:
            from transformers import AutoModel
            AutoModel.from_pretrained(REGISTRY[name])  # populate HF cache
        except Exception as e:  # gated / offline: card still gets written
            print(f"[hf_import] weight fetch failed for {name} ({e!r})",
                  file=sys.stderr)
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{name}.json"
    with open(path, "w") as f:
        json.dump(card_to_json(card), f, indent=2)
        f.write("\n")
    print(f"{name}: wrote {path} (source: {source})")
    return path


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="Import HF model configs as architecture cards "
                    "(reference python/download_models.py equivalent)")
    p.add_argument("models", nargs="*", help="registry names (see --list)")
    p.add_argument("--list", action="store_true", dest="list_models")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out_dir", type=Path,
                   default=Path(__file__).parent / "data" / "models")
    p.add_argument("--hub", action="store_true",
                   help="attempt live HF hub fetch before falling back")
    p.add_argument("--weights", action="store_true",
                   help="also populate the local HF weight cache (needs --hub)")
    args = p.parse_args(argv)

    if args.weights and not args.hub:
        p.error("--weights requires --hub (weight fetch needs hub access)")
    if args.list_models:
        for name, hf in REGISTRY.items():
            print(f"{name:16s} {hf}")
        return 0
    names = sorted(REGISTRY) if args.all else args.models
    if not names:
        p.error("no models given (use --all or --list)")
    for name in names:
        import_model(name, args.out_dir, allow_hub=args.hub,
                     weights=args.weights)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
