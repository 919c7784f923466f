"""HF-config -> architecture-card mapping (hf_import.py).

Config dicts below mirror the public HF configs of the registry models;
mapping them must reproduce the committed cards field-for-field (reference
python/download_models.py caches exactly these configs).
"""
from __future__ import annotations

import json

import pytest

from dlnetbench_tpu.core.model_card import load_model_card
from dlnetbench_tpu import hf_import


GPT2_L = {"model_type": "gpt2", "n_embd": 1280, "n_head": 20, "n_layer": 36,
          "n_positions": 1024, "n_inner": None, "vocab_size": 50257}

LLAMA3_8B = {"model_type": "llama", "hidden_size": 4096,
             "num_attention_heads": 32, "num_key_value_heads": 8,
             "intermediate_size": 14336, "max_position_embeddings": 8192,
             "num_hidden_layers": 32, "vocab_size": 128256}

MIXTRAL = {"model_type": "mixtral", "hidden_size": 4096,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "intermediate_size": 14336, "max_position_embeddings": 32768,
           "num_hidden_layers": 32, "vocab_size": 32000,
           "num_local_experts": 8, "num_experts_per_tok": 2}

VIT_B = {"model_type": "vit", "hidden_size": 768, "num_attention_heads": 12,
         "intermediate_size": 3072, "num_hidden_layers": 12,
         "image_size": 224, "patch_size": 16, "num_labels": 1000}


# the catalog's config of microsoft/Phi-4-mini-flash-reasoning
PHI4FLASH = {"model_type": "phi4flash", "hidden_size": 2560,
             "intermediate_size": 10240, "num_attention_heads": 40,
             "num_key_value_heads": 20, "num_hidden_layers": 32,
             "max_position_embeddings": 262144, "sliding_window": 512,
             "mb_per_layer": 2, "layer_norm_eps": 1e-05,
             "tie_word_embeddings": True, "vocab_size": 200064}


# the catalog's config of moonshotai/Kimi-VL-A3B-Instruct: the language
# model's settings, with no model_type of their own
KIMI_VL = {"vocab_size": 163840, "max_position_embeddings": 131072,
           "hidden_size": 2048, "intermediate_size": 11264,
           "moe_intermediate_size": 1408, "num_hidden_layers": 27,
           "num_attention_heads": 16, "n_shared_experts": 2,
           "n_routed_experts": 64, "ep_size": 1,
           "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
           "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
           "qk_nope_head_dim": 128, "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
           "moe_layer_freq": 1, "first_k_dense_replace": 1,
           "norm_topk_prob": True, "scoring_func": "sigmoid",
           "seq_aux": True, "num_key_value_heads": 16,
           "hidden_act": "silu", "rms_norm_eps": 1e-05,
           "rope_theta": 800000, "rope_scaling": None,
           "attention_bias": False, "tie_word_embeddings": False}


# the catalog's config of Qwen/Qwen3-Next-80B-A3B-Instruct
QWEN3_NEXT = {"decoder_sparse_step": 1, "full_attention_interval": 4,
              "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
              "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
              "linear_key_head_dim": 128, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_value_head_dim": 128,
              "max_position_embeddings": 262144, "mlp_only_layers": [],
              "model_type": "qwen3_next", "moe_intermediate_size": 512,
              "norm_topk_prob": True, "num_attention_heads": 16,
              "num_experts": 512, "num_experts_per_tok": 10,
              "num_hidden_layers": 48, "num_key_value_heads": 2,
              "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
              "rope_scaling": None, "rope_theta": 10000000,
              "shared_expert_intermediate_size": 512,
              "tie_word_embeddings": False, "use_sliding_window": False,
              "vocab_size": 151936}
# the catalog row's config of LFM2-8B-A1B
LFM2_MOE = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": (["conv"] * 2 + (["full_attention"] + ["conv"] * 3) * 4
                    + ["full_attention", "conv", "conv", "full_attention",
                       "conv", "conv"]),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


@pytest.mark.parametrize("name,cfg", [
    ("gpt2_l", GPT2_L), ("llama3_8b", LLAMA3_8B),
    ("mixtral_8x7b", MIXTRAL), ("vit_b", VIT_B),
    ("phi4_mini_flash_reasoning", PHI4FLASH),
    ("kimi_vl_a3b", KIMI_VL),
    ("kimi_vl_a3b", {"model_type": "kimi_vl", "text_config": {
        **KIMI_VL, "model_type": "deepseek_v3"}}),
    ("qwen3_next_80b_a3b", QWEN3_NEXT),
    ("qwen3_next_80b_a3b", {**QWEN3_NEXT, "layer_types": (
        ["linear_attention"] * 3 + ["full_attention"]) * 12}),
    ("lfm2_8b_a1b", LFM2_MOE),
])
def test_mapping_reproduces_committed_card(name, cfg):
    got = hf_import.card_from_hf_config(name, cfg)
    want = load_model_card(name)
    assert got == want


def test_gpt2_default_inner_is_4x():
    card = hf_import.card_from_hf_config("gpt2_l", GPT2_L)
    assert card.ff_dim == 4 * 1280 and card.tied_embeddings


def test_unknown_model_type_raises():
    with pytest.raises(ValueError, match="model_type"):
        hf_import.card_from_hf_config("x", {"model_type": "mamba"})
    with pytest.raises(KeyError):
        hf_import.fetch_card("not_a_model")


def test_card_json_roundtrip(tmp_path):
    """import_model (offline fallback) writes a card that load_model_card
    parses back to the identical dataclass, for every registry model."""
    for name in hf_import.REGISTRY:
        hf_import.import_model(name, tmp_path)
        assert load_model_card(name, tmp_path) == load_model_card(name)


def test_cli_list_and_all(tmp_path, capsys):
    assert hf_import.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "meta-llama/Meta-Llama-3-8B" in out and "gpt2-large" in out
    assert hf_import.main(["--all", "--out_dir", str(tmp_path)]) == 0
    written = sorted(p.stem for p in tmp_path.glob("*.json"))
    assert written == sorted(hf_import.REGISTRY)
    # moe block survives the roundtrip as nested JSON
    raw = json.loads((tmp_path / "mixtral_8x7b.json").read_text())
    assert raw["moe_params"]["num_experts_per_tok"] == 2


def test_phi4flash_layer_map_is_the_published_one():
    kinds = hf_import.phi4flash_layer_kinds(32)
    assert kinds[:17:2] == ("mamba",) * 9 and kinds[1:16:2] == ("window",) * 8
    assert kinds[17] == "full"
    assert kinds[18::2] == ("gmu",) * 7 and kinds[19::2] == ("cross",) * 7
    card = hf_import.card_from_hf_config("x", PHI4FLASH)
    assert abs(card.num_params() - 3.85e9) / 3.85e9 < 0.01


def test_latent_moe_card_states_the_gate_and_the_shared_experts():
    card = hf_import.card_from_hf_config("kimi_vl_a3b", KIMI_VL)
    moe = card.moe_params
    assert (moe.num_experts, moe.num_experts_per_tok, moe.scoring,
            moe.routed_scale, moe.shared_experts, moe.expert_ff_dim,
            moe.first_dense_layers) == (64, 6, "sigmoid", 2.446, 2, 1408, 1)
    assert card.layer_kinds == ("mla",) * 27 and card.rms_norm
    assert (card.kv_lora_rank, card.qk_nope_head_dim,
            card.qk_rope_head_dim, card.v_head_dim) == (512, 128, 64, 128)
    assert not card.tied_embeddings and card.rope_theta == 800000.0
    # 13.76 M of attention and 584.8 M of experts a layer: 15.96 B
    assert card.mixer_params("mla") == 13763072
    assert card.ffn_params(0) == 3 * 2048 * 11264
    assert card.ffn_params(1) == 66 * 3 * 2048 * 1408 + 2048 * 64 + 64
    assert card.num_params() == pytest.approx(15.96e9, rel=1e-3)
    json_card = hf_import.card_to_json(card)
    assert json_card["moe_params"]["scoring"] == "sigmoid"
    # Mixtral's card keeps its two keys
    assert hf_import.card_to_json(load_model_card("mixtral_8x7b"))[
        "moe_params"] == {"num_experts": 8, "num_experts_per_tok": 2}


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("moe_layer_freq", 2),
    ("rope_scaling", {"type": "yarn", "factor": 40})])
def test_latent_moe_import_refuses_what_no_layer_computes(key, value):
    with pytest.raises(ValueError, match=key):
        hf_import.card_from_hf_config("x", {**KIMI_VL, key: value})


def test_linear_moe_card_states_the_layers_the_gate_and_the_lanes():
    """``layer_types`` derived from ``full_attention_interval`` where
    the file leaves them out; the head's own width, the rotated lanes,
    the zero-centred norm and the shared expert's gate on the card."""
    card = hf_import.card_from_hf_config("qwen3_next_80b_a3b", QWEN3_NEXT)
    assert card.layer_kinds == ("gdn", "gdn", "gdn", "gated") * 12
    assert hf_import.linear_layer_kinds(6, 3) \
        == ("gdn", "gdn", "gated") * 2
    moe = card.moe_params
    assert (moe.num_experts, moe.num_experts_per_tok, moe.scoring,
            moe.shared_experts, moe.shared_gate, moe.expert_ff_dim,
            moe.first_dense_layers) == (512, 10, "softmax", 1, True, 512, 0)
    assert (card.attn_head_dim, card.rope_dim, card.kv_heads) == (256, 64, 2)
    assert (card.linear_key_heads, card.linear_value_heads,
            card.linear_key_dim, card.linear_value_dim, card.linear_conv) \
        == (16, 32, 128, 128, 4)
    assert card.rms_norm and card.norm_plus_one and card.norm_eps == 1e-6
    assert not card.tied_embeddings and card.rope_theta == 1e7
    # the issue's table: 33.72 M a linear mixer, 27.26 M the gated
    # attention, 4.20 M + 512 x 3.146 M an expert layer: 79.7 B
    assert card.mixer_params("gdn") == pytest.approx(33.72e6, rel=1e-3)
    assert card.mixer_params("gated") == pytest.approx(27.26e6, rel=1e-3)
    assert card.ffn_params(0) == 513 * 3 * 2048 * 512 + 2048 * 512 + 2048
    assert card.num_params() == pytest.approx(79.7e9, rel=1e-3)
    for key, value in (("mlp_only_layers", [3]), ("norm_topk_prob", False),
                       ("decoder_sparse_step", 2),
                       ("shared_expert_intermediate_size", 700),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match="linear-attention import"):
            hf_import.card_from_hf_config("x", {**QWEN3_NEXT, key: value})


def test_conv_moe_card_states_the_mixers_the_gate_and_the_tie():
    """``layer_types`` to mixer kinds, ``num_dense_layers`` to the
    leading dense FFNs, the attention's missing output gate, the conv's
    taps and the tie on the card; the card written out and read back is
    the card (a field at false where the default is true is kept)."""
    card = hf_import.card_from_hf_config("lfm2_8b_a1b", LFM2_MOE)
    attention = [2, 6, 10, 14, 18, 21]
    assert [i for i, k in enumerate(card.layer_kinds) if k == "gated"] \
        == attention
    assert set(card.layer_kinds) == {"conv", "gated"}
    moe = card.moe_params
    assert (moe.num_experts, moe.num_experts_per_tok, moe.scoring,
            moe.routed_scale, moe.shared_experts, moe.expert_ff_dim,
            moe.first_dense_layers) == (32, 4, "sigmoid", 1.0, 0, 1792, 2)
    assert (card.head_dim, card.attn_head_dim, card.rope_dim,
            card.kv_heads, card.short_conv) == (64, 0, 0, 8, 3)
    assert not card.attn_output_gate and card.tied_embeddings
    assert card.rms_norm and not card.norm_plus_one
    assert card.norm_eps == 1e-5 and card.rope_theta == 1e6
    # the issue's table: 16.78 M a conv mixer, 10.49 M the attention,
    # 44.04 M the dense FFN, 352.39 M an expert layer; 8.34 B tied
    assert card.mixer_params("conv") == pytest.approx(16.78e6, rel=1e-3)
    assert card.mixer_params("gated") == pytest.approx(10.49e6, rel=1e-3)
    assert card.ffn_params(1) == 3 * 2048 * 7168
    assert card.ffn_params(2) == 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert card.num_params() == pytest.approx(8.34e9, rel=1e-3)
    raw = hf_import.card_to_json(card)
    assert raw["attn_output_gate"] is False and raw["short_conv"] == 3
    assert "attn_output_gate" not in hf_import.card_to_json(
        load_model_card("qwen3_next_80b_a3b"))
    for key, value in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match="short-convolution import"):
            hf_import.card_from_hf_config("x", {**LFM2_MOE, key: value})


def _smallthinker_row():
    """The keys of the catalog's row for SmallThinker-21BA3B-Instruct
    (``config.json`` as published)."""
    layout = [0, 1, 1, 1] * 13
    return {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": list(layout), "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": list(layout),
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}


def test_swa_moe_card_states_the_layers_the_router_and_the_activation():
    card = hf_import.card_from_hf_config("smallthinker_21b_a3b",
                                         _smallthinker_row())
    assert card == load_model_card("smallthinker_21b_a3b")
    assert card == hf_import.card_from_hf_config(
        "smallthinker_21b_a3b",
        {**_smallthinker_row(), "model_type": "smallthinker"})
    kinds = card.layer_kinds
    assert len(kinds) == 52 and kinds[:5] == ("nope", "swa", "swa", "swa",
                                              "nope")
    assert [i for i, k in enumerate(kinds) if k == "nope"] \
        == list(range(0, 52, 4))
    assert (card.embed_dim, card.num_heads, card.kv_heads,
            card.attn_head_dim, card.sliding_window, card.rope_theta,
            card.norm_eps, card.vocab_size, card.seq_len) == (
        2560, 28, 4, 128, 4096, 1.5e6, 1e-6, 151936, 16384)
    assert not card.attn_output_gate and not card.attn_head_norm
    assert card.rms_norm and not card.tied_embeddings
    moe = card.moe_params
    assert (moe.num_experts, moe.num_experts_per_tok, moe.expert_ff_dim,
            moe.scoring, moe.shared_experts, moe.first_dense_layers,
            moe.early_router, moe.activation) == (
        64, 6, 768, "softmax", 0, 0, True, "relu")
    # the published "21B" and "A3B"
    assert card.num_params() == pytest.approx(21.507e9, rel=1e-4)
    assert hf_import.card_to_json(card)["moe_params"] == {
        "num_experts": 64, "num_experts_per_tok": 6, "expert_ff_dim": 768,
        "early_router": True, "activation": "relu"}


@pytest.mark.parametrize("over,match", [
    ({"rope_layout": [0, 1, 0, 1] + [0, 1, 1, 1] * 12}, "layer 2 has"),
    ({"sliding_window_layout": [1] + [1, 1, 1] + [0, 1, 1, 1] * 12},
     "layer 0 has"),
    ({"rope_layout": [0, 1, 1, 1]}, "52 sliding_window_layout and 4"),
    ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling")])
def test_swa_moe_import_refuses_what_no_layer_computes(over, match):
    """Layouts that differ in a layer (a window without RoPE, RoPE over
    the whole sequence) are refused with the layer's number; so are a
    router without its softmax, unnormalised weights and scaled RoPE."""
    with pytest.raises(ValueError, match=match):
        hf_import.card_from_hf_config("x", {**_smallthinker_row(), **over})


def _laguna_row():
    """The keys of the catalog's row for Laguna-S-2.1 (``config.json``
    as published)."""
    period = ["full_attention"] + ["sliding_attention"] * 3
    return {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 1048576,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": period * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}


def test_headgate_moe_card_states_the_head_counts_the_gate_and_the_ropes():
    card = hf_import.card_from_hf_config("laguna_s_2_1", _laguna_row())
    assert card == load_model_card("laguna_s_2_1")
    kinds = card.layer_kinds
    assert len(kinds) == 48 and kinds[:5] == ("gated", "swa", "swa", "swa",
                                              "gated")
    assert [i for i, k in enumerate(kinds) if k == "gated"] \
        == list(range(0, 48, 4))
    assert (card.embed_dim, card.num_heads, card.window_heads,
            card.kv_heads, card.attn_head_dim, card.sliding_window,
            card.ff_dim, card.norm_eps, card.vocab_size, card.seq_len) == (
        3072, 48, 72, 8, 128, 512, 12288, 1e-6, 100352, 1048576)
    assert card.attn_output_gate == "head" and not card.attn_head_norm
    assert card.rms_norm and not card.tied_embeddings
    assert (card.rope_theta, card.rope_dim, card.window_rope_theta,
            card.window_rope_dim) == (5e5, 64, 1e4, 128)
    assert card.rope_yarn == (128.0, 8192.0, 32.0, 1.0, 1.4852030263919618)
    moe = card.moe_params
    assert (moe.num_experts, moe.num_experts_per_tok, moe.expert_ff_dim,
            moe.scoring, moe.routed_scale, moe.shared_experts,
            moe.shared_gate, moe.first_dense_layers, moe.early_router,
            moe.activation) == (
        256, 10, 1024, "softmax", 2.5, 1, False, 1, False, "silu")
    # the published "~118B"
    assert card.num_params() == pytest.approx(117.56e9, rel=1e-4)
    raw = hf_import.card_to_json(card)
    assert raw["attn_output_gate"] == "head" and raw["window_heads"] == 72
    assert raw["rope_yarn"] == [128.0, 8192.0, 32.0, 1.0,
                                1.4852030263919618]
    assert raw["moe_params"] == {
        "num_experts": 256, "num_experts_per_tok": 10, "routed_scale": 2.5,
        "shared_experts": 1, "expert_ff_dim": 1024,
        "first_dense_layers": 1}
    # without the per-layer FFN list, mlp_only_layers says the same
    row = {k: v for k, v in _laguna_row().items()
           if k not in ("mlp_layer_types", "gating_types")}
    assert hf_import.card_from_hf_config("laguna_s_2_1", row) == card
    # plain RoPE on the full layers is a card without YaRN's numbers
    rope = _laguna_row()["rope_parameters"]
    plain = {**rope, "full_attention": {"rope_type": "default",
                                        "rope_theta": 500000}}
    other = hf_import.card_from_hf_config(
        "x", {**_laguna_row(), "rope_parameters": plain})
    assert other.rope_yarn == () and other.rope_dim == 128


def _rope_with(kind, **over):
    rope = _laguna_row()["rope_parameters"]
    return {"rope_parameters": {**rope, kind: {**rope[kind], **over}}}


@pytest.mark.parametrize("over,match", [
    ({"layer_types": ["full_attention"] * 47},
     "47 entries of layer_types for 48 layers"),
    ({"num_attention_heads_per_layer": [48, 72, 72, 72] * 11},
     "44 entries of num_attention_heads_per_layer"),
    ({"mlp_layer_types": ["dense"]}, "1 entries of mlp_layer_types"),
    ({"num_attention_heads_per_layer": [48, 72, 70, 72] + [48, 72, 72, 72]
      * 11}, "layer 2 has 70 query heads, which 8"),
    ({"num_attention_heads_per_layer": [48, 72, 72, 72, 48, 64, 72, 72]
      + [48, 72, 72, 72] * 10}, "layer 5 has 64 query heads where earlier"),
    ({"layer_types": ["chunked_attention"] + ["sliding_attention"] * 47},
     "layer 0 is a 'chunked_attention' layer"),
    ({"mlp_layer_types": ["dense", "sparse", "dense"] + ["sparse"] * 45},
     "layer 2 has a 'dense' FFN after an expert layer"),
    ({"gating": "per-lane"}, "gating"),
    (_rope_with("sliding_attention", rope_type="yarn"),
     "sliding_attention.rope_type"),
    (_rope_with("full_attention", rope_type="llama3"),
     "full_attention.rope_type"),
    ({"moe_router_logit_softcapping": 30.0}, "softcapping"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"moe_apply_router_weight_on_input": True}, "weight_on_input"),
    ({"shared_expert_intermediate_size": 1536}, "a shared expert of 1536")])
def test_headgate_moe_import_refuses_what_no_layer_computes(over, match):
    """A per-layer list of another length than the layers, a head count
    the key/value heads do not divide or that differs within a kind
    (with the layer's number), an unknown kind of layer, a dense layer
    after an expert layer, another gate, scaled RoPE that no layer
    turns by, a capped router, unnormalised weights, the weight on an
    expert's input."""
    with pytest.raises(ValueError, match=match):
        hf_import.card_from_hf_config("x", {**_laguna_row(), **over})


def _minicpm_sala_row():
    """The keys of the catalog's row for MiniCPM-SALA (``config.json`` as
    published)."""
    sparse_at = {0, 9, 16, 17, 22, 29, 30, 31}
    return {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "mixer_types": ["minicpm4" if li in sparse_at else "lightning-attn"
                        for li in range(32)],
        "num_attention_heads": 32, "num_hidden_layers": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
        "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True}


def test_sparse_linear_card_states_the_mixers_the_scalars_and_the_sizes():
    import math
    card = hf_import.card_from_hf_config("minicpm_sala", _minicpm_sala_row())
    assert card == load_model_card("minicpm_sala")
    kinds = card.layer_kinds
    assert len(kinds) == 32
    assert [i for i, k in enumerate(kinds) if k == "sparse"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert set(kinds) == {"sparse", "lightning"}
    assert (card.embed_dim, card.num_heads, card.kv_heads,
            card.attn_head_dim, card.ff_dim, card.vocab_size) \
        == (4096, 32, 2, 128, 16384, 73448)
    assert (card.linear_key_heads, card.linear_value_heads,
            card.linear_key_dim, card.linear_value_dim) == (32, 32, 128, 128)
    assert card.embed_scale == 12.0 and card.logit_scale == 256 / 4096
    assert card.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert card.published_layers == 32
    # MiniCPM4's sparse_config where the row gives none; the row's own
    # where it does
    assert card.sparse_attention == (32, 16, 64, 64, 2048, 1, 8192)
    own = hf_import.card_from_hf_config("x", {
        **_minicpm_sala_row(), "sparse_config": {"topk": 96,
                                                 "dense_len": 4096}})
    assert own.sparse_attention == (32, 16, 64, 96, 2048, 1, 4096)
    assert (card.attn_output_gate, card.attn_head_norm, card.rms_norm,
            card.tied_embeddings, card.norm_eps) \
        == (True, True, True, False, 1e-6)
    assert card.num_params() == pytest.approx(9.48e9, rel=5e-3)
    assert hf_import.card_to_json(card)["sparse_attention"] \
        == [32, 16, 64, 64, 2048, 1, 8192]


@pytest.mark.parametrize("over,match", [
    ({"mixer_types": ["minicpm4"] * 31}, "31 entries of mixer_types"),
    ({"mixer_types": ["minicpm4"] * 5 + ["mamba2"] + ["minicpm4"] * 26},
     "layer 5 is a 'mamba2' mixer"),
    ({"lightning_nkv": 8}, "lightning_nkv"),
    ({"lightning_scale": "1/d"}, "lightning_scale"),
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"lightning_use_rope": False}, "lightning_use_rope"),
    ({"qk_norm": False}, "qk_norm"),
    ({"use_output_norm": False}, "use_output_norm"),
    ({"attn_use_output_gate": False}, "attn_use_output_gate"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias")])
def test_sparse_linear_import_refuses_what_no_layer_computes(over, match):
    """An unknown mixer is refused by its layer's number; so are a list
    of another length, grouped lightning keys, another scale, RoPE on a
    sparse layer and a layer without its norms or gates."""
    with pytest.raises(ValueError, match=match):
        hf_import.card_from_hf_config("x", {**_minicpm_sala_row(), **over})
