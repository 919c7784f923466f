"""Model FLOPs a token of one training step of the latent-attention
expert decoder as this chip runs it: what the forward and backward
passes require, recomputation not counted, causal attention counted once
(the keys a query sees, averaged), and of the routed experts the share
that is held here (a token's ``top_k * held / experts`` of them, in
expectation)."""
from __future__ import annotations


def matmul_params_per_token(arch: dict) -> float:
    """Weights a token is multiplied with (the embedding is a lookup,
    the untied head a matmul)."""
    d, h, r = arch["embed_dim"], arch["num_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    attn = (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d)
    routed = arch["top_k"] * arch["held"][1] / arch["num_experts"]
    expert = (3 * d * (routed * arch["expert_ff_dim"]
                       + arch["shared_ff_dim"])
              + d * arch["num_experts"])
    dense = arch["first_dense"]
    return (arch["num_layers"] * attn + dense * 3 * d * arch["ff_dim"]
            + (arch["num_layers"] - dense) * expert
            + d * arch["vocab_size"])


def forward_flops_per_token(arch: dict, seq: int) -> float:
    keys = (seq + 1) / 2
    lanes = (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
             + arch["v_head_dim"])
    scores = arch["num_layers"] * arch["num_heads"] * 2 * keys * lanes
    return 2.0 * matmul_params_per_token(arch) + scores


def flops_per_token(arch: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(arch, seq)
