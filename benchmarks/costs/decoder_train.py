"""Model FLOPs a token of one training step of the gated decoder: what
the forward and backward passes require, recomputation not counted,
causal attention counted once (half the square)."""
from __future__ import annotations


def matmul_params_per_token(arch: dict) -> int:
    """Weights a token is multiplied with (the embedding is a lookup)."""
    d, f = arch["embed_dim"], arch["ff_dim"]
    dq = arch["num_heads"] * arch["head_dim"]
    dkv = arch["num_kv_heads"] * arch["head_dim"]
    attn = d * dq + 2 * d * dkv + dq * d
    if arch["num_experts"] > 1:
        mlp = arch["top_k"] * 3 * d * f + d * arch["num_experts"]
    else:
        mlp = 3 * d * f
    return arch["num_layers"] * (attn + mlp) + d * arch["vocab_size"]


def flops_per_token(arch: dict, seq: int) -> float:
    attn = 2 * 2 * (seq / 2) * arch["num_heads"] * arch["head_dim"]
    return 3 * (2 * matmul_params_per_token(arch)
                + arch["num_layers"] * attn)
