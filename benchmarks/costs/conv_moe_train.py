"""Model FLOPs a token of one training step of the short-convolution
expert decoder as this chip runs it: what the forward and backward
passes require, recomputation not counted, causal attention counted once
(the keys a query sees, averaged) at the head's true lanes, the gated
convolution's elementwise chain (``costs/short_conv.py``'s forward), and
of the routed experts the share that is held here (a token's ``top_k *
held / experts`` of them, in expectation)."""
from __future__ import annotations


def matmul_params_per_token(arch: dict) -> float:
    """Weights a token is multiplied with (the embedding is a lookup,
    the tied head a matmul with the same table)."""
    d = arch["embed_dim"]
    dq = arch["num_heads"] * arch["head_dim"]
    dkv = arch["num_kv_heads"] * arch["head_dim"]
    mixer = {"conv": 4 * d * d, "gated": 2 * d * dq + 2 * d * dkv}
    routed = arch["top_k"] * arch["held"][1] / arch["num_experts"]
    expert = 3 * d * routed * arch["expert_ff_dim"] + d * arch["num_experts"]
    dense = 3 * d * arch["ff_dim"]
    return (sum(mixer[k] + (dense if li < arch["first_dense"] else expert)
                for li, k in enumerate(arch["layer_kinds"]))
            + d * arch["vocab_size"])


def forward_flops_per_token(arch: dict, seq: int) -> float:
    kinds = arch["layer_kinds"]
    keys = (seq + 1) / 2
    scores = (kinds.count("gated") * arch["num_heads"] * 2 * keys
              * 2 * arch["head_dim"])
    chain = (kinds.count("conv") * (2 * arch["short_conv"] + 1)
             * arch["embed_dim"])
    return 2.0 * matmul_params_per_token(arch) + scores + chain


def flops_per_token(arch: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(arch, seq)
