"""Model FLOPs a token of one training step of the linear-attention
expert decoder as this chip runs it: what the forward and backward
passes require, recomputation not counted, causal attention counted once
(the keys a query sees, averaged), the delta rule as its token
recurrence (``costs/gated_delta_rule.py``), and of the routed experts
the share that is held here (a token's ``top_k * held / experts`` of
them, in expectation)."""
from __future__ import annotations

from benchmarks.costs import gated_delta_rule


def matmul_params_per_token(arch: dict) -> float:
    """Weights a token is multiplied with (the embedding is a lookup,
    the untied head a matmul)."""
    d = arch["embed_dim"]
    hv = arch["linear_value_heads"]
    qk = arch["linear_key_heads"] * arch["linear_key_dim"]
    vz = hv * arch["linear_value_dim"]
    dq = arch["num_heads"] * arch["head_dim"]
    dkv = arch["num_kv_heads"] * arch["head_dim"]
    mixer = {"gdn": d * (2 * qk + 2 * vz) + d * 2 * hv + vz * d,
             "gated": d * 2 * dq + 2 * d * dkv + dq * d}
    routed = arch["top_k"] * arch["held"][1] / arch["num_experts"]
    expert = (3 * d * (routed * arch["expert_ff_dim"]
                       + arch["shared_ff_dim"])
              + d * arch["num_experts"] + d)
    return (sum(mixer[k] + expert for k in arch["layer_kinds"])
            + d * arch["vocab_size"])


def forward_flops_per_token(arch: dict, seq: int) -> float:
    kinds = arch["layer_kinds"]
    keys = (seq + 1) / 2
    scores = (kinds.count("gated") * arch["num_heads"] * 2 * keys
              * 2 * arch["head_dim"])
    conv = 2 * arch["linear_conv"] * (
        2 * arch["linear_key_heads"] * arch["linear_key_dim"]
        + arch["linear_value_heads"] * arch["linear_value_dim"])
    linear = kinds.count("gdn") * (
        conv + gated_delta_rule.forward_flops_per_token(**arch))
    return 2.0 * matmul_params_per_token(arch) + scores + linear


def flops_per_token(arch: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(arch, seq)
