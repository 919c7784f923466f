"""Model FLOPs a token of one training step of the hybrid decoder: what
the forward and backward passes require, recomputation not counted,
causal attention counted once (the keys a query sees, averaged)."""
from __future__ import annotations


def matmul_params_per_token(arch: dict) -> int:
    """Weights a token is multiplied with (the embedding is a lookup,
    the tied head a matmul)."""
    d, f = arch["embed_dim"], arch["ff_dim"]
    e, n, r = arch["ssm_inner"], arch["ssm_state"], arch["ssm_dt_rank"]
    dq = arch["num_heads"] * arch["head_dim"]
    dkv = arch["num_kv_heads"] * arch["head_dim"]
    mixer = {"mamba": d * 2 * e + e * (r + 2 * n) + r * e + e * d,
             "window": 2 * d * dq + 2 * d * dkv,
             "full": 2 * d * dq + 2 * d * dkv,
             "cross": 2 * d * dq,
             "gmu": 2 * d * e}
    return (sum(mixer[k] + 3 * d * f for k in arch["layer_kinds"])
            + d * arch["vocab_size"])


def keys_per_query(kind: str, seq: int, window: int) -> float:
    if kind == "window":
        return sum(min(i + 1, window) for i in range(seq)) / seq
    return (seq + 1) / 2


def forward_flops_per_token(arch: dict, seq: int) -> float:
    pairs, dh = arch["num_heads"] // 2, arch["head_dim"]
    flops = 2.0 * matmul_params_per_token(arch)
    for kind in arch["layer_kinds"]:
        if kind in ("window", "full", "cross"):
            # two softmax maps a pair: q.k over dh, the map times a
            # value of 2 dh
            flops += (pairs * 2 * keys_per_query(kind, seq, arch["window"])
                      * (2 * dh + 2 * 2 * dh))
        elif kind == "mamba":
            flops += arch["ssm_inner"] * (7 * arch["ssm_state"] + 3)
    return flops


def flops_per_token(arch: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(arch, seq)
