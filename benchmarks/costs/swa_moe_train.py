"""Model FLOPs a token of one training step of the window-and-full
attention expert decoder as this chip runs it: what the forward and
backward passes require, recomputation not counted, attention counted
over the keys a query truly sees (a full layer's causal average, a
window layer's band), and of the routed experts the share that is held
here (a token's ``top_k * held / experts`` of them, in expectation)."""
from __future__ import annotations

from benchmarks.costs.window_flash_attention import pairs


def keys_seen(kind: str, seq: int, window: int) -> float:
    """Mean keys a query of a ``kind`` layer sees over ``seq`` tokens:
    token ``t`` sees ``t + 1``, a ``swa`` layer's at most ``window``
    (``costs/window_flash_attention.pairs`` over the tokens)."""
    return pairs(kind, seq, window) / seq


def matmul_params_per_token(arch: dict) -> float:
    """Weights a token is multiplied with (the embedding is a lookup,
    the untied head a matmul)."""
    d = arch["embed_dim"]
    dq = arch["num_heads"] * arch["head_dim"]
    dkv = arch["num_kv_heads"] * arch["head_dim"]
    routed = arch["top_k"] * arch["held"][1] / arch["num_experts"]
    layer = (2 * d * dq + 2 * d * dkv + d * arch["num_experts"]
             + 3 * d * routed * arch["expert_ff_dim"])
    return arch["num_layers"] * layer + d * arch["vocab_size"]


def forward_flops_per_token(arch: dict, seq: int) -> float:
    scores = sum(arch["num_heads"] * 2 * keys_seen(k, seq, arch["window"])
                 * 2 * arch["head_dim"] for k in arch["layer_kinds"])
    return 2.0 * matmul_params_per_token(arch) + scores


def flops_per_token(arch: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(arch, seq)
