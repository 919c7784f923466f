"""Model FLOPs a token of one training step of the head-gated
window-and-full attention expert decoder as this chip runs it: what the
forward and backward passes require, recomputation not counted,
attention counted over the keys a query truly sees at the layer's own
head count (a full layer's causal average over ``num_heads``, a window
layer's band over ``window_heads``), the gate's projection a head, and
of the routed experts the share that is held here (a token's ``top_k *
held / experts`` of them, in expectation) beside the shared one."""
from __future__ import annotations

from benchmarks.costs.window_flash_attention import pairs


def heads_of(arch: dict, kind: str) -> int:
    return arch["window_heads" if kind == "swa" else "num_heads"]


def matmul_params_per_token(arch: dict) -> float:
    """Weights a token is multiplied with (the embedding is a lookup,
    the untied head a matmul)."""
    d, dh = arch["embed_dim"], arch["head_dim"]
    dkv = arch["num_kv_heads"] * dh
    attn = sum(2 * d * heads_of(arch, k) * dh + 2 * d * dkv
               + d * heads_of(arch, k) for k in arch["layer_kinds"])
    routed = arch["top_k"] * arch["held"][1] / arch["num_experts"]
    expert = (3 * d * (routed * arch["expert_ff_dim"]
                       + arch["shared_ff_dim"])
              + d * arch["num_experts"])
    dense = arch["first_dense"]
    return (attn + dense * 3 * d * arch["ff_dim"]
            + (arch["num_layers"] - dense) * expert
            + d * arch["vocab_size"])


def forward_flops_per_token(arch: dict, seq: int) -> float:
    scores = sum(heads_of(arch, k) * 2
                 * pairs(k, seq, arch["window"]) / seq
                 * 2 * arch["head_dim"] for k in arch["layer_kinds"])
    return 2.0 * matmul_params_per_token(arch) + scores


def flops_per_token(arch: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(arch, seq)
