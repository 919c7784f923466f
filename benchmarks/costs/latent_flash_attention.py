"""Operations and bytes of the causal flash attention of every layer of
one training step of a latent-attention model in which each layer is
recomputed in the backward: the forward, one recomputation of it, and
the backward, from shapes alone, with scores over ``qk_nope + qk_rope``
lanes and values of ``v_head_dim``.

Counted as the algorithm needs them, as ``costs/flash_attention.py``
counts them: the forward is ``Q K^T`` over the score lanes and ``P V``
over the value lanes; the backward the scores once more, ``dV``, ``dP``
(value lanes), ``dQ`` and ``dK`` (score lanes); each ``2 B H S S lanes``
operations, halved by the causal mask.  A kernel that pads 192 score
lanes to 256, or that recomputes the scores in each of two backward
kernels, does more than this and is charged for it.  Bytes are each
operand read or written once in the stored dtype: the forward reads Q,
K, V and writes O; the backward reads Q, K, V, O, dO and writes dQ, dK,
dV.  Every head has keys and values of its own (no grouping).
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, num_heads: int, num_layers: int,
         qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
         dtype_bytes: int = 2, **_) -> dict:
    qk = qk_nope_head_dim + qk_rope_head_dim
    pairs = batch * num_heads * seq * seq // 2          # causal
    score, value = 2 * pairs * qk, 2 * pairs * v_head_dim
    forward, backward = score + value, 3 * score + 2 * value
    rows = batch * seq * num_heads * dtype_bytes
    fwd_bytes = rows * (2 * qk + 2 * v_head_dim)
    bwd_bytes = rows * (4 * qk + 4 * v_head_dim)
    return {"flops": num_layers * (2 * forward + backward),
            "bytes": num_layers * (2 * fwd_bytes + bwd_bytes)}
