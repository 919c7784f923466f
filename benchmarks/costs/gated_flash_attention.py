"""Operations and bytes of the causal flash attention of every gated
attention layer of one training step in which each layer is recomputed
in the backward: the forward, one recomputation of it, and the
backward, from shapes alone, with grouped keys and values
(``num_kv_heads`` of them serve ``num_heads`` queries) and one width
``head_dim`` for scores and values.

Counted as the algorithm needs them, as ``costs/flash_attention.py``
counts them: the forward is ``Q K^T`` and ``P V``; the backward the
scores once more, ``dV``, ``dP``, ``dQ`` and ``dK``; each ``2 B H S S
head_dim`` operations, halved by the causal mask.  A backward split into
two kernels that each recompute the scores does more than this and is
charged for it.  Bytes are each operand read or written once in the
stored dtype: the forward reads Q, K, V and writes O; the backward
reads Q, K, V, O, dO and writes dQ, dK, dV.
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, num_heads: int, num_kv_heads: int,
         head_dim: int, layer_kinds, dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == "gated")
    unit = 2 * batch * num_heads * seq * seq * head_dim // 2    # causal
    q = batch * seq * num_heads * head_dim * dtype_bytes
    kv = batch * seq * num_kv_heads * head_dim * dtype_bytes
    return {"flops": layers * (2 * 2 * unit + 5 * unit),
            "bytes": layers * (2 * (2 * q + 2 * kv) + 4 * q + 4 * kv)}
