"""Operations and bytes of the block-sparse attention of every sparse
layer of one training step: the forward ONCE and the backward once (the
layer is recomputed in the backward, but its checkpoint keeps the
kernel's output, lse and lists, so no second forward runs), over the
(query, key) pairs the run's own selections admit.

The pairs come from the step's counter ``sparse.selected`` (the record's
``sparse["selected"]``, the median of the window's steps): the (token,
key/value head, block) entries of every sparse layer's lists.  A block
is ``block_size`` keys, all at or before the token but in the token's
own block, where token ``t`` sees ``t % block_size + 1``: over a
sequence that is ``(block_size - 1) / 2`` pairs fewer a (token, group).
Each pair costs a group's ``G = num_heads / num_kv_heads`` query heads
``2 head_dim`` operations a product: ``Q K^T`` and ``P V`` forward; the
scores once more, ``dV``, ``dP``, ``dQ`` and ``dK`` backward.  What the
kernels compute beyond the pairs (a visited tile holds blocks some of
its tokens did not choose: ``sparse.visited``), a backward in two
kernels that each recompute the scores, and the selection itself (a
scope of its own) earn nothing.  Bytes are each operand once in the
stored dtype: the forward reads Q, K, V and writes O; the backward reads
Q, K, V, O, dO and writes dQ, dK, dV at ``num_kv_heads``.
"""
from __future__ import annotations

import statistics


def pairs(selected: float, *, batch: int, seq: int, num_kv_heads: int,
          layers: int, block_size: int) -> float:
    """(query, key) pairs a group of the step's lists admit."""
    return selected * block_size \
        - layers * batch * num_kv_heads * seq * (block_size - 1) / 2


def cost(*, batch: int, seq: int, num_heads: int, num_kv_heads: int,
         head_dim: int, layer_kinds, sparse_sizes, sparse: dict,
         dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == "sparse")
    n = pairs(statistics.median(sparse["selected"]), batch=batch, seq=seq,
              num_kv_heads=num_kv_heads, layers=layers,
              block_size=sparse_sizes[2])
    unit = 2 * (num_heads // num_kv_heads) * n * head_dim
    q = batch * seq * num_heads * head_dim * dtype_bytes
    kv = batch * seq * num_kv_heads * head_dim * dtype_bytes
    return {"flops": 2 * unit + 5 * unit,
            "bytes": layers * (2 * q + 2 * kv + 4 * q + 4 * kv)}
