"""Operations and bytes of one layer's three forward grouped expert
matmuls (gate, up, down) in one step, from shapes alone.

Each routed assignment that an expert keeps is one row through three
``D x F`` matmuls.  Kept rows are counted at their most: every one of
the ``T * k`` assignments, or every slot ``E * C`` of the capacity rule
where that is fewer; rows dropped at capacity make the true count, and
so the true share, lower by the drop rate, never higher.  Bytes: the
experts' weights once, the rows in and out, in the stored dtype.
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, embed_dim: int, ff_dim: int,
         num_experts: int, top_k: int, capacity_factor: float,
         dtype_bytes: int = 2, **_) -> dict:
    tokens = batch * seq
    slots = num_experts * max(1, int(capacity_factor * tokens * top_k
                                     / num_experts))
    rows = min(tokens * top_k, slots)
    weights = 3 * num_experts * embed_dim * ff_dim * dtype_bytes
    acts = rows * (2 * embed_dim + 3 * ff_dim + embed_dim) * dtype_bytes
    # all three matmuls are one "call" of the family a layer
    return {"flops": 3 * 2 * rows * embed_dim * ff_dim,
            "bytes": weights + acts}
