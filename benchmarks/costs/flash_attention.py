"""Operations and bytes of causal flash attention, forward and backward,
for one layer of one step, from shapes alone.

Counted as the algorithm needs them, not as a kernel happens to do them:
forward is two matmuls (QK^T, PV), backward five (the scores once more,
dV, dP, dQ, dK), each ``2*B*H*S*S*Dh`` operations, halved by the causal
mask.  A backward split into two kernels that each recompute the scores
does more work than this and is charged for it.  Bytes are each operand
read or written once in the stored dtype: forward reads Q, K, V and
writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV.
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, num_heads: int, num_kv_heads: int,
         head_dim: int, dtype_bytes: int = 2, **_) -> dict:
    unit = 2 * batch * num_heads * seq * seq * head_dim // 2   # causal
    q = batch * seq * num_heads * head_dim * dtype_bytes
    kv = batch * seq * num_kv_heads * head_dim * dtype_bytes
    return {"flops": 7 * unit,
            "bytes": (2 * q + 2 * kv) + (4 * q + 4 * kv)}
