"""Operations and bytes of the forward grouped expert matmuls (gate, up,
down) of every expert layer of one training step in which each layer is
recomputed in the backward (the forward and one recomputation of it;
the expert backward is XLA einsums, not these kernels), for the share of
the routed experts that is held here, from shapes alone.

A held expert's rows are counted at their expectation under an even
router: of a step's ``T * top_k`` assignments the share ``held /
experts`` lands here, and each is one row through three ``D x F``
matmuls.  The true count moves with the router's imbalance (the
``window`` line has the rows routed); slots a kernel multiplies beyond
an expert's rows are its own cost.  Bytes: the held experts' weights
once a pass, the rows in and out, in the stored dtype.
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, embed_dim: int, expert_ff_dim: int,
         num_experts: int, held, top_k: int, num_layers: int,
         first_dense: int, dtype_bytes: int = 2, **_) -> dict:
    rows = batch * seq * top_k * held[1] / num_experts
    weights = 3 * held[1] * embed_dim * expert_ff_dim * dtype_bytes
    acts = rows * (2 * embed_dim + 3 * expert_ff_dim
                   + embed_dim) * dtype_bytes
    passes = 2 * (num_layers - first_dense)
    return {"flops": passes * 3 * 2 * rows * embed_dim * expert_ff_dim,
            "bytes": passes * (weights + acts)}
