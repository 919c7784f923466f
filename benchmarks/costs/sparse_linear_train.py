"""Model FLOPs a token of one training step of the sparse-and-linear
hybrid decoder: what the forward and the backward require, recomputed
operations not counted (the utilization the ``window`` line carries).

Multiply-accumulates a token of the forward: a sparse layer's
projections (queries with the gate's lanes beside them, keys, values,
output) and its attention over the keys its selection admits (a token
``t`` of a selecting sequence sees at most ``topk`` blocks of
``block_size`` keys, of its own block ``t % block_size + 1``; every
earlier key where the sequence does not select), two products a pair;
a lightning layer's five projections and its rule (``k^T v`` and ``q
S``: two ``d x d`` products a head); a SwiGLU's three matrices in every
layer; the head's ``D x V``.  Forward and backward are 2 + 4 operations
a multiply-accumulate.  The selection's scores (a token's queries
against the compressed keys it can see) have no backward: 2 a
multiply-accumulate.
"""
from __future__ import annotations


def attended_keys(seq: int, sizes) -> float:
    """Keys a token attends, the mean over a sequence's tokens."""
    _, _, block, topk, _, _, dense_len = sizes
    if seq <= dense_len:
        return (seq + 1) / 2
    most = topk * block
    seen = sum(min(t + 1, most - (block - 1 - t % block))
               for t in range(seq))
    return seen / seq


def macs(arch: dict, seq: int) -> dict:
    """Forward multiply-accumulates a token, by part."""
    d, f, v = arch["embed_dim"], arch["ff_dim"], arch["vocab_size"]
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    lh, ld = arch["lightning_heads"], arch["lightning_dim"]
    kinds = list(arch["layer_kinds"])
    ns, nt = kinds.count("sparse"), kinds.count("lightning")
    sizes = arch["sparse_sizes"]
    selects = seq > sizes[6]
    seen_windows = seq / (2 * sizes[1]) if selects else 0.0
    return {
        "sparse_proj": ns * (d * 2 * h * dh + 2 * d * hkv * dh + h * dh * d),
        "sparse_pairs": ns * 2 * h * dh * attended_keys(seq, sizes),
        "select": ns * h * dh * seen_windows,
        "lightning_proj": nt * 5 * d * lh * ld,
        "lightning_rule": nt * 2 * lh * ld * ld,
        "mlp": len(kinds) * 3 * d * f,
        "head": d * v,
    }


def flops_per_token(arch: dict, seq: int) -> float:
    parts = macs(arch, seq)
    trained = sum(v for k, v in parts.items() if k != "select")
    return 6 * trained + 2 * parts["select"]
