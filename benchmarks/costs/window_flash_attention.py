"""Operations and bytes of the attention of every window (``swa``) or
every full (``nope``) layer of one training step in which each layer is
recomputed in the backward: the forward, one recomputation of it, and
the backward, from shapes alone, with grouped keys and values
(``num_kv_heads`` of them serve ``num_heads`` queries) and one width
``head_dim`` for scores and values.

Counted as the algorithm needs them, as ``costs/flash_attention.py``
counts them: the forward is ``Q K^T`` and ``P V``; the backward the
scores once more, ``dV``, ``dP``, ``dQ`` and ``dK``; each ``2 B H
head_dim`` operations for every (query, key) pair the mask admits.  A
full layer admits ``S (S + 1) / 2`` pairs; a window layer the band's:
token ``t`` sees ``min(t + 1, window)`` keys, so ``window (window + 1)
/ 2 + (S - window) window``.  Work a kernel does outside the band
(blocks at its edge multiplied whole) earns nothing here, nor does a
backward split into two kernels that each recompute the scores.  Bytes
are each operand read or written once in the stored dtype: the forward
reads Q, K, V and writes O; the backward reads Q, K, V, O, dO and
writes dQ, dK, dV, the key/value gradients at the ``num_kv_heads`` the
model has: a group's gradients written a query head and summed
afterwards are the kernel's own cost.

``cost_of`` counts the layers of one ``kind``; a spec file's ``cost``
names a module's function ``cost``, so this module's is the window
layers' and ``costs/full_flash_attention.py``'s the full layers'.
"""
from __future__ import annotations


def pairs(kind: str, seq: int, window: int) -> int:
    """(query, key) pairs a ``kind`` layer's mask admits a sequence."""
    if kind != "swa" or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def cost_of(kind: str, *, batch: int, seq: int, num_heads: int,
            num_kv_heads: int, head_dim: int, window: int, layer_kinds,
            dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == kind)
    unit = 2 * batch * num_heads * pairs(kind, seq, window) * head_dim
    q = batch * seq * num_heads * head_dim * dtype_bytes
    kv = batch * seq * num_kv_heads * head_dim * dtype_bytes
    return {"flops": layers * (2 * 2 * unit + 5 * unit),
            "bytes": layers * (2 * (2 * q + 2 * kv) + 4 * q + 4 * kv)}


def cost(**shapes) -> dict:
    """Every window layer of the step."""
    return cost_of("swa", **shapes)
