"""Operations and bytes of the gated delta rule of every linear layer of
one training step in which each layer is recomputed in the backward:
the forward, one recomputation of it, and the backward, from shapes
alone.

Counted as the token recurrence needs them, for one (token, value head)
and its ``dk x dv`` state: the decay (1), ``S'^T k`` (2), the delta
written into the state (2) and ``S^T q`` (2): 7 a state element forward,
twice that backward.  That an implementation runs chunks of tokens as
matrix products (more operations, all on the MXU), keeps or recomputes
the chunks' states, or repeats a key head for the value heads it serves,
is how it gets there, not what the arithmetic needs: it is charged for
it.  Bytes are each operand read or written once in its stored dtype:
the forward reads q and k (the key heads), v, g and beta (float32) and
writes o; the backward reads those and do and writes the five
cotangents.

By this count the bytes bind at the benchmark's shapes (1.5 GB a layer
against 0.24 TFLOP), so a share of the roofline computed from it is a
floor's share, as ``costs/selective_scan.py``'s is.
"""
from __future__ import annotations


def forward_flops_per_token(*, linear_value_heads: int, linear_key_dim: int,
                            linear_value_dim: int, **_) -> int:
    return 7 * linear_value_heads * linear_key_dim * linear_value_dim


def cost(*, batch: int, seq: int, layer_kinds, linear_key_heads: int,
         linear_value_heads: int, linear_key_dim: int,
         linear_value_dim: int, dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == "gdn")
    tokens = batch * seq
    forward = tokens * forward_flops_per_token(
        linear_value_heads=linear_value_heads,
        linear_key_dim=linear_key_dim, linear_value_dim=linear_value_dim)
    inputs = tokens * (
        (2 * linear_key_heads * linear_key_dim
         + linear_value_heads * linear_value_dim) * dtype_bytes
        + 2 * linear_value_heads * 4)
    out = tokens * linear_value_heads * linear_value_dim * dtype_bytes
    return {"flops": layers * (2 * forward + 2 * forward),
            "bytes": layers * (2 * (inputs + out) + 2 * inputs + out)}
