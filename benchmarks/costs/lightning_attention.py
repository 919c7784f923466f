"""Operations and bytes of the lightning rule of every lightning layer
of one training step: the forward once and the backward once, from
shapes alone (each layer is recomputed in the backward and the rule's
forward kernel runs a second time there: that run earns nothing).

Counted as the token recurrence needs them, for one (token, head) and
its ``d x d`` state: the decay (1), ``k^T v`` added to the state (2) and
``q S`` (2): 5 a state element forward, twice that backward.  That an
implementation runs chunks of tokens as matrix products (more
operations, all on the MXU) and keeps the chunks' states is how it gets
there: it is charged for it.  Bytes are each operand once in its stored
dtype: the forward reads q, k, v and writes o; the backward reads q, k,
v, do and writes the three cotangents.  By this count the HBM bytes bind
at the benchmark's shapes (1.5 GB a layer against 0.13 TFLOP), so a
share of the roofline computed from it is a floor's share, as
``costs/gated_delta_rule.py``'s is.
"""
from __future__ import annotations


def forward_flops_per_token(*, lightning_heads: int, lightning_dim: int,
                            **_) -> int:
    return 5 * lightning_heads * lightning_dim * lightning_dim


def cost(*, batch: int, seq: int, layer_kinds, lightning_heads: int,
         lightning_dim: int, dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == "lightning")
    tokens = batch * seq
    forward = tokens * forward_flops_per_token(
        lightning_heads=lightning_heads, lightning_dim=lightning_dim)
    operand = tokens * lightning_heads * lightning_dim * dtype_bytes
    return {"flops": layers * (forward + 2 * forward),
            "bytes": layers * (4 * operand + 7 * operand)}
