"""Operations and bytes of the attention of every full (``gated``) layer
of one training step of the head-gated decoder, each layer recomputed
in the backward: ``costs/window_flash_attention.py``'s count (forward,
one recomputation, backward; grouped keys and values; each operand
once) at the full causal mask, ``S (S + 1) / 2`` pairs a head, and the
full layers' own query heads, ``num_heads`` over ``num_kv_heads``.
The gate a head is another scope's (``attn.gate``) and earns nothing
here."""
from __future__ import annotations

from benchmarks.costs.window_flash_attention import cost_of


def cost(**shapes) -> dict:
    """Every full layer of the step."""
    return cost_of("gated", **shapes)
