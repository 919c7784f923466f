"""Operations and bytes of the attention of every full (``nope``) layer
of one training step in which each layer is recomputed in the backward:
``costs/window_flash_attention.py``'s count (forward, one
recomputation, backward; grouped keys and values; each operand once) at
the full causal mask, ``S (S + 1) / 2`` pairs a head.
``costs/flash_attention.py`` counts one layer's forward and backward
and cannot carry the recomputation nor the number of layers."""
from __future__ import annotations

from benchmarks.costs.window_flash_attention import cost_of


def cost(**shapes) -> dict:
    """Every full layer of the step."""
    return cost_of("nope", **shapes)
