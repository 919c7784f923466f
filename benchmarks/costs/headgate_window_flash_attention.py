"""Operations and bytes of the attention of every window (``swa``) layer
of one training step of the head-gated decoder, each layer recomputed
in the backward: ``costs/window_flash_attention.py``'s count (forward,
one recomputation, backward; the band's true pairs; grouped keys and
values; each operand once) at the window layers' own query heads,
``window_heads`` over ``num_kv_heads``."""
from __future__ import annotations

from benchmarks.costs.window_flash_attention import cost_of


def cost(*, window_heads: int, **shapes) -> dict:
    """Every window layer of the step."""
    return cost_of("swa", **{**shapes, "num_heads": window_heads})
