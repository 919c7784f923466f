"""MoE decode for the serving tier (ISSUE 15 tentpole leg d):
per-expert token batching under continuous batching.

Training-side MoE enforces capacity by DROPPING over-capacity tokens —
the residual carries them and the loss absorbs it.  A serving engine
cannot drop: every admitted slot's token must produce its next token
this step.  So the serving MoE MLP batches tokens per expert into
capacity-``C`` buffers and, when routing overflows an expert, runs
ADDITIONAL rounds (a ``lax.while_loop`` with a dynamic trip count)
until every token is processed — losslessly, with wall time
proportional to ``ceil(max_expert_load / C)``.

That makes expert load imbalance a LATENCY story, not a loss story:
balanced routing fits one round; skewed routing pays
``ceil(top_k * B / C)`` rounds on the hot expert while the others'
capacity idles — which is exactly the p99 effect the committed study
measures under a seeded skew.  The skew itself is an injection knob
(``skew_bias``): a seeded per-expert router-logit bias, the
imbalance-shaped sibling of the fault plans' seeded delays — measured
telemetry (per-expert load, rounds per step) rides the flight ring and
the record either way.

The routing math builds on ``layers.router_logits`` / top-k softmax —
the same spelling the training tiers use — so a serving MoE model is
the training model, not a fork.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.utils.seeded import Rng

_F32 = jnp.float32


def skew_bias(num_experts: int, skew: float, seed: int):
    """Seeded per-expert router-logit bias emulating expert-load skew
    (host-side, plan-replayable — the splitmix64 generator every
    seeded injection in this repo uses).  ``skew = 0`` returns None
    (the bias is not even added — bit-identical routing); larger skew
    concentrates routing mass on the seeded draw's favorites."""
    if skew == 0.0:
        return None
    import numpy as np

    rng = Rng((seed + 1) * 0xA24BAED4)
    draws = np.array([rng.u01() for _ in range(num_experts)],
                     dtype=np.float32)
    return jnp.asarray(float(skew) * draws)


def decode_capacity(batch: int, top_k: int, num_experts: int,
                    capacity_factor: float) -> int:
    """Per-round per-expert slots of the serving MoE MLP — the
    training tier's capacity arithmetic (models/moe.group_capacity)
    over the decode batch."""
    from dlnetbench_tpu.models.moe import group_capacity
    return group_capacity(batch, top_k, num_experts, capacity_factor)


def moe_mlp_rounds(x, w_router, w_gate, w_up, w_down, *, top_k: int,
                   capacity: int, bias=None, active=None):
    """The serving MoE MLP: ``x`` [B, d] one token per slot ->
    ``(y [B, d], load [E] int32, rounds int32)``.

    Tokens are batched per expert into ``capacity`` dispatch slots per
    round; overflow runs further rounds (dynamic ``while_loop`` trip
    count = ``ceil(max_load / capacity)``) until every routed
    (token, expert) pair is computed — LOSSLESS: the result is the
    top-k gated sum ``sum_e gate[b,e] * f_e(x_b)`` exactly, whatever
    the round count.  ``bias`` (the seeded skew) is added to the
    router logits; ``active`` [B] masks inactive slots out of routing
    (they occupy no capacity and report no load).  ``load`` is this
    call's per-expert routed-token histogram and ``rounds`` the trip
    count — the expert-imbalance telemetry the engine records."""
    b, d = x.shape
    e = w_gate.shape[0]
    logits = L.router_logits(x, w_router)
    if bias is not None:
        logits = logits + bias[None, :]
    top_vals, idx = lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top_vals, axis=-1)
    onehot = jax.nn.one_hot(idx, e, dtype=_F32)          # [B, k, E]
    gate = jnp.sum(onehot * weights[..., None], axis=1)  # [B, E]
    mask = jnp.sum(onehot, axis=1)                       # [B, E]
    if active is not None:
        act = active.astype(_F32)[:, None]
        mask = mask * act
        gate = gate * act
    pos = jnp.cumsum(mask, axis=0) - 1.0                 # queue order
    load = jnp.sum(mask, axis=0)                         # [E]
    rounds = jnp.ceil(jnp.max(load) / capacity).astype(jnp.int32)
    xf = x.astype(_F32)

    def cond(carry):
        return carry[0] < rounds

    def body(carry):
        r, y = carry
        lo = r.astype(_F32) * capacity
        sel = mask * (pos >= lo) * (pos < lo + capacity)
        disp = jax.nn.one_hot((pos - lo).astype(jnp.int32), capacity,
                              dtype=_F32) * sel[..., None]  # [B, E, C]
        xe = jnp.einsum("bec,bd->ecd", disp, xf).astype(x.dtype)
        h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xe, w_gate,
                                   preferred_element_type=_F32))
        h = h * jnp.einsum("ecd,edh->ech", xe, w_up,
                           preferred_element_type=_F32)
        out = jnp.einsum("ech,ehd->ecd", h.astype(x.dtype), w_down,
                         preferred_element_type=_F32)
        y = y + jnp.einsum("ecd,bec->bd", out, disp * gate[..., None])
        return r + 1, y

    _, y = lax.while_loop(cond, body,
                          (jnp.int32(0), jnp.zeros((b, d), _F32)))
    return y.astype(x.dtype), load.astype(jnp.int32), rounds
