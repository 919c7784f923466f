"""Building-block layers (pure JAX, no flax): norms, RoPE, MLPs, MoE
routing (dense, and capacity-based by row gathers).

Conventions: parameters are plain dict pytrees; compute dtype is the
input's dtype (bfloat16 on TPU) with float32 accumulation where precision
matters (norm statistics, softmax, router logits); matmuls request float32
``preferred_element_type`` so the MXU accumulates in fp32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dlnetbench_tpu.metrics.spans import mark, scope
from dlnetbench_tpu.ops import fp8 as qf8
from dlnetbench_tpu.ops import int8 as q8
from dlnetbench_tpu.ops.grouped_matmul import (
    owner_of,
    packed_first,
    packed_rows,
)

_F32 = jnp.float32


def init_dense(key, shape, scale, dtype):
    """Gaussian init in fp32, cast to the compute dtype (shared by all
    model families)."""
    return (jax.random.normal(key, shape, _F32) * scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(_F32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def _rmsnorm_fwd(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(_F32)), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return (x * rstd.astype(x.dtype)) * scale, (x, scale, rstd)


def _rmsnorm_bwd(eps, res, dy):
    # Hand-written so the scale gradient and the input gradient stay
    # SEPARATE fusions: the autodiff-generated single fusion (dscale
    # cross-row reduction + per-token cross-lane reduction + full dx, one
    # loop) runs ~26x slower than memory bandwidth on v5e (3.4 ms vs
    # 0.13 ms for the same bytes; ~15% of a whole train step).
    x, scale, rstd = res
    xhat = x * rstd.astype(x.dtype)
    dscale = jnp.sum(dy.astype(_F32) * xhat.astype(_F32),
                     axis=tuple(range(x.ndim - 1))).astype(scale.dtype)
    xhat, dy, dscale = jax.lax.optimization_barrier((xhat, dy, dscale))
    t = dy * scale
    c = jnp.mean(t.astype(_F32) * xhat.astype(_F32), axis=-1, keepdims=True)
    # second barrier: fusing the per-token reduction INTO the dx
    # elementwise pass regenerates the same slow mixed-reduction loop
    xhat, t, c = jax.lax.optimization_barrier((xhat, t, c))
    dx = (t.astype(_F32) - xhat.astype(_F32) * c) * rstd
    return dx.astype(x.dtype), dscale


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(_F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


def rope_freqs(theta: float, lanes: int, yarn):
    """(inverse frequencies [lanes / 2], the factor on cos and sin) of
    RoPE under YaRN as ``transformers`` computes it, in float32 on the
    host.  ``yarn`` = (factor, original positions, beta_fast, beta_slow,
    attention factor): pair ``i`` turns at ``theta^(-2i/lanes)`` times
    ``(1 - ramp_i) + ramp_i / factor``, ``ramp`` rising from 0 at pair
    ``low`` to 1 at pair ``high``, the pairs that make ``beta_fast`` and
    ``beta_slow`` turns over the original positions (floor and
    ceiling, within the lanes)."""
    factor, original, beta_fast, beta_slow, attention_factor = yarn

    def pair_of(turns):
        return (lanes * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), lanes - 1)
    high = high + 0.001 if high == low else high
    i = np.arange(lanes // 2, dtype=np.float32)
    ramp = np.clip((i - low) / np.float32(high - low), 0.0, 1.0)
    base = np.float32(theta) ** (-2.0 * i / np.float32(lanes))
    inv_freq = base * ((1.0 - ramp) + ramp / np.float32(factor))
    return inv_freq.astype(np.float32), float(attention_factor)


def rope(q, k, positions, theta=10000.0, yarn=None):
    """Rotary embeddings; q/k: [..., S, H, Dh], positions: [S].  ``yarn``
    = ``rope_freqs``'s numbers, or None for plain RoPE."""
    dh = q.shape[-1]
    if yarn is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=_F32) / dh))
        factor = 1.0
    else:
        inv_freq, factor = rope_freqs(theta, dh, yarn)
    angles = positions.astype(_F32)[:, None] * inv_freq[None, :]  # [S, Dh/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor

    def rot(x):
        x1, x2 = jnp.split(x.astype(_F32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU, backward by autodiff.

    Rounds each projection to the compute dtype IMMEDIATELY so the
    saved residuals are bf16, not f32 (the MXU still accumulates in
    f32; silu stays f32 elementwise and fuses).  Measured perf-neutral
    on v5e at B=2 S=2048 — the save traffic overlaps MXU work — but it
    halves activation memory, which is what lets larger B/S fit without
    remat.
    """
    g = jnp.dot(x, w_gate, preferred_element_type=_F32).astype(x.dtype)
    u = jnp.dot(x, w_up, preferred_element_type=_F32).astype(x.dtype)
    h = (jax.nn.silu(g.astype(_F32)) * u.astype(_F32)).astype(g.dtype)
    return jnp.dot(h, w_down, preferred_element_type=_F32).astype(x.dtype)


def quantized_swiglu(x, w_gate, w_up, w_down, *, mlp_dtype: str,
                     quant_fusion: str = "composed",
                     int8_backward: str = "master"):
    """The ONE dispatch point for the low-precision SwiGLU recipes
    (transformer._block calls this; TransformerConfig validates the
    combinations):

    * ``quant_fusion="composed"`` — the original XLA paths
      (ops/int8.py swiglu_int8 / swiglu_int8_sb, ops/fp8.py
      swiglu_fp8): quantization as separate amax/rescale passes.
    * ``quant_fusion="fused"`` — the fused-quantization Pallas kernels
      (ops/quantized_matmul.py): scale application inlined into the
      matmul prologue/epilogue."""
    if mlp_dtype == "int8":
        if quant_fusion == "fused":
            return q8.swiglu_int8_fused(x, w_gate, w_up, w_down)
        if int8_backward == "switchback":
            return q8.swiglu_int8_sb(x, w_gate, w_up, w_down)
        return q8.swiglu_int8(x, w_gate, w_up, w_down)
    if mlp_dtype == "float8":
        if quant_fusion == "fused":
            return qf8.swiglu_fp8_fused(x, w_gate, w_up, w_down)
        return qf8.swiglu_fp8(x, w_gate, w_up, w_down)
    raise ValueError(f"quantized_swiglu: not a quantized mlp_dtype "
                     f"{mlp_dtype!r}")


def gelu_mlp(x, w_in, b_in, w_out, b_out):
    # same bf16-rounding discipline as swiglu: don't let autodiff save
    # the f32 [B, S, ff_dim] pre-activation
    a = (jnp.dot(x, w_in, preferred_element_type=_F32)
         + b_in.astype(_F32)).astype(x.dtype)
    h = jax.nn.gelu(a.astype(_F32)).astype(x.dtype)
    return (jnp.dot(h, w_out,
                    preferred_element_type=_F32) + b_out).astype(x.dtype)


def router_logits(x, w_router):
    """The ONE spelling of the router projection (f32 — router logits
    are precision-sensitive): ``moe_router`` here, the seeded grouped
    routing in ``models/moe.py`` and the serving MoE decode all build
    on it, so their expert assignments can never drift apart."""
    return jnp.dot(x.astype(_F32), w_router.astype(_F32))


def moe_router(x, w_router, top_k: int, *, scoring: str = "softmax",
               bias=None, scale: float = 1.0):
    """Token router: returns (weights [T, k], expert indices [T, k]),
    scores and selection in float32.  ``scoring`` is the gate the
    model's card states (``MoEParams.scoring``):

    * ``"softmax"``: the top-k of the logits, softmax over the selected
      (Mixtral convention), times ``scale`` where a card states one.
    * ``"sigmoid"``: scores ``s = sigmoid(logits)``; the top-k of
      ``s + bias``, ``bias`` [E] a per-expert correction that steers
      the selection only and has no gradient; the weights are ``s``
      itself at the selected, divided by their sum and times ``scale``
      (the DeepSeek-V3 family's gate without groups)."""
    with scope("moe.router"):
        logits = router_logits(x, w_router)
        if scoring == "softmax":
            top_vals, top_idx = jax.lax.top_k(logits, top_k)
            w = jax.nn.softmax(top_vals, axis=-1)
            return (w if scale == 1.0 else w * scale), top_idx
        if scoring != "sigmoid":
            raise ValueError(f"moe_router: unknown scoring {scoring!r} "
                             f"(softmax | sigmoid)")
        s = jax.nn.sigmoid(logits)
        choose = s if bias is None else \
            s + jax.lax.stop_gradient(bias.astype(_F32))
        _, top_idx = jax.lax.top_k(choose, top_k)
        w = jnp.take_along_axis(s, top_idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * scale, top_idx


def moe_dense(x2d, w_router, w_gate, w_up, w_down, top_k: int):
    """Dense (every-expert-computes-selected-tokens) MoE for single-device
    execution: experts stacked on the leading axis of w_* ([E, ...]).
    Selection via one-hot combine — compiler-friendly, no dynamic shapes.
    """
    t, d = x2d.shape
    e = w_gate.shape[0]
    weights, idx = moe_router(x2d, w_router, top_k)        # [T,k], [T,k]
    with scope("moe.router"):
        # combine[t, e] = sum_k weights[t,k] * (idx[t,k]==e)
        combine = jnp.sum(jax.nn.one_hot(idx, e, dtype=_F32)
                          * weights[..., None], axis=1)    # [T, E]
    with scope("moe.experts"):
        h = jnp.einsum("td,edh->teh", x2d, w_gate,
                       preferred_element_type=_F32)
        u = jnp.einsum("td,edh->teh", x2d, w_up,
                       preferred_element_type=_F32)
        h = jax.nn.silu(h) * u
        y = jnp.einsum("teh,ehd->ted", h.astype(x2d.dtype), w_down,
                       preferred_element_type=_F32)        # [T, E, D]
    with scope("moe.combine"):
        return jnp.einsum("ted,te->td", y, combine).astype(x2d.dtype)


class PackedRows(NamedTuple):
    """A packed layout of a plan's expert rows (``MoePlan.packed``):
    one buffer of R rows (``packed_room``) in which expert e's kept
    rows lie in arrival order from row ``start[e]`` on, the next
    row-block boundary past the expert before it.

    ``src`` [1, R] int32: the token in each row, T in an empty one
    (past an expert's count within its last block, or past the live
    prefix); one group of R rows, which is how the kernels see the
    buffer (``grouped_matmul``'s packed form).
    ``start`` [E] int32 (``grouped_matmul.packed_first`` in rows, which
    the kernels read off the same counts)."""
    src: jax.Array
    start: jax.Array


class MoePlan(NamedTuple):
    """A routing as indices: what dispatch, combine and their backward
    passes move rows through.

    ``slot`` [T, E] int32: the token's place in expert e's buffer, -1
    where it is not routed there or was dropped at capacity.
    ``src`` [E, C] int32, its inverse: the token in each slot, T (one
    past the last token) in an empty slot.
    ``idx`` [T, k] int32: the token's experts, as the router chose;
    E (one past the last) for a choice this plan does not carry.

    The plan has two sides.  Tokens into slots (dispatch, the combine's
    transpose) always go by ``src``: E * C rows.  Slots back into
    tokens (combine, the dispatch's transpose, the gate's gradient) go
    by whichever side is the smaller, which ``_plan_side`` reads off
    these shapes: the k * T (token, choice) pairs through ``idx`` and
    ``slot``, or the E * C slots through ``src`` again.

    And two layouts of the experts' rows.  Padded, ``[E, C, d]``: room
    for C rows of every expert; what a capacity fills (``moe_grouped``,
    the SPMD step) and what a bound smaller than the pairs leaves
    (Kimi, Qwen: the slot side).  Packed (``packed`` set, by
    ``moe_dispatch_held`` alone), ``[1, R, d]``: where C is a bound so
    loose that E * C exceeds what all the pairs can fill
    (``packed_room``; LFM2, SmallThinker), every pass above goes
    through ``packed`` instead, the same rows in the same order from
    the same ``slot`` and ``src``, which stay as they are (the pair
    side reads ``slot``; ``packed.src`` is gathered from ``src``)."""
    slot: jax.Array
    src: jax.Array
    idx: jax.Array
    packed: PackedRows | None = None


def packed_room(pairs: int, e: int, c: int, row_block: int | None):
    """R, the rows of the packed buffer that takes the place of
    ``[E, C]`` slots, or None where the layout stays padded: a fact of
    static shapes.  ``row_block`` is the kernels' (None: a caller that
    has no kernels to lay rows out for); all E experts together hold at
    most the ``pairs`` rows of the routing, each from a row-block
    boundary, so R = ``packed_rows`` always suffices, and the layer
    packs where that is less than the E * C the bound reserves."""
    if row_block is None or c % row_block:
        return None
    room = packed_rows(pairs, e, row_block)
    return room if room < e * c else None


# The slot side sums the first quarter, half or all of the slots, the
# least that holds the rows routed here.  Read on the chip alone,
# [32, 1536, 2048] into [16384, 2048] at a fill of 21 % and
# [16, 4096, 2048] at 38 % (PERF.md section 6, PR 39): the sum costs
# 1.3 ms and 66 ns a slot it visits, so all 49 152 read 4.5 ms and a
# quarter 2.1, against 7.5 on the pair side; of 65 536 the half reads
# 3.5 against 4.7.  Finer steps win nothing there (sixteenths 2.1 and
# 3.2) and every step is a copy of the gather and the scatter in the
# step's code, some 2.3 MB at these shapes, in each of a step's twelve
# sites.  A loop over chunks of the held slots read worse than one
# step: every scatter into [T, d] costs 0.8 ms before its first row.
_SLOT_SHARES = (4, 2, 1)


def _plan_side(plan: MoePlan, site: str) -> str:
    """The side of the plan that the token-side pass ``site`` moves
    rows through, from the plan's static shapes alone: ``"slots"``
    where the experts' buffers hold fewer rows than the routing has
    (token, choice) pairs, E * C < k * T, a chip that holds a share of
    the router's experts; ``"pairs"`` where every expert is here and a
    capacity factor of one or more makes the buffers the larger side.
    The choice is a fact of the traced program, so it is marked once
    for each traced site (``spans.mark``: on the build's ``compile``
    span under a tracer, nothing without one), with the plan's
    ``layout`` (``padded`` | ``packed``) and the ``room`` its buffer
    has, E * C or R rows; a packed plan is always on the pair side
    (R holds every pair, and it packs only where E * C exceeds R)."""
    (t, e), c, k = plan.slot.shape, plan.src.shape[1], plan.idx.shape[1]
    side = "slots" if e * c < k * t else "pairs"
    mark("moe.plan_side", site=site, side=side, pairs=k * t,
         rows=e * c if side == "slots" else k * t,
         layout="padded" if plan.packed is None else "packed",
         room=e * c if plan.packed is None else plan.packed.src.shape[1])
    return side


def moe_plan(idx, pos, keep, cap: int) -> MoePlan:
    """The plan of a routing.  ``pos`` [G, g, E] int32: each token's
    place in the queue of its (group, expert); ``keep`` [G, g, E] bool:
    routed there and under ``cap``, the slots a group has in an
    expert's buffer (C = G * cap).  ``src`` comes from a sort of each
    queue (the kept first, in slot order), not from a scatter of
    scalars: the TPU's scatter takes its updates one after another, a
    price that rows of 8 KB carry (``_sum_by_token``) and indices do
    not."""
    n_groups, g, e = pos.shape
    t = n_groups * g
    group = jnp.arange(n_groups, dtype=jnp.int32)[:, None, None]
    slot = jnp.where(keep, pos + group * cap, -1).reshape(t, e)
    key = jnp.where(keep, pos, g).transpose(0, 2, 1)        # [G, E, g]
    token = jax.lax.broadcasted_iota(jnp.int32, key.shape, 2) + group * g
    _, queue = jax.lax.sort_key_val(key, token, dimension=2)
    n = min(cap, g)
    kept = jnp.sum(keep, axis=1, dtype=jnp.int32)           # [G, E]
    src = jnp.where(jnp.arange(n) < kept[..., None], queue[..., :n], t)
    src = jnp.pad(src, ((0, 0), (0, 0), (0, cap - n)), constant_values=t)
    return MoePlan(slot, src.transpose(1, 0, 2).reshape(e, n_groups * cap),
                   idx)


def _to_slots(x, plan: MoePlan, w=None):
    """Token rows [T, d] into the experts' buffers [E, C, d] ([1, R, d]
    where the plan is packed): slot (e, c) gets the row of token
    ``src[e, c]``, times ``w[token, e]`` where ``w`` [T, E] is given;
    an empty slot gets zeros."""
    src = plan.src if plan.packed is None else plan.packed.src
    xe = jnp.take(x, src, axis=0, mode="fill", fill_value=0)
    if w is None:
        return xe
    ws = _slot_weights(w, plan)
    return xe.astype(ws.dtype) * ws[..., None]


def _slot_weights(w, plan: MoePlan):
    """``w`` [T, E] at each slot's (token, expert) -> [E, C] ([1, R]
    where the plan is packed); zero in an empty slot."""
    if plan.packed is None:
        return jnp.take_along_axis(w.T, plan.src, axis=1, mode="fill",
                                   fill_value=0)
    src, start = plan.packed
    owner = owner_of(jnp.arange(src.shape[1], dtype=jnp.int32), start[1:])
    # an empty row names token T: past the last weight, so the fill
    return jnp.take(w.reshape(-1), src * w.shape[1] + owner, mode="fill",
                    fill_value=0)


def _chosen(plan: MoePlan, e: int):
    """[T, k, E] bool: expert e is the token's k-th choice."""
    return plan.idx[..., None] == jnp.arange(e)


def _of_choice(a, plan: MoePlan):
    """``a`` [T, E] at each token's k experts -> [T, k]; a sum over
    E against a one-hot, which costs less than a gather of scalars."""
    return jnp.sum(jnp.where(_chosen(plan, a.shape[1]), a[:, None, :], 0),
                   axis=-1)


def _from_slots(out, plan: MoePlan):
    """The rows [k, T, d] that a token's k experts hold for it in
    ``out`` [E, C, d] ([1, R, d] where the plan is packed); zeros where
    the token was dropped.  k leads so that the gathered [k * T, d]
    rows need no copy to be seen as that: a [T, k, d] view is another
    tiling on the TPU."""
    row = _slot_of_choice(plan)
    return jnp.take(out.reshape(-1, out.shape[-1]), row.T, axis=0,
                    mode="fill", fill_value=0)


def _slot_of_choice(plan: MoePlan):
    """[T, k] int32: the slot, counted through all the buffers, that
    holds the token's k-th choice (the row of the packed buffer where
    the plan is packed); E * C (R), one past the last, where none
    does."""
    e, c = plan.src.shape
    if plan.packed is None:
        slot = _of_choice(plan.slot, plan)                  # [T, k]
        return jnp.where(slot >= 0, plan.idx * c + slot, e * c)
    src, start = plan.packed
    row = _of_choice(jnp.where(plan.slot >= 0, plan.slot + start, -1), plan)
    # a choice that is not held matches no expert and sums to 0
    return jnp.where((row >= 0) & (plan.idx < e), row, src.shape[1])


def _sum_by_token(rows, plan: MoePlan, w=None):
    """Slot rows [E, C, d] summed into their tokens' rows [T, d], in
    float32: row (e, c), times ``w[src[e, c], e]`` where ``w`` [T, E]
    is given, is added to token ``src[e, c]``; an empty slot adds
    nothing.  The slot side of ``_from_slots`` and a sum over k.

    The slots are sorted by token, which puts the held ones first
    (an empty slot names token T), and one gather and one scatter-add
    in that order take the least share of them (``_SLOT_SHARES``) that
    holds every held one: what moves is the rows the plan holds, not
    the room it has for them.  The TPU applies a scatter's updates
    in their order, so the same inputs give the same bits."""
    e, c, d = rows.shape
    t, n = plan.slot.shape[0], e * c
    src = plan.src.reshape(n)
    token, order = jax.lax.sort_key_val(src, jnp.arange(n, dtype=jnp.int32))
    rows = rows.reshape(n, d)
    ws = None if w is None else _slot_weights(w, plan).reshape(n)

    def first(size):
        def summed():
            r = jnp.take(rows, order[:size], axis=0).astype(_F32)
            if ws is not None:
                r = r * jnp.take(ws, order[:size])[:, None]
            return jnp.zeros((t, d), _F32).at[token[:size]].add(
                r, mode="drop", indices_are_sorted=True)
        return summed
    sizes = sorted({-(-n // share) for share in _SLOT_SHARES})
    held = jnp.sum(src < t, dtype=jnp.int32)
    return jax.lax.switch(jnp.sum(held > jnp.asarray(sizes[:-1])),
                          [first(size) for size in sizes])


@jax.custom_vjp
def dispatch_rows(x, plan: MoePlan):
    """``xe[e, c] = x[src[e, c]]`` in x's dtype, zeros in empty slots
    (the grouped kernels' amax and the expert backward rely on padded
    rows being zero): a gather of E * C rows, whichever side the plan
    has (of the R rows ``xe[0, r] = x[packed.src[0, r]]`` where the
    plan is packed).  Its transpose is a combine with weight one and
    takes the
    plan's smaller side (``_plan_side``): a gather of the k * T pairs'
    rows and a sum over k, or the sum of the slots' rows by token."""
    with scope("moe.dispatch"):
        return _to_slots(x, plan)


def _dispatch_rows_fwd(x, plan):
    return dispatch_rows(x, plan), plan


def _dispatch_rows_bwd(plan, dxe):
    with scope("moe.dispatch"):
        if _plan_side(plan, "dispatch.bwd") == "slots":
            dx = _sum_by_token(dxe, plan)
        else:
            dx = jnp.sum(_from_slots(dxe, plan), axis=0, dtype=_F32)
        return dx.astype(dxe.dtype), None


dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


def moe_dispatch(x2d, w_router, num_experts: int, top_k: int,
                 capacity_factor: float):
    """Capacity-based token dispatch (GShard/Switch style), shared by the
    single-device sparse MoE below and the EP-sharded SPMD step
    (models/spmd.py _moe_block — identical math, with all_to_alls
    inserted around the expert compute).  Tokens land in per-expert
    buffers of C = floor(T*k/E * capacity_factor) slots in arrival
    order (a cumsum position); tokens beyond capacity are dropped (their
    combine weight is zero, the residual carries them).

    Returns (xe [E, C, d] expert inputs in x2d's dtype, plan: the
    ``MoePlan`` of the routing, gate [T, E] combine weights); combine
    with ``moe_combine``."""
    t, _ = x2d.shape
    e = num_experts
    weights, idx = moe_router(x2d, w_router, top_k)         # [T,k] each
    cap = max(1, int(capacity_factor * t * top_k / e))
    xe, plan, gate, _ = _dispatch_choices(x2d, weights, idx, e, cap)
    return xe, plan, gate


def _dispatch_choices(x2d, weights, idx, n: int, slots: int,
                      row_block: int | None = None):
    """The one dispatch body: the choices ``idx`` [T, k] among ``n``
    experts (``n`` itself names no expert) with their ``weights``, into
    ``slots`` rows an expert in arrival order; a choice past ``slots``
    is left out.  Returns ``(xe [n, slots, d], plan, gate [T, n],
    load [n])``, ``load`` the rows routed to each, kept or not.  With
    the kernels' ``row_block`` given, the rows are packed where that
    takes less room (``packed_room``): ``xe`` is then ``[1, R, d]``
    and the plan carries the layout."""
    with scope("moe.router"):
        onehot = jax.nn.one_hot(idx, n, dtype=_F32)         # [T, k, n]
        gate = jnp.sum(onehot * weights[..., None], axis=1)  # [T, n]
    with scope("moe.dispatch"):
        routed = jnp.sum(onehot, axis=1).astype(jnp.int32)  # [T, n] 0/1
        pos = jnp.cumsum(routed, axis=0) - 1                # arrival order
        keep = (routed > 0) & (pos < slots)
        plan = moe_plan(idx, pos[None], keep[None], slots)
        room = packed_room(idx.size, n, slots, row_block)
        if room is not None:
            plan = plan._replace(packed=_pack(
                plan, jnp.sum(keep, axis=0, dtype=jnp.int32), room,
                row_block))
        xe = dispatch_rows(x2d, plan)                       # [n, slots, d]
        load = jnp.sum(routed, axis=0)
    return xe, plan, gate, load


def _pack(plan: MoePlan, kept, room: int, row_block: int) -> PackedRows:
    """The packed layout of ``plan``'s rows in a buffer of ``room``
    rows: ``kept`` [E] rows an expert, each expert's from the next
    multiple of ``row_block`` (``packed_first``).  A row block of the
    buffer mirrors a row block of its owner's slots, so the source map
    is a gather of ``src`` by blocks, indices and not rows; a block
    past the live prefix mirrors none and names token T throughout."""
    (t, e), c = plan.slot.shape, plan.src.shape[1]
    first = packed_first(kept, row_block)                   # [E + 1]
    blk = jnp.arange(room // row_block, dtype=jnp.int32)
    owner = owner_of(blk, first[1:])            # E past the live prefix
    # its owner's first block: the last boundary at or before it
    base = jnp.max(jnp.where(first <= blk[:, None], first, 0), axis=1)
    mirrored = owner * (c // row_block) + blk - base
    src = jnp.take(plan.src.reshape(e * c // row_block, row_block),
                   mirrored, axis=0, mode="fill", fill_value=t)
    return PackedRows(src.reshape(1, room), first[:-1] * row_block)


def moe_dispatch_held(x2d, weights, idx, held: tuple, slots: int,
                      row_block: int | None = None):
    """Dispatch of a routing ``(weights, idx)`` [T, k] over ALL the
    router's experts to the ``held = (first, count)`` of them that live
    here, ``slots`` rows an expert, in arrival order.  No capacity rule
    drops a row: ``slots`` is a bound the load is not to reach, and a
    row past it is left out AND shows in ``load``, from which the
    caller (``moe.moe_held``) counts it and fails the step.  A choice
    whose expert is not held is neither dispatched nor combined: in the
    plan it carries the index ``count``, one past the held, which
    ``_chosen`` matches to no expert and ``_from_slots`` sends to the
    zero fill row.  Where ``count * slots`` is less than the T * k
    pairs, a chip with a share of the experts, most pairs are such
    choices: combine and both backward passes then move rows through
    the plan's slot side (``_plan_side``), the held rows and not one
    for every pair.  Where it is more than all the pairs can fill,
    ``packed_room(T * k, count, slots, row_block)`` rows, a bound so
    loose that most of its slots can never hold a row, and the caller
    names the ``row_block`` of the kernels that will read the buffer
    (``moe.moe_held`` does; a caller without one keeps ``[count,
    slots, d]``), the rows are packed: one buffer ``[1, R, d]``, each
    expert's rows from a row-block boundary, the bound enforced and
    counted as before, and dispatch, combine and their transposes fill,
    gather and weigh R rows, not ``count * slots``.

    Returns ``(xe [count, slots, d] or [1, R, d], plan, gate [T, count],
    load)``: the ``moe_dispatch`` contract over the held experts, and
    ``load`` [count] int32, the rows routed to each (kept or not)."""
    first, n = held
    with scope("moe.router"):
        local = idx - first
        local = jnp.where((local >= 0) & (local < n), local, n)
    return _dispatch_choices(x2d, weights, local, n, slots, row_block)


@jax.custom_vjp
def moe_combine(out, plan: MoePlan, gate):
    """Per-expert outputs [E, C, d] ([1, R, d] where the plan is packed:
    the same rows, found at ``packed.start[e] + slot``) back to tokens
    [T, d], in ``out``'s dtype, with the plan and the combine weights of
    ``moe_dispatch``:
    ``y[t] = sum_k gate[t, e_k] * out[e_k, slot[t, e_k]]`` over the
    token's top-k, product and sum in float32; a dropped choice adds
    nothing.  On the plan's pair side (``_plan_side``) the k * T rows
    are gathered and summed over k; on its slot side the E * C slot
    rows, times their slot's gate, are summed by token.  The transpose
    is a dispatch of ``dy`` with the gate as a per-slot weight on
    either side; the gate's gradient is the dot of ``dy[t]`` with the
    expert's row for t, taken a pair over the gathered rows or a slot
    over the rows where they lie and read back as E * C scalars."""
    with scope("moe.combine"):
        if _plan_side(plan, "combine") == "slots":
            return _sum_by_token(out, plan,
                                 gate.astype(_F32)).astype(out.dtype)
        w = _of_choice(gate.astype(_F32), plan).T           # [k, T]
        rows = _from_slots(out, plan).astype(_F32)
        return jnp.sum(rows * w[..., None], axis=0).astype(out.dtype)


def _moe_combine_fwd(out, plan, gate):
    return moe_combine(out, plan, gate), (out, plan, gate)


def _moe_combine_bwd(res, dy):
    out, plan, gate = res
    with scope("moe.combine"):
        if _plan_side(plan, "combine.bwd") == "slots":
            dys = _to_slots(dy, plan).astype(_F32)          # [E, C, d]
            dout = dys * _slot_weights(gate.astype(_F32), plan)[..., None]
            dot = jnp.sum(out.astype(_F32) * dys, axis=-1)  # [E, C]
            dw = jnp.take(dot.reshape(-1), _slot_of_choice(plan),
                          mode="fill", fill_value=0)        # [T, k]
        else:
            dout = _to_slots(dy, plan, gate.astype(_F32))
            dw = jnp.sum(_from_slots(out, plan).astype(_F32)
                         * dy.astype(_F32), axis=-1).T      # [T, k]
        dgate = jnp.sum(jnp.where(_chosen(plan, gate.shape[1]),
                                  dw[..., None], 0), axis=1)   # [T, E]
        return dout.astype(out.dtype), None, dgate.astype(gate.dtype)


moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)


def moe_sparse(x2d, w_router, w_gate, w_up, w_down, top_k: int,
               capacity_factor: float = 1.25):
    """Capacity-based sparse MoE for single-device execution.  Expert
    FLOPs are E*C*ffn ~ k*cf*T*ffn instead of moe_dense's E*T*ffn.  At
    capacity_factor >= E/top_k nothing drops and the result matches
    moe_dense exactly (tests/test_models.py pins this)."""
    e = w_gate.shape[0]
    xe, plan, gate = moe_dispatch(x2d, w_router, e, top_k, capacity_factor)
    with scope("moe.experts"):
        h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xe, w_gate,
                                   preferred_element_type=_F32))
        h = h * jnp.einsum("ecd,edh->ech", xe, w_up,
                           preferred_element_type=_F32)
        out = jnp.einsum("ech,ehd->ecd", h.astype(x2d.dtype), w_down,
                         preferred_element_type=_F32)       # [E, C, d]
    with scope("moe.combine"):
        return moe_combine(out, plan, gate).astype(x2d.dtype)


def cross_entropy(logits, targets):
    """Mean token cross-entropy; logits [.., V] in any dtype, fp32 inside.
    Computed as mean(logsumexp - logits[target]) so the full [.., V]
    log-probability tensor is never materialized (log_softmax would write
    and re-read it — half a GB at B=2 S=2048 V=32k)."""
    lse = jax.scipy.special.logsumexp(logits.astype(_F32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt.astype(_F32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def blocked_head_cross_entropy(x, table, targets, row_block: int):
    """``cross_entropy(x @ table.T, targets)`` for ``x`` [rows, D], a
    tied ``table`` [V, D] and ``targets`` [rows], ``row_block`` rows at
    a time, so that [rows, V] logits never lie whole in HBM; ``rows``
    is a multiple of ``row_block``.

    The loss is the last thing a step computes, so a block's logits
    gradient, (softmax - onehot) / rows, is formed in the loop
    iteration that formed the logits, and the block's ``dx`` and its
    share of ``dtable`` are taken while the logits are still there:
    three matmuls a block, where autodiff of checkpointed blocks makes
    every block's logits a second time.  A caller that takes no
    gradient compiles to the logits and the loss alone."""
    return _blocked_head_fwd(x, table, targets, row_block)[0]


def _blocked_head_fwd(x, table, targets, row_block):
    rows, d = x.shape
    blocks = rows // row_block

    def block(dtable, xt):
        xb, tb = xt
        logits = jnp.dot(xb, table.T)                       # [R, V]
        z = logits.astype(_F32)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        tgt = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
        onehot = jnp.arange(table.shape[0]) == tb[:, None]
        dlogits = ((jnp.exp(z - lse[:, None]) - onehot)
                   / rows).astype(logits.dtype)
        dtable = dtable + jnp.dot(dlogits.T, xb,
                                  preferred_element_type=_F32)
        return (dtable.astype(table.dtype),
                (jnp.mean(lse - tgt.astype(_F32)), jnp.dot(dlogits, table)))

    with scope("head_loss"):
        dtable, (losses, dx) = jax.lax.scan(
            block, jnp.zeros_like(table),
            (x.reshape(blocks, row_block, d),
             targets.reshape(blocks, row_block)))
        return jnp.mean(losses), (dx.reshape(rows, d), dtable)


def _blocked_head_bwd(row_block, res, g):
    dx, dtable = res
    with scope("head_loss"):
        return ((g * dx).astype(dx.dtype),
                (g * dtable).astype(dtable.dtype), None)


blocked_head_cross_entropy.defvjp(_blocked_head_fwd, _blocked_head_bwd)
