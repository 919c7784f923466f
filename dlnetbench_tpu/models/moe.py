"""Expert-parallel MoE core (ISSUE 15): seeded deterministic routing,
capacity/drop accounting, routing stats, and the grouped expert FFN.

The reference's ``hybrid_3d_moe`` proxy replays all-to-all VOLUMES; the
SPMD tier (models/spmd.py) has run the GShard capacity dispatch with
real math since the seed — but its token-drop rule was per-rank arrival
order, so the set of dropped tokens depended on how the batch happened
to be sharded.  This module makes routing a first-class, certifiable
schedule:

* **Seeded grouped token-drop** — capacity is enforced per GROUP of
  ``group_tokens`` consecutive tokens in canonical (batch-row,
  sequence) order, and within a group the dispatch queue order is a
  seeded splitmix-style priority over GLOBAL token ids instead of
  arrival order.  Because a group never straddles a shard boundary
  (``group_tokens`` must divide the sequence shard), the kept/dropped
  set is a pure function of ``(tokens, router weights, seed,
  group_tokens)`` — IDENTICAL across shard counts, which is what lets
  the dryrun certify token-identical routing between sharded and
  single-device execution (the acceptance bar the arrival-order rule
  could never meet).  ``drop_seed=None`` + one group delegates to
  ``layers.moe_dispatch`` — bit-identical legacy behavior.
* **The routing is a plan of indices** — ``dispatch`` returns a
  ``layers.MoePlan`` (each token's slot in each expert's buffer, the
  token in each slot, the token's experts), not [T, E, C] one-hots:
  dispatch, combine and their backward passes gather rows through it
  (``layers.dispatch_rows``, ``layers.moe_combine``), and the
  per-expert kept counts the grouped kernels skip by are read off it.
* **Drop closed form** — ``expected_drops`` states the capacity
  arithmetic (``sum_e,g max(0, n_ge - cap_g)``) the property tests pin
  the measured drop counts against.
* **Routing stats** — per-expert load, drop rate and router entropy as
  in-graph arrays (``dispatch(..., with_stats=True)``) plus the
  ``stats_globals`` formatter that shapes them as record globals
  (hoisted by ``metrics/parser.py``, volatile at merge like every
  measured quantity).
* **Grouped expert FFN** — ``moe_grouped`` runs the sparse MoE through
  the Pallas grouped-matmul kernels (ops/grouped_matmul.py): per-expert
  token batching with count-aware block skipping and the PR-3 int8/fp8
  VMEM-prologue quantization recipes.
* **Schedule twin** — ``a2a_elems_per_rank`` mirrors the native
  schedule's all-to-all message arithmetic
  (``core/schedule.moe_schedule``), so the native-vs-SPMD MoE parity
  test compares one formula against the twin's ACTUAL dispatch buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dlnetbench_tpu.metrics.spans import scope
from dlnetbench_tpu.models import layers as L

_F32 = jnp.float32


# ----------------------------------------------------------- priority
def token_priority(seed: int, gids):
    """Seeded per-token drop priority: a 32-bit murmur3-style finalizer
    over the GLOBAL token id, xor-folded with the seed.  Pure function
    of (seed, gid) — the same token gets the same priority on every
    rank of every mesh, which is the whole point."""
    h = gids.astype(jnp.uint32) ^ jnp.uint32((seed * 0x9E3779B9)
                                             & 0xFFFFFFFF)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def group_capacity(group_tokens: int, top_k: int, num_experts: int,
                   capacity_factor: float) -> int:
    """Per-(group, expert) dispatch slots — the ONE capacity spelling
    (``layers.moe_dispatch`` uses the same arithmetic at group =
    the whole batch)."""
    return max(1, int(capacity_factor * group_tokens * top_k
                      / num_experts))


def expected_drops(counts, cap_g: int):
    """The capacity-factor closed form: tokens routed beyond their
    (group, expert) capacity.  ``counts``: [G, E] routed-assignment
    histogram.  The property tests pin measured drops to this."""
    over = jnp.maximum(counts - cap_g, 0)
    return jnp.sum(over)


# ----------------------------------------------------------- dispatch
def dispatch(x2d, w_router, num_experts: int, top_k: int,
             capacity_factor: float, *, drop_seed: int | None = None,
             group_tokens: int = 0, gids=None, with_stats: bool = False):
    """Capacity-based token dispatch with seeded grouped token-drop.

    Returns ``(xe [E, C_total, d], plan, gate [T, E])`` (+ ``stats``
    with ``with_stats``) — the ``layers.moe_dispatch`` contract, the
    plan a ``layers.MoePlan`` over an expert buffer subdivided into
    per-group capacity blocks (``C_total = G * cap_g``).

    * ``group_tokens = 0`` (one group) + ``drop_seed = None`` is the
      LEGACY path — it delegates to ``layers.moe_dispatch`` outright,
      bit-identical to the pre-ISSUE-15 harness.
    * ``drop_seed`` set: within each group the dispatch queue order is
      the seeded priority over ``gids`` (global token ids; defaults to
      ``arange(T)`` for single-device callers) instead of arrival
      order.
    * ``group_tokens > 0``: capacity is per group of that many
      CONSECUTIVE tokens; T must divide evenly.  Because groups nest
      inside every shard's local block (validated by the SPMD config),
      assignments are shard-count invariant.
    """
    t, _ = x2d.shape
    e = num_experts
    g = group_tokens or t
    if t % g:
        raise ValueError(f"moe.dispatch: {t} tokens not divisible by "
                         f"group_tokens={g}")
    if drop_seed is None and g == t:
        xe, plan, gate = L.moe_dispatch(x2d, w_router, e, top_k,
                                        capacity_factor)
        if not with_stats:
            return xe, plan, gate
        cap = group_capacity(t, top_k, e, capacity_factor)
        with scope("moe.dispatch"):
            counts = jnp.sum(jax.nn.one_hot(plan.idx, e, dtype=jnp.int32),
                             axis=(0, 1))[None]          # [1, E]
            stats = _routing_stats(x2d, w_router, counts, plan, cap)
        return xe, plan, gate, stats

    n_groups = t // g
    cap_g = group_capacity(g, top_k, e, capacity_factor)
    weights, idx = L.moe_router(x2d, w_router, top_k)
    with scope("moe.router"):
        onehot = jax.nn.one_hot(idx, e, dtype=_F32)          # [T, k, E]
        gate = jnp.sum(onehot * weights[..., None], axis=1)  # [T, E]
    with scope("moe.dispatch"):
        routed = jnp.sum(onehot, axis=1).astype(jnp.int32)   # [T, E] 0/1
        routed = routed.reshape(n_groups, g, e)
        if drop_seed is not None:
            if gids is None:
                gids = jnp.arange(t, dtype=jnp.int32)
            prio = token_priority(drop_seed, gids).reshape(n_groups, g)
            order = jnp.argsort(prio, axis=1)                # queue order
            inv = jnp.argsort(order, axis=1)
            rs = jnp.take_along_axis(routed, order[..., None], axis=1)
            pos_s = jnp.cumsum(rs, axis=1) - 1
            pos = jnp.take_along_axis(pos_s, inv[..., None], axis=1)
        else:
            pos = jnp.cumsum(routed, axis=1) - 1
        keep = (routed > 0) & (pos < cap_g)                  # [G, g, E]
        plan = L.moe_plan(idx, pos, keep, cap_g)
        xe = L.dispatch_rows(x2d, plan)
        if not with_stats:
            return xe, plan, gate
        counts = jnp.sum(routed, axis=1)                     # [G, E]
        stats = _routing_stats(x2d, w_router, counts, plan, cap_g)
    return xe, plan, gate, stats


def _routing_stats(x2d, w_router, counts, plan, cap_g: int) -> dict:
    """In-graph routing stats: routed/kept histograms, drop count (and
    its closed form — equal by construction, pinned by tests), router
    entropy of the MEAN full-softmax distribution (normalized to
    [0, 1] by ln E)."""
    e = counts.shape[-1]
    probs = jax.nn.softmax(L.router_logits(x2d, w_router), axis=-1)
    p_mean = jnp.mean(probs, axis=0)                     # [E]
    entropy = -jnp.sum(p_mean * jnp.log(p_mean + 1e-12))
    routed = jnp.sum(counts, axis=0)                     # [E]
    kept = jnp.sum(plan.slot >= 0, axis=0, dtype=jnp.int32)  # [E]
    return {
        "routed": routed,
        "kept": kept,
        "dropped": jnp.sum(routed) - jnp.sum(kept),
        "expected_dropped": expected_drops(counts, cap_g),
        "entropy": entropy / jnp.log(jnp.asarray(float(e))),
    }


def stats_globals(stats, *, num_experts: int, top_k: int,
                  capacity_factor: float, drop_seed: int | None,
                  group_tokens: int) -> dict:
    """Shape measured routing stats (host-side numpy-ables) as record
    globals: the knobs are COMPARABLE (different routing configs are
    different runs), the measured load/drop/entropy ride the volatile
    ``moe`` block (metrics/merge) and hoist as ``moe_*`` columns
    (metrics/parser)."""
    import numpy as np
    routed = np.asarray(stats["routed"], dtype=float)
    total = max(float(routed.sum()), 1.0)
    load = routed / total
    mean = max(float(load.mean()), 1e-12)
    return {
        "moe_experts": int(num_experts),
        "moe_top_k": int(top_k),
        "moe_capacity_factor": float(capacity_factor),
        "moe_drop_seed": (int(drop_seed) if drop_seed is not None
                          else None),
        "moe_group_tokens": int(group_tokens),
        "moe": {
            "expert_load": [round(float(v), 6) for v in load],
            "load_imbalance": round(float(load.max()) / mean, 4),
            "drop_rate": round(float(stats["dropped"]) / total, 6),
            "router_entropy": round(float(stats["entropy"]), 6),
        },
    }


# -------------------------------------------------- grouped expert FFN
def expert_ffn(xe, w_gate, w_up, w_down, *, impl: str = "einsum",
               quant: str | None = None, counts=None,
               mlp_int8: bool = False, backward: str = "einsum",
               activation: str = "silu", bound: int | None = None):
    """The expert-FFN dispatch point shared by the single-device MoE
    below and the EP-sharded SPMD path: ``xe`` [E, C, d] dispatch
    buffers -> [E, C, d].

    * ``impl="einsum"`` — the XLA batched-einsum path (the pre-ISSUE-15
      spelling; ``mlp_int8`` keeps the r5 int8_dot_batched recipe).
    * ``impl="grouped"`` — the Pallas grouped-matmul kernels with
      optional fused int8/fp8 quantization (``quant``) and count-aware
      block skipping (``counts``); ``backward`` is ``grouped_ffn``'s
      (``"counted"``: the backward skips the row blocks the forward
      skipped) and ``activation`` the gate's (``grouped_ffn``'s: ``silu``
      or ``relu``; the einsum path is the SwiGLU only); ``bound`` = C
      says that ``xe`` is a packed buffer ``[1, R, d]`` (``grouped_ffn``'s:
      the grouped kernels with the counted backward alone).
    """
    with scope("moe.experts"):
        if impl == "grouped":
            from dlnetbench_tpu.ops.grouped_matmul import grouped_ffn
            return grouped_ffn(xe, w_gate, w_up, w_down, counts=counts,
                               fmt=quant, backward=backward,
                               activation=activation,
                               bound=bound).astype(_F32)
        if impl != "einsum":
            raise ValueError(f"moe.expert_ffn: unknown impl {impl!r} "
                             f"(einsum | grouped)")
        if activation != "silu":
            raise ValueError(f"moe.expert_ffn: the einsum path has the "
                             f"SwiGLU only, not {activation!r}")
        if mlp_int8:
            from dlnetbench_tpu.ops.int8 import int8_dot_batched
            dt = xe.dtype
            g = int8_dot_batched(xe, w_gate.astype(dt))
            u = int8_dot_batched(xe, w_up.astype(dt))
            h = jax.nn.silu(g.astype(_F32)) * u.astype(_F32)
            out = int8_dot_batched(h.astype(dt), w_down.astype(dt))
            return out.astype(_F32)
        h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xe, w_gate,
                                   preferred_element_type=_F32))
        h = h * jnp.einsum("ecd,edh->ech", xe, w_up,
                           preferred_element_type=_F32)
        return jnp.einsum("ech,ehd->ecd", h.astype(xe.dtype), w_down,
                          preferred_element_type=_F32)


def moe_grouped(x2d, w_router, w_gate, w_up, w_down, top_k: int,
                capacity_factor: float = 1.25, *,
                quant: str | None = None,
                drop_seed: int | None = None):
    """Single-device sparse MoE through the grouped Pallas kernels
    (``TransformerConfig.moe_impl="grouped"``): the ``layers.moe_sparse``
    schedule with the expert FFN running as per-expert token batches —
    blocks past an expert's kept-token count are skipped, and ``quant``
    selects the fused int8/fp8 recipes."""
    e = w_gate.shape[0]
    xe, plan, gate, stats = dispatch(x2d, w_router, e, top_k,
                                     capacity_factor, drop_seed=drop_seed,
                                     with_stats=True)
    y = expert_ffn(xe, w_gate, w_up, w_down,
                   impl="grouped", quant=quant, counts=stats["kept"])
    with scope("moe.combine"):
        # the grouped kernels compute in x2d's dtype and expert_ffn
        # widens what they return: combine in the kernels' dtype, so
        # that its gathers move rows of that width, not float32's
        return L.moe_combine(y.astype(x2d.dtype), plan, gate)


def moe_held(x2d, w_router, w_gate, w_up, w_down, top_k: int, *,
             held: tuple, slots: int, scoring: str = "softmax",
             bias=None, scale: float = 1.0, router_x=None,
             activation: str = "silu"):
    """The routed experts' part of an expert layer on a chip that holds
    ``held = (first, count)`` of the router's experts (``w_gate`` /
    ``w_up`` / ``w_down`` are stacked over those ``count``): the router
    scores all of them and takes its top-k over all of them, the rows
    whose expert lives here go through the grouped kernels, and the
    result is what those experts add.  No token is dropped: ``slots``
    bounds an expert's rows and a row past it is counted.  Because the
    buffer is a bound and not a capacity it is loose by design, most
    of its slots the dispatch's zeros, so the experts take the counted
    backward (``grouped_ffn(backward="counted")``: kernels over the row
    blocks the forward multiplied); ``moe_grouped``, whose capacity
    drops rows to stay nearly full, keeps the einsums over every slot.
    Which layout the rows take is read off the static shapes here
    (``layers.packed_room`` under the kernels' ``row_block``), as the
    plan's side is: where ``count * slots`` exceeds the R rows that
    all k * T pairs can fill when each expert's rows start at a
    row-block boundary (a whole layer, LFM2; a loose bound,
    SmallThinker), ONE packed buffer ``[1, R, d]`` goes through
    dispatch, the kernels (``grouped_ffn(bound=slots)``) and combine,
    and the fills, gathers and row blocks are R's, not the bound's;
    where the
    bound leaves fewer slots than pairs (Kimi, Qwen) the buffer stays
    ``[count, slots, d]`` and the plan takes its slot side.  The two
    layouts hold the same rows in the same blocks: equal to the bit,
    ``past_bound`` included (``tests/test_moe_packed.py``).
    ``router_x`` [T, d] is what the router reads where that is another
    tensor than the experts' ``x2d`` (a router placed before attention
    reads the layer's normed input); ``activation`` the experts' gate's
    (``grouped_ffn``).

    Returns ``(y [T, d], routing)``; ``routing`` holds int32 scalars,
    ``routed`` rows routed to held experts, ``max_load`` the largest
    load of one of them, ``past_bound`` rows left out at ``slots``
    (a step that reads one has not computed the layer), and
    ``choices`` [T, k], the router's selection over all its experts."""
    from dlnetbench_tpu.ops.grouped_matmul import row_block
    routed_on = x2d if router_x is None else router_x
    weights, idx = L.moe_router(routed_on, w_router, top_k, scoring=scoring,
                                bias=bias, scale=scale)
    e, d, f = w_gate.shape
    xe, plan, gate, load = L.moe_dispatch_held(
        x2d, weights, idx, held, slots,
        row_block=row_block(e, slots, d, f, x2d.dtype))
    with scope("moe.dispatch"):
        stats = _routing_stats(routed_on, w_router, load[None], plan, slots)
        routing = {"routed": jnp.sum(stats["routed"]),
                   "max_load": jnp.max(stats["routed"]),
                   "past_bound": stats["dropped"], "choices": idx}
    y = expert_ffn(xe, w_gate, w_up, w_down, impl="grouped",
                   counts=stats["kept"], backward="counted",
                   activation=activation,
                   bound=None if plan.packed is None else slots)
    with scope("moe.combine"):
        return L.moe_combine(y.astype(x2d.dtype), plan, gate), routing


# ------------------------------------------------------- schedule twin
def a2a_elems_per_rank(tokens_per_mb: int, top_k: int, embed_dim: int,
                       ep: int) -> int:
    """The native schedule's per-rank all-to-all message arithmetic
    (``core/schedule.moe_schedule``: ``tokens_per_mb * top_k *
    embed_dim // num_expert_shards`` — reference
    hybrid_3d_moe.cpp:354-359), restated here so the parity test can
    pin BOTH tiers to one formula."""
    return tokens_per_mb * top_k * embed_dim // ep


def spmd_a2a_elems(cfg, dp: int, tp: int) -> int:
    """The JAX twin's ACTUAL per-rank dispatch-buffer elements per
    microbatch tick: the [E, C, d] buffer ``_moe_block`` hands the
    EP all-to-all.  At ``capacity_factor == 1`` (and divisible shapes)
    this equals ``a2a_elems_per_rank`` over this rank's token share —
    the native-vs-SPMD schedule-parity certification
    (tests/test_moe.py)."""
    mb_size = cfg.batch // (dp * cfg.num_microbatches)
    t_loc = mb_size * (cfg.seq_len // tp)
    cap = group_capacity(cfg.moe_group_tokens or t_loc, cfg.top_k,
                         cfg.num_experts, cfg.capacity_factor)
    n_groups = t_loc // (cfg.moe_group_tokens or t_loc)
    return cfg.num_experts * n_groups * cap * cfg.embed_dim
