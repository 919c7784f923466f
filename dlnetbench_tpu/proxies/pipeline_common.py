"""Shared GPipe pipeline engine for the hybrid proxies (2D / 3D / 3D-MoE).

Reference structure (cpp/hybrid_parallel/hybrid_2d.cpp:90-169): GPipe runs
all microbatches forward, then all backward, then one blocking DP allreduce
of the stage's gradient shard.  Per rank and per microbatch the work is
recv -> compute -> send (direction mirrored in backward); stage position
asymmetry (first stage never receives, last never sends) is encoded here as
masked ``ppermute`` edge shifts (SURVEY.md §7.3 hard-part 3).

The 3D variant adds two TP allreduces per microbatch per direction after
the p2p hop (Megatron column+row parallel linear, hybrid_3d.cpp:142-148,
177-183).  The MoE variant instead adds ``2 x layers_per_stage``
all-to-alls per microbatch per direction (token dispatch + combine per MoE
layer, hybrid_3d_moe.cpp:161-165, 196-200) and replaces the gradient sync
with the two-level scheme (non-expert over EP, expert shard over DP,
hybrid_3d_moe.cpp:202-208).

All three are one jitted shard_map program over a (dp, pp, tp) mesh; the
tp axis carries TP or EP grouping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from dlnetbench_tpu.utils.jax_compat import shard_map
from jax.sharding import PartitionSpec as P

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.core.model_card import ModelCard
from dlnetbench_tpu.core.model_stats import ModelStats
from dlnetbench_tpu.core.schedule import (
    moe_schedule, pipeline_schedule, zb_tables, zb_unit_ticks)
from dlnetbench_tpu.parallel import collectives as col
from dlnetbench_tpu.parallel.buffers import scaled_elems, sharded_zeros
from dlnetbench_tpu.parallel.mesh import (
    AXIS_DP, AXIS_PP, AXIS_TP, describe_mesh, make_grid_mesh)
from dlnetbench_tpu.proxies import burn as burnlib
from dlnetbench_tpu.proxies.base import ProxyConfig, StepBundle


def _infer_dp(world: int, num_stages: int, tp: int, dp: int,
              label: str = "stages*tp (reference hybrid_3d.cpp:272)") -> int:
    if dp:
        return dp
    if world % (num_stages * tp) != 0:
        raise ValueError(f"world {world} not divisible by "
                         f"{label} = {num_stages * tp}")
    return world // (num_stages * tp)


def build(stats: ModelStats, card: ModelCard, cfg: ProxyConfig, *,
          mode: str, num_stages: int, num_microbatches: int,
          tp: int = 1, num_expert_shards: int = 1, dp: int = 0,
          schedule: str = "gpipe", devices=None,
          dtype=jnp.float32) -> StepBundle:
    """``schedule``: "gpipe" (all-fwd-then-all-bwd, the reference's only
    schedule, hybrid_2d.cpp:106-161), "1f1b" (rebuild extra: pp-1
    forward warmup ticks, then interleaved fwd/bwd pairs, then backward
    cooldown — the up and down pipe hops of a steady-state pair ride the
    bidirectional links together instead of in two serial phases), or
    "zb" (rebuild extra: ZB-H1 zero-bubble — backward split into the
    input-grad hop half and a local weight-grad half that fills the drain
    bubble; core/schedule.py zb_tables)."""
    assert mode in ("2d", "3d", "moe")
    if schedule not in ("gpipe", "1f1b", "zb"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    devices = devices if devices is not None else jax.devices()
    world = len(devices)
    inner = num_expert_shards if mode == "moe" else tp
    dp = _infer_dp(world, num_stages, inner, dp)

    moe = None
    if mode == "moe":
        moe = moe_schedule(stats, card, num_stages=num_stages,
                           num_microbatches=num_microbatches,
                           num_expert_shards=num_expert_shards, dp=dp)
        sched = moe.pipe
    else:
        sched = pipeline_schedule(stats, card, num_stages=num_stages,
                                  num_microbatches=num_microbatches,
                                  dp=dp, tp=tp)
    mesh = make_grid_mesh(dp=dp, pp=num_stages, tp=inner, devices=devices)
    cal = burnlib.calibrate()

    fwd_iters = cal.iters_for_us(sched.fwd_us_per_stage_mb * cfg.time_scale)
    bwd_iters = cal.iters_for_us(sched.bwd_us_per_stage_mb * cfg.time_scale)
    # zb splits backward into equal input-grad (B) and weight-grad (W)
    # halves (dgrad and wgrad each re-walk the layer's matmuls once)
    half_bwd_iters = cal.iters_for_us(
        sched.bwd_us_per_stage_mb / 2 * cfg.time_scale)

    pipe_elems = scaled_elems(sched.pipe_msg_elems, cfg.size_scale)
    dp_elems = scaled_elems(sched.dp_sync_elems, cfg.size_scale)
    tp_elems = scaled_elems(sched.tp_msg_elems, cfg.size_scale) \
        if sched.tp_msg_elems else 0
    a2a_elems = 0
    if moe is not None:
        a2a_elems = scaled_elems(moe.a2a_elems, cfg.size_scale)
        a2a_elems += (-a2a_elems) % num_expert_shards  # divisible for A2A
        ne_elems = scaled_elems(moe.nonexpert_sync_elems, cfg.size_scale)
        ex_elems = scaled_elems(moe.expert_sync_elems, cfg.size_scale)

    act = sharded_zeros(mesh, P(), (pipe_elems,), dtype)
    # second carry only exists for 1f1b/zb's independent down-hop; gpipe
    # runs feed a 1-element dummy (like ne_in/ex_in) and never touch it
    act2 = sharded_zeros(mesh, P(), (pipe_elems,), dtype) \
        if schedule in ("1f1b", "zb") else None
    grad_shard = sharded_zeros(mesh, P(), (dp_elems,), dtype)
    tp_buf = sharded_zeros(mesh, P(), (max(tp_elems, 1),), dtype)
    a2a_buf = sharded_zeros(mesh, P(), (max(a2a_elems, num_expert_shards),),
                            dtype)
    ne_buf = sharded_zeros(mesh, P(), (max(ne_elems, 1),), dtype) \
        if moe is not None else None
    ex_buf = sharded_zeros(mesh, P(), (max(ex_elems, 1),), dtype) \
        if moe is not None else None
    state0 = sharded_zeros(mesh, P(), burnlib.DEFAULT_SHAPE,
                           burnlib.DEFAULT_DTYPE) + burnlib.make_state()

    a2a_count = moe.a2a_per_direction if moe is not None else 0
    # per-iteration collective counts — shared by the schedule bodies, the
    # comm-only variants AND the comm_model declaration (drift-proof)
    pp_hops = 2 * num_microbatches
    tp_allreduces = 2 * 2 * num_microbatches       # 2/dir/mb (Megatron)
    ep_alltoalls = 2 * num_microbatches * a2a_count

    def inner_comms(state, bufs, with_comm):
        """Per-microbatch TP allreduces or MoE A2As, after the p2p hop."""
        outs = []
        if not with_comm:
            return outs
        if mode == "3d":
            t = bufs["tp"]
            for _ in range(2):  # column + row parallel linear
                t = col.allreduce(col.tie(t, state), AXIS_TP)
                outs.append(t)
        elif mode == "moe":
            a = bufs["a2a"].reshape(num_expert_shards, -1)
            for _ in range(a2a_count):  # dispatch+combine per MoE layer
                a = col.alltoall(col.tie(a, state), AXIS_TP)
                outs.append(a)
        return outs

    S, M = num_stages, num_microbatches
    # the pipeline clock: both schedules take M + S - 1 ticks per
    # direction — the GPipe fill/drain bubble the reference realizes with
    # blocking recv chains (hybrid_2d.cpp:106-133: stage s's first compute
    # is serialized behind s upstream computes).  One SPMD program cannot
    # block per-stage, so idle ticks are stage-GATED burns instead
    # (rank-predicated trip count, burnlib.burn_if) while the hop keeps
    # every device participating (masked ppermute with per-tick sender
    # sets, so each edge still carries exactly M messages per direction).
    ticks_per_direction = M + S - 1
    # static per-tick sender sets — shared by the schedule bodies, the
    # hop-only variant, and the emitted counts, so they cannot drift.
    # gpipe: stage s computes mb k at tick s+k (fwd) / (S-1-s)+k (bwd)
    gp_fwd_senders = [[s for s in range(S - 1) if s <= t < s + M]
                      for t in range(ticks_per_direction)]
    gp_bwd_senders = [[s for s in range(1, S)
                       if (S - 1 - s) <= t < (S - 1 - s) + M]
                      for t in range(ticks_per_direction)]
    # 1f1b: warmup fill (stage s's k-th warm fwd at tick s+k), M steady
    # fwd/bwd pairs, and a drain where stage s's bwds spill (S-1-s) ticks
    fill_senders = [[s for s in range(min(t + 1, S - 1)) if t - s < M]
                    for t in range(S - 1)]
    steady_f_senders = [[s for s in range(S - 1) if (S - 1 - s + i) < M]
                        for i in range(M)]
    steady_b_senders = [[s for s in range(1, S) if i >= (S - 1 - s)]
                        for i in range(M)]
    drain_senders = [[s for s in range(1, S)
                      if (S - 1 - s) - M <= d < (S - 1 - s)]
                     for d in range(S - 1)]
    # zb: ZB-H1 greedy tick tables (F / input-grad B / weight-grad W);
    # only F and B hop (W is the local weight-grad half)
    zb = zb_tables(S, M) if schedule == "zb" else None
    # backward weight in forward units, from the stats (2.0 for the stat
    # model's bwd = 2 x fwd convention; see ticks_total below)
    bwd_units = (sched.bwd_us_per_stage_mb / sched.fwd_us_per_stage_mb
                 if sched.fwd_us_per_stage_mb > 0 else 2.0)
    if schedule == "gpipe":
        _sender_tables = (gp_fwd_senders, gp_bwd_senders)
    elif schedule == "zb":
        _sender_tables = (zb.f_senders(S), zb.b_senders())
    else:
        _sender_tables = (fill_senders, steady_f_senders,
                          steady_b_senders, drain_senders)
    # permute ops per iteration and total edge messages (must be exactly
    # one per microbatch per edge per direction — the masking invariant)
    pp_permute_ticks = sum(1 for tab in _sender_tables for x in tab if x)
    pp_edge_messages = sum(len(x) for tab in _sender_tables for x in tab)
    assert pp_edge_messages == 2 * M * (S - 1), \
        f"sender masks lost messages: {pp_edge_messages} != {2 * M * (S-1)}"

    def step(state, act_b, act2_b, grad_b, tp_b, a2a_b, ne_b, ex_b, *,
             with_compute: bool, with_comm: bool):
        def burn_(s, iters, active=None):
            if not with_compute:
                return s
            if active is None:
                return burnlib.burn(s, iters)
            return burnlib.burn_if(s, iters, active)

        bufs = {"tp": tp_b, "a2a": a2a_b}
        outs = []
        cur = act_b
        stage = col.axis_index(AXIS_PP)

        if schedule == "gpipe":
            # phase 1 — forward, T = M+S-1 ticks: stage s computes mb k at
            # tick s+k (active window [s, s+M)); senders are the stages
            # whose window covers the tick, so edge s->s+1 moves one
            # message per microbatch and idle stages only sync the permute
            for t in range(ticks_per_direction):
                active = (stage <= t) & (t < stage + M)
                state = burn_(state, fwd_iters, active)
                senders = gp_fwd_senders[t]
                if with_comm and senders:
                    cur = col.shift_up(col.tie(cur, state), AXIS_PP, senders)
                state = col.tie(state, cur)
                if t >= S - 1:  # one mb wave completes per steady tick
                    outs.extend(inner_comms(state, bufs, with_comm))
            # phase 2 — backward, mirrored: stage s active [(S-1-s),
            # (S-1-s)+M), wave flows from the last stage down
            for t in range(ticks_per_direction):
                off = (S - 1) - stage
                active = (off <= t) & (t < off + M)
                state = burn_(state, bwd_iters, active)
                senders = gp_bwd_senders[t]
                if with_comm and senders:
                    cur = col.shift_down(col.tie(cur, state), AXIS_PP,
                                         senders)
                state = col.tie(state, cur)
                if t >= S - 1:
                    outs.extend(inner_comms(state, bufs, with_comm))
        elif schedule == "zb":
            # ZB-H1: one unit op per stage per tick from the greedy
            # tables.  F hops up and B hops down on independent carries
            # (1f1b's overlap property); W is a local burn only — the
            # weight-grad half that fills what 1f1b leaves as bubble.
            def stage_in(stages_list):
                if not stages_list:
                    return None
                pred = (stage == stages_list[0])
                for s in stages_list[1:]:
                    pred = pred | (stage == s)
                return pred

            f_send, b_send = _sender_tables
            cur_b = act2_b
            for t in range(zb.ticks):
                pf = stage_in(zb.f_stages[t])
                if pf is not None:
                    state = burn_(state, fwd_iters, pf)
                pb = stage_in(zb.b_stages[t])
                if pb is not None:
                    state = burn_(state, half_bwd_iters, pb)
                pw = stage_in(zb.w_stages[t])
                if pw is not None:
                    state = burn_(state, half_bwd_iters, pw)
                up = col.shift_up(col.tie(cur, state), AXIS_PP, f_send[t]) \
                    if with_comm and f_send[t] else cur
                down = col.shift_down(col.tie(cur_b, state), AXIS_PP,
                                      b_send[t]) \
                    if with_comm and b_send[t] else cur_b
                # inner TP/EP traffic rides wave completions so totals
                # stay 2 calls x M (same as the other schedules)
                if (S - 1) in zb.f_stages[t]:
                    outs.extend(inner_comms(state, bufs, with_comm))
                if 0 in zb.b_stages[t]:
                    outs.extend(inner_comms(state, bufs, with_comm))
                cur, cur_b = up, down
                state = col.tie(col.tie(state, cur), cur_b)
            outs.append(cur_b)
        else:  # 1f1b: fill / steady pairs / drain, same (M+S-1)-tick clock
            # Unlike the GPipe ticks (blocking send: inner comms tie on the
            # hop, matching the reference's serial recv/compute/send +
            # allreduce order), every 1f1b hop is async (native tier:
            # slot-indexed Isend) — inner comms depend only on the burn,
            # and the next tick ties on the hop landing.
            cur_b = act2_b
            # fill: stage s's k-th warmup fwd at tick s+k, k < S-1-s
            for t in range(S - 1):
                active = (stage <= t) & (t - stage < M)
                state = burn_(state, fwd_iters, active)
                senders = fill_senders[t]
                if with_comm and senders:
                    cur = col.shift_up(col.tie(cur, state), AXIS_PP, senders)
                state = col.tie(state, cur)
            # steady: M pair ticks; the up-hop of one microbatch and the
            # down-hop of another are issued on INDEPENDENT carries
            # (neither burn nor the other hop depends on them until the
            # tick ends), so XLA can ride both directions of the
            # bidirectional links together — the property that makes
            # 1F1B's comm pattern differ from GPipe's two serial phases
            for i in range(M):
                # fwd of mb (S-1-stage)+i while it exists
                active_f = (S - 1 - stage + i) < M
                state = burn_(state, fwd_iters, active_f)
                senders_f = steady_f_senders[i]
                up = col.shift_up(col.tie(cur, state), AXIS_PP, senders_f) \
                    if with_comm and senders_f else cur
                outs.extend(inner_comms(state, bufs, with_comm))
                # bwd of mb i-(S-1-stage) once the bwd wave arrived
                active_b = i >= (S - 1 - stage)
                state = burn_(state, bwd_iters, active_b)
                senders_b = steady_b_senders[i]
                down = col.shift_down(col.tie(cur_b, state), AXIS_PP,
                                      senders_b) \
                    if with_comm and senders_b else cur_b
                outs.extend(inner_comms(state, bufs, with_comm))
                cur, cur_b = up, down
                state = col.tie(col.tie(state, cur), cur_b)
            # drain: stage s's remaining bwds spill (S-1-s) ticks past the
            # steady phase (bounded below for M < S-1-s)
            for d in range(S - 1):
                off = (S - 1) - stage
                active = (d < off) & (d >= off - M)
                state = burn_(state, bwd_iters, active)
                senders = drain_senders[d]
                if with_comm and senders:
                    cur_b = col.shift_down(col.tie(cur_b, state), AXIS_PP,
                                           senders)
                state = col.tie(state, cur_b)
            outs.append(cur_b)
        # phase 3: gradient sync
        if with_comm:
            if mode == "moe":
                # two-level: non-expert over EP, expert shard over DP
                # (hybrid_3d_moe.cpp:202-208)
                outs.append(col.allreduce(col.tie(ne_b, state), AXIS_TP))
                outs.append(col.allreduce(col.tie(ex_b, state), AXIS_DP))
            else:
                outs.append(col.allreduce(col.tie(grad_b, state), AXIS_DP))
        return (state, cur, *col.fence(*outs))

    # the stand-in for a buffer this mode does not have lives on the
    # mesh like every other argument, not on the default device
    zero = sharded_zeros(mesh, P(), (1,), dtype)
    ne_in = ne_buf if ne_buf is not None else zero
    ex_in = ex_buf if ex_buf is not None else zero
    act2_in = act2 if act2 is not None else zero

    def make(with_compute, with_comm):
        fn = shard_map(
            functools.partial(step, with_compute=with_compute,
                              with_comm=with_comm),
            mesh=mesh, in_specs=tuple(P() for _ in range(8)),
            out_specs=P(), check_vma=False)
        # request donation of every carried buffer; the executor keeps
        # only the ones whose leaves have a shape-matched output to
        # rebind from (schedule/mode dependent: gpipe never outputs the
        # act2 dummy, the A2A buffer comes back reshaped, the TP/grad
        # buffers only exist as outputs in their modes) and records the
        # dropped ones in the compile meta as ``undonated``; the burn
        # state (argument 0) stays undonated (proxies/dp.py says why)
        return executor.Program(
            fn=fn,
            args=(state0, act, act2_in, grad_shard, tp_buf, a2a_buf,
                  ne_in, ex_in),
            donate_argnums=tuple(range(1, 8)))

    # per-collective comm-only variants
    def make_var(body, *bufs):
        fn = shard_map(body, mesh=mesh, in_specs=tuple(P() for _ in bufs),
                       out_specs=P(), check_vma=False)
        return executor.Program(fn=fn, args=bufs)

    def pp_body(a, a2=None):
        """Hop-only replay of the schedule's permute ticks (same sender
        masks as the full step, burns elided)."""
        outs = []
        if schedule == "gpipe":
            for senders in gp_fwd_senders:
                if senders:
                    a = col.shift_up(a, AXIS_PP, senders)
                    outs.append(a)
            for senders in gp_bwd_senders:
                if senders:
                    a = col.shift_down(a, AXIS_PP, senders)
                    outs.append(a)
        elif schedule == "zb":  # per-tick up/down on independent carries
            f_send, b_send = _sender_tables
            for t in range(zb.ticks):
                if f_send[t]:
                    a = col.shift_up(a, AXIS_PP, f_send[t])
                    outs.append(a)
                if b_send[t]:
                    a2 = col.shift_down(a2, AXIS_PP, b_send[t])
                    outs.append(a2)
        else:  # 1f1b: steady pairs on independent carries (overlappable)
            for senders in fill_senders:
                if senders:
                    a = col.shift_up(a, AXIS_PP, senders)
                    outs.append(a)
            for i in range(M):
                senders_f = steady_f_senders[i]
                senders_b = steady_b_senders[i]
                if senders_f:
                    a = col.shift_up(a, AXIS_PP, senders_f)
                    outs.append(a)
                if senders_b:
                    a2 = col.shift_down(a2, AXIS_PP, senders_b)
                    outs.append(a2)
            for senders in drain_senders:
                if senders:
                    a2 = col.shift_down(a2, AXIS_PP, senders)
                    outs.append(a2)
        return col.fence(*outs)

    pp_bufs = (act,) if schedule == "gpipe" else (act, act2_in)
    variants = {"pp_comm": make_var(pp_body, *pp_bufs)}
    if mode == "moe":
        def ep_body(a):
            a = a.reshape(num_expert_shards, -1)
            outs = []
            for _ in range(ep_alltoalls):
                a = col.alltoall(a, AXIS_TP)
                outs.append(a)
            return col.fence(*outs)

        def dp_ep_body(ne, ex):
            return col.fence(col.allreduce(ne, AXIS_TP),
                             col.allreduce(ex, AXIS_DP))

        variants["ep_comm"] = make_var(ep_body, a2a_buf)
        variants["dp_ep_comm"] = make_var(dp_ep_body, ne_buf, ex_buf)
    else:
        def dp_body(g):
            return col.allreduce(g, AXIS_DP)

        variants["dp_comm"] = make_var(dp_body, grad_shard)
        if mode == "3d":
            def tp_body(t):
                outs = []
                for _ in range(tp_allreduces):
                    t = col.allreduce(t, AXIS_TP)
                    outs.append(t)
                return col.fence(*outs)

            variants["tp_comm"] = make_var(tp_body, tp_buf)

    itemsize = jnp.dtype(dtype).itemsize
    meta = {
        "proxy": {"2d": "hybrid_2d", "3d": "hybrid_3d",
                  "moe": "hybrid_3d_moe"}[mode],
        "model": stats.name,
        "world_size": world,
        "dp": dp, "num_stages": num_stages, "tp": tp,
        "num_expert_shards": num_expert_shards if mode == "moe" else 0,
        "num_microbatches": num_microbatches,
        "schedule": schedule,
        # both schedules pay the (S-1)-tick fill/drain bubble; analysis can
        # divide runtime by this to recover per-tick cost
        "ticks_per_direction": ticks_per_direction,
        # pipeline clock in UNIT ticks (1 unit = one fwd): gpipe/1f1b
        # span (M+S-1) fwd ticks plus (M+S-1) bwd ticks; zb reports its
        # greedy table's real weighted makespan (3M + S - 1 when M is
        # not tiny and bwd = 2 x fwd).  The backward weight is DERIVED
        # from the stats' bwd/fwd ratio, not hardcoded — a stats file
        # breaking the 2x convention changes the weights, not the
        # honesty.  Dividing runtime by this gives a schedule-comparable
        # per-unit cost (the zero-bubble gain).
        "ticks_total": (zb_unit_ticks(zb, bwd_units) if zb is not None
                        else (1.0 + bwd_units) * ticks_per_direction),
        "pp_permute_ticks": pp_permute_ticks,
        "pp_edge_messages": pp_edge_messages,
        "layers_per_stage": sched.layers_per_stage,
        "pipe_msg_bytes": int(pipe_elems * itemsize),
        "schedule_pipe_msg_bytes": int(sched.pipe_msg_elems
                                       * stats.bytes_per_element),
        "dp_sync_bytes": int(dp_elems * itemsize),
        "tp_msg_bytes": int(tp_elems * itemsize),
        "a2a_bytes": int(a2a_elems * itemsize),
        "fwd_us_per_stage_mb": sched.fwd_us_per_stage_mb * cfg.time_scale,
        "bwd_us_per_stage_mb": sched.bwd_us_per_stage_mb * cfg.time_scale,
        "burn_ns_per_iter": cal.ns_per_iter,
        # bytes each timed region moves per iteration (analysis/bandwidth.py)
        "comm_model": {
            "pp_comm_time": [{"kind": "p2p", "group": num_stages,
                              "bytes": int(pp_hops * pipe_elems * itemsize)}],
            **({"ep_comm_time": [{"kind": "alltoall",
                                  "group": num_expert_shards,
                                  "bytes": int(ep_alltoalls * a2a_elems
                                               * itemsize)}],
                "dp_ep_comm_time": [
                    {"kind": "allreduce", "group": num_expert_shards,
                     "bytes": int(ne_elems * itemsize)},
                    {"kind": "allreduce", "group": dp,
                     "bytes": int(ex_elems * itemsize)}]}
               if mode == "moe" else
               {"dp_comm_time": [{"kind": "allreduce", "group": dp,
                                  "bytes": int(dp_elems * itemsize)}],
                **({"tp_comm_time": [
                    {"kind": "allreduce", "group": tp,
                     "bytes": int(tp_allreduces * tp_elems * itemsize)}]}
                   if mode == "3d" else {})}),
        },
        "mesh": describe_mesh(mesh),
        "size_scale": cfg.size_scale,
        "time_scale": cfg.time_scale,
    }
    compiled = executor.compile_programs(
        {"full": make(True, True),
         "compute": make(True, False),
         "comm": make(False, True),
         **variants}, meta)
    return StepBundle(
        full=compiled["full"],
        compute=compiled["compute"],
        comm=compiled["comm"],
        variants={k: compiled[k] for k in variants},
        global_meta=meta,
    )
