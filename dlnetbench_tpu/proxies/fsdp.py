"""FSDP / ZeRO-3 proxy: prefetched unit allgathers + gradient
reduce-scatter, with optional hybrid-sharding replicas.

Reference hot loop (cpp/data_parallel/fsdp.cpp:73-163):

    Allgather(unit 0)
    for u in 0..units-2:                      # forward
        Iallgather(unit u+1)                  # prefetch next unit
        usleep(fwd/units); Wait(u+1)          # compute hides the gather
    for u in units-1..1:                      # backward
        Iallgather(unit u-1)                  # prefetch previous unit
        usleep(bwd/units)
        Reduce_Scatter_block(unit u grads)
        [replicas>1] Iallreduce(shard u) on the replica comm
        Wait(u-1)
    unit 0 bwd + reduce-scatter [+ final allreduce]; WaitAll

World = sharding_factor x num_replicas over a 2D mesh (replica axis = dp,
shard axis = tp), mirroring the reference's two comm splits
(fsdp.cpp:257-265).  The prefetch overlap is dataflow: each allgather's
operand is tied to the chain state *before* the burn that hides it, and its
result is consumed after — XLA gets exactly the reference's overlap window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from dlnetbench_tpu.utils.jax_compat import shard_map
from jax.sharding import PartitionSpec as P

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.core.model_stats import ModelStats
from dlnetbench_tpu.core.schedule import fsdp_schedule
from dlnetbench_tpu.parallel import collectives as col
from dlnetbench_tpu.parallel.buffers import scaled_elems, sharded_zeros
from dlnetbench_tpu.parallel.mesh import AXIS_DP, AXIS_TP, describe_mesh, make_fsdp_mesh
from dlnetbench_tpu.proxies import burn as burnlib
from dlnetbench_tpu.proxies.base import ProxyConfig, StepBundle


def build(stats: ModelStats, num_units: int, cfg: ProxyConfig,
          devices=None, sharding_factor: int | None = None,
          dtype=jnp.float32) -> StepBundle:
    devices = devices if devices is not None else jax.devices()
    world = len(devices)
    sched = fsdp_schedule(stats, num_units, world, sharding_factor)
    mesh = make_fsdp_mesh(sched.num_replicas, sched.sharding_factor, devices)
    cal = burnlib.calibrate()

    fwd_iters = cal.iters_for_us(sched.fwd_us_per_unit * cfg.time_scale)
    bwd_iters = cal.iters_for_us(sched.bwd_us_per_unit * cfg.time_scale)
    shard_elems = scaled_elems(sched.shard_size, cfg.size_scale)
    has_replicas = sched.num_replicas > 1

    # per-rank: one parameter shard + one gradient shard per unit
    shards = [sharded_zeros(mesh, P(), (shard_elems,), dtype)
              for _ in range(num_units)]
    state0 = sharded_zeros(mesh, P(), burnlib.DEFAULT_SHAPE,
                           burnlib.DEFAULT_DTYPE) + burnlib.make_state()

    def step(state, shard_bufs, *, with_compute: bool, with_comm: bool):
        def gather(buf, dep):
            if not with_comm:
                return buf
            return col.allgather(col.tie(buf, dep), AXIS_TP)

        def burn_(s, iters):
            return burnlib.burn(s, iters) if with_compute else s

        def grad_sync(full_unit, dep):
            """reduce-scatter this unit's grads; cross-replica allreduce."""
            if not with_comm:
                return full_unit[:shard_elems]
            g = col.reduce_scatter(col.tie(full_unit, dep), AXIS_TP)
            if has_replicas:
                g = col.allreduce(g, AXIS_DP)
            return g

        outs = []
        # forward: gather unit 0 eagerly, then prefetch u+1 under compute
        full = gather(shard_bufs[0], state)
        for u in range(num_units - 1):
            nxt = gather(shard_bufs[u + 1], state)   # issue before burn
            state = burn_(state, fwd_iters)
            state = col.tie(state, full)             # Wait(u) semantics
            full = nxt
        state = burn_(state, fwd_iters)              # last unit fwd
        state = col.tie(state, full)

        # backward: unit N-1 is still resident from the forward's last
        # prefetch (the reference also reuses it, fsdp.cpp:111-117 gathers
        # only units N-2..0 in backward: 2N-1 gathers per step total);
        # prefetch u-1 under compute, reduce-scatter grads of u
        for u in range(num_units - 1, 0, -1):
            prv = gather(shard_bufs[u - 1], state)
            state = burn_(state, bwd_iters)
            outs.append(grad_sync(full, state))
            state = col.tie(state, prv)
            full = prv
        state = burn_(state, bwd_iters)              # unit 0 bwd
        outs.append(grad_sync(full, state))
        return (state, *col.fence(*outs))            # WaitAll (fsdp.cpp:153-162)

    def make(with_compute, with_comm):
        fn = shard_map(
            functools.partial(step, with_compute=with_compute,
                              with_comm=with_comm),
            mesh=mesh, in_specs=(P(), tuple(P() for _ in shards)),
            out_specs=P(), check_vma=False)
        # donate every parameter/gradient shard — the outputs are
        # (state', per-unit grad shards), shape-matched, so XLA reuses
        # the buffers instead of copying per step; the burn state stays
        # undonated (proxies/dp.py says why)
        return executor.Program(fn=fn, args=(state0, tuple(shards)),
                                donate_argnums=(1,))

    # comm-only sub-schedules for per-collective timers (reference
    # fsdp.cpp:61-66 allgather / reduce_scatter timers)
    full_units = [sharded_zeros(mesh, P(),
                                (shard_elems * sched.sharding_factor,), dtype)
                  for _ in range(num_units)]

    def make_var(body, bufs):
        fn = shard_map(body, mesh=mesh,
                       in_specs=(tuple(P() for _ in bufs),),
                       out_specs=P(), check_vma=False)
        return executor.Program(fn=fn, args=(tuple(bufs),))

    def ag_body(bufs):
        # match the full schedule's gather count: N forward + N-1 backward.
        # The backward-round operands are tied to the forward results so XLA
        # cannot CSE the structurally-identical second gather of each buffer.
        outs = [col.allgather(b, AXIS_TP) for b in bufs]
        outs += [col.allgather(col.tie(b, outs[-1]), AXIS_TP)
                 for b in bufs[:-1]]
        return col.fence(*outs)

    def rs_body(bufs):
        outs = []
        for full in bufs:
            g = col.reduce_scatter(full, AXIS_TP)
            if has_replicas:
                g = col.allreduce(g, AXIS_DP)
            outs.append(g)
        return col.fence(*outs)

    meta = {
        "proxy": "fsdp",
        "model": stats.name,
        "world_size": world,
        "num_units": num_units,
        "sharding_factor": sched.sharding_factor,
        "num_replicas": sched.num_replicas,
        "shard_bytes": int(shard_elems * jnp.dtype(dtype).itemsize),
        "schedule_shard_bytes": int(sched.shard_size * stats.bytes_per_element),
        "unit_bytes": int(shard_elems * sched.sharding_factor
                          * jnp.dtype(dtype).itemsize),
        "fwd_us_per_unit": sched.fwd_us_per_unit * cfg.time_scale,
        "bwd_us_per_unit": sched.bwd_us_per_unit * cfg.time_scale,
        "burn_ns_per_iter": cal.ns_per_iter,
        # bytes per iteration per timed region (analysis/bandwidth.py):
        # allgather = (N fwd + N-1 bwd prefetch) gathers of a full unit;
        # reduce_scatter = N scatters (+ N cross-replica allreduces of the
        # shard when hybrid-sharded)
        "comm_model": {
            "allgather_time": [
                {"kind": "allgather", "group": sched.sharding_factor,
                 "bytes": int((2 * num_units - 1) * shard_elems
                              * sched.sharding_factor
                              * jnp.dtype(dtype).itemsize)}],
            "reduce_scatter_time": [
                {"kind": "reduce_scatter", "group": sched.sharding_factor,
                 "bytes": int(num_units * shard_elems
                              * sched.sharding_factor
                              * jnp.dtype(dtype).itemsize)}] + (
                [{"kind": "allreduce", "group": sched.num_replicas,
                  "bytes": int(num_units * shard_elems
                               * jnp.dtype(dtype).itemsize)}]
                if has_replicas else []),
        },
        "mesh": describe_mesh(mesh),
        "size_scale": cfg.size_scale,
        "time_scale": cfg.time_scale,
    }
    compiled = executor.compile_programs(
        {"full": make(True, True),
         "compute": make(True, False),
         "comm": make(False, True),
         "allgather": make_var(ag_body, shards),
         "reduce_scatter": make_var(rs_body, full_units)}, meta)
    return StepBundle(
        full=compiled["full"],
        compute=compiled["compute"],
        comm=compiled["comm"],
        variants={"allgather": compiled["allgather"],
                  "reduce_scatter": compiled["reduce_scatter"]},
        global_meta=meta,
    )
