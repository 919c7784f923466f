"""Data-parallel proxy: bucketed gradient allreduce overlapped with
backward compute.

Reference hot loop (cpp/data_parallel/dp.cpp:87-106):

    usleep(fwd)                         # simulated forward
    for each bucket i:
        usleep(bwd / num_buckets)       # simulated bucket backward
        Iallreduce(bucket i)            # async, request/stream i
    WaitAll                             # timed: exposed comm ("barrier")

TPU-native expression: one jitted ``shard_map`` program over a flat mesh
axis.  The burn chain plays the compute; each bucket's ``psum`` operand is
``tie``-d to the chain state *after* that bucket's backward burn, so XLA
may start the allreduce exactly where the reference issues its
``Iallreduce`` — after bucket-i compute, overlapping everything that
follows.  The returned outputs depend on all psums (the ``WaitAll``).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from dlnetbench_tpu.utils.jax_compat import shard_map
from jax.sharding import PartitionSpec as P

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.core.model_stats import ModelStats
from dlnetbench_tpu.core.schedule import dp_schedule
from dlnetbench_tpu.parallel import collectives as col
from dlnetbench_tpu.parallel.buffers import scaled_elems, sharded_zeros
from dlnetbench_tpu.parallel.mesh import AXIS_FLAT, describe_mesh, make_flat_mesh
from dlnetbench_tpu.proxies import burn as burnlib
from dlnetbench_tpu.proxies.base import ProxyConfig, StepBundle


def build(stats: ModelStats, num_buckets: int, cfg: ProxyConfig,
          mesh=None, dtype=jnp.float32) -> StepBundle:
    mesh = mesh if mesh is not None else make_flat_mesh()
    world = mesh.devices.size
    sched = dp_schedule(stats, num_buckets)
    cal = burnlib.calibrate()

    fwd_iters = cal.iters_for_us(sched.fwd_us * cfg.time_scale)
    bwd_iters = cal.iters_for_us(sched.bwd_us_per_bucket * cfg.time_scale)
    bucket_elems = [scaled_elems(s, cfg.size_scale) for s in sched.bucket_sizes]

    # every rank holds the full bucket (allreduce semantics, dp.cpp:227-232)
    grads = [sharded_zeros(mesh, P(), (e,), dtype) for e in bucket_elems]
    state0 = sharded_zeros(mesh, P(), burnlib.DEFAULT_SHAPE,
                           burnlib.DEFAULT_DTYPE) + burnlib.make_state()

    def step(state, buckets, *, with_compute: bool, with_comm: bool):
        if with_compute:
            state = burnlib.burn(state, fwd_iters)
        outs = []
        for g in buckets:
            if with_compute:
                state = burnlib.burn(state, bwd_iters)
            if with_comm:
                outs.append(col.allreduce(col.tie(g, state), AXIS_FLAT))
            else:
                outs.append(g)
        # WaitAll: outputs tie every allreduce together (dp.cpp:191)
        return (state, *col.fence(*outs))

    def make(with_compute, with_comm):
        fn = shard_map(
            functools.partial(step, with_compute=with_compute,
                              with_comm=with_comm),
            mesh=mesh, in_specs=(P(), tuple(P() for _ in grads)),
            out_specs=P(), check_vma=False)
        # donate every gradient bucket: the outputs are exactly
        # (state', allreduced buckets), so XLA updates in place instead
        # of allocating + copying per step; the executor rebinds the
        # donated args from the outputs
        # the burn state (argument 0) is NOT donated: aliased to its
        # output, the loop-carried state is kept in HBM and every burn
        # iteration pays a round trip — 1.29 us against the calibrated
        # 0.35 us on a v5e (my chip run, PR 21) — while donating 128 KB
        # saves nothing
        return executor.Program(fn=fn, args=(state0, tuple(grads)),
                                donate_argnums=(1,))

    bucket_bytes = [int(e * jnp.dtype(dtype).itemsize)
                    for e in bucket_elems]
    meta = {
        "proxy": "dp",
        "model": stats.name,
        "world_size": world,
        "num_buckets": num_buckets,
        "bucket_bytes": bucket_bytes,
        "schedule_bucket_bytes": sched.bucket_bytes,
        "fwd_us": sched.fwd_us * cfg.time_scale,
        "bwd_us_per_bucket": sched.bwd_us_per_bucket * cfg.time_scale,
        "burn_ns_per_iter": cal.ns_per_iter,
        # bytes each timed region moves per iteration
        # (analysis/bandwidth.py).  Mapped to the comm-only variant's
        # directly-timed program — NOT to barrier_time, whose exposed
        # residual (t_full - t_compute) shrinks with overlap and would
        # yield a "bandwidth" unbounded by the physical link
        "comm_model": {"comm_time": [
            {"kind": "allreduce", "group": world,
             "bytes": sum(bucket_bytes)}]},
        "mesh": describe_mesh(mesh),
        "size_scale": cfg.size_scale,
        "time_scale": cfg.time_scale,
    }
    compiled = executor.compile_programs(
        {"full": make(True, True),
         "compute": make(True, False),
         "comm": make(False, True)}, meta)
    return StepBundle(
        full=compiled["full"],
        compute=compiled["compute"],
        comm=compiled["comm"],
        global_meta=meta,
        # checkpointable state: the gradient buckets + burn carry (the
        # executor donated private clones, so these stay readable) —
        # what a dp trainer of this schedule would snapshot
        state={"grads": grads, "burn_state": state0},
    )
