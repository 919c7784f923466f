"""Ulysses (DeepSpeed-style) sequence-parallel proxy — rebuild extension.

No reference counterpart (SURVEY.md §5.7).  Schedule: activations are
sequence-sharded; each attention layer does an all-to-all that reshards
sequence -> heads (every rank then holds the FULL sequence for a subset of
heads), computes attention, and a second all-to-all reshards back.  Two
A2As per layer forward, two backward; MLP compute between layers; optional
DP gradient sync.  A2A message = B x (N/sp) x d elements
(``core.schedule.sequence_schedule``).
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
from dlnetbench_tpu.core.schedule import sequence_schedule
from dlnetbench_tpu.parallel import collectives as col
from dlnetbench_tpu.parallel.buffers import scaled_elems, sharded_zeros
from dlnetbench_tpu.parallel.mesh import AXIS_DP, AXIS_SP, describe_mesh, make_sp_mesh
from dlnetbench_tpu.proxies import burn as burnlib
from dlnetbench_tpu.proxies.base import ProxyConfig, StepBundle
from dlnetbench_tpu.proxies.pipeline_common import _infer_dp


def build(stats: ModelStats, card: ModelCard, cfg: ProxyConfig, *,
          sp: int, dp: int = 0, devices=None, dtype=jnp.float32,
          max_layers: int | None = None) -> StepBundle:
    devices = devices if devices is not None else jax.devices()
    world = len(devices)
    dp = _infer_dp(world, sp, 1, dp, label="sp")
    if card.num_heads % sp != 0:
        raise ValueError(f"num_heads {card.num_heads} not divisible by "
                         f"sp={sp} (Ulysses shards the head axis)")
    sched = sequence_schedule(stats, card, sp)
    mesh = make_sp_mesh(sp, dp, devices)
    cal = burnlib.calibrate()

    # attention compute per layer: full seq x heads/sp = all sp blocks' worth
    attn_iters = cal.iters_for_us(sched.attn_us_per_block * sp * cfg.time_scale)
    mlp_us_per_layer = (stats.ffn_fwd_us / max(sched.layers, 1)) / sp
    mlp_iters = cal.iters_for_us(mlp_us_per_layer * cfg.time_scale)
    layers = min(sched.layers, max_layers) if max_layers else sched.layers

    a2a_elems = scaled_elems(sched.a2a_elems, cfg.size_scale)
    a2a_elems += (-a2a_elems) % sp  # divisible for the A2A split
    grad_elems = scaled_elems(stats.model_size // max(sp, 1), cfg.size_scale)

    acts = sharded_zeros(mesh, P(), (max(a2a_elems, sp),), dtype)
    grads = sharded_zeros(mesh, P(), (grad_elems,), dtype)
    state0 = sharded_zeros(mesh, P(), burnlib.DEFAULT_SHAPE,
                           burnlib.DEFAULT_DTYPE) + burnlib.make_state()

    def layer_pass(state, a, attn_i, mlp_i, with_compute, with_comm):
        if with_comm:  # seq -> heads reshard
            a = col.alltoall(col.tie(a, state).reshape(sp, -1),
                             AXIS_SP).reshape(-1)
            state = col.tie(state, a)
        if with_compute:
            state = burnlib.burn(state, attn_i)
        if with_comm:  # heads -> seq reshard
            a = col.alltoall(col.tie(a, state).reshape(sp, -1),
                             AXIS_SP).reshape(-1)
            state = col.tie(state, a)
        if with_compute:
            state = burnlib.burn(state, mlp_i)
        return state, a

    def step(state, a, grad_b, *, with_compute: bool, with_comm: bool):
        for _ in range(layers):  # forward
            state, a = layer_pass(state, a, attn_iters, mlp_iters,
                                  with_compute, with_comm)
        for _ in range(layers):  # backward (~2x compute, 2 more A2As)
            state, a = layer_pass(state, a, 2 * attn_iters, 2 * mlp_iters,
                                  with_compute, with_comm)
        outs = []
        if with_comm and dp > 1:
            outs.append(col.allreduce(col.tie(grad_b, state), AXIS_DP))
        return (state, a, *col.fence(*outs)) if outs else (state, a)

    def make(with_compute, with_comm):
        fn = shard_map(
            functools.partial(step, with_compute=with_compute,
                              with_comm=with_comm),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False)
        # donate activations/grad shard (grad only donated when dp > 1
        # emits its allreduce output to rebind from); the burn state
        # stays undonated (proxies/dp.py says why)
        return executor.Program(fn=fn, args=(state0, acts, grads),
                                donate_argnums=(1, 2))

    a2a_total = layers * 4  # 2 per layer fwd + 2 per layer bwd; shared
                            # by a2a_body and the comm_model declaration

    def a2a_body(a):
        for _ in range(a2a_total):
            a = col.alltoall(a.reshape(sp, -1), AXIS_SP).reshape(-1)
        return a

    a2a_prog = executor.Program(
        fn=shard_map(a2a_body, mesh=mesh, in_specs=(P(),),
                     out_specs=P(), check_vma=False),
        args=(acts,))

    meta = {
        "proxy": "ulysses",
        "model": stats.name,
        "world_size": world,
        "dp": dp, "sp": sp,
        "layers": layers,
        "seq_per_rank": sched.seq_per_rank,
        "a2a_bytes": int(a2a_elems * jnp.dtype(dtype).itemsize),
        "schedule_a2a_bytes": int(sched.a2a_elems * stats.bytes_per_element),
        "a2a_per_layer": 4,
        # which estimator produced the attention burn budget (see
        # core/schedule.py sequence_schedule)
        "attn_time_source": sched.attn_time_source,
        "burn_ns_per_iter": cal.ns_per_iter,
        "comm_model": {"a2a_comm_time": [
            {"kind": "alltoall", "group": sp,
             "bytes": int(a2a_total * a2a_elems
                          * jnp.dtype(dtype).itemsize)}]},
        "mesh": describe_mesh(mesh),
        "size_scale": cfg.size_scale,
        "time_scale": cfg.time_scale,
    }
    compiled = executor.compile_programs(
        {"full": make(True, True),
         "compute": make(True, False),
         "comm": make(False, True),
         "a2a_comm": a2a_prog}, meta)
    return StepBundle(
        full=compiled["full"],
        compute=compiled["compute"],
        comm=compiled["comm"],
        variants={"a2a_comm": compiled["a2a_comm"]},
        global_meta=meta,
    )
