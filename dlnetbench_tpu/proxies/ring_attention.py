"""Ring-attention (context-parallel) proxy — rebuild extension.

No reference counterpart exists (SURVEY.md §2.5/§5.7: the reference has no
sequence parallelism); this is the sixth proxy family the TPU rebuild adds.
Schedule: the sequence axis is sharded over ``sp`` devices; each attention
layer rotates K/V blocks around the ring with ``ppermute`` while computing
block-local attention, so each rank sees all ``sp`` KV blocks in ``sp-1``
hops — communication hidden behind per-block attention compute (the natural
ICI-torus idiom).  Backward mirrors the ring with ~2x compute; MLP compute
(no sequence-axis comm) burns between layers; when ``dp > 1`` a gradient
allreduce over the dp axis closes the step, like the other proxies.

Message math comes from ``core.schedule.sequence_schedule``:
KV block = 2 x B x (N/sp) x kv_dim elements per hop per layer.
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
    sched = sequence_schedule(stats, card, sp)
    mesh = make_sp_mesh(sp, dp, devices)
    cal = burnlib.calibrate()

    # one burn per (layer, kv block); MLP burn per layer
    attn_iters = cal.iters_for_us(sched.attn_us_per_block * cfg.time_scale)
    mlp_us_per_layer = (stats.ffn_fwd_us / max(sched.layers, 1)) / sp
    mlp_iters = cal.iters_for_us(mlp_us_per_layer * cfg.time_scale)
    layers = min(sched.layers, max_layers) if max_layers else sched.layers

    kv_elems = scaled_elems(sched.kv_block_elems, cfg.size_scale)
    grad_elems = scaled_elems(stats.model_size // max(sp, 1), cfg.size_scale)

    kv = sharded_zeros(mesh, P(), (kv_elems,), dtype)
    grads = sharded_zeros(mesh, P(), (grad_elems,), dtype)
    state0 = sharded_zeros(mesh, P(), burnlib.DEFAULT_SHAPE,
                           burnlib.DEFAULT_DTYPE) + burnlib.make_state()

    def ring_pass(state, kv_b, iters_per_block, with_compute, with_comm):
        for hop in range(sp):
            if with_compute:
                state = burnlib.burn(state, iters_per_block)
            if with_comm and hop < sp - 1:
                kv_b = col.ring_shift(col.tie(kv_b, state), AXIS_SP)
                state = col.tie(state, kv_b)
        return state, kv_b

    def step(state, kv_b, grad_b, *, with_compute: bool, with_comm: bool):
        for _ in range(layers):  # forward
            state, kv_b = ring_pass(state, kv_b, attn_iters,
                                    with_compute, with_comm)
            if with_compute:
                state = burnlib.burn(state, mlp_iters)
        for _ in range(layers):  # backward (~2x attention compute)
            state, kv_b = ring_pass(state, kv_b, 2 * attn_iters,
                                    with_compute, with_comm)
            if with_compute:
                state = burnlib.burn(state, 2 * mlp_iters)
        outs = []
        if with_comm and dp > 1:
            outs.append(col.allreduce(col.tie(grad_b, state), AXIS_DP))
        return (state, kv_b, *col.fence(*outs)) if outs else (state, kv_b)

    def make(with_compute, with_comm):
        fn = shard_map(
            functools.partial(step, with_compute=with_compute,
                              with_comm=with_comm),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
            check_vma=False)
        # donate KV block/grad shard (grad is only rebindable — hence
        # only donated — when dp > 1 produces its allreduce output); the
        # burn state stays undonated (proxies/dp.py says why)
        return executor.Program(fn=fn, args=(state0, kv, grads),
                                donate_argnums=(1, 2))

    # one ring pass per layer fwd + one bwd (bwd doubles compute, not
    # hops); shared by ring_body and the comm_model declaration
    ring_shifts = layers * 2 * (sp - 1)

    def ring_body(kv_b):
        for _ in range(ring_shifts):
            kv_b = col.ring_shift(kv_b, AXIS_SP)
        return kv_b

    ring_prog = executor.Program(
        fn=shard_map(ring_body, mesh=mesh, in_specs=(P(),),
                     out_specs=P(), check_vma=False),
        args=(kv,))

    meta = {
        "proxy": "ring_attention",
        "model": stats.name,
        "world_size": world,
        "dp": dp, "sp": sp,
        "layers": layers,
        "seq_per_rank": sched.seq_per_rank,
        "kv_block_bytes": int(kv_elems * jnp.dtype(dtype).itemsize),
        "schedule_kv_block_bytes": int(sched.kv_block_elems
                                       * stats.bytes_per_element),
        "ring_hops_per_layer": sp - 1,
        "attn_us_per_block": sched.attn_us_per_block * cfg.time_scale,
        # which estimator produced attn_us_per_block: "ffn_stats" (stat
        # file carried FFN timings) or "even_split_fallback" (0.5 guess)
        "attn_time_source": sched.attn_time_source,
        "burn_ns_per_iter": cal.ns_per_iter,
        "comm_model": {"ring_comm_time": [
            {"kind": "p2p", "group": sp,
             "bytes": int(ring_shifts * kv_elems
                          * jnp.dtype(dtype).itemsize)}]},
        "mesh": describe_mesh(mesh),
        "size_scale": cfg.size_scale,
        "time_scale": cfg.time_scale,
    }
    compiled = executor.compile_programs(
        {"full": make(True, True),
         "compute": make(True, False),
         "comm": make(False, True),
         "ring_comm": ring_prog}, meta)
    return StepBundle(
        full=compiled["full"],
        compute=compiled["compute"],
        comm=compiled["comm"],
        variants={"ring_comm": compiled["ring_comm"]},
        global_meta=meta,
    )
