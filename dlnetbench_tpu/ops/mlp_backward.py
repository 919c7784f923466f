"""Fused Pallas kernels for the SwiGLU backward — the r4 attack on the
train step's dominant bucket (docs/PERF.md: the backward matmul fusions
are 41.6% of the step at ~0.80 of MXU peak, while bare same-shape dots
measure 0.99).

What the fusion buys (per layer, bench shape T=12288, D=4096, F=14336):
the autodiff backward materializes two [T, F] intermediates in HBM —
``dh`` (the down-projection gradient) and ``h`` (the recomputed hidden)
— each a write plus one or two reads of ~350 MB.  Here:

* ``dgdu_kernel``: dg, du are produced directly from (dy, Wd, g, u);
  the ``dh = dy @ Wd^T`` tile lives only in VMEM as the dot accumulator
  and the silu-gradient epilogue consumes it in-register.
* ``dwd_kernel``: dWd = h^T @ dy with the ``h = silu(g) * u`` tile
  recomputed elementwise in VMEM per contraction step — h never exists
  in HBM.

dx / dWg / dWu remain plain XLA dots (measured at ~0.99 of peak in
isolation; no fusion value to add).  Both kernels run under
``interpret=True`` off-TPU so the path is unit-testable on the CPU mesh
(tests/test_mlp_backward.py).

The reference has no kernels at all — its backward is a simulated-time
roofline entry (reference python/model_stats.py:140); this file exists
because the rebuild executes the real compute tier.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.ops.pallas_common import (
    F32 as _F32,
    compiler_params as _compiler_params,
    fit_block,
)


def _silu_parts(g_f32):
    sig = jax.nn.sigmoid(g_f32)
    silu = g_f32 * sig
    return silu, sig + silu * (1.0 - sig)   # silu(g), silu'(g)


# --------------------------------------------------------- dg/du kernel

def _dgdu_kernel(dy_ref, wd_ref, g_ref, u_ref, dg_ref, du_ref):
    # dh tile = dy (bm, D) @ Wd^T (D, bn) — accumulator only, in VMEM
    dh = jax.lax.dot_general(dy_ref[...], wd_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=_F32)
    silu, dsilu = _silu_parts(g_ref[...].astype(_F32))
    u = u_ref[...].astype(_F32)
    dg_ref[...] = (dh * u * dsilu).astype(dg_ref.dtype)
    du_ref[...] = (dh * silu).astype(du_ref.dtype)


def dgdu(dy, wd, g, u, *, block_m: int = 1024, block_n: int = 2048):
    """dg, du [T, F] from dy [T, D], Wd [F, D], saved g, u [T, F].

    The full D axis is contracted per grid lane (D tiles of dy and Wd
    fit VMEM at these block sizes), so there is no k loop and the
    silu-gradient epilogue runs in the same lane as the dot.
    """
    t, d = dy.shape
    f = wd.shape[0]
    block_m = fit_block(t, block_m)
    block_n = fit_block(f, block_n)
    grid = (t // block_m, f // block_n)
    return pl.pallas_call(
        _dgdu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, f), g.dtype),
            jax.ShapeDtypeStruct((t, f), u.dtype),
        ],
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=pallas_common.interpret_mode(),
    )(dy, wd, g, u)


# ----------------------------------------------------------- dWd kernel

def _dwd_kernel(g_ref, u_ref, dy_ref, dwd_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    silu, _ = _silu_parts(g_ref[...].astype(_F32))
    h = (silu * u_ref[...].astype(_F32)).astype(g_ref.dtype)  # [bk, bm]
    acc_ref[...] += jax.lax.dot_general(h, dy_ref[...],
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=_F32)

    @pl.when(k == nk - 1)
    def _emit():
        dwd_ref[...] = acc_ref[...].astype(dwd_ref.dtype)


def dwd(g, u, dy, *, block_f: int = 2048, block_d: int = 2048,
        block_k: int = 1024):
    """dWd [F, D] = h^T @ dy with h = silu(g) * u recomputed per tile."""
    t, f = g.shape
    d = dy.shape[1]
    block_f = fit_block(f, block_f)
    block_d = fit_block(d, block_d)
    block_k = fit_block(t, block_k)
    grid = (f // block_f, d // block_d, t // block_k)
    return pl.pallas_call(
        _dwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, block_f), lambda i, j, k: (k, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, block_f), lambda i, j, k: (k, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, block_d), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_f, block_d), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f, d), _F32),
        scratch_shapes=[pltpu.VMEM((block_f, block_d), _F32)],
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=pallas_common.interpret_mode(),
    )(g, u, dy)


# ------------------------------------------------- fused-backward SwiGLU

@jax.custom_vjp
def swiglu_pallas_bwd(x, w_gate, w_up, w_down):
    """SwiGLU whose backward runs the two fused Pallas kernels above
    (dh and h never reach HBM) plus three pure XLA dots (dx, dWg, dWu).
    Forward is the shared three-dot body (models.layers.swiglu_fwd_res),
    residuals saved bf16 (x, g, u)."""
    from dlnetbench_tpu.models.layers import swiglu_fwd_res
    return swiglu_fwd_res(x, w_gate, w_up, w_down)[0]


def _fwd(x, w_gate, w_up, w_down):
    from dlnetbench_tpu.models.layers import swiglu_fwd_res
    return swiglu_fwd_res(x, w_gate, w_up, w_down)


def _bwd(res, dy):
    x, g, u, w_gate, w_up, w_down = res
    t_nk = (((1,), (1,)), ((), ()))   # a @ b^T
    t_km = (((0,), (0,)), ((), ()))   # a^T @ b
    dg, du = dgdu(dy, w_down, g, u)
    dx = (jax.lax.dot_general(dg, w_gate, t_nk,
                              preferred_element_type=_F32)
          + jax.lax.dot_general(du, w_up, t_nk,
                                preferred_element_type=_F32)).astype(x.dtype)
    dwg = jax.lax.dot_general(x, dg, t_km, preferred_element_type=_F32)
    dwu = jax.lax.dot_general(x, du, t_km, preferred_element_type=_F32)
    dwd_ = dwd(g, u, dy)
    return (dx, dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd_.astype(w_down.dtype))


swiglu_pallas_bwd.defvjp(_fwd, _bwd)
