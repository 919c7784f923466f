"""int8 matmul with per-tensor dynamic scales — the quantized MLP
compute path that is ACTUALLY fast on this hardware.

Measured on v5e (r4/r5, docs/PERF.md): chained int8->int32 matmuls run
at 387-390 TOP/s = 0.98-0.99 of the 394 TOP/s int8 peak, and the
END-TO-END int8-MLP train step runs the HEADLINE config (no remat) at
494.3 ms vs 537.5 bf16 — a 1.087x step-level win at loss parity (r5,
bench.py int8_step; needs the fused swiglu_int8 VJP below) — the only
low-precision path with a measured end-to-end win on this chip (fp8
reaches 0.70 of its peak in isolation but has no step-level win
recorded).

Same recipe shape as fp8_dot: bf16 master weights/activations,
per-tensor symmetric scaling to [-127, 127], int32 accumulation on the
MXU, scales re-applied to the result; the backward is straight-through
in the master dtype (quantization treated as identity — the standard
recipe when gradients are not quantized).

The reference's low-precision support is communication-buffer dtype
selection only (`PROXY_FLOAT8`, data_types.hpp:36-79); it has no
quantized compute path at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dlnetbench_tpu.ops import quantized_matmul as qmm

_F32 = jnp.float32
_QMAX = 127.0


def _quantize(x):
    """Per-tensor symmetric scaling to int8: (x_q, scale) with
    x ~= x_q * scale; the scale is clamped so an all-zero tensor stays
    representable.  Delegates to the ONE definition in
    ops/quantized_matmul.py (shared with the fused Pallas kernels,
    which is what makes the fused-vs-composed int8 results EXACTLY
    equal, not just close)."""
    return qmm.quantize_tensor(x, "int8")


@jax.custom_vjp
def int8_dot(x, w):
    """[..., K] x [K, N] -> [..., N]: int8 operands, int32 MXU
    accumulation, result in x.dtype.  Backward is straight-through in
    the master dtype."""
    out, _ = _int8_dot_fwd(x, w)
    return out


def _int8_matmul(a, b_mat, out_dtype):
    """Quantized a @ b_mat with per-tensor scales and int32 MXU
    accumulation — the ONE definition of the int8 dot recipe (forward
    and SwitchBack activation-grad dots share it)."""
    aq, sa = _quantize(a)
    bq, sb = _quantize(b_mat)
    acc = jax.lax.dot_general(aq, bq,
                              (((a.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(_F32) * (sa * sb)).astype(out_dtype)


def _int8_dot_fwd(x, w):
    return _int8_matmul(x, w, x.dtype), (x, w)


# master-dtype straight-through backward, shared with the fp8 path
from dlnetbench_tpu.ops.fp8 import straight_through_dot_bwd  # noqa: E402

int8_dot.defvjp(_int8_dot_fwd, straight_through_dot_bwd)


@jax.custom_vjp
def int8_dot_batched(x, w):
    """[E, C, K] x [E, K, N] -> [E, C, N]: per-tensor-scaled int8
    operands, int32 MXU accumulation batched over the leading (expert)
    axis — the MoE/EP sibling of ``int8_dot`` (models/spmd.py expert
    einsums).  Backward is straight-through in the master dtype."""
    out, _ = _int8_dot_batched_fwd(x, w)
    return out


def _int8_dot_batched_fwd(x, w):
    xq, sx = _quantize(x)
    wq, sw = _quantize(w)
    acc = jax.lax.dot_general(xq, wq,
                              (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.int32)
    out = acc.astype(_F32) * (sx * sw)
    return out.astype(x.dtype), (x, w)


def _int8_dot_batched_bwd(res, dy):
    x, w = res
    d_x = jax.lax.dot_general(
        dy, w, (((2,), (2,)), ((0,), (0,)))).astype(x.dtype)
    d_w = jax.lax.dot_general(
        x, dy, (((1,), (1,)), ((0,), (0,)))).astype(w.dtype)
    return d_x, d_w


int8_dot_batched.defvjp(_int8_dot_batched_fwd, _int8_dot_batched_bwd)


@jax.custom_vjp
def swiglu_int8(x, w_gate, w_up, w_down):
    """SwiGLU with all three matmuls in int8 (the int8 sibling of
    layers.swiglu / ops.fp8.swiglu_fp8 — same bf16-rounding discipline
    for saved residuals).

    Whole-op custom VJP rather than three composed ``int8_dot``s: the
    composition's down-projection dot saves its input ``h`` ([B, S, ff]
    — ~345 MB/layer at bench shape) as a residual, which the bf16
    path's XLA-fused backward never materializes.  Here the backward
    recomputes ``h`` elementwise from the (anyway-saved) g/u
    pre-activations, so the residual footprint matches the bf16 path
    and the int8 step fits where the composition OOM'd (r5,
    docs/PERF.md).  Backward stays straight-through in the master
    dtype, identical in semantics to the composed form."""
    out, _ = _swiglu_int8_fwd(x, w_gate, w_up, w_down)
    return out


def _swiglu_int8_fwd(x, w_gate, w_up, w_down):
    g = int8_dot(x, w_gate)
    u = int8_dot(x, w_up)
    h = (jax.nn.silu(g.astype(_F32)) * u.astype(_F32)).astype(g.dtype)
    out = int8_dot(h, w_down)
    return out, (x, g, u, w_gate, w_up, w_down)


# shared SwiGLU backward — one definition for the composed, fused and
# SwitchBack recipes, living beside the fused kernels (ops/
# quantized_matmul.py) so the fp8 fused path uses it without an import
# cycle; ``act_dot`` selects plain-matmul vs quantized activation-grad
# dots, everything else (h recompute, silu derivative, master-dtype dW
# matmuls) exists once there
_swiglu_bwd_impl = qmm.swiglu_bwd_impl


# the master-dtype backward shared with the fp8 swiglus (one
# definition, ops/quantized_matmul.py)
_swiglu_int8_bwd = qmm.swiglu_master_bwd


swiglu_int8.defvjp(_swiglu_int8_fwd, _swiglu_int8_bwd)


@jax.custom_vjp
def swiglu_int8_fused(x, w_gate, w_up, w_down):
    """SwiGLU with all three matmuls through the fused-quantization
    Pallas kernel (ops/quantized_matmul.py): activation quantization in
    the kernel prologue, int32 MXU accumulation, ``sa*sb`` epilogue
    in-register — the composed recipe's separate amax/rescale HBM
    passes are gone and the quantized activation never exists in HBM.
    Numerically EXACTLY equal to ``swiglu_int8`` (shared scale
    definition, associative int32 accumulation); same residual
    contract (``h`` recomputed, not saved) and the same master-dtype
    straight-through backward."""
    out, _ = qmm.swiglu_fused_fwd_res(x, w_gate, w_up, w_down, "int8")
    return out


def _swiglu_int8_fused_fwd(x, w_gate, w_up, w_down):
    return qmm.swiglu_fused_fwd_res(x, w_gate, w_up, w_down, "int8")


swiglu_int8_fused.defvjp(_swiglu_int8_fused_fwd, _swiglu_int8_bwd)


@jax.custom_vjp
def swiglu_int8_sb(x, w_gate, w_up, w_down):
    """SwiGLU, int8 forward AND int8 activation-gradient (dx-side)
    backward — the SwitchBack recipe (arXiv:2304.13013 pattern: the
    three dL/dactivation matmuls are quantized per-tensor; the three
    dL/dW matmuls stay in the master dtype, where gradient accuracy
    lives).  Relative to ``swiglu_int8`` this moves the backward's
    dh = dy@Wd^T and dx = dg@Wg^T + du@Wu^T onto the 2x int8 MXU rate.

    Numerics are a RECIPE CHANGE (quantization error enters upstream
    gradients), so this is opt-in via
    ``TransformerConfig.int8_backward="switchback"``; the r5 loss-
    trajectory study (docs/studies/int8_step_r5) measures the drift
    against the master-dtype backward before trusting the speed."""
    out, _ = _swiglu_int8_fwd(x, w_gate, w_up, w_down)
    return out


def _sb_dot(a, b_mat):
    return _int8_matmul(a, b_mat, a.dtype)


def _swiglu_int8_sb_bwd(res, dy):
    return _swiglu_bwd_impl(res, dy, _sb_dot)


swiglu_int8_sb.defvjp(_swiglu_int8_fwd, _swiglu_int8_sb_bwd)
