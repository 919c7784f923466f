"""float8 (e4m3) matmul with per-tensor dynamic scales — the fp8 MLP
compute path.

The stat files model a ``float8`` dtype and v5e-class chips run fp8 at
2x the bf16 MXU rate (core/hardware.py peak tables; the reference's
compile-time ``PROXY_FLOAT8`` buffer selection, data_types.hpp:36-79,
covers only communication buffers — it has no fp8 COMPUTE path at all).
This module supplies the compute path TPU-style:

  * bf16 master weights and activations; each operand is scaled by
    max-abs / 448 (the e4m3 finite max) per tensor, cast to
    ``float8_e4m3fn``, multiplied with f32 accumulation on the MXU, and
    the product of the two scales is applied to the result.
  * the backward pass is straight-through: quantization is treated as
    identity and the gradient matmuls run in the master dtype (the
    standard transformer-engine-style recipe for fp8 forward without
    fp8 gradient plumbing).

``fp8_dot`` is jit/vmap-compatible (shapes static, scales dynamic) and
runs everywhere jax does (unit-testable on CPU).  Measured on v5e (r5,
docs/PERF.md): e4m3 dots execute NATIVELY on the MXU at up to 0.70 of
the fp8 peak — 274 TF/s, above the bf16 peak, killing the r3/r4
"upcast" theory, which turned out to be an HBM-residency measurement
artifact; the remaining gap to peak is quantization overhead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dlnetbench_tpu.ops import quantized_matmul as qmm

_F32 = jnp.float32
_E4M3_MAX = 448.0      # float8_e4m3fn finite max


def _quantize(x):
    """Per-tensor dynamic scaling to e4m3: returns (x_q, scale) with
    x ~= x_q * scale.  Delegates to the ONE definition in
    ops/quantized_matmul.py (shared with the fused Pallas kernels, so
    the fused-vs-composed A/B compares recipes, not scale formulas)."""
    return qmm.quantize_tensor(x, "float8")


@jax.custom_vjp
def fp8_dot(x, w):
    """[..., K] x [K, N] -> [..., N]: e4m3 operands, f32 accumulation,
    result in x.dtype.  Backward is straight-through in the master
    dtype."""
    out, _ = _fp8_dot_fwd(x, w)
    return out


def _fp8_dot_fwd(x, w):
    xq, sx = _quantize(x)
    wq, sw = _quantize(w)
    out = jnp.dot(xq, wq, preferred_element_type=_F32) * (sx * sw)
    return out.astype(x.dtype), (x, w)


# master-dtype backward shared by every quantized dot (fp8, int8 —
# ops/int8.py imports this name); the definition lives beside the
# fused kernels in ops/quantized_matmul.py
straight_through_dot_bwd = qmm.straight_through_dot_bwd

_fp8_dot_bwd = straight_through_dot_bwd


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def swiglu_fp8(x, w_gate, w_up, w_down):
    """SwiGLU with all three matmuls in e4m3 (layers.swiglu's fp8
    sibling — same bf16-rounding discipline for saved residuals)."""
    g = fp8_dot(x, w_gate)      # already x.dtype (fp8_dot's contract)
    u = fp8_dot(x, w_up)
    h = (jax.nn.silu(g.astype(_F32)) * u.astype(_F32)).astype(g.dtype)
    return fp8_dot(h, w_down)


@jax.custom_vjp
def swiglu_fp8_fused(x, w_gate, w_up, w_down):
    """SwiGLU with all three matmuls through the fused-quantization
    Pallas kernel (ops/quantized_matmul.py): per-tensor e4m3 scales
    applied in the kernel prologue/epilogue instead of as separate XLA
    passes — the attack on the fp8 chain's 0.56-of-peak quantization
    overhead (docs/PERF.md r5/r6).  Whole-op custom VJP so the backward
    recomputes ``h`` instead of saving it (the same residual contract
    as swiglu_int8); backward is straight-through in the master
    dtype."""
    out, _ = qmm.swiglu_fused_fwd_res(x, w_gate, w_up, w_down, "float8")
    return out


def _swiglu_fp8_fused_fwd(x, w_gate, w_up, w_down):
    return qmm.swiglu_fused_fwd_res(x, w_gate, w_up, w_down, "float8")


swiglu_fp8_fused.defvjp(_swiglu_fp8_fused_fwd, qmm.swiglu_master_bwd)

