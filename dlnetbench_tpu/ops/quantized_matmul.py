"""Fused-quantization Pallas matmuls: amax/scale application inlined
into the int8 and fp8(e4m3) MXU path.

Why this file exists (r5 evidence, docs/PERF.md): the BARE int8 matmul
runs at 0.98-0.99 of the 394 TOP/s int8 peak and e4m3 executes natively
at 274 TF/s, yet the end-to-end quantized paths lose their margin to
quantization overhead — the composed recipe (ops/int8.py, ops/fp8.py)
runs per-tensor amax reduction, rescale/cast, and the post-matmul
``sa*sb`` application as SEPARATE XLA passes, each a full HBM round
trip of the [T, K] activation (the quantized copy is materialized in
HBM and read back by the matmul).  That is exactly the
dequant/rescale-fusion gap SwitchBack (Wortsman et al. 2023,
arXiv:2304.13013) and the FP8-formats recipe (Micikevicius et al. 2022,
arXiv:2209.05433) identify between paper-rate and achieved-rate
low-precision training.

The kernel here fuses all three stages into the matmul itself:

* **Prologue**: the activation tile is loaded in the master dtype
  (bf16), quantized in VMEM against a PROVIDED per-tensor scale —
  the quantized activation never exists in HBM, and the activation is
  read exactly once.
* **Body**: int8 x int8 -> int32 (or e4m3 x e4m3 -> f32) MXU dots,
  accumulated in a VMEM scratch across the contraction grid axis.
* **Epilogue**: ``sa * sb`` applied in-register to the final
  accumulator tile, output written once in the master dtype.

Weights are pre-quantized ONCE per step by the caller
(``quantize_tensor`` — a [K, N] pass, small next to the [T, K]
activation traffic the fusion removes).

The scale is dynamic (``*_dot_fused``): it comes from a fresh amax of
the CURRENT activation.  One XLA reduction pass over x remains, but the
separate quantize-write + quantized-read passes of the composed path
are gone.

All kernels run under ``interpret=True`` off-TPU (pallas_common), so
the CPU-mesh tier-1 lane unit-tests them (tests/test_quantized_matmul).
The reference has no quantized compute at all — its low-precision
support is comm-buffer dtype selection (data_types.hpp:36-79).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.ops.pallas_common import F32, compiler_params, fit_block

# format table: (quantized dtype, symmetric max, MXU accumulator dtype)
_FORMATS = {
    "int8": (jnp.int8, 127.0, jnp.int32),
    "float8": (jnp.float8_e4m3fn, 448.0, F32),
}


def formats() -> tuple[str, ...]:
    return tuple(_FORMATS)


def scale_from_amax(amax, fmt: str):
    """The ONE definition of the per-tensor symmetric scale:
    ``max(amax, eps) / qmax`` — shared by the composed paths
    (ops/int8.py, ops/fp8.py ``_quantize``) and the fused kernels, so
    the int8 fused-vs-composed comparison is exact, not just close."""
    _, qmax, _ = _FORMATS[fmt]
    return jnp.maximum(amax, 1e-12) / qmax


def _cast_q(scaled, fmt: str):
    """Scaled master-dtype values -> quantized dtype, saturating at the
    format's range.  For a fresh scale the clamp is the identity, which
    is what keeps the fused int8 result EXACTLY equal to the composed
    one."""
    qdtype, qmax, _ = _FORMATS[fmt]
    if fmt == "int8":
        return jnp.clip(jnp.round(scaled), -qmax, qmax).astype(qdtype)
    return jnp.clip(scaled, -qmax, qmax).astype(qdtype)


def quantize_tensor(x, fmt: str):
    """Per-tensor symmetric quantization via XLA: ``(x_q, scale)`` with
    ``x ~= x_q * scale``.  This is the ONCE-PER-STEP weight path (and
    the composed recipe's activation path — ops/int8.py and ops/fp8.py
    delegate here)."""
    xf = x.astype(F32)
    scale = scale_from_amax(jnp.max(jnp.abs(xf)), fmt)
    return _cast_q(xf / scale, fmt), scale


# ------------------------------------------------------------- kernel

def _fused_matmul_kernel(x_ref, wq_ref, sx_ref, sw_ref, out_ref, acc_ref,
                         *, fmt: str):
    """Grid (i, j, k) = (row blocks, col blocks, contraction blocks);
    k is the minor accumulation axis."""
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, _, acc_dtype = _FORMATS[fmt]
    xf = x_ref[...].astype(F32)
    sx = sx_ref[0, 0]
    # prologue: quantize the activation tile in VMEM — x_q never
    # exists in HBM, x is read once in the master dtype
    xq = _cast_q(xf / sx, fmt)
    acc_ref[...] += jax.lax.dot_general(
        xq, wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype)

    @pl.when(k == nk - 1)
    def _emit():
        # epilogue: sa*sb applied in-register to the accumulator tile
        out_ref[...] = (acc_ref[...].astype(F32)
                        * (sx * sw_ref[0, 0])).astype(out_ref.dtype)


# frozen default grid blocks (the pre-tuning constants): what every
# call without explicit blocks and without a tuning-DB hit runs on —
# locked bit-identical by tests/test_tuning.py
DEFAULT_BLOCKS = {"block_m": 1024, "block_n": 2048, "block_k": 2048}


def _tuned_blocks(t: int, kdim: int, n: int, fmt: str, xdtype) -> dict:
    """Grid blocks for an (t, kdim) @ (kdim, n) fused matmul: the
    tuning DB's answer (dlnetbench_tpu/tuning — frozen after first
    consult per shape key) or ``DEFAULT_BLOCKS``.  Tuned values are
    validated positive (``fit_block`` then shrinks them to divisors
    exactly as it does the defaults, so any positive tuned block is
    runnable — the committed value records the search's intent, the
    fit the shape's constraint)."""
    from dlnetbench_tpu import tuning

    def check(cfg: dict) -> None:
        for name in DEFAULT_BLOCKS:
            blk = cfg.get(name)
            if not isinstance(blk, int) or blk <= 0:
                raise ValueError(f"fused_matmul: tuned {name}={blk!r} "
                                 f"is not a positive int")
    return tuning.consult(
        "quantized_matmul",
        tuning.params.quantized_matmul_key(t, kdim, n, fmt, xdtype),
        DEFAULT_BLOCKS, validate=check)


def fused_matmul(x, wq, sw, sx, *, fmt: str, out_dtype=None,
                 block_m: int | None = None,
                 block_n: int | None = None, block_k: int | None = None):
    """[..., K] master-dtype x  @  [K, N] pre-quantized w  ->  [..., N].

    ``sx`` is the PROVIDED activation scale, ``sw`` the weight scale
    from ``quantize_tensor``.

    Grid blocks: explicit arguments win; with none given the tuning DB
    is consulted per (shape, dtype, chip) key and an empty DB keeps the
    frozen ``DEFAULT_BLOCKS`` bit-identically (ISSUE 9).
    """
    if fmt not in _FORMATS:
        raise ValueError(f"unknown quantization format {fmt!r}; "
                         f"expected one of {formats()}")
    _, _, acc_dtype = _FORMATS[fmt]
    lead = x.shape[:-1]
    kdim = x.shape[-1]
    n = wq.shape[1]
    if wq.shape[0] != kdim:
        raise ValueError(f"fused_matmul: contraction mismatch "
                         f"x[..., {kdim}] @ wq[{wq.shape[0]}, {n}]")
    t = math.prod(lead) if lead else 1
    x2 = x.reshape(t, kdim)
    if block_m is None and block_n is None and block_k is None:
        blocks = _tuned_blocks(t, kdim, n, fmt, x.dtype)
    else:  # explicit caller blocks: fill gaps from the frozen defaults
        blocks = {"block_m": block_m or DEFAULT_BLOCKS["block_m"],
                  "block_n": block_n or DEFAULT_BLOCKS["block_n"],
                  "block_k": block_k or DEFAULT_BLOCKS["block_k"]}
    bm = fit_block(t, blocks["block_m"])
    bn = fit_block(n, blocks["block_n"])
    bk = fit_block(kdim, blocks["block_k"])
    grid = (t // bm, n // bn, kdim // bk)

    out_dtype = out_dtype or x.dtype
    (y,) = pl.pallas_call(
        functools.partial(_fused_matmul_kernel, fmt=fmt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((t, n), out_dtype)],
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.interpret_mode(),
    )(x2, wq,
      jnp.asarray(sx, F32).reshape(1, 1),
      jnp.asarray(sw, F32).reshape(1, 1))
    return y.reshape(*lead, n)


# -------------------------------------------------- forward-only dots

def fused_dot(x, w, fmt: str):
    """Fresh-scaling fused dot (forward only — custom-VJP wrappers below
    and the swiglu-level VJPs in ops/int8.py / ops/fp8.py define the
    backward): one XLA amax reduction over x, weight quantized via
    ``quantize_tensor``, everything else in-kernel."""
    sx = scale_from_amax(jnp.max(jnp.abs(x.astype(F32))), fmt)
    wq, sw = quantize_tensor(w, fmt)
    return fused_matmul(x, wq, sw, sx, fmt=fmt)


# ------------------------------------------- differentiable wrappers

def straight_through_dot_bwd(res, g):
    """Master-dtype backward shared by every quantized dot (the fused
    ones here, the composed ones in ops/fp8.py and ops/int8.py — both
    import this definition): quantization treated as identity, so the
    gradient matmuls are the plain bf16/f32 ones."""
    x, w = res
    gf = g.astype(F32)
    dx = jnp.dot(gf, w.astype(F32).T).astype(x.dtype)
    # contract all leading (batch) axes of x against g: dw [K, N]
    lead = tuple(range(x.ndim - 1))
    dw = jax.lax.dot_general(
        x.astype(F32), gf, ((lead, lead), ((), ()))).astype(w.dtype)
    return dx, dw


# ----------------------------------------------- shared SwiGLU bodies

def swiglu_fused_fwd_res(x, w_gate, w_up, w_down, fmt: str):
    """Fresh-scaling fused-SwiGLU forward, returning (y, residuals).
    The residuals are (x, g, u, weights) — the hidden ``h`` is NOT
    saved (the r5 no-remat OOM contract, same as ops.int8.swiglu_int8):
    the backward recomputes it elementwise from g/u."""
    g = fused_dot(x, w_gate, fmt)
    u = fused_dot(x, w_up, fmt)
    h = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(g.dtype)
    out = fused_dot(h, w_down, fmt)
    return out, (x, g, u, w_gate, w_up, w_down)


def swiglu_bwd_impl(res, dy, act_dot):
    """Shared SwiGLU backward (moved here from ops/int8.py so the fp8
    fused path can use it without an import cycle): ``act_dot(a, b)``
    (master-dtype result) runs the three ACTIVATION-GRADIENT matmuls
    (dh, and the two dx legs) — a plain matmul for the
    straight-through recipe, the quantized int8 dot for SwitchBack.
    Everything else (h recompute instead of save, silu derivative, the
    three master-dtype dW matmuls) exists ONCE here."""
    x, g, u, w_gate, w_up, w_down = res
    gf, uf = g.astype(F32), u.astype(F32)
    silu_g = jax.nn.silu(gf)
    h = (silu_g * uf).astype(g.dtype)          # recomputed, not saved

    # down projection: activation grad via act_dot, dW in master dtype
    dh = act_dot(dy, w_down.T).astype(F32)
    d_wd = jnp.matmul(h.reshape(-1, h.shape[-1]).T,
                      dy.reshape(-1, dy.shape[-1])).astype(w_down.dtype)

    # silu(g) * u elementwise backward
    sg = jax.nn.sigmoid(gf)
    d_g = (dh * uf * (sg * (1.0 + gf * (1.0 - sg)))).astype(g.dtype)
    d_u = (dh * silu_g).astype(u.dtype)

    # gate/up projections
    d_wg = jnp.matmul(x.reshape(-1, x.shape[-1]).T,
                      d_g.reshape(-1, d_g.shape[-1])).astype(w_gate.dtype)
    d_wu = jnp.matmul(x.reshape(-1, x.shape[-1]).T,
                      d_u.reshape(-1, d_u.shape[-1])).astype(w_up.dtype)
    d_x = (act_dot(d_g, w_gate.T) + act_dot(d_u, w_up.T)).astype(x.dtype)
    return d_x, d_wg, d_wu, d_wd


def swiglu_master_bwd(res, dy):
    """The master-dtype (straight-through) SwiGLU backward — the ONE
    definition both the int8 and fp8 fused swiglus defvjp with, so the
    recipes the A/B bench assumes symmetric cannot silently diverge."""
    return swiglu_bwd_impl(res, dy, jnp.matmul)


@jax.custom_vjp
def int8_dot_fused(x, w):
    """[..., K] x [K, N] -> [..., N]: the fused-kernel sibling of
    ops.int8.int8_dot — same recipe, same straight-through backward,
    quantization fused into the matmul.  int32 accumulation makes the
    result EXACTLY equal to the composed form (same scales, associative
    int32 sums, same f32 epilogue)."""
    return fused_dot(x, w, "int8")


def _int8_dot_fused_fwd(x, w):
    return fused_dot(x, w, "int8"), (x, w)


int8_dot_fused.defvjp(_int8_dot_fused_fwd, straight_through_dot_bwd)


@jax.custom_vjp
def fp8_dot_fused(x, w):
    """The fused-kernel sibling of ops.fp8.fp8_dot (e4m3, f32
    accumulation); matches the composed form to e4m3 quantization
    tolerance (tiled f32 accumulation order differs)."""
    return fused_dot(x, w, "float8")


def _fp8_dot_fused_fwd(x, w):
    return fused_dot(x, w, "float8"), (x, w)


fp8_dot_fused.defvjp(_fp8_dot_fused_fwd, straight_through_dot_bwd)
