"""Grouped (per-expert) Pallas matmuls: the MoE expert-FFN kernel.

The expert FFN of a dispatched MoE layer is E independent matmuls over
per-expert token buffers — ``[E, C, K] @ [E, K, N] -> [E, C, N]`` —
where ``C`` is the capacity (dispatch slots) per expert.  XLA runs it
as one batched einsum that pays the FULL ``E*C`` token grid even when
routing left most slots empty.  This kernel family makes the dispatch
layout a first-class grid:

* **gather/scatter skipping** — the per-expert VALID-token counts ride
  as a scalar-prefetch operand (the splash-kernel pattern, ISSUE 10):
  a token block lying wholly beyond its expert's count issues no MXU
  work and no fresh DMA (its index map clamps to an already-resident
  block) and writes zeros — under skewed routing the kernel does the
  work the tokens need, not the work the padding implies.
* **fused quantization** (the PR-3 recipe, ops/quantized_matmul.py):
  with ``fmt`` int8/float8 the activation tile is quantized in the
  VMEM PROLOGUE against a provided PER-EXPERT scale, int32/f32 MXU
  accumulation, ``sx[e] * sw[e]`` applied in-register in the epilogue
  — the quantized activation never exists in HBM.  Scale spelling is
  shared with the composed paths (``scale_from_amax`` / ``_cast_q``),
  so the int8 grouped result is EXACTLY the composed reference.
* **tuning-DB site** (ISSUE 9): the grid blocks consult the DB under
  op ``grouped_ffn`` keyed per (E, C, K, N, fmt, dtype); an empty DB
  keeps the frozen ``DEFAULT_BLOCKS`` bit-identically, explicit block
  arguments always win.

``grouped_ffn`` stacks three grouped matmuls into the SwiGLU expert
FFN with a straight-through (master-dtype) custom VJP — the same
backward recipe every quantized path in this repo uses.  The forward
hands its two projections ``g = x @ w_gate`` and ``u = x @ w_up`` to
the backward as residuals, in the dtype its kernels wrote them (the
``layers.swiglu`` discipline), so the backward is the gradient
of the function that was evaluated and computes neither again: six
full-grid einsums, not eight.  Rows past an expert's count need no
mask there: a skipped block's ``g`` and ``u`` are the kernel's zeros,
which make ``h``, ``dg`` and ``du`` zero whatever ``dy`` holds.  All
kernels run under ``interpret=True`` off-TPU (pallas_common), so the
CPU-mesh tier-1 lane unit-tests them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.ops.pallas_common import F32, compiler_params, fit_block
from dlnetbench_tpu.ops.quantized_matmul import (
    _FORMATS,
    _cast_q,
    scale_from_amax,
)

# frozen default grid blocks (the pre-tuning constants): what every
# call without explicit blocks and without a tuning-DB hit runs on —
# locked bit-identical by tests/test_moe.py
DEFAULT_BLOCKS = {"block_c": 512, "block_n": 1024, "block_k": 1024}


def _tuned_blocks(e: int, c: int, kdim: int, n: int, fmt: str | None,
                  xdtype) -> dict:
    """Tuning-DB consult for the grouped-FFN grid blocks (op
    ``grouped_ffn``), or ``DEFAULT_BLOCKS``; tuned values validated
    positive (``fit_block`` then shrinks to divisors exactly as it
    does the defaults)."""
    from dlnetbench_tpu import tuning

    def check(cfg: dict) -> None:
        for name in DEFAULT_BLOCKS:
            blk = cfg.get(name)
            if not isinstance(blk, int) or blk <= 0:
                raise ValueError(f"grouped_matmul: tuned {name}={blk!r} "
                                 f"is not a positive int")
    return tuning.consult(
        "grouped_ffn",
        tuning.params.grouped_ffn_key(e, c, kdim, n, fmt or "none",
                                      xdtype),
        DEFAULT_BLOCKS, validate=check)


def _grouped_kernel(counts_ref, sx_ref, sw_ref, x_ref, w_ref, out_ref,
                    acc_ref, *, fmt: str | None, block_c: int):
    """Grid (e, ci, ni, ki); ki is the minor accumulation axis.  A
    token block wholly beyond its expert's count contributes no dot
    (its inputs were never re-DMA'd — the index map clamped to block 0)
    and emits zeros.  ``counts_ref``/``sx_ref``/``sw_ref`` are the
    scalar-prefetched [E] per-expert counts and scales."""
    e = pl.program_id(0)
    ci = pl.program_id(1)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    live = ci * block_c < counts_ref[e]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_dtype = _FORMATS[fmt][2] if fmt else F32

    @pl.when(live)
    def _dot():
        xf = x_ref[0].astype(F32)
        if fmt:
            # prologue: quantize the activation tile in VMEM against
            # this EXPERT's scale — x_q never exists in HBM
            xq = _cast_q(xf / sx_ref[e], fmt)
            wblk = w_ref[0]
        else:
            xq, wblk = xf, w_ref[0].astype(F32)
        acc_ref[...] += jax.lax.dot_general(
            xq, wblk, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)

    @pl.when(ki == nk - 1)
    def _emit():
        scale = (sx_ref[e] * sw_ref[e]) if fmt \
            else jnp.float32(1.0)
        val = acc_ref[...].astype(F32) * scale
        out_ref[0] = jnp.where(live, val, 0.0).astype(out_ref.dtype)


def grouped_matmul(x, w, *, counts=None, sx=None, sw=None,
                   fmt: str | None = None, out_dtype=None,
                   block_c: int | None = None,
                   block_n: int | None = None,
                   block_k: int | None = None):
    """``[E, C, K] @ [E, K, N] -> [E, C, N]`` per-expert matmul.

    ``counts`` ([E] int32, optional): valid tokens per expert — token
    blocks wholly past the count are SKIPPED (no MXU work, no fresh
    DMA, zero output).  ``None`` computes every block (the dense
    capacity-buffer contract: padded rows are zeros and produce
    zeros).

    Quantized form (``fmt`` = "int8" | "float8"): ``w`` must be
    PRE-QUANTIZED per expert ([E, K, N] in the quantized dtype), with
    ``sw`` [E] its per-expert scales and ``sx`` [E] the per-expert
    activation scales the prologue quantizes against.

    Grid blocks: explicit arguments win; with none given the tuning DB
    is consulted (op ``grouped_ffn``) and an empty DB keeps the frozen
    ``DEFAULT_BLOCKS`` bit-identically (ISSUE 9)."""
    e, c, kdim = x.shape
    if w.shape[0] != e or w.shape[1] != kdim:
        raise ValueError(f"grouped_matmul: shape mismatch "
                         f"x{x.shape} @ w{w.shape}")
    n = w.shape[2]
    if fmt is not None:
        if fmt not in _FORMATS:
            raise ValueError(f"grouped_matmul: unknown fmt {fmt!r}; "
                             f"one of {tuple(_FORMATS)}")
        if sx is None or sw is None:
            raise ValueError("grouped_matmul: fmt set but sx/sw "
                             "per-expert scales missing")
    if block_c is None and block_n is None and block_k is None:
        blocks = _tuned_blocks(e, c, kdim, n, fmt, x.dtype)
    else:
        blocks = {"block_c": block_c or DEFAULT_BLOCKS["block_c"],
                  "block_n": block_n or DEFAULT_BLOCKS["block_n"],
                  "block_k": block_k or DEFAULT_BLOCKS["block_k"]}
        for name, blk in blocks.items():
            if not isinstance(blk, int) or blk <= 0:
                raise ValueError(f"grouped_matmul: {name}={blk!r} must "
                                 f"be a positive int")
    bc = fit_block(c, blocks["block_c"])
    bn = fit_block(n, blocks["block_n"])
    bk = fit_block(kdim, blocks["block_k"])
    grid = (e, c // bc, n // bn, kdim // bk)

    if counts is None:
        counts = jnp.full((e,), c, jnp.int32)
    counts = counts.astype(jnp.int32)
    sx_a = (jnp.asarray(sx, F32).reshape(e) if fmt
            else jnp.zeros((e,), F32))
    sw_a = (jnp.asarray(sw, F32).reshape(e) if fmt
            else jnp.zeros((e,), F32))

    def x_index(ei, ci, ni, ki, counts_ref, _sx, _sw):
        # skipped blocks clamp to the expert's block 0: an already-
        # visited block, so the revisit issues no fresh DMA
        cc = jnp.where(ci * bc < counts_ref[ei], ci, 0)
        return (ei, cc, ki)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), x_index),
            pl.BlockSpec((1, bk, bn),
                         lambda ei, ci, ni, ki, *_: (ei, ki, ni)),
        ],
        out_specs=pl.BlockSpec(
            (1, bc, bn), lambda ei, ci, ni, ki, *_: (ei, ci, ni)),
        scratch_shapes=[pltpu.VMEM((bc, bn),
                                   _FORMATS[fmt][2] if fmt else F32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, fmt=fmt, block_c=bc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, n), out_dtype or x.dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        name="grouped_mm",
        interpret=pallas_common.interpret_mode(),
    )(counts, sx_a, sw_a, x, w)
    return out


def quantize_experts(w, fmt: str):
    """Per-expert symmetric quantization of a stacked weight
    ``[E, K, N]`` -> ``(wq [E, K, N], sw [E])`` — the once-per-step
    weight path of the grouped kernels (``quantize_tensor`` vmapped
    over the expert axis; same ``scale_from_amax`` spelling)."""
    wf = w.astype(F32)
    amax = jnp.max(jnp.abs(wf), axis=(1, 2))
    sw = scale_from_amax(amax, fmt)
    return _cast_q(wf / sw[:, None, None], fmt), sw


def expert_amax(x):
    """Per-expert activation amax of a dispatch buffer ``[E, C, K]``
    (padded rows are zeros and cannot inflate it) -> [E] f32."""
    return jnp.max(jnp.abs(x.astype(F32)), axis=(1, 2))


def _ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks):
    """The three grouped dots of the expert SwiGLU -> ``(y, g, u)``:
    the ONE body behind the primal and the VJP's forward, so the
    rounding of what the backward reads cannot drift from what the
    forward computed (``layers.swiglu``'s discipline: ``g``,
    ``u`` stay in the kernels' output dtype).  ``blocks`` is the
    (block_c, block_n, block_k) triple (hashable — it rides a
    custom_vjp nondiff argnum)."""
    kw = dict(counts=counts,
              **dict(zip(("block_c", "block_n", "block_k"), blocks)))
    if fmt:
        sx = scale_from_amax(expert_amax(x), fmt)
        wgq, swg = quantize_experts(w_gate, fmt)
        wuq, swu = quantize_experts(w_up, fmt)
        g = grouped_matmul(x, wgq, sx=sx, sw=swg, fmt=fmt, **kw)
        u = grouped_matmul(x, wuq, sx=sx, sw=swu, fmt=fmt, **kw)
        h = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(g.dtype)
        sh = scale_from_amax(expert_amax(h), fmt)
        wdq, swd = quantize_experts(w_down, fmt)
        return (grouped_matmul(h, wdq, sx=sh, sw=swd, fmt=fmt, **kw),
                g, u)
    g = grouped_matmul(x, w_gate, **kw)
    u = grouped_matmul(x, w_up, **kw)
    h = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(g.dtype)
    return grouped_matmul(h, w_down, **kw), g, u


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped_ffn(x, w_gate, w_up, w_down, counts, fmt, blocks):
    return _ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks)[0]


def _grouped_ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks):
    y, g, u = _ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks)
    return y, (x, g, u, w_gate, w_up, w_down, counts)


def _grouped_ffn_bwd(fmt, blocks, res, dy):
    """Straight-through master-dtype backward (the recipe every
    quantized path shares): six batched einsums over the expert axis,
    all ``E * C`` slots, float32 accumulation.  ``g`` and ``u`` are
    the forward's own (for every ``fmt``: the gradient is taken at the
    activations the forward fed to ``silu(g) * u``), widened to
    float32 for the elementwise block; only ``h`` is made again from
    them.  No count mask is needed: in a block the forward skipped,
    ``g`` and ``u`` are its zeros, so ``h``, ``dg`` and ``du`` vanish
    there and those rows get zero ``dx`` whatever ``dy`` holds; in a
    live block, rows past the count are the dispatch's zero fill and
    carry zero cotangent (their combine weights are zero)."""
    x, g, u, w_gate, w_up, w_down, counts = res
    xf = x.astype(F32)
    g = g.astype(F32)
    u = u.astype(F32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    h = silu * u
    dyf = dy.astype(F32)
    dh = jnp.einsum("ecd,ehd->ech", dyf, w_down.astype(F32))
    dwd = jnp.einsum("ech,ecd->ehd", h, dyf).astype(w_down.dtype)
    dg = dh * u * (sig + silu * (1.0 - sig))
    du = dh * silu
    dx = (jnp.einsum("ech,edh->ecd", dg, w_gate.astype(F32))
          + jnp.einsum("ech,edh->ecd", du, w_up.astype(F32)))
    dwg = jnp.einsum("ecd,ech->edh", xf, dg).astype(w_gate.dtype)
    dwu = jnp.einsum("ecd,ech->edh", xf, du).astype(w_up.dtype)
    # counts is state, not a weight: zero cotangent (it rides the
    # primal signature as f32 precisely so this zero is well-typed)
    return (dx.astype(x.dtype), dwg, dwu, dwd,
            jnp.zeros_like(counts))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def grouped_ffn(x, w_gate, w_up, w_down, *, counts=None,
                fmt: str | None = None, block_c: int | None = None,
                block_n: int | None = None, block_k: int | None = None):
    """The grouped expert SwiGLU: ``x`` [E, C, d] dispatch buffers,
    weights [E, d, h] / [E, h, d] stacked per expert -> [E, C, d].

    ``counts`` enables the gather/scatter block skipping, ``fmt``
    selects the fused-quantization recipes (per-expert dynamic scales,
    straight-through backward).  Block shapes are a tuning-DB site
    (op ``grouped_ffn``); ``None`` consults, explicit ints win."""
    if fmt is not None and fmt not in _FORMATS:
        raise ValueError(f"grouped_ffn: unknown fmt {fmt!r}; one of "
                         f"{tuple(_FORMATS)} or None")
    e, c, _ = x.shape
    counts_f = (jnp.full((e,), float(c), F32) if counts is None
                else counts.astype(F32))
    return _grouped_ffn(x, w_gate, w_up, w_down, counts_f, fmt,
                        (block_c, block_n, block_k))
