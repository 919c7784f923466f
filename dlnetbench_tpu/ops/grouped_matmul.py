"""Grouped (per-expert) Pallas matmuls: the MoE expert-FFN kernel.

The expert FFN of a dispatched MoE layer is E independent matmuls over
per-expert token buffers — ``[E, C, K] @ [E, K, N] -> [E, C, N]`` —
where ``C`` is the capacity (dispatch slots) per expert.  XLA runs it
as one batched einsum that pays the FULL ``E*C`` token grid even when
routing left most slots empty.  This kernel family makes the dispatch
layout a first-class grid:

* **a tile plan from the matmul's own shape** (``tile_plan``,
  ``n_outer``) — what a grid step brings into VMEM.  A dimension is
  taken whole or by a divisor that is a multiple of the 128-lane tile
  (1408 = 11 x 128 whole, never 128 by halving).  The plan prefers the
  whole contraction (no accumulator pass; one operand stays put across
  the inner grid axis), then the widest output tile the VMEM budget
  allows: where an expert's whole weight fits, it is fetched once an
  expert and stays across its row blocks; where it does not, the grid
  takes the order in which the operand that is fetched again is the
  cheaper one (a weight block stays while the rows pass, or the rows
  stay while the weight's blocks pass).  Gate/up and down are
  different matmuls (K and N swap) and each gets the plan of its own
  shape.
* **operands as stored** — bf16 tiles go to the MXU as bf16 with
  float32 accumulation (float32 inputs stay float32); only the
  quantizing prologue below makes a float32 copy of its tile.
* **gather/scatter skipping** — the per-expert VALID-token counts ride
  as a scalar-prefetch operand (the splash-kernel pattern, ISSUE 10):
  a token block lying wholly beyond its expert's count issues no MXU
  work and writes zeros, and its index maps name the NEXT live step's
  input blocks (``index_maps``, ``hold_table``), so it fetches nothing
  of its own at any ``nk`` and the next expert's tiles arrive while
  the last live step still multiplies — under skewed routing the
  kernel does the work the tokens need, not the work the padding
  implies.
* **fused quantization** (the PR-3 recipe, ops/quantized_matmul.py):
  with ``fmt`` int8/float8 the activation tile is quantized in the
  VMEM PROLOGUE against a provided PER-EXPERT scale, int32/f32 MXU
  accumulation, ``sx[e] * sw[e]`` applied in-register in the epilogue
  — the quantized activation never exists in HBM.  Scale spelling is
  shared with the composed paths (``scale_from_amax`` / ``_cast_q``),
  so the int8 grouped result is EXACTLY the composed reference.
* **tuning-DB site** (ISSUE 9): the grid blocks consult the DB under
  op ``grouped_ffn`` keyed per (E, C, K, N, fmt, dtype); an empty DB
  gives the shape's own ``tile_plan`` bit-identically, explicit block
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
from dlnetbench_tpu.ops.pallas_common import (
    F32,
    LANES,
    compiler_params,
    fit_block,
)
from dlnetbench_tpu.ops.quantized_matmul import (
    _FORMATS,
    _cast_q,
    scale_from_amax,
)

# The tile plan's two constants.  A grid step's tiles (two buffers of
# each operand and of the output, the float32 product) stay under three
# quarters of the Mosaic limit the kernel family runs under; the rest is
# the compiler's own.  The row block is the granule the kernel skips
# by, and Mosaic unrolls a step's product into straight-line code, so
# it also sets the size of the executable: 512 rows multiply no faster
# than 256 on a v5e and load twice the code (PERF.md section 6, PR 31).
VMEM_BUDGET = pallas_common.DEFAULT_VMEM_LIMIT_MB * 2 ** 20 * 3 // 4
ROW_BLOCK = 256
# an output tile narrower than this many lanes (or the whole of a
# narrower N) is never worth a whole contraction
_MIN_BLOCK_N = 512
_BLOCK_NAMES = ("block_c", "block_n", "block_k")


def tile_bytes(bc: int, bn: int, bk: int, itemsize: int,
                quantized: bool = False) -> int:
    """VMEM one grid step asks for: the activation tile, the weight
    block and the output tile, each twice (the pipeline fetches the
    next while this one multiplies), and the float32 product; the
    quantizing prologue widens the activation tile to float32 first."""
    return (2 * (bc * bk + bk * bn + bc * bn) * itemsize + bc * bn * 4
            + (bc * bk * 4 if quantized else 0))


def blocks_of(dim: int, unit: int) -> list:
    """What may tile ``dim``, widest first: the whole of it, then every
    divisor that is a multiple of ``unit``."""
    return [dim] + [b for b in range(dim - unit, 0, -unit)
                    if dim % b == 0 and b % unit == 0]


def n_outer(c: int, kdim: int, n: int, bc: int, bn: int, bk: int) -> bool:
    """The grid order that fetches fewer bytes an expert: row blocks
    outside output-column blocks (False; the activation tile stays
    while the weight's blocks pass) or inside them (True; a weight
    block stays while the rows pass).  With a whole contraction the
    operand of the outer axis is fetched once; the inner one once for
    each outer block, unless it has a single block."""
    nc, nn, whole_k = c // bc, n // bn, bk == kdim
    x, w = c * kdim, kdim * n
    c_out = (x if whole_k else x * nn) \
        + (w if whole_k and nn == 1 else w * nc)
    n_out = (w if whole_k else w * nc) \
        + (x if whole_k and nc == 1 else x * nn)
    return n_out < c_out


def tile_plan(c: int, kdim: int, n: int, itemsize: int, *,
              quantized: bool = False, budget: int = VMEM_BUDGET) -> dict:
    """The grid blocks of ``[C, K] @ [K, N]`` an expert, from the
    matmul's own shape: a dimension is taken whole or by a divisor that
    is a multiple of the 128-lane tile (rows: of the dtype's sublane
    tile), so 1408 = 11 x 128 is 1408 or 11 blocks, never 128 by
    halving.  Of what fits ``budget`` it prefers the whole contraction
    (no accumulator pass, and one operand stays put across the inner
    grid axis), then the widest output tile: the whole of N keeps an
    expert's weight resident across its row blocks.  ``n_outer`` then
    orders the grid for these blocks."""
    rows = blocks_of(c, 8 * max(1, 4 // itemsize))
    bc = next((b for b in rows if b <= ROW_BLOCK), rows[-1])
    cands = [(bn, bk) for bk in blocks_of(kdim, LANES)
             for bn in blocks_of(n, LANES)]

    def need(p):
        return tile_bytes(bc, *p, itemsize, quantized)
    fits = [p for p in cands if need(p) <= budget] or [min(cands, key=need)]
    bn, bk = max(fits, key=lambda p: (p[0] >= min(n, _MIN_BLOCK_N),
                                      p[1], p[0]))
    return {"block_c": bc, "block_n": bn, "block_k": bk}


def _tuned_blocks(e: int, c: int, kdim: int, n: int, fmt: str | None,
                  xdtype) -> dict:
    """Tuning-DB consult for the grouped-FFN grid blocks (op
    ``grouped_ffn``), or the matmul's own ``tile_plan``; tuned values
    validated positive (``fit_block`` then shrinks them to divisors)."""
    from dlnetbench_tpu import tuning

    def check(cfg: dict) -> None:
        for name in _BLOCK_NAMES:
            blk = cfg.get(name)
            if not isinstance(blk, int) or blk <= 0:
                raise ValueError(f"grouped_matmul: tuned {name}={blk!r} "
                                 f"is not a positive int")
    return tuning.consult(
        "grouped_ffn",
        tuning.params.grouped_ffn_key(e, c, kdim, n, fmt or "none",
                                      xdtype),
        tile_plan(c, kdim, n, jnp.dtype(xdtype).itemsize,
                  quantized=fmt is not None), validate=check)


def hold_table(counts, block_c: int):
    """``(te, tc)`` [E] int32: the expert and the row block that the
    steps past expert ``e``'s count name.  They look ahead: row block 0
    of the next expert that has a row, so that its tiles arrive behind
    the last live step's multiplying and not behind a step that hides
    nothing; when no expert with a row follows, they look back to the
    last live row block (``te <= e`` tells the two apart; block 0 of
    expert 0 when no expert has a row)."""
    e = counts.shape[0]
    live_blocks = (counts + block_c - 1) // block_c
    idx = jnp.arange(e, dtype=jnp.int32)
    at_or_after = jax.lax.cummin(jnp.where(live_blocks > 0, idx, e),
                                 reverse=True)
    ahead = jnp.concatenate([at_or_after[1:], jnp.full((1,), e, jnp.int32)])
    back = jax.lax.cummax(jnp.where(live_blocks > 0, idx, 0))
    return (jnp.where(ahead < e, ahead, back),
            jnp.where(ahead < e, 0, jnp.maximum(live_blocks[back] - 1, 0)))


def index_maps(bc: int, nn: int, nk: int, n_out: bool):
    """The three block index maps ``(x, w, out)`` over the grid
    ``(e, ci, ni, ki)`` (``(e, ni, ci, ki)`` when ``n_out``), each
    taking the grid indices and then the prefetched ``counts, te, tc``
    (``hold_table``).  A step past its expert's count names the input
    blocks of the next live step (of the last one, when none follows):
    the pipeline fetches a block only when its index changes, so such a
    step moves no input byte that a live step does not need, and the
    one fetch a run of them starts is the next live step's own, begun
    while the last live step still multiplies.  Its output block is
    its own (the kernel writes its zeros)."""
    def named(ei, a, b, ki, counts, te, tc):
        """(expert, row block, column block, contraction block) whose
        input blocks this step names."""
        ci, ni = (b, a) if n_out else (a, b)
        live = ci * bc < counts[ei]
        ahead = te[ei] > ei
        # rows inside column blocks: an expert's rows start again at
        # the next column block
        again = ((counts[ei] > 0) & (ni + 1 < nn)) if n_out else False
        return (jnp.where(live | again, ei, te[ei]),
                jnp.where(live, ci, jnp.where(again, 0, tc[ei])),
                jnp.where(live, ni, jnp.where(
                    again, ni + 1, jnp.where(ahead, 0, nn - 1))),
                jnp.where(live, ki, jnp.where(again | ahead, 0, nk - 1)))

    def x_index(ei, a, b, ki, counts, te, tc, *_):
        e_, c_, _n, k_ = named(ei, a, b, ki, counts, te, tc)
        return e_, c_, k_

    def w_index(ei, a, b, ki, counts, te, tc, *_):
        e_, _c, n_, k_ = named(ei, a, b, ki, counts, te, tc)
        return e_, k_, n_

    def out_index(ei, a, b, ki, *_):
        return (ei, b, a) if n_out else (ei, a, b)
    return x_index, w_index, out_index


def _grouped_kernel(counts_ref, _te, _tc, sx_ref, sw_ref, x_ref, w_ref,
                    out_ref, *acc, fmt: str | None, block_c: int,
                    n_out: bool):
    """One grid step.  A row block wholly beyond its expert's count
    multiplies nothing (and fetched nothing of its own:
    ``index_maps``) and emits zeros.  The operands go to the MXU in the dtype they are stored in,
    float32 accumulation; with the whole contraction in one block
    (no ``acc`` scratch) the product is written as it comes.
    ``counts_ref``/``sx_ref``/``sw_ref`` are the scalar-prefetched [E]
    per-expert counts and scales."""
    e = pl.program_id(0)
    ci = pl.program_id(2 if n_out else 1)
    live = ci * block_c < counts_ref[e]

    def product():
        xblk, wblk = x_ref[0], w_ref[0]
        if fmt:
            # prologue: quantize the activation tile in VMEM against
            # this EXPERT's scale — x_q never exists in HBM
            xblk = _cast_q(xblk.astype(F32) / sx_ref[e], fmt)
        elif xblk.dtype != wblk.dtype:
            both = jnp.promote_types(xblk.dtype, wblk.dtype)
            xblk, wblk = xblk.astype(both), wblk.astype(both)
        return jax.lax.dot_general(
            xblk, wblk, (((1,), (0,)), ((), ())),
            preferred_element_type=_FORMATS[fmt][2] if fmt else F32)

    def emit(val):
        if fmt:
            val = val.astype(F32) * (sx_ref[e] * sw_ref[e])
        out_ref[0] = val.astype(out_ref.dtype)

    if not acc:
        @pl.when(live)
        def _whole():
            emit(product())

        @pl.when(jnp.logical_not(live))
        def _zeros():
            out_ref[0] = jnp.zeros_like(out_ref[0])
        return

    acc_ref, = acc
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _dot():
        acc_ref[...] += product()

    @pl.when(ki == pl.num_programs(3) - 1)
    def _emit():
        emit(acc_ref[...])


def grouped_matmul(x, w, *, counts=None, sx=None, sw=None,
                   fmt: str | None = None, out_dtype=None,
                   block_c: int | None = None,
                   block_n: int | None = None,
                   block_k: int | None = None):
    """``[E, C, K] @ [E, K, N] -> [E, C, N]`` per-expert matmul.

    ``counts`` ([E] int32, optional): valid tokens per expert — token
    blocks wholly past the count are SKIPPED (no MXU work, no input
    DMA, zero output).  ``None`` computes every block (the dense
    capacity-buffer contract: padded rows are zeros and produce
    zeros).

    Quantized form (``fmt`` = "int8" | "float8"): ``w`` must be
    PRE-QUANTIZED per expert ([E, K, N] in the quantized dtype), with
    ``sw`` [E] its per-expert scales and ``sx`` [E] the per-expert
    activation scales the prologue quantizes against.

    Grid blocks: explicit arguments win (one left out is the plan's);
    with none given the tuning DB is consulted (op ``grouped_ffn``)
    and an empty DB gives this shape's ``tile_plan``.  The grid's
    order follows from the blocks (``n_outer``)."""
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
    given = {name: blk for name, blk in
             zip(_BLOCK_NAMES, (block_c, block_n, block_k))
             if blk is not None}
    for name, blk in given.items():
        if not isinstance(blk, int) or blk <= 0:
            raise ValueError(f"grouped_matmul: {name}={blk!r} must "
                             f"be a positive int")
    blocks = {**(tile_plan(c, kdim, n, x.dtype.itemsize,
                           quantized=fmt is not None) if given
                 else _tuned_blocks(e, c, kdim, n, fmt, x.dtype)), **given}
    bc = fit_block(c, blocks["block_c"])
    bn = fit_block(n, blocks["block_n"])
    bk = fit_block(kdim, blocks["block_k"])
    nc, nn, nk = c // bc, n // bn, kdim // bk
    n_out = n_outer(c, kdim, n, bc, bn, bk)

    if counts is None:
        counts = jnp.full((e,), c, jnp.int32)
    counts = counts.astype(jnp.int32)
    te, tc = hold_table(counts, bc)
    sx_a = (jnp.asarray(sx, F32).reshape(e) if fmt
            else jnp.zeros((e,), F32))
    sw_a = (jnp.asarray(sw, F32).reshape(e) if fmt
            else jnp.zeros((e,), F32))

    x_index, w_index, out_index = index_maps(bc, nn, nk, n_out)
    acc_dtype = _FORMATS[fmt][2] if fmt else F32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(e, nn, nc, nk) if n_out else (e, nc, nn, nk),
        in_specs=[pl.BlockSpec((1, bc, bk), x_index),
                  pl.BlockSpec((1, bk, bn), w_index)],
        out_specs=pl.BlockSpec((1, bc, bn), out_index),
        scratch_shapes=([pltpu.VMEM((bc, bn), acc_dtype)] if nk > 1
                        else []),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, fmt=fmt, block_c=bc,
                          n_out=n_out),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, n), out_dtype or x.dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        name="grouped_mm",
        interpret=pallas_common.interpret_mode(),
    )(counts, te, tc, sx_a, sw_a, x, w)
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
