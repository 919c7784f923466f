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
products, not eight.  Which backward runs is the caller's word on
what its buffer is (``backward=``):

* ``"einsum"`` (the default; ``moe.moe_grouped``, the quantized
  ``fmt`` recipes, ``counts=None`` under the EP-sharded
  ``a2a_expert_ffn``): six full-grid XLA einsums over all ``E * C``
  slots (``_grouped_ffn_bwd``).  Right where the buffer is a
  CAPACITY: rows are dropped to fit it, so it is nearly full (80 % in
  the Mixtral cell) and XLA fuses the weight gradients with the
  optimizer's update of a one-layer stack.  Rows past an expert's
  count need no mask there: a skipped block's ``g`` and ``u`` are the
  kernel's zeros, which make ``h``, ``dg`` and ``du`` zero whatever
  ``dy`` holds.
* ``"counted"`` (``moe.moe_held``): four kernels that multiply exactly
  the row blocks the forward multiplied (``_grouped_ffn_counted_bwd``,
  a second ``custom_vjp`` over the same ``_ffn_fwd``).  Right where
  the buffer is a BOUND: nothing may be dropped, so it is loose by
  design (21-50 % full in the three ``hybrid.py`` cells) and most of
  what the einsums multiply is the dispatch's zeros.  The row side
  (``dh``; ``dx`` as one float32 sum of two products) reuses the
  forward's tile plan and index maps with the weight contracted on
  its last dimension as stored (``grouped_mm_bwd_dh``, whose epilogue
  makes ``h``, ``dg``, ``du`` from the float32 ``dh`` tile, each
  rounded once; ``grouped_mm_bwd_dx``); the contraction side
  (``grouped_mm_bwd_dw``: dW_down; dW_gate and dW_up in one call)
  walks an expert's row blocks as the last, ``arbitrary`` grid axis
  into a float32 sum of the whole ``[K, N]`` gradient, a block past
  the count naming the last live block (``last_live``).  None of the
  three names is ``grouped_mm``: the forward family's roofline
  metrics find their kernels by that name and count only them.

Two layouts of the experts' rows run through the same kernel bodies,
tiles and names.  PADDED, ``[E, C, K]``: room for C rows of every
expert, the grid's row axis over each expert's C / bc blocks; what a
capacity fills (``moe.moe_grouped``, the SPMD step) and what
``moe.moe_held`` keeps where its bound leaves fewer slots than the
routing has pairs (Kimi, Qwen).  PACKED, ``[1, R, K]`` (``bound=`` C on
``grouped_matmul`` and ``grouped_ffn``): where a bound is so loose
that ``E * C`` exceeds the ``R = packed_rows(k * T, E, bc)`` rows all
the pairs can fill (LFM2, SmallThinker), ``moe_held`` hands over one
buffer in which expert e's rows start at row block
``packed_first(counts, bc)[e]``.  The row-side calls then walk the
buffer's R / bc blocks as one group whose live rows are a prefix, a
block multiplied by the weight of the expert that owns it
(``packed_tables``: consecutive blocks of one expert keep its weight
resident), the blocks past the prefix dead as above; the contraction
side walks expert e's at most C / bc blocks from its first.  What
differs is the grid's row axis, the index maps and the prefetched
tables: a row block's products are the padded form's to the bit, and
an expert's blocks add up in the same order.

All kernels run under ``interpret=True`` off-TPU (pallas_common), so
the CPU-mesh tier-1 lane unit-tests them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.metrics import spans
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
                quantized: bool = False, *, pairs: int = 1,
                row_tiles: int = 1) -> int:
    """VMEM one grid step asks for: the activation tile, the weight
    block and the output tile, each twice (the pipeline fetches the
    next while this one multiplies), and the float32 product; the
    quantizing prologue widens the activation tile to float32 first.
    The backward's row-side kernels sum ``pairs`` products a step and
    move ``row_tiles`` tiles of the output's shape (the SwiGLU
    epilogue reads two and writes three)."""
    return (2 * (pairs * (bc * bk + bk * bn) + row_tiles * bc * bn)
            * itemsize + bc * bn * 4 + (bc * bk * 4 if quantized else 0))


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
              quantized: bool = False, budget: int = VMEM_BUDGET,
              block_c: int | None = None, pairs: int = 1,
              row_tiles: int = 1) -> dict:
    """The grid blocks of ``[C, K] @ [K, N]`` an expert, from the
    matmul's own shape: a dimension is taken whole or by a divisor that
    is a multiple of the 128-lane tile (rows: of the dtype's sublane
    tile), so 1408 = 11 x 128 is 1408 or 11 blocks, never 128 by
    halving.  Of what fits ``budget`` it prefers the whole contraction
    (no accumulator pass, and one operand stays put across the inner
    grid axis), then the widest output tile: the whole of N keeps an
    expert's weight resident across its row blocks.  ``n_outer`` then
    orders the grid for these blocks.  ``block_c`` fixes the row block
    (the backward takes the forward's); ``pairs`` and ``row_tiles`` are
    ``tile_bytes``'s."""
    rows = blocks_of(c, 8 * max(1, 4 // itemsize))
    bc = block_c or next((b for b in rows if b <= ROW_BLOCK), rows[-1])
    cands = [(bn, bk) for bk in blocks_of(kdim, LANES)
             for bn in blocks_of(n, LANES)]

    def need(p):
        return tile_bytes(bc, *p, itemsize, quantized, pairs=pairs,
                          row_tiles=row_tiles)
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


def _fit_blocks(e: int, c: int, kdim: int, n: int, fmt: str | None,
                xdtype, blocks) -> tuple:
    """``(bc, bn, bk)`` of one grouped matmul from its (block_c,
    block_n, block_k) arguments: explicit ones win (one left out is
    the plan's); with none given the tuning DB is consulted; each is
    then shrunk to a divisor of its dimension."""
    given = {name: blk for name, blk in zip(_BLOCK_NAMES, blocks)
             if blk is not None}
    for name, blk in given.items():
        if not isinstance(blk, int) or blk <= 0:
            raise ValueError(f"grouped_matmul: {name}={blk!r} must "
                             f"be a positive int")
    planned = {**(tile_plan(c, kdim, n, jnp.dtype(xdtype).itemsize,
                            quantized=fmt is not None) if given
                  else _tuned_blocks(e, c, kdim, n, fmt, xdtype)), **given}
    return tuple(fit_block(dim, planned[name])
                 for dim, name in zip((c, n, kdim), _BLOCK_NAMES))


def row_block(e: int, c: int, d: int, f: int, xdtype,
              blocks=(None, None, None)) -> int:
    """The row block of an expert FFN ``[E, C, d] -> f -> d``: the
    granule its forward kernels skip by, its counted backward walks
    and a packed buffer is laid out in (the gate projection's)."""
    return _fit_blocks(e, c, d, f, None, xdtype, blocks)[0]


def packed_rows(pairs: int, e: int, block_c: int) -> int:
    """R, the rows of a packed buffer: what ``pairs`` rows over ``e``
    experts can fill at the very most when each expert's rows start at
    a multiple of ``block_c``, itself a whole number of row blocks."""
    return -(-(pairs + e * (block_c - 1)) // block_c) * block_c


def packed_first(counts, block_c: int):
    """[E + 1] int32, THE packed layout, in row blocks: the block at
    which each expert's rows start, the next one past the expert before
    it (``first[0] = 0``), and last the end of the live prefix; expert
    e's rows lie from row ``first[e] * block_c`` on.  The plan that
    fills a packed buffer (``layers._pack``) and the kernels that walk
    it both read the layout off the counts through this."""
    blocks = jax.lax.div(counts.astype(jnp.int32) + (block_c - 1), block_c)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(blocks, dtype=jnp.int32)])


def owner_of(at, ends):
    """For each position of ``at`` (ascending from 0) the segment it
    lies in, where segment i ends before ``ends[i]`` (ascending, a
    segment may be empty): the number of ends at or before it, so a
    position past them all reads ``len(ends)``."""
    return jnp.sum(at[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)


def packed_tables(counts, block_c: int, n_blocks: int):
    """The prefetched tables of a row-side call over a packed buffer
    of ``n_blocks`` row blocks, which the grid walks as ONE group whose
    live rows are a prefix: ``(rows, te, tc, owner)``, the first three
    that group's ``counts`` and ``hold_table`` (a dead step looks back
    to the last live block), and ``owner`` [n_blocks] int32, the expert
    whose rows a block holds and whose weight multiplies it (any
    expert's for a dead block, which no step names)."""
    first = packed_first(counts, block_c)
    live = first[-1:]
    owner = owner_of(jnp.arange(n_blocks, dtype=jnp.int32), first[1:-1])
    return (live * block_c, jnp.zeros((1,), jnp.int32),
            jnp.maximum(live - 1, 0), owner)


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


def named_step(bc: int, nn: int, nk: int, n_out: bool):
    """``named(ei, a, b, ki, counts, te, tc)`` -> the (expert, row
    block, column block, contraction block) whose input blocks a step
    of the grid ``(e, ci, ni, ki)`` (``(e, ni, ci, ki)`` when
    ``n_out``) names: its own when its row block is live, else the
    next live step's (of the last one, when none follows;
    ``hold_table``)."""
    def named(ei, a, b, ki, counts, te, tc):
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
    return named


def index_maps(bc: int, nn: int, nk: int, n_out: bool,
               packed: bool = False):
    """The three block index maps ``(x, w, out)`` over the grid
    ``(e, ci, ni, ki)`` (``(e, ni, ci, ki)`` when ``n_out``), each
    taking the grid indices and then the prefetched ``counts, te, tc``
    (``hold_table``).  A step past its expert's count names the input
    blocks of the next live step (of the last one, when none follows):
    the pipeline fetches a block only when its index changes, so such a
    step moves no input byte that a live step does not need, and the
    one fetch a run of them starts is the next live step's own, begun
    while the last live step still multiplies.  Its output block is
    its own (the kernel writes its zeros).  ``packed``: the rows are
    one group (``packed_tables``) and the weight is the one of the
    expert that owns the named row block, the fourth prefetched
    table."""
    named = named_step(bc, nn, nk, n_out)

    def x_index(ei, a, b, ki, counts, te, tc, *_):
        e_, c_, _n, k_ = named(ei, a, b, ki, counts, te, tc)
        return e_, c_, k_

    def w_index(ei, a, b, ki, counts, te, tc, *rest):
        e_, c_, n_, k_ = named(ei, a, b, ki, counts, te, tc)
        return rest[0][c_] if packed else e_, k_, n_

    def out_index(ei, a, b, ki, *_):
        return (ei, b, a) if n_out else (ei, a, b)
    return x_index, w_index, out_index


def _owner_is_the_maps(kernel):
    """The body of a packed row-side call: ``kernel`` without the
    fourth prefetched table (``packed_tables``' ``owner``), which only
    the index maps read."""
    def body(counts_ref, te_ref, tc_ref, _owner, *refs):
        return kernel(counts_ref, te_ref, tc_ref, *refs)
    return body


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
                   block_k: int | None = None,
                   bound: int | None = None):
    """``[E, C, K] @ [E, K, N] -> [E, C, N]`` per-expert matmul.

    ``counts`` ([E] int32, optional): valid tokens per expert — token
    blocks wholly past the count are SKIPPED (no MXU work, no input
    DMA, zero output).  ``None`` computes every block (the dense
    capacity-buffer contract: padded rows are zeros and produce
    zeros).

    Packed form (``bound`` = C given): ``x`` is ``[1, R, K]``, one
    group of rows, expert e's ``counts[e]`` (at most C) lying from row
    ``packed_first(counts, bc)[e] * bc`` on, -> ``[1, R, N]``.  The
    tiles are those of ``[E, C, K]``, so a row block's product is the
    padded form's to the bit; the grid's row axis runs over the R / bc
    blocks of the buffer, each multiplied by its owner's weight
    (``packed_tables``), the blocks past the live prefix skipped as
    above.

    Quantized form (``fmt`` = "int8" | "float8"): ``w`` must be
    PRE-QUANTIZED per expert ([E, K, N] in the quantized dtype), with
    ``sw`` [E] its per-expert scales and ``sx`` [E] the per-expert
    activation scales the prologue quantizes against.

    Grid blocks: explicit arguments win (one left out is the plan's);
    with none given the tuning DB is consulted (op ``grouped_ffn``)
    and an empty DB gives this shape's ``tile_plan``.  The grid's
    order follows from the blocks (``n_outer``)."""
    packed = bound is not None
    e, (groups, rows, kdim) = w.shape[0], x.shape
    c = bound if packed else rows
    if w.shape[1] != kdim or groups != (1 if packed else e):
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
    if packed and (counts is None or fmt is not None):
        raise ValueError("grouped_matmul: the packed form needs counts "
                         "and has no quantized form")
    bc, bn, bk = _fit_blocks(e, c, kdim, n, fmt, x.dtype,
                             (block_c, block_n, block_k))
    nn, nk = n // bn, kdim // bk
    n_out = n_outer(c, kdim, n, bc, bn, bk)

    if counts is None:
        counts = jnp.full((e,), c, jnp.int32)
    counts = counts.astype(jnp.int32)
    sx_a = (jnp.asarray(sx, F32).reshape(e) if fmt
            else jnp.zeros((e,), F32))
    sw_a = (jnp.asarray(sw, F32).reshape(e) if fmt
            else jnp.zeros((e,), F32))
    kernel = functools.partial(_grouped_kernel, fmt=fmt, block_c=bc,
                               n_out=n_out)
    if rows % bc:
        raise ValueError(f"grouped_matmul: {rows} rows are no whole "
                         f"number of {bc}-row blocks")
    nc = rows // bc
    if packed:
        tables = packed_tables(counts, bc, nc)
        kernel = _owner_is_the_maps(kernel)
    else:
        tables = (counts, *hold_table(counts, bc))

    x_index, w_index, out_index = index_maps(bc, nn, nk, n_out, packed)
    acc_dtype = _FORMATS[fmt][2] if fmt else F32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables) + 2,
        grid=(groups, nn, nc, nk) if n_out else (groups, nc, nn, nk),
        in_specs=[pl.BlockSpec((1, bc, bk), x_index),
                  pl.BlockSpec((1, bk, bn), w_index)],
        out_specs=pl.BlockSpec((1, bc, bn), out_index),
        scratch_shapes=([pltpu.VMEM((bc, bn), acc_dtype)] if nk > 1
                        else []),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((groups, rows, n),
                                       out_dtype or x.dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        name="grouped_mm",
        interpret=pallas_common.interpret_mode(),
    )(*tables, sx_a, sw_a, x, w)
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


ACTIVATIONS = ("silu", "relu")


def gate_act(g, act: str):
    """``(a(g), a'(g))`` of the gate's activation, the float32 ``g``
    in: ``silu`` (SwiGLU) or ``relu`` (``max(g, 0)``, slope ``[g > 0]``).
    The forward, the einsum backward and the counted backward's tiles
    all take the activation and its slope from here."""
    if act == "relu":
        pos = g > 0
        return jnp.where(pos, g, 0.0), jnp.where(pos, 1.0, 0.0)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    return silu, sig + silu * (1.0 - sig)


def _ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks, act="silu",
             bound=None):
    """The three grouped dots of the gated expert FFN -> ``(y, g, u)``:
    the ONE body behind the primal and the VJP's forward, so the
    rounding of what the backward reads cannot drift from what the
    forward computed (``layers.swiglu``'s discipline: ``g``,
    ``u`` stay in the kernels' output dtype).  ``blocks`` is the
    (block_c, block_n, block_k) triple (hashable — it rides a
    custom_vjp nondiff argnum), ``bound`` ``grouped_matmul``'s (a
    packed ``x``)."""
    kw = dict(counts=counts, bound=bound,
              **dict(zip(("block_c", "block_n", "block_k"), blocks)))
    if bound is not None:       # one layout under all three products
        kw["block_c"] = row_block(w_gate.shape[0], bound, x.shape[-1],
                                  w_gate.shape[2], x.dtype, blocks)
    if fmt:
        sx = scale_from_amax(expert_amax(x), fmt)
        wgq, swg = quantize_experts(w_gate, fmt)
        wuq, swu = quantize_experts(w_up, fmt)
        g = grouped_matmul(x, wgq, sx=sx, sw=swg, fmt=fmt, **kw)
        u = grouped_matmul(x, wuq, sx=sx, sw=swu, fmt=fmt, **kw)
        h = (gate_act(g.astype(F32), act)[0]
             * u.astype(F32)).astype(g.dtype)
        sh = scale_from_amax(expert_amax(h), fmt)
        wdq, swd = quantize_experts(w_down, fmt)
        return (grouped_matmul(h, wdq, sx=sh, sw=swd, fmt=fmt, **kw),
                g, u)
    g = grouped_matmul(x, w_gate, **kw)
    u = grouped_matmul(x, w_up, **kw)
    h = (gate_act(g.astype(F32), act)[0] * u.astype(F32)).astype(g.dtype)
    return grouped_matmul(h, w_down, **kw), g, u


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_ffn(x, w_gate, w_up, w_down, counts, fmt, blocks, act):
    return _ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks, act)[0]


def _grouped_ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks, act):
    y, g, u = _ffn_fwd(x, w_gate, w_up, w_down, counts, fmt, blocks, act)
    return y, (x, g, u, w_gate, w_up, w_down, counts)


def _grouped_ffn_bwd(fmt, blocks, act, res, dy):
    """Straight-through master-dtype backward (the recipe every
    quantized path shares): six batched einsums over the expert axis,
    all ``E * C`` slots, float32 accumulation.  ``g`` and ``u`` are
    the forward's own (for every ``fmt``: the gradient is taken at the
    activations the forward fed to ``act(g) * u``), widened to
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
    a, slope = gate_act(g, act)
    h = a * u
    dyf = dy.astype(F32)
    dh = jnp.einsum("ecd,ehd->ech", dyf, w_down.astype(F32))
    dwd = jnp.einsum("ech,ecd->ehd", h, dyf).astype(w_down.dtype)
    dg = dh * u * slope
    du = dh * a
    dx = (jnp.einsum("ech,edh->ecd", dg, w_gate.astype(F32))
          + jnp.einsum("ech,edh->ecd", du, w_up.astype(F32)))
    dwg = jnp.einsum("ecd,ech->edh", xf, dg).astype(w_gate.dtype)
    dwu = jnp.einsum("ecd,ech->edh", xf, du).astype(w_up.dtype)
    # counts is state, not a weight: zero cotangent (it rides the
    # primal signature as f32 precisely so this zero is well-typed)
    return (dx.astype(x.dtype), dwg, dwu, dwd,
            jnp.zeros_like(counts))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


# ------------------------------------------- the counted backward
def _as_one_dtype(a, b):
    """Two tiles of one product in one dtype, as stored when they
    agree."""
    if a.dtype == b.dtype:
        return a, b
    both = jnp.promote_types(a.dtype, b.dtype)
    return a.astype(both), b.astype(both)


def _swiglu_bwd_tiles(dh, g, u, dtype, act: str = "silu"):
    """``(h, dg, du)`` of ``h = act(g) * u`` from the float32 tile
    ``dh`` and the forward's ``g``, ``u``: float32 arithmetic, each
    rounded once to ``dtype``."""
    g, u = g.astype(F32), u.astype(F32)
    a, slope = gate_act(g, act)
    return ((a * u).astype(dtype), (dh * u * slope).astype(dtype),
            (dh * a).astype(dtype))


def _bwd_rows_kernel(counts_ref, _te, _tc, *refs, pairs: int,
                     swiglu: bool, block_c: int, n_out: bool, nk: int,
                     act: str = "silu"):
    """One grid step of the backward's row side: the sum over ``pairs``
    of ``x_p [bc, bk] @ w_p [bn, bk]^T`` (the weight as the forward
    stores it, contracted on its last dimension), float32, rounded
    once.  With ``swiglu`` the float32 sum is ``dh`` and the step
    writes ``h``, ``dg``, ``du`` from it and the forward's ``g``,
    ``u`` tiles instead.  A row block past its expert's count
    multiplies nothing and writes zeros, as in the forward."""
    xs, ws = refs[:pairs], refs[pairs:2 * pairs]
    rest = refs[2 * pairs:]
    gu, rest = (rest[:2], rest[2:]) if swiglu else ((), rest)
    outs, acc = (rest[:3], rest[3:]) if swiglu else (rest[:1], rest[1:])
    e = pl.program_id(0)
    ci = pl.program_id(2 if n_out else 1)
    live = ci * block_c < counts_ref[e]

    def product():
        total = None
        for x_ref, w_ref in zip(xs, ws):
            xblk, wblk = _as_one_dtype(x_ref[0], w_ref[0])
            part = jax.lax.dot_general(
                xblk, wblk, (((1,), (1,)), ((), ())),
                preferred_element_type=F32)
            total = part if total is None else total + part
        return total

    def emit(val):
        if swiglu:
            tiles = _swiglu_bwd_tiles(val, gu[0][0], gu[1][0],
                                      outs[0].dtype, act)
            for out_ref, tile in zip(outs, tiles):
                out_ref[0] = tile
        else:
            outs[0][0] = val.astype(outs[0].dtype)

    def zeros():
        for out_ref in outs:
            out_ref[0] = jnp.zeros_like(out_ref[0])

    if nk == 1:
        pl.when(live)(lambda: emit(product()))
        pl.when(jnp.logical_not(live))(zeros)
        return

    acc_ref, = acc
    ki = pl.program_id(3)
    last = ki == nk - 1

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _dot():
        acc_ref[...] += product()

    pl.when(last & live)(lambda: emit(acc_ref[...]))
    pl.when(last & jnp.logical_not(live))(zeros)


def _bwd_rows(xs, ws, counts, blocks, *, swiglu=None, name: str,
              act: str = "silu", bound: int | None = None):
    """The backward's row side as one kernel: ``sum_p xs[p] [E, C, K] @
    ws[p] [E, N, K]^T -> [E, C, N]`` in ``xs[0]``'s dtype, row blocks
    past ``counts`` skipped on the forward's tile plan and index maps
    (``tile_plan``, ``index_maps``).  ``swiglu = (g, u)`` [E, C, N]:
    the float32 sum is ``dh`` and the result is ``(h, dg, du)`` in
    ``g``'s dtype, ``act`` the gate's activation.  ``blocks = (bc,
    block_n, block_k)``: the forward's row block and the caller's
    explicit blocks, if any.  ``bound`` = C: the packed form, as
    ``grouped_matmul``'s (``xs``, ``g``, ``u`` and the result
    ``[1, R, .]``)."""
    packed = bound is not None
    _, n, kdim = ws[0].shape
    groups, rows, _ = xs[0].shape
    c = bound if packed else rows
    pairs = len(xs)
    bc, given_n, given_k = blocks
    plan = tile_plan(c, kdim, n, xs[0].dtype.itemsize, block_c=bc,
                     pairs=pairs, row_tiles=5 if swiglu else 1)
    bn = fit_block(n, given_n or plan["block_n"])
    bk = fit_block(kdim, given_k or plan["block_k"])
    nn, nk = n // bn, kdim // bk
    n_out = n_outer(c, kdim, n, bc, bn, bk)
    kernel = functools.partial(_bwd_rows_kernel, pairs=pairs,
                               swiglu=swiglu is not None, block_c=bc,
                               n_out=n_out, nk=nk, act=act)
    nc = rows // bc
    if packed:
        tables = packed_tables(counts, bc, nc)
        kernel = _owner_is_the_maps(kernel)
    else:
        tables = (counts, *hold_table(counts, bc))
    named = named_step(bc, nn, nk, n_out)

    def x_index(*idx):
        e_, c_, _n, k_ = named(*idx[:7])
        return e_, c_, k_

    def w_index(*idx):
        e_, c_, n_, k_ = named(*idx[:7])
        return idx[7][c_] if packed else e_, n_, k_

    def tile_index(*idx):
        # g and u of a step past the count: the next live step's, as
        # its x and w (the step reads neither)
        e_, c_, n_, _k = named(*idx[:7])
        return e_, c_, n_

    def out_index(ei, a, b, ki, *_):
        return (ei, b, a) if n_out else (ei, a, b)

    out_spec = pl.BlockSpec((1, bc, bn), out_index)
    tile = jax.ShapeDtypeStruct((groups, rows, n),
                                swiglu[0].dtype if swiglu else xs[0].dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(groups, nn, nc, nk) if n_out else (groups, nc, nn, nk),
            in_specs=([pl.BlockSpec((1, bc, bk), x_index)] * pairs
                      + [pl.BlockSpec((1, bn, bk), w_index)] * pairs
                      + [pl.BlockSpec((1, bc, bn), tile_index)]
                      * (2 if swiglu else 0)),
            out_specs=[out_spec] * 3 if swiglu else out_spec,
            scratch_shapes=([pltpu.VMEM((bc, bn), F32)] if nk > 1
                            else []),
        ),
        out_shape=[tile] * 3 if swiglu else tile,
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        name=name,
        interpret=pallas_common.interpret_mode(),
    )(*tables, *xs, *ws, *(swiglu or ()))


def last_live(counts, block_c: int):
    """``(be, bl)`` [E] int32: the expert and the row block that the
    contraction-side steps past expert ``e``'s count name, the last
    live row block at or before ``e`` (``hold_table``'s idea, looking
    back only: the block is in VMEM already, so such a step fetches
    nothing; block 0 of expert 0 when no expert up to ``e`` has a
    row)."""
    live_blocks = (counts + block_c - 1) // block_c
    idx = jnp.arange(counts.shape[0], dtype=jnp.int32)
    back = jax.lax.cummax(jnp.where(live_blocks > 0, idx, 0))
    return back, jnp.maximum(live_blocks[back] - 1, 0)


def dw_tile_plan(bc: int, kdim: int, n: int, itemsize: int, *,
                 outs: int = 1, budget: int = VMEM_BUDGET) -> dict:
    """The output tile ``(block_k, block_n)`` of ``[C, K]^T @ [C, N]``
    an expert, summed over row blocks of ``bc``: a dimension whole or
    by a lane-multiple divisor, the largest tile that fits ``budget``
    (the whole of ``[K, N]`` reads every row block once; a tile of it
    reads one operand again for each block of the other's dimension).
    A step holds the ``[bc, bk]`` tile and ``outs`` tiles ``[bc, bn]``
    twice, and for each output its float32 sum and the tile twice."""
    def need(p):
        bk, bn = p
        return (2 * bc * (bk + outs * bn) * itemsize
                + outs * bk * bn * (4 + 2 * itemsize))
    cands = [(bk, bn) for bk in blocks_of(kdim, LANES)
             for bn in blocks_of(n, LANES)]
    fits = [p for p in cands if need(p) <= budget] or [min(cands, key=need)]
    bk, bn = max(fits, key=lambda p: (p[0] * p[1], p[1]))
    return {"block_k": bk, "block_n": bn}


def _bwd_dw_kernel(counts_ref, _be, _bl, a_ref, *refs, outs: int,
                   block_c: int):
    """One grid step of the backward's contraction side: row block
    ``ci`` of ``a [bc, bk]^T @ b_j [bc, bn]`` added to output ``j``'s
    float32 sum, which the last row block writes in the weight's
    dtype.  A block past the expert's count adds nothing (and fetched
    nothing: ``last_live``), so an expert with no row writes zeros."""
    b_refs, out_refs, accs = (refs[:outs], refs[outs:2 * outs],
                              refs[2 * outs:])
    ci = pl.program_id(3)
    live = ci * block_c < counts_ref[pl.program_id(0)]

    @pl.when(ci == 0)
    def _init():
        for acc_ref in accs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _dot():
        for b_ref, acc_ref in zip(b_refs, accs):
            ablk, bblk = _as_one_dtype(a_ref[0], b_ref[0])
            acc_ref[...] += jax.lax.dot_general(
                ablk, bblk, (((0,), (0,)), ((), ())),
                preferred_element_type=F32)

    @pl.when(ci == pl.num_programs(3) - 1)
    def _emit():
        for out_ref, acc_ref in zip(out_refs, accs):
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _bwd_dw(a, bs, counts, blocks, out_dtypes, *, name: str,
            bound: int | None = None):
    """The backward's contraction side as one kernel: ``a [E, C, K]^T
    @ bs[j] [E, C, N] -> [E, K, N]`` for each ``j``, the ragged
    dimension contracted: the row-block axis is the grid's last
    (``arbitrary``), a block past ``counts`` issues no product and
    names the last live block's inputs.  ``blocks = (bc, block_k,
    block_n)`` as in ``_bwd_rows``.  ``bound`` = C: the packed form
    (``a``, ``bs`` ``[1, R, .]``), in which expert e's at most C / bc
    row blocks are walked from its first in the buffer
    (``packed_first``); the prefetched tables are then the counts, each
    expert's first block and the last live block at or before it, all
    counted through the buffer."""
    packed = bound is not None
    e, c = counts.shape[0], bound if packed else a.shape[1]
    kdim, n = a.shape[-1], bs[0].shape[-1]
    outs = len(bs)
    bc, given_k, given_n = blocks
    plan = dw_tile_plan(bc, kdim, n, a.dtype.itemsize, outs=outs)
    bk = fit_block(kdim, given_k or plan["block_k"])
    bn = fit_block(n, given_n or plan["block_n"])
    if packed:
        first = packed_first(counts, bc)
        # the blocks run on through the buffer, so the last live one
        # at or before e is the largest so far
        tables = (counts, first[:-1], jax.lax.cummax(jnp.where(
            first[1:] > first[:-1], first[1:] - 1, 0)))
    else:
        tables = (counts, *last_live(counts, bc))

    def rows(ei, ci, counts, t1, t2):
        live = ci * bc < counts[ei]
        if packed:
            return 0, jnp.where(live, t1[ei] + ci, t2[ei])
        return jnp.where(live, ei, t1[ei]), jnp.where(live, ci, t2[ei])

    def a_index(ei, ki, ni, ci, *pre):
        e_, c_ = rows(ei, ci, *pre)
        return e_, c_, ki

    def b_index(ei, ki, ni, ci, *pre):
        e_, c_ = rows(ei, ci, *pre)
        return e_, c_, ni

    return pl.pallas_call(
        functools.partial(_bwd_dw_kernel, outs=outs, block_c=bc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(e, kdim // bk, n // bn, c // bc),
            in_specs=([pl.BlockSpec((1, bc, bk), a_index)]
                      + [pl.BlockSpec((1, bc, bn), b_index)] * outs),
            out_specs=[pl.BlockSpec(
                (1, bk, bn), lambda ei, ki, ni, ci, *_: (ei, ki, ni))]
            * outs,
            scratch_shapes=[pltpu.VMEM((bk, bn), F32)] * outs,
        ),
        out_shape=[jax.ShapeDtypeStruct((e, kdim, n), dt)
                   for dt in out_dtypes],
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        name=name,
        interpret=pallas_common.interpret_mode(),
    )(*tables, a, *bs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped_ffn_counted(x, w_gate, w_up, w_down, counts, blocks, act,
                         bound):
    return _ffn_fwd(x, w_gate, w_up, w_down, counts, None, blocks, act,
                    bound)[0]


def _grouped_ffn_counted_fwd(x, w_gate, w_up, w_down, counts, blocks, act,
                             bound):
    y, g, u = _ffn_fwd(x, w_gate, w_up, w_down, counts, None, blocks, act,
                       bound)
    return y, (x, g, u, w_gate, w_up, w_down, counts)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _counted_bwd(blocks, act, bound, x, g, u, w_gate, w_up, w_down, counts,
                 dy):
    """The backward that multiplies the row blocks the forward
    multiplied: the six products of ``_grouped_ffn_bwd`` as four
    kernels keyed by the forward's counts and its row block.  ``dh =
    dy @ w_down^T`` stays float32 inside its kernel, whose epilogue
    writes ``h``, ``dg``, ``du`` (each rounded once to the stored
    dtype, which is what the MXU's default precision does to the
    einsums' float32 operands); ``dx`` is the float32 sum of its two
    products, rounded once; the weight gradients sum live row blocks
    only.  A block is live here if and only if it was live in the
    forward: a live block's rows past the count are multiplied as the
    einsums multiply them, and a skipped block's ``g``, ``u`` are the
    forward's zeros, which add nothing to any sum.  A ``jit`` of its
    own, so that the expert layers of one shape in a step trace and
    lower these kernels once and call them (XLA inlines the calls).
    ``bound`` = C: ``x``, ``g``, ``u``, ``dy`` are packed ``[1, R, .]``
    (``grouped_matmul``), the same row blocks where they lie there."""
    e, d, f = w_gate.shape
    _, block_n, block_k = blocks
    # the row block the forward's kernels skipped by
    bc = row_block(e, x.shape[1] if bound is None else bound, d, f,
                   x.dtype, blocks)
    cnt = counts.astype(jnp.int32)
    h, dg, du = _bwd_rows((dy,), (w_down,), cnt, (bc, block_n, block_k),
                          swiglu=(g, u), name="grouped_mm_bwd_dh", act=act,
                          bound=bound)
    dx = _bwd_rows((dg, du), (w_gate, w_up), cnt, (bc, block_n, block_k),
                   name="grouped_mm_bwd_dx", bound=bound)
    dwd, = _bwd_dw(h, (dy,), cnt, (bc, block_k, block_n),
                   (w_down.dtype,), name="grouped_mm_bwd_dw", bound=bound)
    dwg, dwu = _bwd_dw(x, (dg, du), cnt, (bc, block_k, block_n),
                       (w_gate.dtype, w_up.dtype),
                       name="grouped_mm_bwd_dw", bound=bound)
    return dx.astype(x.dtype), dwg, dwu, dwd, jnp.zeros_like(counts)


def _grouped_ffn_counted_bwd(blocks, act, bound, res, dy):
    return _counted_bwd(blocks, act, bound, *res, dy)


_grouped_ffn_counted.defvjp(_grouped_ffn_counted_fwd,
                            _grouped_ffn_counted_bwd)


def grouped_ffn(x, w_gate, w_up, w_down, *, counts=None,
                fmt: str | None = None, block_c: int | None = None,
                block_n: int | None = None, block_k: int | None = None,
                backward: str = "einsum", activation: str = "silu",
                bound: int | None = None):
    """The grouped gated expert FFN ``(act(x W_gate) * x W_up) W_down``:
    ``x`` [E, C, d] dispatch buffers, weights [E, d, h] / [E, h, d]
    stacked per expert -> [E, C, d].  ``activation`` is the gate's, a
    static word the model's card states (``ACTIVATIONS``: ``silu``, the
    SwiGLU, or ``relu``), the same in the forward, its recomputation
    and either backward (``gate_act``).

    ``counts`` enables the gather/scatter block skipping, ``fmt``
    selects the fused-quantization recipes (per-expert dynamic scales,
    straight-through backward).  Block shapes are a tuning-DB site
    (op ``grouped_ffn``); ``None`` consults, explicit ints win.

    ``backward``: ``"einsum"``, six XLA einsums over every slot, or
    ``"counted"``, kernels over the row blocks the forward multiplied
    (the caller's word on what its buffer is: a bound that is loose by
    design, so that most of its slots are empty; it needs ``counts``
    and has no quantized form).  ``bound`` = C: ``x`` is a packed
    buffer ``[1, R, d]`` -> ``[1, R, d]`` (``grouped_matmul``'s packed
    form: each expert's rows from a row-block boundary, at most C of
    them), which only the counted backward takes.  Each traced call leaves a
    mark ``moe.experts_bwd`` (``spans.mark``: ``path``, ``slots`` =
    the buffer's rows, E * C or R, ``row_block``) on the build's
    ``compile`` span under a tracer."""
    if fmt is not None and fmt not in _FORMATS:
        raise ValueError(f"grouped_ffn: unknown fmt {fmt!r}; one of "
                         f"{tuple(_FORMATS)} or None")
    if backward not in ("einsum", "counted"):
        raise ValueError(f"grouped_ffn: unknown backward {backward!r} "
                         f"(einsum | counted)")
    if backward == "counted" and (counts is None or fmt is not None):
        raise ValueError("grouped_ffn: the counted backward needs counts "
                         "and has no quantized form")
    if activation not in ACTIVATIONS:
        raise ValueError(f"grouped_ffn: unknown activation {activation!r} "
                         f"{ACTIVATIONS}")
    if bound is not None and backward != "counted":
        raise ValueError("grouped_ffn: a packed buffer (bound=) takes "
                         "the counted backward only")
    e, d = w_gate.shape[:2]
    c = x.shape[1] if bound is None else bound
    blocks = (block_c, block_n, block_k)
    if spans.is_enabled():
        spans.mark("moe.experts_bwd", path=backward,
                   slots=x.shape[0] * x.shape[1],
                   row_block=_fit_blocks(e, c, d, w_gate.shape[2], fmt,
                                         x.dtype, blocks)[0])
    counts_f = (jnp.full((e,), float(c), F32) if counts is None
                else counts.astype(F32))
    if backward == "counted":
        return _grouped_ffn_counted(x, w_gate, w_up, w_down, counts_f,
                                    blocks, activation, bound)
    return _grouped_ffn(x, w_gate, w_up, w_down, counts_f, fmt, blocks,
                        activation)
