"""Attention over the key blocks each query chooses for itself
(InfLLM-V2, arXiv:2509.24663, as MiniCPM4 / MiniCPM-SALA run it): two
stages, both here.

``select_blocks(q, k, sizes)`` -> ``int32 [B, S, Hkv, topk]``.  With
``G = Hq / Hkv`` query heads a key/value head, ``dh`` lanes a head and
``SparseSizes`` ``(kernel_size, kernel_stride, block_size, topk,
window_size, init_blocks, dense_len)``, for token ``t`` and group ``g``:

  (a) compressed keys ``Kc[g, j] = mean(k[g, stride j : stride j +
      kernel])``;
  (b) ``p[t, h, j] = softmax_j(q[t, h] . Kc[g, j] / sqrt(dh))`` over the
      ``j`` whose window ends at or before ``t`` (float32; all zero
      where there is none);
  (c) ``P[t, g, j]`` its sum over the group's heads;
  (d) block ``b`` is keys ``[block b, block (b + 1))``, ``score[t, g,
      b]`` the largest ``P`` of ``j = r b - 1 ... r b + r - 1`` with
      ``r = block / stride`` (a max-pool of ``r + 1``, stride ``r``,
      padding 1);
  (e) the first ``init_blocks`` blocks and the ``window_size / block``
      blocks ending at ``t``'s own score +inf;
  (f) the selection is the ``topk`` best of blocks ``0 ... t // block``,
      best first, ties to the lower index, all of them where fewer
      exist, the list padded with ``-1``.  A block's place in the list
      is a count, ``#{j : score[j] > score[b], or equal with j < b}``,
      and the list's entry ``p`` the block whose place is ``p``
      (``_best_blocks``): ``lax.top_k``'s answer to the integer, with
      no sort.

The selection is integers: ``q`` and ``k`` enter it under
``stop_gradient``.  The scores run in blocks of query rows
(``lax.map``), so ``p`` ``[S, Hq, S / stride]`` never lies whole in HBM.

``block_sparse_attention(q, k, v, plan_visits(blocks, block, dtype))``
-> ``[B, S, Hq, dv]``: ``softmax`` over the keys ``s <= t`` of token
``t``'s own blocks of ``q[t, h] . k[g, s] / sqrt(dh)``, times ``v``; one
list serves the group's ``G`` heads.  A key outside a token's own
selection adds nothing to that token, in the forward or in any of the
three gradients.

How it runs.  The top is counted because a ``top_k`` of 256 lowers, on
this chip, to a key/payload sort of every row: 1.56 ms a row block of
512, 32 of them a step at 16k, 50 of the selection's 53 ms, where the
two counting passes take 5.  The all-pairs compare ``[rows, Hkv, nblk,
nblk]`` fuses into its sum and the places' compare ``[rows, Hkv, topk,
nblk]`` into its max: neither is written to HBM.
``plan_visits`` makes, ONCE a call, all that the kernels
read of the lists (``Visits``): a membership matrix ``[B, Hkv, S, S /
block]`` (0/1 in the inputs' dtype, 8 MB a group at 16k) and, from it,
the tiles ``(Tq tokens) x (Tk keys)`` that hold any selected (token,
block) pair (``tile_visits``), listed by row tile and by key tile.  The
three kernels (``sparse_fwd``, ``sparse_bwd_dq``, ``sparse_bwd_dkv``)
walk a row of tiles through those lists, scalar-prefetched DEVICE data
made anew every call: grid ``(B, Hkv, row tiles, S / Tk)``, step ``r``
of a row names its ``r``-th visited tile, the steps past its count do
nothing (no MXU work, and the index maps revisit the last tile, which
copies nothing).
Inside a visited tile the mask of a token row is its membership row
expanded to keys (one small matrix product with a 0/1 matrix built from
iotas) and the causal test; the group's ``G`` heads are the rows that
share it: a grid step loops over them with the one mask, each head's
queries ``[Tq, dh]`` against the tile's keys.  ``dk`` / ``dv`` walk the
transposed lists (which row tiles chose this key tile) and sum the
group's heads in VMEM, so they leave at ``Hkv`` heads.  A union of a
row tile's lists is what is visited: what ``tile_visits`` counts beyond
the selected pairs is work the mask throws away (the step's counters
``sparse.selected`` / ``sparse.visited``).

The forward's output and lse wear ``flash_attention.KEPT_NAMES``, the
lists and their ``Visits`` ``BLOCKS_NAME``: a ``jax.checkpoint`` whose
policy saves those names runs neither the selection, nor the plan, nor
the forward kernel again.
Off the TPU the kernels run in interpret mode.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.ops.flash_attention import KEPT_NAMES

_F32 = pallas_common.F32
_NEG_INF = -1e30             # finite "-inf": keeps masked rows NaN-free
_LOG2E = 1.4426950408889634  # base-2 online softmax, as flash_attention
_VMEM_LIMIT_MB = 64
_BLOCK_Q = (256, 128, 64, 32, 16, 8)    # a tile's tokens
_BLOCK_K = (512, 256, 128)              # a tile's keys
_SELECT_ROWS = 512      # query rows of the selection's scores at a time
# the name of a layer's selection, and of what ``plan_visits`` makes of
# it, under a checkpoint (beside KEPT_NAMES)
BLOCKS_NAME = "attn_blocks"
NONE = -1               # a list's padding: no block


class SparseSizes(NamedTuple):
    """The selection's seven sizes (MiniCPM4's ``sparse_config``)."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    dense_len: int = 8192

    def check(self, s: int) -> None:
        if (self.kernel_size % self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.window_size % self.block_size
                or self.window_size < self.block_size
                or s % self.block_size or s < self.kernel_size):
            # a window of a block at least: every token's list holds its
            # own block, so no row tile and no key tile goes unvisited
            # (the kernels write their output at a row's last visit)
            raise ValueError(
                f"sparse attention: {self} over {s} tokens needs the "
                f"stride dividing kernel and block, the block dividing "
                f"window (one block at least) and sequence")


# ------------------------------------------------------ the selection

def _compressed_keys(k, sizes: SparseSizes):
    """[B, S, Hkv, dh] -> (mean-pooled keys [B, S / stride, Hkv, dh] in
    ``k``'s dtype; entry ``j`` is the window from ``stride j``, the
    entries whose window passes the end are zeros)."""
    b, s, hkv, dh = k.shape
    st, m = sizes.kernel_stride, sizes.kernel_size // sizes.kernel_stride
    part = k.astype(_F32).reshape(b, s // st, st, hkv, dh).sum(2)
    n = s // st - m + 1
    pooled = sum(part[:, i:i + n] for i in range(m)) / sizes.kernel_size
    return jnp.pad(pooled, ((0, 0), (0, m - 1), (0, 0), (0, 0))
                   ).astype(k.dtype)


def _block_scores(q_rows, t_rows, kc, sizes: SparseSizes):
    """Steps (b)-(e) for a block of query rows: q_rows [B, R, Hkv, G,
    dh], their positions t_rows [R], kc [B, J, Hkv, dh] ->
    [B, R, Hkv, S / block] float32, -inf where a block is not visible."""
    dh, j_all = q_rows.shape[-1], kc.shape[1]
    ratio = sizes.block_size // sizes.kernel_stride
    nblk = j_all // ratio
    sc = jnp.einsum("brgqd,bjgd->brgqj", q_rows, kc,
                    preferred_element_type=_F32) / math.sqrt(dh)
    ends = jnp.arange(j_all) * sizes.kernel_stride + sizes.kernel_size - 1
    valid = (ends[None, :] <= t_rows[:, None])[None, :, None, None, :]
    sc = jnp.where(valid, sc, -jnp.inf)
    top = jnp.max(sc, -1, keepdims=True)
    e = jnp.where(valid, jnp.exp(sc - jnp.where(top > -jnp.inf, top, 0.0)),
                  0.0)
    den = jnp.sum(e, -1, keepdims=True)
    p = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=3)   # [B,R,Hkv,J]
    # max-pool of ratio + 1, stride ratio, padding 1
    pp = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (1, 0)))
    inner = pp[..., :j_all].reshape(*p.shape[:-1], nblk, ratio).max(-1)
    score = jnp.maximum(inner, pp[..., ratio::ratio])
    blk = jnp.arange(nblk)[None, :]
    own = (t_rows // sizes.block_size)[:, None]
    forced = (blk < sizes.init_blocks) | (
        blk > own - sizes.window_size // sizes.block_size)
    score = jnp.where(forced[None, :, None, :], jnp.inf, score)
    return jnp.where((blk <= own)[None, :, None, :], score, -jnp.inf)


def _best_blocks(score, take: int):
    """[..., nblk] float32 scores -> int32 [..., take]: the blocks of the
    ``take`` best scores, best first, ties to the lower index, ``NONE``
    where the score at that place is -inf.  ``lax.top_k``'s lists without
    its sort: a block's place is the count of the blocks ahead of it."""
    nblk = score.shape[-1]
    iota = jax.lax.broadcasted_iota
    theirs, ours = score[..., :, None], score[..., None, :]
    lower = iota(jnp.int32, (nblk, nblk), 0) < iota(jnp.int32, (nblk, nblk), 1)
    ahead = jnp.where(lower, theirs >= ours, theirs > ours)
    place = jnp.sum(ahead, -2, dtype=jnp.int32)             # [..., nblk]
    here = ((place[..., None, :] == iota(jnp.int32, (take, nblk), 0))
            & (ours > -jnp.inf))
    return jnp.max(jnp.where(here, iota(jnp.int32, (take, nblk), 1), NONE),
                   -1)


def select_blocks(q, k, sizes: SparseSizes):
    """``int32 [B, S, Hkv, topk]``: each token's and key/value head's
    blocks, best first, ``NONE`` where fewer exist (the module's
    docstring, steps (a)-(f))."""
    sizes = SparseSizes(*sizes)
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    sizes.check(s)
    q, k = jax.lax.stop_gradient((q, k))
    kc = _compressed_keys(k, sizes)
    rows = math.gcd(s, _SELECT_ROWS)
    take = min(sizes.topk, s // sizes.block_size)
    qr = q.reshape(b, s // rows, rows, hkv, hq // hkv, dh).swapaxes(0, 1)
    tr = jnp.arange(s).reshape(s // rows, rows)

    def one(xs):
        return _best_blocks(_block_scores(*xs, kc, sizes), take)
    out = jax.lax.map(one, (qr, tr)).swapaxes(0, 1).reshape(b, s, hkv, take)
    if take < sizes.topk:
        out = jnp.pad(out, ((0, 0),) * 3 + ((0, sizes.topk - take),),
                      constant_values=NONE)
    return checkpoint_name(out, BLOCKS_NAME)


# ------------------------------------------------- lists to tile visits

def membership(blocks, nblk: int, dtype):
    """[B, S, Hkv, topk] lists -> [B, Hkv, S, nblk]: 1 where the token
    chose the block."""
    hit = (blocks[..., None] == jnp.arange(nblk, dtype=blocks.dtype)).any(-2)
    return hit.swapaxes(1, 2).astype(dtype)


def tile_plan(s: int, block_size: int) -> tuple:
    """``(Tq, Tk)``: a tile's tokens and keys, the largest of the
    candidates that divide the sequence (``Tk`` a whole number of
    blocks)."""
    tq = next((c for c in _BLOCK_Q if s % c == 0), None)
    tk = next((c for c in _BLOCK_K if s % c == 0 and c % block_size == 0), s)
    if not tq or s % tk or tk % block_size:
        raise ValueError(f"sparse attention: {s} tokens do not tile into "
                         f"({tq}, {tk}) with blocks of {block_size}")
    return tq, tk


def tile_visits(member, tq: int, tk_blocks: int):
    """[B, Hkv, S, nblk] membership -> bool [B, Hkv, S / Tq, nblk /
    tk_blocks]: the tiles that hold a selected (token, block) pair."""
    b, hkv, s, nblk = member.shape
    m = member.reshape(b, hkv, s // tq, tq, nblk // tk_blocks, tk_blocks)
    return (m > 0).any((3, 5))


def _visit_lists(visit):
    """bool [..., rows, cols] -> (the visited columns of each row,
    ascending, then the others; each row's count), int32."""
    order = jnp.argsort(~visit, axis=-1, stable=True).astype(jnp.int32)
    return order, visit.sum(-1).astype(jnp.int32)


class Visits(NamedTuple):
    """What the three kernels read of a call's lists (``plan_visits``).
    The tile's sizes are its shapes': ``Tq = S / rows.shape[2]``, ``Tk =
    S / rows.shape[3]``, a block ``S / member.shape[3]`` keys."""
    member: jax.Array       # [B, Hkv, S, S / block] 0/1, the inputs' dtype
    rows: jax.Array         # int32 [B, Hkv, S / Tq, S / Tk]: a row tile's
    row_counts: jax.Array   # visited key tiles first; how many [.., S / Tq]
    cols: jax.Array         # int32 [B, Hkv, S / Tk, S / Tq]: a key tile's
    col_counts: jax.Array   # visiting row tiles first; how many

    @property
    def tiles(self) -> tuple:
        """``(Tq, Tk, block_size)``."""
        s = self.member.shape[2]
        return (s // self.rows.shape[2], s // self.rows.shape[3],
                s // self.member.shape[3])


def plan_visits(blocks, block_size: int, dtype) -> Visits:
    """int32 [B, S, Hkv, n] lists (``select_blocks``'s, ``NONE`` padded;
    every token's must hold its own block, as the selection's window
    sees to) -> their ``Visits``, each array under ``BLOCKS_NAME``."""
    s = blocks.shape[1]
    tq, tk = tile_plan(s, block_size)
    member = membership(blocks, s // block_size, dtype)
    visit = tile_visits(member, tq, tk // block_size)
    return Visits(*(checkpoint_name(x, BLOCKS_NAME) for x in (
        member, *_visit_lists(visit),
        *_visit_lists(visit.swapaxes(-1, -2)))))


def counters(blocks, visits: Visits) -> dict:
    """A call's two counters, in (token, key/value head, block) pairs:
    ``selected`` the pairs the lists hold, ``visited`` the pairs of the
    tiles the kernels walk."""
    tq, tk, block_size = visits.tiles
    return {"selected": jnp.sum(blocks >= 0),
            "visited": jnp.sum(visits.row_counts) * (tq * tk // block_size)}


# ------------------------------------------------------------ kernels

def _compiler_params():
    return pallas_common.compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_mb=_VMEM_LIMIT_MB)


def _tile_mask(mem, qi, kb, tq: int, tk: int, block_size: int):
    """bool [Tq, Tk] of row tile ``qi`` against key tile ``kb``: the
    token chose the key's block (``mem`` [Tq, nblk] times a 0/1 matrix
    that repeats a block's column over its keys) and the key is not
    after the token."""
    nblk = mem.shape[1]
    key = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (nblk, tk), 1)
    first = block_size * jax.lax.broadcasted_iota(jnp.int32, (nblk, tk), 0)
    spread = ((key >= first) & (key < first + block_size)).astype(mem.dtype)
    chosen = jax.lax.dot_general(mem, spread, (((1,), (0,)), ((), ())),
                                 preferred_element_type=_F32)
    tok = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    keys = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return (chosen > 0.5) & (tok >= keys)


def _head(ref, h: int, d: int):
    return ref[0, :, h * d:(h + 1) * d]


def _fwd_kernel(lst_ref, cnt_ref, q_ref, k_ref, v_ref, mem_ref, o_ref,
                lse_ref, acc_ref, m_ref, l_ref, *, scale: float, group: int,
                tq: int, tk: int, block_size: int):
    bi, g, i, r = (pl.program_id(a) for a in range(4))
    nk = pl.num_programs(3)
    row = (bi * pl.num_programs(1) + g) * pl.num_programs(2) + i
    n = cnt_ref[row]
    dh, dv = q_ref.shape[2] // group, o_ref.shape[2] // group

    @pl.when(r == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(r < n)
    def _step():
        mask = _tile_mask(mem_ref[0, 0], i, lst_ref[row * nk + r], tq, tk,
                          block_size)
        k, v = k_ref[0], v_ref[0]
        for h in range(group):      # the rows that share the mask
            q = (_head(q_ref, h, dh).astype(_F32) * (scale * _LOG2E)
                 ).astype(k.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=_F32)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp2(m_prev - m_new)
            # a row with no chosen key in this tile adds nothing
            p = jnp.where(mask, jnp.exp2(s - m_new), 0.0)
            l_new = l_ref[h][:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=_F32)
            cols = slice(h * dv, (h + 1) * dv)
            acc_ref[:, cols] = acc_ref[:, cols] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(r == n - 1)
    def _emit():
        for h in range(group):
            l = l_ref[h][:, :1]
            cols = slice(h * dv, (h + 1) * dv)
            o_ref[0, :, cols] = (acc_ref[:, cols] / l).astype(o_ref.dtype)
            lse_ref[0, 0, :, h:h + 1] = (m_ref[h][:, :1] + jnp.log2(l)) \
                / _LOG2E


def _probs(q_ref, k, lse_ref, mask, h: int, dh: int, scale: float):
    """A head's (queries [Tq, dh], probabilities [Tq, Tk] float32, zero
    outside the mask) of a visited tile, from the kept lse."""
    q = _head(q_ref, h, dh)
    s = jax.lax.dot_general((q.astype(_F32) * scale).astype(k.dtype), k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32)
    return q, jnp.where(mask, jnp.exp(s - lse_ref[0, 0, :, h:h + 1]), 0.0)


def _dq_kernel(lst_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               dcap_ref, mem_ref, dq_ref, acc_ref, *, scale: float,
               group: int, tq: int, tk: int, block_size: int):
    bi, g, i, r = (pl.program_id(a) for a in range(4))
    nk = pl.num_programs(3)
    row = (bi * pl.num_programs(1) + g) * pl.num_programs(2) + i
    n = cnt_ref[row]
    dh, dv = q_ref.shape[2] // group, do_ref.shape[2] // group

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(r < n)
    def _step():
        mask = _tile_mask(mem_ref[0, 0], i, lst_ref[row * nk + r], tq, tk,
                          block_size)
        k, v = k_ref[0], v_ref[0]
        for h in range(group):
            _, p = _probs(q_ref, k, lse_ref, mask, h, dh, scale)
            dp = jax.lax.dot_general(_head(do_ref, h, dv), v,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=_F32)
            ds = (p * (dp - dcap_ref[0, 0, :, h:h + 1]) * scale
                  ).astype(k.dtype)
            cols = slice(h * dh, (h + 1) * dh)
            acc_ref[:, cols] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=_F32)

    @pl.when(r == n - 1)
    def _emit():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(lst_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dcap_ref, mem_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale: float, group: int, tq: int, tk: int,
                block_size: int):
    bi, g, j, r = (pl.program_id(a) for a in range(4))
    nq = pl.num_programs(3)
    row = (bi * pl.num_programs(1) + g) * pl.num_programs(2) + j
    n = cnt_ref[row]
    dh, dv = q_ref.shape[2] // group, do_ref.shape[2] // group

    @pl.when(r == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(r < n)
    def _step():
        mask = _tile_mask(mem_ref[0, 0], lst_ref[row * nq + r], j, tq, tk,
                          block_size)
        k, v = k_ref[0], v_ref[0]
        for h in range(group):      # the group's heads sum here
            q, p = _probs(q_ref, k, lse_ref, mask, h, dh, scale)
            do = _head(do_ref, h, dv)
            dv_acc[...] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=_F32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=_F32)
            ds = (p * (dp - dcap_ref[0, 0, :, h:h + 1]) * scale
                  ).astype(q.dtype)
            dk_acc[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=_F32)

    @pl.when(r == n - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# -------------------------------------------------------------- calls

def _flat(x):
    """[B, S, H, d] -> [B, S, H * d]: a head is a slice of the lanes."""
    return x.reshape(*x.shape[:2], -1)


def _group_cols(x, hkv: int):
    """[B, S, Hq] -> [B, Hkv, S, G]: a token a sublane, a head of the
    group a lane."""
    b, s, hq = x.shape
    return x.reshape(b, s, hkv, hq // hkv).swapaxes(1, 2)


def _mark_grid(kernel: str, grid, tq: int, tk: int, by_rows: bool) -> None:
    """A sparse kernel site on the build's ``compile`` span: ``steps``
    the grid's product, ``live`` the steps at or under the diagonal, the
    most a selection can visit; how many it does is the run's data
    (``counters``)."""
    rows, cols = grid[2], grid[3]
    if not by_rows:                     # rows are key tiles
        under = sum(cols - (j * tk) // tq for j in range(rows))
    else:
        under = sum(min(cols, ((i + 1) * tq - 1) // tk + 1)
                    for i in range(rows))
    spans.mark("sparse.grid", kernel=kernel, steps=math.prod(grid),
               live=grid[0] * grid[1] * under)


class _Plan(NamedTuple):
    tq: int
    tk: int
    block_size: int
    group: int
    scale: float

    def statics(self) -> dict:
        return {"scale": self.scale, "group": self.group, "tq": self.tq,
                "tk": self.tk, "block_size": self.block_size}


def _row_specs(plan: _Plan, grid, nblk: int, by_rows: bool):
    """Block specs of a kernel whose grid rows are row tiles
    (``by_rows``: the minor axis names a visited key tile) or key tiles
    (the minor axis names a visiting row tile)."""
    tq, tk, g = plan.tq, plan.tk, plan.group

    def at(a, r, lst, cnt):
        """The tile that step ``r`` of grid row ``a`` names; the steps
        past the row's count revisit its last, which copies nothing."""
        bi, gi, i = a
        row = (bi * grid[1] + gi) * grid[2] + i
        return lst[row * grid[3] + jnp.minimum(r, cnt[row] - 1)]

    def qi(bi, gi, i, r, lst, cnt):
        return i if by_rows else at((bi, gi, i), r, lst, cnt)

    def kj(bi, gi, i, r, lst, cnt):
        return at((bi, gi, i), r, lst, cnt) if by_rows else i

    def tok(width):     # [B, S, Hq * width]: the group's heads' lanes
        return pl.BlockSpec((1, tq, g * width),
                            lambda *a: (a[0], qi(*a), a[1]))

    def key(width):     # [B, S, Hkv * width]
        return pl.BlockSpec((1, tk, width),
                            lambda *a: (a[0], kj(*a), a[1]))
    col = pl.BlockSpec((1, 1, tq, g), lambda *a: (a[0], a[1], qi(*a), 0))
    mem = pl.BlockSpec((1, 1, tq, nblk),
                       lambda *a: (a[0], a[1], qi(*a), 0))
    return tok, key, col, mem


def _fwd_call(q, k, v, visits: Visits, plan: _Plan):
    b, s, hq, dh = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    grid = (b, hkv, s // plan.tq, s // plan.tk)
    _mark_grid("sparse_fwd", grid, plan.tq, plan.tk, True)
    member = visits.member
    tok, key, col, mem = _row_specs(plan, grid, member.shape[-1], True)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **plan.statics()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[tok(dh), key(dh), key(dv), mem],
            out_specs=[tok(dv), col],
            scratch_shapes=[
                pltpu.VMEM((plan.tq, plan.group * dv), _F32),
                pltpu.VMEM((plan.group, plan.tq, pallas_common.LANES), _F32),
                pltpu.VMEM((plan.group, plan.tq, pallas_common.LANES), _F32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, s, hq * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, hkv, s, plan.group), _F32)],
        compiler_params=_compiler_params(),
        name="sparse_fwd",
        interpret=pallas_common.interpret_mode(),
    )(visits.rows.reshape(-1), visits.row_counts.reshape(-1), _flat(q),
      _flat(k), _flat(v), member)
    return out.reshape(b, s, hq, dv), lse


def _bwd_calls(q, k, v, visits: Visits, lse, dcap, do, plan: _Plan):
    b, s, hq, dh = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    nblk = visits.member.shape[-1]
    args = (_flat(q), _flat(k), _flat(v), _flat(do), lse, dcap,
            visits.member)
    grid = (b, hkv, s // plan.tq, s // plan.tk)
    _mark_grid("sparse_bwd_dq", grid, plan.tq, plan.tk, True)
    tok, key, col, mem = _row_specs(plan, grid, nblk, True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **plan.statics()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[tok(dh), key(dh), key(dv), tok(dv), col, col, mem],
            out_specs=tok(dh),
            scratch_shapes=[pltpu.VMEM((plan.tq, plan.group * dh), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, s, hq * dh), q.dtype),
        compiler_params=_compiler_params(),
        name="sparse_bwd_dq",
        interpret=pallas_common.interpret_mode(),
    )(visits.rows.reshape(-1), visits.row_counts.reshape(-1), *args)
    grid_t = (b, hkv, s // plan.tk, s // plan.tq)
    _mark_grid("sparse_bwd_dkv", grid_t, plan.tq, plan.tk, False)
    tok, key, col, mem = _row_specs(plan, grid_t, nblk, False)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, **plan.statics()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid_t,
            in_specs=[tok(dh), key(dh), key(dv), tok(dv), col, col, mem],
            out_specs=[key(dh), key(dv)],
            scratch_shapes=[pltpu.VMEM((plan.tk, dh), _F32),
                            pltpu.VMEM((plan.tk, dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, s, hkv * dh), k.dtype),
                   jax.ShapeDtypeStruct((b, s, hkv * dv), v.dtype)],
        compiler_params=_compiler_params(),
        name="sparse_bwd_dkv",
        interpret=pallas_common.interpret_mode(),
    )(visits.cols.reshape(-1), visits.col_counts.reshape(-1), *args)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape))


# ------------------------------------------------------------ public op

def _plan(q, k, visits: Visits) -> _Plan:
    hq, hkv = q.shape[2], k.shape[2]
    if hq % hkv:
        raise ValueError(f"sparse attention: {hq} query heads over {hkv}")
    return _Plan(*visits.tiles, hq // hkv, 1.0 / math.sqrt(q.shape[3]))


@jax.custom_vjp
def block_sparse_attention(q, k, v, visits: Visits):
    """q [B, S, Hq, dh], k [B, S, Hkv, dh], v [B, S, Hkv, dv], visits
    ``plan_visits``'s of the lists -> [B, S, Hq, dv]; see the module's
    docstring."""
    return _vjp_fwd(q, k, v, visits)[0]


def _vjp_fwd(q, k, v, visits):
    out, lse = (checkpoint_name(x, name) for x, name in zip(
        _fwd_call(q, k, v, visits, _plan(q, k, visits)), KEPT_NAMES))
    return out, (q, k, v, visits, out, lse)


def _vjp_bwd(res, do):
    q, k, v, visits, out, lse = res
    with spans.scope("attn"), spans.scope("attn.sparse"):
        do = do.astype(q.dtype)
        dcap = _group_cols(jnp.sum(do.astype(_F32) * out.astype(_F32), -1),
                           k.shape[2])
        grads = _bwd_calls(q, k, v, visits, lse, dcap, do,
                           _plan(q, k, visits))
    # the lists are integers and the membership a function of them
    return (*grads, Visits(jnp.zeros_like(visits.member), *(
        np.zeros(x.shape, jax.dtypes.float0) for x in visits[1:])))


block_sparse_attention.defvjp(_vjp_fwd, _vjp_bwd)


def reference_attention(q, k, v, blocks, block_size: int = 64):
    """Dense masked softmax over each token's own blocks, float32: what
    the tests compare with."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    member = membership(blocks, s // block_size, _F32)      # [B,Hkv,S,nb]
    allowed = jnp.repeat(member, block_size, axis=-1) > 0
    allowed &= jnp.tril(jnp.ones((s, s), bool))
    allowed = jnp.repeat(allowed, hq // hkv, axis=1)        # [B,Hq,S,S]
    kf, vf = (jnp.repeat(t.astype(_F32), hq // hkv, axis=2) for t in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(_F32), kf,
                    precision="highest") / math.sqrt(dh)
    pr = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr, vf,
                      precision="highest").astype(v.dtype)
