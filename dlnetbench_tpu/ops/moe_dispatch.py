"""Decomposed MoE all-to-alls: dispatch/combine as ppermute chunk
loops interleaved with the expert FFN (ISSUE 15 tentpole leg b).

The EP block of ``models/spmd.py`` ends in two BLOCKING collectives —
``all_to_all`` to dispatch tokens to their experts' owners and a second
one to combine the results — with the whole expert FFN serialized
between them.  This module applies the PR-4 recipe
(``ops/collective_matmul.py``, Wang et al. ASPLOS'23) to the a2a pair:
break each all-to-all into PER-PEER blocks moved with ``lax.ppermute``
and interleave every block's hops with the expert compute that is
already data-complete:

    offset t (bidirectional: half the peers over each ring direction):
      dispatch hop   send my tokens for rank me+t, recv rank me-t's
      expert FFN     run MY experts over the landed block
      combine hop    return the results; recv my tokens' results
                     from rank me+t

Hop t+1's dispatch permute depends only on ``ein`` — never on hop t's
FFN — so XLA overlaps it with the in-flight expert compute; ``chunks``
subdivides each block's FFN along the capacity axis for finer
interleave grain.  Per-rank wire volume is EXACTLY the monolithic
pair's ((n-1)/n of the buffer, each direction), which is what keeps
the native-vs-SPMD a2a-bytes parity intact.

Backward overlaps the same way (custom VJP): the transpose of the
combine a2a is a dispatch-shaped loop carrying the result cotangents
out, the per-block FFN VJPs run as the blocks land (inputs re-used
from saved forward blocks; the FFN forward is recomputed in the VJP —
MoE-block remat), and the dispatch transpose carries the input
cotangents home.

``fake_compute``/``fake_comm`` are the A/B decomposition legs
(``collective_matmul`` conventions): identical wire schedule with the
FFN stubbed, or the full FLOPs with identity hops — which is what
makes the measured overlap-fraction metric
(``metrics/stats.overlap_fraction``) ride the MoE step for free.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dlnetbench_tpu.ops.collective_matmul import _bidir_sources, comm_stub
from dlnetbench_tpu.utils.jax_compat import axis_size as _axis_size

_F32 = jnp.float32


def _hop(x, axis_name: str, offset: int, fake_comm: bool):
    """One distance-``offset`` collective permute: rank i's data lands
    on rank ``(i + offset) % n`` (on a physical ring/torus the fabric
    routes it over |offset| hops — the same wire cost the monolithic
    a2a pays for that peer pair).  With ``fake_comm`` the permute is
    the identity (compute-only A/B leg)."""
    if fake_comm:
        return x
    n = _axis_size(axis_name)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def _ffn_block(xblk, wg, wu, wd, ffn, chunks: int, fake: bool):
    """One peer block's expert FFN ([eloc, C, d] -> [eloc, C, d] f32)
    through the caller's ``ffn(x, wg, wu, wd)``; ``chunks`` splits the
    capacity axis so each slice's MXU work can interleave with
    in-flight permutes at finer grain."""
    if fake:
        return comm_stub(xblk.shape, _F32, xblk, wg, wu, wd)
    c = xblk.shape[1]
    if chunks <= 1 or c < 2:
        return ffn(xblk, wg, wu, wd)
    bounds = [round(i * c / chunks) for i in range(chunks + 1)]
    parts = [ffn(lax.slice_in_dim(xblk, lo, hi, axis=1), wg, wu, wd)
             for lo, hi in zip(bounds, bounds[1:]) if hi > lo]
    return jnp.concatenate(parts, axis=1)


def _offsets(n: int):
    """Bidirectional offset schedule: (offset, direction) pairs — the
    first ``down`` peers arrive over the +1 direction, the rest over
    -1 (``collective_matmul._bidir_sources``)."""
    down, up = _bidir_sources(n)
    out = []
    for t in range(1, max(down, up) + 1):
        if t <= down:
            out.append((t, +1))
        if t <= up:
            out.append((t, -1))
    return out


def _blk(buf, idx, eloc: int):
    return lax.dynamic_slice_in_dim(buf, idx * eloc, eloc, axis=0)


def _put(buf, val, idx, eloc: int):
    return lax.dynamic_update_slice_in_dim(buf, val, idx * eloc, axis=0)


def _impl(ein, wg, wu, wd, axis_name, expert_ffn, chunks, fk_compute,
          fk_comm, collect_recv: bool):
    """The fused loop.  Returns ``(out, recv)``: ``out`` [E, C, d] f32
    in the monolithic combine layout (block r = rank r's experts'
    results for my tokens), ``recv`` the received dispatch blocks
    keyed by SOURCE rank (saved as the VJP residual when
    ``collect_recv``, else None)."""
    n = _axis_size(axis_name)
    ffn = partial(_ffn_block, wg=wg, wu=wu, wd=wd, ffn=expert_ffn,
                  chunks=chunks, fake=fk_compute)
    if n == 1:
        out = ffn(ein)
        return out, (ein if collect_recv else None)
    me = lax.axis_index(axis_name)
    e, c, d = ein.shape
    eloc = e // n
    out = jnp.zeros((e, c, d), _F32)
    recv = jnp.zeros_like(ein) if collect_recv else None

    # own block first: my experts' share of my own tokens needs no wire
    own = _blk(ein, me, eloc)
    out = _put(out, ffn(own), me, eloc)
    if collect_recv:
        recv = _put(recv, own, me, eloc)
    for t, direction in _offsets(n):
        src = (me - direction * t) % n     # whose tokens land here
        dst = (me + direction * t) % n     # whose experts get mine
        # dispatch hop: depends only on ein — XLA overlaps it with the
        # previous offsets' FFNs still in flight
        landed = _hop(_blk(ein, dst, eloc), axis_name, direction * t,
                      fk_comm)
        if collect_recv:
            recv = _put(recv, landed, src, eloc)
        res = ffn(landed)
        # combine hop: the result returns to its tokens' owner; what
        # arrives is MY tokens' result from rank dst
        back = _hop(res, axis_name, -direction * t, fk_comm)
        out = _put(out, back, dst, eloc)
    return out, recv


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _a2a_ffn(ein, wg, wu, wd, axis_name, expert_ffn, chunks, fk_compute,
             fk_comm):
    out, _ = _impl(ein, wg, wu, wd, axis_name, expert_ffn, chunks,
                   fk_compute, fk_comm, False)
    return out


def _a2a_ffn_fwd(ein, wg, wu, wd, axis_name, expert_ffn, chunks,
                 fk_compute, fk_comm):
    out, recv = _impl(ein, wg, wu, wd, axis_name, expert_ffn, chunks,
                      fk_compute, fk_comm, True)
    return out, (recv, wg, wu, wd)


def _a2a_ffn_bwd(axis_name, expert_ffn, chunks, fk_compute, fk_comm, res,
                 dout):
    """The transposed loop: combine^T carries result cotangents to the
    rank that computed them, the per-block FFN VJP runs as they land
    (forward recomputed from the saved received blocks — MoE remat),
    dispatch^T carries the input cotangents home.  Same wire volume,
    same overlap structure, same fake-leg semantics as forward."""
    recv, wg, wu, wd = res
    n = _axis_size(axis_name)

    def block_vjp(xblk, dblk):
        if fk_compute:
            dx = comm_stub(xblk.shape, xblk.dtype, xblk, dblk)
            zg = comm_stub(wg.shape, _F32, xblk, dblk)
            zu = comm_stub(wu.shape, _F32, xblk, dblk)
            zd = comm_stub(wd.shape, _F32, xblk, dblk)
            return dx, zg, zu, zd
        _, pull = jax.vjp(
            lambda b, a, u_, d_: _ffn_block(b, a, u_, d_, expert_ffn,
                                            chunks, False),
            xblk, wg, wu, wd)
        return pull(dblk.astype(_F32))

    if n == 1:
        dx, dwg, dwu, dwd = block_vjp(recv, dout)
        return (dx.astype(recv.dtype), dwg.astype(wg.dtype),
                dwu.astype(wu.dtype), dwd.astype(wd.dtype))

    me = lax.axis_index(axis_name)
    eloc = recv.shape[0] // n
    d_ein = jnp.zeros_like(recv)

    dx, dwg, dwu, dwd = block_vjp(_blk(recv, me, eloc),
                                  _blk(dout, me, eloc))
    d_ein = _put(d_ein, dx.astype(recv.dtype), me, eloc)
    for t, direction in _offsets(n):
        src = (me - direction * t) % n
        dst = (me + direction * t) % n
        # combine^T: my cotangent for rank dst's computation travels
        # out; rank src's cotangent for MY computation lands
        d_res = _hop(_blk(dout, dst, eloc), axis_name, direction * t,
                     fk_comm)
        dx, g_, u_, w_ = block_vjp(_blk(recv, src, eloc), d_res)
        dwg, dwu, dwd = dwg + g_, dwu + u_, dwd + w_
        # dispatch^T: the input cotangent returns to its token owner
        back = _hop(dx.astype(recv.dtype), axis_name, -direction * t,
                    fk_comm)
        d_ein = _put(d_ein, back, dst, eloc)
    return (d_ein, dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype))


_a2a_ffn.defvjp(_a2a_ffn_fwd, _a2a_ffn_bwd)


def a2a_expert_ffn(ein, w_gate, w_up, w_down, axis_name: str,
                   expert_ffn, *, chunks: int = 1,
                   fake_compute: bool = False, fake_comm: bool = False):
    """``combine_a2a(expert_ffn(dispatch_a2a(ein)))`` as ONE fused
    ppermute chunk loop (call inside ``shard_map`` over ``axis_name``).

    ``ein``: [E, C, d] — this rank's per-expert dispatch buffers over
    the GLOBAL expert set; experts are sharded over the axis (E must
    divide by its size) and the local expert weights are [E/n, ...].
    Returns the combined [E, C, d] f32 buffer in the monolithic
    layout.  Backward overlaps too (custom VJP).  ``expert_ffn(x, wg,
    wu, wd)`` is the caller's expert FFN on one peer block ([E/n, c, d]
    -> [E/n, c, d] f32), a hashable callable: the model's dispatch
    point with its switches bound (models/spmd._moe_block)."""
    if w_gate.ndim != 3:
        raise ValueError(f"a2a_expert_ffn: expert weights must be "
                         f"[E_local, d, h], got {w_gate.shape}")
    return _a2a_ffn(ein, w_gate, w_up, w_down, axis_name, expert_ffn,
                    int(chunks), bool(fake_compute), bool(fake_comm))
