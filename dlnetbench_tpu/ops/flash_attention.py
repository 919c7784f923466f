"""Blockwise (flash) attention as a Pallas TPU kernel, with custom VJP.

Design (TPU-first, not a port — the reference has no kernels at all):

* The S x S score matrix never exists in HBM.  The grid walks
  (batch, q_head, q_block, kv_block) with the kv_block axis innermost;
  VMEM scratch carries the online-softmax state (running max ``m``,
  running sum ``l``, fp32 accumulator) across kv steps, and the output
  block is written once, on the last kv step for that q row block.
* Causality is exploited at block granularity: kv blocks entirely above
  the diagonal are skipped with ``pl.when`` (no MXU work issued) and their
  HBM->VMEM DMA is elided by clamping the BlockSpec index maps to the last
  working block (same-index revisits copy nothing); straddling blocks are
  masked in-register.
* GQA maps q head ``h`` to kv head ``h // group`` purely in the
  ``BlockSpec`` index maps — no materialized KV broadcast.
* Backward is the standard flash-attention recomputation from the
  saved logsumexp, in the dk/dv kernel (grid minor axis = q blocks):
  dk/dv are produced per q-head and group-summed by the wrapper, which
  keeps every output block written by exactly one grid lane.  The same
  kernel accumulates dq from the ``ds`` it holds, one query head's whole
  dq in a float32 VMEM scratch that leaves through an output block of
  the head's size, so the scores, exp and dP are computed once
  (``_dq_resident``).  Where a head's dq does not fit its share of the
  VMEM limit, a second kernel (grid minor axis = kv blocks) recomputes
  them for dq alone.
* Head dims that are not lane-aligned (e.g. gpt2's 64) are zero-padded
  to 128 in the wrapper; padding columns contribute nothing to scores and
  are sliced off the outputs, so numerics are unchanged.
* The dense kernels take a query/key width that differs from the value
  width (latent attention: scores over 192, values of 128): each is
  padded to its own multiple of the lane tile (``_padded``), the scores
  contract the one and ``P V`` the other, so the values are never
  widened to the keys' width.  On the MXU a contraction runs in passes
  of 128, so 192 padded to 256 costs the two passes that 192 costs.
  The splash kernels keep one width.
* The block-sparse (splash) kernels further down are the same bodies
  under a host-built ``BlockMask``.  Their grid is (batch, q_head,
  row block, step of the row's visit range): the minor axis is as long
  as the mask's widest visit range, not ``S // block``, and step ``r``
  of row ``i`` names block ``first[i] + r``.  A grid step that does
  nothing still costs a step, and under a narrow band nearly all of an
  ``S // block`` axis does nothing.

On non-TPU backends the same kernels run under ``interpret=True`` so the
whole path is unit-testable on the CPU mesh (tests/test_flash_attention.py
checks fwd+grad against the einsum reference in ops/xla_attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.ops import attention_mask as amask
from dlnetbench_tpu.ops import pallas_common

_F32 = pallas_common.F32
_LANES = pallas_common.LANES  # TPU lane width; head dim padded to this
_MAX_HEAD_DIM = 2 * _LANES   # widest head the dense kernels take
_SUBLANES = 8                # fp32 sublane tile: row vectors (lse, D) are
                             # stored (B, H, 8, S) so blocks are (8, block_q)
_NEG_INF = -1e30             # finite "-inf": keeps masked rows NaN-free
_LOG2E = 1.4426950408889634  # the VPU's transcendental unit is exp2; doing
                             # the online softmax in the base-2 domain folds
                             # the ln2 conversion into the (free) q scale —
                             # one fewer multiply per score element.  The
                             # softmax is algebraically identical and the
                             # saved lse is converted back to natural log.
# Default block sizes are direction-specific (measured at S=4096 on v5e,
# with the parallel dimension_semantics below): the forward kernel gains
# ~40% from 2048-wide blocks (fewer online-softmax rescale rounds, deeper
# MXU pipelining per grid lane), while both backward kernels peak at 1024
# (the dq/dkv bodies hold more live blocks, so 2048 spills).  1024 was
# itself ~2.5x faster than 512 at S=2048.
_BLOCK_CANDIDATES_FWD = (2048, 1024, 512, 256, 128)
_BLOCK_CANDIDATES_BWD = (1024, 512, 256, 128)
_BLOCK_CANDIDATES = _BLOCK_CANDIDATES_BWD   # shape gate: the common subset


_VMEM_LIMIT_MB = 64
# the share of it that a head's resident dq may take in the dk/dv
# kernel (``_dq_resident``); the score tiles have the rest
_DQ_RESIDENT_SHARE = 0.5


def _compiler_params(dq_resident: bool = False):
    """Mosaic params shared by all the kernels: the minor grid axis
    carries the online-softmax / accumulator scratch (sequential); the
    outer (batch, head, row-block) axes are independent — declaring them
    ``parallel`` lets Mosaic pipeline DMA across grid rows instead of
    treating the whole grid as one sequential chain (measured: the 2048
    forward blocks are ~1.7x slower without it).  With a head's dq
    resident the row-block axis carries that accumulator too and is
    sequential; batch and head stay independent.  The VMEM cap stays at
    64 MiB (tighter than the matmul-family default — these kernels hold
    more live blocks per lane) so 2048-wide blocks keep double-buffering
    headroom on v5e/v5p (128 MiB physical VMEM)."""
    return pallas_common.compiler_params(
        ("parallel", "parallel",
         "arbitrary" if dq_resident else "parallel", "arbitrary"),
        vmem_limit_mb=_VMEM_LIMIT_MB)


# At and beyond this length the dense-attention fallback materializes a
# >= 4-billion-entry score matrix — the silent degradation is ALWAYS a
# bug, so block resolution fails loud instead of returning "unsupported"
# (ops/__init__.py's auto dispatcher would otherwise quietly hand a 64k
# sequence to the einsum path; pallas_common.fit_block has the same
# guard for its matmul-family callers).
LONG_SEQ = 64 * 1024


def _pick_block(seq_len: int, candidates=_BLOCK_CANDIDATES) -> int | None:
    for b in candidates:
        if seq_len % b == 0 and seq_len >= b:
            return b
    if seq_len >= LONG_SEQ:
        raise ValueError(
            f"flash/splash attention: no block candidate in {candidates} "
            f"divides seq_len {seq_len}, and at S >= {LONG_SEQ} the dense "
            f"fallback would materialize the S^2 score matrix — pad the "
            f"sequence to a multiple of {min(candidates)}")
    return None


def flash_supported(q, k, v) -> bool:
    """Shape gate for the "auto" dispatcher: sequence divisible into
    lane-aligned blocks and a head dim we can pad to one lane tile."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    return (_pick_block(s) is not None and hq % hkv == 0
            and max(dh, v.shape[3]) <= _MAX_HEAD_DIM)


def splash_supported(q, k, v) -> bool:
    """The block-sparse kernels keep one head width of at most a lane
    tile."""
    return flash_supported(q, k, v) and q.shape[3] == v.shape[3] <= _LANES


def _mask_causal(s, i, j, block_q: int, block_k: int):
    """Mask score block ``s`` at grid position (q block i, kv block j)."""
    qi = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    ki = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(qi >= ki, s, _NEG_INF)


# ------------------------------------------------------------------ fwd

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)      # q block
    j = pl.program_id(3)      # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # kv block j touches q block i iff its first key is <= the last query
    q_end = i * block_q + block_q - 1
    work = (j * block_k <= q_end) if causal else (j >= 0)
    # last kv block that does work for this q block
    last_j = jnp.minimum(nk - 1, q_end // block_k) if causal else nk - 1

    @pl.when(work)
    def _step():
        # base-2 online softmax: scores scaled by scale*log2(e) so the
        # transcendentals are exp2 (what the VPU natively computes);
        # softmax ratios are unchanged
        q = q_ref[0].astype(_F32) * (scale * _LOG2E)      # [bq, dh]
        k = k_ref[0]                                      # [bk, dh]
        s = jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)                  # [bq, bk]
        if causal:
            s = _mask_causal(s, i, j, block_q, block_k)

        m_prev = m_ref[:, :1]                             # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp2(m_prev - m_new)                  # [bq, 1]
        p = jnp.exp2(s - m_new)                           # [bq, bk]
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)                  # [bq, dh]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == last_j)
    def _emit():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # back to natural log for the backward kernels' exp(s - lse)
        lse = (m_ref[:, 0] + jnp.log2(l[:, 0])) / _LOG2E   # [bq]
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[2:])


def _fwd(q, k, v, *, causal: bool, block_q: int, block_k: int):
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = 1.0 / (dh ** 0.5)    # scale by the REAL head dim, pre-padding

    dh_p, dv_p = _padded(dh), _padded(v.shape[3])
    # head-flattened [B, S, H*dh_p]: a free reshape when Dh == lane width,
    # so the kernel reads activations in their native [B, S, ...] layout —
    # the [B,H,S,D] variant cost a physical 33 MB transpose per tensor per
    # layer per direction (~1.1 ms each on v5e, measured)
    qt = _to_bsf(q, dh_p)        # [B, S, Hq*dh_p]
    kt = _to_bsf(k, dh_p)
    vt = _to_bsf(v, dv_p)

    nq, nk = s // block_q, s // block_k
    grid = (b, hq, nq, nk)

    def kv_index(bi, h, i, j):
        if causal:
            # clamp skipped above-diagonal steps to the previous block so
            # no DMA is issued for fully-masked KV (same-index revisit)
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (bi, j, h // group)

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width), kv_index,
                            memory_space=pltpu.VMEM)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh_p),
                         lambda bi, h, i, j: (bi, i, h),
                         memory_space=pltpu.VMEM),
            kv_spec(dh_p), kv_spec(dv_p),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv_p),
                         lambda bi, h, i, j: (bi, i, h),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, _SUBLANES, block_q),
                         lambda bi, h, i, j: (bi, h, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hq * dv_p), q.dtype),
            jax.ShapeDtypeStruct((b, hq, _SUBLANES, s), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv_p), _F32),
            pltpu.VMEM((block_q, _LANES), _F32),
            pltpu.VMEM((block_q, _LANES), _F32),
        ],
        compiler_params=_compiler_params(),
        name="flash_fwd",
        interpret=pallas_common.interpret_mode(),
    )(qt, kt, vt)
    return _from_bsf(out, hq, v.shape[3]), lse


# ------------------------------------------------------------------ bwd

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dq_ref,
               dq_acc,
               *, scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_end = i * block_q + block_q - 1
    work = (j * block_k <= q_end) if causal else (j >= 0)
    last_j = jnp.minimum(nk - 1, q_end // block_k) if causal else nk - 1

    @pl.when(work)
    def _step():
        k = k_ref[0]
        s = jax.lax.dot_general(
            (q_ref[0].astype(_F32) * scale).astype(k.dtype), k,
            (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        if causal:
            s = _mask_causal(s, i, j, block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])           # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)                  # [bq, bk]
        ds = p * (dp - dcap_ref[0, 0, 0][:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    @pl.when(j == last_j)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_refs(refs):
    """The dk/dv kernels' outputs and scratch as Pallas hands them:
    (dk, dv, dk_acc, dv_acc), or with a head's dq resident
    (dk, dv, dq, dk_acc, dv_acc, dq_acc).  Always six, ``None`` where
    the dq kernel does that work."""
    if len(refs) == 4:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
        return dk_ref, dv_ref, None, dk_acc, dv_acc, None
    return refs


def _dq_resident_init(dq_acc, j, i):
    """Zeros at the head's first grid step.  Keyed, like the emit, on
    the grid's indices and not on ``work``: under a mask a head's last
    step may visit nothing, and a query block that no key block visits
    leaves zeros."""
    @pl.when((j == 0) & (i == 0))
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)


def _dq_resident_emit(dq_ref, dq_acc, j, i):
    """The one write of the head's output block, after the step of the
    head's last grid step."""
    @pl.when((j == pl.num_programs(2) - 1) & (i == pl.num_programs(3) - 1))
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dq_resident_add(dq_acc, ds, k, i, block_q: int):
    """``dq[q block i] += ds k``: the product the dq kernel ends with,
    on the ``ds`` the dk/dv kernel holds.  Key blocks arrive in
    ascending order (the outer grid axis), so the float32 sum has the
    dq kernel's order."""
    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
    dq_acc[rows, :] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=_F32)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, *refs,
                scale: float, causal: bool, block_q: int, block_k: int):
    dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = _dkv_refs(refs)
    j = pl.program_id(2)      # kv block (outer)
    i = pl.program_id(3)      # q block (inner / minor)
    nq = pl.num_programs(3)

    # first q block whose last query reaches this kv block
    first_i = (j * block_k) // block_q if causal else 0
    work = (i >= first_i)

    @pl.when(i == first_i)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if dq_acc is not None:
        _dq_resident_init(dq_acc, j, i)

    @pl.when(work)
    def _step():
        k = k_ref[0]
        q = q_ref[0]
        s = jax.lax.dot_general(
            (q.astype(_F32) * scale).astype(k.dtype), k,
            (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        if causal:
            s = _mask_causal(s, i, j, block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])           # [bq, bk]
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32)                  # [bk, dh]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)                  # [bq, bk]
        ds = (p * (dp - dcap_ref[0, 0, 0][:, None]) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32)                  # [bk, dh]
        if dq_acc is not None:
            _dq_resident_add(dq_acc, ds, k, i, block_q)

    @pl.when(i == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_acc is not None:
        _dq_resident_emit(dq_ref, dq_acc, j, i)


def _validate_blocks(s: int, what: str):
    """Loud validator for block configs that come from outside the
    kernel's own defaults (the tuning DB, the tuner's
    ``override_blocks``): every block must be a positive divisor of the
    sequence — a truncated grid would silently leave dq rows unwritten
    and drop query contributions from dk/dv while a sweep records a
    plausible-looking time."""
    def check(cfg: dict) -> None:
        for name, blk in cfg.items():
            if not isinstance(blk, int) or blk <= 0 or s % blk:
                raise ValueError(
                    f"{what}: block {name}={blk!r} does not "
                    f"divide seq_len {s}")
    return check


def _checked_override(override_blocks, s: int, what: str):
    """The tuner's ``((bq_dq, bk_dq), (bq_dkv, bk_dkv))``, held to
    ``_validate_blocks``."""
    (bq_dq, bk_dq), (bq_dkv, bk_dkv) = override_blocks
    _validate_blocks(s, what)({"bq_dq": bq_dq, "bk_dq": bk_dq,
                               "bq_dkv": bq_dkv, "bk_dkv": bk_dkv})
    return override_blocks


def _resolve_bwd_blocks(q, k, causal: bool, bq: int, bk: int,
                        consult_db: bool = True):
    """Backward per-kernel blocks where the tuner gave no
    ``override_blocks``: only when the caller passed no explicit blocks
    (``consult_db``) the tuning DB (``dlnetbench_tpu/tuning``, frozen
    after first consult per shape key), else (bq, bk) for both kernels:
    the caller's explicit blocks, or the defaults, so an empty DB is
    bit-identical to the pre-tuning harness and explicit arguments are
    never silently overlaid by a DB hit.  The dq kernel (minor axis =
    kv blocks, accumulator [bq, dh]) and the dk/dv kernel (minor axis =
    q blocks, accumulators 2x[bk, dh]) have different live sets, so a
    record holds a pair of blocks for each."""
    b, s, hq, _ = q.shape
    if not consult_db:
        return (bq, bk), (bq, bk)
    from dlnetbench_tpu import tuning
    cfg = tuning.consult(
        "flash_bwd",
        tuning.params.flash_bwd_key(b, s, hq, k.shape[2], q.shape[3],
                                    causal, q.dtype),
        {"bq_dq": bq, "bk_dq": bk, "bq_dkv": bq, "bk_dkv": bk},
        validate=_validate_blocks(s, "flash_attention backward"))
    return ((cfg["bq_dq"], cfg["bk_dq"]), (cfg["bq_dkv"], cfg["bk_dkv"]))


def _dq_resident(q, dh_p: int, bq: int, bk: int, fit: bool):
    """Whether the dk/dv kernel also produces dq, and the blocks it then
    runs at, from ``q.shape`` and the blocks alone: ``(fused, bq, bk)``.
    It does where one query head's whole dq, the float32 accumulator
    ``[S, dh_p]`` and the output block in ``q``'s dtype (twice: Pallas
    double-buffers it), fits ``_DQ_RESIDENT_SHARE`` of the kernels' VMEM
    limit.  Past that (at bf16 and 128 lanes, S of 64k and more) the dq
    kernel recomputes the scores for dq alone, at its own pair of
    blocks.  The score tiles have to fit beside it: where the four
    float32 tiles of a step (s, p, dP, dS) at the caller's blocks and
    the resident dq pass the limit together, the blocks halve until they
    do (``fit``; blocks of 2048, which a caller sized for the forward:
    measured on the v5e at S=16384, 28 heads of 128, window 4096, the
    kernel took 63.0 ms at 2048 x 2048 beside 16 MiB of dq and 16.3 at
    1024 x 1024, where dkv + dq at 2048 took 14.5 + 11.3).  The tuner's
    ``override_blocks`` go as they are.  The choice is a fact of the
    traced program, marked once for each traced site (``spans.mark``: on
    the build's ``compile`` span under a tracer, nothing without
    one)."""
    limit = _VMEM_LIMIT_MB * 2 ** 20
    resident = q.shape[1] * dh_p * (4 + 2 * q.dtype.itemsize)
    fused = resident <= _DQ_RESIDENT_SHARE * limit
    while (fused and fit and max(bq, bk) > _LANES
           and resident + 4 * bq * bk * 4 > limit):
        bq, bk = max(bq // 2, _LANES), max(bk // 2, _LANES)
    spans.mark("flash.bwd", fused=fused, dq_resident_bytes=resident,
               block_q=bq, block_k=bk)
    return fused, bq, bk


def _dq_resident_parts(q, dh_p: int, fused: bool):
    """What the dk/dv call gains with a head's dq resident, each a list
    to append (empty where it is not): the output block (the head's
    whole dq, its index fixed over both block axes so it is written back
    once a head), its shape, the accumulator."""
    if not fused:
        return [], [], []
    b, s, hq, _ = q.shape
    return ([pl.BlockSpec((1, s, dh_p), lambda bi, h, *_: (bi, 0, h))],
            [jax.ShapeDtypeStruct((b, s, hq * dh_p), q.dtype)],
            [pltpu.VMEM((s, dh_p), _F32)])


def _dq_call(qt, kt, vt, dot, lse, dcap, *, scale: float, causal: bool,
             group: int, block_q: int, block_k: int):
    """dq from a kernel of its own (grid minor axis = kv blocks), where a
    head's dq is not resident in the dk/dv kernel (``_dq_resident``)."""
    b, s, _ = qt.shape
    hq = lse.shape[1]
    dh_p, dv_p = qt.shape[2] // hq, dot.shape[2] // hq

    def kv_index(bi, h, i, j):
        if causal:  # no DMA for fully-masked KV blocks (see _fwd)
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (bi, j, h // group)

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda bi, h, i, j: (bi, i, h),
                            memory_space=pltpu.VMEM)

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width), kv_index,
                            memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, _SUBLANES, block_q),
                            lambda bi, h, i, j: (bi, h, 0, i),
                            memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(b, hq, s // block_q, s // block_k),
        in_specs=[q_spec(dh_p), kv_spec(dh_p), kv_spec(dv_p),
                  q_spec(dv_p), row_spec, row_spec],
        out_specs=q_spec(dh_p),
        out_shape=jax.ShapeDtypeStruct((b, s, hq * dh_p), qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh_p), _F32)],
        compiler_params=_compiler_params(),
        name="flash_bwd_dq",
        interpret=pallas_common.interpret_mode(),
    )(qt, kt, vt, dot, lse, dcap)


def _bwd_impl(q, k, v, out, lse, do, *, causal: bool,
              block_q: int, block_k: int, override_blocks=None,
              consult_db: bool = True):
    (bq_dq, bk_dq), (bq_dkv, bk_dkv) = (
        _checked_override(override_blocks, q.shape[1],
                          "flash_attention backward override_blocks")
        if override_blocks is not None
        else _resolve_bwd_blocks(q, k, causal, block_q, block_k,
                                 consult_db=consult_db))
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = 1.0 / (dh ** 0.5)
    dv = v.shape[3]
    dh_p, dv_p = _padded(dh), _padded(dv)
    fused, bq_dkv, bk_dkv = _dq_resident(q, dh_p, bq_dkv, bk_dkv,
                                         fit=override_blocks is None)

    qt, kt = _to_bsf(q, dh_p), _to_bsf(k, dh_p)
    vt, dot, ot = (_to_bsf(x, dv_p) for x in (v, do, out))
    # D_i = rowsum(dO * O): cheap elementwise, plain XLA; only the tiny
    # [B, S, Hq] result is transposed to the kernel's row-vector layout
    dcap = jnp.sum((dot.astype(_F32) * ot.astype(_F32))
                   .reshape(b, s, hq, dv_p), axis=-1)     # [B, S, Hq]
    dcap = jnp.broadcast_to(jnp.swapaxes(dcap, 1, 2)[:, :, None, :],
                            (b, hq, _SUBLANES, s))        # sublane-replicated

    # dk/dv per q-head; inner (minor) axis walks q blocks
    nq_t, nk_t = s // bq_dkv, s // bk_dkv

    def qi_index(bi, h, j, i):
        if causal:  # skip DMA of q blocks strictly above this kv diagonal
            i = jnp.maximum(i, (j * bk_dkv) // bq_dkv)
        return i

    def q_spec_t(width):
        return pl.BlockSpec(
            (1, bq_dkv, width),
            lambda bi, h, j, i: (bi, qi_index(bi, h, j, i), h),
            memory_space=pltpu.VMEM)

    def kv_spec_t(width):
        return pl.BlockSpec((1, bk_dkv, width),
                            lambda bi, h, j, i: (bi, j, h // group),
                            memory_space=pltpu.VMEM)

    def kv_out_t(width):
        return pl.BlockSpec((1, bk_dkv, width),
                            lambda bi, h, j, i: (bi, j, h),
                            memory_space=pltpu.VMEM)
    row_spec_t = pl.BlockSpec((1, 1, _SUBLANES, bq_dkv),
                              lambda bi, h, j, i: (bi, h, 0, qi_index(bi, h, j, i)),
                              memory_space=pltpu.VMEM)
    dq_spec, dq_shape, dq_scratch = _dq_resident_parts(q, dh_p, fused)
    dk_h, dv_h, *dq_res = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq_dkv, block_k=bk_dkv),
        grid=(b, hq, nk_t, nq_t),
        in_specs=[q_spec_t(dh_p), kv_spec_t(dh_p), kv_spec_t(dv_p),
                  q_spec_t(dv_p), row_spec_t, row_spec_t],
        out_specs=[kv_out_t(dh_p), kv_out_t(dv_p)] + dq_spec,
        out_shape=[jax.ShapeDtypeStruct((b, s, hq * dh_p), k.dtype),
                   jax.ShapeDtypeStruct((b, s, hq * dv_p), v.dtype)]
        + dq_shape,
        scratch_shapes=[pltpu.VMEM((bk_dkv, dh_p), _F32),
                        pltpu.VMEM((bk_dkv, dv_p), _F32)] + dq_scratch,
        compiler_params=_compiler_params(dq_resident=fused),
        name="flash_bwd_dkv",
        interpret=pallas_common.interpret_mode(),
    )(qt, kt, vt, dot, lse, dcap)
    dq = dq_res[0] if fused else _dq_call(
        qt, kt, vt, dot, lse, dcap, scale=scale, causal=causal, group=group,
        block_q=bq_dq, block_k=bk_dq)

    # sum the q-head group into each kv head (GQA): consecutive q heads
    # share a kv head, so the flattened head axis folds as [Hkv, group]
    dk = dk_h.reshape(b, s, hkv, group, dh_p).sum(axis=3)
    dv_sum = dv_h.reshape(b, s, hkv, group, dv_p).sum(axis=3)
    return (_from_bsf(dq, hq, dh),
            dk[..., :dh].astype(k.dtype),
            dv_sum[..., :dv].astype(v.dtype))


# ------------------------------------------------------- layout helpers

def _padded(width: int) -> int:
    """A head's width as the kernels block it: the next multiple of the
    lane tile."""
    return -(-width // _LANES) * _LANES


def _to_bsf(x, dh_p: int):
    """[B, S, H, Dh] -> [B, S, H*dh_p]: zero-pad the head dim to one lane
    tile and flatten heads into the minor axis.  A FREE reshape when
    Dh == dh_p (the layout is unchanged) — the kernels block the flat axis
    per head via their index maps, so no transpose ever materializes."""
    b, s, h, dh = x.shape
    if dh < dh_p:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dh_p - dh)))
    return x.reshape(b, s, h * dh_p)


def _from_bsf(x, h: int, dh: int):
    """[B, S, H*dh_p] -> [B, S, H, Dh], dropping head-dim padding."""
    b, s, f = x.shape
    return x.reshape(b, s, h, f // h)[..., :dh]


# ------------------------------------------------------------ public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int | None = None, block_k: int | None = None):
    """Blockwise attention; same contract as ops/xla_attention.py.

    q: [B, S, Hq, Dh], k: [B, S, Hkv, Dh], v: [B, S, Hkv, Dv] with
    Hq % Hkv == 0; Dv may differ from Dh (-> [B, S, Hq, Dv]).
    """
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k)
    return out


def _resolve_blocks(q, k, block_q, block_k,
                    candidates=_BLOCK_CANDIDATES):
    s, dh = q.shape[1], q.shape[3]
    hq, hkv = q.shape[2], k.shape[2]
    if hq % hkv or dh > _MAX_HEAD_DIM:
        raise ValueError(
            f"flash_attention: unsupported shape (Hq={hq} % Hkv={hkv} != 0 "
            f"or head dim {dh} > {_MAX_HEAD_DIM}); use "
            f"ops.attention(..., impl='auto')")
    bq = block_q or _pick_block(s, candidates)
    bk = block_k or _pick_block(s, candidates)
    if bq is None or bk is None or s % bq or s % bk:
        raise ValueError(
            f"flash_attention: seq_len {s} not divisible into blocks "
            f"{_BLOCK_CANDIDATES}; use ops.attention(..., impl='auto')")
    return bq, bk


def _flash_fwd(q, k, v, causal, block_q, block_k):
    bq, bk = _resolve_blocks(q, k, block_q, block_k,
                             candidates=_BLOCK_CANDIDATES_FWD)
    if block_q is None and block_k is None:
        # no explicit blocks from the caller: the tuning DB may answer
        # (dlnetbench_tpu/tuning — frozen after first consult per shape
        # key; explicit arguments always bypass it); an empty/absent DB
        # keeps today's _pick_block defaults bit-identically
        from dlnetbench_tpu import tuning
        b, s, hq, dh = q.shape
        cfg = tuning.consult(
            "flash_fwd",
            tuning.params.flash_fwd_key(b, s, hq, k.shape[2], dh,
                                        causal, q.dtype),
            {"block_q": bq, "block_k": bk},
            validate=_validate_blocks(s, "flash_attention forward"))
        bq, bk = cfg["block_q"], cfg["block_k"]
    out, lse = _fwd(q, k, v, causal=causal, block_q=bq, block_k=bk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    bq, bk = _resolve_blocks(q, k, block_q, block_k,
                             candidates=_BLOCK_CANDIDATES_BWD)
    # explicit caller blocks bind the backward too (pre-tuning
    # behavior): only an all-default call may let the DB answer
    return _bwd_impl(q, k, v, out, lse, g, causal=causal,
                     block_q=bq, block_k=bk,
                     consult_db=block_q is None and block_k is None)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- splash (block-sparse)
# The masked generalization of the kernels above (ISSUE 10): a host-
# precomputed BlockMask (ops/attention_mask.py) drives the grid through
# scalar-prefetch arrays —
#   * the minor grid axis is as long as the mask's widest visit range
#     (``BlockMask.q_visits`` / ``kv_visits``), and step r of row i
#     names block first[i] + r: SKIP blocks outside a row's range have
#     no grid step at all.  A row with fewer visits than the widest
#     (the first rows of a window, a short document) spends the steps
#     left over on nothing: no MXU work (``pl.when`` off) and no DMA
#     (the index maps clamp to the row's last block, and a same-index
#     revisit copies nothing — the trick the causal kernels use for the
#     fully-masked tail).  Under the plain-causal spec the widest row
#     visits every block and the grid is the dense kernels',
#   * FULL blocks skip the in-register mask apply,
#   * PARTIAL blocks mask against the row intervals [lo[q], hi[q]]
#     (two compares — causal, window and segment semantics all reduce
#     to the interval form).
# With the plain-causal spec the visit set, the mask booleans and every
# arithmetic op match the dense kernels exactly, so splash is
# bit-identical to ``flash_attention(causal=True)`` — locked by
# tests/test_flash_attention.py.

def _splash_prefetch(bm):
    """The 4 per-q-block int32 prefetch arrays of a BlockMask (fwd/dq
    grids): visit range + FULL-detection bounds."""
    return (jnp.asarray(bm.q_first_k), jnp.asarray(bm.q_last_k),
            jnp.asarray(bm.blk_lo_max), jnp.asarray(bm.blk_hi_min))


def _row_i32(arr, s: int):
    """[S] int32 -> the kernels' (SUBLANES, S) row-vector layout."""
    return jnp.broadcast_to(jnp.asarray(arr, jnp.int32)[None, :],
                            (_SUBLANES, s))


def _visited(first_ref, last_ref, row, r):
    """The block that step ``r`` of ``row``'s visit range names, for an
    index map.  A row with fewer visits than the widest revisits its
    last block on the steps left over, which copies nothing."""
    return jnp.minimum(first_ref[row] + r, last_ref[row])


def _mark_grid(kernel: str, grid, bm):
    """How much of a block-sparse call's grid does work, a fact of the
    traced program like ``flash.bwd``: ``steps`` the grid's product,
    ``live`` the steps whose (row, block) pair the mask visits."""
    spans.mark("flash.grid", kernel=kernel, steps=math.prod(grid),
               live=grid[0] * grid[1] * bm.visited)


def _interval_mask(s, lo, hi, j, block_q: int, block_k: int):
    """Mask score block ``s`` against the row intervals: key column k
    allowed iff lo[q] <= k <= hi[q].  ``lo``/``hi``: [bq] int32 (this
    q block's rows)."""
    ki = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = (ki >= lo[:, None]) & (ki <= hi[:, None])
    return jnp.where(keep, s, _NEG_INF)


def _splash_fwd_kernel(first_ref, last_ref, lomax_ref, himin_ref,
                       q_ref, k_ref, v_ref, lo_ref, hi_ref,
                       o_ref, lse_ref, acc_ref, m_ref, l_ref,
                       *, scale: float, block_q: int, block_k: int):
    i = pl.program_id(2)      # q block
    r = pl.program_id(3)      # step of its visit range
    lj = last_ref[i]
    j = first_ref[i] + r      # kv block

    @pl.when(r == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    work = j <= lj
    full = ((lomax_ref[i] <= j * block_k)
            & (himin_ref[i] >= (j + 1) * block_k - 1))

    def _step(masked: bool):
        q = q_ref[0].astype(_F32) * (scale * _LOG2E)      # [bq, dh]
        k = k_ref[0]                                      # [bk, dh]
        s = jax.lax.dot_general(
            q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)                  # [bq, bk]
        if masked:
            s = _interval_mask(s, lo_ref[0], hi_ref[0], j,
                               block_q, block_k)
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    # FULL blocks skip the in-register mask apply; the two bodies are
    # otherwise the same code (identical float results when the mask is
    # all-true, which is what keeps causal-spec splash bit-identical)
    pl.when(work & full)(lambda: _step(False))
    pl.when(work & ~full)(lambda: _step(True))

    @pl.when(j == lj)
    def _emit():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse = (m_ref[:, 0] + jnp.log2(l[:, 0])) / _LOG2E
        lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[2:])


def _splash_fwd(q, k, v, spec, *, block_q: int, block_k: int):
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = 1.0 / (dh ** 0.5)
    dh_p = _LANES
    bm = amask.block_mask(spec, s, block_q, block_k)

    qt, kt, vt = (_to_bsf(x, dh_p) for x in (q, k, v))
    grid = (b, hq, bm.nq, bm.q_visits)
    _mark_grid("flash_fwd", grid, bm)

    def kv_index(bi, h, i, r, first_ref, last_ref, lomax_ref, himin_ref):
        return (bi, _visited(first_ref, last_ref, i, r), h // group)

    def q_index(bi, h, i, j, *_refs):
        return (bi, i, h)

    def row_index(bi, h, i, j, *_refs):
        return (0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh_p), q_index),
            pl.BlockSpec((1, block_k, dh_p), kv_index),
            pl.BlockSpec((1, block_k, dh_p), kv_index),
            pl.BlockSpec((_SUBLANES, block_q), row_index),
            pl.BlockSpec((_SUBLANES, block_q), row_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dh_p), q_index),
            pl.BlockSpec((1, 1, _SUBLANES, block_q),
                         lambda bi, h, i, j, *_r: (bi, h, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dh_p), _F32),
            pltpu.VMEM((block_q, _LANES), _F32),
            pltpu.VMEM((block_q, _LANES), _F32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_splash_fwd_kernel, scale=scale,
                          block_q=block_q, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, s, hq * dh_p), q.dtype),
            jax.ShapeDtypeStruct((b, hq, _SUBLANES, s), _F32),
        ],
        compiler_params=_compiler_params(),
        name="flash_fwd",
        interpret=pallas_common.interpret_mode(),
    )(*_splash_prefetch(bm), qt, kt, vt,
      _row_i32(bm.lo, s), _row_i32(bm.hi, s))
    return _from_bsf(out, hq, dh), lse


def _splash_dq_kernel(first_ref, last_ref, lomax_ref, himin_ref,
                      q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                      lo_ref, hi_ref, dq_ref, dq_acc,
                      *, scale: float, block_q: int, block_k: int):
    i = pl.program_id(2)
    r = pl.program_id(3)
    lj = last_ref[i]
    j = first_ref[i] + r

    @pl.when(r == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    work = j <= lj
    full = ((lomax_ref[i] <= j * block_k)
            & (himin_ref[i] >= (j + 1) * block_k - 1))

    def _step(masked: bool):
        k = k_ref[0]
        s = jax.lax.dot_general(
            (q_ref[0].astype(_F32) * scale).astype(k.dtype), k,
            (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        if masked:
            s = _interval_mask(s, lo_ref[0], hi_ref[0], j,
                               block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)
        ds = p * (dp - dcap_ref[0, 0, 0][:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    pl.when(work & full)(lambda: _step(False))
    pl.when(work & ~full)(lambda: _step(True))

    @pl.when(j == lj)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _splash_dkv_kernel(firsti_ref, lasti_ref, lomax_ref, himin_ref,
                       q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                       lo_ref, hi_ref, *refs,
                       scale: float, block_q: int, block_k: int):
    dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = _dkv_refs(refs)
    j = pl.program_id(2)      # kv block (outer)
    r = pl.program_id(3)      # step of its visit range (inner / minor)
    fi, li = firsti_ref[j], lasti_ref[j]
    work = fi + r <= li
    # q block; past the range the last one again, as the index maps
    # have it, so that the FULL test reads inside its arrays
    i = _visited(firsti_ref, lasti_ref, j, r)

    @pl.when(r == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if dq_acc is not None:
        _dq_resident_init(dq_acc, j, r)

    full = ((lomax_ref[i] <= j * block_k)
            & (himin_ref[i] >= (j + 1) * block_k - 1))

    def _step(masked: bool):
        k = k_ref[0]
        q = q_ref[0]
        s = jax.lax.dot_general(
            (q.astype(_F32) * scale).astype(k.dtype), k,
            (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        if masked:
            s = _interval_mask(s, lo_ref[0], hi_ref[0], j,
                               block_q, block_k)
        p = jnp.exp(s - lse_ref[0, 0, 0][:, None])
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)
        ds = (p * (dp - dcap_ref[0, 0, 0][:, None]) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32)
        if dq_acc is not None:
            _dq_resident_add(dq_acc, ds, k, i, block_q)

    pl.when(work & full)(lambda: _step(False))
    pl.when(work & ~full)(lambda: _step(True))

    @pl.when(r == li - fi)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_acc is not None:
        _dq_resident_emit(dq_ref, dq_acc, j, r)


def _splash_dq_call(qt, kt, vt, dot, lse, dcap, spec, *, scale: float,
                    group: int, block_q: int, block_k: int):
    """The masked ``_dq_call``: per-q-block visit ranges at ITS block
    shape."""
    b, s, _ = qt.shape
    hq = lse.shape[1]
    dh_p = _LANES
    bm_dq = amask.block_mask(spec, s, block_q, block_k)
    grid = (b, hq, bm_dq.nq, bm_dq.q_visits)
    _mark_grid("flash_bwd_dq", grid, bm_dq)

    def kv_index(bi, h, i, r, first_ref, last_ref, *_r):
        return (bi, _visited(first_ref, last_ref, i, r), h // group)

    def q_index(bi, h, i, j, *_r):
        return (bi, i, h)

    def row_index(bi, h, i, j, *_r):
        return (bi, h, 0, i)

    def mrow_index(bi, h, i, j, *_r):
        return (0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh_p), q_index),
            pl.BlockSpec((1, block_k, dh_p), kv_index),
            pl.BlockSpec((1, block_k, dh_p), kv_index),
            pl.BlockSpec((1, block_q, dh_p), q_index),
            pl.BlockSpec((1, 1, _SUBLANES, block_q), row_index),
            pl.BlockSpec((1, 1, _SUBLANES, block_q), row_index),
            pl.BlockSpec((_SUBLANES, block_q), mrow_index),
            pl.BlockSpec((_SUBLANES, block_q), mrow_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh_p), q_index),
        scratch_shapes=[pltpu.VMEM((block_q, dh_p), _F32)],
    )
    return pl.pallas_call(
        functools.partial(_splash_dq_kernel, scale=scale,
                          block_q=block_q, block_k=block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, hq * dh_p), qt.dtype),
        compiler_params=_compiler_params(),
        name="flash_bwd_dq",
        interpret=pallas_common.interpret_mode(),
    )(*_splash_prefetch(bm_dq), qt, kt, vt, dot, lse, dcap,
      _row_i32(bm_dq.lo, s), _row_i32(bm_dq.hi, s))


def _splash_bwd_impl(q, k, v, out, lse, do, spec, *,
                     block_q: int, block_k: int, override_blocks=None,
                     consult_db: bool = True):
    (bq_dq, bk_dq), (bq_dkv, bk_dkv) = (
        _checked_override(override_blocks, q.shape[1],
                          "splash_attention backward override_blocks")
        if override_blocks is not None
        else _resolve_splash_bwd_blocks(q, k, spec, block_q, block_k,
                                        consult_db=consult_db))
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = 1.0 / (dh ** 0.5)
    dh_p = _LANES
    fused, bq_dkv, bk_dkv = _dq_resident(q, dh_p, bq_dkv, bk_dkv,
                                         fit=override_blocks is None)

    qt, kt, vt = (_to_bsf(x, dh_p) for x in (q, k, v))
    dot = _to_bsf(do, dh_p)
    ot = _to_bsf(out, dh_p)
    dcap = jnp.sum((dot.astype(_F32) * ot.astype(_F32))
                   .reshape(b, s, hq, dh_p), axis=-1)
    dcap = jnp.broadcast_to(jnp.swapaxes(dcap, 1, 2)[:, :, None, :],
                            (b, hq, _SUBLANES, s))

    # dk/dv kernel: transposed visit ranges (per-kv-block q range) at
    # its own block shape; the minor grid axis walks a kv block's q
    # blocks
    bm_t = amask.block_mask(spec, s, bq_dkv, bk_dkv)
    grid_t = (b, hq, bm_t.nk, bm_t.kv_visits)
    _mark_grid("flash_bwd_dkv", grid_t, bm_t)

    def q_index_t(bi, h, j, r, firsti_ref, lasti_ref, *_r):
        return (bi, _visited(firsti_ref, lasti_ref, j, r), h)

    def kv_index_t(bi, h, j, i, *_r):
        return (bi, j, h // group)

    def kv_out_t(bi, h, j, i, *_r):
        return (bi, j, h)

    def row_index_t(bi, h, j, r, firsti_ref, lasti_ref, *_r):
        return (bi, h, 0, _visited(firsti_ref, lasti_ref, j, r))

    def mrow_index_t(bi, h, j, r, firsti_ref, lasti_ref, *_r):
        return (0, _visited(firsti_ref, lasti_ref, j, r))

    dq_spec, dq_shape, dq_scratch = _dq_resident_parts(q, dh_p, fused)
    grid_spec_t = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid_t,
        in_specs=[
            pl.BlockSpec((1, bq_dkv, dh_p), q_index_t),
            pl.BlockSpec((1, bk_dkv, dh_p), kv_index_t),
            pl.BlockSpec((1, bk_dkv, dh_p), kv_index_t),
            pl.BlockSpec((1, bq_dkv, dh_p), q_index_t),
            pl.BlockSpec((1, 1, _SUBLANES, bq_dkv), row_index_t),
            pl.BlockSpec((1, 1, _SUBLANES, bq_dkv), row_index_t),
            pl.BlockSpec((_SUBLANES, bq_dkv), mrow_index_t),
            pl.BlockSpec((_SUBLANES, bq_dkv), mrow_index_t),
        ],
        out_specs=[pl.BlockSpec((1, bk_dkv, dh_p), kv_out_t),
                   pl.BlockSpec((1, bk_dkv, dh_p), kv_out_t)] + dq_spec,
        scratch_shapes=[pltpu.VMEM((bk_dkv, dh_p), _F32),
                        pltpu.VMEM((bk_dkv, dh_p), _F32)] + dq_scratch,
    )
    dk_h, dv_h, *dq_res = pl.pallas_call(
        functools.partial(_splash_dkv_kernel, scale=scale,
                          block_q=bq_dkv, block_k=bk_dkv),
        grid_spec=grid_spec_t,
        out_shape=[jax.ShapeDtypeStruct((b, s, hq * dh_p), k.dtype),
                   jax.ShapeDtypeStruct((b, s, hq * dh_p), v.dtype)]
        + dq_shape,
        compiler_params=_compiler_params(dq_resident=fused),
        name="flash_bwd_dkv",
        interpret=pallas_common.interpret_mode(),
    )(jnp.asarray(bm_t.kv_first_q), jnp.asarray(bm_t.kv_last_q),
      jnp.asarray(bm_t.blk_lo_max), jnp.asarray(bm_t.blk_hi_min),
      qt, kt, vt, dot, lse, dcap,
      _row_i32(bm_t.lo, s), _row_i32(bm_t.hi, s))
    dq = dq_res[0] if fused else _splash_dq_call(
        qt, kt, vt, dot, lse, dcap, spec, scale=scale, group=group,
        block_q=bq_dq, block_k=bk_dq)

    dk = dk_h.reshape(b, s, hkv, group, dh_p).sum(axis=3)
    dv = dv_h.reshape(b, s, hkv, group, dh_p).sum(axis=3)
    return (_from_bsf(dq, hq, dh),
            dk[..., :dh].astype(k.dtype),
            dv[..., :dh].astype(v.dtype))


def _resolve_splash_bwd_blocks(q, k, spec, bq: int, bk: int,
                               consult_db: bool = True):
    """Splash backward per-kernel blocks, same precedence as the dense
    path (``_resolve_bwd_blocks``): only for all-default calls the
    tuning DB under the MASK-labeled ``splash_bwd`` key (sparsity
    changes the live set, so splash and dense optima are distinct
    records), else (bq, bk) for both."""
    b, s, hq, _ = q.shape
    if not consult_db:
        return (bq, bk), (bq, bk)
    from dlnetbench_tpu import tuning
    cfg = tuning.consult(
        "splash_bwd",
        tuning.params.splash_key(b, s, hq, k.shape[2], q.shape[3],
                                 spec.label(), q.dtype),
        {"bq_dq": bq, "bk_dq": bk, "bq_dkv": bq, "bk_dkv": bk},
        validate=_validate_blocks(s, "splash_attention backward"))
    return ((cfg["bq_dq"], cfg["bk_dq"]), (cfg["bq_dkv"], cfg["bk_dkv"]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def splash_attention(q, k, v, spec, block_q: int | None = None,
                     block_k: int | None = None):
    """Block-sparse masked attention; same tensor contract as
    ``flash_attention``, with a static ``MaskSpec``
    (ops/attention_mask.py) instead of the ``causal`` flag.  The
    plain-causal spec is bit-identical (fwd and grads) to
    ``flash_attention(causal=True)``."""
    out, _ = _splash_vjp_fwd(q, k, v, spec, block_q, block_k)
    return out


def _splash_vjp_fwd(q, k, v, spec, block_q, block_k):
    if not q.shape[3] == v.shape[3] <= _LANES:
        raise ValueError(
            f"splash_attention: one head width of at most {_LANES} "
            f"(got {q.shape[3]} and {v.shape[3]}); the dense kernels "
            f"take two")
    bq, bk = _resolve_blocks(q, k, block_q, block_k,
                             candidates=_BLOCK_CANDIDATES_FWD)
    if block_q is None and block_k is None:
        # all-default call: the tuning DB may answer (splash blocks are
        # their own PR-9 site, keyed per shape x mask label — the mask
        # changes which blocks even run, so dense records never answer)
        from dlnetbench_tpu import tuning
        b, s, hq, dh = q.shape
        cfg = tuning.consult(
            "splash_fwd",
            tuning.params.splash_key(b, s, hq, k.shape[2], dh,
                                     spec.label(), q.dtype),
            {"block_q": bq, "block_k": bk},
            validate=_validate_blocks(s, "splash_attention forward"))
        bq, bk = cfg["block_q"], cfg["block_k"]
    out, lse = _splash_fwd(q, k, v, spec, block_q=bq, block_k=bk)
    return out, (q, k, v, out, lse)


def _splash_vjp_bwd(spec, block_q, block_k, res, g):
    q, k, v, out, lse = res
    bq, bk = _resolve_blocks(q, k, block_q, block_k,
                             candidates=_BLOCK_CANDIDATES_BWD)
    return _splash_bwd_impl(q, k, v, out, lse, g, spec,
                            block_q=bq, block_k=bk,
                            consult_db=block_q is None and block_k is None)


splash_attention.defvjp(_splash_vjp_fwd, _splash_vjp_bwd)
