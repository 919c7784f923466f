"""Quantized paged-attention decode kernel (ISSUE 12 tentpole).

The serving tier's paged KV pools can be stored int8 / fp8(e4m3) with
per-page-per-head f32 scales (serving/kv_cache.py).  The jax-shipped
``pallas.ops.tpu.paged_attention`` kernel reads bf16/f32 pools only, so
the quantized cache gets its own decode kernel here:

* the sequence's pages are gathered CONTIGUOUS in their quantized
  dtype (one XLA gather of int8/fp8 — half the HBM traffic of a bf16
  gather, a quarter of an f32 one; the quantized pages never
  round-trip through HBM as a wider dtype), along with the matching
  per-page scales;
* the Pallas kernel walks the gathered sequence in
  ``pages_per_compute_block``-page KV blocks and **dequantizes each
  page tile in the VMEM prologue** against the prefetched scales (the
  Pallas input pipeline has the scale block resident before the body
  runs — the PR-3 VMEM-prologue recipe applied to the attention read
  path), then runs the usual f32 online-softmax accumulation;
* masking is by sequence length, exactly like the dense gather
  fallback (``kv_cache._gather_attention`` with scales), which is the
  parity reference the CPU-mesh tests lock this kernel against under
  ``interpret=True`` — and ``chip_smoke.py``'s kernels phase on the chip.

``q`` arrives PRE-SCALED by ``head_dim**-0.5`` (the convention every
paged-attention impl in this repo shares).  ``pages_per_compute_block``
is this kernel's tuning-DB site (op ``paged_attention_quant`` — keyed
with the quant format, since dequant changes the arithmetic intensity;
see ``kv_cache.resolve_pages_per_compute_block``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.ops.pallas_common import F32, compiler_params

# finite mask value (matches kv_cache.MASK_VALUE): exp(mask - m)
# underflows to exactly 0, and a fully-masked tail block can never
# produce an inf - inf NaN in the online-softmax rescale
_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            acc_ref, m_ref, l_ref, *, ppcb: int, page_size: int):
    """Grid (b, h_kv, t): t walks the gathered sequence in blocks of
    ``ppcb`` pages; accumulators carry the online softmax across t
    (minor, "arbitrary"), emitted on the last block.  ``len_ref`` is
    the scalar-prefetched [B] lengths; ``ks_ref``/``vs_ref`` hold this
    sequence's [1, Hkv, Pmax] page scales in SMEM."""
    bi = pl.program_id(0)
    h = pl.program_id(1)
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    bt = ppcb * page_size

    @pl.when(t == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # VMEM prologue: dequantize this block's page tiles against their
    # per-page scales — the quantized copy never exists outside VMEM in
    # a wider dtype.  Each scale is a scalar read from SMEM, spread
    # over its page's rows of a [bt, 1] column by a select chain.
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)

    def scale_col(s_ref):
        col = jnp.full((bt, 1), s_ref[0, h, t * ppcb], F32)
        for p in range(1, ppcb):
            col = jnp.where(row >= p * page_size,
                            s_ref[0, h, t * ppcb + p], col)
        return col

    kf = k_ref[0, 0].astype(F32) * scale_col(ks_ref)     # [bt, Dh]
    vf = v_ref[0, 0].astype(F32) * scale_col(vs_ref)

    q = q_ref[0, 0].astype(F32)                           # [G, Dh]
    s = jax.lax.dot_general(q, kf, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)   # [G, bt]
    pos = t * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[bi], s, _NEG_INF)

    m_prev = m_ref[:, :1]                                 # [G, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                # [G, bt]
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, vf, (((1,), (0,)), ((), ())),
                             preferred_element_type=F32)  # [G, Dh]
    acc_ref[:] = acc_ref[:] * alpha + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(t == nt - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def quant_paged_attention(q, k_pages, v_pages, k_scale, v_scale,
                          lengths, page_indices, *, fmt: str,
                          pages_per_compute_block: int):
    """Decode attention over a quantized page pool.

    q: [B, Hq, Dh] pre-scaled; k/v_pages: [Hkv, P, S, Dh] int8/fp8;
    k/v_scale: [Hkv, P] f32; lengths: [B]; page_indices: [B, Pmax].
    ``fmt`` names the recipe ('int8' | 'float8' — validation only; the
    stored dtype already encodes it)."""
    if fmt not in ("int8", "float8"):
        raise ValueError(f"quant_paged_attention: unknown fmt {fmt!r}")
    b, hq, dh = q.shape
    hkv, _, page_size, _ = k_pages.shape
    pmax = page_indices.shape[1]
    ppcb = pages_per_compute_block
    if pmax % ppcb:
        raise ValueError(
            f"quant_paged_attention: pages_per_compute_block {ppcb} "
            f"does not divide pages_per_seq {pmax}")
    g = hq // hkv
    t_len = pmax * page_size

    # gather QUANTIZED (int8/fp8 through HBM — 1/2 the bytes of a
    # bf16 gather, 1/4 of an f32 one) + the per-page scales that ride
    # beside the pages
    kg = jnp.moveaxis(k_pages[:, page_indices], 0, 1).reshape(
        b, hkv, t_len, dh)
    vg = jnp.moveaxis(v_pages[:, page_indices], 0, 1).reshape(
        b, hkv, t_len, dh)
    ksg = jnp.moveaxis(k_scale[:, page_indices], 0, 1)   # [B, Hkv, Pmax]
    vsg = jnp.moveaxis(v_scale[:, page_indices], 0, 1)
    q4 = q.reshape(b, hkv, g, dh)
    bt = ppcb * page_size
    smem_scales = pl.BlockSpec((1, hkv, pmax),
                               lambda bi, h, t, _len: (bi, 0, 0),
                               memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, pmax // ppcb),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda bi, h, t, _len: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, dh),
                         lambda bi, h, t, _len: (bi, h, t, 0)),
            pl.BlockSpec((1, 1, bt, dh),
                         lambda bi, h, t, _len: (bi, h, t, 0)),
            smem_scales,
            smem_scales,
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda bi, h, t, _len: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dh), F32),
            pltpu.VMEM((g, 128), F32),
            pltpu.VMEM((g, 128), F32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ppcb=ppcb, page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=pallas_common.interpret_mode(),
    )(lengths.astype(jnp.int32), q4, kg, vg, ksg, vsg)
    return out.reshape(b, hq, dh)
