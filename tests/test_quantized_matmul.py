"""Fused-quantization Pallas matmuls (ops/quantized_matmul.py, ISSUE 3
tentpole) — interpret-mode unit tests against the composed XLA
reference: int8 EXACT (shared scale definition + associative int32
accumulation), fp8 within e4m3 quantization tolerance, and the
transformer config plumbing.

On the chip the same kernels run at the bench shape in
``chip_smoke.py``'s kernels phase."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu.ops import quantized_matmul as qmm
from dlnetbench_tpu.ops.fp8 import fp8_dot, swiglu_fp8_fused
from dlnetbench_tpu.ops.int8 import (
    int8_dot,
    swiglu_int8,
    swiglu_int8_fused,
)

_F32 = jnp.float32


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.key(key), shape,
                             jnp.bfloat16) * scale


# shapes that exercise multi-block grids in all three axes at the
# default block sizes AND odd small blocks via fit_block halving
_SHAPES = [(128, 256, 64), (48, 32, 40), (8, 16, 8)]


@pytest.mark.parametrize("t,k,n", _SHAPES)
def test_int8_fused_exact_vs_composed(t, k, n):
    """int8 fused must equal the composed XLA path EXACTLY: the scale
    formula is shared (quantized_matmul.scale_from_amax), int32
    accumulation is associative across the contraction tiling, and the
    f32 sa*sb epilogue is the same arithmetic."""
    x = _rand(0, (t, k))
    w = _rand(1, (k, n), 0.05)
    got = qmm.int8_dot_fused(x, w)
    want = int8_dot(x, w)
    assert got.dtype == want.dtype
    assert jnp.array_equal(got, want), "fused int8 != composed int8"


def test_int8_fused_exact_with_small_blocks():
    """Force a multi-block grid on every axis (block 32/64 over 128-256
    dims) so the k-loop accumulation and block epilogue are actually
    exercised, not degenerate single-block grids."""
    x = _rand(2, (128, 256))
    w = _rand(3, (256, 128), 0.05)
    sx = qmm.scale_from_amax(jnp.max(jnp.abs(x.astype(_F32))), "int8")
    wq, sw = qmm.quantize_tensor(w, "int8")
    got = qmm.fused_matmul(x, wq, sw, sx, fmt="int8",
                           block_m=32, block_n=64, block_k=64)
    want = int8_dot(x, w)
    assert jnp.array_equal(got, want)


def test_fp8_fused_close_to_composed():
    """fp8 accumulates in f32, so the tiled accumulation order differs
    from the composed single dot — equal within e4m3 quantization
    tolerance, and far tighter than the quantization error itself."""
    x = _rand(4, (128, 256))
    w = _rand(5, (256, 64), 0.05)
    got = qmm.fp8_dot_fused(x, w).astype(_F32)
    want = fp8_dot(x, w).astype(_F32)
    rel = jnp.linalg.norm(got - want) / jnp.maximum(
        jnp.linalg.norm(want), 1e-9)
    assert rel < 1e-2, f"fused fp8 vs composed relative error {rel}"
    # and both near the full-precision reference
    full = jnp.dot(x.astype(_F32), w.astype(_F32))
    rel_full = jnp.linalg.norm(got - full) / jnp.linalg.norm(full)
    assert rel_full < 0.05


def test_fused_dots_leading_batch_dims():
    x = _rand(6, (4, 8, 32))
    w = _rand(7, (32, 16), 0.1)
    assert qmm.int8_dot_fused(x, w).shape == (4, 8, 16)
    assert jnp.array_equal(qmm.int8_dot_fused(x, w), int8_dot(x, w))
    assert qmm.fp8_dot_fused(x, w).shape == (4, 8, 16)


def test_fused_dot_straight_through_grads_match_composed():
    x = _rand(8, (32, 16))
    w = _rand(9, (16, 24), 0.1)
    cot = _rand(10, (32, 24))

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w).astype(_F32)
                                    * cot.astype(_F32))

    for fused, composed in ((qmm.int8_dot_fused, int8_dot),
                            (qmm.fp8_dot_fused, fp8_dot)):
        gf = jax.grad(loss(fused), argnums=(0, 1))(x, w)
        gc = jax.grad(loss(composed), argnums=(0, 1))(x, w)
        for a, b in zip(gf, gc):
            # both backwards are the identical master-dtype dots
            assert jnp.array_equal(a, b)


def test_swiglu_fused_matches_composed():
    x = _rand(13, (48, 32))
    wg = _rand(14, (32, 40), 0.1)
    wu = _rand(15, (32, 40), 0.1)
    wd = _rand(16, (40, 32), 0.1)
    # int8: exact, forward and (shared master-dtype) backward
    assert jnp.array_equal(swiglu_int8_fused(x, wg, wu, wd),
                           swiglu_int8(x, wg, wu, wd))
    cot = _rand(17, (48, 32))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(_F32) * cot.astype(_F32))

    gf = jax.grad(loss(swiglu_int8_fused), argnums=(0, 1, 2, 3))(
        x, wg, wu, wd)
    gc = jax.grad(loss(swiglu_int8), argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    for a, b, name in zip(gf, gc, ("dx", "dwg", "dwu", "dwd")):
        assert jnp.array_equal(a, b), name
    # fp8: within quantization tolerance of the composed swiglu
    from dlnetbench_tpu.ops.fp8 import swiglu_fp8
    got = swiglu_fp8_fused(x, wg, wu, wd).astype(_F32)
    want = swiglu_fp8(x, wg, wu, wd).astype(_F32)
    rel = jnp.linalg.norm(got - want) / jnp.maximum(
        jnp.linalg.norm(want), 1e-9)
    assert rel < 2e-2


def test_swiglu_fused_residual_contract():
    """The fused-kernel swiglu keeps the r5 residual contract: exactly
    the two [T, F] pre-activations (g, u) cross the fwd/bwd boundary —
    ``h`` is recomputed, never saved (the no-remat OOM fix)."""
    x = _rand(18, (48, 32))
    wg = _rand(19, (32, 40), 0.1)
    wu = _rand(20, (32, 40), 0.1)
    wd = _rand(21, (40, 32), 0.1)
    for fn in (swiglu_int8_fused, swiglu_fp8_fused):
        _, vjp = jax.vjp(fn, x, wg, wu, wd)
        n_tf = sum(1 for l in jax.tree.leaves(vjp)
                   if getattr(l, "shape", None) == (48, 40))
        assert n_tf == 2, (fn.__name__, n_tf)


def test_quantize_tensor_shared_with_composed_paths():
    """ops/int8.py and ops/fp8.py _quantize must BE the shared
    definition — this is what makes the fused-vs-composed int8 A/B an
    apples-to-apples recipe comparison."""
    from dlnetbench_tpu.ops.fp8 import _quantize as qf
    from dlnetbench_tpu.ops.int8 import _quantize as qi
    x = _rand(27, (64, 32), 3.0)
    for fn, fmt in ((qi, "int8"), (qf, "float8")):
        xq, s = fn(x)
        xq2, s2 = qmm.quantize_tensor(x, fmt)
        assert jnp.array_equal(xq, xq2) and jnp.array_equal(s, s2)


def test_fused_matmul_validation():
    x = _rand(28, (16, 32))
    wq, sw = qmm.quantize_tensor(_rand(29, (32, 16)), "int8")
    with pytest.raises(ValueError, match="unknown quantization format"):
        qmm.fused_matmul(x, wq, sw, 1.0, fmt="int4")
    with pytest.raises(ValueError, match="contraction mismatch"):
        qmm.fused_matmul(_rand(30, (16, 8)), wq, sw, 1.0, fmt="int8")


_TINY = dict(vocab_size=128, embed_dim=32, num_heads=4, num_kv_heads=2,
             ff_dim=64, num_layers=2, seq_len=16, gated=True,
             max_positions=0)


def test_transformer_quant_config_validation():
    from dlnetbench_tpu.models import transformer as tfm
    with pytest.raises(ValueError, match="quant_fusion"):
        tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                              quant_fusion="pallas")
    with pytest.raises(ValueError, match="nothing to quantize"):
        tfm.TransformerConfig(**_TINY, quant_fusion="fused")
    with pytest.raises(ValueError, match="master-dtype"):
        tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                              quant_fusion="fused",
                              int8_backward="switchback")
    # a legal combination
    tfm.TransformerConfig(**_TINY, mlp_dtype="float8", quant_fusion="fused")


@pytest.mark.parametrize("mlp_dtype", ["int8", "float8"])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_transformer_fused_trains(mlp_dtype, scan_layers):
    """The full vertical: fused-quantization MLPs inside a train step,
    through both layer-stack codepaths (scan and unrolled), loss finite
    and falling on a repeated batch, grads flowing."""
    from dlnetbench_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(**_TINY, mlp_dtype=mlp_dtype,
                                quant_fusion="fused",
                                scan_layers=scan_layers)
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.seq_len + 1),
                                0, cfg.vocab_size)
    step = jax.jit(lambda p, t: jax.value_and_grad(tfm.loss_fn)(p, t, cfg))
    loss, g = step(params, tokens)
    assert jnp.isfinite(loss)
    gmax = jnp.max(jnp.abs(g["layers"]["w_gate"].astype(_F32)))
    assert gmax > 0
    # a second step on the same batch after one SGD update
    loss2, _ = step(jax.tree.map(
        lambda a, b: a - 0.5 * b.astype(a.dtype), params, g), tokens)
    assert jnp.isfinite(loss2) and float(loss2) < float(loss)


def test_transformer_fused_dynamic_matches_composed_int8():
    """quant_fusion is an IMPLEMENTATION switch, not a recipe switch:
    with fresh scaling the int8 fused step must produce bitwise the
    same loss as the composed step."""
    from dlnetbench_tpu.models import transformer as tfm
    cfg_f = tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                                  quant_fusion="fused")
    cfg_c = tfm.TransformerConfig(**_TINY, mlp_dtype="int8")
    params = tfm.init_params(jax.random.key(0), cfg_f)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg_f.seq_len + 1),
                                0, cfg_f.vocab_size)
    loss_f = jax.jit(lambda p, t: tfm.loss_fn(p, t, cfg_f))(params, tokens)
    loss_c = jax.jit(lambda p, t: tfm.loss_fn(p, t, cfg_c))(params, tokens)
    assert float(loss_f) == float(loss_c)
