"""Fused-quantization Pallas matmuls (ops/quantized_matmul.py, ISSUE 3
tentpole) — interpret-mode unit tests against the composed XLA
reference: int8 EXACT (shared scale definition + associative int32
accumulation), fp8 within e4m3 quantization tolerance, delayed-scaling
state threading, and the transformer config plumbing.

On the chip the same kernels run at the bench shape in
``chip_smoke.py``'s kernels phase."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu.ops import quantized_matmul as qmm
from dlnetbench_tpu.ops.fp8 import fp8_dot, swiglu_fp8_fused
from dlnetbench_tpu.ops.int8 import (
    int8_dot,
    swiglu_int8,
    swiglu_int8_fused,
    swiglu_int8_fused_delayed,
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


def test_delayed_dot_state_threading():
    """The delayed-scaling contract: (1) with amax_in = the TRUE amax,
    the result equals fresh scaling exactly (int8); (2) amax_out is
    the true amax of the CURRENT activation (the next step's state);
    (3) a stale, too-small amax saturates instead of overflowing; (4)
    the carried state gets a zero gradient."""
    x = _rand(11, (64, 32))
    w = _rand(12, (32, 48), 0.1)
    true_amax = jnp.max(jnp.abs(x.astype(_F32)))

    y, amax_out = qmm.int8_dot_fused_delayed(x, w, true_amax)
    assert jnp.array_equal(y, int8_dot(x, w))
    assert jnp.array_equal(amax_out, true_amax)

    y_stale, amax_out2 = qmm.int8_dot_fused_delayed(x, w, true_amax * 0.1)
    assert bool(jnp.all(jnp.isfinite(y_stale.astype(_F32))))
    # the emitted state is the fresh amax regardless of the stale scale
    assert jnp.array_equal(amax_out2, true_amax)

    def loss(x, w, amax):
        y, _ = qmm.int8_dot_fused_delayed(x, w, amax)
        return jnp.sum(y.astype(_F32))

    gx, gw, gamax = jax.grad(loss, argnums=(0, 1, 2))(x, w, true_amax)
    assert float(jnp.sum(jnp.abs(gamax))) == 0.0
    gx_ref, gw_ref = jax.grad(
        lambda x, w: jnp.sum(int8_dot(x, w).astype(_F32)),
        argnums=(0, 1))(x, w)
    assert jnp.array_equal(gx, gx_ref) and jnp.array_equal(gw, gw_ref)

    # fp8 delayed: same contract, quantization-tolerance equality
    yf, am = qmm.fp8_dot_fused_delayed(x, w, true_amax)
    assert jnp.array_equal(am, true_amax)
    ref = fp8_dot(x, w).astype(_F32)
    rel = (jnp.linalg.norm(yf.astype(_F32) - ref)
           / jnp.maximum(jnp.linalg.norm(ref), 1e-9))
    assert rel < 1e-2


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


def test_swiglu_fused_delayed_state_and_grads():
    """Layer-level delayed scaling: with the TRUE amaxes as incoming
    state the output is exactly the fresh-scaling fused result, the
    emitted state is [amax_x, amax_h] of THIS step, and gradients match
    the master backward; the state slot gets zero gradient."""
    x = _rand(22, (48, 32))
    wg = _rand(23, (32, 40), 0.1)
    wu = _rand(24, (32, 40), 0.1)
    wd = _rand(25, (40, 32), 0.1)

    # true amaxes of this step's activations
    amax_x = jnp.max(jnp.abs(x.astype(_F32)))
    g = int8_dot(x, wg)
    u = int8_dot(x, wu)
    h = (jax.nn.silu(g.astype(_F32)) * u.astype(_F32)).astype(g.dtype)
    amax_h = jnp.max(jnp.abs(h.astype(_F32)))
    qs = jnp.stack([amax_x, amax_h])

    y, new_qs = swiglu_int8_fused_delayed(x, wg, wu, wd, qs)
    assert jnp.array_equal(y, swiglu_int8_fused(x, wg, wu, wd))
    assert jnp.allclose(new_qs, qs)

    cot = _rand(26, (48, 32))

    def loss_delayed(x, wg, wu, wd, qs):
        y, _ = swiglu_int8_fused_delayed(x, wg, wu, wd, qs)
        return jnp.sum(y.astype(_F32) * cot.astype(_F32))

    def loss_master(*a):
        return jnp.sum(swiglu_int8(*a).astype(_F32) * cot.astype(_F32))

    gd = jax.grad(loss_delayed, argnums=(0, 1, 2, 3, 4))(x, wg, wu, wd, qs)
    gm = jax.grad(loss_master, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
    for a, b, name in zip(gd[:4], gm, ("dx", "dwg", "dwu", "dwd")):
        assert jnp.array_equal(a, b), name
    assert float(jnp.sum(jnp.abs(gd[4]))) == 0.0

    # a cold (ones) state still produces finite output and the emitted
    # state converges to the truth in one step — the warm-in contract
    y2, qs2 = swiglu_int8_fused_delayed(x, wg, wu, wd, jnp.ones(2, _F32))
    assert bool(jnp.all(jnp.isfinite(y2.astype(_F32))))
    assert jnp.array_equal(qs2[0], amax_x)


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


def test_quantized_swiglu_dispatcher_guards_delayed_state():
    """The layers-level dispatcher must mirror the config validation
    for direct callers: handing delayed state to a composed-configured
    call is an error, not a silent reroute to the fused kernel."""
    from dlnetbench_tpu.models import layers as L
    x = _rand(40, (8, 16))
    w = _rand(41, (16, 24), 0.1)
    wd = _rand(42, (24, 16), 0.1)
    with pytest.raises(ValueError, match="requires quant_fusion='fused'"):
        L.quantized_swiglu(x, w, w, wd, mlp_dtype="int8",
                           quant_fusion="composed",
                           amax_state=jnp.ones(2, _F32))


def test_transformer_quant_config_validation():
    from dlnetbench_tpu.models import transformer as tfm
    with pytest.raises(ValueError, match="quant_fusion"):
        tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                              quant_fusion="pallas")
    with pytest.raises(ValueError, match="quant_scaling"):
        tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                              quant_fusion="fused", quant_scaling="stale")
    with pytest.raises(ValueError, match="nothing to quantize"):
        tfm.TransformerConfig(**_TINY, quant_fusion="fused")
    with pytest.raises(ValueError, match="requires quant_fusion='fused'"):
        tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                              quant_scaling="delayed")
    with pytest.raises(ValueError, match="master-dtype"):
        tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                              quant_fusion="fused",
                              int8_backward="switchback")
    # legal combos
    cfg = tfm.TransformerConfig(**_TINY, mlp_dtype="float8",
                                quant_fusion="fused",
                                quant_scaling="delayed")
    assert tfm.needs_qstate(cfg)
    with pytest.raises(ValueError, match="delayed"):
        tfm.init_qstate(tfm.TransformerConfig(**_TINY))


@pytest.mark.parametrize("mlp_dtype", ["int8", "float8"])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_transformer_fused_delayed_trains(mlp_dtype, scan_layers):
    """The full vertical: delayed-scaling fused MLPs inside a train
    step, state threaded through both layer-stack codepaths (scan and
    unrolled), loss finite, grads flowing, state moving off init."""
    from dlnetbench_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(**_TINY, mlp_dtype=mlp_dtype,
                                quant_fusion="fused",
                                quant_scaling="delayed",
                                scan_layers=scan_layers)
    params = tfm.init_params(jax.random.key(0), cfg)
    qs = tfm.init_qstate(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.seq_len + 1),
                                0, cfg.vocab_size)
    step = jax.jit(lambda p, t, q: jax.value_and_grad(
        tfm.loss_fn, has_aux=True)(p, t, cfg, q))
    (loss, new_qs), g = step(params, tokens, qs)
    assert jnp.isfinite(loss)
    assert new_qs.shape == (cfg.num_layers, 2)
    assert bool(jnp.any(new_qs != qs)), "delayed state never updated"
    gmax = jnp.max(jnp.abs(g["layers"]["w_gate"].astype(_F32)))
    assert gmax > 0
    # second step with the threaded state: still finite, state stable
    # (same batch -> same amaxes up to the one-step param update)
    (loss2, qs3), _ = step(jax.tree.map(
        lambda a, b: a - 1e-3 * b.astype(a.dtype), params, g),
        tokens, new_qs)
    assert jnp.isfinite(loss2)
    assert bool(jnp.all(jnp.isfinite(qs3)))


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


def test_forward_requires_qstate_when_delayed():
    from dlnetbench_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(**_TINY, mlp_dtype="int8",
                                quant_fusion="fused",
                                quant_scaling="delayed")
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (1, cfg.seq_len), 0,
                                cfg.vocab_size)
    with pytest.raises(ValueError, match="qstate"):
        tfm.forward(params, tokens, cfg)
