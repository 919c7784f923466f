"""Flash-attention kernel vs. the einsum reference (ops/xla_attention.py).

Runs the Pallas kernels in interpret mode on the CPU mesh (conftest forces
JAX_PLATFORMS=cpu), checking forward values and all three input gradients.
The einsum implementation is the ground truth; tolerances are fp32-tight.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu import ops
from dlnetbench_tpu.ops import flash_attention, flash_supported, xla_attention


def _make_qkv(key, b, s, hq, hkv, dh, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, dh), dtype)
    k = jax.random.normal(kk, (b, s, hkv, dh), dtype)
    v = jax.random.normal(kv, (b, s, hkv, dh), dtype)
    return q, k, v


CASES = [
    # b, s, hq, hkv, dh, causal
    (1, 256, 2, 2, 128, True),    # MHA, aligned head dim
    (2, 256, 4, 2, 128, True),    # GQA group 2
    (1, 256, 4, 1, 64, True),     # MQA + head-dim padding (gpt2-style 64)
    (1, 256, 2, 2, 128, False),   # non-causal (ViT-style)
    (1, 384, 2, 2, 128, True),    # seq that only 128 divides
]


@pytest.mark.parametrize("b,s,hq,hkv,dh,causal", CASES)
def test_forward_matches_reference(b, s, hq, hkv, dh, causal):
    q, k, v = _make_qkv(jax.random.key(0), b, s, hq, hkv, dh)
    want = xla_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, 128, 128)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("b,s,hq,hkv,dh,causal", CASES)
def test_gradients_match_reference(b, s, hq, hkv, dh, causal):
    q, k, v = _make_qkv(jax.random.key(1), b, s, hq, hkv, dh)
    cot = jax.random.normal(jax.random.key(2), q.shape, q.dtype)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal) * cot)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 128, 128) * cot)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        assert jnp.max(jnp.abs(a - b_)) < 5e-4


@pytest.mark.parametrize("hq,hkv,dqk,dv", [
    (2, 2, 192, 128),     # latent attention: 128 + 64 lanes of scores
    (4, 2, 192, 128),     # ... grouped
    (2, 2, 24, 16),       # both under one lane tile, still two widths
    (2, 2, 128, 256)])    # values wider than the scores
def test_two_widths_forward_and_gradients_match_reference(hq, hkv, dqk,
                                                          dv):
    """Scores over ``dqk`` lanes beside values of ``dv``: the kernels
    pad each to its own multiple of 128 and scale by the scores' real
    width."""
    kq, kk, kv, kc = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(kq, (1, 256, hq, dqk))
    k = jax.random.normal(kk, (1, 256, hkv, dqk))
    v = jax.random.normal(kv, (1, 256, hkv, dv))
    cot = jax.random.normal(kc, (1, 256, hq, dv))
    want = xla_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, True, 128, 128)
    assert got.shape == want.shape == (1, 256, hq, dv)
    assert jnp.max(jnp.abs(got - want)) < 2e-5
    g_ref = jax.grad(lambda *a: jnp.sum(
        xla_attention(*a, causal=True) * cot), argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, True, 128, 128) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        assert a.shape == b_.shape
        assert jnp.max(jnp.abs(a - b_)) < 5e-4


def test_two_widths_go_to_the_dense_kernels_only():
    from dlnetbench_tpu.ops import attention
    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    from dlnetbench_tpu.ops.flash_attention import (splash_attention,
                                                    splash_supported)
    q = jnp.zeros((1, 256, 2, 192))
    v = jnp.zeros((1, 256, 2, 128))
    assert flash_supported(q, q, v) and not splash_supported(q, q, v)
    assert not flash_supported(jnp.zeros((1, 256, 2, 320)), q, v)
    with pytest.raises(ValueError, match="one head width"):
        splash_attention(q, q, v, MaskSpec(window=64))
    # a masked call at two widths has the dense-masked reference
    out = attention(q, q, v, causal=True, mask=MaskSpec(window=64))
    assert out.shape == (1, 256, 2, 128)


def test_dispatcher_and_support_gate():
    q, k, v = _make_qkv(jax.random.key(3), 1, 256, 2, 2, 128)
    assert flash_supported(q, k, v)
    out = ops.attention(q, k, v, causal=True, impl="flash")
    ref = ops.attention(q, k, v, causal=True, impl="xla")
    assert jnp.max(jnp.abs(out - ref)) < 2e-5
    # auto on CPU -> xla path, still correct
    auto = ops.attention(q, k, v, causal=True, impl="auto")
    assert jnp.max(jnp.abs(auto - ref)) < 1e-6
    with pytest.raises(ValueError):
        ops.attention(q, k, v, causal=True, impl="nope")


def test_unsupported_seq_falls_back():
    q, k, v = _make_qkv(jax.random.key(4), 1, 100, 2, 2, 64)
    assert not flash_supported(q, k, v)
    out = ops.attention(q, k, v, causal=True, impl="auto")
    assert out.shape == q.shape
    with pytest.raises(ValueError):
        flash_attention(q, k, v, True, None, None)


def test_bf16_forward_close():
    q, k, v = _make_qkv(jax.random.key(5), 1, 256, 2, 2, 128,
                        dtype=jnp.bfloat16)
    want = xla_attention(q, k, v, causal=True).astype(jnp.float32)
    got = flash_attention(q, k, v, True, 128, 128).astype(jnp.float32)
    assert jnp.max(jnp.abs(got - want)) < 3e-2


# ------------------------------------------ splash (block-sparse) masks

from dlnetbench_tpu.ops import attention_mask as am  # noqa: E402
from dlnetbench_tpu.ops.flash_attention import splash_attention  # noqa: E402

longcontext = pytest.mark.longcontext

MASK_SPECS = [
    am.MaskSpec(causal=True, window=40),
    am.MaskSpec(causal=True, seg_avg=50, seg_seed=3),
    am.MaskSpec(causal=False, seg_avg=64, seg_seed=1),
    am.MaskSpec(causal=True, window=32, seg_avg=80, seg_seed=5),
]


def _masked_ref(q, k, v, spec):
    return xla_attention(q, k, v, causal=spec.causal,
                       dense_mask=jnp.asarray(
                           am.dense_mask(spec, q.shape[1])))


@longcontext
def test_splash_causal_bit_identical_to_flash():
    """The acceptance bar: splash with the plain-causal BlockMask is
    BIT-identical to the dense causal flash path — forward AND all
    three gradients (same visit set, same mask booleans, same
    arithmetic; full blocks skipping the mask apply changes nothing
    because an all-true where() is the identity)."""
    q, k, v = _make_qkv(jax.random.key(6), 2, 256, 4, 2, 128)
    spec = am.MaskSpec(causal=True)
    a = flash_attention(q, k, v, True, 128, 128)
    b = splash_attention(q, k, v, spec, 128, 128)
    assert jnp.all(a == b)
    cot = jax.random.normal(jax.random.key(7), q.shape, q.dtype)
    gf = jax.grad(lambda *xs: jnp.sum(
        flash_attention(*xs, True, 128, 128) * cot),
        argnums=(0, 1, 2))(q, k, v)
    gs = jax.grad(lambda *xs: jnp.sum(
        splash_attention(*xs, spec, 128, 128) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for a_, b_ in zip(gf, gs):
        assert jnp.all(a_ == b_)


@longcontext
@pytest.mark.parametrize("spec", MASK_SPECS)
def test_splash_masked_matches_dense_reference(spec):
    """Window / segment / intersection specs vs the dense reference
    applying the SAME mask (fwd <= 1e-5; grads via jax.vjp)."""
    q, k, v = _make_qkv(jax.random.key(8), 2, 256, 4, 2, 128)
    want = _masked_ref(q, k, v, spec)
    got = splash_attention(q, k, v, spec, 64, 64)
    assert jnp.max(jnp.abs(got - want)) < 1e-5
    cot = jax.random.normal(jax.random.key(9), q.shape, q.dtype)
    _, vjp_ref = jax.vjp(lambda *xs: _masked_ref(*xs, spec), q, k, v)
    _, vjp_spl = jax.vjp(lambda *xs: splash_attention(*xs, spec, 64, 64),
                         q, k, v)
    for a_, b_ in zip(vjp_ref(cot), vjp_spl(cot)):
        assert jnp.max(jnp.abs(a_ - b_)) < 1e-4


@longcontext
def test_splash_gqa_and_padded_head_dim():
    """GQA group summing and the head-dim zero-padding path under a
    masked spec (the gpt2-style Dh=64)."""
    spec = am.MaskSpec(causal=True, window=48)
    q, k, v = _make_qkv(jax.random.key(10), 1, 256, 4, 1, 64)
    want = _masked_ref(q, k, v, spec)
    got = splash_attention(q, k, v, spec, 64, 64)
    assert jnp.max(jnp.abs(got - want)) < 1e-5


@longcontext
def test_ops_attention_mask_dispatch():
    """ops.attention routes mask specs: flash -> splash kernels, xla ->
    the dense-masked reference; both agree, and a causal-flag mismatch
    fails loud."""
    from dlnetbench_tpu import ops
    spec = am.MaskSpec(causal=True, window=32)
    q, k, v = _make_qkv(jax.random.key(11), 1, 256, 2, 2, 128)
    a = ops.attention(q, k, v, causal=True, impl="flash", mask=spec)
    b = ops.attention(q, k, v, causal=True, impl="xla", mask=spec)
    assert jnp.max(jnp.abs(a - b)) < 1e-5
    # the plain-causal spec collapses onto the dense-causal default
    c = ops.attention(q, k, v, causal=True, impl="flash",
                      mask=am.MaskSpec(causal=True))
    assert jnp.all(c == ops.attention(q, k, v, causal=True,
                                      impl="flash"))
    with pytest.raises(ValueError, match="causal"):
        ops.attention(q, k, v, causal=False, impl="xla", mask=spec)


@longcontext
def test_block_candidates_cover_64k_128k():
    """ISSUE 10 satellite: every candidate list must resolve a block at
    the long-context bench lengths, and an unresolvable S >= 64k must
    raise NAMING the sequence length instead of silently handing the
    dense path a 4-billion-entry score matrix."""
    from dlnetbench_tpu.ops import flash_attention as _m
    import importlib
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    for s in (64 * 1024, 128 * 1024):
        for cands in (fa._BLOCK_CANDIDATES_FWD, fa._BLOCK_CANDIDATES_BWD):
            b = fa._pick_block(s, cands)
            assert b is not None and s % b == 0
    with pytest.raises(ValueError, match="65537"):
        fa._pick_block(64 * 1024 + 1)
    # below the long-context threshold the gate still degrades softly
    assert fa._pick_block(100) is None


@longcontext
def test_auto_dispatch_refuses_silent_dense_at_64k():
    from dlnetbench_tpu import ops
    q = jnp.zeros((1, 64 * 1024, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="65536"):
        ops.attention(q, q, q, causal=True, impl="auto")
    q_bad = jnp.zeros((1, 64 * 1024 + 1, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="65537"):
        ops.attention(q_bad, q_bad, q_bad, causal=True, impl="auto")


@longcontext
def test_fit_block_refuses_sub_lane_grid_on_long_dim():
    from dlnetbench_tpu.ops import pallas_common
    assert pallas_common.fit_block(64 * 1024, 2048) == 2048
    with pytest.raises(ValueError, match=str(64 * 1024 + 1)):
        pallas_common.fit_block(64 * 1024 + 1, 2048)
    # short dims keep the soft degradation
    assert pallas_common.fit_block(100, 64) == 4
