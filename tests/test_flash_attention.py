"""Flash-attention kernel vs. the einsum reference (ops/xla_attention.py).

Runs the Pallas kernels in interpret mode on the CPU mesh (conftest forces
JAX_PLATFORMS=cpu), checking forward values and all three input gradients.
The einsum implementation is the ground truth; tolerances are fp32-tight.
"""
from __future__ import annotations

import dataclasses
import functools
import re

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

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
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
    g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(
        xla_attention(*a, causal=True) * cot), argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, True, 128, 128) * cot),
        argnums=(0, 1, 2)))(q, k, v)
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


# masks whose widest visit range is shorter than the row of blocks and
# whose rows differ in theirs, so that the kernels' minor grid axis is
# the band's and some rows leave steps of it over: a window of three
# blocks under groups of three, documents under a window, a window that
# is no multiple of the block, documents that look both ways
BAND_W384 = am.MaskSpec(causal=True, window=384)
BAND_DOCS = am.MaskSpec(causal=True, window=100, seg_avg=150, seg_seed=5)
BAND_W200 = am.MaskSpec(causal=True, window=200)

MASKED_CASES = {
    # id: (spec, b, s, hq, hkv, (block_q, block_k))
    **{spec.label(): (spec, 2, 256, 4, 2, (64, 64)) for spec in MASK_SPECS},
    "band_window384_groups_of_3": (BAND_W384, 1, 1024, 6, 2, (128, 128)),
    "band_documents_window": (BAND_DOCS, 2, 512, 4, 2, (64, 64)),
    "band_window200_off_block": (BAND_W200, 1, 512, 4, 1, (64, 64)),
    "band_window200_bk_over_bq": (BAND_W200, 1, 512, 2, 2, (64, 128)),
    "band_documents_both_ways": (MASK_SPECS[2], 1, 512, 2, 2, (64, 64)),
}


def _assert_ragged_band(spec, s, bq, bk):
    """The mask's grids are shorter than ``s // block`` both ways, and
    not every row fills them."""
    bm = am.block_mask(spec, s, bq, bk)
    assert bm.q_visits < bm.nk and bm.kv_visits < bm.nq
    assert len(set((bm.q_last_k - bm.q_first_k).tolist())) > 1
    assert len(set((bm.kv_last_q - bm.kv_first_q).tolist())) > 1


@longcontext
@pytest.mark.parametrize("name", sorted(MASKED_CASES))
def test_splash_masked_matches_dense_reference(name):
    """Window / segment / intersection specs vs the dense reference
    applying the SAME mask (fwd <= 1e-5; grads via jax.vjp)."""
    spec, b, s, hq, hkv, (bq, bk) = MASKED_CASES[name]
    if name.startswith("band_"):
        _assert_ragged_band(spec, s, bq, bk)
    q, k, v = _make_qkv(jax.random.key(8), b, s, hq, hkv, 128)
    want = _masked_ref(q, k, v, spec)
    got = splash_attention(q, k, v, spec, bq, bk)
    assert jnp.max(jnp.abs(got - want)) < 1e-5
    cot = jax.random.normal(jax.random.key(9), q.shape, q.dtype)
    _, vjp_ref = jax.vjp(lambda *xs: _masked_ref(*xs, spec), q, k, v)
    _, vjp_spl = jax.vjp(lambda *xs: splash_attention(*xs, spec, bq, bk),
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


# ------------------------------------------------ a head's dq resident
# The dk/dv kernel also produces dq where one query head's dq fits its
# share of the VMEM limit (``_dq_resident``); the dq kernel stays for
# the sizes past it.  Both paths on the same inputs: the rule's share
# set to nothing is the two-kernel path, as a size past the rule takes
# it.

import importlib  # noqa: E402

from dlnetbench_tpu.metrics import spans  # noqa: E402

fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")

# tests/test_attention_mask.py's SPECS[1], [2] and [3]: a window, a
# causal document mask, a document mask that looks both ways
WINDOW = am.MaskSpec(causal=True, window=24)
DOCS = am.MaskSpec(causal=True, seg_avg=20, seg_seed=3)
DOCS_BOTH_WAYS = am.MaskSpec(causal=False, seg_avg=16, seg_seed=1)

RESIDENT_CASES = {
    # id: (b, s, hq, hkv, dqk, dv, causal or a MaskSpec,
    #      the dq kernel's (bq, bk), the dk/dv kernel's (bq, bk))
    "causal": (2, 256, 2, 2, 128, 128, True, (64, 64), (64, 64)),
    "non_causal": (1, 256, 2, 2, 128, 128, False, (64, 64), (64, 64)),
    "window": (2, 256, 2, 1, 128, 128, WINDOW, (64, 64), (64, 64)),
    "documents": (1, 256, 2, 2, 128, 128, DOCS, (64, 64), (64, 64)),
    "documents_both_ways": (1, 256, 2, 2, 128, 128, DOCS_BOTH_WAYS,
                            (64, 64), (64, 64)),
    "28_over_4": (1, 128, 28, 4, 128, 128, True, (64, 64), (64, 64)),
    "14_over_2_window": (1, 128, 14, 2, 128, 128, WINDOW,
                         (64, 64), (64, 64)),
    "dh64_padded": (1, 256, 4, 1, 64, 64, True, (128, 128), (128, 128)),
    "dh64_padded_window": (1, 256, 4, 2, 64, 64, WINDOW,
                           (64, 64), (64, 64)),
    "scores192_values128": (1, 256, 4, 4, 192, 128, True,
                            (128, 128), (128, 128)),
    "scores192_values128_grouped": (1, 256, 4, 2, 192, 128, False,
                                    (64, 64), (64, 64)),
    "bq_over_bk": (1, 256, 2, 2, 128, 128, True, (128, 64), (128, 64)),
    "bk_over_bq": (1, 256, 2, 1, 128, 128, True, (64, 128), (64, 128)),
    "bk_over_bq_documents": (1, 256, 2, 2, 128, 128, DOCS,
                             (64, 128), (64, 128)),
    # the two kernels at different pairs: the sums' order differs
    "other_blocks": (1, 256, 2, 2, 128, 128, True, (128, 128), (64, 128)),
    "other_blocks_window": (1, 256, 2, 2, 128, 128, WINDOW,
                            (128, 64), (64, 64)),
    # the ragged bands of ``MASKED_CASES``: the grids' minor axes are
    # the band's, 4 of 8 steps and under
    **{name: (b, s, hq, hkv, 128, 128, spec, blocks, blocks)
       for name, (spec, b, s, hq, hkv, blocks) in MASKED_CASES.items()
       if name.startswith("band_")},
    "band_window200_other_blocks": (1, 512, 2, 2, 128, 128, BAND_W200,
                                    (128, 64), (64, 64)),
}


def _backward(case, monkeypatch=None):
    """dq, dk, dv of ``_bwd_impl`` / ``_splash_bwd_impl`` on the case's
    seeded inputs and the ``flash.bwd`` marks of the trace; with
    ``monkeypatch`` the rule's share of the VMEM limit is nothing."""
    b, s, hq, hkv, dqk, dv, mask, dq_blocks, dkv_blocks = case
    kq, kk, kv, kd = jax.random.split(jax.random.key(21), 4)
    q = jax.random.normal(kq, (b, s, hq, dqk), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, dqk), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, dv), jnp.float32)
    do = jax.random.normal(kd, (b, s, hq, dv), jnp.float32)
    bq, bk = dkv_blocks
    kw = dict(block_q=bq, block_k=bk)
    if isinstance(mask, bool):
        out, lse = fa._fwd(q, k, v, causal=mask, **kw)
        run = functools.partial(fa._bwd_impl, causal=mask)
    else:
        out, lse = fa._splash_fwd(q, k, v, mask, **kw)
        run = lambda *a, **o: fa._splash_bwd_impl(*a, mask, **o)  # noqa: E731
    if monkeypatch is not None:
        monkeypatch.setattr(fa, "_DQ_RESIDENT_SHARE", 0.0)
    tracer = spans.enable()
    try:
        grads = run(q, k, v, out, lse, do, **kw,
                    override_blocks=(dq_blocks, dkv_blocks))
    finally:
        spans.disable()
    marks = [s_["attrs"] for s_ in tracer.export()["spans"]
             if s_["name"] == "flash.bwd"]
    return grads, marks, (q, k, v, do, mask)


@pytest.fixture(scope="module")
def resident_backward():
    """``_backward`` of a named case on the resident path, run once a
    module: the comparison with the two kernels and the one with the
    reference read the same gradients."""
    return functools.cache(lambda name: _backward(RESIDENT_CASES[name]))


@longcontext
@pytest.mark.parametrize("name", sorted(RESIDENT_CASES))
def test_resident_dq_matches_the_two_kernels(name, monkeypatch,
                                             resident_backward):
    """The dk/dv kernel with a head's dq resident against dkv + dq: dq,
    dk and dv bit-equal where both run the same blocks (key blocks
    arrive in the dq kernel's order), else within this file's
    tolerance; each path marks itself once."""
    case = RESIDENT_CASES[name]
    b, s, hq, hkv, dqk, dv, mask, dq_blocks, dkv_blocks = case
    one, one_marks, _ = resident_backward(name)
    two, two_marks, _ = _backward(case, monkeypatch)
    mark = {"dq_resident_bytes": s * fa._padded(dqk) * (4 + 2 * 4),
            "block_q": dkv_blocks[0], "block_k": dkv_blocks[1]}
    assert one_marks == [{"fused": True, **mark}]
    assert two_marks == [{"fused": False, **mark}]
    for a, c, what in zip(one, two, ("dq", "dk", "dv")):
        assert a.shape == c.shape and a.dtype == c.dtype, what
        if dq_blocks == dkv_blocks or what != "dq":
            assert jnp.array_equal(a, c), what
        else:
            assert jnp.max(jnp.abs(a - c)) < 1e-4, what


@longcontext
@pytest.mark.parametrize("name", [
    "causal", "window", "documents_both_ways", "scores192_values128",
    "bk_over_bq", "band_window384_groups_of_3", "band_documents_window",
    "band_window200_off_block", "band_window200_bk_over_bq"])
def test_resident_dq_matches_reference(name, resident_backward):
    """And against the einsum reference under the same mask."""
    (dq, dk, dv), _, (q, k, v, do, mask) = resident_backward(name)

    def ref(q, k, v):
        if isinstance(mask, bool):
            return xla_attention(q, k, v, causal=mask)
        return _masked_ref(q, k, v, mask)
    want = jax.jit(lambda *x: jax.vjp(ref, *x[:3])[1](x[3]))(q, k, v, do)
    for a, c in zip(want, (dq, dk, dv)):
        assert jnp.max(jnp.abs(a - c)) < 5e-4


# q's shape and dtype at the seven cells' attention layers (B, S, Hq, dh)
CELL_Q = {
    "minerva7b_train": ((2, 6144, 32, 128), 3 << 20),
    "mixtral8x7b_train": ((2, 4096, 32, 128), 2 << 20),
    "phi4miniflash_train_s8k": ((1, 8192, 20, 128), 4 << 20),
    "kimivl_a3b_train_s8k": ((2, 8192, 16, 192), 8 << 20),
    "qwen3next_a3b_train_s16k": ((1, 16384, 16, 256), 16 << 20),
    "lfm2_8b_a1b_train_s8k": ((1, 8192, 32, 64), 4 << 20),
    "smallthinker_21b_a3b_train_s16k": ((1, 16384, 28, 128), 8 << 20),
}


def _rule(shape, dtype=jnp.bfloat16, blocks=(1024, 1024), fit=True):
    """``_dq_resident`` at a shape, with what it marked: (fused, the
    dk/dv kernel's blocks, the resident bytes)."""
    q = jax.ShapeDtypeStruct(shape, dtype)
    tracer = spans.enable()
    try:
        fused, bq, bk = fa._dq_resident(q, fa._padded(shape[3]), *blocks,
                                        fit=fit)
    finally:
        spans.disable()
    mark, = [s["attrs"] for s in tracer.export()["spans"]]
    assert (mark["fused"], mark["block_q"], mark["block_k"]) == (fused, bq,
                                                                 bk)
    return fused, (bq, bk), mark["dq_resident_bytes"]


@pytest.mark.parametrize("cell", sorted(CELL_Q))
def test_a_head_of_dq_is_resident_at_every_cells_shape(cell):
    """The float32 accumulator and the bf16 output block twice: each
    half of what the rule counts, all seven under half of 64 MiB
    (Qwen's 16 + 16 MiB is the rule's edge), at the backward's default
    blocks as they are."""
    shape, half = CELL_Q[cell]
    assert _rule(shape) == (True, (1024, 1024), 2 * half)


@pytest.mark.parametrize("shape,dtype,fused", [
    ((1, 32768, 8, 128), jnp.bfloat16, True),       # 16 + 16 MiB
    ((1, 65536, 8, 128), jnp.bfloat16, False),      # 32 + 32
    ((1, 131072, 8, 64), jnp.bfloat16, False),
    ((1, 32768, 8, 256), jnp.bfloat16, False),
    ((1, 16384, 8, 256), jnp.float32, False),       # 16 + 2 x 16
])
def test_past_the_vmem_rule_the_two_kernels_stay(shape, dtype, fused):
    """From ``q.shape`` and its dtype alone; the long-context paths
    (a head's dq of 32 MB and more) keep dkv + dq, and the traced
    program holds the dq kernel there."""
    assert _rule(shape, dtype)[:2] == (fused, (1024, 1024))
    if shape[1] > 32768:
        return      # tracing a 64k-long grid is the slow part, once is enough
    b, s, hq, dh = shape
    args = [jax.ShapeDtypeStruct(x, dtype) for x in (
        shape, shape, shape, shape)] + [
        jax.ShapeDtypeStruct((b, hq, 8, s), jnp.float32),
        jax.ShapeDtypeStruct(shape, dtype)]
    text = str(jax.make_jaxpr(functools.partial(
        fa._bwd_impl, causal=True, block_q=1024, block_k=1024,
        consult_db=False))(*args))
    assert ("flash_bwd_dq" in text) == (not fused)
    assert "flash_bwd_dkv" in text


@pytest.mark.parametrize("shape,blocks,fit,want", [
    # SmallThinker's window layers: the model's 2048 x 2048 (64 MiB of
    # float32 score tiles) beside 16 MiB of dq pass the 64 MiB limit
    ((1, 16384, 28, 128), (2048, 2048), True, (1024, 1024)),
    ((1, 16384, 28, 128), (2048, 1024), True, (2048, 1024)),   # 32 + 16
    ((1, 16384, 28, 128), (2048, 2048), False, (2048, 2048)),  # the tuner's
    ((2, 4096, 32, 128), (2048, 2048), True, (1024, 1024)),    # 64 + 4
    ((1, 16384, 16, 256), (1024, 1024), True, (1024, 1024)),   # 16 + 32
    ((1, 32768, 8, 128), (2048, 1024), True, (2048, 1024)),    # 32 + 32
])
def test_blocks_halve_until_the_score_tiles_fit_beside_dq(shape, blocks, fit,
                                                          want):
    assert _rule(shape, blocks=blocks, fit=fit)[:2] == (True, want)


def test_past_the_rule_the_callers_blocks_stand():
    assert _rule((1, 65536, 8, 128), blocks=(2048, 2048))[:2] == \
        (False, (2048, 2048))


@pytest.mark.parametrize("shape,hkv,window,block,fwd,dkv", [
    # smallthinker_21b_a3b_train_s16k: blocks of 2048 for both
    # directions, the backward fitted to 1024 beside a head's dq; a row
    # block sees the diagonal and two blocks before it, a key block of
    # 1024 is seen from five
    ((1, 16384, 28, 128), 4, 4096, 2048, "1, 28, 8, 3", "1, 28, 16, 5"),
    # laguna_s21_train_s16k: a window of one block, two visits each way
    ((1, 16384, 72, 128), 8, 512, 512, "1, 72, 32, 2", "1, 72, 32, 2"),
], ids=["smallthinker", "laguna"])
def test_the_window_layers_backward_runs_at_the_fitted_blocks(
        shape, hkv, window, block, fwd, dkv):
    """The window layers' grids as traced from ``ops.attention`` at the
    blocks the models hand it: the forward's and the backward's (at the
    fitted blocks), each minor axis the mask's widest visit range and
    not ``S // block``."""
    from dlnetbench_tpu import ops
    kv = shape[:2] + (hkv, shape[3])
    spec = am.MaskSpec(causal=True, window=window)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(ops.attention(
        q, k, v, causal=True, impl="flash", mask=spec, block_q=block,
        block_k=block).astype(jnp.float32)), argnums=(0, 1, 2)))(
        *[jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in (shape, kv, kv)]))
    grids = {name: grid for grid, name in re.findall(
        r"grid=\((.*?)\).*?name=(flash_\w+)", text, re.S)}
    assert grids == {"flash_fwd": fwd, "flash_bwd_dkv": dkv}


def test_resident_dq_without_a_tracer_marks_nothing():
    assert not spans.is_enabled()
    assert fa._dq_resident(jax.ShapeDtypeStruct((1, 256, 2, 128),
                                                jnp.float32), 128, 64, 64,
                           fit=True) == (True, 64, 64)
    assert spans.current() is None


# ------------------------------------------- the grid follows the mask
# The block-sparse kernels' minor grid axis is as long as the mask's
# widest visit range, and step r of row i names block first[i] + r.
# The grid that was there before, ``S // block`` long with the steps
# outside a row's range doing nothing, is the same kernels under a mask
# whose widest visit is every block: the same tiles in the same order,
# so the same floats.

def _full_grid_masks(monkeypatch):
    """``block_mask`` with both widest visits at ``S // block``."""
    compact = am.block_mask

    def full(*args):
        bm = compact(*args)
        return dataclasses.replace(bm, q_visits=bm.nk, kv_visits=bm.nq)
    monkeypatch.setattr(fa.amask, "block_mask", full)


def _masked_call(name, dtype):
    """out, lse, dq, dk, dv of a ``MASKED_CASES`` call at its blocks,
    and its ``flash.grid`` marks by kernel."""
    spec, b, s, hq, hkv, (bq, bk) = MASKED_CASES[name]
    q, k, v = _make_qkv(jax.random.key(31), b, s, hq, hkv, 128, dtype)
    do = jax.random.normal(jax.random.key(32), q.shape, dtype)
    tracer = spans.enable()
    try:
        out, lse = fa._splash_fwd(q, k, v, spec, block_q=bq, block_k=bk)
        grads = fa._splash_bwd_impl(q, k, v, out, lse, do, spec,
                                    block_q=bq, block_k=bk,
                                    consult_db=False)
    finally:
        spans.disable()
    marks = {s_["attrs"]["kernel"]: s_["attrs"]
             for s_ in tracer.export()["spans"] if s_["name"] == "flash.grid"}
    return (out, lse, *grads), marks


@longcontext
@pytest.mark.parametrize("two_kernels", [False, True],
                         ids=["fused", "two_kernels"])
@pytest.mark.parametrize("name,dtype", [
    ("band_window384_groups_of_3", jnp.float32),
    ("band_window384_groups_of_3", jnp.bfloat16),
    ("band_documents_window", jnp.float32),
    ("band_window200_bk_over_bq", jnp.bfloat16),
    ("causal&seg(avg=50,seed=3)", jnp.float32),
    ("band_documents_both_ways", jnp.bfloat16),
])
def test_compact_grid_gives_the_full_grids_floats(name, dtype, two_kernels,
                                                  monkeypatch):
    """Outputs, lse and all three gradients at fixed blocks, bit for
    bit, on the band's grid and on the ``S // block`` one; and the
    band's grid is the shorter by the factor the marks give."""
    if two_kernels:
        monkeypatch.setattr(fa, "_DQ_RESIDENT_SHARE", 0.0)
    compact, marks = _masked_call(name, dtype)
    _full_grid_masks(monkeypatch)
    full, full_marks = _masked_call(name, dtype)
    kernels = ["flash_fwd", "flash_bwd_dkv"] + ["flash_bwd_dq"] * two_kernels
    assert sorted(marks) == sorted(full_marks) == sorted(kernels)
    spec, b, s, hq, _, (bq, bk) = MASKED_CASES[name]
    for kernel in kernels:
        assert full_marks[kernel]["steps"] == b * hq * (s // bq) * (s // bk)
        assert marks[kernel]["live"] == full_marks[kernel]["live"]
        assert marks[kernel]["steps"] < full_marks[kernel]["steps"]
    for a, c, what in zip(compact, full, ("out", "lse", "dq", "dk", "dv")):
        assert a.dtype == c.dtype and jnp.array_equal(a, c), what


@longcontext
@pytest.mark.parametrize("two_kernels", [False, True],
                         ids=["fused", "two_kernels"])
def test_each_block_sparse_call_marks_its_grid(two_kernels, monkeypatch):
    """``flash.grid`` once a traced site: the grid's product and the
    steps that visit a block, from the mask at that kernel's blocks.
    A window of 384 in blocks of 128 at S = 1024: eight rows of at most
    four visits, 1 + 2 + 3 + 5 x 4 = 26 of the 32 steps live."""
    if two_kernels:
        monkeypatch.setattr(fa, "_DQ_RESIDENT_SHARE", 0.0)
    _, marks = _masked_call("band_window384_groups_of_3", jnp.float32)
    grid = {"steps": 6 * 8 * 4, "live": 6 * 26}
    want = {"flash_fwd": grid, "flash_bwd_dkv": grid}
    if two_kernels:
        want["flash_bwd_dq"] = grid
    assert marks == {k: {"kernel": k, **g} for k, g in want.items()}
    bm = am.block_mask(BAND_W384, 1024, 128, 128)
    assert (bm.q_visits, bm.kv_visits, bm.visited) == (4, 4, 26)


def test_the_plain_causal_grid_is_the_dense_kernels():
    """Where the widest row visits every block the grid is ``S // block``
    long to the last index (and ``test_splash_causal_bit_identical_to_
    flash`` holds the floats)."""
    def grids(fn):
        x = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.float32)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v)), argnums=(0, 1, 2)))(x, x, x))
        return re.findall(r"grid=\((.*?)\)", text)
    dense = grids(lambda *a: flash_attention(*a, True, 128, 128))
    assert dense == ["1, 2, 4, 4"] * 2
    assert grids(lambda *a: splash_attention(
        *a, am.MaskSpec(causal=True), 128, 128)) == dense


def test_a_block_sparse_call_without_a_tracer_marks_nothing():
    assert not spans.is_enabled()
    x = jnp.ones((1, 256, 1, 128), jnp.float32)
    out, lse = fa._splash_fwd(x, x, x, WINDOW, block_q=64, block_k=64)
    fa._splash_bwd_impl(x, x, x, out, lse, x, WINDOW, block_q=64,
                        block_k=64, consult_db=False)
    assert spans.current() is None
