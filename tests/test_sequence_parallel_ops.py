"""Real-compute ring / Ulysses attention vs. full attention (ops/sequence_parallel.py).

Runs on the 8-device virtual CPU mesh (conftest).  Ground truth: the einsum
attention over the gathered sequence, sliced back to each device's shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from dlnetbench_tpu.utils.jax_compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dlnetbench_tpu.ops import xla_attention
from dlnetbench_tpu.ops.sequence_parallel import (
    ring_attention,
    ulysses_attention,
)

AXIS = "sp"


def _mesh(n):
    return Mesh(jax.devices()[:n], (AXIS,))


def _qkv(key, b, s, hq, hkv, dh):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, s, hq, dh), jnp.float32),
            jax.random.normal(kk, (b, s, hkv, dh), jnp.float32),
            jax.random.normal(kv, (b, s, hkv, dh), jnp.float32))


def _sharded(fn, mesh):
    spec = P(None, AXIS, None, None)
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False))


CASES = [
    # n, b, s, hq, hkv, dh, causal
    (4, 2, 64, 4, 4, 16, True),
    (4, 1, 64, 4, 2, 16, True),    # GQA
    (8, 1, 64, 8, 8, 8, True),
    (4, 2, 64, 4, 4, 16, False),
]


@pytest.mark.parametrize("n,b,s,hq,hkv,dh,causal", CASES)
def test_ring_matches_full(n, b, s, hq, hkv, dh, causal):
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(0), b, s, hq, hkv, dh)
    want = xla_attention(q, k, v, causal=causal)
    fn = _sharded(functools.partial(ring_attention, axis_name=AXIS,
                                    causal=causal), mesh)
    got = fn(q, k, v)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


@pytest.mark.parametrize("n,b,s,hq,hkv,dh,causal", CASES[:1] + CASES[2:])
def test_ulysses_matches_full(n, b, s, hq, hkv, dh, causal):
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(1), b, s, hq, hkv, dh)
    want = xla_attention(q, k, v, causal=causal)
    fn = _sharded(functools.partial(ulysses_attention, axis_name=AXIS,
                                    causal=causal, impl="xla"), mesh)
    got = fn(q, k, v)
    assert jnp.max(jnp.abs(got - want)) < 2e-5


def test_ring_gradients_match_full():
    n, b, s, hq, hkv, dh = 4, 1, 64, 4, 2, 16
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(2), b, s, hq, hkv, dh)
    cot = jax.random.normal(jax.random.key(3), q.shape, q.dtype)

    def ref_loss(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) * cot)

    spec = P(None, AXIS, None, None)

    def ring_loss_local(q, k, v, cot):
        out = ring_attention(q, k, v, axis_name=AXIS, causal=True)
        return lax.psum(jnp.sum(out * cot), AXIS)

    ring_loss = jax.jit(shard_map(
        ring_loss_local, mesh=mesh, in_specs=(spec,) * 4, out_specs=P(),
        check_vma=False))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda q, k, v: ring_loss(q, k, v, cot),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_ring):
        assert jnp.max(jnp.abs(a - b_)) < 5e-5


# ----------------------------------------- block-sparse masks (ISSUE 10)

from dlnetbench_tpu.ops import attention_mask as am  # noqa: E402

longcontext = pytest.mark.longcontext

MASK_SPECS = [
    am.MaskSpec(causal=True, window=20),
    am.MaskSpec(causal=True, seg_avg=24, seg_seed=9),
    am.MaskSpec(causal=False, seg_avg=16, seg_seed=2),
    am.MaskSpec(causal=True, window=24, seg_avg=32, seg_seed=4),
]


@longcontext
@pytest.mark.parametrize("spec", MASK_SPECS)
def test_masked_ring_matches_dense_reference(spec):
    """Sparse ring attention (hop-verdict gating + in-hop interval
    masks) vs full attention applying the SAME mask densely on the
    gathered sequence."""
    n, b, s, hq, hkv, dh = 4, 2, 64, 4, 2, 16
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(6), b, s, hq, hkv, dh)
    want = xla_attention(q, k, v, causal=spec.causal,
                       dense_mask=jnp.asarray(am.dense_mask(spec, s)))
    fn = _sharded(functools.partial(ring_attention, axis_name=AXIS,
                                    causal=spec.causal, spec=spec), mesh)
    got = fn(q, k, v)
    assert jnp.max(jnp.abs(got - want)) < 2e-5
    # the mask must actually skip hops (the point of the gating)
    assert am.ring_skipped_hop_fraction(spec, s, n) > 0


@longcontext
def test_causal_fast_path_gates_future_hops():
    """ISSUE 10 satellite: plain-causal rings now SKIP the compute leg
    of strictly-future hops (they used to run a full _block_scores and
    merge a provably-zero contribution).  The verdict table is the
    causal triangle, and numerics stay identical to the gathered
    reference (the skipped merge was already the exact f32 identity)."""
    import numpy as np
    work = am.ring_hop_work(None, 64, 4)
    me, src = np.indices((4, 4))
    assert (work == (src <= me)).all()
    n, b, s, hq, hkv, dh = 4, 1, 64, 4, 2, 16
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(7), b, s, hq, hkv, dh)
    want = xla_attention(q, k, v, causal=True)
    fn = _sharded(functools.partial(ring_attention, axis_name=AXIS,
                                    causal=True), mesh)
    assert jnp.max(jnp.abs(fn(q, k, v) - want)) < 2e-5


@longcontext
def test_masked_ring_gradients_match_dense_reference():
    spec = am.MaskSpec(causal=True, window=20)
    n, b, s, hq, hkv, dh = 4, 1, 64, 4, 2, 16
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(8), b, s, hq, hkv, dh)
    cot = jax.random.normal(jax.random.key(9), q.shape, q.dtype)
    dm = jnp.asarray(am.dense_mask(spec, s))

    def ref_loss(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True,
                                   dense_mask=dm) * cot)

    sspec = P(None, AXIS, None, None)

    def ring_loss_local(q, k, v, cot):
        out = ring_attention(q, k, v, axis_name=AXIS, causal=True,
                             spec=spec)
        return lax.psum(jnp.sum(out * cot), AXIS)

    ring_loss = jax.jit(shard_map(
        ring_loss_local, mesh=mesh, in_specs=(sspec,) * 4,
        out_specs=P(), check_vma=False))
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda q, k, v: ring_loss(q, k, v, cot),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_ring):
        assert jnp.max(jnp.abs(a - b_)) < 5e-5


@longcontext
def test_masked_ulysses_matches_dense_reference():
    spec = am.MaskSpec(causal=True, window=20)
    n, b, s, hq, hkv, dh = 4, 2, 64, 4, 4, 16
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(10), b, s, hq, hkv, dh)
    want = xla_attention(q, k, v, causal=True,
                       dense_mask=jnp.asarray(am.dense_mask(spec, s)))
    fn = _sharded(functools.partial(ulysses_attention, axis_name=AXIS,
                                    causal=True, impl="xla", spec=spec),
                  mesh)
    assert jnp.max(jnp.abs(fn(q, k, v) - want)) < 2e-5


@longcontext
@pytest.mark.slow
def test_ring_64k_window_locality_and_skip():
    """The S=64k case the machinery was built for (slow lane): a
    sliding-window masked ring over 8 shards at 64k tokens runs, is
    finite, skips >= 70% of the hop grid, and is LOCAL — scrambling
    keys more than a window behind a query must not change its output
    (the dense reference at this length is unbuildable by design, so
    locality is the checkable ground truth)."""
    n, s = 8, 64 * 1024
    s_loc = s // n
    spec = am.MaskSpec(causal=True, window=512)
    assert am.ring_skipped_hop_fraction(spec, s, n) >= 0.7
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(11), 1, s, 1, 1, 8)
    fn = _sharded(functools.partial(ring_attention, axis_name=AXIS,
                                    causal=True, spec=spec), mesh)
    out = fn(q, k, v)
    assert bool(jnp.all(jnp.isfinite(out)))
    # scramble shard 0's keys/values: rows whose whole window lies past
    # shard 0 (q >= s_loc + window) must be bit-unchanged
    k2 = k.at[:, :s_loc].set(
        jax.random.normal(jax.random.key(12), (1, s_loc, 1, 8)))
    v2 = v.at[:, :s_loc].set(
        jax.random.normal(jax.random.key(13), (1, s_loc, 1, 8)))
    out2 = fn(k2 * 0 + q, k2, v2)   # same q
    far = s_loc + spec.window
    assert bool(jnp.all(out[:, far:] == out2[:, far:]))
    assert not bool(jnp.all(out[:, :s_loc] == out2[:, :s_loc]))


def test_ulysses_gradients_match_full():
    n, b, s, hq, hkv, dh = 4, 1, 64, 4, 4, 16
    mesh = _mesh(n)
    q, k, v = _qkv(jax.random.key(4), b, s, hq, hkv, dh)
    cot = jax.random.normal(jax.random.key(5), q.shape, q.dtype)

    def ref_loss(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=True) * cot)

    spec = P(None, AXIS, None, None)

    def ul_loss_local(q, k, v, cot):
        out = ulysses_attention(q, k, v, axis_name=AXIS, causal=True,
                                impl="xla")
        return lax.psum(jnp.sum(out * cot), AXIS)

    ul_loss = jax.jit(shard_map(
        ul_loss_local, mesh=mesh, in_specs=(spec,) * 4, out_specs=P(),
        check_vma=False))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_ul = jax.grad(lambda q, k, v: ul_loss(q, k, v, cot),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_ul):
        assert jnp.max(jnp.abs(a - b_)) < 5e-5
