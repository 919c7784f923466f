"""Decomposed collective matmuls (ops/collective_matmul.py) vs the fused
references, forward and backward, on the virtual CPU mesh.

The references need no shard_map at all: with the layouts used here the
global semantics of gather-then-dot AND dot-then-psum_scatter are both
exactly ``jnp.dot(global_x, global_w)`` (the gather only reassembles the
global array; the scatter only distributes the full product), so every
comparison is against the plain dot — and each decomposed program
compiles ONCE via ``jax.vjp`` (fwd + bwd share the trace), keeping the
suite inside the tier-1 wall budget.

The all-gather-matmul forward is per-row identical math (exact); ring
reduce-scatter and the dw rings accumulate in ring order, so those carry
the documented f32 reduction tolerance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dlnetbench_tpu.ops import collective_matmul as CM
from dlnetbench_tpu.utils.jax_compat import shard_map

MB, S_LOC, D, K = 2, 4, 16, 12   # K even: exercises both ring directions


def _mesh(devs, n):
    return Mesh(np.array(devs[:n]).reshape(n), ("r",))


def _ref_value_and_grads(x, w):
    """Fused-path semantics of BOTH ops at these layouts: the plain dot."""
    def f(a, b):
        return jnp.dot(a, b)
    out, vjp = jax.vjp(f, x, w)
    return out, vjp(jnp.sin(out))


def _run_value_and_grads(fn, mesh, in_specs, out_specs, x, w):
    """One trace for forward + backward of a shard_map'd decomposed op."""
    sm = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)

    @jax.jit        # eagerly, a shard_map runs primitive by primitive
    def both(x, w):
        out, vjp = jax.vjp(sm, x, w)
        return out, vjp(jnp.sin(out))
    return both(x, w)


@pytest.mark.parametrize("n,chunks", [(2, 1), (4, 2)])
def test_all_gather_matmul_matches_fused(eight_devices, n, chunks):
    mesh = _mesh(eight_devices, n)
    x = jax.random.normal(jax.random.key(0), (MB, n * S_LOC, D),
                          jnp.float32)
    w = jax.random.normal(jax.random.key(1), (D, K), jnp.float32) * 0.1

    o_ref, g_ref = _ref_value_and_grads(x, w)
    o_dec, g_dec = _run_value_and_grads(
        lambda a, b: CM.all_gather_matmul(a, b, "r", gather_axis=1,
                                          chunks=chunks),
        mesh, (P(None, "r", None), P()), P(), x, w)
    # forward: per-row identical math -> exact
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_dec))
    # dx (decomposed reduce-scatter) and dw (ring accumulation): f32 tol
    for a, b in zip(g_ref, g_dec):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,chunks", [(2, 1), (4, 2)])
def test_matmul_reduce_scatter_matches_fused(eight_devices, n, chunks):
    mesh = _mesh(eight_devices, n)
    a = jax.random.normal(jax.random.key(2), (MB, n * S_LOC, D),
                          jnp.float32)
    w = jax.random.normal(jax.random.key(3), (D, K), jnp.float32) * 0.1

    o_ref, g_ref = _ref_value_and_grads(a, w)
    # row-parallel layout: contraction dim of a and rows of w sharded;
    # psum_scatter of the partial products == the full dot, distributed
    o_dec, g_dec = _run_value_and_grads(
        lambda x_, y_: CM.matmul_reduce_scatter(x_, y_, "r",
                                                scatter_axis=1,
                                                chunks=chunks),
        mesh, (P(None, None, "r"), P("r", None)), P(None, "r", None),
        a, w)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_dec),
                               rtol=1e-5, atol=1e-5)
    for x_, y_ in zip(g_ref, g_dec):
        np.testing.assert_allclose(np.asarray(x_), np.asarray(y_),
                                   rtol=1e-5, atol=1e-5)


def test_odd_output_width_unidirectional_fallback(eight_devices):
    """K=1 cannot split across the bidirectional rings — the
    reduce-scatter must fall back to one ring, still correct."""
    mesh = _mesh(eight_devices, 4)
    a = jax.random.normal(jax.random.key(4), (MB, 4 * S_LOC, D),
                          jnp.float32)
    w = jax.random.normal(jax.random.key(5), (D, 1), jnp.float32)
    out = jax.jit(shard_map(
        lambda x_, y_: CM.matmul_reduce_scatter(x_, y_, "r",
                                                scatter_axis=1),
        mesh=mesh, in_specs=(P(None, None, "r"), P("r", None)),
        out_specs=P(None, "r", None), check_vma=False))(a, w)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.dot(a, w)),
                               rtol=1e-5, atol=1e-6)


def test_ab_legs_keep_shapes(eight_devices):
    """The A/B decomposition legs: fake_comm (compute leg — full FLOPs,
    permutes stripped) and fake_compute (comm leg — full wire schedule,
    matmuls stubbed) must both preserve the output contract."""
    mesh = _mesh(eight_devices, 4)
    x = jax.random.normal(jax.random.key(6), (MB, 4 * S_LOC, D),
                          jnp.float32)
    w = jax.random.normal(jax.random.key(7), (D, K), jnp.float32)
    for leg in ("fake_comm", "fake_compute"):
        out = jax.jit(shard_map(
            lambda a, b: CM.all_gather_matmul(a, b, "r", gather_axis=1,
                                              **{leg: True}),
            mesh=mesh, in_specs=(P(None, "r", None), P()),
            out_specs=P(), check_vma=False))(x, w)
        assert out.shape == (MB, 4 * S_LOC, K), leg
        assert np.all(np.isfinite(np.asarray(out))), leg
    # 1-rank axis degenerates to the plain dot exactly
    mesh1 = _mesh(eight_devices, 1)
    x1 = x[:, :S_LOC]
    o1 = shard_map(lambda a, b: CM.all_gather_matmul(a, b, "r"),
                   mesh=mesh1, in_specs=(P(), P()), out_specs=P(),
                   check_vma=False)(x1, w)
    np.testing.assert_array_equal(np.asarray(o1),
                                  np.asarray(jnp.dot(x1, w)))
