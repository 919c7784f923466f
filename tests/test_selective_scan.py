"""ops/selective_scan.py: both implementations, forward and hand-written
backward, against the recurrence as it is written (``reference_scan``,
autodiff over a ``lax.scan`` with ``[T, E, N]`` alive)."""
import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu.ops import selective_scan as ss


def inputs(b=2, t=50, e=128, n=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(ks[0], (b, t, e)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, e)) - 2)
    a = -jnp.exp(jax.random.normal(ks[2], (e, n)) * 0.5)
    bm = jax.random.normal(ks[3], (b, t, n)).astype(dtype)
    cm = jax.random.normal(ks[4], (b, t, n)).astype(dtype)
    d = jax.random.normal(ks[5], (e,))
    return u, delta, a, bm, cm, d


def value_and_grads(fn, args, w):
    return jax.value_and_grad(
        lambda *x: jnp.sum(fn(*x).astype(jnp.float32) * w),
        argnums=tuple(range(6)))(*args)


def rel(got, want):
    got, want = (x.astype(jnp.float32) for x in (got, want))
    return float(jnp.linalg.norm(got - want)
                 / (jnp.linalg.norm(want) + 1e-30))


# T = 50 is no multiple of any chunk; E = 384 makes three channel
# blocks for the kernels
@pytest.mark.parametrize("impl,chunk,e", [
    ("xla", 16, 128), ("xla", 64, 128), ("pallas", 16, 128),
    ("pallas", 32, 384)])
def test_forward_and_vjp_against_the_plain_recurrence(impl, chunk, e):
    args = inputs(e=e)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    want_s = ss.reference_scan(*args)
    got_s = ss.selective_scan(*args, impl, chunk)
    assert got_s.shape == want_s.shape and rel(got_s, want_s) < 1e-5
    want = value_and_grads(ss.reference_scan, args, w)
    got = value_and_grads(lambda *x: ss.selective_scan(*x, impl, chunk),
                          args, w)
    for name, g, r in zip("u delta A B C D".split(), got[1], want[1]):
        assert g.shape == r.shape and g.dtype == r.dtype
        assert rel(g, r) < 1e-5, name


def test_state_is_float32_whatever_the_inputs():
    """bf16 activations: the result is the float32 recurrence on the
    rounded inputs, rounded once at the end."""
    args = inputs(dtype=jnp.bfloat16)
    want = ss.reference_scan(*(x.astype(jnp.float32) for x in args))
    for impl in ("xla", "pallas"):
        got = ss.selective_scan(*args, impl, 16)
        assert got.dtype == jnp.bfloat16
        assert rel(got, want) < 4e-3, impl


def test_auto_is_xla_off_the_tpu_and_pallas_needs_lane_multiples():
    args = inputs(e=96)
    assert rel(ss.selective_scan(*args), ss.reference_scan(*args)) < 1e-5
    with pytest.raises(ValueError, match="multiple of 128"):
        ss.selective_scan(*args, "pallas")
    with pytest.raises(ValueError, match="unknown selective_scan impl"):
        ss.selective_scan(*args, "cuda")


def test_backward_keeps_chunk_boundaries_only():
    """The residuals of the forward hold the state at chunk boundaries
    ([T / chunk, ...]), never one a step."""
    args = inputs(b=1, t=64, e=128)
    _, res = ss._vjp_fwd(*args, "xla", 16)
    hs = res[-1]
    assert hs.shape == (4, 1, 16, 128)
    text = jax.jit(jax.grad(lambda *x: jnp.sum(
        ss.selective_scan(*x, "xla", 16)), argnums=(0, 1))).lower(
            *args).as_text()
    assert "64x128x16" not in text and "64x16x128" not in text \
        and "64x1x16x128" not in text
