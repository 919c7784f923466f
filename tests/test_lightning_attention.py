"""``ops/lightning_attention.py``: the chunked forms (the Pallas kernel
pair in interpret mode, the scan of chunks) against the token
recurrence, forward and three gradients."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from dlnetbench_tpu.ops import lightning_attention as la

F32 = jnp.float32
B, H, DK, DV = 2, 4, 16, 8
SCALE = DK ** -0.5


def draws(t, dtype):
    ks = jax.random.split(jax.random.key(3), 4)
    q, k = (jax.random.normal(kk, (B, t, H, DK), F32).astype(dtype)
            for kk in ks[:2])
    v = jax.random.normal(ks[2], (B, t, H, DV), F32).astype(dtype)
    return q, k, v, jax.random.normal(ks[3], (B, t, H, DV), F32)


def value_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o.astype(F32) * w), o
    (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    return (o, *grads)


def rel(a, b):
    a, b = a.astype(F32), b.astype(F32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def reference():
    """{length: (o, dq, dk, dv)} of the recurrence in float32 on the
    values the case's dtype holds, made once a (length, dtype)."""
    made = {}

    def get(t, dtype, decay):
        if (t, dtype) not in made:
            q, k, v, w = draws(t, dtype)
            made[t, dtype] = value_and_grads(
                lambda q, k, v: la.reference_rule(q, k, v, decay, SCALE),
                *(x.astype(F32) for x in (q, k, v)), w)
        return made[t, dtype]
    return get


# a length that is no multiple of the chunk, one that is, and one
# shorter than a chunk; float32 and bfloat16; both sweeps
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("t,chunk,dtype,tol", [
    (80, 32, "float32", 2e-5), (64, 16, "float32", 2e-5),
    (24, 32, "float32", 2e-5), (80, 16, "bfloat16", 2e-2),
    (96, None, "float32", 2e-5)])
def test_chunks_equal_the_token_recurrence(reference, impl, t, chunk, dtype,
                                           tol):
    decay = la.head_log_decay(H, 1, 4)
    q, k, v, w = draws(t, jnp.dtype(dtype))
    got = value_and_grads(
        lambda q, k, v: la.lightning_attention(q, k, v, decay, SCALE, impl,
                                               chunk), q, k, v, w)
    want = reference(t, jnp.dtype(dtype), decay)
    assert got[0].dtype == jnp.dtype(dtype) and got[0].shape == (B, t, H, DV)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert rel(a, b) <= tol, (name, rel(a, b))


def test_the_two_sweeps_run_the_same_chunk():
    decay = la.head_log_decay(H, 0, 4)
    q, k, v, w = draws(64, jnp.bfloat16)
    a, b = (value_and_grads(
        lambda q, k, v: la.lightning_attention(q, k, v, decay, SCALE, impl,
                                               16), q, k, v, w)
        for impl in ("pallas", "xla"))
    for x, y in zip(a, b):
        assert rel(x, y) <= 1e-6


def test_decay_is_the_published_slopes_and_layer_factor():
    """``lambda_h = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5))``:
    the first layer decays most, the last hardly, a later head less."""
    import math
    first, last = la.head_log_decay(32, 0, 32), la.head_log_decay(32, 31, 32)
    assert float(first[0]) == pytest.approx(-2 ** -0.25 * (1 + 1e-5))
    assert float(first[31]) == pytest.approx(-2 ** -8.0 * (1 + 1e-5))
    assert float(last[0]) == pytest.approx(-2 ** -0.25 * 1e-5, rel=1e-3)
    mid = la.head_log_decay(32, 3, 32)
    assert float(mid[7]) == pytest.approx(
        -2 ** -2.0 * (1 - 3 / 31 + 1e-5))
    assert math.exp(float(first[0])) < math.exp(float(first[31])) < 1.0
    # no decay at all is the plain causal linear attention
    q, k, v, _ = draws(16, F32)
    o = la.lightning_attention(q, k, v, jnp.zeros(H), 1.0, "xla", 16)
    want = jnp.einsum("bhts,bshe->bthe",
                      jnp.tril(jnp.einsum("bthd,bshd->bhts", q, k)), v)
    assert rel(o, want) <= 1e-5


def test_the_decay_gets_no_gradient_and_an_unknown_impl_is_refused():
    q, k, v, w = draws(16, F32)
    decay = la.head_log_decay(H, 1, 4)
    g = jax.grad(lambda d: jnp.sum(
        la.lightning_attention(q, k, v, d, SCALE, "xla", 16) * w))(decay)
    assert not jnp.any(g)
    with pytest.raises(ValueError, match="unknown lightning_attention"):
        la.lightning_attention(q, k, v, decay, SCALE, "cuda")
