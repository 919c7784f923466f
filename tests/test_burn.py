"""Burn-kernel calibration tests (CPU: numbers are arbitrary but the
calibration contract — linearity and budget mapping — must hold)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.proxies import burn as burnlib
from dlnetbench_tpu.utils.timing import time_callable


def test_burn_zero_iters_identity():
    s = burnlib.make_state()
    out = burnlib.burn(s, 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(s))


def test_burn_deterministic_and_bounded():
    s = burnlib.make_state()
    a = jax.jit(lambda v: burnlib.burn(v, 10))(s)
    b = jax.jit(lambda v: burnlib.burn(v, 10))(s)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.all(np.abs(np.asarray(a, dtype=np.float32)) <= 1.0)
    assert np.all(np.isfinite(np.asarray(a, dtype=np.float32)))


def test_calibration_budget_mapping():
    cal = burnlib.calibrate()
    assert cal.ns_per_iter > 0
    assert cal.iters_for_us(0) == 0
    n = cal.iters_for_us(1000.0)
    assert n >= 1
    # round trip within one iteration
    assert abs(cal.us_for_iters(n) - 1000.0) <= cal.ns_per_iter / 1000.0


def test_burn_time_scales_linearly():
    cal = burnlib.calibrate()
    s = burnlib.make_state()
    f1 = jax.jit(lambda v: burnlib.burn(v, 200))
    f4 = jax.jit(lambda v: burnlib.burn(v, 800))
    f1(s).block_until_ready(); f4(s).block_until_ready()
    t1 = min(time_callable(f1, s, reps=5))
    t4 = min(time_callable(f4, s, reps=5))
    ratio = (t4 - t1) / max(t1, 1e-9)
    # 4x iters => ~3x extra time over the base measurement; allow wide
    # tolerance for CI noise but reject constant-time (DCE'd) behavior
    assert t4 > t1 * 1.5, (t1, t4, ratio)


def test_calibration_probes_outlast_the_dispatch_floor(monkeypatch):
    """A probe shorter than one dispatch plus fence reads as the host,
    not the device (on the v5e the 64/256-iteration pair read 2.2x too
    fast): the pair must grow until the difference is the device's."""
    host_s, per_iter_s = 700e-6, 1.25e-6

    def fake_time(fn, state, reps=1):
        # the device runs under the host's own latency: a short program
        # costs the host's overhead, a long one its device time on top
        n = fn.__wrapped__.keywords["iters"]
        device_s = n * per_iter_s
        hidden = min(device_s, host_s / 2)
        return [host_s + device_s - hidden] * reps

    monkeypatch.setattr(burnlib, "time_callable", fake_time)
    monkeypatch.setattr(burnlib, "dispatch_fence_s", lambda: host_s)
    cal = burnlib._calibrate_on_device(
        burnlib.DEFAULT_SHAPE, "bfloat16", jax.devices()[0], 64, 256)
    assert cal.ns_per_iter == pytest.approx(per_iter_s * 1e9, rel=1e-6)
