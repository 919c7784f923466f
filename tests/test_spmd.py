"""SPMD training-step tests: the dp x pp x tp(+sp,+ep) step must compile,
run, learn, and agree with a single-device reference on the 8-device mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.models import spmd


def test_factor_mesh():
    assert spmd.factor_mesh(8) == (2, 2, 2)
    assert spmd.factor_mesh(4) == (1, 2, 2)
    assert spmd.factor_mesh(2) == (1, 1, 2)
    assert spmd.factor_mesh(1) == (1, 1, 1)


def test_validate_errors():
    cfg = spmd.SpmdConfig(num_layers=3)
    with pytest.raises(ValueError, match="layers"):
        cfg.validate(2, 2, 2)


def test_spmd_step_runs_and_learns(eight_devices):
    mesh, cfg, step, params, tokens = spmd.build(8)
    assert mesh.devices.shape == (2, 2, 2)
    losses = []
    for _ in range(5):
        params, loss = step(params, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # params stayed finite
    for leaf in jax.tree.leaves(params):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))


@pytest.mark.slow  # ~20s sharded train step; bf16 twin covers the fast lane
def test_spmd_int8_mlp_step_runs_and_learns(eight_devices):
    """mlp_int8=True (expert matmuls quantized per-tensor, int32 MXU
    accumulation, straight-through backward) on the full dp x pp x tp
    mesh: the step runs, learns, and stays close to the master-dtype
    loss — the r5 single-chip int8 win certified on the EP-sharded
    path."""
    cfg = spmd.SpmdConfig(mlp_int8=True)
    mesh, cfg, step, params, tokens = spmd.build(8, cfg)
    losses = []
    for _ in range(5):
        params, loss = step(params, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # quantization must not move the first-step loss far off master
    _, _, step_m, params_m, _ = spmd.build(8, spmd.SpmdConfig())
    _, l_m = step_m(params_m, tokens)
    assert losses[0] == pytest.approx(float(l_m), rel=0.05)


@pytest.fixture(scope="module")
def single_device_step():
    """The one-device reference step at lossless EP capacity, built once
    for the cases that hold a mesh's step to it."""
    return spmd.build(1, spmd.SpmdConfig(capacity_factor=8.0))[2]


def test_spmd_matches_dataparallel_only(eight_devices, single_device_step):
    """pp=tp=1 (pure dp) must equal full dp x pp x tp on the same data to
    within numerical tolerance — the parallelism must not change the math.
    Capacity is set lossless (cap >= T*k): with finite capacity the EP
    token-drop pattern legitimately depends on the local token pool size,
    so only the no-drop regime is bitwise-comparable across tp."""
    cfg = spmd.SpmdConfig(capacity_factor=8.0)
    _, _, step8, params, tokens = spmd.build(8, cfg)
    p8, l8 = step8(params, tokens)
    p1, l1 = single_device_step(params, tokens)
    assert float(l8) == pytest.approx(float(l1), rel=2e-3)
    # spot-check a parameter after one update
    d8 = np.asarray(p8["layers"]["wq"], dtype=np.float32)
    d1 = np.asarray(p1["layers"]["wq"], dtype=np.float32)
    np.testing.assert_allclose(d8, d1, rtol=0.05, atol=2e-4)


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_spmd_sequence_parallel_modes_match(eight_devices, sp_mode,
                                            single_device_step):
    """ring / ulysses attention (ops/sequence_parallel.py) must produce the
    same training step as megatron SP and as the single-device reference
    (lossless EP capacity, see test_spmd_matches_dataparallel_only)."""
    cfg = spmd.SpmdConfig(capacity_factor=8.0, sp_mode=sp_mode)
    _, _, step8, params, tokens = spmd.build(8, cfg)
    p8, l8 = step8(params, tokens)
    p1, l1 = single_device_step(params, tokens)
    assert float(l8) == pytest.approx(float(l1), rel=2e-3)
    d8 = np.asarray(p8["layers"]["wq"], dtype=np.float32)
    d1 = np.asarray(p1["layers"]["wq"], dtype=np.float32)
    np.testing.assert_allclose(d8, d1, rtol=0.05, atol=2e-4)


# ---- r7 overlap paths: decomposed collective matmuls + bucketed sync ----
# Small shapes (the parity signal is structural, not scale) so the six
# extra 8-device compiles stay inside the tier-1 wall-time budget.
_SMALL = dict(embed_dim=32, num_heads=4, num_kv_heads=4, ff_dim=32,
              num_layers=2, seq_len=16, vocab_size=64, batch=8,
              capacity_factor=8.0)


@pytest.fixture(scope="module")
def small_baseline(eight_devices):
    """One blocking-baseline step at lossless EP capacity, shared by
    every overlap-parity test below (params/tokens included so all
    variants step the same state)."""
    cfg = spmd.SpmdConfig(**_SMALL)
    _, _, step, params, tokens = spmd.build(8, cfg)
    p0, l0 = step(params, tokens)
    return params, tokens, p0, l0


def _tree_max_diff(pa, pb):
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        pa, pb)
    return max(jax.tree.leaves(diffs))


@pytest.mark.parametrize("sp_mode", ["megatron", "ring", "ulysses"])
def test_spmd_decomposed_tp_overlap_matches(small_baseline, sp_mode):
    """tp_overlap=decomposed (ppermute-pipelined collective matmuls,
    ops/collective_matmul.py) must reproduce the blocking psum path: in
    megatron mode every TP projection decomposes (tight tolerance — the
    only reordering is the ring reduce-scatter accumulation); in
    ring/ulysses only the vocab-parallel head does, compared against the
    megatron baseline at the established cross-mode tolerance."""
    params, tokens, p0, l0 = small_baseline
    cfg = spmd.SpmdConfig(sp_mode=sp_mode, tp_overlap="decomposed",
                          tp_overlap_chunks=2, **_SMALL)
    _, _, step, _, _ = spmd.build(8, cfg)
    px, lx = step(params, tokens)
    if sp_mode == "megatron":
        assert float(lx) == pytest.approx(float(l0), rel=1e-5)
        assert _tree_max_diff(px, p0) <= 1e-4
    else:
        assert float(lx) == pytest.approx(float(l0), rel=2e-3)
        d8 = np.asarray(px["layers"]["wq"], dtype=np.float32)
        d1 = np.asarray(p0["layers"]["wq"], dtype=np.float32)
        np.testing.assert_allclose(d8, d1, rtol=0.05, atol=2e-4)


def test_spmd_bucketed_grad_sync_matches(small_baseline):
    """grad_sync=bucketed (reverse-layer-order per-bucket psums chained
    with collectives.tie) is elementwise-identical math to the
    monolithic sync — the whole updated param tree must agree leaf-wise
    (grad-tree equality at fixed lr)."""
    params, tokens, p0, l0 = small_baseline
    cfg = spmd.SpmdConfig(grad_sync="bucketed", grad_bucket_layers=1,
                          **_SMALL)
    _, _, step, _, _ = spmd.build(8, cfg)
    px, lx = step(params, tokens)
    assert float(lx) == pytest.approx(float(l0), rel=1e-6)
    assert _tree_max_diff(px, p0) <= 1e-6


def test_spmd_decomposed_plus_bucketed_matches(small_baseline):
    """Both overlap paths together (the bench/driver 'overlapped'
    config), with a multi-layer bucket group."""
    params, tokens, p0, l0 = small_baseline
    cfg = spmd.SpmdConfig(tp_overlap="decomposed", tp_overlap_chunks=1,
                          grad_sync="bucketed", grad_bucket_layers=2,
                          **_SMALL)
    _, _, step, _, _ = spmd.build(8, cfg)
    px, lx = step(params, tokens)
    assert float(lx) == pytest.approx(float(l0), rel=1e-5)
    assert _tree_max_diff(px, p0) <= 1e-4


def test_spmd_overlap_config_validation():
    with pytest.raises(ValueError, match="tp_overlap"):
        spmd.SpmdConfig(tp_overlap="magic").validate(2, 2, 2)
    with pytest.raises(ValueError, match="grad_sync"):
        spmd.SpmdConfig(grad_sync="eager").validate(2, 2, 2)
    with pytest.raises(ValueError, match="chunks"):
        spmd.SpmdConfig(tp_overlap_chunks=0).validate(2, 2, 2)
    # A/B variants are defined for the megatron split only
    mesh, *_ = spmd.build(8, spmd.SpmdConfig())
    with pytest.raises(ValueError, match="variant"):
        spmd.make_train_step(mesh, spmd.SpmdConfig(), variant="half")
    with pytest.raises(ValueError, match="megatron"):
        spmd.make_train_step(mesh, spmd.SpmdConfig(sp_mode="ring"),
                             variant="comm")


def test_spmd_ring_runs_with_indivisible_heads(eight_devices):
    """ring mode has no heads%tp constraint (all heads stay local)."""
    cfg = spmd.SpmdConfig(num_heads=3, num_kv_heads=3, embed_dim=48,
                          capacity_factor=8.0, sp_mode="ring")
    _, _, step, params, tokens = spmd.build(8, cfg)
    _, loss = step(params, tokens)
    assert np.isfinite(float(loss))
    # megatron rejects the same shape
    with pytest.raises(ValueError, match="heads"):
        spmd.SpmdConfig(num_heads=3, num_kv_heads=3,
                        embed_dim=48).validate(2, 2, 2)


# ----------------------------------------- block-sparse masks (ISSUE 10)

longcontext = pytest.mark.longcontext


@longcontext
@pytest.mark.parametrize("kw", [
    dict(attention_window=8),
    dict(attention_seg_avg=12, attention_seg_seed=4),
    dict(attention_window=12, attention_seg_avg=16),
])
def test_spmd_masked_ring_matches_megatron(eight_devices, kw):
    """The dryrun-matrix certification as a test: for every masked
    config the sparse ring step (hop-verdict gating) must produce the
    SAME training step as megatron applying the identical mask densely
    on the gathered sequence — and the mask must actually skip hops."""
    import dataclasses

    from dlnetbench_tpu.parallel.mesh import make_grid_mesh
    mesh = make_grid_mesh(dp=2, pp=1, tp=4, devices=eight_devices)
    cfg_m = spmd.SpmdConfig(batch=8, num_microbatches=2,
                            capacity_factor=8.0, sp_mode="megatron",
                            **kw)
    cfg_r = dataclasses.replace(cfg_m, sp_mode="ring")
    params = spmd.init_params(jax.random.key(0), cfg_m)
    tokens = jax.random.randint(jax.random.key(1),
                                (8, cfg_m.seq_len + 1), 0,
                                cfg_m.vocab_size)
    p_m, l_m = spmd.make_train_step(mesh, cfg_m)(params, tokens)
    p_r, l_r = spmd.make_train_step(mesh, cfg_r)(params, tokens)
    assert abs(float(l_m) - float(l_r)) <= 1e-4
    for a, b in zip(jax.tree.leaves(p_m), jax.tree.leaves(p_r)):
        assert float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))) <= 1e-4
    stats = cfg_r.ring_hop_stats(4)
    # strict: the mask must skip hops BEYOND the causal triangle
    from dlnetbench_tpu.ops import attention_mask as amask
    assert stats["ring_skipped_hop_fraction"] \
        > amask.ring_skipped_hop_fraction(None, cfg_r.seq_len, 4)
    assert stats["ring_hops"] == 16


@longcontext
def test_spmd_mask_knob_validation_and_stats():
    with pytest.raises(ValueError, match="attention_window"):
        spmd.SpmdConfig(attention_window=-1).validate(2, 2, 2)
    cfg = spmd.SpmdConfig(attention_window=8)
    assert cfg.mask_spec is not None and cfg.mask_spec.window == 8
    assert spmd.SpmdConfig().mask_spec is None
    # plain causal still skips the strictly-future hop triangle
    frac = spmd.SpmdConfig().ring_hop_stats(4)
    assert frac["ring_skipped_hop_fraction"] == pytest.approx(6 / 16)
