"""Real-model tests: shapes, finiteness, gradient flow, and learning on
tiny configs (CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlnetbench_tpu.core.model_card import load_model_card
from dlnetbench_tpu.models import transformer as tfm
from dlnetbench_tpu.models import vit as vitm


def _tiny_cfg(card_name="llama3_8b", **kw):
    card = load_model_card(card_name)
    cfg = tfm.TransformerConfig.from_card(card, seq_len=32, num_layers=2,
                                          vocab_size=64)
    return tfm.TransformerConfig(**{**cfg.__dict__, "embed_dim": 64,
                                    "num_heads": 4, "num_kv_heads": 2,
                                    "ff_dim": 128, "dtype": "float32", **kw})


def test_llama_forward_shapes():
    cfg = _tiny_cfg()
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_gpt2_forward():
    cfg = _tiny_cfg("gpt2_l", max_positions=32)
    assert not cfg.gated
    params = tfm.init_params(jax.random.key(0), cfg)
    assert "pos_embed" in params and "head" not in params  # tied embeddings
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_moe_forward():
    cfg = _tiny_cfg("mixtral_8x7b")
    assert cfg.num_experts == 8 and cfg.top_k == 2
    params = tfm.init_params(jax.random.key(0), cfg)
    assert params["layers"]["w_gate"].shape == (2, 8, 64, 128)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits = tfm.forward(params, tokens, cfg)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_moe_sparse_matches_dense_at_full_capacity():
    """At capacity_factor >= E/top_k no token drops, so the capacity-based
    dispatch must reproduce the dense-dispatch result exactly (modulo
    accumulation order)."""
    import dataclasses
    cfg = _tiny_cfg("mixtral_8x7b")
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    dense = tfm.forward(params, tokens, cfg)
    sparse_cfg = dataclasses.replace(
        cfg, moe_impl="sparse",
        moe_capacity_factor=cfg.num_experts / cfg.top_k)
    sparse = tfm.forward(params, tokens, sparse_cfg)
    np.testing.assert_allclose(np.asarray(dense, np.float32),
                               np.asarray(sparse, np.float32),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("impl", ["sparse", "grouped"])
def test_moe_gather_gradients_match_dense_at_full_capacity(impl):
    """The hand-written backward of the row-gather dispatch and combine
    against plain autodiff of ``moe_dense``, which has no dispatch:
    with no token dropped the loss and every leaf's gradient agree."""
    import dataclasses
    cfg = _tiny_cfg("mixtral_8x7b")
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 17), 0,
                                cfg.vocab_size)
    gather_cfg = dataclasses.replace(
        cfg, moe_impl=impl,
        moe_capacity_factor=cfg.num_experts / cfg.top_k)
    want, got = (jax.jit(jax.value_and_grad(
        lambda p, c=c: tfm.loss_fn(p, tokens, c)))(params)
        for c in (cfg, gather_cfg))
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    for (path, a), b in zip(jax.tree.leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale, path


def test_moe_sparse_trains_and_drops_gracefully():
    """At the production capacity factor (1.25) some tokens drop; the
    forward stays finite and the loss still falls under SGD (dropped
    tokens ride the residual)."""
    import dataclasses
    cfg = dataclasses.replace(_tiny_cfg("mixtral_8x7b"), moe_impl="sparse")
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 17), 0,
                                cfg.vocab_size)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(tfm.loss_fn)(p, tokens, cfg)
        return jax.tree.map(lambda a, b: a - 0.05 * b.astype(a.dtype),
                            p, g), loss

    losses = []
    for _ in range(8):
        params, loss = step(params)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_unknown_impl_rejected():
    import dataclasses
    with pytest.raises(ValueError, match="moe_impl"):
        dataclasses.replace(_tiny_cfg("mixtral_8x7b"), moe_impl="topk")


def test_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = _tiny_cfg()
    params = tfm.init_params(jax.random.key(0), cfg)
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(5)
    l1 = tfm.forward(params, t1, cfg)
    l2 = tfm.forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, :10]), np.asarray(l2[0, :10]),
                               rtol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_loss_decreases_with_sgd():
    cfg = _tiny_cfg()
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (4, 17), 0, cfg.vocab_size)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(tfm.loss_fn)(p, tokens, cfg)
        return loss, jax.tree.map(lambda a, b: a - 0.5 * b, p, g)

    losses = []
    for _ in range(8):
        loss, params = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_vit_forward_and_grad():
    card = load_model_card("vit_b")
    cfg = vitm.ViTConfig.from_card(card, num_layers=2, image_size=32)
    cfg = vitm.ViTConfig(**{**cfg.__dict__, "embed_dim": 64, "num_heads": 4,
                            "ff_dim": 128, "num_classes": 10,
                            "dtype": "float32"})
    params = vitm.init_params(jax.random.key(0), cfg)
    images = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    logits = jax.jit(lambda p: vitm.forward(p, images, cfg))(params)
    assert logits.shape == (2, 10)
    labels = jnp.array([1, 3])
    g = jax.jit(jax.grad(
        lambda p: vitm.loss_fn(p, images, labels, cfg)))(params)
    leaves = jax.tree.leaves(g)
    assert all(np.all(np.isfinite(np.asarray(x, dtype=np.float32)))
               for x in leaves)
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in leaves)


def test_vit_card_guard():
    card = load_model_card("llama3_8b")
    with pytest.raises(ValueError, match="not a ViT"):
        vitm.ViTConfig.from_card(card)
    with pytest.raises(ValueError, match="ViT card"):
        tfm.TransformerConfig.from_card(load_model_card("vit_b"))


def test_remat_agrees_with_no_remat():
    """``remat=True`` (each block recomputed in the backward) must give
    the same loss and gradients as storing the activations, through
    both layer-stack forms."""
    cfg0 = tfm.TransformerConfig(
        vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, ff_dim=64,
        num_layers=2, seq_len=16, gated=True, max_positions=0,
        dtype="float32")
    params = tfm.init_params(jax.random.key(0), cfg0)
    tokens = jax.random.randint(jax.random.key(1), (2, 17), 0, 64)

    def lg(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tokens, cfg)))(params)

    l0, g0 = lg(cfg0)
    for scan_layers in (True, False):
        l1, g1 = lg(dataclasses.replace(cfg0, remat=True,
                                        scan_layers=scan_layers))
        assert jnp.allclose(l0, l1, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)


_STEP_TINY = dict(vocab_size=128, embed_dim=32, num_heads=4, num_kv_heads=2,
                  ff_dim=64, num_layers=2, seq_len=16, gated=True,
                  max_positions=0)
_STEP_CONFIGS = {
    "dense_bf16": dict(),
    "dense_gelu": dict(gated=False, max_positions=32),
    "dense_int8_composed": dict(mlp_dtype="int8"),
    "dense_int8_fused": dict(mlp_dtype="int8", quant_fusion="fused"),
    "dense_float8_composed": dict(mlp_dtype="float8"),
    "moe_sparse": dict(num_experts=4, top_k=2, moe_impl="sparse"),
    "moe_grouped": dict(num_experts=4, top_k=2, moe_impl="grouped"),
}


@pytest.mark.parametrize("name", [*_STEP_CONFIGS, "hybrid"])
def test_step_builder_has_one_contract(name):
    """``make_train_k(cfg, k, lr)`` is ``train_k(params, tokens) ->
    (params', losses[k])`` for every model family and MLP recipe: the
    state that comes back has the tree the caller handed in (both
    benchmark runners feed it to the next call, donated), and the
    losses are finite."""
    from dlnetbench_tpu.models import bench_step, hybrid
    if name == "hybrid":
        cfg = hybrid.HybridConfig(
            vocab_size=128, embed_dim=32, num_heads=4, num_kv_heads=2,
            ff_dim=64, seq_len=16, ssm_inner=64, ssm_dt_rank=2,
            attention_window=8,
            layer_kinds=("mamba", "window", "full", "gmu", "cross"))
        params = hybrid.init_params(jax.random.key(0), cfg)
    else:
        cfg = tfm.TransformerConfig(**{**_STEP_TINY,
                                       **_STEP_CONFIGS[name]})
        params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.seq_len + 1),
                                0, cfg.vocab_size)
    train_k = jax.jit(bench_step.make_train_k(cfg, 2, 1e-2),
                      donate_argnums=bench_step.DONATE_ARGNUMS)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    out = train_k(params, tokens)
    assert isinstance(out, tuple) and len(out) == 2
    new_params, losses = out
    assert jax.tree.map(lambda a: (a.shape, a.dtype), new_params) == shapes
    assert losses.shape == (2,)
    assert bool(jnp.all(jnp.isfinite(losses)))
