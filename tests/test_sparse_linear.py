"""The sparse-and-linear hybrid decoder (``models/hybrid.py`` with
``sparse`` and ``lightning`` layers, dense SwiGLUs and MiniCPM's three
scalars) against the benchmark's plain reference
(``benchmarks/reference_sparse_linear.py``: float32, precision
"highest", nothing of the program) at the cell's rehearsal sizes with a
sequence longer than ``dense_len``: loss, selections and every gradient
leaf, with and without ``remat``; the lists a checkpoint keeps; the
layer at or under ``dense_len``; the scalars."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks import reference_sparse_linear as ref
from benchmarks import weights_sparse_linear as weights
from benchmarks.runners import train_sparse_linear
from dlnetbench_tpu.models import bench_step, hybrid
from dlnetbench_tpu.ops import sparse_attention as sa

CELL = "minicpm_sala_train_s16k"
SEQ = 128


@pytest.fixture(scope="module")
def arch():
    cell = harness.rehearsal(harness.load_cell(CELL))
    assert cell.traffic["seq_len"] == SEQ
    return weights.arch_of(cell.config)


def config(arch, seq=SEQ, **over):
    return train_sparse_linear.config_of(
        arch, seq, **{"attention_impl": "xla", "loss_row_block": 64,
                      **over})


def with_(cfg, **over):
    return hybrid.HybridConfig(**{**cfg.__dict__, **over})


def tokens(arch, seq=SEQ, rows=2):
    return weights.make_token_pool(7, 1, rows, seq + 1,
                                   arch["vocab_size"])[0]


def moved(params, seed=9):
    """The seeded weights with every norm's weight drawn away from one."""
    keys = iter(jax.random.split(jax.random.key(seed), 64))

    def leaf(path, a):
        name = path[-1].key
        if "norm" in name:
            return a + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(leaf, params)


@pytest.fixture(scope="module")
def reference(arch):
    """(params, tokens, loss, gradients by name, lists) of the reference,
    made once; its backward a layer at a time (what the timed size
    runs) is autodiff of the whole loss."""
    p, t = moved(weights.make_params(arch, 7)), tokens(arch)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss_fn(p, t, arch)))(p, t)
        loss_l, grads_l, lists = ref.LayerwiseGrad(arch)(
            ref.unstack(p, arch), t)
    want, by_layer = ref._names(grads, arch), ref._names(grads_l, arch)
    assert float(loss_l) == pytest.approx(float(loss), rel=1e-6)
    assert set(want) == set(by_layer) and len(lists) == 1
    for k in want:
        assert float(jnp.linalg.norm(by_layer[k] - want[k])
                     / jnp.linalg.norm(want[k])) <= 2e-5, k
    return p, t, float(loss), want, np.asarray(jnp.stack(lists))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_selection_and_every_gradient_leaf_against_the_reference(
        arch, reference, remat):
    p, t, want_loss, want, want_lists = reference
    cfg = config(arch, remat=remat)
    assert cfg.has_selection and cfg.returns_aux and not cfg.has_experts
    (loss, picked), grads = jax.jit(
        jax.value_and_grad(hybrid.loss_and_routing, has_aux=True),
        static_argnums=2)(p, t, cfg)
    assert float(loss) == pytest.approx(want_loss, rel=2e-6)
    got = ref._names(grads, arch)
    assert set(got) == set(want)
    for name in want:
        err = float(jnp.linalg.norm(got[name] - want[name])
                    / jnp.linalg.norm(want[name]))
        assert err <= 5e-5, (name, err)
    assert set(picked) == set(hybrid.SELECTION)
    assert np.array_equal(np.asarray(picked["blocks"]), want_lists)
    assert picked["blocks"].shape == (1, 2, SEQ, 2, 4)
    assert int(picked["selected"]) == int((want_lists >= 0).sum())
    assert int(picked["selected"]) <= int(picked["visited"])


def selections(jaxpr: str) -> int:
    """How often a program ranks block scores: a ranking ends in the
    step's only int32 ``reduce_max`` (``sparse_attention._best_blocks``:
    the block at each place of the list)."""
    return len(re.findall(r"i32\[[\d,]*\] = reduce_max", jaxpr))


def test_a_checkpoint_keeps_the_lists_and_selects_once_a_step(arch):
    """The step's program ranks its block scores once a sparse layer:
    the backward's recomputation reads the kept lists (and the kept
    output and lse: one forward kernel)."""
    cfg = config(arch, remat=True)
    p, t = weights.make_params(arch, 7), tokens(arch)
    step = jax.jit(bench_step.make_train_k(cfg, 1, 0.1))
    jaxpr = str(jax.make_jaxpr(step)(p, t))
    assert selections(jaxpr) == 1
    # nor is the plan of visits made again: its two sorts (the visited
    # tiles by row tile and by key tile), once
    assert jaxpr.count("jit[name=argsort") == 2
    assert jaxpr.count("name=sparse_fwd") == 1
    assert jaxpr.count("name=sparse_bwd_dq") == 1
    assert jaxpr.count("name=sparse_bwd_dkv") == 1
    # without the policy's names the recomputation selects again
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid, "_KEPT", ())
        assert selections(str(jax.make_jaxpr(jax.jit(
            bench_step.make_train_k(cfg, 1, 0.1)))(p, t))) == 2
    plain = with_(cfg, remat=False)
    jaxpr = str(jax.make_jaxpr(jax.jit(
        bench_step.make_train_k(plain, 1, 0.1)))(p, t))
    assert selections(jaxpr) == 1
    from dlnetbench_tpu.metrics import spans
    jax.clear_caches()      # a cached trace marks nothing
    tracer = spans.enable()
    try:
        with spans.span("compile"):
            jax.make_jaxpr(jax.jit(bench_step.make_train_k(cfg, 1, 0.1)))(
                p, t)
    finally:
        spans.disable()
    attrs = next(s["attrs"] for s in tracer.export()["spans"]
                 if s["name"] == "compile")
    kept = [(m["kind"], m["value"]) for m in attrs["remat.kept"]]
    # the lists and the five arrays ``plan_visits`` makes of them
    assert sorted(kept) == sorted(
        [("sparse", "attn_out"), ("sparse", "attn_lse")]
        + [("sparse", sa.BLOCKS_NAME)] * 6)
    grids = {m["kernel"]: m for m in attrs["sparse.grid"]}
    assert set(grids) == {"sparse_fwd", "sparse_bwd_dq", "sparse_bwd_dkv"}
    assert all(0 < m["live"] <= m["steps"] for m in grids.values())


def test_at_or_under_dense_len_a_sparse_layer_is_a_gated_nope_layer(arch):
    """Bit for bit: the same projections, norms and gate, every earlier
    key, no position; nothing is selected and the step returns a loss
    alone."""
    seq = arch["sparse_sizes"][6]
    cfg = config(arch, seq=seq)
    assert not cfg.has_selection and not cfg.returns_aux
    nope = with_(cfg, layer_kinds=("nope",) + cfg.layer_kinds[1:])
    p, t = moved(weights.make_params(arch, 7)), tokens(arch, seq)
    a, b = (jax.jit(jax.value_and_grad(hybrid.loss_fn), static_argnums=2)(
        p, t, c) for c in (cfg, nope))
    assert float(a[0]) == float(b[0])
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(
        jax.tree.leaves(a[1]), jax.tree.leaves(b[1])))
    y = jax.random.normal(jax.random.key(2), (1, seq, cfg.embed_dim))
    mp = jax.tree.map(lambda w: w[0], p["gated"])
    out, picked = hybrid.sparse_mixer(cfg, y, mp)
    assert picked is None
    assert bool(jnp.array_equal(out, hybrid.gated_mixer(cfg, y, mp, "nope")))
    # and the reference agrees with both at that length
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss_fn(p, t, arch))
    assert float(a[0]) == pytest.approx(want, rel=2e-6)


def test_the_three_scalars_and_the_published_depth(arch):
    """``c`` and the decay read the PUBLISHED depth 32, not the cut's
    4; a scalar of 1.0 is no operation of the program."""
    cfg = config(arch)
    assert cfg.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert (cfg.embed_scale, cfg.lightning_depth) == (12.0, 32)
    assert cfg.logit_scale == arch["logit_scale"]
    assert cfg.sparse_sizes == arch["sparse_sizes"]
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim) \
        == (4, 4, 16)
    p, t = weights.make_params(arch, 7), tokens(arch, rows=1)
    base = float(hybrid.loss_and_routing(p, t, cfg)[0])
    for over in ({"residual_scale": 1.0}, {"embed_scale": 1.0},
                 {"logit_scale": 1.0}, {"lightning_depth": 0}):
        other = float(hybrid.loss_and_routing(p, t, with_(cfg, **over))[0])
        assert abs(other - base) > 1e-4, over
    plain = with_(cfg, residual_scale=1.0, embed_scale=1.0, logit_scale=1.0)
    scaled = str(jax.make_jaxpr(lambda p: hybrid.loss_fn(p, t, cfg))(p))
    unscaled = str(jax.make_jaxpr(lambda p: hybrid.loss_fn(p, t, plain))(p))
    assert scaled.count(" mul ") - unscaled.count(" mul ") == 2 * 4 + 2


def test_a_configuration_refuses_what_no_layer_computes(arch):
    cfg = config(arch)
    for over, match in (
            ({"sparse_sizes": (8, 4, 16)}, "seven"),
            ({"sparse_sizes": (8, 3, 16, 4, 32, 1, 64)}, "stride"),
            ({"gdn_value_heads": 2}, "lightning layers need"),
            ({"gdn_key_dim": 15}, "lightning layers need"),
            ({"layer_kinds": ("sparse", "linear")}, "must name")):
        with pytest.raises(ValueError, match=match):
            with_(cfg, **over)


def test_benchmark_weights_follow_the_programs_layout(arch):
    cfg = config(arch)
    mine = {k: v[0] for k, v in weights.shapes(arch).items()}
    theirs = {k: v[0] for k, v in hybrid.param_shapes(cfg).items()}
    assert mine == theirs
    assert set(theirs) >= {"lightning/o_norm", "lightning/wz", "gated/wq"}
    f32 = {k.rsplit("/", 1)[-1] for k in mine} & hybrid.F32_LEAVES
    assert f32 == set(weights.F32_LEAVES)
    p = weights.make_params(arch, 7)
    assert p["gated"]["wq"].shape == (1, 64, 2 * 8 * 16)
    assert p["lightning"]["o_norm"].dtype == jnp.float32


def test_card_states_the_layers_and_the_config_follows_it():
    from dlnetbench_tpu.core.model_card import load_model_card
    card = load_model_card("minicpm_sala")
    assert card.layer_kinds.count("sparse") == 8
    assert card.layer_kinds[:4] == ("sparse",) + ("lightning",) * 3
    cfg = hybrid.HybridConfig.from_card(
        card, seq_len=16384, layer_kinds=card.layer_kinds[:4])
    assert cfg.has_selection and cfg.lightning_depth == 32
    assert cfg.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert cfg.logit_scale == 256 / 4096 and cfg.embed_scale == 12.0
    assert cfg.sparse_sizes == (32, 16, 64, 64, 2048, 1, 8192)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.attn_gate,
            cfg.head_norm, cfg.tied_head) == (32, 2, 128, True, True, False)
    shapes = hybrid.param_shapes(cfg)
    count = sum(math.prod(s) for s, _ in shapes.values())
    assert count == 1_711_117_696 + 0   # ISSUE 50's 1711.1 M
    assert not hybrid.HybridConfig.from_card(
        card, seq_len=8192, layer_kinds=card.layer_kinds[:4]).has_selection
    assert card.num_params() == pytest.approx(9.48e9, rel=5e-3)
