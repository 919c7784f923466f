"""models/hybrid.py: the hybrid decoder of five kinds of layer against
the benchmark's plain reference and against dense formulas, at a small
size in float32 on the CPU."""
import math

import jax
import jax.numpy as jnp
import pytest

from benchmarks import reference_hybrid as ref
from benchmarks import weights_hybrid
from dlnetbench_tpu import ops
from dlnetbench_tpu.core.model_card import load_model_card
from dlnetbench_tpu.models import bench_step, hybrid
from dlnetbench_tpu.models import layers as L

KINDS = ("mamba", "window", "mamba", "window", "mamba", "full", "gmu",
         "cross", "gmu", "cross")
CONFIG = {"num_attention_heads": 8, "num_key_value_heads": 4,
          "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
          "layer_kinds": list(KINDS), "num_hidden_layers": 10,
          "sliding_window": 16, "layer_norm_eps": 1e-5,
          "torch_dtype": "float32",
          "assumed": {"ssm_inner": 128, "ssm_state": 16, "ssm_conv": 4,
                      "ssm_dt_rank": 4}}


def config(**over):
    return hybrid.HybridConfig(
        vocab_size=256, embed_dim=64, num_heads=8, num_kv_heads=4,
        ff_dim=128, layer_kinds=KINDS, seq_len=64, ssm_inner=128,
        ssm_state=16, ssm_dt_rank=4, attention_window=16,
        dtype="float32", **over)


@pytest.fixture(scope="module")
def case():
    arch = weights_hybrid.arch_of(CONFIG)
    params = weights_hybrid.make_params(arch, 2**31 + 5)
    tokens = jax.random.randint(jax.random.key(1), (2, 65), 0, 256)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(p, tokens, arch)))(params)
    return arch, params, tokens, want


def leaves(tree):
    """{name: leaf of one layer}."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(k.key) for k in path)
        if len(path) > 1:
            out.update({f"{name}/{i}": a[i] for i in range(a.shape[0])})
        else:
            out[name] = a
    return out


def test_benchmark_weights_follow_the_programs_layout():
    arch = weights_hybrid.arch_of(CONFIG)
    assert weights_hybrid.shapes(arch) == hybrid.param_shapes(config())
    made = weights_hybrid.make_params(arch, 3)
    own = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(3), config()))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), made) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), own)


@pytest.mark.parametrize("remat,rows", [(False, 0), (True, 32)])
def test_loss_and_every_gradient_leaf_against_the_reference(
        case, remat, rows):
    arch, params, tokens, (want_loss, want_grad) = case
    cfg = config(remat=remat, loss_row_block=rows)
    with jax.default_matmul_precision("highest"):
        loss, grad = jax.jit(jax.value_and_grad(
            lambda p: hybrid.loss_fn(p, tokens, cfg)))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got, want = leaves(grad), leaves(want_grad)
    assert set(got) == set(want)
    for name in want:
        gap = float(jnp.linalg.norm(got[name] - want[name])
                    / (jnp.linalg.norm(want[name]) + 1e-30))
        assert gap < 2e-4, (name, gap)


def test_full_layers_keys_and_values_hold_every_cross_layers_part(case):
    """The gradient of the full layer's W_k and W_v is a sum over the
    layers that read its keys and values.  With one cross layer's read
    cut off (backward only) that layer's part is missing, so
    g(cut 7) + g(cut 9) = g(whole) + g(cut both), and each part is
    there to miss."""
    arch, params, tokens, (_, whole) = case
    cfg = config()
    real = hybrid.diff_attention

    @jax.jit        # one build: which layers are cut is an argument
    def grad_with_cut(is_cut):
        def cut(cfg_, y, p, kv, li, window):
            kv = jax.tree.map(lambda a: jnp.where(
                is_cut[li], jax.lax.stop_gradient(a), a), kv)
            return real(cfg_, y, p, kv, li, window)
        hybrid.diff_attention = cut
        try:
            with jax.default_matmul_precision("highest"):
                return jax.grad(
                    lambda p: hybrid.loss_fn(p, tokens, cfg))(params)
        finally:
            hybrid.diff_attention = real
    cross = [li for li, k in enumerate(KINDS) if k == "cross"]
    assert cross == [7, 9]
    cut7, cut9, both = (
        grad_with_cut(jnp.isin(jnp.arange(len(KINDS)), jnp.array(c)))
        for c in ([7], [9], [7, 9]))
    full = cfg.index_in_group(KINDS.index("full"))
    for leaf in ("wk", "wv"):
        g, g7, g9, g0 = (t["attn"][leaf][full]
                         for t in (whole, cut7, cut9, both))
        scale = float(jnp.linalg.norm(g))
        assert float(jnp.linalg.norm(g7 + g9 - g - g0)) < 1e-4 * scale
        for part in (g - g7, g - g9, g0):
            assert float(jnp.linalg.norm(part)) > 1e-2 * scale


def test_gmu_reads_the_last_mamba_layers_scan_output(case):
    arch, params, tokens, _ = case
    cfg = config()
    assert cfg.memory_layer == 4
    seen = {}
    real = hybrid.gmu_mixer

    def spy(y, memory, p):
        seen.setdefault("memory", memory)
        return real(y, memory, p)
    hybrid.gmu_mixer = spy
    try:
        hybrid.forward(params, tokens[:, :-1], cfg)
    finally:
        hybrid.gmu_mixer = real
    x = params["embed"][tokens[:, :-1]]
    for li in range(5):
        bp = jax.tree.map(lambda a: a[li], params["block"])
        mp = jax.tree.map(lambda a: a[cfg.index_in_group(li)],
                          params[cfg.group_of(KINDS[li])])
        x, handed, _ = hybrid._layer(cfg, li, x, bp, mp, bp, None, None)
    assert jnp.allclose(seen["memory"], handed, rtol=1e-5, atol=1e-6)


def dense_diff_attention(q, k, v, lam, window):
    """[S, P, 2, dh] queries, [S, Pkv, 2, dh] keys, [S, Pkv, 2 dh]
    values: the formula, one pair at a time."""
    s, pairs, _, dh = q.shape
    group = pairs // k.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = (j <= i) & ((i - j < window) if window else True)
    out = []
    for p in range(pairs):
        maps = []
        for h in range(2):
            sc = q[:, p, h] @ k[:, p // group, h].T / math.sqrt(dh)
            maps.append(jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf)))
        out.append((maps[0] - lam * maps[1]) @ v[:, p // group])
    return jnp.stack(out, 1)


@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_differential_attention_against_the_dense_formula(window, impl):
    """Through ``ops.attention`` both ways (the kernels in interpret
    mode): pairs padded to the value's width, the sqrt(2) at the
    query's projection, the window's mask."""
    cfg = config(attention_impl=impl)
    s, d, dh = 128, cfg.embed_dim, cfg.head_dim
    ks = jax.random.split(jax.random.key(4), 8)
    y = jax.random.normal(ks[0], (1, s, d))
    p = {"wq": jax.random.normal(ks[1], (d, d)) / 8,
         "wk": jax.random.normal(ks[2], (d, d // 2)) / 8,
         "wv": jax.random.normal(ks[3], (d, d // 2)) / 8,
         "wo": jnp.eye(d), "sub_norm": jnp.ones(2 * dh),
         **{f"lambda_{n}": jax.random.normal(k, (dh,)) * 0.1
            for n, k in zip(("q1", "k1", "q2", "k2"), ks[4:])}}
    li = 3
    cfg_w = hybrid.HybridConfig(**{**cfg.__dict__, "attention_window":
                                   window or 512})
    with jax.default_matmul_precision("highest"):
        got = hybrid.diff_attention(cfg_w, y, p,
                                    hybrid.project_kv(cfg_w, y, p), li,
                                    bool(window))[0]
        lam0 = hybrid.lambda_init(li)
        lam = (math.exp(float(p["lambda_q1"] @ p["lambda_k1"]))
               - math.exp(float(p["lambda_q2"] @ p["lambda_k2"])) + lam0)
        o = dense_diff_attention(
            (y[0] @ p["wq"]).reshape(s, 4, 2, dh),
            (y[0] @ p["wk"]).reshape(s, 2, 2, dh),
            (y[0] @ p["wv"]).reshape(s, 2, 2 * dh), lam, window)
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        want = (o * (1 - lam0)).reshape(s, d)
    assert jnp.allclose(got, want, rtol=2e-4, atol=2e-5)


def test_window_layers_mask_is_the_last_w_keys():
    """A key W or more steps back moves nothing; one W - 1 back does."""
    q = jax.random.normal(jax.random.key(0), (1, 64, 2, 8))
    k = jax.random.normal(jax.random.key(1), (1, 64, 2, 8))
    v = jax.random.normal(jax.random.key(2), (1, 64, 2, 8))
    spec = hybrid.MaskSpec(causal=True, window=16)

    def last_row(v_):
        return ops.attention(q, k, v_, causal=True, impl="xla",
                             mask=spec)[0, -1]
    bump = jnp.zeros_like(v)
    assert jnp.allclose(last_row(v), last_row(v + bump.at[0, 47].set(9.)))
    assert not jnp.allclose(last_row(v),
                            last_row(v + bump.at[0, 48].set(9.)))


def test_one_step_builder_builds_the_hybrid_step(case):
    arch, params, tokens, (want_loss, _) = case
    cfg = config(remat=True)
    step = jax.jit(bench_step.make_train_k(cfg, 2, 0.05))
    new, losses = step(params, tokens)
    assert losses.shape == (2,)
    assert abs(float(losses[0]) - float(want_loss)) < 1e-3
    assert float(losses[1]) < float(losses[0])
    assert jax.tree.map(lambda a: a.dtype, new) \
        == jax.tree.map(lambda a: a.dtype, params)


def test_card_states_the_pattern_and_config_follows_it():
    card = load_model_card("phi4_mini_flash_reasoning")
    assert len(card.layer_kinds) == card.num_layers == 32
    cfg = hybrid.HybridConfig.from_card(card, seq_len=128)
    assert cfg.layer_kinds == card.layer_kinds
    assert cfg.memory_layer == 16 and cfg.layer_kinds[17] == "full"
    assert (cfg.head_dim, cfg.dt_rank, cfg.ssm_inner) == (64, 160, 5120)
    assert cfg.group_sizes() == {"mamba": 9, "attn": 9, "gmu": 7,
                                 "cross": 7, "mla": 0, "gdn": 0,
                                 "gated": 0, "conv": 0, "lightning": 0}
    with pytest.raises(ValueError, match="a mamba layer before"):
        hybrid.HybridConfig.from_card(card, layer_kinds=("gmu", "mamba"))
    with pytest.raises(ValueError, match="exactly one full"):
        hybrid.HybridConfig.from_card(card, layer_kinds=("window", "cross"))
    with pytest.raises(ValueError, match="states no layer_kinds"):
        hybrid.HybridConfig.from_card(load_model_card("minerva_7b"))


def head_case(dtype, rows=256, d=64, v=512, seed=0):
    kx, kt, ky = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(kx, (rows, d), jnp.float32)
    table = jax.random.normal(kt, (v, d), jnp.float32) / math.sqrt(d)
    targets = jax.random.randint(ky, (rows,), 0, v)
    return x.astype(dtype), table.astype(dtype), targets


def whole_head(x, table, targets):
    return L.cross_entropy(jnp.dot(x, table.T), targets)


def checkpointed_head(x, table, targets, block):
    """The blocked head as it was before the fused form: every block's
    logits made again in the backward."""
    xb = x.reshape(-1, block, x.shape[-1])
    part = jax.checkpoint(lambda xt: whole_head(xt[0], table, xt[1]))
    return jnp.mean(jax.lax.map(part, (xb, targets.reshape(-1, block))))


def rel(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_blocked_head_loss_and_gradients_against_whole_logits(blocks):
    x, table, targets = head_case(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, (wx, wt) = jax.value_and_grad(whole_head, (0, 1))(
            x, table, targets)
        loss, (gx, gt) = jax.jit(jax.value_and_grad(
            lambda a, b: L.blocked_head_cross_entropy(
                a, b, targets, x.shape[0] // blocks), (0, 1)))(x, table)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert rel(gx, wx) < 1e-5 and rel(gt, wt) < 1e-5


@pytest.mark.parametrize("cotangent", [3.0, -0.5])
def test_blocked_head_scales_its_gradients_by_the_cotangent(cotangent):
    x, table, targets = head_case(jnp.float32)

    def grads(scale):
        return jax.grad(lambda a, b: scale * L.blocked_head_cross_entropy(
            a, b, targets, 64), (0, 1))(x, table)
    for got, one in zip(grads(cotangent), grads(1.0)):
        assert rel(got, cotangent * one) < 1e-6


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_blocked_head_in_bf16_is_no_further_from_float32_than_before(seed):
    """Same operands, same blocks: the logits' gradient is rounded to
    bf16 once where autodiff rounded the softmax and then added the
    target's -1/rows in bf16, and a block's share of the table's
    gradient joins the carry in float32: the table's gradient comes
    closer to the float32 one, x's stays where it was."""
    x, table, targets = head_case(jnp.bfloat16, seed=seed)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(whole_head, (0, 1))(
            x.astype(jnp.float32), table.astype(jnp.float32), targets)
    fused = jax.grad(lambda a, b: L.blocked_head_cross_entropy(
        a, b, targets, 64), (0, 1))(x, table)
    before = jax.grad(lambda a, b: checkpointed_head(a, b, targets, 64),
                      (0, 1))(x, table)
    (gx, gt), (ox, ot), (wx, wt) = fused, before, want
    assert gx.dtype == gt.dtype == jnp.bfloat16
    assert rel(gt, wt) < rel(ot, wt)
    assert rel(gx, wx) < rel(ox, wx) * 1.02
