"""Hybrid decoder with a per-layer pattern of mixers (the SambaY family,
arXiv:2507.06607): Mamba, sliding-window, full and cross attention and
gated memory units in one stack, differential attention
(arXiv:2410.05258) in every attention layer, LayerNorm with bias around a
SwiGLU MLP, no positional encoding, the embedding tied to the head.

``HybridConfig.layer_kinds`` names each layer's mixer, one of ``KINDS``:

* ``mamba``  — selective state-space mixer (``ops.selective_scan``).  The
  LAST of them is the memory layer: its scan output ``s`` (after the
  skip, before the gate) is handed to every ``gmu`` layer.
* ``window`` / ``full`` — causal self-attention, ``window`` over the
  last ``attention_window`` keys.  The one ``full`` layer's keys and
  values are handed to every ``cross`` layer.
* ``gmu``    — gated memory unit: ``(m * silu(y W1)) W2``.
* ``cross``  — attention with queries of its own over the ``full``
  layer's keys and values (causal), so their gradients sum back there.

Every layer is ``x += mixer(LN(x)); x += SwiGLU(LN(x))``.  Parameters are
stacked by kind (``block`` holds what every layer has: both norms and
the MLP), layers are unrolled as the bench recipe unrolls them, and
``remat`` checkpoints each layer.

Differential attention runs over the kernels ``ops.attention`` already
has: heads are paired by adjacent index, ``(q1, k1)`` and ``(q2, k2)``
are each zero-padded from ``head_dim`` to the value's ``2 * head_dim``
and attend in two calls over ``V = [v1; v2]``; the query is scaled by
sqrt(2) at its projection (before it is rounded to the activation
dtype), so the kernels' ``1 / sqrt(2 * head_dim)`` is the published
``1 / sqrt(head_dim)``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from dlnetbench_tpu import ops
from dlnetbench_tpu.core.model_card import ModelCard
from dlnetbench_tpu.metrics.spans import scope
from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.ops.attention_mask import MaskSpec
from dlnetbench_tpu.ops.selective_scan import selective_scan

_F32 = jnp.float32
KINDS = ("mamba", "window", "full", "gmu", "cross")
# which stack of parameters a layer's mixer reads
GROUP_OF = {"mamba": "mamba", "window": "attn", "full": "attn",
            "gmu": "gmu", "cross": "cross"}
# leaves kept in float32 whatever the model's dtype (the family's
# convention: the recurrence's own parameters, lambdas and norms)
F32_LEAVES = frozenset({
    "norm1", "norm1_b", "norm2", "norm2_b", "final_norm", "final_norm_b",
    "a_log", "d_skip", "b_dt", "conv_b", "sub_norm",
    "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"})
_SPLASH_BLOCKS = (2048, 1024, 512, 256, 128)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    embed_dim: int
    num_heads: int
    num_kv_heads: int
    ff_dim: int
    layer_kinds: tuple
    seq_len: int
    ssm_inner: int
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0            # 0 = embed_dim / 16
    attention_window: int = 512
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False             # jax.checkpoint each layer
    attention_impl: str = "auto"    # ops.attention: auto | flash | xla
    scan_impl: str = "auto"         # ops.selective_scan: auto|pallas|xla
    loss_row_block: int = 0         # head and loss in blocks of this
                                    # many rows, each block's gradients
                                    # taken with its loss, so that
                                    # [T, V] logits never lie whole in
                                    # HBM; 0 = whole

    def __post_init__(self):
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        if not kinds or set(kinds) - set(KINDS):
            raise ValueError(f"layer_kinds {kinds} must name {KINDS}")
        if self.num_heads % 2 or self.num_kv_heads % 2 \
                or self.num_heads % self.num_kv_heads:
            raise ValueError("differential attention pairs adjacent "
                             "heads: head counts must be even")
        first = {k: kinds.index(k) for k in set(kinds)}
        if "gmu" in first and not (
                "mamba" in first and self.memory_layer < first["gmu"]):
            raise ValueError("a gmu layer needs a mamba layer before it")
        if "cross" in first and not (
                kinds.count("full") == 1
                and first["full"] < first["cross"]):
            raise ValueError("cross layers need exactly one full layer "
                             "before them")

    @classmethod
    def from_card(cls, card: ModelCard, *, seq_len: int | None = None,
                  layer_kinds: tuple | None = None,
                  **over) -> "HybridConfig":
        if not card.layer_kinds:
            raise ValueError(f"{card.name} states no layer_kinds; use "
                             f"models.transformer")
        return cls(vocab_size=card.vocab_size, embed_dim=card.embed_dim,
                   num_heads=card.num_heads, num_kv_heads=card.kv_heads,
                   ff_dim=card.ff_dim,
                   layer_kinds=tuple(layer_kinds or card.layer_kinds),
                   seq_len=seq_len or card.seq_len,
                   ssm_inner=card.ssm_inner, ssm_state=card.ssm_state,
                   ssm_conv=card.ssm_conv, ssm_dt_rank=card.ssm_dt_rank,
                   attention_window=card.sliding_window, **over)

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or self.embed_dim // 16

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def memory_layer(self) -> int:
        """The last mamba layer: its scan output is the memory."""
        kinds = self.layer_kinds
        return len(kinds) - 1 - kinds[::-1].index("mamba")

    def index_in_group(self, li: int) -> int:
        group = GROUP_OF[self.layer_kinds[li]]
        return sum(1 for k in self.layer_kinds[:li]
                   if GROUP_OF[k] == group)

    def group_sizes(self) -> dict:
        out = {g: 0 for g in ("mamba", "attn", "gmu", "cross")}
        for k in self.layer_kinds:
            out[GROUP_OF[k]] += 1
        return out


def lambda_init(li: int) -> float:
    """The differential attention's constant of layer ``li`` (its index
    in the model as it is run)."""
    return 0.8 - 0.6 * math.exp(-0.3 * li)


def param_shapes(cfg: HybridConfig) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}: the layout both
    ``init_params`` and the benchmark's seeded weights follow.  ``init``
    is a scale for normal draws, or one of "ones", "zeros", "a_log",
    "b_dt"."""
    d, f, v = cfg.embed_dim, cfg.ff_dim, cfg.vocab_size
    e, n, r, w = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    dh = cfg.head_dim
    dq, dkv = cfg.num_heads * dh, cfg.num_kv_heads * dh
    nl = cfg.num_layers
    sizes = cfg.group_sizes()
    s_d = 1.0 / math.sqrt(d)
    out = {
        "embed": ((v, d), s_d),      # tied: the table is the head too
        "final_norm": ((d,), "ones"),
        "final_norm_b": ((d,), "zeros"),
        "block/norm1": ((nl, d), "ones"),
        "block/norm1_b": ((nl, d), "zeros"),
        "block/norm2": ((nl, d), "ones"),
        "block/norm2_b": ((nl, d), "zeros"),
        "block/w_gate": ((nl, d, f), s_d),
        "block/w_up": ((nl, d, f), s_d),
        "block/w_down": ((nl, f, d), 1.0 / math.sqrt(f)),
    }
    if (m := sizes["mamba"]):
        out.update({
            "mamba/w_in": ((m, d, 2 * e), s_d),
            "mamba/conv_w": ((m, w, e), 1.0 / math.sqrt(w)),
            "mamba/conv_b": ((m, e), "zeros"),
            "mamba/w_x": ((m, e, r + 2 * n), 1.0 / math.sqrt(e)),
            "mamba/w_dt": ((m, r, e), 1.0 / math.sqrt(r)),
            "mamba/b_dt": ((m, e), "b_dt"),
            "mamba/a_log": ((m, e, n), "a_log"),
            "mamba/d_skip": ((m, e), "ones"),
            "mamba/w_out": ((m, e, d), 1.0 / math.sqrt(e)),
        })
    for group, m in (("attn", sizes["attn"]), ("cross", sizes["cross"])):
        if not m:
            continue
        out.update({
            f"{group}/wq": ((m, d, dq), s_d),
            f"{group}/wo": ((m, dq, d), 1.0 / math.sqrt(dq)),
            f"{group}/sub_norm": ((m, 2 * dh), "ones"),
            **{f"{group}/lambda_{x}": ((m, dh), 0.1)
               for x in ("q1", "k1", "q2", "k2")},
        })
        if group == "attn":
            out.update({"attn/wk": ((m, d, dkv), s_d),
                        "attn/wv": ((m, d, dkv), s_d)})
    if (m := sizes["gmu"]):
        out.update({"gmu/w1": ((m, d, e), s_d),
                    "gmu/w2": ((m, e, d), 1.0 / math.sqrt(e))})
    return out


def init_leaf(key, name: str, shape, init, dtype):
    """One leaf of ``param_shapes``.  ``a_log`` is log(1..N) a channel,
    ``b_dt`` the inverse softplus of a step drawn log-uniform in
    [1e-3, 1e-1]; the float32 leaves stay float32."""
    dt = _F32 if name.rsplit("/", 1)[-1] in F32_LEAVES else dtype
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "zeros":
        return jnp.zeros(shape, dt)
    if init == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=_F32)), shape
        ).astype(dt)
    if init == "b_dt":
        step = jnp.exp(jax.random.uniform(
            key, shape, _F32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    return (jax.random.normal(key, shape, _F32) * init).astype(dt)


def init_params(key, cfg: HybridConfig) -> dict:
    spec = param_shapes(cfg)
    tree: dict = {}
    for k, (name, (shape, init)) in zip(
            jax.random.split(key, len(spec)), sorted(spec.items())):
        leaf = init_leaf(k, name, shape, init, cfg.jdtype)
        group, _, sub = name.rpartition("/")
        (tree.setdefault(group, {}) if group else tree)[sub] = leaf
    return tree


# ------------------------------------------------------------- mixers

def _norm(cfg, x, w, b):
    return L.layernorm(x, w, b, cfg.norm_eps).astype(x.dtype)


def _silu(x):
    return jax.nn.silu(x.astype(_F32)).astype(x.dtype)


def _causal_conv(u, w, b):
    """Depthwise causal convolution along time: u [B, S, E], w [K, E]
    (tap K-1 is the current step), b [E]."""
    k, s = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0))).astype(_F32)
    out = b.astype(_F32)
    for i in range(k):
        out = out + up[:, i:i + s] * w[i].astype(_F32)
    return out.astype(u.dtype)


def mamba_mixer(cfg: HybridConfig, y, p):
    """(out [B, S, D], s [B, S, E]: the scan's output, the memory)."""
    e, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
    uz = jnp.dot(y, p["w_in"])
    u = _silu(_causal_conv(uz[..., :e], p["conv_w"], p["conv_b"]))
    xp = jnp.dot(u, p["w_x"])
    delta = jax.nn.softplus(
        jnp.dot(xp[..., :r], p["w_dt"], preferred_element_type=_F32)
        + p["b_dt"])
    with scope("ssm.scan"):
        s = selective_scan(u, delta, -jnp.exp(p["a_log"]),
                           xp[..., r:r + n], xp[..., r + n:],
                           p["d_skip"], cfg.scan_impl)
    return jnp.dot(s * _silu(uz[..., e:]), p["w_out"]), s


def gmu_mixer(y, memory, p):
    return jnp.dot(memory * _silu(jnp.dot(y, p["w1"])), p["w2"])


def _pairs(t, first: int):
    """[B, S, H, dh] -> [B, S, H / 2, 2 * dh]: head ``2p + first`` of
    each adjacent pair, zero-padded to the value's width."""
    b, s, h, dh = t.shape
    t = t.reshape(b, s, h // 2, 2, dh)[:, :, :, first]
    return jnp.concatenate([t, jnp.zeros_like(t)], axis=-1)


def project_kv(cfg: HybridConfig, y, p):
    """(k1, k2, V) as the attention kernels take them."""
    b, s, _ = y.shape
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    k = jnp.dot(y, p["wk"]).reshape(b, s, hkv, dh)
    v = jnp.dot(y, p["wv"]).reshape(b, s, hkv // 2, 2 * dh)
    return _pairs(k, 0), _pairs(k, 1), v


def _splash_block(cfg: HybridConfig, s: int):
    """Blocks no wider than the window, so that the block-sparse
    kernels skip what lies outside it."""
    limit = max(cfg.attention_window, 128)
    return next((b for b in _SPLASH_BLOCKS if b <= limit and s % b == 0),
                None)


def diff_attention(cfg: HybridConfig, y, p, kv, li: int, window: bool):
    """Differential attention of layer ``li`` over ``kv`` = (k1, k2, V)."""
    b, s, d = y.shape
    hq, dh = cfg.num_heads, cfg.head_dim
    k1, k2, v = kv
    # sqrt(2): the kernels divide by sqrt(2 * dh), the model by sqrt(dh)
    q = (jnp.dot(y, p["wq"], preferred_element_type=_F32)
         * math.sqrt(2.0)).astype(y.dtype).reshape(b, s, hq, dh)
    mask, block = None, None
    if window:
        mask = MaskSpec(causal=True, window=cfg.attention_window)
        block = _splash_block(cfg, s)
    a1, a2 = (ops.attention(_pairs(q, i), k, v, causal=True,
                            impl=cfg.attention_impl, mask=mask,
                            block_q=block, block_k=block).astype(_F32)
              for i, k in ((0, k1), (1, k2)))
    lam0 = lambda_init(li)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    o = a1 - lam * a2                                 # [B, S, Hq/2, 2dh]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.norm_eps)
    o = (o * p["sub_norm"] * (1.0 - lam0)).astype(y.dtype)
    return jnp.dot(o.reshape(b, s, hq * dh), p["wo"])


def _layer(cfg: HybridConfig, li: int, x, bp, mp, memory, kv):
    """Layer ``li``: returns (x, handed) where ``handed`` is the memory
    (the memory layer), (k1, k2, V) (the full layer) or None."""
    kind = cfg.layer_kinds[li]
    handed = None
    if kind == "mamba":
        with scope("ssm"):
            out, s = mamba_mixer(cfg, _norm(cfg, x, bp["norm1"],
                                            bp["norm1_b"]), mp)
            x = x + out
        if li == cfg.memory_layer:
            handed = s
    elif kind == "gmu":
        with scope("gmu"):
            x = x + gmu_mixer(_norm(cfg, x, bp["norm1"], bp["norm1_b"]),
                              memory, mp)
    else:
        with scope("attn"):
            y = _norm(cfg, x, bp["norm1"], bp["norm1_b"])
            if kind != "cross":
                kv = project_kv(cfg, y, mp)
            if kind == "full":
                handed = kv
            x = x + diff_attention(cfg, y, mp, kv, li, kind == "window")
    with scope("mlp"):
        y = _norm(cfg, x, bp["norm2"], bp["norm2_b"])
        x = x + L.swiglu(y, bp["w_gate"], bp["w_up"], bp["w_down"])
    return x, handed


def forward(params: dict, tokens, cfg: HybridConfig):
    """tokens [B, S] -> the last layer's output [B, S, D] (before the
    final norm; ``loss_fn`` goes on from it)."""
    with scope("embed"):
        x = params["embed"][tokens]
    layer = _layer
    if cfg.remat:
        layer = jax.checkpoint(_layer, static_argnums=(0, 1))
    memory = kv = None
    for li, kind in enumerate(cfg.layer_kinds):
        gi = cfg.index_in_group(li)
        bp = jax.tree.map(lambda a: a[li], params["block"])
        mp = jax.tree.map(lambda a: a[gi], params[GROUP_OF[kind]])
        x, handed = layer(cfg, li, x, bp, mp, memory, kv)
        if kind == "mamba" and handed is not None:
            memory = handed
        elif kind == "full":
            kv = handed
    return x


def loss_fn(params: dict, tokens, cfg: HybridConfig):
    """Next-token cross-entropy on a [B, S+1] token batch.  Where the
    rows are a multiple of ``cfg.loss_row_block`` the tied head and the
    loss run block by block and each block leaves its gradients behind
    (``layers.blocked_head_cross_entropy``); the final norm is taken
    over all rows at once either way."""
    x = forward(params, tokens[:, :-1], cfg)
    targets = tokens[:, 1:]
    with scope("head_loss"):
        x = _norm(cfg, x, params["final_norm"], params["final_norm_b"])
        rows, block = x.shape[0] * x.shape[1], cfg.loss_row_block
        if not block or rows <= block or rows % block:
            return L.cross_entropy(jnp.dot(x, params["embed"].T), targets)
        return L.blocked_head_cross_entropy(
            x.reshape(rows, -1), params["embed"], targets.reshape(rows),
            block)
