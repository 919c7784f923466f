"""The plain reference every cell's ``correct`` is decided against.

The gated decoder family the two configurations share (RMSNorm, RoPE,
grouped-query causal attention, SwiGLU, and top-k routed experts under a
capacity rule), written in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes nothing the program made: weights come from
``benchmarks/weights.py`` (made from the seed), tokens from the runner.

What is not plain is only what makes the timed sizes fit beside nothing
else on one chip: backpropagation goes one layer at a time, attention
one (batch row, head) at a time, the experts one at a time, and the
MLP, the experts and the head in blocks of rows.  The arithmetic is the
textbook's.

``precision="int8"`` is the CONTROL, not a reference: the same code with
every weight matmul's operands (forward and backward) rounded to a
per-tensor symmetric int8 grid, the nearest precision below the bf16 the
configurations state.  ``correct`` must come out false for it.

Departures from the published models, all the program's own: RoPE theta
10000 and RMSNorm eps 1e-6 for both configurations; no sliding window;
experts under a capacity factor in arrival order (the published Mixtral
drops no token).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROW_BLOCK = 2048
EPS = 1e-6
ROPE_THETA = 10000.0


# ----------------------------------------------------------- matmuls
def _mm_f32(a, b):
    return jnp.dot(a, b, precision="highest", preferred_element_type=F32)


def _q8(t):
    """Per-tensor symmetric int8 grid, kept in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 127.0
    return jnp.clip(jnp.round(t / s), -127, 127) * s


@jax.custom_vjp
def _mm_int8(a, b):
    return _mm_f32(_q8(a), _q8(b))


def _mm_int8_fwd(a, b):
    qa, qb = _q8(a), _q8(b)
    return _mm_f32(qa, qb), (qa, qb)


def _mm_int8_bwd(res, dy):
    qa, qb = res
    qd = _q8(dy)
    return _mm_f32(qd, qb.T), _mm_f32(qa.T, qd)


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)

MATMULS = {"float32": _mm_f32, "int8": _mm_int8}


# ------------------------------------------------------------ pieces
def rmsnorm(x, w):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def rope(t, positions):
    """t [S, H, Dh]; rotate the two halves of each head (the program's
    and the HF convention)."""
    dh = t.shape[-1]
    inv = 1.0 / (ROPE_THETA ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    t1, t2 = t[..., :dh // 2], t[..., dh // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def silu(g):
    return g / (1.0 + jnp.exp(-g))


def row_blocks(fn, *xs, block=ROW_BLOCK):
    """``fn`` over blocks of leading rows of ``xs``, each block
    rematerialised in the backward pass; results concatenated."""
    t = xs[0].shape[0]
    if t <= block or t % block:
        return fn(*xs)
    split = tuple(x.reshape(t // block, block, *x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a), split)
    return jax.tree.map(lambda o: o.reshape(t, *o.shape[2:]), out)


def attention_one(q, k, v):
    """One batch row, one head: q, k, v [S, Dh]; causal."""
    s, dh = k.shape
    sc = jnp.einsum("qd,kd->qk", q, k, precision="highest") \
        / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    sc = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
    pr = sc / jnp.sum(sc, -1, keepdims=True)
    return jnp.einsum("qk,kd->qd", pr, v, precision="highest")


def attention(x, lp, arch, mm):
    """x [B, S, D] -> [B, S, D]; the normed input goes in."""
    b, s, d = x.shape
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    g = h // hkv
    pos = jnp.arange(s)
    x2 = x.reshape(b * s, d)
    q = mm(x2, lp["wq"]).reshape(b, s, h, dh)
    k = mm(x2, lp["wk"]).reshape(b, s, hkv, dh)
    v = mm(x2, lp["wv"]).reshape(b, s, hkv, dh)
    q = jax.vmap(rope, (0, None))(q, pos)
    k = jax.vmap(rope, (0, None))(k, pos)
    # every query head against its group's keys and values, one
    # (batch row, head) at a time
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qh, kh, vh = (t.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
                  for t in (q, k, v))
    oh = jax.lax.map(lambda a: jax.checkpoint(attention_one)(*a),
                     (qh, kh, vh))
    o = oh.reshape(b, h, s, dh).transpose(0, 2, 1, 3).reshape(b * s, d)
    return mm(o, lp["wo"]).reshape(b, s, d)


def dense_mlp(y, lp, mm):
    def rows(yb):
        return mm(silu(mm(yb, lp["w_gate"])) * mm(yb, lp["w_up"]),
                  lp["w_down"])
    return row_blocks(rows, y)


def expert_capacity(tokens: int, top_k: int, experts: int,
                    factor: float) -> int:
    """Slots an expert has for one batch (the program's documented rule:
    the whole batch is one group)."""
    return max(1, int(factor * tokens * top_k / experts))


def route(y, w_router, arch):
    """Top-k routing under the capacity rule.  Returns the combine
    weights [T, E]: softmax over the k selected logits, zero for an
    assignment that arrived after its expert was full (arrival order is
    row order)."""
    e, k = arch["num_experts"], arch["top_k"]
    logits = _mm_f32(y, w_router)
    top, idx = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx, e, dtype=F32)            # [T, k, E]
    gate = jnp.sum(onehot * w[..., None], axis=1)         # [T, E]
    mask = jnp.sum(onehot, axis=1)
    pos = jnp.cumsum(mask, axis=0) - 1.0
    cap = expert_capacity(y.shape[0], k, e, arch["capacity_factor"])
    return gate * mask * (pos < cap)


def moe_mlp(y, lp, arch, mm):
    """Every expert computes every row and the combine weights keep
    what was routed to it and fitted; one expert at a time, its weights
    widened to float32 only while it runs."""
    combine = route(y, lp["w_router"], arch)

    def one_expert(out, ws):
        wg, wu, wd, cb = ws
        wg, wu, wd = (w.astype(F32) for w in (wg, wu, wd))

        def rows(yb, cbb):
            return mm(silu(mm(yb, wg)) * mm(yb, wu), wd) * cbb
        return out + row_blocks(rows, y, cb[:, None]), None
    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(y),
        (lp["w_gate"], lp["w_up"], lp["w_down"], combine.T))
    return out


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def block(x, lp, arch, mm):
    moe = arch["num_experts"] > 1
    lp = {k: a if moe and k in EXPERT_STACKS else a.astype(F32)
          for k, a in lp.items()}
    b, s, d = x.shape
    x = x + attention(rmsnorm(x, lp["norm1"]), lp, arch, mm)
    y = rmsnorm(x, lp["norm2"]).reshape(b * s, d)
    if arch["num_experts"] > 1:
        y2 = moe_mlp(y, lp, arch, mm)
    else:
        y2 = dense_mlp(y, lp, mm)
    return x + y2.reshape(b, s, d)


def layers_of(p: dict):
    """The layers of ``p`` one at a time, whether stacked on a leading
    axis (the program's layout; each is sliced when it is asked for) or
    already a list."""
    if isinstance(p["layers"], dict):
        n = next(iter(p["layers"].values())).shape[0]
        return ({k: a[li] for k, a in p["layers"].items()}
                for li in range(n))
    return iter(p["layers"])


def unstack(p: dict) -> dict:
    """The program's layout (layers stacked on a leading axis) as a list
    of layers: the form the reference works in, so that a layer's
    gradient is a layer's size."""
    return {**p, "layers": list(layers_of(p))}


def head_loss(x, final_norm, head, targets, mm):
    """Mean cross-entropy of the last block's output x [B, S, D] against
    targets [B, S]."""
    b, s, d = x.shape
    xf = rmsnorm(x, final_norm.astype(F32)).reshape(b * s, d)
    head = head.astype(F32)

    def rows(xb, tb):
        logits = mm(xb, head)
        m = jnp.max(logits, -1, keepdims=True)
        lse = m[:, 0] + jnp.log(jnp.sum(jnp.exp(logits - m), -1))
        return lse - jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
    return jnp.mean(row_blocks(rows, xf, targets.reshape(-1)))


def embed(table, tokens):
    return table[tokens].astype(F32)


def loss_fn(p, tokens, arch, precision="float32"):
    """Mean next-token cross-entropy of a [B, S+1] batch, as one
    function (small sizes; ``loss_and_grads`` is the same arithmetic a
    layer at a time)."""
    mm = MATMULS[precision]
    x = embed(p["embed"], tokens[:, :-1])
    for lp in p["layers"]:
        x = block(x, lp, arch, mm)
    return head_loss(x, p["final_norm"], p["head"], tokens[:, 1:], mm)


class LayerwiseGrad:
    """Loss and gradients of ``loss_fn`` by plain backpropagation, one
    jitted call for each layer and direction, so that only one layer's
    float32 weights, gradients and activations are alive at a time.
    Gradients come back in the weights' own dtype: what the optimizer
    gets."""

    def __init__(self, arch, precision="float32"):
        mm = MATMULS[precision]
        blk = functools.partial(block, arch=arch, mm=mm)
        self.embed = jax.jit(embed)
        self.block = jax.jit(blk)
        self.block_vjp = jax.jit(
            lambda x, lp, ct: jax.vjp(blk, x, lp)[1](ct))
        self.head = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, mm=mm), argnums=(0, 1, 2)))
        self.embed_vjp = jax.jit(
            lambda table, tokens, ct: jax.vjp(
                lambda t: embed(t, tokens), table)[1](ct)[0])

    def __call__(self, p, tokens):
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        xs = [self.embed(p["embed"], inp)]
        for lp in p["layers"]:
            xs.append(self.block(xs[-1], lp))
        loss, (ct, g_norm, g_head) = self.head(
            xs.pop(), p["final_norm"], p["head"], tgt)
        g_layers = []
        for lp in reversed(p["layers"]):
            ct, g_lp = self.block_vjp(xs.pop(), lp, ct)
            g_layers.append(g_lp)
        return loss, {"embed": self.embed_vjp(p["embed"], inp, ct),
                      "layers": g_layers[::-1], "final_norm": g_norm,
                      "head": g_head}


# ------------------------------------------------------- train steps
def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf, one per layer and weight:
    {"layers/0/wq": ..., "head": ...} (device scalars).  Takes the
    program's stacked layout or the reference's list of layers."""
    if isinstance(tree["layers"], dict):
        tree = unstack(tree)
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        a = leaf.astype(F32)
        out[name] = jnp.sqrt(jnp.sum(a * a))
    return out


def diff_norms(a, b) -> dict:
    if isinstance(a["layers"], dict):
        a, b = unstack(a), unstack(b)
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))


def norm_readers(lr: float):
    """(first, delta): jitted readers of the per-leaf norms of the first
    gradient as the optimizer got it, (p0 - p1) / lr, and of the
    parameters' change p0 - p; the same two for both sides."""
    first = jax.jit(lambda a, b: jax.tree.map(
        lambda n: n / lr, diff_norms(a, b)))
    return first, jax.jit(diff_norms)


def sgd_steps(p0, batches, arch, lr: float, precision="float32"):
    """The program's optimizer, followed exactly: stateless SGD on
    weights STORED in their own dtype (bf16), ``p <- dtype(p - lr *
    dtype(g))``, one step for each batch.  All else in float32.

    Returns ``{"losses": [...], "grad_norms": {leaf: norm of the first
    gradient as the optimizer gets it, from the state after one step},
    "delta_norms": {leaf: norm of the parameters' change after the last
    step}}`` as Python floats."""
    grad = LayerwiseGrad(arch, precision)

    def sgd(p, g):
        return jax.tree.map(
            lambda a, b: (a.astype(F32) - lr * b.astype(a.dtype)
                          .astype(F32)).astype(a.dtype), p, g)

    update = jax.jit(sgd, donate_argnums=(0,))
    first, delta = norm_readers(lr)
    p = p0 = unstack(p0)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            loss, g = grad(p, tokens)
            # the first update keeps p0 for the norms; later ones reuse
            # the state's buffers
            p = (jax.jit(sgd, donate_argnums=(1,)) if i == 0
                 else update)(p, g)
            del g
            losses.append(float(loss))
            if i == 0:
                grad_norms = jax.device_get(first(p0, p))
        delta_norms = jax.device_get(delta(p0, p))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta_norms.items()}}


# ------------------------------------------------------- comparisons
def worst_leaf_gap(got: dict, want: dict) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but
    zero)."""
    ref = sorted(want.values())
    median = ref[len(ref) // 2]
    worst, where = 0.0, ""
    for name, w in want.items():
        gap = abs(got[name] - w) / max(w, median, 1e-30)
        if not gap <= worst:          # a NaN gap is the worst there is
            worst, where = gap, name
    return float(worst), where
