"""The plain reference of the window-and-full attention expert decoder
(``smallthinker_*`` configurations): SmallThinker-21BA3B as its
``config.json`` states it, written in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes nothing the program made (weights by
``benchmarks/weights_swa_moe.py`` from the seed, tokens from the runner).

With ``x`` the residual stream [T, D], RMSNorm without bias (a plain
weight), H query heads over Hkv key/value heads of ``dh`` lanes, query
head ``h`` reading key/value head ``h // (H / Hkv)``, layer ``i``:

* ``y1 = rmsnorm1(x)``.  The router, BEFORE attention: ``l = y1 W_r``
  over ALL the router's experts in float32; a token's experts are the
  top-k of ``l``, their weights the softmax over those k logits.
* attention on the same ``y1``: ``q = y1 W_q``, ``k = y1 W_k``, ``v = y1
  W_v``, no bias, no norm a head.  A ``swa`` layer: RoPE on all ``dh``
  lanes (the two halves of a head; the pairing is immaterial under
  seeded weights), and token ``t`` sees keys ``max(0, t - window + 1)
  ... t``.  A ``nope`` layer: no position at all, keys ``0 ... t``.
  ``o = softmax(q k^T / sqrt(dh)) v`` under an explicit mask, ``x += o
  W_o``.
* experts, on the stream after attention: ``y2 = rmsnorm2(x)``; ``x +=
  sum_i w_i W_down_i(relu(W_gate_i y2) * W_up_i y2)``, the sum over
  those of the token's experts that are HELD here (``arch["held"]``: the
  chip's share of the layer).  No shared expert; no token is dropped: an
  expert computes every row routed to it, however many.
* final RMSNorm, an untied head, mean next-token cross-entropy.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time, attention one (batch row, head) at a time in
blocks of query rows against the whole [rows, S] mask, the experts one
at a time (each over all rows, its combine weights keeping what was
routed to it), the head in blocks of rows.  ``precision="int8"`` is the
CONTROL, as in ``benchmarks/reference.py``: every weight matmul's
operands, forward and backward, on a per-tensor int8 grid; the router's
logits stay float32, as the model computes them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import F32, MATMULS, embed, row_blocks
from benchmarks.reference_latent_moe import head_loss, rmsnorm, rope

_mm_f32 = MATMULS["float32"]
GROUP = "gated"     # the program's stack of every attention layer


def attention_head(q, k, v, window: int | None):
    """One batch row, one head: q, k, v [S, dh]; causal, over the last
    ``window`` keys where given; a block of queries at a time."""
    s, dh = k.shape
    pos = jnp.arange(s)

    def rows(qb, pb):
        sc = jnp.einsum("qd,kd->qk", qb, k, precision="highest") \
            / math.sqrt(dh)
        seen = pb[:, None] >= pos[None, :]
        if window is not None:
            seen &= pb[:, None] - pos[None, :] < window
        sc = jnp.where(seen, sc, -jnp.inf)
        sc = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
        pr = sc / jnp.sum(sc, -1, keepdims=True)
        return jnp.einsum("qk,kd->qd", pr, v, precision="highest")
    return row_blocks(rows, q, pos)


def attention(y, mp, arch, mm, kind: str):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    y2 = y.reshape(b * s, d)
    q = mm(y2, mp["wq"]).reshape(b, s, h, dh)
    k = mm(y2, mp["wk"]).reshape(b, s, hkv, dh)
    v = mm(y2, mp["wv"]).reshape(b, s, hkv, dh)
    window = None
    if kind == "swa":
        turn = jax.vmap(functools.partial(rope, theta=arch["rope_theta"]))
        q, k, window = turn(q), turn(k), arch["window"]
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))

    def heads_first(t):         # [B, S, H, dh] -> [B * H, S, dh]
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    one = functools.partial(attention_head, window=window)
    o = jax.lax.map(lambda a: jax.checkpoint(one)(*a),
                    tuple(heads_first(t) for t in (q, k, v)))
    o = o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    return mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)


def route(y, w_router, arch):
    """(combine weights [T, E] over ALL the router's experts, zero where
    an expert is not among the token's top-k; the selection [T, k])."""
    top, idx = jax.lax.top_k(_mm_f32(y, w_router), arch["top_k"])
    w = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx, arch["num_experts"], dtype=F32)
    return jnp.sum(onehot * w[..., None], axis=1), idx


def expert_layer(y, combine, fp, arch, mm):
    """y [T, D] (normed) -> the held routed experts' part [T, D].  Each
    held expert computes every row and its combine weights keep what was
    routed to it; its weights are widened to float32 only while it
    runs."""
    first, n = arch["held"]

    def one_expert(out, ws):
        wg, wu, wd, cb = ws
        wg, wu, wd = (w.astype(F32) for w in (wg, wu, wd))

        def rows(yb, cbb):
            return mm(jnp.maximum(mm(yb, wg), 0.0) * mm(yb, wu), wd) * cbb
        return out + row_blocks(rows, y, cb[:, None]), None
    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(y),
        (fp["w_gate"], fp["w_up"], fp["w_down"],
         combine[:, first:first + n].T))
    return out


def layer(x, lp, *, kind: str, arch, mm):
    """One layer; ``lp`` = {"block", "mixer", "ffn"} in the weights' own
    dtype.  Returns (x, the layer's selection [T, k])."""
    bp, mp = (jax.tree.map(lambda a: a.astype(F32), lp[g])
              for g in ("block", "mixer"))
    b, s, d = x.shape
    y1 = rmsnorm(x, bp["norm1"], arch["eps"])
    combine, idx = route(y1.reshape(b * s, d),
                         lp["ffn"]["w_router"].astype(F32), arch)
    x = x + attention(y1, mp, arch, mm, kind)
    y2 = rmsnorm(x, bp["norm2"], arch["eps"]).reshape(b * s, d)
    out = expert_layer(y2, combine, lp["ffn"], arch, mm)
    return x + out.reshape(b, s, d), idx


def unstack(p: dict, arch) -> dict:
    """The program's layout (parameters stacked by group) as a list of
    layers {"block", "mixer", "ffn"}: the form the reference works in."""
    if "layers" in p:
        return p
    layers = [{"block": {k: a[li] for k, a in p["block"].items()},
               "mixer": {k: a[li] for k, a in p[GROUP].items()},
               "ffn": {k: a[li] for k, a in p["moe"].items()}}
              for li in range(arch["num_layers"])]
    return {"embed": p["embed"], "head": p["head"],
            "final_norm": p["final_norm"], "layers": layers}


def _layer_fns(arch, mm):
    return [functools.partial(layer, kind=kind, arch=arch, mm=mm)
            for kind in arch["layer_kinds"]]


def loss_fn(p, tokens, arch, precision="float32"):
    """Mean next-token cross-entropy of a [B, S+1] batch as one function
    (small sizes; ``LayerwiseGrad`` is the same arithmetic a layer at a
    time)."""
    mm = MATMULS[precision]
    p = unstack(p, arch)
    x = embed(p["embed"], tokens[:, :-1])
    for fn, lp in zip(_layer_fns(arch, mm), p["layers"]):
        x, _ = fn(x, lp)
    return head_loss(x, p["final_norm"], p["head"], tokens[:, 1:], mm,
                     arch["eps"])


class LayerwiseGrad:
    """Loss, gradients and the layers' selections by plain
    backpropagation, one jitted call for each kind of layer and
    direction.  Gradients come back in the weights' own dtype: what the
    optimizer gets."""

    def __init__(self, arch, precision="float32"):
        mm = MATMULS[precision]
        jitted: dict = {}       # one compile a kind of layer

        def pair(fn):
            key = fn.keywords["kind"]
            if key not in jitted:
                jitted[key] = (jax.jit(fn), jax.jit(
                    lambda x, lp, ct: jax.vjp(
                        fn, x, lp, has_aux=True)[1](ct)))
            return jitted[key]
        self.layers = [pair(fn) for fn in _layer_fns(arch, mm)]
        self.embed = jax.jit(embed)
        self.head = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, mm=mm, eps=arch["eps"]),
            argnums=(0, 1, 2)))
        self.embed_vjp = jax.jit(
            lambda table, tokens, ct: jax.vjp(
                lambda t: embed(t, tokens), table)[1](ct)[0])

    def __call__(self, p, tokens):
        """(loss, gradients, [selection [T, k] of each layer])."""
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        xs, chosen = [self.embed(p["embed"], inp)], []
        for (fwd, _), lp in zip(self.layers, p["layers"]):
            x, idx = fwd(xs[-1], lp)
            xs.append(x)
            chosen.append(idx)
        loss, (ct, g_norm, g_head) = self.head(
            xs.pop(), p["final_norm"], p["head"], tgt)
        g_layers = []
        for (_, vjp), lp in zip(self.layers[::-1], p["layers"][::-1]):
            ct, g_lp = vjp(xs.pop(), lp, ct)
            g_layers.append(g_lp)
        return loss, {"embed": self.embed_vjp(p["embed"], inp, ct),
                      "head": g_head, "final_norm": g_norm,
                      "layers": g_layers[::-1]}, chosen


# ------------------------------------------------------- train steps
def _names(tree, arch) -> dict:
    """{name: leaf}: "embed", "<group>/<layer>/<leaf>", whichever layout
    ``tree`` has."""
    out = {k: tree[k] for k in ("embed", "head", "final_norm")}
    if "layers" not in tree:
        for g in ("block", GROUP, "moe"):
            for k, a in tree[g].items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
        return out
    for li, lp in enumerate(tree["layers"]):
        for g, part in (("block", "block"), (GROUP, "mixer"),
                        ("moe", "ffn")):
            out.update({f"{g}/{li}/{k}": a for k, a in lp[part].items()})
    return out


def diff_norms(a, b, arch) -> dict:
    """Euclidean norm of a - b, one for each layer's each weight."""
    a, b = _names(a, arch), _names(b, arch)
    return {k: jnp.sqrt(jnp.sum((a[k].astype(F32) - b[k].astype(F32))
                                ** 2)) for k in a}


def norm_readers(lr: float, arch):
    """(first, delta): jitted readers of the per-leaf norms of the first
    gradient as the optimizer got it, (p0 - p1) / lr, and of the
    parameters' change p0 - p; the same two for both sides."""
    arch = dict(arch)
    delta = jax.jit(functools.partial(diff_norms, arch=arch))
    first = jax.jit(lambda a, b: jax.tree.map(
        lambda n: n / lr, diff_norms(a, b, arch)))
    return first, delta


def sgd_steps(make_p0, batches, arch, lr: float, precision="float32"):
    """The program's optimizer, followed exactly: stateless SGD on
    weights STORED in their own dtype, ``p <- dtype(p - lr * dtype(g))``,
    one step for each batch; all else in float32.  ``make_p0()`` gives
    the seeded weights anew each time it is called, so that no second
    copy of them lives through the backward passes.  Returns what
    ``reference_latent_moe.sgd_steps`` returns: losses, the two sets of
    norms and ``"chosen"``, the first step's selections
    [layers, T, k] (host integers)."""
    grad = LayerwiseGrad(arch, precision)

    def sgd(p, g):
        return jax.tree.map(
            lambda a, b: (a.astype(F32) - lr * b.astype(a.dtype)
                          .astype(F32)).astype(a.dtype), p, g)

    update = jax.jit(sgd, donate_argnums=(0,))
    first, delta = norm_readers(lr, arch)
    p = unstack(make_p0(), arch)
    losses, grad_norms, chosen = [], None, None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            loss, g, idx = grad(p, tokens)
            p = update(p, g)
            del g
            losses.append(float(loss))
            if i == 0:
                chosen = jax.device_get(jnp.stack(idx))
                grad_norms = jax.device_get(first(make_p0(), p))
        delta_norms = jax.device_get(delta(make_p0(), p))
    return {"losses": losses, "chosen": chosen,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta_norms.items()}}
