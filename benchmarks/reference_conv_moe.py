"""The plain reference of the short-convolution expert decoder
(``lfm2_*`` configurations): LFM2-8B-A1B as its ``config.json`` states
it, written in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes nothing the program made (weights by
``benchmarks/weights_conv_moe.py`` from the seed, tokens from the
runner).

With ``x`` the residual stream [T, D] and ``norm(u) = u / sqrt(mean(u^2)
+ eps) * w`` (``w`` one at the start, no bias anywhere), layer ``i`` is
``x += mixer_i(norm(x)); x += ffn_i(norm(x))``: the mixer by
``layer_types[i]``, the FFN a dense SwiGLU where ``i <
num_dense_layers``, else the expert layer.

* conv layer (gated short convolution): ``[b | c | u] = y W_in``, three
  streams of D lanes in this order; ``z = b * u``; ``h_t = sum_j w_j *
  z_(t-K+1+j)``, a causal depthwise convolution over time of K =
  ``conv_L_cache`` taps written as K shifted products, ``z`` zero before
  the sequence's start, tap K-1 the current token, NO activation and NO
  bias; ``(c * h) W_out``.
* attention layer (grouped keys and values): ``q = y W_q``, ``k = y
  W_k``, ``v = y W_v``; ``q <- norm(q)``, ``k <- norm(k)`` over each
  head's lanes with one weight each, BEFORE RoPE; RoPE by halves on all
  the head's lanes; causal softmax of ``q k^T / sqrt(dh)``; ``o W_o``.
  No gate, no window.
* expert layer: ``l = y W_r`` over ALL the router's experts in float32;
  ``s = sigmoid(l)``; a token's experts are the top-k of ``s + b`` (``b``
  the selection bias: no gradient, not in the weight); their weights are
  ``s`` at those over (their sum + 1e-6), times ``routed_scale``;
  ``sum_i w_i swiglu_i(y)`` over those of the token's experts that are
  HELD here (``arch["held"]``).  No shared expert; no token is dropped.
* final ``norm``, the head is the embedding table (tied), mean
  next-token cross-entropy.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time, attention runs one (batch row, head) and one
block of queries at a time, the experts one at a time, MLPs and head in
blocks of rows.  ``precision="int8"`` is the CONTROL, as in
``benchmarks/reference.py``: every weight matmul's operands, forward and
backward, on a per-tensor int8 grid; the router's scores and the
convolution's products stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import F32, MATMULS, embed, row_blocks, silu
from benchmarks.reference_latent_moe import head_loss, rmsnorm, rope, swiglu
from benchmarks.reference_linear_moe import attention_head

_mm_f32 = MATMULS["float32"]
MLP = ("w_gate", "w_up", "w_down")
MIXERS = ("conv", "gated")


def short_conv(y, mp, mm):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    bcu = mm(y.reshape(b * s, d), mp["w_in"]).reshape(b, s, 3 * d)
    z = bcu[..., :d] * bcu[..., 2 * d:]
    taps = mp["conv_w"]
    k = taps.shape[0]
    zp = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    h = sum(zp[:, j:j + s] * taps[j] for j in range(k))
    g = bcu[..., d:2 * d] * h
    return mm(g.reshape(b * s, d), mp["w_out"]).reshape(b, s, d)


def attention(y, mp, arch, mm):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    y2 = y.reshape(b * s, d)
    q = rmsnorm(mm(y2, mp["wq"]).reshape(b, s, h, dh), mp["q_norm"],
                arch["eps"])
    k = rmsnorm(mm(y2, mp["wk"]).reshape(b, s, hkv, dh), mp["k_norm"],
                arch["eps"])
    v = mm(y2, mp["wv"]).reshape(b, s, hkv, dh)
    turn = jax.vmap(functools.partial(rope, theta=arch["rope_theta"]))
    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))

    def heads_first(t):         # [B, S, H, dh] -> [B * H, S, dh]
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    o = jax.lax.map(lambda a: jax.checkpoint(attention_head)(*a),
                    tuple(heads_first(t) for t in (q, k, v)))
    o = o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    return mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)


def route(y, w_router, bias, arch):
    """(combine weights [T, E] over ALL the router's experts, zero where
    an expert is not among the token's top-k; the selection [T, k])."""
    e, k = arch["num_experts"], arch["top_k"]
    s = 1.0 / (1.0 + jnp.exp(-_mm_f32(y, w_router)))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = s * jnp.sum(jax.nn.one_hot(idx, e, dtype=F32), axis=1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * arch["routed_scale"], idx


def expert_layer(y, fp, arch, mm):
    """y [T, D] (normed) -> (the held routed experts' part [T, D], the
    selection [T, k]).  Each held expert computes every row and its
    combine weights keep what was routed to it; its weights are widened
    to float32 only while it runs."""
    combine, idx = route(y, fp["w_router"].astype(F32),
                         fp["router_bias"].astype(F32), arch)
    first, n = arch["held"]

    def one_expert(out, ws):
        wg, wu, wd, cb = ws
        wg, wu, wd = (w.astype(F32) for w in (wg, wu, wd))

        def rows(yb, cbb):
            return mm(silu(mm(yb, wg)) * mm(yb, wu), wd) * cbb
        return out + row_blocks(rows, y, cb[:, None]), None
    out, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(y),
        (fp["w_gate"], fp["w_up"], fp["w_down"],
         combine[:, first:first + n].T))
    return out, idx


def layer(x, lp, *, kind: str, dense: bool, arch, mm):
    """One layer; ``lp`` = {"block", "mixer", "ffn"} in the weights' own
    dtype.  Returns (x, the expert layer's selection or None)."""
    bp, mp = (jax.tree.map(lambda a: a.astype(F32), lp[g])
              for g in ("block", "mixer"))
    b, s, d = x.shape
    y = rmsnorm(x, bp["norm1"], arch["eps"])
    x = x + (short_conv(y, mp, mm) if kind == "conv"
             else attention(y, mp, arch, mm))
    y = rmsnorm(x, bp["norm2"], arch["eps"]).reshape(b * s, d)
    if dense:
        out, idx = swiglu(y, *(lp["ffn"][k].astype(F32) for k in MLP),
                          mm), None
    else:
        out, idx = expert_layer(y, lp["ffn"], arch, mm)
    return x + out.reshape(b, s, d), idx


def unstack(p: dict, arch) -> dict:
    """The program's layout (parameters stacked by group) as a list of
    layers {"block", "mixer", "ffn"}: the form the reference works in."""
    if "layers" in p:
        return p
    nd = arch["first_dense"]
    layers, seen = [], dict.fromkeys(MIXERS, 0)
    for li, kind in enumerate(arch["layer_kinds"]):
        gi = seen[kind]
        seen[kind] += 1
        ffn = ({k: p["block"][k][li] for k in MLP} if li < nd else
               {k: a[li - nd] for k, a in p["moe"].items()})
        layers.append({
            "block": {k: a[li] for k, a in p["block"].items()
                      if k not in MLP},
            "mixer": {k: a[gi] for k, a in p[kind].items()},
            "ffn": ffn})
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "layers": layers}


def _layer_fns(arch, mm):
    return [functools.partial(layer, kind=kind,
                              dense=li < arch["first_dense"], arch=arch,
                              mm=mm)
            for li, kind in enumerate(arch["layer_kinds"])]


def loss_fn(p, tokens, arch, precision="float32"):
    """Mean next-token cross-entropy of a [B, S+1] batch as one function
    (small sizes; ``LayerwiseGrad`` is the same arithmetic a layer at a
    time)."""
    mm = MATMULS[precision]
    p = unstack(p, arch)
    x = embed(p["embed"], tokens[:, :-1])
    for fn, lp in zip(_layer_fns(arch, mm), p["layers"]):
        x, _ = fn(x, lp)
    return head_loss(x, p["final_norm"], p["embed"], tokens[:, 1:], mm,
                     arch["eps"])


class LayerwiseGrad:
    """Loss, gradients and the expert layers' selections by plain
    backpropagation, one jitted call for each kind of layer and
    direction.  Gradients come back in the weights' own dtype: what the
    optimizer gets; the tied table's is the sum of what reaches it as
    the head and as the embedding."""

    def __init__(self, arch, precision="float32"):
        mm = MATMULS[precision]
        jitted: dict = {}       # one compile a kind of layer

        def pair(fn):
            key = (fn.keywords["kind"], fn.keywords["dense"])
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
            lambda table, tokens, ct, g_head: (jax.vjp(
                lambda t: embed(t, tokens), table)[1](ct)[0].astype(F32)
                + g_head.astype(F32)).astype(table.dtype))

    def __call__(self, p, tokens):
        """(loss, gradients, [selection [T, k] of each expert layer])."""
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        xs, chosen = [self.embed(p["embed"], inp)], []
        for (fwd, _), lp in zip(self.layers, p["layers"]):
            x, idx = fwd(xs[-1], lp)
            xs.append(x)
            if idx is not None:
                chosen.append(idx)
        loss, (ct, g_norm, g_head) = self.head(
            xs.pop(), p["final_norm"], p["embed"], tgt)
        g_layers = []
        for (_, vjp), lp in zip(self.layers[::-1], p["layers"][::-1]):
            ct, g_lp = vjp(xs.pop(), lp, ct)
            g_layers.append(g_lp)
        return loss, {"embed": self.embed_vjp(p["embed"], inp, ct, g_head),
                      "final_norm": g_norm,
                      "layers": g_layers[::-1]}, chosen


# ------------------------------------------------------- train steps
def _names(tree, arch) -> dict:
    """{name: leaf}: "embed", "<group>/<index in group>/<leaf>",
    whichever layout ``tree`` has."""
    out = {k: tree[k] for k in ("embed", "final_norm")}
    if "layers" not in tree:
        for g in ("block", *MIXERS, "moe"):
            for k, a in tree.get(g, {}).items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
        return out
    nd, seen = arch["first_dense"], dict.fromkeys(MIXERS, 0)
    for li, (kind, lp) in enumerate(zip(arch["layer_kinds"],
                                        tree["layers"])):
        out.update({f"block/{li}/{k}": a for k, a in lp["block"].items()})
        out.update({f"{kind}/{seen[kind]}/{k}": a
                    for k, a in lp["mixer"].items()})
        ffn = f"block/{li}" if li < nd else f"moe/{li - nd}"
        out.update({f"{ffn}/{k}": a for k, a in lp["ffn"].items()})
        seen[kind] += 1
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
    [expert layers, T, k] (host integers)."""
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
                chosen = jax.device_get(jnp.stack(idx)) if idx else None
                grad_norms = jax.device_get(first(make_p0(), p))
        delta_norms = jax.device_get(delta(make_p0(), p))
    return {"losses": losses, "chosen": chosen,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta_norms.items()}}
