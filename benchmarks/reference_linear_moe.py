"""The plain reference of the linear-attention expert decoder
(``qwen3next_*`` configurations): Qwen3-Next-80B-A3B as its
``config.json`` states it, written in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes nothing the program made (weights by
``benchmarks/weights_linear_moe.py`` from the seed, tokens from the
runner).

With ``x`` the residual stream [T, D] and ``norm0(u) = u / sqrt(mean(u^2)
+ eps) * (1 + w)`` (``w`` zero at the start), layer ``i`` (from 0) is
full attention where ``(i + 1) % full_attention_interval == 0``, else
linear; every layer is ``x += mixer(norm0(x)); x += experts(norm0(x))``.

* linear layer (Gated DeltaNet, ``hk`` key heads and ``hv`` value heads):
  ``[q | k | v | z] = y W_qkvz``, ``[b | a] = y W_ba``; ``[q | k | v] <-
  silu(conv(.))``, a causal depthwise convolution over time without bias;
  a head: ``q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk)``, ``k <- k /
  sqrt(sum k^2 + 1e-6)``; key head ``h // (hv / hk)`` serves value head
  ``h``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; the rule ONE TOKEN AT A TIME, ``S' = exp(g_t) S``, ``S =
  S' + k_t (beta_t (v_t - S'^T k_t))^T``, ``o_t = S^T q_t`` (a
  ``lax.scan`` over tokens, never the chunked form); ``o <- o /
  sqrt(mean(o^2) + eps) * w_n`` a head, times ``silu(z)``, times ``W_o``.
* full layer (gated attention, grouped keys and values): ``[q | gate] =
  y W_q`` a head, ``k = y W_k``, ``v = y W_v``; ``q <- norm0(q)``, ``k <-
  norm0(k)`` a head; RoPE by halves on the first ``rope_dim`` lanes;
  causal softmax of ``q k^T / sqrt(dh)``; ``(o * sigmoid(gate)) W_o``.
* expert layer: ``l = y W_r`` over ALL the router's experts in float32;
  a token's experts are the top-k of ``l``, their weights the softmax
  over those k logits; ``sum_i w_i swiglu_i(y)`` over those of the
  token's experts that are HELD here (``arch["held"]``) plus
  ``sigmoid(y w_sg) swiglu_shared(y)``.  No token is dropped.
* final ``norm0``, an untied head, mean next-token cross-entropy.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time, the recurrence is checkpointed in blocks of
tokens, attention runs one (batch row, head) and one block of queries at
a time, the experts one at a time, MLPs and head in blocks of rows.
``precision="int8"`` is the CONTROL, as in ``benchmarks/reference.py``:
every weight matmul's operands, forward and backward, on a per-tensor
int8 grid; the router's logits stay float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import F32, MATMULS, embed, row_blocks, silu
from benchmarks.reference_latent_moe import head_loss as _head_loss
from benchmarks.reference_latent_moe import rmsnorm, rope, swiglu

_mm_f32 = MATMULS["float32"]
TOKEN_BLOCK = 128       # tokens of the recurrence between two kept states


def norm0(x, w, eps):
    return rmsnorm(x, 1.0 + w, eps)


def unit(t):
    return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)


def causal_conv(u, w):
    """u [S, E], w [K, E] (tap K-1 is the current token)."""
    k, s = w.shape[0], u.shape[0]
    up = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(up[i:i + s] * w[i] for i in range(k))


def delta_rule(q, k, v, g, beta):
    """The recurrence over q, k [S, H, dk], v [S, H, dv], g, beta
    [S, H]: one token a step, each block of ``TOKEN_BLOCK`` tokens made
    again in the backward from the state that entered it."""
    s, h, dk = q.shape

    def token(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        st = jnp.exp(g_t)[:, None, None] * st
        delta = b_t[:, None] * (v_t - jnp.sum(st * k_t[:, :, None], 1))
        st = st + k_t[:, :, None] * delta[:, None, :]
        return st, jnp.sum(st * q_t[:, :, None], 1)

    def block(st, xs):
        return jax.lax.scan(token, st, xs)

    xs = (q, k, v, g, beta)
    s0 = jnp.zeros((h, dk, v.shape[-1]), F32)
    if s <= TOKEN_BLOCK or s % TOKEN_BLOCK:
        return block(s0, xs)[1]
    xs = tuple(x.reshape(s // TOKEN_BLOCK, TOKEN_BLOCK, *x.shape[1:])
               for x in xs)
    o = jax.lax.scan(jax.checkpoint(block), s0, xs)[1]
    return o.reshape(s, *o.shape[2:])


def linear_attention(y, mp, arch, mm):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    hk, hv = arch["linear_key_heads"], arch["linear_value_heads"]
    dk, dv = arch["linear_key_dim"], arch["linear_value_dim"]
    nqk, nv = hk * dk, hv * dv
    y2 = y.reshape(b * s, d)
    qkvz = mm(y2, mp["w_qkvz"]).reshape(b, s, 2 * nqk + 2 * nv)
    ba = mm(y2, mp["w_ba"]).reshape(b, s, 2 * hv)
    decay = -jnp.exp(mp["a_log"])

    def one_row(qkvz, ba):
        qkv = silu(causal_conv(qkvz[:, :2 * nqk + nv], mp["conv_w"]))
        q = unit(qkv[:, :nqk].reshape(s, hk, dk)) / math.sqrt(dk)
        k = unit(qkv[:, nqk:2 * nqk].reshape(s, hk, dk))
        q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
        v = qkv[:, 2 * nqk:].reshape(s, hv, dv)
        beta = 1.0 / (1.0 + jnp.exp(-ba[:, :hv]))
        g = decay * jax.nn.softplus(ba[:, hv:] + mp["dt_bias"])
        o = rmsnorm(delta_rule(q, k, v, g, beta), mp["o_norm"],
                    arch["eps"])
        z = qkvz[:, 2 * nqk + nv:].reshape(s, hv, dv)
        return (o * silu(z)).reshape(s, nv)

    o = jax.lax.map(lambda a: one_row(*a), (qkvz, ba))
    return mm(o.reshape(b * s, nv), mp["w_out"]).reshape(b, s, d)


def attention_head(q, k, v):
    """One batch row, one head: q, k, v [S, dh]; causal; a block of
    queries at a time."""
    s, dh = k.shape
    pos = jnp.arange(s)

    def rows(qb, pb):
        sc = jnp.einsum("qd,kd->qk", qb, k, precision="highest") \
            / math.sqrt(dh)
        sc = jnp.where(pb[:, None] >= pos[None, :], sc, -jnp.inf)
        sc = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
        pr = sc / jnp.sum(sc, -1, keepdims=True)
        return jnp.einsum("qk,kd->qd", pr, v, precision="highest")
    return row_blocks(rows, q, pos)


def gated_attention(y, mp, arch, mm):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    h, hkv, dh, dr = (arch["num_heads"], arch["num_kv_heads"],
                      arch["head_dim"], arch["rope_dim"])
    y2 = y.reshape(b * s, d)
    qg = mm(y2, mp["wq"]).reshape(b, s, h, 2 * dh)
    k = mm(y2, mp["wk"]).reshape(b, s, hkv, dh)
    v = mm(y2, mp["wv"]).reshape(b, s, hkv, dh)
    q = norm0(qg[..., :dh], mp["q_norm"], arch["eps"])
    k = norm0(k, mp["k_norm"], arch["eps"])
    turn = jax.vmap(functools.partial(rope, theta=arch["rope_theta"]))
    q = jnp.concatenate([turn(q[..., :dr]), q[..., dr:]], -1)
    k = jnp.concatenate([turn(k[..., :dr]), k[..., dr:]], -1)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))

    def heads_first(t):         # [B, S, H, dh] -> [B * H, S, dh]
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    o = jax.lax.map(lambda a: jax.checkpoint(attention_head)(*a),
                    tuple(heads_first(t) for t in (q, k, v)))
    o = o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    o = o / (1.0 + jnp.exp(-qg[..., dh:]))
    return mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)


def route(y, w_router, arch):
    """(combine weights [T, E] over ALL the router's experts, zero where
    an expert is not among the token's top-k; the selection [T, k])."""
    top, idx = jax.lax.top_k(_mm_f32(y, w_router), arch["top_k"])
    w = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx, arch["num_experts"], dtype=F32)
    return jnp.sum(onehot * w[..., None], axis=1), idx


def expert_layer(y, fp, arch, mm):
    """y [T, D] (normed) -> (the held routed experts' part plus the
    gated shared expert [T, D], the selection [T, k]).  Each held expert
    computes every row and its combine weights keep what was routed to
    it; its weights are widened to float32 only while it runs."""
    combine, idx = route(y, fp["w_router"].astype(F32), arch)
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
    shared = swiglu(y, *(fp[k].astype(F32) for k in
                         ("ws_gate", "ws_up", "ws_down")), mm)
    gate = mm(y, fp["ws_sig"].astype(F32)[:, None])
    return out + shared / (1.0 + jnp.exp(-gate)), idx


def layer(x, lp, *, kind: str, arch, mm):
    """One layer; ``lp`` = {"block", "mixer", "ffn"} in the weights' own
    dtype.  Returns (x, the expert layer's selection)."""
    bp, mp = (jax.tree.map(lambda a: a.astype(F32), lp[g])
              for g in ("block", "mixer"))
    b, s, d = x.shape
    mixer = linear_attention if kind == "gdn" else gated_attention
    x = x + mixer(norm0(x, bp["norm1"], arch["eps"]), mp, arch, mm)
    y = norm0(x, bp["norm2"], arch["eps"]).reshape(b * s, d)
    out, idx = expert_layer(y, lp["ffn"], arch, mm)
    return x + out.reshape(b, s, d), idx


def head_loss(x, final_norm, head, targets, mm, eps):
    return _head_loss(x, 1.0 + final_norm.astype(F32), head, targets, mm,
                      eps)


GROUPS = ("block", "gdn", "gated", "moe")


def unstack(p: dict, arch) -> dict:
    """The program's layout (parameters stacked by group) as a list of
    layers {"block", "mixer", "ffn"}: the form the reference works in."""
    if "layers" in p:
        return p
    layers, seen = [], {"gdn": 0, "gated": 0}
    for li, kind in enumerate(arch["layer_kinds"]):
        gi = seen[kind]
        seen[kind] += 1
        layers.append({
            "block": {k: a[li] for k, a in p["block"].items()},
            "mixer": {k: a[gi] for k, a in p[kind].items()},
            "ffn": {k: a[li] for k, a in p["moe"].items()}})
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
    """Loss, gradients and the expert layers' selections by plain
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
    """{name: leaf}: "embed", "<group>/<index in group>/<leaf>",
    whichever layout ``tree`` has."""
    out = {k: tree[k] for k in ("embed", "head", "final_norm")}
    if "layers" not in tree:
        for g in GROUPS:
            for k, a in tree[g].items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
        return out
    seen = {"gdn": 0, "gated": 0}
    for li, (kind, lp) in enumerate(zip(arch["layer_kinds"],
                                        tree["layers"])):
        out.update({f"block/{li}/{k}": a for k, a in lp["block"].items()})
        out.update({f"{kind}/{seen[kind]}/{k}": a
                    for k, a in lp["mixer"].items()})
        out.update({f"moe/{li}/{k}": a for k, a in lp["ffn"].items()})
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
    norms and ``"chosen"``, the first step's selections [layers, T, k]
    (host integers)."""
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
