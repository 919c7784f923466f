"""The plain reference of the latent-attention expert decoder
(``kimivl_*`` configurations): the language model of Kimi-VL-A3B /
Moonlight as their ``config.json`` states it (the DeepSeek-V3 family
with ``q_lora_rank`` null, one selection group, plain RoPE), written in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
It imports nothing of the program and takes nothing the program made
(weights by ``benchmarks/weights_latent_moe.py`` from the seed, tokens
from the runner).

With ``h`` the residual stream [T, D], RMSNorm without bias, H heads:

* attention: ``y = rmsnorm(h)``; ``q = y W_q -> [T, H, nope + rope]``;
  ``ckv = y W_kva -> [T, r + rope]``; ``c = rmsnorm(ckv[:, :r])`` with a
  weight of its own; ``k_rope = rope(ckv[:, r:])``, ONE head shared by
  all H; ``c W_kvb -> [T, H, nope + dv]`` split into ``k_nope`` and
  ``v``; ``q_rope = rope(q[..., nope:])``; ``k = [k_nope | k_rope]``;
  scores ``q k^T / sqrt(nope + rope)``, causal softmax, ``o = P v``,
  ``h += o W_o``.  RoPE rotates the two halves of the ``rope`` lanes
  (the pairing is immaterial under seeded weights).
* dense layers (the first ``first_dense``): ``h += swiglu(rmsnorm(h))``.
* expert layers: ``y = rmsnorm(h)``; ``s = sigmoid(y W_g)`` over ALL the
  router's experts in float32; a token's experts are the top-k of
  ``s + b`` (``b`` the selection bias: no gradient, not in the weight);
  their weights are ``s`` at those, over their sum plus 1e-20, times
  ``routed_scale``; ``h += sum_i w_i swiglu_i(y) + swiglu_shared(y)``,
  the sum over those of the token's experts that are HELD here
  (``arch["held"]``: the chip's share of the layer), the shared expert
  whole.  No token is dropped: an expert computes every row routed to
  it, however many.
* final RMSNorm, an untied head, mean next-token cross-entropy.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time, attention one (batch row, head) at a time, the
experts one at a time (each over all rows, its combine weights keeping
what was routed to it), MLPs and head in blocks of rows.
``precision="int8"`` is the CONTROL, as in ``benchmarks/reference.py``:
every weight matmul's operands, forward and backward, on a per-tensor
int8 grid; the router's scores stay float32, as the model computes them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import (F32, MATMULS, attention_one, embed,
                                  row_blocks, silu)

_mm_f32 = MATMULS["float32"]


def rmsnorm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(t, theta):
    """t [S, H, R]: rotate the two halves of each head by position."""
    s, _, r = t.shape
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    t1, t2 = t[..., :r // 2], t[..., r // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def latent_attention(y, mp, arch, mm):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    h, r = arch["num_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    y2 = y.reshape(b * s, d)
    q = mm(y2, mp["wq"]).reshape(b, s, h, dn + dr)
    ckv = mm(y2, mp["w_kva"])
    c = rmsnorm(ckv[:, :r], mp["kv_norm"], arch["eps"])
    kv = mm(c, mp["w_kvb"]).reshape(b, s, h, dn + dv)
    turn = jax.vmap(functools.partial(rope, theta=arch["rope_theta"]))
    k_rope = turn(ckv[:, r:].reshape(b, s, 1, dr))
    q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)

    def heads_first(t):         # [B, S, H, F] -> [B * H, S, F]
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, t.shape[-1])
    o = jax.lax.map(lambda a: jax.checkpoint(attention_one)(*a),
                    tuple(heads_first(t) for t in (q, k, kv[..., dn:])))
    o = o.reshape(b, h, s, dv).transpose(0, 2, 1, 3).reshape(b * s, h * dv)
    return mm(o, mp["wo"]).reshape(b, s, d)


def swiglu(y, w_gate, w_up, w_down, mm):
    def rows(yb):
        return mm(silu(mm(yb, w_gate)) * mm(yb, w_up), w_down)
    return row_blocks(rows, y)


def route(y, w_router, bias, arch):
    """(combine weights [T, E] over ALL the router's experts, zero where
    an expert is not among the token's top-k; the selection [T, k])."""
    e, k = arch["num_experts"], arch["top_k"]
    s = 1.0 / (1.0 + jnp.exp(-_mm_f32(y, w_router)))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    w = s * jnp.sum(jax.nn.one_hot(idx, e, dtype=F32), axis=1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * arch["routed_scale"], idx


def expert_layer(y, fp, arch, mm):
    """y [T, D] (normed) -> (the held routed experts' part plus the
    shared expert [T, D], the selection [T, k]).  Each held expert
    computes every row and its combine weights keep what was routed to
    it; its weights are widened to float32 only while it runs."""
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
    shared = swiglu(y, *(fp[k].astype(F32) for k in
                         ("ws_gate", "ws_up", "ws_down")), mm)
    return out + shared, idx


def layer(x, lp, *, dense: bool, arch, mm):
    """One layer; ``lp`` = {"block", "mixer", "ffn"} in the weights' own
    dtype.  Returns (x, the expert layer's selection or None)."""
    bp, mp = (jax.tree.map(lambda a: a.astype(F32), lp[g])
              for g in ("block", "mixer"))
    b, s, d = x.shape
    x = x + latent_attention(rmsnorm(x, bp["norm1"], arch["eps"]), mp,
                             arch, mm)
    y = rmsnorm(x, bp["norm2"], arch["eps"]).reshape(b * s, d)
    if dense:
        out, idx = swiglu(y, *(lp["ffn"][k].astype(F32) for k in
                               ("w_gate", "w_up", "w_down")), mm), None
    else:
        out, idx = expert_layer(y, lp["ffn"], arch, mm)
    return x + out.reshape(b, s, d), idx


def head_loss(x, final_norm, head, targets, mm, eps):
    """Mean cross-entropy against targets [B, S]; ``head`` [V, D]."""
    b, s, d = x.shape
    xf = rmsnorm(x, final_norm.astype(F32), eps).reshape(b * s, d)
    head = head.astype(F32).T

    def rows(xb, tb):
        logits = mm(xb, head)
        m = jnp.max(logits, -1, keepdims=True)
        lse = m[:, 0] + jnp.log(jnp.sum(jnp.exp(logits - m), -1))
        return lse - jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
    return jnp.mean(row_blocks(rows, xf, targets.reshape(-1)))


MLP = ("w_gate", "w_up", "w_down")


def unstack(p: dict, arch) -> dict:
    """The program's layout (parameters stacked by group) as a list of
    layers {"block", "mixer", "ffn"}: the form the reference works in."""
    if "layers" in p:
        return p
    nd = arch["first_dense"]
    layers = []
    for li in range(arch["num_layers"]):
        ffn = ({k: p["block"][k][li] for k in MLP} if li < nd else
               {k: a[li - nd] for k, a in p["moe"].items()})
        layers.append({
            "block": {k: a[li] for k, a in p["block"].items()
                      if k not in MLP},
            "mixer": {k: a[li] for k, a in p["mla"].items()},
            "ffn": ffn})
    return {"embed": p["embed"], "head": p["head"],
            "final_norm": p["final_norm"], "layers": layers}


def _layer_fns(arch, mm):
    return [functools.partial(layer, dense=li < arch["first_dense"],
                              arch=arch, mm=mm)
            for li in range(arch["num_layers"])]


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
            key = fn.keywords["dense"]
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
        """(loss, gradients, [selection [T, k] of each expert layer])."""
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        xs, chosen = [self.embed(p["embed"], inp)], []
        for (fwd, _), lp in zip(self.layers, p["layers"]):
            x, idx = fwd(xs[-1], lp)
            xs.append(x)
            if idx is not None:
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
        for g in ("block", "mla", "moe"):
            for k, a in tree[g].items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
        return out
    nd = arch["first_dense"]
    for li, lp in enumerate(tree["layers"]):
        out.update({f"block/{li}/{k}": a for k, a in lp["block"].items()})
        out.update({f"mla/{li}/{k}": a for k, a in lp["mixer"].items()})
        ffn = f"block/{li}" if li < nd else f"moe/{li - nd}"
        out.update({f"{ffn}/{k}": a for k, a in lp["ffn"].items()})
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
    ``reference.sgd_steps`` returns, and ``"chosen"``: the first step's
    selections [expert layers, T, k] (host integers)."""
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
