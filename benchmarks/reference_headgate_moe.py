"""The plain reference of the head-gated window-and-full attention
expert decoder (``laguna_*`` configurations): Laguna-S-2.1 as its
``config.json`` states it, written in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes nothing the program made (weights by
``benchmarks/weights_headgate_moe.py`` from the seed, tokens from the
runner).

With ``x`` the residual stream [T, D], RMSNorm without bias (a plain
weight), Hkv key/value heads of ``dh`` lanes, layer ``l`` of kind
``layer_kinds[l]`` with ``H`` query heads (the window layers' count or
the full layers'), query head ``h`` reading key/value head
``h // (H / Hkv)``:

* ``y = rmsnorm1(x)``; ``q = y W_q``, ``k = y W_k``, ``v = y W_v``, no
  bias, no norm a head.
* a window layer (``swa``): RoPE by halves on the first
  ``rope_window`` lanes (all of them as published) at
  ``inv_i = theta^(-2i/lanes)``; token ``t`` sees keys
  ``max(0, t - window + 1) ... t``.
* a full layer (``gated``): RoPE by halves on the first ``rope_full``
  lanes ``d`` (half of them as published), the rest unturned, under
  YaRN: ``c(n) = d ln(L0 / (2 pi n)) / (2 ln theta)``, ``low =
  max(floor(c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)),
  d - 1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
  ``inv_i = theta^(-2i/d) ((1 - ramp_i) + ramp_i / factor)``, and cos
  and sin each times ``attention_factor``; keys ``0 ... t``.
* ``o = softmax(q k^T / sqrt(dh)) v`` under an explicit mask; the gate
  a head ``g = sigmoid(y W_g)`` [T, H] (float32), ``o[:, h] *= g[:, h]``;
  ``x += o W_o``.
* the leading ``first_dense`` layers: ``x += swiglu(rmsnorm2(x))``.
* expert layers: ``y2 = rmsnorm2(x)``; ``p = softmax(y2 W_r)`` over ALL
  the router's experts in float32; a token's experts are its top-k by
  ``p``, their weights ``routed_scale * p / sum of the k``;
  ``x += sum_i w_i swiglu_i(y2) + swiglu_shared(y2)``, the sum over
  those of the token's experts that are HELD here (``arch["held"]``),
  the shared expert whole and ungated.  No token is dropped.
* final RMSNorm, an untied head, mean next-token cross-entropy.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time, attention one (batch row, head) at a time in
blocks of query rows against the whole [rows, S] mask, the experts one
at a time (each over all rows, its combine weights keeping what was
routed to it), MLPs and head in blocks of rows.  ``precision="int8"`` is
the CONTROL, as in ``benchmarks/reference.py``: every weight matmul's
operands, forward and backward, on a per-tensor int8 grid (the gate's
projection among them); the router's scores stay float32, as the model
computes them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import F32, MATMULS, embed, row_blocks, silu
from benchmarks.reference_latent_moe import (MLP, head_loss, rmsnorm,
                                             swiglu)
from benchmarks.reference_swa_moe import attention_head

_mm_f32 = MATMULS["float32"]
GROUPS = ("gated", "swa")   # the program's stacks of attention layers


def yarn_range(lanes: int, theta: float, yarn) -> tuple:
    """(low, high): the pairs between which YaRN's ramp rises."""
    _, original, beta_fast, beta_slow, _ = yarn

    def c(turns):
        return (lanes * math.log(original / (2 * math.pi * turns))
                / (2 * math.log(theta)))
    return (max(math.floor(c(beta_fast)), 0),
            min(math.ceil(c(beta_slow)), lanes - 1))


def inv_freqs(theta: float, lanes: int, yarn):
    """(inverse frequencies [lanes / 2] float32, the factor on cos and
    sin)."""
    i = jnp.arange(lanes // 2, dtype=F32)
    inv = theta ** (-2.0 * i / lanes)
    if yarn is None:
        return inv, 1.0
    low, high = yarn_range(lanes, theta, yarn)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return inv * ((1.0 - ramp) + ramp / yarn[0]), yarn[4]


def rope(t, spec):
    """t [S, H, dh]; rotate the two halves of the first ``lanes`` lanes
    of each head by position; ``spec`` = (theta, lanes, yarn)."""
    theta, lanes, yarn = spec
    inv, factor = inv_freqs(theta, lanes, yarn)
    ang = jnp.arange(t.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    t1, t2 = t[..., :lanes // 2], t[..., lanes // 2:lanes]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos,
                            t[..., lanes:]], -1)


def attention(y, mp, arch, mm, kind: str):
    """y [B, S, D] (normed) -> [B, S, D]."""
    b, s, d = y.shape
    hkv, dh = arch["num_kv_heads"], arch["head_dim"]
    h = arch["window_heads" if kind == "swa" else "num_heads"]
    y2 = y.reshape(b * s, d)
    q = mm(y2, mp["wq"]).reshape(b, s, h, dh)
    k = mm(y2, mp["wk"]).reshape(b, s, hkv, dh)
    v = mm(y2, mp["wv"]).reshape(b, s, hkv, dh)
    turn = jax.vmap(functools.partial(
        rope, spec=arch["rope_window" if kind == "swa" else "rope_full"]))
    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))

    def heads_first(t):         # [B, S, H, dh] -> [B * H, S, dh]
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    one = functools.partial(
        attention_head, window=arch["window"] if kind == "swa" else None)
    o = jax.lax.map(lambda a: jax.checkpoint(one)(*a),
                    tuple(heads_first(t) for t in (q, k, v)))
    o = o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    g = 1.0 / (1.0 + jnp.exp(-mm(y2, mp["wg"])))
    o = o * g.reshape(b, s, h, 1)
    return mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)


def route(y, w_router, arch):
    """(combine weights [T, E] over ALL the router's experts, zero where
    an expert is not among the token's top-k; the selection [T, k])."""
    p = jax.nn.softmax(_mm_f32(y, w_router), axis=-1)
    top, idx = jax.lax.top_k(p, arch["top_k"])
    w = arch["routed_scale"] * top / jnp.sum(top, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, arch["num_experts"], dtype=F32)
    return jnp.sum(onehot * w[..., None], axis=1), idx


def expert_layer(y, fp, arch, mm):
    """y [T, D] (normed) -> (the held routed experts' part plus the
    shared expert [T, D], the selection [T, k])."""
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
    return out + shared, idx


def layer(x, lp, *, kind: str, dense: bool, arch, mm):
    """One layer; ``lp`` = {"block", "mixer", "ffn"} in the weights' own
    dtype.  Returns (x, the expert layer's selection or None)."""
    bp, mp = (jax.tree.map(lambda a: a.astype(F32), lp[g])
              for g in ("block", "mixer"))
    b, s, d = x.shape
    x = x + attention(rmsnorm(x, bp["norm1"], arch["eps"]), mp, arch, mm,
                      kind)
    y = rmsnorm(x, bp["norm2"], arch["eps"]).reshape(b * s, d)
    if dense:
        out, idx = swiglu(y, *(lp["ffn"][k].astype(F32) for k in MLP),
                          mm), None
    else:
        out, idx = expert_layer(y, lp["ffn"], arch, mm)
    return x + out.reshape(b, s, d), idx


def _places(arch) -> list:
    """[(kind, its index in its stack, dense?, its index among the
    layers with its FFN)] a layer."""
    seen = {g: 0 for g in GROUPS}
    out, nd = [], arch["first_dense"]
    for li, kind in enumerate(arch["layer_kinds"]):
        out.append((kind, seen[kind], li < nd, li if li < nd else li - nd))
        seen[kind] += 1
    return out


def unstack(p: dict, arch) -> dict:
    """The program's layout (parameters stacked by group) as a list of
    layers {"block", "mixer", "ffn"}: the form the reference works in."""
    if "layers" in p:
        return p
    layers = []
    for li, (kind, gi, dense, fi) in enumerate(_places(arch)):
        ffn = ({k: p["block"][k][fi] for k in MLP} if dense else
               {k: a[fi] for k, a in p["moe"].items()})
        layers.append({
            "block": {k: a[li] for k, a in p["block"].items()
                      if k not in MLP},
            "mixer": {k: a[gi] for k, a in p[kind].items()},
            "ffn": ffn})
    return {"embed": p["embed"], "head": p["head"],
            "final_norm": p["final_norm"], "layers": layers}


def _layer_fns(arch, mm):
    return [functools.partial(layer, kind=kind, dense=dense, arch=arch,
                              mm=mm)
            for kind, _, dense, _ in _places(arch)]


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
        for g in ("block", *GROUPS, "moe"):
            for k, a in tree[g].items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
        return out
    for li, ((kind, gi, dense, fi), lp) in enumerate(
            zip(_places(arch), tree["layers"])):
        out.update({f"block/{li}/{k}": a for k, a in lp["block"].items()})
        out.update({f"{kind}/{gi}/{k}": a for k, a in lp["mixer"].items()})
        ffn = f"block/{fi}" if dense else f"moe/{fi}"
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
                chosen = jax.device_get(jnp.stack(idx))
                grad_norms = jax.device_get(first(make_p0(), p))
        delta_norms = jax.device_get(delta(make_p0(), p))
    return {"losses": losses, "chosen": chosen,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta_norms.items()}}
