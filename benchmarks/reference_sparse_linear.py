"""The plain reference of the sparse-and-linear hybrid decoder
(``minicpm_sala_*`` configurations): MiniCPM-SALA as its ``config.json``
states it and, where that is silent, as the configuration file's
``assumed`` block says, written in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes nothing the program made (weights by
``benchmarks/weights_sparse_linear.py`` from the seed, tokens from the
runner).

With ``x`` the residual stream [T, D], RMSNorm without bias (a plain
weight), ``c = scale_depth / sqrt(PUBLISHED depth)``:

* ``x0 = scale_emb E[tokens]``; every layer ``x += c mixer(rmsnorm1(x))``
  then ``x += c swiglu(rmsnorm2(x))``; logits ``(rmsnorm_f(x) *
  dim_model_base / hidden) H^T``, head untied, mean next-token
  cross-entropy.
* a sparse layer (``minicpm4``), ``H`` query heads over ``Hkv``
  key/value heads of ``dh`` lanes, groups of ``G = H / Hkv``: ``[q | z]
  = y W_q`` a head (the program's layout: a head's query lanes, then
  its lanes of the gate's projection), ``k = y W_k``, ``v = y W_v``;
  RMSNorm a head on ``q`` and ``k``; no position.  A sequence at or
  under ``dense_len``: causal softmax.  A longer one, for token ``t``
  and group ``g`` (``select``): compressed keys ``Kc[j] = mean(k[stride
  j : stride j + kernel])``; ``p[t, h, j] = softmax_j(q[t, h] . Kc[j] /
  sqrt(dh))`` over the ``j`` with ``stride j + kernel - 1 <= t``;
  ``P[t, j]`` its sum over the group's heads; block ``b`` (keys ``[block
  b, block (b + 1))``) scores ``max P[t, j]`` over the valid ``j`` of
  ``r b - 1 ... r b + r - 1``, ``r = block / stride``; the first
  ``init_blocks`` blocks and the ``window / block`` blocks ending at
  ``t``'s own score +inf; the selection is the ``topk`` best of blocks
  ``0 ... t // block`` by a stable sort (ties to the lower index), all
  of them where fewer exist; no gradient passes through it.  ``o[t, h]
  = softmax over the keys s <= t of the selected blocks of (q[t, h] .
  k[s] / sqrt(dh))`` times ``v[s]``, an explicit [rows, S] mask.  Then
  ``o * sigmoid(z)``, then ``W_o``.
* a lightning layer: ``q, k, v = y W_q, y W_k, y W_v`` at ``lh`` heads of
  ``ld`` lanes; RMSNorm a head on ``q`` and ``k``; RoPE by halves on
  every lane; the recurrence ONE TOKEN AT A TIME, ``S = lambda_h S +
  k_t^T v_t``, ``o_t = q_t S / sqrt(ld)`` (a ``lax.scan`` over tokens,
  never the chunked form), ``lambda_h = exp(-2^(-8 (h + 1) / lh) (1 - l
  / (L - 1) + 1e-5))`` with ``l`` the layer's PUBLISHED index and ``L``
  the published depth; RMSNorm over each head's output, times
  ``sigmoid(y W_z)``, then ``W_o``.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time, the recurrence is checkpointed in blocks of
tokens, attention and the selection run one (batch row, group) and one
block of query rows at a time, MLPs and head in blocks of rows.
``precision="int8"`` is the CONTROL, as in ``benchmarks/reference.py``:
every weight matmul's operands, forward and backward, on a per-tensor
int8 grid (the gates' projections among them); the selection's scores
stay float32, as the model computes them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import F32, MATMULS, embed, row_blocks
from benchmarks.reference_latent_moe import MLP, rmsnorm, rope, swiglu
from benchmarks.reference_latent_moe import head_loss as _head_loss

GROUP_OF = {"sparse": "gated", "lightning": "lightning"}
TOKEN_BLOCK = 128       # tokens of the recurrence between two kept states
ATTN_ROWS = 256         # query rows of a group's 16 heads at a time
NONE = -1               # a list's padding


def sigmoid(t):
    return 1.0 / (1.0 + jnp.exp(-t))


# ------------------------------------------------------- sparse layer
def select(q, k, sizes):
    """One batch row, one group: q [S, G, dh], k [S, dh] -> int32
    [S, topk], each token's blocks best first, ``NONE`` where fewer
    exist."""
    kernel, stride, block, topk, window, init, _ = sizes
    s, _, dh = q.shape
    n_c, nblk, ratio = (s - kernel) // stride + 1, s // block, block // stride
    starts = stride * jnp.arange(n_c)
    kc = jnp.mean(k[starts[:, None] + jnp.arange(kernel)[None, :]], axis=1)
    ends = starts + kernel - 1
    # block b pools the compressed keys ratio b - 1 ... ratio b + ratio - 1
    pooled = ratio * jnp.arange(nblk)[:, None] + jnp.arange(-1, ratio)[None]
    inside = (pooled >= 0) & (pooled < n_c)
    pooled = jnp.clip(pooled, 0, n_c - 1)
    blk = jnp.arange(nblk)[None, :]

    def rows(qb, pb):
        sc = jnp.einsum("rgd,jd->rgj", qb, kc, precision="highest") \
            / math.sqrt(dh)
        ok = ends[None, :] <= pb[:, None]                   # [R, J]
        sc = jnp.where(ok[:, None, :], sc, -jnp.inf)
        top = jnp.max(sc, -1, keepdims=True)
        e = jnp.where(ok[:, None, :],
                      jnp.exp(sc - jnp.where(jnp.isfinite(top), top, 0.0)),
                      0.0)
        den = jnp.sum(e, -1, keepdims=True)
        p = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=1)
        p = jnp.where(ok, p, -jnp.inf)
        score = jnp.max(jnp.where(inside[None], p[:, pooled], -jnp.inf), -1)
        own = (pb // block)[:, None]
        forced = (blk < init) | (blk > own - window // block)
        visible = blk <= own
        score = jnp.where(visible, jnp.where(forced, jnp.inf, score),
                          -jnp.inf)
        take = min(topk, nblk)
        order = jnp.argsort(-score, axis=-1, stable=True)[:, :take]
        seen = jnp.take_along_axis(visible, order, -1)
        lists = jnp.where(seen, order, NONE).astype(jnp.int32)
        return jnp.pad(lists, ((0, 0), (0, topk - take)),
                       constant_values=NONE)
    return row_blocks(rows, q, jnp.arange(s))


def attend(q, k, v, lists, block: int):
    """One batch row, one group: q [S, G, dh], k, v [S, dh], lists
    [S, n] -> [S, G, dh]: each token over the keys at or before it of
    its own blocks (all earlier keys where ``lists`` is None)."""
    s, _, dh = q.shape
    pos = jnp.arange(s)

    def rows(qb, pb, lb):
        sc = jnp.einsum("rgd,kd->rgk", qb, k, precision="highest") \
            / math.sqrt(dh)
        seen = pb[:, None] >= pos[None, :]
        if lb is not None:
            member = (lb[:, :, None] == jnp.arange(s // block)).any(1)
            seen &= jnp.repeat(member, block, axis=1)
        sc = jnp.where(seen[:, None, :], sc, -jnp.inf)
        sc = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
        pr = sc / jnp.sum(sc, -1, keepdims=True)
        return jnp.einsum("rgk,kd->rgd", pr, v, precision="highest")
    if lists is None:
        return row_blocks(lambda qb, pb: rows(qb, pb, None), q, pos,
                          block=ATTN_ROWS)
    return row_blocks(rows, q, pos, lists, block=ATTN_ROWS)


def sparse_attention(y, mp, arch, mm):
    """y [B, S, D] (normed) -> ([B, S, D], the selection [B, S, Hkv,
    topk] or None where the sequence does not select)."""
    b, s, d = y.shape
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    sizes = arch["sparse_sizes"]
    y2 = y.reshape(b * s, d)
    qz = mm(y2, mp["wq"]).reshape(b, s, h, 2 * dh)
    q = rmsnorm(qz[..., :dh], mp["q_norm"], arch["eps"])
    k = rmsnorm(mm(y2, mp["wk"]).reshape(b, s, hkv, dh), mp["k_norm"],
                arch["eps"])
    v = mm(y2, mp["wv"]).reshape(b, s, hkv, dh)

    def groups_first(t):    # [B, S, Hkv, ...] -> [B * Hkv, S, ...]
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape(b * hkv, *t.shape[2:])
    qg = groups_first(q.reshape(b, s, hkv, h // hkv, dh))
    kg, vg = groups_first(k), groups_first(v)
    selects = s > sizes[6]
    if selects:
        lists = jax.lax.map(
            lambda a: select(*a, sizes),
            jax.lax.stop_gradient((qg, kg)))
        o = jax.lax.map(lambda a: attend(*a, sizes[2]), (qg, kg, vg, lists))
    else:
        lists = None
        o = jax.lax.map(lambda a: attend(*a, None, sizes[2]), (qg, kg, vg))
    o = jnp.moveaxis(o.reshape(b, hkv, s, h // hkv, dh), 1, 2)
    o = o.reshape(b, s, h, dh) * sigmoid(qz[..., dh:])
    out = mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)
    if lists is None:
        return out, None
    return out, jnp.moveaxis(lists.reshape(b, hkv, s, -1), 1, 2)


# ---------------------------------------------------- lightning layer
def decays(heads: int, layer: int, depth: int):
    """``lambda_h`` [H] of PUBLISHED layer ``layer`` of ``depth``."""
    slopes = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads)
    return jnp.exp(-slopes * (1.0 - layer / (depth - 1) + 1e-5))


def decayed_rule(q, k, v, lam):
    """The recurrence over q, k, v [S, H, d], lam [H]: one token a step,
    each block of ``TOKEN_BLOCK`` tokens made again in the backward from
    the state that entered it."""
    s, h, d = q.shape

    def token(st, xs):
        q_t, k_t, v_t = xs
        st = lam[:, None, None] * st + k_t[:, :, None] * v_t[:, None, :]
        return st, jnp.sum(st * q_t[:, :, None], 1) / math.sqrt(d)

    def block(st, xs):
        return jax.lax.scan(token, st, xs)

    xs = (q, k, v)
    s0 = jnp.zeros((h, d, v.shape[-1]), F32)
    if s <= TOKEN_BLOCK or s % TOKEN_BLOCK:
        return block(s0, xs)[1]
    xs = tuple(x.reshape(s // TOKEN_BLOCK, TOKEN_BLOCK, *x.shape[1:])
               for x in xs)
    o = jax.lax.scan(jax.checkpoint(block), s0, xs)[1]
    return o.reshape(s, *o.shape[2:])


def lightning_attention(y, mp, arch, mm, li: int):
    """y [B, S, D] (normed) -> [B, S, D]; ``li`` the layer's published
    index."""
    b, s, d = y.shape
    h, dh = arch["lightning_heads"], arch["lightning_dim"]
    y2 = y.reshape(b * s, d)
    q, k, v, z = (mm(y2, mp[w]).reshape(b, s, h, dh)
                  for w in ("wq", "wk", "wv", "wz"))
    turn = jax.vmap(functools.partial(rope, theta=arch["rope_theta"]))
    q = turn(rmsnorm(q, mp["q_norm"], arch["eps"]))
    k = turn(rmsnorm(k, mp["k_norm"], arch["eps"]))
    lam = decays(h, li, arch["published_layers"])
    o = jax.lax.map(lambda a: decayed_rule(*a, lam), (q, k, v))
    o = rmsnorm(o, mp["o_norm"], arch["eps"]) * sigmoid(z)
    return mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)


# ------------------------------------------------------------ a layer
def layer(x, lp, *, kind: str, li: int, arch, mm):
    """One layer; ``lp`` = {"block", "mixer", "ffn"} in the weights' own
    dtype.  Returns (x, a sparse layer's selection or None)."""
    bp, mp = (jax.tree.map(lambda a: a.astype(F32), lp[g])
              for g in ("block", "mixer"))
    b, s, d = x.shape
    c = arch["residual_scale"]
    y = rmsnorm(x, bp["norm1"], arch["eps"])
    if kind == "sparse":
        out, lists = sparse_attention(y, mp, arch, mm)
    else:
        out, lists = lightning_attention(y, mp, arch, mm, li), None
    x = x + c * out
    y = rmsnorm(x, bp["norm2"], arch["eps"]).reshape(b * s, d)
    out = swiglu(y, *(lp["ffn"][k].astype(F32) for k in MLP), mm)
    return x + c * out.reshape(b, s, d), lists


def embedded(table, tokens, scale: float):
    return scale * embed(table, tokens)


def head_loss(x, final_norm, head, targets, mm, eps, scale: float):
    """Mean cross-entropy against targets [B, S]; ``head`` [V, D]; the
    final normed stream times ``scale`` before the head (the scale rides
    on the norm's weight: the same product)."""
    return _head_loss(x, scale * final_norm.astype(F32), head, targets, mm,
                      eps)


def _places(arch) -> list:
    """[(kind, its index in its stack)] a layer."""
    seen = {k: 0 for k in GROUP_OF}
    out = []
    for kind in arch["layer_kinds"]:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def unstack(p: dict, arch) -> dict:
    """The program's layout (parameters stacked by group) as a list of
    layers {"block", "mixer", "ffn"}: the form the reference works in."""
    if "layers" in p:
        return p
    layers = []
    for li, (kind, gi) in enumerate(_places(arch)):
        layers.append({
            "block": {k: a[li] for k, a in p["block"].items()
                      if k not in MLP},
            "mixer": {k: a[gi] for k, a in p[GROUP_OF[kind]].items()},
            "ffn": {k: p["block"][k][li] for k in MLP}})
    return {"embed": p["embed"], "head": p["head"],
            "final_norm": p["final_norm"], "layers": layers}


def _layer_fns(arch, mm):
    return [functools.partial(layer, kind=kind, li=li, arch=arch, mm=mm)
            for li, kind in enumerate(arch["layer_kinds"])]


def loss_fn(p, tokens, arch, precision="float32"):
    """Mean next-token cross-entropy of a [B, S+1] batch as one function
    (small sizes; ``LayerwiseGrad`` is the same arithmetic a layer at a
    time)."""
    mm = MATMULS[precision]
    p = unstack(p, arch)
    x = embedded(p["embed"], tokens[:, :-1], arch["embed_scale"])
    for fn, lp in zip(_layer_fns(arch, mm), p["layers"]):
        x, _ = fn(x, lp)
    return head_loss(x, p["final_norm"], p["head"], tokens[:, 1:], mm,
                     arch["eps"], arch["logit_scale"])


class LayerwiseGrad:
    """Loss, gradients and the sparse layers' selections by plain
    backpropagation, one jitted call for each layer and direction (a
    lightning layer's decay is its own).  Gradients come back in the
    weights' own dtype: what the optimizer gets."""

    def __init__(self, arch, precision="float32"):
        mm = MATMULS[precision]

        def pair(fn):
            return (jax.jit(fn), jax.jit(
                lambda x, lp, ct: jax.vjp(fn, x, lp, has_aux=True)[1](ct)))
        self.layers = [pair(fn) for fn in _layer_fns(arch, mm)]
        self.embed = jax.jit(functools.partial(
            embedded, scale=arch["embed_scale"]))
        self.head = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, mm=mm, eps=arch["eps"],
                              scale=arch["logit_scale"]),
            argnums=(0, 1, 2)))
        self.embed_vjp = jax.jit(
            lambda table, tokens, ct: jax.vjp(
                lambda t: embedded(t, tokens, arch["embed_scale"]),
                table)[1](ct)[0])

    def __call__(self, p, tokens):
        """(loss, gradients, [selection [B, S, Hkv, topk] of each
        selecting sparse layer])."""
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        xs, chosen = [self.embed(p["embed"], inp)], []
        for (fwd, _), lp in zip(self.layers, p["layers"]):
            x, lists = fwd(xs[-1], lp)
            xs.append(x)
            if lists is not None:
                chosen.append(lists)
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
        for g in ("block", *sorted({GROUP_OF[k]
                                    for k in arch["layer_kinds"]})):
            for k, a in tree[g].items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
        return out
    for li, ((kind, gi), lp) in enumerate(zip(_places(arch),
                                              tree["layers"])):
        out.update({f"block/{li}/{k}": a for k, a in lp["block"].items()})
        out.update({f"{GROUP_OF[kind]}/{gi}/{k}": a
                    for k, a in lp["mixer"].items()})
        out.update({f"block/{li}/{k}": a for k, a in lp["ffn"].items()})
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
    copy of them lives through the backward passes.  Returns losses, the
    two sets of norms and ``"blocks"``, the first step's selections
    [sparse layers, B, S, Hkv, topk] (host integers; None where no layer
    selects)."""
    grad = LayerwiseGrad(arch, precision)

    def sgd(p, g):
        return jax.tree.map(
            lambda a, b: (a.astype(F32) - lr * b.astype(a.dtype)
                          .astype(F32)).astype(a.dtype), p, g)

    update = jax.jit(sgd, donate_argnums=(0,))
    first, delta = norm_readers(lr, arch)
    p = unstack(make_p0(), arch)
    losses, grad_norms, blocks = [], None, None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            loss, g, lists = grad(p, tokens)
            p = update(p, g)
            del g
            losses.append(float(loss))
            if i == 0:
                blocks = (jax.device_get(jnp.stack(lists)) if lists
                          else None)
                grad_norms = jax.device_get(first(make_p0(), p))
        delta_norms = jax.device_get(delta(make_p0(), p))
    return {"losses": losses, "blocks": blocks,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta_norms.items()}}
