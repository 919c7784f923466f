"""The plain reference of the hybrid decoder (``phi4miniflash_*``
configurations): Mamba, window, full and cross attention layers and
gated memory units in one stack, differential attention, LayerNorm with
bias, SwiGLU, a tied head, no positional encoding.  Written from the
papers (arXiv:2507.06607 SambaY, 2312.00752 Mamba, 2410.05258
Differential Transformer) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; it imports nothing of the
program and takes nothing the program made (weights by
``benchmarks/weights_hybrid.py`` from the seed, tokens from the runner).

Every layer l: ``x += mixer_l(LN(x)); x += W_down(silu(W_gate y) * W_up
y)``, ``y = LN(x)``.  The mixers:

* mamba: ``(u, z) = split(y W_in)``; ``u = silu(conv_4(u) + b)`` (causal,
  depthwise); ``(r, B_t, C_t) = split(u W_x)``; ``delta = softplus(r
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(delta_t A) h_{t-1} +
  (delta_t u_t) B_t^T``; ``s_t = h_t C_t + D u_t``; out ``(s * silu(z))
  W_out``.  The recurrence is a ``lax.scan`` over time, one step as it is
  written.  The last mamba layer's ``s`` is the memory ``m``.
* gmu: ``(m * silu(y W_1)) W_2``.
* window / full / cross attention: heads paired by adjacent index,
  ``A_i = softmax(q_i k_i^T / sqrt(dh) + mask)`` dense, ``o = (A_1 -
  lambda A_2) [v1; v2]``, ``lambda = exp(lq1.lk1) - exp(lq2.lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``, ``o =
  RMSNorm(o) * (1 - lambda_init)``, out ``concat(o) W_o``; the mask is
  causal, and ``i - j < window`` in window layers; a cross layer has
  ``W_q``, ``W_o``, lambdas and norm of its own over the full layer's k
  and v.

What is not plain is only what makes the timed size fit: backpropagation
goes a layer at a time (the cotangents of the memory and of the shared
keys and values are summed over the layers that read them and handed to
the layer that made them), attention one (batch row, head pair) at a
time, the scan's backward by chunks of time steps, the MLP and the head
in blocks of rows.  ``precision="int8"`` is the CONTROL, as in
``benchmarks/reference.py``: every weight matmul's operands, forward and
backward, on a per-tensor int8 grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import F32, MATMULS, row_blocks, silu

GROUP_OF = {"mamba": "mamba", "window": "attn", "full": "attn",
            "gmu": "gmu", "cross": "cross"}
SCAN_CHUNK = 256     # time steps whose states the backward keeps at once
SCAN_UNROLL = 8      # steps a loop iteration (the loop's overhead only)


def memory_layer(kinds) -> int:
    return len(kinds) - 1 - tuple(kinds)[::-1].index("mamba")


def lambda_init(li):
    return 0.8 - 0.6 * jnp.exp(-0.3 * li)


def layernorm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def softplus(x):
    return jnp.logaddexp(x, 0.0)


# ------------------------------------------------------------- mamba
def recurrence(u, delta, a, bm, cm, d):
    """One batch row: u, delta [T, E], a [E, N], bm, cm [T, N], d [E]
    -> s [T, E].  The state is held as [N, E] (channels last), which
    is the same arithmetic in the layout the chip's vector unit fills."""
    t, e = u.shape
    at = a.T

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[None, :] * at) * h \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0) + d * u_t

    def steps(h, xs):
        return jax.lax.scan(step, h, xs, unroll=SCAN_UNROLL)

    h0 = jnp.zeros((at.shape[0], e), F32)
    xs = (u, delta, bm, cm)
    if t > SCAN_CHUNK and t % SCAN_CHUNK == 0:
        xs = tuple(x.reshape(t // SCAN_CHUNK, SCAN_CHUNK, -1) for x in xs)
        _, s = jax.lax.scan(jax.checkpoint(steps), h0, xs)
        return s.reshape(t, e)
    return steps(h0, xs)[1]


def mamba(y, mp, arch, mm):
    """y [B, S, D] -> (out [B, S, D], s [B, S, E])."""
    b, s, d = y.shape
    e, n, r = arch["ssm_inner"], arch["ssm_state"], arch["ssm_dt_rank"]
    uz = mm(y.reshape(b * s, d), mp["w_in"]).reshape(b, s, 2 * e)
    u, z = uz[..., :e], uz[..., e:]
    k = mp["conv_w"].shape[0]
    up = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    u = silu(sum(up[:, i:i + s] * mp["conv_w"][i] for i in range(k))
             + mp["conv_b"])
    xp = mm(u.reshape(b * s, e), mp["w_x"])
    delta = softplus(mm(xp[:, :r], mp["w_dt"]) + mp["b_dt"])
    sc = jax.vmap(recurrence, (0, 0, None, 0, 0, None))(
        u, delta.reshape(b, s, e), -jnp.exp(mp["a_log"]),
        xp[:, r:r + n].reshape(b, s, n), xp[:, r + n:].reshape(b, s, n),
        mp["d_skip"])
    out = mm((sc * silu(z)).reshape(b * s, e), mp["w_out"])
    return out.reshape(b, s, d), sc


def gmu(y, memory, mp, mm):
    b, s, d = y.shape
    e = memory.shape[-1]
    gate = silu(mm(y.reshape(b * s, d), mp["w1"]))
    return mm(memory.reshape(b * s, e) * gate, mp["w2"]).reshape(b, s, d)


# --------------------------------------------------------- attention
def diff_attention_one(q1, q2, k1, k2, v, lam, window: int):
    """One batch row, one head pair: q, k [S, dh], v [S, 2 dh]."""
    s, dh = q1.shape
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    allowed = j <= i
    if window:
        allowed &= (i - j) < window

    def soft(q, k):
        sc = jnp.einsum("qd,kd->qk", q, k, precision="highest") \
            / math.sqrt(dh)
        sc = jnp.where(allowed, sc, -jnp.inf)
        sc = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
        return sc / jnp.sum(sc, -1, keepdims=True)
    return jnp.einsum("qk,kd->qd", soft(q1, k1) - lam * soft(q2, k2), v,
                      precision="highest")


def project_kv(y, mp, arch, mm):
    """(k [B, S, Pkv, 2, dh], v [B, S, Pkv, 2 dh]): pairs of adjacent
    heads."""
    b, s, d = y.shape
    hkv, dh = arch["num_kv_heads"], arch["head_dim"]
    y2 = y.reshape(b * s, d)
    return (mm(y2, mp["wk"]).reshape(b, s, hkv // 2, 2, dh),
            mm(y2, mp["wv"]).reshape(b, s, hkv // 2, 2 * dh))


def diff_attention(y, mp, kv, li, arch, mm, window: int):
    b, s, d = y.shape
    h, dh = arch["num_heads"], arch["head_dim"]
    k, v = kv
    pairs = h // 2
    group = pairs // k.shape[2]
    q = mm(y.reshape(b * s, d), mp["wq"]).reshape(b, s, pairs, 2, dh)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    lam0 = lambda_init(li)
    lam = (jnp.exp(jnp.sum(mp["lambda_q1"] * mp["lambda_k1"]))
           - jnp.exp(jnp.sum(mp["lambda_q2"] * mp["lambda_k2"])) + lam0)

    def heads_first(t):         # [B, S, P, F] -> [B * P, S, F]
        return t.transpose(0, 2, 1, 3).reshape(b * pairs, s, t.shape[-1])
    one = jax.checkpoint(functools.partial(diff_attention_one,
                                           lam=lam, window=window))
    o = jax.lax.map(lambda a: one(*a), tuple(
        heads_first(t) for t in (q[:, :, :, 0], q[:, :, :, 1],
                                 k[:, :, :, 0], k[:, :, :, 1], v)))
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + arch["eps"])
    o = o * mp["sub_norm"] * (1.0 - lam0)
    o = o.reshape(b, pairs, s, 2 * dh).transpose(0, 2, 1, 3)
    return mm(o.reshape(b * s, h * dh), mp["wo"]).reshape(b, s, d)


# ------------------------------------------------------------- layers
def mlp(y, bp, mm):
    def rows(yb):
        return mm(silu(mm(yb, bp["w_gate"])) * mm(yb, bp["w_up"]),
                  bp["w_down"])
    return row_blocks(rows, y)


def layer(x, lp, memory, kv, li, *, kind: str, hands: bool, arch, mm):
    """One layer; ``lp`` = {"block": ..., "mixer": ...} in the weights'
    own dtype.  Returns (x, handed): the memory from the mamba layer
    that ``hands``, (k, v) from the full layer, else None."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    bp, mp = lp["block"], lp["mixer"]
    b, s, d = x.shape
    y = layernorm(x, bp["norm1"], bp["norm1_b"], arch["eps"])
    handed = None
    if kind == "mamba":
        out, sc = mamba(y, mp, arch, mm)
        handed = sc if hands else None
    elif kind == "gmu":
        out = gmu(y, memory, mp, mm)
    else:
        if kind != "cross":
            kv = project_kv(y, mp, arch, mm)
        handed = kv if kind == "full" else None
        out = diff_attention(y, mp, kv, li, arch, mm,
                             arch["window"] if kind == "window" else 0)
    x = x + out
    y = layernorm(x, bp["norm2"], bp["norm2_b"], arch["eps"])
    return x + mlp(y.reshape(b * s, d), bp, mm).reshape(b, s, d), handed


def head_loss(x, final_norm, final_norm_b, table, targets, mm, eps):
    """Mean cross-entropy against targets [B, S]; the head is the
    embedding table, transposed."""
    b, s, d = x.shape
    xf = layernorm(x, final_norm.astype(F32), final_norm_b.astype(F32),
                   eps).reshape(b * s, d)
    head = table.astype(F32).T

    def rows(xb, tb):
        logits = mm(xb, head)
        m = jnp.max(logits, -1, keepdims=True)
        lse = m[:, 0] + jnp.log(jnp.sum(jnp.exp(logits - m), -1))
        return lse - jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
    return jnp.mean(row_blocks(rows, xf, targets.reshape(-1)))


def embed(table, tokens):
    return table[tokens].astype(F32)


def unstack(p: dict, kinds) -> dict:
    """The program's layout (parameters stacked by kind) as a list of
    layers {"block", "mixer"}: the form the reference works in."""
    if "layers" in p:
        return p
    count: dict = {}
    layers = []
    for li, kind in enumerate(kinds):
        g = GROUP_OF[kind]
        gi = count.get(g, 0)
        count[g] = gi + 1
        layers.append({
            "block": {k: a[li] for k, a in p["block"].items()},
            "mixer": {k: a[gi] for k, a in p[g].items()}})
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "final_norm_b": p["final_norm_b"], "layers": layers}


def _layer_fns(arch, mm):
    kinds = arch["layer_kinds"]
    mem = memory_layer(kinds) if "mamba" in kinds else -1
    return [functools.partial(layer, kind=k, hands=(li == mem),
                              arch=arch, mm=mm)
            for li, k in enumerate(kinds)]


def loss_fn(p, tokens, arch, precision="float32"):
    """Mean next-token cross-entropy of a [B, S+1] batch as one function
    (small sizes; ``LayerwiseGrad`` is the same arithmetic a layer at a
    time)."""
    mm = MATMULS[precision]
    p = unstack(p, arch["layer_kinds"])
    x = embed(p["embed"], tokens[:, :-1])
    memory = kv = None
    for li, (fn, lp) in enumerate(zip(_layer_fns(arch, mm), p["layers"])):
        x, handed = fn(x, lp, memory, kv, float(li))
        if fn.keywords["kind"] == "mamba" and handed is not None:
            memory = handed
        elif fn.keywords["kind"] == "full":
            kv = handed
    return head_loss(x, p["final_norm"], p["final_norm_b"], p["embed"],
                     tokens[:, 1:], mm, arch["eps"])


def _add(a, b):
    if a is None:
        return b
    return jax.tree.map(jnp.add, a, b)


class LayerwiseGrad:
    """Loss and gradients of ``loss_fn`` by plain backpropagation, one
    jitted call for each layer and direction.  Gradients come back in
    the weights' own dtype: what the optimizer gets."""

    def __init__(self, arch, precision="float32"):
        mm = MATMULS[precision]
        self.kinds = arch["layer_kinds"]
        self.memory_layer = (memory_layer(self.kinds)
                             if "mamba" in self.kinds else -1)
        fns = _layer_fns(arch, mm)
        jitted: dict = {}       # one compile a (kind, hands), not a layer

        def pair(fn):
            key = (fn.keywords["kind"], fn.keywords["hands"])
            if key not in jitted:
                def vjp(x, lp, memory, kv, li, ct):
                    return jax.vjp(
                        lambda *a: fn(*a, li), x, lp, memory, kv)[1](ct)
                jitted[key] = (jax.jit(fn), jax.jit(vjp))
            return jitted[key]
        self.layers = [pair(fn) for fn in fns]
        self.embed = jax.jit(embed)
        self.head = jax.jit(jax.value_and_grad(
            functools.partial(head_loss, mm=mm, eps=arch["eps"]),
            argnums=(0, 1, 2, 3)))
        self.embed_vjp = jax.jit(
            lambda table, tokens, ct, g_head: (jax.vjp(
                lambda t: embed(t, tokens), table)[1](ct)[0].astype(F32)
                + g_head.astype(F32)).astype(table.dtype))

    def __call__(self, p, tokens):
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        xs = [self.embed(p["embed"], inp)]
        memory = kv = None
        for li, ((fwd, _), lp) in enumerate(zip(self.layers,
                                                p["layers"])):
            x, handed = fwd(xs[-1], lp, memory, kv, float(li))
            xs.append(x)
            if self.kinds[li] == "mamba" and handed is not None:
                memory = handed
            elif self.kinds[li] == "full":
                kv = handed
        loss, (ct, g_norm, g_norm_b, g_head) = self.head(
            xs.pop(), p["final_norm"], p["final_norm_b"], p["embed"], tgt)
        g_layers, ct_memory, ct_kv = [], None, None
        for li in reversed(range(len(self.kinds))):
            kind = self.kinds[li]
            reads_m, reads_kv = kind == "gmu", kind == "cross"
            hands_m = li == self.memory_layer
            if hands_m and ct_memory is None:   # no layer read it
                ct_memory = jnp.zeros_like(memory)
            if kind == "full" and ct_kv is None:
                ct_kv = jax.tree.map(jnp.zeros_like, kv)
            ct_handed = (ct_memory if hands_m
                         else ct_kv if kind == "full" else None)
            ct, g_lp, d_m, d_kv = self.layers[li][1](
                xs.pop(), p["layers"][li], memory if reads_m else None,
                kv if reads_kv else None, float(li), (ct, ct_handed))
            if hands_m:
                ct_memory = None
            if reads_m:
                ct_memory = _add(ct_memory, d_m)
            if reads_kv:
                ct_kv = _add(ct_kv, d_kv)
            g_layers.append(g_lp)
        return loss, {
            "embed": self.embed_vjp(p["embed"], inp, ct, g_head),
            "layers": g_layers[::-1], "final_norm": g_norm,
            "final_norm_b": g_norm_b}


# ------------------------------------------------------- train steps
def _names(tree, kinds) -> dict:
    """{name: leaf}: "embed", "block/<layer>/<leaf>",
    "<group>/<index in group>/<leaf>", whichever layout ``tree`` has."""
    out = {k: tree[k] for k in ("embed", "final_norm", "final_norm_b")}
    if "layers" in tree:
        count: dict = {}
        for li, (kind, lp) in enumerate(zip(kinds, tree["layers"])):
            g = GROUP_OF[kind]
            gi = count.get(g, 0)
            count[g] = gi + 1
            out.update({f"block/{li}/{k}": a
                        for k, a in lp["block"].items()})
            out.update({f"{g}/{gi}/{k}": a
                        for k, a in lp["mixer"].items()})
        return out
    for g, leaves in tree.items():
        if isinstance(leaves, dict):
            for k, a in leaves.items():
                out.update({f"{g}/{i}/{k}": a[i]
                            for i in range(a.shape[0])})
    return out


def diff_norms(a, b, kinds) -> dict:
    """Euclidean norm of a - b, one for each layer's each weight."""
    a, b = _names(a, kinds), _names(b, kinds)
    return {k: jnp.sqrt(jnp.sum((a[k].astype(F32) - b[k].astype(F32))
                                ** 2)) for k in a}


def norm_readers(lr: float, kinds):
    """(first, delta): jitted readers of the per-leaf norms of the first
    gradient as the optimizer got it, (p0 - p1) / lr, and of the
    parameters' change p0 - p; the same two for both sides."""
    kinds = tuple(kinds)
    delta = jax.jit(functools.partial(diff_norms, kinds=kinds))
    first = jax.jit(lambda a, b: jax.tree.map(
        lambda n: n / lr, diff_norms(a, b, kinds)))
    return first, delta


def sgd_steps(make_p0, batches, arch, lr: float, precision="float32"):
    """The program's optimizer, followed exactly: stateless SGD on
    weights STORED in their own dtype, ``p <- dtype(p - lr * dtype(g))``,
    one step for each batch; all else in float32.  ``make_p0()`` gives
    the seeded weights anew each time it is called, so that no second
    copy of them lives through the backward passes.  Returns what
    ``reference.sgd_steps`` returns."""
    kinds = arch["layer_kinds"]
    grad = LayerwiseGrad(arch, precision)

    def sgd(p, g):
        return jax.tree.map(
            lambda a, b: (a.astype(F32) - lr * b.astype(a.dtype)
                          .astype(F32)).astype(a.dtype), p, g)

    update = jax.jit(sgd, donate_argnums=(0,))
    first, delta = norm_readers(lr, kinds)
    p = unstack(make_p0(), kinds)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            loss, g = grad(p, tokens)
            p = update(p, g)
            del g
            losses.append(float(loss))
            if i == 0:
                grad_norms = jax.device_get(first(make_p0(), p))
        delta_norms = jax.device_get(delta(make_p0(), p))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": {k: float(v) for k, v in delta_norms.items()}}
