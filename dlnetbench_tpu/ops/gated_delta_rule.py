"""Gated delta rule: the recurrence of a Gated DeltaNet mixer
(arXiv:2412.06464), a matrix state a head, decayed by a learned gate and
corrected by a rank-one delta a token,

    S' = exp(g_t) S_{t-1}                                    [dk, dv]
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t                                          [dv]

over ``q, k: [B, T, H, dk]``, ``v: [B, T, H, dv]``, ``g, beta:
[B, T, H]`` (``g <= 0`` the log of the decay).  The state is float32
whatever the inputs are.

It runs in chunks of ``C`` tokens as matrix products (the WY / UT form
of the paper's section 3).  With ``c_i`` the sum of ``g`` over the
chunk's tokens up to ``i`` and ``G_ij = exp(c_i - c_j)`` for ``i >= j``:

    A  = tril(diag(beta) (G * K K^T), -1)
    T  = (I + A)^-1 diag(beta)        unit lower triangular, float32
    W  = T (K * exp(c)),  U = T V
    V' = U - W S                      S the state entering the chunk
    O  = (Q * exp(c)) S + tril(G * Q K^T) V'
    S <- exp(c_C) S + (K * exp(c_C - c))^T V'

ONLY differences ``c_i - c_j <= 0`` are ever exponentiated (``exp(-c)``
overflows where ``exp(c)`` merely underflows): the mask goes on the
exponent, not on the result.  Everything that does not read the state is
computed for all chunks at once (``_prepare``: einsums; the inverse by
blocks, ``_inv_unit_lower``); what reads it is a sweep over the chunks
that carries ``S``: ``impl="xla"`` a ``lax.scan``, ``impl="pallas"`` the
kernel ``gdr_fwd`` (grid heads x chunks, the chunk axis sequential, the
state in a float32 VMEM scratch).  ``"auto"`` is ``_AUTO`` (what the
chip timed faster at the benchmark's shapes, PERF.md) on a TPU and XLA
elsewhere; off the TPU ``"pallas"`` runs in interpret mode, for tests.

The backward is written by hand.  The forward keeps the state entering
EVERY chunk (``[B, H, T / C, dk, dv]`` in the inputs' dtype, the
operand the backward's products take it as: 32 KiB a head and chunk at
128 x 128 in bfloat16) and nothing else of its own; the backward makes
the chunks' matrices again, sweeps the chunks last to first carrying
``dS`` (``lax.scan`` or the kernel ``gdr_bwd``) and takes every gradient
as products over all chunks at once.  It wears the ``linattn.rule``
scope itself: a ``custom_vjp``'s backward is traced outside the
caller's.

Heads do not meet in the rule, so a long sequence runs ``_head_block``
heads at a time, one block after another (``lax.map``; unrolled, the
step took twice as long to compile): the matrices of all chunks of all
heads at once are some 120 bytes a token and head and lane in the
backward, 3.7 GB at 16384 tokens and 32 heads of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.metrics.spans import scope
from dlnetbench_tpu.ops import pallas_common

_F32 = pallas_common.F32
CHUNK = 64
_INV_BASE = 16      # a diagonal block inverted as a product of powers
_AUTO = "pallas"    # the sweep "auto" takes on a TPU
_HEAD_TOKENS = 1 << 16      # tokens x heads of one pass over the chunks


def _resolve(impl: str) -> str:
    if impl == "auto":
        return _AUTO if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown gated_delta_rule impl {impl!r}")
    return impl


# ------------------------------------------------- a chunk's matrices

def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _mm_exact(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision="highest",
                      preferred_element_type=_F32)


def _inv_unit_lower(a):
    """``(I + a)^-1`` for ``a [..., n, n]`` strictly lower triangular,
    float32.  Diagonal blocks of ``_INV_BASE`` rows by the product
    ``(I - a)(I + a^2)(I + a^4)...`` (exact: ``a`` is nilpotent), then
    pairs of blocks merged, ``[[X1, 0], [-X2 a21 X1, X2]]``, until one
    is left."""
    n = a.shape[-1]
    if n <= _INV_BASE:
        eye = jnp.eye(n, dtype=_F32)
        x, p, span = eye - a, a, 2
        while span < n:
            p = _mm_exact("...ij,...jk->...ik", p, p)
            x = _mm_exact("...ij,...jk->...ik", x, eye + p)
            span *= 2
        return x
    h = n // 2
    x1 = _inv_unit_lower(a[..., :h, :h])
    x2 = _inv_unit_lower(a[..., h:, h:])
    x21 = -_mm_exact("...ij,...jk->...ik", x2,
                     _mm_exact("...ij,...jk->...ik", a[..., h:, :h], x1))
    top = jnp.concatenate([x1, jnp.zeros_like(x21).swapaxes(-1, -2)], -1)
    return jnp.concatenate([top, jnp.concatenate([x21, x2], -1)], -2)


def _chunks(x, chunk: int):
    """[B, T, H, ...] -> [B, H, T / chunk, chunk, ...]."""
    b, t, h = x.shape[:3]
    x = x.reshape(b, t // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def _tokens(x):
    """[B, H, nc, chunk, ...] -> [B, T, H, ...]."""
    x = jnp.moveaxis(x, 1, 3)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _prepare(q, k, v, g, beta):
    """The matrices of every chunk that do not read the state, from
    inputs already in chunks ([B, H, nc, C, ...]).  Matmul operands are
    in the inputs' dtype, sums in float32; ``G``, ``X = (I + A)^-1`` and
    the products with the gates are float32."""
    dt, c = q.dtype, q.shape[3]
    cum = jnp.cumsum(g.astype(_F32), axis=-1)               # c_i <= 0
    low = jnp.tril(jnp.ones((c, c), bool))
    gam = jnp.exp(jnp.where(low, cum[..., :, None] - cum[..., None, :],
                            -jnp.inf))
    eg = jnp.exp(cum)[..., None]
    er = jnp.exp(cum[..., -1:] - cum)[..., None]
    bt = beta.astype(_F32)
    kk = _mm("...id,...jd->...ij", k, k)
    a = jnp.where(jnp.tril(low, -1), bt[..., :, None] * gam * kk, 0.0)
    x = _inv_unit_lower(a)
    t = (x * bt[..., None, :]).astype(dt)
    kg = (k.astype(_F32) * eg).astype(dt)
    qk = _mm("...id,...jd->...ij", q, k)
    return {
        "gam": gam, "eg": eg, "er": er, "kk": kk, "qk": qk,
        "x": x, "t": t, "kg": kg,
        "w": _mm("...ij,...jd->...id", t, kg).astype(dt),
        "u": _mm("...ij,...jd->...id", t, v).astype(dt),
        "qg": (q.astype(_F32) * eg).astype(dt),
        "p": (gam * qk).astype(dt),
        "kr": (k.astype(_F32) * er).astype(dt),
        "ec": jnp.exp(cum[..., -1]),
    }


# ------------------------------------------------------ the sweeps, xla

def _chunk_major(x):
    """[B, H, nc, ...] -> [nc, B, H, ...]."""
    return jnp.moveaxis(x, 2, 0)


def _xla_fwd(m):
    """(O [B, H, nc, C, dv] float32, S0 [B, H, nc, dk, dv]: the state
    entering each chunk, as the products take it)."""
    dt = m["w"].dtype
    b, h, _, _, dk = m["w"].shape
    dv = m["u"].shape[-1]

    def step(s, xs):
        w, u, qg, p, kr, ec = xs
        sb = s.astype(dt)
        vn = u.astype(_F32) - _mm("bhid,bhde->bhie", w, sb)
        o = _mm("bhid,bhde->bhie", qg, sb) \
            + _mm("bhij,bhje->bhie", p, vn.astype(dt))
        s_out = ec[..., None, None] * s \
            + _mm("bhid,bhie->bhde", kr, vn.astype(dt))
        return s_out, (o, sb)

    xs = tuple(_chunk_major(m[n]) for n in ("w", "u", "qg", "p", "kr", "ec"))
    _, (o, s0) = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), _F32), xs)
    return jnp.moveaxis(o, 0, 2), jnp.moveaxis(s0, 0, 2)


def _xla_bwd(m, do):
    """(dV' [B, H, nc, C, dv], dS_C [B, H, nc, dk, dv]: the gradient of
    the state LEAVING each chunk), float32, last chunk first."""
    dt = m["w"].dtype
    b, h, _, _, dk = m["w"].shape
    dv = do.shape[-1]

    def step(ds, xs):
        w, qg, p, kr, ec, d_o = xs
        dvn = _mm("bhij,bhie->bhje", p, d_o) \
            + _mm("bhid,bhde->bhie", kr, ds.astype(dt))
        ds_in = _mm("bhid,bhie->bhde", qg, d_o) + ec[..., None, None] * ds \
            - _mm("bhid,bhie->bhde", w, dvn.astype(dt))
        return ds_in, (dvn, ds)

    xs = tuple(_chunk_major(x) for x in
               (m["w"], m["qg"], m["p"], m["kr"], m["ec"], do))
    _, (dvn, dsc) = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), _F32), xs,
                                 reverse=True)
    return jnp.moveaxis(dvn, 0, 2), jnp.moveaxis(dsc, 0, 2)


# --------------------------------------------------- the sweeps, pallas

def _compiler_params():
    return pallas_common.compiler_params(("parallel", "arbitrary"),
                                         vmem_limit_mb=64)


def _heads(x):
    """[B, H, nc, ...] -> [B * H, nc, ...]."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _lanes(ec, dv: int):
    """[BH, nc] -> [BH, nc, 1, dv], every lane alike: a chunk's decay as
    a row the state is multiplied with."""
    return jnp.broadcast_to(ec[..., None, None], (*ec.shape, 1, dv))


def _fwd_kernel(w_ref, u_ref, qg_ref, p_ref, krt_ref, ec_ref,
                o_ref, s0_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    dt = w_ref.dtype
    sb = s.astype(dt)
    s0_ref[0, 0] = sb
    vn = u_ref[0, 0].astype(_F32) - jnp.dot(
        w_ref[0, 0], sb, preferred_element_type=_F32)
    vb = vn.astype(dt)
    o_ref[0, 0] = (
        jnp.dot(qg_ref[0, 0], sb, preferred_element_type=_F32)
        + jnp.dot(p_ref[0, 0], vb, preferred_element_type=_F32)
    ).astype(o_ref.dtype)
    s_ref[...] = ec_ref[0, 0] * s + jnp.dot(
        krt_ref[0, 0], vb, preferred_element_type=_F32)


def _pallas_fwd(m):
    b, h, nc, c, dk = m["w"].shape
    dv = m["u"].shape[-1]

    def at(*block):
        return pl.BlockSpec((1, 1, *block), lambda i, j: (i, j, 0, 0))

    o, s0 = pl.pallas_call(
        _fwd_kernel, grid=(b * h, nc),
        in_specs=[at(c, dk), at(c, dv), at(c, dk), at(c, c), at(dk, c),
                  at(1, dv)],
        out_specs=[at(c, dv), at(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((b * h, nc, c, dv), _F32),
                   jax.ShapeDtypeStruct((b * h, nc, dk, dv),
                                        m["w"].dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_compiler_params(),
        name="gdr_fwd",
        interpret=pallas_common.interpret_mode(),
    )(*(_heads(m[n]) for n in ("w", "u", "qg", "p")),
      _heads(m["kr"]).swapaxes(-1, -2), _lanes(_heads(m["ec"]), dv))
    return (o.reshape(b, h, nc, c, dv), s0.reshape(b, h, nc, dk, dv))


def _bwd_kernel(wt_ref, qgt_ref, pt_ref, kr_ref, ec_ref, do_ref,
                dvn_ref, dsc_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)     # the LAST chunk: time reversed
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    ds = ds_ref[...]
    dsc_ref[0, 0] = ds
    dt = wt_ref.dtype
    d_o = do_ref[0, 0]
    dvn = jnp.dot(pt_ref[0, 0], d_o, preferred_element_type=_F32) \
        + jnp.dot(kr_ref[0, 0], ds.astype(dt), preferred_element_type=_F32)
    dvn_ref[0, 0] = dvn
    ds_ref[...] = (
        jnp.dot(qgt_ref[0, 0], d_o, preferred_element_type=_F32)
        + ec_ref[0, 0] * ds
        - jnp.dot(wt_ref[0, 0], dvn.astype(dt),
                  preferred_element_type=_F32))


def _pallas_bwd(m, do):
    b, h, nc, c, dk = m["w"].shape
    dv = do.shape[-1]

    def at(*block):
        return pl.BlockSpec((1, 1, *block),
                            lambda i, j: (i, nc - 1 - j, 0, 0))

    def t(name):
        return _heads(m[name]).swapaxes(-1, -2)

    dvn, dsc = pl.pallas_call(
        _bwd_kernel, grid=(b * h, nc),
        in_specs=[at(dk, c), at(dk, c), at(c, c), at(c, dk), at(1, dv),
                  at(c, dv)],
        out_specs=[at(c, dv), at(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((b * h, nc, c, dv), _F32),
                   jax.ShapeDtypeStruct((b * h, nc, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_compiler_params(),
        name="gdr_bwd",
        interpret=pallas_common.interpret_mode(),
    )(t("w"), t("qg"), t("p"), _heads(m["kr"]),
      _lanes(_heads(m["ec"]), dv), _heads(do))
    return (dvn.reshape(b, h, nc, c, dv), dsc.reshape(b, h, nc, dk, dv))


# ------------------------------------------------------------ public op

def _pad_time(x, pad: int):
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


def _padded_chunks(xs, chunk: int):
    """Each of ``xs`` [B, T, H, ...] padded to whole chunks and split
    into them.  A padded token has k = 0, beta = 0, g = 0: the state
    passes through it unchanged."""
    pad = -xs[0].shape[1] % chunk
    return tuple(_chunks(_pad_time(x, pad), chunk) for x in xs)


def _head_block(t: int, h: int) -> int:
    """Heads a pass over the chunks takes at once: all of them where
    ``_HEAD_TOKENS`` holds them, else the largest divisor of ``h`` that
    it holds."""
    return max(d for d in range(1, h + 1)
               if h % d == 0 and (d == 1 or d * t <= _HEAD_TOKENS))


def _by_heads(fn, hb: int, *xs):
    """``fn`` over blocks of ``hb`` heads of ``xs`` [B, T, H, ...], one
    block after another (``lax.map``: one block's matrices are alive at
    a time); its outputs, heads on axis 2, put together again."""
    h = xs[0].shape[2]
    if hb == h:
        return fn(*xs)

    def split(x):
        x = x.reshape(*x.shape[:2], h // hb, hb, *x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    def join(y):
        y = jnp.moveaxis(y, 0, 2)
        return y.reshape(*y.shape[:2], h, *y.shape[4:])
    return jax.tree.map(join, jax.lax.map(lambda a: fn(*a),
                                          tuple(split(x) for x in xs)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_rule(q, k, v, g, beta, impl: str = "auto",
                     chunk: int = CHUNK):
    """``o [B, T, H, dv]`` in ``v``'s dtype; see the module's docstring.
    ``chunk`` is the number of tokens a chunk holds and the distance
    between two kept states (a power of two, 16 at least); T need not
    be a multiple of it."""
    return _vjp_fwd(q, k, v, g, beta, impl, chunk)[0]


def _vjp_fwd(q, k, v, g, beta, impl, chunk):
    sweep = _pallas_fwd if _resolve(impl) == "pallas" else _xla_fwd
    t = q.shape[1]

    def heads(q, k, v, g, beta):
        o, s0 = sweep(_prepare(*_padded_chunks((q, k, v, g, beta), chunk)))
        # the states with the heads on axis 2, as the tokens have them
        return _tokens(o)[:, :t].astype(v.dtype), jnp.moveaxis(s0, 1, 2)
    o, s0 = _by_heads(heads, _head_block(t, q.shape[2]), q, k, v, g, beta)
    return o, (q, k, v, g, beta, s0)


def _vjp_bwd(impl, chunk, res, do):
    q, k, v, g, beta, s0 = res
    with scope("linattn.rule"):
        sweep = _pallas_bwd if _resolve(impl) == "pallas" else _xla_bwd
        return _by_heads(
            functools.partial(_heads_bwd, sweep, chunk),
            _head_block(q.shape[1], q.shape[2]), q, k, v, g, beta, s0,
            do.astype(q.dtype))


def _heads_bwd(sweep, chunk, q, k, v, g, beta, s0, do):
    """The five cotangents of some heads; ``s0`` [B, nc, H, dk, dv] the
    states the forward kept for them."""
    t, dt = q.shape[1], q.dtype
    s0b = jnp.swapaxes(s0, 1, 2)
    qc, kc, vc, gc, bc, doc = _padded_chunks((q, k, v, g, beta, do), chunk)
    m = _prepare(qc, kc, vc, gc, bc)
    dvn, dsc = sweep(m, doc)
    dvb = dvn.astype(dt)
    low = jnp.tril(jnp.ones((chunk, chunk), bool))
    vn = (m["u"].astype(_F32)
          - _mm("...id,...de->...ie", m["w"], s0b)).astype(dt)
    # O = Qg S0 + P V';  S_C = ec S0 + Kr^T V';  V' = U - W S0
    dqg = _mm("...ie,...de->...id", doc, s0b)
    dp = jnp.where(low, _mm("...ie,...je->...ij", doc, vn), 0.0)
    dkr = _mm("...ie,...de->...id", vn, dsc.astype(dt))
    dec = jnp.sum(dsc * s0b.astype(_F32), axis=(-1, -2))
    dw = -_mm("...ie,...de->...id", dvb, s0b)
    # W = T Kg, U = T V, T = X diag(beta)
    d_t = _mm("...id,...jd->...ij", dw.astype(dt), m["kg"]) \
        + _mm("...ie,...je->...ij", dvb, vc)
    dkg = _mm("...ij,...id->...jd", m["t"], dw.astype(dt))
    d_v = _mm("...ij,...ie->...je", m["t"], dvb)
    bt = bc.astype(_F32)
    dbeta = jnp.sum(d_t * m["x"], axis=-2)
    # X = (I + A)^-1: dA = -X^T dX X^T on the strict lower triangle
    dx = d_t * bt[..., None, :]
    da = -_mm_exact("...ji,...jk->...ik", m["x"],
                    _mm_exact("...ij,...kj->...ik", dx, m["x"]))
    da = jnp.where(jnp.tril(low, -1), da, 0.0)
    # A_ij = beta_i G_ij KK_ij
    dbeta = dbeta + jnp.sum(da * m["gam"] * m["kk"], axis=-1)
    dkk = da * bt[..., :, None] * m["gam"]
    dqk = dp * m["gam"]
    # every path into G_ij = exp(c_i - c_j), times G
    dgam = (da * bt[..., :, None] * m["kk"] + dp * m["qk"]) * m["gam"]
    kf, qf = kc.astype(_F32), qc.astype(_F32)
    d_q = dqg * m["eg"] + _mm("...ij,...jd->...id", dqk.astype(dt), kc)
    d_k = (dkg * m["eg"] + dkr * m["er"]
           + _mm("...ij,...id->...jd", dqk.astype(dt), qc)
           + _mm("...ij,...jd->...id",
                 (dkk + dkk.swapaxes(-1, -2)).astype(dt), kc))
    # c_i enters through exp(c_i) (Qg, Kg), exp(c_C - c_i) (Kr),
    # exp(c_C) (the state's decay) and G
    d_er = jnp.sum(dkr * kf, -1) * m["er"][..., 0]
    dcum = (jnp.sum(dqg * qf + dkg * kf, -1) * m["eg"][..., 0] - d_er
            + jnp.sum(dgam, -1) - jnp.sum(dgam, -2))
    dcum = dcum.at[..., -1].add(jnp.sum(d_er, -1) + dec * m["ec"])
    d_g = jnp.flip(jnp.cumsum(jnp.flip(dcum, -1), -1), -1)
    return (_tokens(d_q)[:, :t].astype(q.dtype),
            _tokens(d_k)[:, :t].astype(k.dtype),
            _tokens(d_v)[:, :t].astype(v.dtype),
            _tokens(d_g)[:, :t].astype(g.dtype),
            _tokens(dbeta)[:, :t].astype(beta.dtype))


gated_delta_rule.defvjp(_vjp_fwd, _vjp_bwd)


def reference_rule(q, k, v, g, beta):
    """The recurrence as it is written, one token at a time in float32,
    with every state alive under autodiff: what the tests compare
    with."""
    f = _F32

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        delta = b_t[..., None] * (v_t - jnp.einsum("bhde,bhd->bhe", s, k_t))
        s = s + k_t[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("bhde,bhd->bhe", s, q_t)

    xs = tuple(x.astype(f).swapaxes(0, 1) for x in (q, k, v, g, beta))
    s0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), f)
    return jax.lax.scan(step, s0, xs)[1].swapaxes(0, 1).astype(v.dtype)
