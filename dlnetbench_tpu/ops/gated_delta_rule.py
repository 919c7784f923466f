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
exponent, not on the result.  Matmul operands are in the inputs' dtype,
sums, the state, ``G``, ``X = (I + A)^-1`` and the products with the
gates in float32, the kept states in the inputs' dtype.

The algebra of a chunk of one head is written once, on tiles
(``_matrices``, ``_chunk_fwd``, ``_chunk_bwd``), and swept over the
chunks in two ways.  ``X`` is made from whole ``[C, C]`` tiles
(``_inv_unit_lower``: the diagonal blocks of 16 rows as a product of
powers, then the blocks' own product, 10 products at 64 and 12 at
128), each a float32 product at ``highest`` (six passes of the MXU,
which has no float32), as is the gradient's ``-X^T dX X^T``.

``impl="pallas"`` (what ``"auto"`` takes on a TPU: ``_AUTO``; off the
TPU it runs in interpret mode, for tests) keeps a chunk in VMEM.  One
grid step of the kernel ``gdr_fwd`` reads the tiles of ``q``, ``k``,
``v`` of ``hb`` heads in the token layout the caller has them in
(``[B, T, H * d]`` through a block ``(1, C, hb * d)``: a head is a
slice of the lanes) with ``c`` and ``beta`` as columns and as rows,
and for each of the heads makes ``G``, ``K K^T``, ``A``, ``X``, ``T``,
``W``, ``U``, ``Q K^T``, then ``V'``, ``O`` and the state's update in a
float32 scratch; it writes ``o``, the state entering the chunk and
``X``, and nothing else of a chunk ever crosses HBM.  The grid is ``(B,
H / hb, T / C)``, the chunk axis sequential; the ``hb`` heads of a step
are independent chains of products, which is what lets the MXUs
overlap them, and all heads run in one call.  ``tile_plan`` gives
``C`` and ``hb`` from the shapes alone: the largest chunk of
``_CHUNKS`` the sequence fills, and the most heads, a divisor of ``H``
that fills whole 128-lane tiles, whose step fits ``_VMEM_BUDGET``.

The backward is written by hand.  The forward keeps the state entering
EVERY chunk (``[B, H, T / C, dk, dv]`` in the inputs' dtype, the
operand the backward's products take it as: 32 KiB a head and chunk at
128 x 128 in bfloat16) and ``X`` (``[B, H, T / C, C, C]`` float32) and
nothing else of its own.  ``gdr_bwd`` sweeps the chunks last to first
carrying ``dS``, makes the chunk's matrices again from the same tiles,
``X`` and the kept state, and forms all five cotangents of the chunk in
the same step, in the token layout; what is left to XLA is adding the
row and column forms of ``dbeta`` and ``dc`` and the reverse sum of
``dc`` within a chunk, over ``[B, T, H]`` scalars.  The backward wears
the ``linattn.rule`` scope itself: a ``custom_vjp``'s backward is
traced outside the caller's.

``impl="xla"`` is the form off the TPU: the same two functions of a
chunk over every head at once (``vmap``) as the steps of a
``lax.scan``, one chunk's matrices alive at a time, the same kept
states and ``X``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.metrics.spans import scope
from dlnetbench_tpu.ops import pallas_common

_F32 = pallas_common.F32
_CHUNKS = (128, 64)     # a chunk's tokens: the largest the sequence fills
_INV_BASE = 16      # a diagonal block inverted as a product of powers
_AUTO = "pallas"    # what "auto" takes on a TPU: a chunk kept in VMEM,
#                     `hb` heads a grid step, every head in one call
_VMEM_BUDGET = 20 << 20     # pallas: bytes of a grid step (tile_plan)
# float32 [C, 128] matrices of a head alive at once in `_chunk_bwd`: the
# plan's own estimate, not the compiler's count (hb 8 runs under the
# kernels' 64 MiB limit on the chip; 16 was never run there)
_LIVE_TILES = 24


def _resolve(impl: str) -> str:
    if impl == "auto":
        return _AUTO if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown gated_delta_rule impl {impl!r}")
    return impl


# ------------------------------------------------ a chunk of one head

_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _dot_exact(a, b, dims=_NN):
    """A float32 product of float32 tiles (six passes of the MXU)."""
    return _dot(a, b, dims, "highest")


def _inv_unit_lower(a):
    """``(I + a)^-1`` for a tile ``a [C, C]`` strictly lower triangular,
    float32, as products of whole tiles: with ``a = d + o``, ``d`` the
    diagonal blocks of ``_INV_BASE`` rows, ``X_d = (I + d)^-1`` is the
    product ``(I - d)(I + d^2)(I + d^4)...`` (block diagonal matrices
    multiply block by block), and ``(I + a)^-1 = (I + b)^-1 X_d`` with
    ``b = X_d o`` strictly lower by blocks, so the same product again,
    ``C / _INV_BASE`` blocks long.  Both are exact: ``d`` and ``b`` are
    nilpotent."""
    c = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = row // _INV_BASE == col // _INV_BASE

    def series(p, y, span: int):
        """``(I + p^2)(I + p^4)... y`` while the power is below
        ``span``."""
        n = 2
        while n < span:
            p = _dot_exact(p, p)
            y = y + _dot_exact(p, y)
            n *= 2
        return y
    d = jnp.where(same, a, 0.0)
    x_d = series(d, (row == col).astype(_F32) - d, min(c, _INV_BASE))
    if c <= _INV_BASE:
        return x_d
    b = _dot_exact(x_d, jnp.where(same, 0.0, a))
    return series(b, x_d - _dot_exact(b, x_d), c // _INV_BASE)


def _matrices(q, k, v, cc, cr, bc, br, x=None):
    """A chunk's matrices that do not read the state, from tiles ``q, k
    [C, dk]``, ``v [C, dv]``, the cumulative gate and ``beta`` as
    columns ``cc, bc [C, 1]`` and as rows ``cr, br [1, C]``; ``X [C,
    C]`` is made here (the forward) unless it is given (the backward,
    which reads the forward's)."""
    dt, c = q.dtype, q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    low, strict = row >= col, row > col
    gam = jnp.exp(jnp.where(low, cc - cr, -jnp.inf))
    last = cc[c - 1:, :]
    eg, er = jnp.exp(cc), jnp.exp(last - cc)
    kk = _dot(k, k, _NT)
    if x is None:
        x = _inv_unit_lower(jnp.where(strict, bc * gam * kk, 0.0))
    t = (x * br).astype(dt)
    kf, qf = k.astype(_F32), q.astype(_F32)
    kg = (kf * eg).astype(dt)
    qk = _dot(q, k, _NT)
    return {
        "low": low, "strict": strict, "gam": gam, "eg": eg, "er": er,
        "ec": jnp.exp(last), "kk": kk, "x": x,
        "kf": kf, "qf": qf, "qk": qk, "t": t, "kg": kg,
        "w": _dot(t, kg).astype(dt), "u": _dot(t, v).astype(dt),
        "qg": (qf * eg).astype(dt), "p": (gam * qk).astype(dt),
        "kr": (kf * er).astype(dt),
    }


def _lanes(x, n: int):
    """A ``[1, 1]`` value as a row of ``n`` lanes: Mosaic broadcasts
    along lanes or along sublanes, not both at once (and folds two
    bare broadcasts into one)."""
    return x + jnp.zeros((1, n), x.dtype)


def _chunk_fwd(q, k, v, cc, cr, bc, br, s):
    """``(O [C, dv]`` float32, the state entering the chunk as the
    products take it, ``X``, the state leaving it) of one head, ``s
    [dk, dv]`` float32 the state entering."""
    m = _matrices(q, k, v, cc, cr, bc, br)
    sb = s.astype(q.dtype)
    vb = (m["u"].astype(_F32) - _dot(m["w"], sb)).astype(q.dtype)
    o = _dot(m["qg"], sb) + _dot(m["p"], vb)
    return o, sb, m["x"], \
        _lanes(m["ec"], s.shape[1]) * s + _dot(m["kr"], vb, _TN)


def _chunk_bwd(q, k, v, cc, cr, bc, br, x, s0, d_o, ds):
    """The cotangents of one head's chunk, float32: ``dq, dk, dv``,
    then ``dbeta`` and the cumulative gate's as a column part and a row
    part each (sums over a matrix's columns come out as columns, sums
    over its rows as rows; the caller adds them), then the gradient of
    the state ENTERING the chunk, ``ds [dk, dv]`` being that of the
    state leaving it."""
    dt, c = q.dtype, q.shape[0]
    m = _matrices(q, k, v, cc, cr, bc, br, x)
    low, gam, eg, er = m["low"], m["gam"], m["eg"], m["er"]
    kf, t, kg, kk = m["kf"], m["t"], m["kg"], m["kk"]
    vn = (m["u"].astype(_F32) - _dot(m["w"], s0)).astype(dt)
    dsb = ds.astype(dt)
    # O = Qg S0 + P V';  S_C = ec S0 + Kr^T V';  V' = U - W S0
    dvn = _dot(m["p"], d_o, _TN) + _dot(m["kr"], dsb)
    dvb = dvn.astype(dt)
    ds_in = _dot(m["qg"], d_o, _TN) + _lanes(m["ec"], ds.shape[1]) * ds \
        - _dot(m["w"], dvb, _TN)
    dqg = _dot(d_o, s0, _NT)
    dp = jnp.where(low, _dot(d_o, vn, _NT), 0.0)
    dkr = _dot(vn, dsb, _NT)
    dec = jnp.sum(jnp.sum(ds * s0.astype(_F32), 1, keepdims=True),
                  0, keepdims=True)
    dw = (-_dot(dvb, s0, _NT)).astype(dt)
    # W = T Kg, U = T V, T = X diag(beta)
    d_t = _dot(dw, kg, _NT) + _dot(dvb, v, _NT)
    dkg = _dot(t, dw, _TN)
    d_v = _dot(t, dvb, _TN)
    # X = (I + A)^-1: dA = -X^T dX X^T on the strict lower triangle
    da = -_dot_exact(x, _dot_exact(d_t * br, x, _NT), _TN)
    da = jnp.where(m["strict"], da, 0.0)
    # A_ij = beta_i G_ij KK_ij
    dbr = jnp.sum(d_t * x, 0, keepdims=True)
    dbc = jnp.sum(da * gam * kk, 1, keepdims=True)
    dkk = (da * bc * gam).astype(dt)
    dqk = (dp * gam).astype(dt)
    # every path into G_ij = exp(c_i - c_j), times G
    dgam = (da * bc * kk + dp * m["qk"]) * gam
    d_q = dqg * eg + _dot(dqk, k)
    d_k = dkg * eg + dkr * er + _dot(dqk, q, _TN) \
        + _dot(dkk, k) + _dot(dkk, k, _TN)
    # c_i enters through exp(c_i) (Qg, Kg), exp(c_C - c_i) (Kr),
    # exp(c_C) (the state's decay) and G
    d_er = jnp.sum(dkr * kf, 1, keepdims=True) * er
    at_end = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    dcc = (jnp.sum(dqg * m["qf"] + dkg * kf, 1, keepdims=True) * eg - d_er
           + jnp.sum(dgam, 1, keepdims=True)
           + jnp.where(at_end, jnp.sum(d_er, 0, keepdims=True)
                       + dec * m["ec"], 0.0))
    dcr = -jnp.sum(dgam, 0, keepdims=True)
    return d_q, d_k, d_v, dbc, dbr, dcc, dcr, ds_in


# ----------------------------------------- the sweeps, pallas: in VMEM

def tile_plan(t: int, h: int, dk: int, dv: int, itemsize: int) -> tuple:
    """``(C, hb)``: the tokens of a chunk and the heads of a grid step,
    from the shapes alone.  ``C`` is the largest of ``_CHUNKS`` that
    ``t`` fills once (a short sequence is not padded to twice its
    length).  ``hb`` is all the heads, or a divisor of ``h`` whose
    heads fill whole 128-lane tiles of a token block (Mosaic takes no
    other last dimension): the largest whose step (the backward's: the
    token tiles in and out, ``X``, the kept state, each twice for the
    pipeline, the carried state, and the chunk's matrices beside them)
    fits ``_VMEM_BUDGET``, the smallest if none does."""
    c = next((c for c in _CHUNKS if t >= c), _CHUNKS[-1])
    d = max(dk, dv, c)
    head = (2 * itemsize * c * (4 * dk + 3 * dv)        # q k v do, dq dk dv
            + 2 * (4 * c * c + itemsize * dk * dv)      # X, the kept state
            + 4 * dk * dv                               # the carried state
            + 4 * _LIVE_TILES * c * d)                  # the chunk's matrices
    whole = [n for n in range(1, h + 1) if h % n == 0
             and (n == h or n * dk % 128 == 0 == n * dv % 128)]
    return c, max((n for n in whole if n * head <= _VMEM_BUDGET),
                  default=whole[0])


def _cols(x, hb: int):
    """[B, T, H] -> [B, H / hb, T, hb]: a token a sublane, a head of the
    group a lane."""
    b, t, h = x.shape
    return x.reshape(b, t, h // hb, hb).swapaxes(1, 2)


def _rows(x, hb: int, chunk: int):
    """[B, T, H] -> [B, H / hb, T / C, hb, C]: a chunk's tokens on the
    lanes."""
    b, t, h = x.shape
    return x.reshape(b, t // chunk, chunk, h // hb, hb) \
        .transpose(0, 3, 1, 4, 2)


def _from_cols(y):
    b, ng, t, hb = y.shape
    return y.swapaxes(1, 2).reshape(b, t, ng * hb)


def _from_rows(y):
    b, ng, nc, hb, c = y.shape
    return y.transpose(0, 2, 4, 1, 3).reshape(b, nc * c, ng * hb)


def _head_tiles(i: int, hb: int, tok_refs, cc_ref, cr_ref, bc_ref, br_ref):
    """Head ``i`` of a grid step: its lanes of the token tiles ``[1, C,
    hb * d]``, then its lane of a column and its sublane of a row."""
    def lanes(ref):
        d = ref.shape[-1] // hb
        return ref[0, :, i * d:(i + 1) * d]

    def col(ref):
        return ref[0, 0, :, i:i + 1]

    def row(ref):
        return ref[0, 0, 0, i:i + 1, :]
    return (*map(lanes, tok_refs), col(cc_ref), row(cr_ref), col(bc_ref),
            row(br_ref))


def _put_head(ref, i: int, hb: int, x):
    d = ref.shape[-1] // hb
    ref[0, :, i * d:(i + 1) * d] = x.astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, cc_ref, cr_ref, bc_ref, br_ref,
                o_ref, s0_ref, x_ref, s_ref, *, hb: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    for i in range(hb):     # independent chains of products
        o, sb, x, s = _chunk_fwd(
            *_head_tiles(i, hb, (q_ref, k_ref, v_ref), cc_ref, cr_ref,
                         bc_ref, br_ref), s_ref[i])
        _put_head(o_ref, i, hb, o)
        s0_ref[0, i, 0], x_ref[0, i, 0], s_ref[i] = sb, x, s


def _bwd_kernel(q_ref, k_ref, v_ref, cc_ref, cr_ref, bc_ref, br_ref, x_ref,
                s0_ref, do_ref, dq_ref, dk_ref, dv_ref, dbc_ref, dbr_ref,
                dcc_ref, dcr_ref, ds_ref, *, hb: int):
    """The chunks last to first; ``ds_ref`` carries the gradient of the
    state LEAVING the chunk."""
    @pl.when(pl.program_id(2) == 0)     # the LAST chunk: time reversed
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for i in range(hb):
        q, k, v, d_o, *small = _head_tiles(
            i, hb, (q_ref, k_ref, v_ref, do_ref), cc_ref, cr_ref, bc_ref,
            br_ref)
        d_q, d_k, d_v, dbc, dbr, dcc, dcr, ds = _chunk_bwd(
            q, k, v, *small, x_ref[0, i, 0], s0_ref[0, i, 0], d_o, ds_ref[i])
        for ref, x in ((dq_ref, d_q), (dk_ref, d_k), (dv_ref, d_v)):
            _put_head(ref, i, hb, x)
        dbc_ref[0, 0, :, i:i + 1], dcc_ref[0, 0, :, i:i + 1] = dbc, dcc
        dbr_ref[0, 0, 0, i:i + 1, :], dcr_ref[0, 0, 0, i:i + 1, :] = dbr, dcr
        ds_ref[i] = ds


def _specs(b: int, t: int, h: int, chunk: int, hb: int, reverse: bool):
    """The grid ``(B, H / hb, T / C)`` and its blocks: a token tile of
    ``d`` lanes a head, the small columns and rows, a matrix a head and
    chunk."""
    nc = t // chunk

    def at(ci):
        return nc - 1 - ci if reverse else ci

    def tok(d):
        return pl.BlockSpec((1, chunk, hb * d),
                            lambda bi, gi, ci: (bi, at(ci), gi))

    def mat(r, c):
        return pl.BlockSpec((1, hb, 1, r, c),
                            lambda bi, gi, ci: (bi, gi, at(ci), 0, 0))
    col = pl.BlockSpec((1, 1, chunk, hb),
                       lambda bi, gi, ci: (bi, gi, at(ci), 0))
    row = pl.BlockSpec((1, 1, 1, hb, chunk),
                       lambda bi, gi, ci: (bi, gi, at(ci), 0, 0))
    return (b, h // hb, nc), tok, mat, col, row


def _compiler_params():
    return pallas_common.compiler_params(
        ("parallel", "parallel", "arbitrary"), vmem_limit_mb=64)


def _flat(x):
    """[B, T, H, d] -> [B, T, H * d]: a head is a slice of the lanes."""
    return x.reshape(*x.shape[:2], -1)


def _pallas_fwd(q, k, v, cum, bt, chunk: int, hb: int):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    grid, tok, mat, col, row = _specs(b, t, h, chunk, hb, False)
    o, s0, x = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb), grid=grid,
        in_specs=[tok(dk), tok(dk), tok(dv), col, row, col, row],
        out_specs=[tok(dv), mat(dk, dv), mat(chunk, chunk)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h, t // chunk, dk, dv),
                                        q.dtype),
                   jax.ShapeDtypeStruct(
                       (b, h, t // chunk, chunk, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_compiler_params(),
        name="gdr_fwd",
        interpret=pallas_common.interpret_mode(),
    )(_flat(q), _flat(k), _flat(v), _cols(cum, hb), _rows(cum, hb, chunk),
      _cols(bt, hb), _rows(bt, hb, chunk))
    return o.reshape(b, t, h, dv), s0, x


def _pallas_bwd(q, k, v, cum, bt, s0, x, do, chunk: int, hb: int):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    grid, tok, mat, col, row = _specs(b, t, h, chunk, hb, True)
    small = [jax.ShapeDtypeStruct((b, h // hb, t, hb), _F32),
             jax.ShapeDtypeStruct((b, h // hb, t // chunk, hb, chunk), _F32)]
    dq, dk_, dv_, dbc, dbr, dcc, dcr = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb), grid=grid,
        in_specs=[tok(dk), tok(dk), tok(dv), col, row, col, row,
                  mat(chunk, chunk), mat(dk, dv), tok(dv)],
        out_specs=[tok(dk), tok(dk), tok(dv), col, row, col, row],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, t, h * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, t, h * dv), v.dtype)]
        + small * 2,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_compiler_params(),
        name="gdr_bwd",
        interpret=pallas_common.interpret_mode(),
    )(_flat(q), _flat(k), _flat(v), _cols(cum, hb), _rows(cum, hb, chunk),
      _cols(bt, hb), _rows(bt, hb, chunk), x, s0, _flat(do))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            _from_cols(dbc) + _from_rows(dbr),
            _from_cols(dcc) + _from_rows(dcr))


# ------------------------------------- the sweeps, xla: a scan's steps

def _chunks(x, chunk: int):
    """[B, T, H, ...] -> [T / chunk, B, H, chunk, ...]."""
    b, t, h = x.shape[:3]
    x = x.reshape(b, t // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(x, (1, 3), (0, 2))


def _tokens(y):
    """[T / chunk, B, H, chunk, ...] -> [B, T, H, ...]."""
    y = jnp.moveaxis(y, (0, 2), (1, 3))
    return y.reshape(y.shape[0], y.shape[1] * y.shape[2], *y.shape[3:])


def _scan_inputs(q, k, v, cum, bt, chunk: int):
    cc, bc = _chunks(cum, chunk), _chunks(bt, chunk)
    return (*(_chunks(a, chunk) for a in (q, k, v)), cc[..., None],
            cc[..., None, :], bc[..., None], bc[..., None, :])


def _state(q, v):
    return jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), _F32)


def _xla_fwd(q, k, v, cum, bt, chunk: int):
    each = jax.vmap(jax.vmap(_chunk_fwd))

    def step(s, xs):
        *kept, s = each(*xs, s)
        return s, kept
    _, (o, s0, x) = jax.lax.scan(step, _state(q, v),
                                 _scan_inputs(q, k, v, cum, bt, chunk))
    return _tokens(o).astype(v.dtype), jnp.moveaxis(s0, 0, 2), \
        jnp.moveaxis(x, 0, 2)


def _xla_bwd(q, k, v, cum, bt, s0, x, do, chunk: int):
    each = jax.vmap(jax.vmap(_chunk_bwd))

    def step(ds, xs):
        *grads, ds = each(*xs, ds)
        return ds, grads
    _, (dq, dk, dv, dbc, dbr, dcc, dcr) = jax.lax.scan(
        step, _state(q, v),
        (*_scan_inputs(q, k, v, cum, bt, chunk), jnp.moveaxis(x, 2, 0),
         jnp.moveaxis(s0, 2, 0), _chunks(do, chunk)), reverse=True)
    return (_tokens(dq), _tokens(dk), _tokens(dv),
            _tokens(dbc[..., 0] + dbr[..., 0, :]),
            _tokens(dcc[..., 0] + dcr[..., 0, :]))


# ------------------------------------------------------------ public op

# The names of a forward's three arrays as its backward and its caller
# read them (``jax.ad_checkpoint.checkpoint_name``): ``o``, the states
# entering the chunks and the chunks' ``X``.  A ``jax.checkpoint`` whose
# policy saves these names keeps all three, and the forward sweep is dead
# in its recomputation; anywhere else a name is the identity (as
# ``flash_attention.KEPT_NAMES``).
KEPT_NAMES = ("rule_out", "rule_states", "rule_chunks")


def _pad_time(x, pad: int):
    """A padded token has k = 0, beta = 0, g = 0: the state passes
    through it unchanged."""
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


def _chunk_cum(g, chunk: int):
    """``c_i``: the sum of ``g [B, T, H]`` over its chunk's tokens up to
    ``i``, float32."""
    b, t, h = g.shape
    return jnp.cumsum(g.astype(_F32).reshape(b, t // chunk, chunk, h),
                      axis=2).reshape(b, t, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_rule(q, k, v, g, beta, impl: str = "auto",
                     chunk: int | None = None):
    """``o [B, T, H, dv]`` in ``v``'s dtype; see the module's docstring.
    ``chunk`` is the number of tokens a chunk holds and the distance
    between two kept states (a power of two, 16 at least; left out, the
    shapes' own: ``tile_plan``); T need not be a multiple of it."""
    return _vjp_fwd(q, k, v, g, beta, impl, chunk)[0]


def _whole_chunks(q, v, chunk, *xs):
    """``(C, hb)`` of the call, ``xs`` [B, T, ...] padded to whole
    chunks, and of them the cumulative gate and ``beta`` in float32."""
    c, hb = tile_plan(*q.shape[1:], v.shape[-1], q.dtype.itemsize)
    chunk = chunk or c
    q, k, v, g, beta, *rest = (_pad_time(a, -q.shape[1] % chunk) for a in xs)
    return chunk, hb, (q, k, v, _chunk_cum(g, chunk), beta.astype(_F32),
                       *rest)


def _vjp_fwd(q, k, v, g, beta, impl, chunk):
    chunk, hb, xs = _whole_chunks(q, v, chunk, q, k, v, g, beta)
    if _resolve(impl) == "pallas":
        o, s0, x = _pallas_fwd(*xs, chunk, hb)
    else:
        o, s0, x = _xla_fwd(*xs, chunk)
    o, s0, x = (checkpoint_name(a, name) for a, name in zip(
        (o[:, :q.shape[1]], s0, x), KEPT_NAMES))
    return o, (q, k, v, g, beta, s0, x)


def _vjp_bwd(impl, chunk, res, do):
    *inputs, s0, x = res
    q, v = inputs[0], inputs[2]
    with scope("linattn.rule"):
        chunk, hb, xs = _whole_chunks(q, v, chunk, *inputs,
                                      do.astype(q.dtype))
        xs = (*xs[:5], s0, x, xs[5])
        if _resolve(impl) == "pallas":
            *grads, dcum = _pallas_bwd(*xs, chunk, hb)
        else:
            *grads, dcum = _xla_bwd(*xs, chunk)
        # c_i is a sum of g up to i: g_i gets every later c's of its chunk
        b, t, h = dcum.shape
        d_g = jnp.flip(jnp.cumsum(jnp.flip(
            dcum.reshape(b, t // chunk, chunk, h), 2), 2), 2).reshape(b, t, h)
        dq, dk, dv, dbeta = grads
        return tuple(d[:, :q.shape[1]].astype(a.dtype)
                     for d, a in zip((dq, dk, dv, d_g, dbeta), inputs))


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
