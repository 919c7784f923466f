"""Lightning attention: linear attention with a constant decay a head
(Lightning Attention-2, arXiv:2401.04658; MiniMax-01, arXiv:2501.08313),
a matrix state a head and no delta,

    S_t = lambda_h S_{t-1} + k_t^T v_t                       [dk, dv]
    o_t = scale q_t S_t                                      [dv]

over ``q, k: [B, T, H, dk]``, ``v: [B, T, H, dv]`` and ``log_decay: [H]``
(``ln lambda_h <= 0``, a constant of the model, float32: it gets no
gradient).  The state is float32 whatever the inputs are.

It runs in chunks of ``C`` tokens as matrix products.  With ``i, j`` a
chunk's tokens from 0 and ``D_ij = lambda^(i - j)`` for ``i >= j``, else
0:

    O  = scale (lambda^(i + 1) * Q) S + (scale D * Q K^T) V
    S <- lambda^C S + (lambda^(C - 1 - j) * K)^T V

Every power is of a difference that is not negative, so none overflows.
``D`` and the two columns of powers are the same for every chunk of a
head: they are built once a call (``_decay_tiles``), never a chunk, and
the Pallas kernels fetch a head's once (a block whose index the chunk
axis does not move).  Matmul operands are in the inputs' dtype, sums,
the state and the products with the powers in float32, the kept states
in the inputs' dtype.

The algebra of a chunk of one head is written once, on tiles
(``_chunk_fwd``, ``_chunk_bwd``), and swept over the chunks in the two
ways ``ops/gated_delta_rule.py`` sweeps its own, whose plan
(``tile_plan``: the chunk's tokens and the heads of a grid step), token
layout, grid and state scratch these are: ``impl="pallas"`` (what
``"auto"`` takes on a TPU; off the TPU interpret mode, for tests) is the
kernel pair ``lightning_fwd`` / ``lightning_bwd`` on the grid ``(B, H /
hb, T / C)``, the chunk axis sequential and the ``[dk, dv]`` state of
each of the step's heads in a float32 VMEM scratch across a head's
chunks; ``impl="xla"`` the same two functions over every head at once as
the steps of a ``lax.scan``.  The forward keeps the state ENTERING every
chunk (``[B, H, T / C, dk, dv]`` in the inputs' dtype) and nothing else
of its own; the backward sweeps the chunks last to first carrying
``dS`` and wears the ``linattn.rule`` scope itself (a ``custom_vjp``'s
backward is traced outside the caller's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlnetbench_tpu.metrics.spans import scope
from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.ops.gated_delta_rule import (_NT, _TN, _chunks,
                                                 _compiler_params, _dot,
                                                 _flat, _lanes, _pad_time,
                                                 _put_head, _specs, _state,
                                                 _tokens, tile_plan)

_F32 = pallas_common.F32


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown lightning_attention impl {impl!r}")
    return impl


def head_log_decay(heads: int, layer: int, depth: int):
    """``ln lambda_h`` [H] float32 of layer ``layer`` of a model
    ``depth`` layers deep, as Lightning Attention-2 builds it: slopes
    ``s_h = 2^(-8 (h + 1) / H)`` times ``1 - layer / (depth - 1) +
    1e-5``, so the last layer hardly decays."""
    slopes = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=_F32) / heads)
    return -slopes * (1.0 - layer / max(depth - 1, 1) + 1e-5)


def _decay_tiles(log_decay, chunk: int):
    """A head's constants of every chunk: ``D [H, C, C]`` and the
    columns ``[H, C, 3]`` of ``lambda^(i + 1)``, ``lambda^(C - 1 - i)``
    and ``lambda^C``, float32.  The mask goes on the exponent."""
    g = log_decay.astype(_F32)[:, None, None]
    i = jnp.arange(chunk, dtype=_F32)
    diff = i[:, None] - i[None, :]
    dmat = jnp.exp(jnp.where(diff >= 0, g * diff, -jnp.inf))
    g = g[:, 0]
    cols = jnp.stack([jnp.exp(g * (i + 1.0)), jnp.exp(g * (chunk - 1.0 - i)),
                      jnp.broadcast_to(jnp.exp(g * chunk), (g.shape[0], chunk))],
                     axis=-1)
    return dmat, cols


# ------------------------------------------------ a chunk of one head

def _powers(cols):
    """(``lambda^(i + 1)`` [C, 1], ``lambda^(C - 1 - i)`` [C, 1],
    ``lambda^C`` [1, 1]) of a head's columns [C, 3]."""
    return cols[:, 0:1], cols[:, 1:2], cols[0:1, 2:3]


def _chunk_fwd(q, k, v, dmat, cols, s, scale: float):
    """``(O [C, dv]`` float32, the state entering the chunk as the
    products take it, the state leaving it) of one head, ``s [dk, dv]``
    float32 the state entering."""
    dt = q.dtype
    a, r, ec = _powers(cols)
    sb = s.astype(dt)
    p = (scale * dmat * _dot(q, k, _NT)).astype(dt)
    o = (scale * a) * _dot(q, sb) + _dot(p, v)
    kr = (k.astype(_F32) * r).astype(dt)
    return o, sb, _lanes(ec, s.shape[1]) * s + _dot(kr, v, _TN)


def _chunk_bwd(q, k, v, dmat, cols, s0, d_o, ds, scale: float):
    """The cotangents of one head's chunk, float32: ``dq, dk, dv`` and
    the gradient of the state ENTERING the chunk, ``ds [dk, dv]`` being
    that of the state leaving it."""
    dt = q.dtype
    a, r, ec = _powers(cols)
    dsb = ds.astype(dt)
    p = (scale * dmat * _dot(q, k, _NT)).astype(dt)
    kr = (k.astype(_F32) * r).astype(dt)
    d_v = _dot(p, d_o, _TN) + _dot(kr, dsb)
    dp = (scale * dmat * _dot(d_o, v, _NT)).astype(dt)
    d_q = _dot(dp, k) + (scale * a) * _dot(d_o, s0, _NT)
    d_k = _dot(dp, q, _TN) + r * _dot(v, dsb, _NT)
    qa = (q.astype(_F32) * (scale * a)).astype(dt)
    return d_q, d_k, d_v, _lanes(ec, ds.shape[1]) * ds + _dot(qa, d_o, _TN)


# ----------------------------------------- the sweeps, pallas: in VMEM

def _lanes_of(ref, i: int, hb: int):
    d = ref.shape[-1] // hb
    return ref[0, :, i * d:(i + 1) * d]


def _fwd_kernel(q_ref, k_ref, v_ref, dmat_ref, cols_ref, o_ref, s0_ref,
                s_ref, *, hb: int, scale: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    for i in range(hb):     # independent chains of products
        o, sb, s = _chunk_fwd(
            *(_lanes_of(r, i, hb) for r in (q_ref, k_ref, v_ref)),
            dmat_ref[i], cols_ref[i], s_ref[i], scale)
        _put_head(o_ref, i, hb, o)
        s0_ref[0, i, 0], s_ref[i] = sb, s


def _bwd_kernel(q_ref, k_ref, v_ref, dmat_ref, cols_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, ds_ref, *, hb: int, scale: float):
    """The chunks last to first; ``ds_ref`` carries the gradient of the
    state LEAVING the chunk."""
    @pl.when(pl.program_id(2) == 0)     # the LAST chunk: time reversed
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for i in range(hb):
        q, k, v, d_o = (_lanes_of(r, i, hb)
                        for r in (q_ref, k_ref, v_ref, do_ref))
        d_q, d_k, d_v, ds = _chunk_bwd(
            q, k, v, dmat_ref[i], cols_ref[i], s0_ref[0, i, 0], d_o,
            ds_ref[i], scale)
        for ref, x in ((dq_ref, d_q), (dk_ref, d_k), (dv_ref, d_v)):
            _put_head(ref, i, hb, x)
        ds_ref[i] = ds


def _head_specs(chunk: int, hb: int):
    """A step's heads' constants: blocks the chunk axis does not move,
    so each is fetched once a group of heads."""
    return [pl.BlockSpec((hb, chunk, chunk), lambda bi, gi, ci: (gi, 0, 0)),
            pl.BlockSpec((hb, chunk, 3), lambda bi, gi, ci: (gi, 0, 0))]


def _pallas_fwd(q, k, v, dmat, cols, chunk: int, hb: int, scale: float):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    grid, tok, mat, _, _ = _specs(b, t, h, chunk, hb, False)
    o, s0 = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, scale=scale), grid=grid,
        in_specs=[tok(dk), tok(dk), tok(dv), *_head_specs(chunk, hb)],
        out_specs=[tok(dv), mat(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h, t // chunk, dk, dv),
                                        q.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_compiler_params(),
        name="lightning_fwd",
        interpret=pallas_common.interpret_mode(),
    )(_flat(q), _flat(k), _flat(v), dmat, cols)
    return o.reshape(b, t, h, dv), s0


def _pallas_bwd(q, k, v, dmat, cols, s0, do, chunk: int, hb: int,
                scale: float):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    grid, tok, mat, _, _ = _specs(b, t, h, chunk, hb, True)
    dq, dk_, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, scale=scale), grid=grid,
        in_specs=[tok(dk), tok(dk), tok(dv), *_head_specs(chunk, hb),
                  mat(dk, dv), tok(dv)],
        out_specs=[tok(dk), tok(dk), tok(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, t, h * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, t, h * dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_compiler_params(),
        name="lightning_bwd",
        interpret=pallas_common.interpret_mode(),
    )(_flat(q), _flat(k), _flat(v), dmat, cols, s0, _flat(do))
    return dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape)


# ------------------------------------- the sweeps, xla: a scan's steps

def _each(fn, scale: float, after: int):
    """``fn`` of a chunk over every (batch row, head): the tiles are a
    row's and a head's (three before the constants, ``after`` behind
    them), the constants a head's."""
    heads = jax.vmap(functools.partial(fn, scale=scale))
    return jax.vmap(heads, in_axes=(0, 0, 0, None, None) + (0,) * after)


def _xla_fwd(q, k, v, dmat, cols, chunk: int, scale: float):
    each = _each(_chunk_fwd, scale, 1)

    def step(s, xs):
        o, sb, s = each(*xs, dmat, cols, s)
        return s, (o, sb)
    _, (o, s0) = jax.lax.scan(
        step, _state(q, v), tuple(_chunks(a, chunk) for a in (q, k, v)))
    return _tokens(o).astype(v.dtype), jnp.moveaxis(s0, 0, 2)


def _xla_bwd(q, k, v, dmat, cols, s0, do, chunk: int, scale: float):
    each = _each(_chunk_bwd, scale, 3)

    def step(ds, xs):
        *tiles, s0_c, do_c = xs
        *grads, ds = each(*tiles, dmat, cols, s0_c, do_c, ds)
        return ds, grads
    _, grads = jax.lax.scan(
        step, _state(q, v),
        (*(_chunks(a, chunk) for a in (q, k, v)), jnp.moveaxis(s0, 2, 0),
         _chunks(do, chunk)), reverse=True)
    return tuple(_tokens(g) for g in grads)


# ------------------------------------------------------------ public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def lightning_attention(q, k, v, log_decay, scale: float = 1.0,
                        impl: str = "auto", chunk: int | None = None):
    """``o [B, T, H, dv]`` in ``v``'s dtype; see the module's docstring.
    ``chunk`` is the number of tokens a chunk holds and the distance
    between two kept states (left out, the shapes' own: ``tile_plan``);
    T need not be a multiple of it: a padded token has ``k = v = 0``,
    and what it reads is dropped."""
    return _vjp_fwd(q, k, v, log_decay, scale, impl, chunk)[0]


def _whole_chunks(q, v, chunk, *xs):
    """``(C, hb)`` of the call and ``xs`` [B, T, ...] padded to whole
    chunks."""
    c, hb = tile_plan(*q.shape[1:], v.shape[-1], q.dtype.itemsize)
    chunk = chunk or c
    return chunk, hb, tuple(_pad_time(a, -q.shape[1] % chunk) for a in xs)


def _vjp_fwd(q, k, v, log_decay, scale, impl, chunk):
    chunk, hb, xs = _whole_chunks(q, v, chunk, q, k, v)
    tiles = _decay_tiles(log_decay, chunk)
    if _resolve(impl) == "pallas":
        o, s0 = _pallas_fwd(*xs, *tiles, chunk, hb, scale)
    else:
        o, s0 = _xla_fwd(*xs, *tiles, chunk, scale)
    return o[:, :q.shape[1]], (q, k, v, log_decay, s0)


def _vjp_bwd(scale, impl, chunk, res, do):
    q, k, v, log_decay, s0 = res
    with scope("linattn.rule"):
        chunk, hb, xs = _whole_chunks(q, v, chunk, q, k, v,
                                      do.astype(q.dtype))
        tiles = _decay_tiles(log_decay, chunk)
        if _resolve(impl) == "pallas":
            grads = _pallas_bwd(*xs[:3], *tiles, s0, xs[3], chunk, hb, scale)
        else:
            grads = _xla_bwd(*xs[:3], *tiles, s0, xs[3], chunk, scale)
        return (*(d[:, :q.shape[1]].astype(a.dtype)
                  for d, a in zip(grads, (q, k, v))),
                jnp.zeros_like(log_decay))


lightning_attention.defvjp(_vjp_fwd, _vjp_bwd)


def reference_rule(q, k, v, log_decay, scale: float = 1.0):
    """The recurrence as it is written, one token at a time in float32,
    with every state alive under autodiff: what the tests compare
    with."""
    lam = jnp.exp(log_decay.astype(_F32))[None, :, None, None]

    def step(s, xs):
        q_t, k_t, v_t = xs
        s = lam * s + k_t[..., :, None] * v_t[..., None, :]
        return s, scale * jnp.einsum("bhde,bhd->bhe", s, q_t)

    xs = tuple(x.astype(_F32).swapaxes(0, 1) for x in (q, k, v))
    return jax.lax.scan(step, _state(q, v), xs)[1].swapaxes(0, 1) \
        .astype(v.dtype)
