"""Selective scan: the recurrence of a Mamba mixer (arXiv:2312.00752),

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) B_t^T      [E, N]
    s_t = h_t C_t + D * u_t                                        [E]

over ``u, delta: [B, T, E]``, ``A: [E, N]``, ``B, C: [B, T, N]``,
``D: [E]``.  The state is float32 whatever the inputs are, and
``[T, E, N]`` never lies in HBM: the forward keeps the state at chunk
boundaries only (``[T / chunk, N, E]``), and the hand-written backward
recomputes the states of one chunk at a time from its boundary, walking
the chunks last to first.

Two implementations of the same arithmetic, chosen by ``impl`` as
``ops.attention`` chooses: ``"pallas"`` (kernels ``ssm_scan_fwd`` and
``ssm_scan_bwd``; the state of one block of 512 channels stays in
vector registers, N on sublanes and channels on lanes; the grid
walks the chunks in order and, inside a chunk, the channel blocks), ``"xla"`` (a ``lax.scan`` over chunks of a
``lax.scan`` over time), ``"auto"`` (the kernels on a TPU where the
channel count is a multiple of 128, XLA elsewhere; off the TPU
``"pallas"`` runs in interpret mode, for tests).

Both backward forms wear the ``ssm.scan`` scope themselves, as
``layers.dispatch_rows`` does: a ``custom_vjp``'s backward is traced
outside the caller's scope.
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
_LANES = pallas_common.LANES
CHUNK = 64          # time steps between two kept states
_GROUP = 16         # rows loaded at once: one bf16 tile, two float32
_E_BLOCKS = (512, 256, 128)


def pallas_supported(u) -> bool:
    return u.shape[-1] % _LANES == 0


def _resolve(impl: str, u) -> str:
    if impl == "auto":
        return ("pallas" if jax.default_backend() == "tpu"
                and pallas_supported(u) else "xla")
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown selective_scan impl {impl!r}")
    if impl == "pallas" and not pallas_supported(u):
        raise ValueError(
            f"selective_scan: impl='pallas' needs a channel count that "
            f"is a multiple of {_LANES}, got {u.shape[-1]}")
    return impl


# ------------------------------------------------------------------ xla

def _time_major(x, chunk):
    """[B, T, F] -> [T / chunk, chunk, B, F]."""
    b, t, f = x.shape
    return x.reshape(b, t // chunk, chunk, f).transpose(1, 2, 0, 3)


def _batch_major(x):
    """[nc, chunk, B, F] -> [B, T, F]."""
    nc, c, b, f = x.shape
    return x.transpose(2, 0, 1, 3).reshape(b, nc * c, f)


def _xla_fwd(u, delta, at, bm, cm, chunk):
    """Returns (y [B, T, E] float32 without the skip, hs [nc, B, N, E]:
    the state entering each chunk)."""
    bsz, _, e = u.shape
    n = at.shape[0]

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        d_a = jnp.exp(d_t[:, None, :] * at)
        h = d_a * h + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    def one_chunk(h, xs):
        h_out, y = jax.lax.scan(step, h, xs)
        return h_out, (h, y)

    xs = tuple(_time_major(x.astype(_F32), chunk)
               for x in (u, delta, bm, cm))
    _, (hs, y) = jax.lax.scan(one_chunk, jnp.zeros((bsz, n, e), _F32), xs)
    return _batch_major(y), hs


def _xla_bwd(u, delta, at, bm, cm, dy, hs, chunk):
    """(du without the skip's part, ddelta, dAt [N, E], dB, dC), all
    float32."""
    bsz, _, e = u.shape
    n = at.shape[0]

    def fstep(h, xs):
        u_t, d_t, b_t = xs
        d_a = jnp.exp(d_t[:, None, :] * at)
        return d_a * h + (d_t * u_t)[:, None, :] * b_t[:, :, None], h

    def bstep(carry, xs):
        g, d_at = carry
        u_t, d_t, b_t, c_t, dy_t, h_prev = xs
        d_a = jnp.exp(d_t[:, None, :] * at)
        x = d_t * u_t
        h = d_a * h_prev + x[:, None, :] * b_t[:, :, None]
        g = g + c_t[:, :, None] * dy_t[:, None, :]
        dc = jnp.sum(h * dy_t[:, None, :], axis=2)
        db = jnp.sum(g * x[:, None, :], axis=2)
        dx = jnp.sum(g * b_t[:, :, None], axis=1)
        q = g * h_prev * d_a
        dd = jnp.sum(q * at, axis=1) + dx * u_t
        d_at = d_at + jnp.sum(q * d_t[:, None, :], axis=0)
        return (g * d_a, d_at), (dx * d_t, dd, db, dc)

    def one_chunk(carry, xs):
        u_c, d_c, b_c, c_c, dy_c, h0 = xs
        _, h_prev = jax.lax.scan(fstep, h0, (u_c, d_c, b_c))
        return jax.lax.scan(bstep, carry,
                            (u_c, d_c, b_c, c_c, dy_c, h_prev),
                            reverse=True)

    xs = tuple(_time_major(x.astype(_F32), chunk)
               for x in (u, delta, bm, cm, dy)) + (hs,)
    zero = (jnp.zeros((bsz, n, e), _F32), jnp.zeros((n, e), _F32))
    (_, d_at), (du, dd, db, dc) = jax.lax.scan(one_chunk, zero, xs,
                                               reverse=True)
    return (_batch_major(du), _batch_major(dd), d_at, _batch_major(db),
            _batch_major(dc))


# --------------------------------------------------------------- pallas

def _e_block(e: int) -> int:
    return next(b for b in _E_BLOCKS if e % b == 0)


def _lanes(x, eb: int):
    """[N, 128], every lane alike -> [N, eb]."""
    return jnp.concatenate([x] * (eb // _LANES), axis=1) \
        if eb > _LANES else x


def _fold(x, eb: int):
    """[N, eb] -> [N, 128]: the lane blocks added up."""
    out = x[:, :_LANES]
    for j in range(1, eb // _LANES):
        out = out + x[:, j * _LANES:(j + 1) * _LANES]
    return out


def _compiler_params():
    return pallas_common.compiler_params(
        ("parallel", "arbitrary", "arbitrary"), vmem_limit_mb=64)


def _fwd_kernel(u_ref, d_ref, at_ref, b_ref, c_ref, dsk_ref,
                y_ref, hs_ref, h_ref, acc_ref, *, chunk: int, eb: int):
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        h_ref[j] = jnp.zeros_like(h_ref[j])

    hs_ref[0, 0] = h_ref[j]
    at = at_ref[:]

    def group(gi, h):
        base = pl.multiple_of(gi * _GROUP, _GROUP)
        u_g = u_ref[0, pl.ds(base, _GROUP), :].astype(_F32)
        d_g = d_ref[0, pl.ds(base, _GROUP), :].astype(_F32)
        for i in range(_GROUP):
            d_t = d_g[i:i + 1, :]
            b_t = _lanes(b_ref[0, base + i].astype(_F32), eb)
            c_t = _lanes(c_ref[0, base + i].astype(_F32), eb)
            h = jnp.exp(d_t * at) * h + (d_t * u_g[i:i + 1, :]) * b_t
            acc_ref[pl.ds(base + i, 1), :] = jnp.sum(
                h * c_t, axis=0, keepdims=True)
        return h

    h_ref[j] = jax.lax.fori_loop(0, chunk // _GROUP, group, h_ref[j])
    y_ref[0] = (acc_ref[:] + dsk_ref[:] * u_ref[0].astype(_F32)
                ).astype(y_ref.dtype)


def _rep(x):
    """[B, T, N] -> [B, T, N, 128], every lane alike: what lets the
    kernels take B_t and C_t as sublane columns with no transpose."""
    return jnp.broadcast_to(x[..., None], (*x.shape, _LANES))


def _pallas_fwd(u, delta, at, bm, cm, dskip, chunk):
    """Returns (s [B, T, E] in u's dtype WITH the skip, hs [B, nc, N,
    E])."""
    bsz, t, e = u.shape
    n = at.shape[0]
    eb, nc = _e_block(e), t // chunk
    te = pl.BlockSpec((1, chunk, eb), lambda b, c, j: (b, c, j))
    rep = pl.BlockSpec((1, chunk, n, _LANES), lambda b, c, j: (b, c, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, eb=eb),
        grid=(bsz, nc, e // eb),
        in_specs=[te, te,
                  pl.BlockSpec((n, eb), lambda b, c, j: (0, j)),
                  rep, rep,
                  pl.BlockSpec((1, eb), lambda b, c, j: (0, j))],
        out_specs=[te,
                   pl.BlockSpec((1, 1, n, eb),
                                lambda b, c, j: (b, c, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, e), u.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, n, e), _F32)],
        scratch_shapes=[pltpu.VMEM((e // eb, n, eb), _F32),
                        pltpu.VMEM((chunk, eb), _F32)],
        compiler_params=_compiler_params(),
        name="ssm_scan_fwd",
        interpret=pallas_common.interpret_mode(),
    )(u, delta, at, _rep(bm), _rep(cm), dskip.reshape(1, e))


def _bwd_kernel(u_ref, d_ref, at_ref, b_ref, c_ref, dsk_ref, dy_ref,
                hs_ref, du_ref, dd_ref, dat_ref, db_ref, dc_ref,
                g_ref, dat_acc, hbuf_ref, du_acc, dd_acc,
                *, chunk: int, eb: int):
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)     # the LAST chunk: time reversed
    def _init():
        g_ref[j] = jnp.zeros_like(g_ref[j])
        dat_acc[j] = jnp.zeros_like(dat_acc[j])

    @pl.when(j == 0)    # the partials add up over the channel blocks
    def _init_partials():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    at = at_ref[:]
    groups = chunk // _GROUP

    def load(gi):
        base = pl.multiple_of(gi * _GROUP, _GROUP)
        return (base, u_ref[0, pl.ds(base, _GROUP), :].astype(_F32),
                d_ref[0, pl.ds(base, _GROUP), :].astype(_F32))

    def forward(gi, h):
        """Recompute: hbuf[t] is the state ENTERING step t."""
        base, u_g, d_g = load(gi)
        for i in range(_GROUP):
            hbuf_ref[base + i] = h
            d_t = d_g[i:i + 1, :]
            b_t = _lanes(b_ref[0, base + i].astype(_F32), eb)
            h = jnp.exp(d_t * at) * h + (d_t * u_g[i:i + 1, :]) * b_t
        return h

    jax.lax.fori_loop(0, groups, forward, hs_ref[0, 0])

    def backward(k, carry):
        g, d_at = carry
        base, u_g, d_g = load(groups - 1 - k)
        dy_g = dy_ref[0, pl.ds(base, _GROUP), :].astype(_F32)
        for i in reversed(range(_GROUP)):
            t = base + i
            d_t, u_t, dy_t = (d_g[i:i + 1, :], u_g[i:i + 1, :],
                              dy_g[i:i + 1, :])
            b_t = _lanes(b_ref[0, t].astype(_F32), eb)
            c_t = _lanes(c_ref[0, t].astype(_F32), eb)
            h_prev = hbuf_ref[t]
            d_a = jnp.exp(d_t * at)
            x = d_t * u_t
            h = d_a * h_prev + x * b_t
            g = g + c_t * dy_t
            dc_ref[0, t] += _fold(h * dy_t, eb)
            db_ref[0, t] += _fold(g * x, eb)
            dx = jnp.sum(g * b_t, axis=0, keepdims=True)
            q = g * h_prev * d_a
            dd_acc[pl.ds(t, 1), :] = (
                jnp.sum(q * at, axis=0, keepdims=True) + dx * u_t)
            du_acc[pl.ds(t, 1), :] = dx * d_t
            d_at = d_at + q * d_t
            g = g * d_a
        return g, d_at

    g, d_at = jax.lax.fori_loop(0, groups, backward,
                                (g_ref[j], dat_acc[j]))
    g_ref[j] = g
    dat_acc[j] = d_at
    dat_ref[0] = d_at       # the first chunk's write is the last
    du_ref[0] = (du_acc[:] + dsk_ref[:] * dy_ref[0].astype(_F32)
                 ).astype(du_ref.dtype)
    dd_ref[0] = dd_acc[:].astype(dd_ref.dtype)


def _pallas_bwd(u, delta, at, bm, cm, dskip, dy, hs, chunk):
    """(du WITH the skip's part, ddelta, dAt [N, E], dB, dC)."""
    bsz, t, e = u.shape
    n = at.shape[0]
    eb, nc = _e_block(e), t // chunk
    ne = e // eb

    def rev(c):
        return nc - 1 - c

    te = pl.BlockSpec((1, chunk, eb), lambda b, c, j: (b, rev(c), j))
    rep = pl.BlockSpec((1, chunk, n, _LANES),
                       lambda b, c, j: (b, rev(c), 0, 0))
    du, dd, d_at, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, eb=eb),
        grid=(bsz, nc, ne),
        in_specs=[te, te,
                  pl.BlockSpec((n, eb), lambda b, c, j: (0, j)),
                  rep, rep,
                  pl.BlockSpec((1, eb), lambda b, c, j: (0, j)),
                  te,
                  pl.BlockSpec((1, 1, n, eb),
                               lambda b, c, j: (b, rev(c), 0, j))],
        out_specs=[te, te,
                   pl.BlockSpec((1, n, eb), lambda b, c, j: (b, 0, j)),
                   rep, rep],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, e), u.dtype),
                   jax.ShapeDtypeStruct((bsz, t, e), _F32),
                   jax.ShapeDtypeStruct((bsz, n, e), _F32),
                   jax.ShapeDtypeStruct((bsz, t, n, _LANES), _F32),
                   jax.ShapeDtypeStruct((bsz, t, n, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((ne, n, eb), _F32),
                        pltpu.VMEM((ne, n, eb), _F32),
                        pltpu.VMEM((chunk, n, eb), _F32),
                        pltpu.VMEM((chunk, eb), _F32),
                        pltpu.VMEM((chunk, eb), _F32)],
        compiler_params=_compiler_params(),
        name="ssm_scan_bwd",
        interpret=pallas_common.interpret_mode(),
    )(u, delta, at, _rep(bm), _rep(cm), dskip.reshape(1, e), dy, hs)
    # the kernel leaves the sums over channels in lane-wide partials
    return (du, dd, jnp.sum(d_at, axis=0), jnp.sum(db, axis=3),
            jnp.sum(dc, axis=3))


# ------------------------------------------------------------ public op

def _pad_time(x, pad: int):
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def selective_scan(u, delta, A, B, C, D, impl: str = "auto",
                   chunk: int = CHUNK):
    """``s [B, T, E]`` in ``u``'s dtype; see the module's docstring.
    ``chunk`` is the number of steps between two kept states (a
    multiple of 16 for the kernels); T need not be a multiple of it."""
    return _vjp_fwd(u, delta, A, B, C, D, impl, chunk)[0]


def _vjp_fwd(u, delta, A, B, C, D, impl, chunk):
    impl = _resolve(impl, u)
    t = u.shape[1]
    pad = -t % chunk
    # a padded step has delta 0: the state passes through it unchanged
    up, dp, bp, cp = (_pad_time(x, pad) for x in (u, delta, B, C))
    at = A.astype(_F32).T
    dskip = D.astype(_F32)
    if impl == "pallas":
        s, hs = _pallas_fwd(up, dp, at, bp, cp, dskip, chunk)
    else:
        y, hs = _xla_fwd(up, dp, at, bp, cp, chunk)
        s = (y + dskip * up.astype(_F32)).astype(u.dtype)
    return s[:, :t], (u, delta, A, B, C, D, hs)


def _vjp_bwd(impl, chunk, res, ds):
    u, delta, A, B, C, D, hs = res
    impl = _resolve(impl, u)
    t = u.shape[1]
    pad = -t % chunk
    with scope("ssm.scan"):
        up, dp, bp, cp, dyp = (_pad_time(x, pad)
                               for x in (u, delta, B, C, ds))
        at = A.astype(_F32).T
        dskip = D.astype(_F32)
        if impl == "pallas":
            du, dd, d_at, db, dc = _pallas_bwd(up, dp, at, bp, cp, dskip,
                                               dyp, hs, chunk)
        else:
            du, dd, d_at, db, dc = _xla_bwd(up, dp, at, bp, cp, dyp, hs,
                                            chunk)
            du = du + dskip * dyp.astype(_F32)
        d_d = jnp.sum(ds.astype(_F32) * u.astype(_F32), axis=(0, 1))
        return (du[:, :t].astype(u.dtype), dd[:, :t].astype(delta.dtype),
                d_at.T.astype(A.dtype), db[:, :t].astype(B.dtype),
                dc[:, :t].astype(C.dtype), d_d.astype(D.dtype))


selective_scan.defvjp(_vjp_fwd, _vjp_bwd)


def reference_scan(u, delta, A, B, C, D):
    """The recurrence as it is written, one step at a time, with
    ``[T, E, N]`` alive under autodiff: what the tests compare with."""
    f = _F32
    at = A.astype(f)

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[:, :, None] * at) * h
             + (d_t * u_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("ben,bn->be", h, c_t) + D.astype(f) * u_t

    xs = tuple(x.astype(f).swapaxes(0, 1) for x in (u, delta, B, C))
    h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), f)
    return jax.lax.scan(step, h0, xs)[1].swapaxes(0, 1).astype(u.dtype)
