"""``python -m dlnetbench_tpu.tuning`` — the tuning driver CLI.

    # search 2 candidates for a tiny int8 fused matmul on this backend
    # and commit the winner (seconds on CPU — the check-tuning lane)
    python -m dlnetbench_tpu.tuning tune --op quantized_matmul \
        --db /tmp/dlnb_tuning --fmt int8 --tokens 64 --d 64 --n 64 \
        --candidates "64,64,64;32,64,64" --k 4 --rounds 2

    # flash-attention backward blocks at the bench shape (on chip)
    python -m dlnetbench_tpu.tuning tune --op flash_bwd \
        --db /tmp/dlnb_tuning --batch 2 --seq 6144 --heads 32 \
        --kv_heads 8 --head_dim 128

    # list what the DB holds
    python -m dlnetbench_tpu.tuning show --db /tmp/dlnb_tuning

Ops: ``quantized_matmul`` (fused Pallas grid blocks),
``flash_fwd`` / ``flash_bwd`` (flash-attention block shapes),
``splash_fwd`` / ``splash_bwd`` (block-sparse masked attention blocks —
``--window``/``--seg_avg``/``--seg_seed`` pick the mask, which rides
in the key), ``paged_attention`` / ``paged_attention_quant``
(``pages_per_compute_block``; the quant op takes ``--fmt`` and
measures the dequantizing kernel over int8/fp8 pools — ISSUE 12),
``tp_overlap_chunks`` (collective-matmul ring grain, needs >= 2
devices), ``grad_bucket_layers`` (bucketed DP grad sync, needs >= 2
devices).  Every op measures with the K-chained fence timing the bench
lines use, prunes band-aware, and commits the winner with its measured
band; keys are built by the SAME ``tuning.params`` builders the consult
sites use, so a committed record is guaranteed consultable.
"""
from __future__ import annotations

import argparse
import json
import sys

from dlnetbench_tpu.tuning import params as tparams
from dlnetbench_tpu.tuning.db import TuningDB
from dlnetbench_tpu.tuning.search import tune_and_commit

OPS = ("quantized_matmul", "flash_fwd", "flash_bwd", "splash_fwd",
       "splash_bwd", "paged_attention", "paged_attention_quant",
       "grouped_ffn", "tp_overlap_chunks", "grad_bucket_layers")


def _parse_candidates(spec: str | None, arity: int,
                      names: tuple[str, ...]) -> list[dict] | None:
    """``"a,b,c;d,e,f"`` -> [{names[0]: a, ...}, ...]; None passes
    through (op-specific default grid)."""
    if not spec:
        return None
    out = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        vals = [int(v) for v in part.split(",")]
        if len(vals) != arity:
            raise ValueError(
                f"--candidates: {part!r} has {len(vals)} fields, "
                f"op wants {arity} ({','.join(names)})")
        out.append(dict(zip(names, vals)))
    if not out:
        raise ValueError("--candidates: empty after parsing")
    return out


def _chain(fn, warm_args, k: int):
    """jit + warm + K-chained measure closure (one sample per call),
    the bench-line timing convention (utils/timing.time_chain)."""
    import jax

    from dlnetbench_tpu.utils.timing import time_chain
    jfn = jax.jit(fn)
    jax.block_until_ready(jfn(*warm_args))      # compile outside timing
    return lambda: time_chain(jfn, *warm_args, k=k)


def _tune_quantized_matmul(args):
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.ops import quantized_matmul as qmm

    t, d, n, fmt = args.tokens, args.d, args.n, args.fmt
    x = jax.random.normal(jax.random.key(0), (t, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (d, n), jnp.bfloat16) * 0.02
    wq, sw = qmm.quantize_tensor(w, fmt)
    sx = qmm.scale_from_amax(jnp.max(jnp.abs(x.astype(jnp.float32))), fmt)
    key = tparams.quantized_matmul_key(t, d, n, fmt, x.dtype)
    cands = _parse_candidates(args.candidates, 3,
                              ("block_m", "block_n", "block_k")) or [
        {"block_m": 1024, "block_n": 2048, "block_k": 2048},  # default
        {"block_m": 512, "block_n": 2048, "block_k": 2048},
        {"block_m": 1024, "block_n": 1024, "block_k": 2048},
        {"block_m": 2048, "block_n": 2048, "block_k": 2048},
    ]

    def measure_cfg(cfg):
        fn = _chain(lambda xx: qmm.fused_matmul(
            xx, wq, sw, sx, fmt=fmt, block_m=cfg["block_m"],
            block_n=cfg["block_n"], block_k=cfg["block_k"]), (x,), args.k)
        return fn  # one compiled closure per candidate

    return "quantized_matmul", key, cands, measure_cfg


def _tune_flash(args, direction: str):
    import importlib

    import jax
    import jax.numpy as jnp

    # the ops package re-exports the flash_attention FUNCTION under the
    # module's name; import the module itself for its internals
    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")

    b, s = args.batch, args.seq
    hq, hkv, dh = args.heads, args.kv_heads, args.head_dim
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    q = jax.random.normal(jax.random.key(0), (b, s, hq, dh), dt)
    k = jax.random.normal(jax.random.key(1), (b, s, hkv, dh), dt)
    v = jax.random.normal(jax.random.key(2), (b, s, hkv, dh), dt)

    if direction == "fwd":
        # key on the ARRAY dtype (str 'float32'/'bfloat16'), exactly as
        # the consult site does — a class repr would never hit
        key = tparams.flash_fwd_key(b, s, hq, hkv, dh, True, q.dtype)
        cands = _parse_candidates(args.candidates, 2,
                                  ("block_q", "block_k")) or [
            {"block_q": bq, "block_k": bk}
            for bq in (2048, 1024, 512) for bk in (2048, 1024, 512)
            if s % bq == 0 and s % bk == 0 and s >= bq and s >= bk]

        def measure_cfg(cfg):
            return _chain(lambda qq, kk, vv: fa.flash_attention(
                qq, kk, vv, True, cfg["block_q"], cfg["block_k"]),
                (q, k, v), args.k)
        return "flash_fwd", key, cands, measure_cfg

    key = tparams.flash_bwd_key(b, s, hq, hkv, dh, True, q.dtype)
    cands = _parse_candidates(args.candidates, 4,
                              ("bq_dq", "bk_dq", "bq_dkv", "bk_dkv")) or [
        {"bq_dq": bb, "bk_dq": bb, "bq_dkv": bb, "bk_dkv": bb}
        for bb in (1024, 512, 256) if s % bb == 0 and s >= bb]
    out, lse = fa._fwd(q, k, v, causal=True,
                       block_q=fa._pick_block(s),
                       block_k=fa._pick_block(s))
    do = jax.random.normal(jax.random.key(3), q.shape, dt)

    def measure_cfg(cfg):
        """The backward at the record's two pairs of blocks.  Where a
        head's dq is resident in the dk/dv kernel (``fa._dq_resident``:
        from the shape alone) the dkv pair is that one kernel's and the
        dq pair is unused; it keeps its meaning, and the record its
        four keys, for the shapes past the rule."""
        blocks = ((cfg["bq_dq"], cfg["bk_dq"]),
                  (cfg["bq_dkv"], cfg["bk_dkv"]))
        return _chain(lambda *a: fa._bwd_impl(
            *a, causal=True, block_q=blocks[0][0], block_k=blocks[0][1],
            override_blocks=blocks), (q, k, v, out, lse, do), args.k)
    return "flash_bwd", key, cands, measure_cfg


def _tune_splash(args, direction: str):
    """Block-sparse (splash) attention blocks — the masked sibling of
    ``_tune_flash``; the MASK rides in both the measured kernel and
    the committed key (``--window`` / ``--seg_avg`` / ``--seg_seed``
    build the MaskSpec), so a window-mask optimum can never answer a
    segment-mask consult."""
    import importlib

    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.ops.attention_mask import MaskSpec

    fa = importlib.import_module("dlnetbench_tpu.ops.flash_attention")
    spec = MaskSpec(causal=True, window=args.window,
                    seg_avg=args.seg_avg, seg_seed=args.seg_seed)

    b, s = args.batch, args.seq
    hq, hkv, dh = args.heads, args.kv_heads, args.head_dim
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    q = jax.random.normal(jax.random.key(0), (b, s, hq, dh), dt)
    k = jax.random.normal(jax.random.key(1), (b, s, hkv, dh), dt)
    v = jax.random.normal(jax.random.key(2), (b, s, hkv, dh), dt)
    key = tparams.splash_key(b, s, hq, hkv, dh, spec.label(), q.dtype)

    if direction == "fwd":
        cands = _parse_candidates(args.candidates, 2,
                                  ("block_q", "block_k")) or [
            {"block_q": bq, "block_k": bk}
            for bq in (2048, 1024, 512) for bk in (2048, 1024, 512)
            if s % bq == 0 and s % bk == 0 and s >= bq and s >= bk]

        def measure_cfg(cfg):
            return _chain(lambda qq, kk, vv: fa.splash_attention(
                qq, kk, vv, spec, cfg["block_q"], cfg["block_k"]),
                (q, k, v), args.k)
        return "splash_fwd", key, cands, measure_cfg

    cands = _parse_candidates(args.candidates, 4,
                              ("bq_dq", "bk_dq", "bq_dkv", "bk_dkv")) or [
        {"bq_dq": bb, "bk_dq": bb, "bq_dkv": bb, "bk_dkv": bb}
        for bb in (1024, 512, 256) if s % bb == 0 and s >= bb]
    out, lse = fa._splash_fwd(q, k, v, spec,
                              block_q=fa._pick_block(s),
                              block_k=fa._pick_block(s))
    do = jax.random.normal(jax.random.key(3), q.shape, dt)

    def measure_cfg(cfg):
        """As ``_tune_flash``'s: the dq pair is unused where a head's
        dq is resident in the dk/dv kernel."""
        blocks = ((cfg["bq_dq"], cfg["bk_dq"]),
                  (cfg["bq_dkv"], cfg["bk_dkv"]))
        return _chain(lambda *a: fa._splash_bwd_impl(
            *a, spec, block_q=blocks[0][0], block_k=blocks[0][1],
            override_blocks=blocks), (q, k, v, out, lse, do), args.k)
    return "splash_bwd", key, cands, measure_cfg


def _tune_paged_attention(args):
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.serving import kv_cache as kvc

    b, hq, hkv, dh = args.batch, args.heads, args.kv_heads, args.head_dim
    pages, psz = args.pages, args.page_size
    q = jax.random.normal(jax.random.key(0), (b, hq, dh), jnp.float32)
    kp = jax.random.normal(jax.random.key(1), (hkv, pages * b, psz, dh),
                           jnp.float32)
    vp = jax.random.normal(jax.random.key(2), kp.shape, jnp.float32)
    lengths = jnp.full((b,), pages * psz, jnp.int32)
    pidx = jnp.arange(pages * b, dtype=jnp.int32).reshape(b, pages)
    key = tparams.paged_attention_key(pages, psz, b, hq, hkv, dh)
    cands = _parse_candidates(args.candidates, 1,
                              ("pages_per_compute_block",)) or [
        {"pages_per_compute_block": c}
        for c in (1, 2, 4, 8, 16) if c <= pages and pages % c == 0]

    def measure_cfg(cfg):
        return _chain(lambda *a: kvc.paged_attention_decode(
            *a, pages_per_compute_block=cfg["pages_per_compute_block"]),
            (q, kp, vp, lengths, pidx), args.k)
    return "paged_attention", key, cands, measure_cfg


def _tune_paged_attention_quant(args):
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.serving import kv_cache as kvc

    b, hq, hkv, dh = args.batch, args.heads, args.kv_heads, args.head_dim
    pages, psz = args.pages, args.page_size
    fmt = {"int8": "int8", "float8": "float8"}[args.fmt]
    qdt = jnp.int8 if fmt == "int8" else jnp.float8_e4m3fn
    q = jax.random.normal(jax.random.key(0), (b, hq, dh), jnp.float32)
    kp = jax.random.randint(jax.random.key(1),
                            (hkv, pages * b, psz, dh), -127,
                            127).astype(qdt)
    vp = jax.random.randint(jax.random.key(2), kp.shape, -127,
                            127).astype(qdt)
    ks = jnp.abs(jax.random.normal(jax.random.key(3),
                                   (hkv, pages * b))) * 0.02 + 1e-4
    vs = jnp.abs(jax.random.normal(jax.random.key(4),
                                   (hkv, pages * b))) * 0.02 + 1e-4
    lengths = jnp.full((b,), pages * psz, jnp.int32)
    pidx = jnp.arange(pages * b, dtype=jnp.int32).reshape(b, pages)
    key = tparams.paged_attention_quant_key(pages, psz, b, hq, hkv, dh,
                                            fmt)
    cands = _parse_candidates(args.candidates, 1,
                              ("pages_per_compute_block",)) or [
        {"pages_per_compute_block": c}
        for c in (1, 2, 4, 8, 16) if c <= pages and pages % c == 0]

    def measure_cfg(cfg):
        return _chain(lambda *a: kvc.paged_attention_decode(
            *a, k_scale=ks, v_scale=vs, fmt=fmt, impl="pallas",
            pages_per_compute_block=cfg["pages_per_compute_block"]),
            (q, kp, vp, lengths, pidx), args.k)
    return "paged_attention_quant", key, cands, measure_cfg


def _tune_tp_overlap_chunks(args):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from dlnetbench_tpu.ops import collective_matmul as CM
    from dlnetbench_tpu.parallel.mesh import AXIS_TP
    from dlnetbench_tpu.utils.jax_compat import shard_map

    tp = args.tp or len(jax.devices())
    if tp < 2:
        raise SystemExit("tp_overlap_chunks tuning needs >= 2 devices "
                         "(one device has no ring to overlap)")
    d, f, s = args.d, args.n, args.seq
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    mesh = Mesh(jax.devices()[:tp], (AXIS_TP,))
    x = jax.random.normal(jax.random.key(0), (1, s, d), dt)
    w = jax.random.normal(jax.random.key(1), (d, f), dt) * 0.02
    key = tparams.tp_overlap_chunks_key(d, f, s, tp, args.dtype)
    cands = _parse_candidates(args.candidates, 1, ("chunks",)) or [
        {"chunks": c} for c in (1, 2, 4, 8)]

    def measure_cfg(cfg):
        from jax.sharding import PartitionSpec as P

        def fn(xx, ww):
            return shard_map(
                lambda a, b2: CM.all_gather_matmul(
                    a, b2, AXIS_TP, gather_axis=1,
                    chunks=cfg["chunks"]),
                mesh=mesh, in_specs=(P(None, AXIS_TP, None), P()),
                out_specs=P(None, AXIS_TP, None),
                check_vma=False)(xx, ww)
        return _chain(fn, (x, w), args.k)
    return "tp_overlap_chunks", key, cands, measure_cfg


def _tune_grad_bucket_layers(args):
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.models import spmd
    from dlnetbench_tpu.parallel.mesh import make_grid_mesh

    dp = args.tp or len(jax.devices())
    if dp < 2:
        raise SystemExit("grad_bucket_layers tuning needs >= 2 devices "
                         "(one device has no grad sync to schedule)")
    mesh = make_grid_mesh(dp=dp, pp=1, tp=1,
                          devices=jax.devices()[:dp])
    base = spmd.SpmdConfig(embed_dim=args.d, ff_dim=args.n,
                           seq_len=args.seq, num_layers=args.layers,
                           batch=dp * 2, num_microbatches=1,
                           grad_sync="bucketed", tp_overlap_chunks=2)
    key = tparams.grad_bucket_layers_key(base.num_layers, dp, 1,
                                         base.embed_dim, base.ff_dim)
    cands = _parse_candidates(args.candidates, 1, ("layers",)) or [
        {"layers": c} for c in (1, 2, 4) if c <= base.num_layers]
    params = spmd.init_params(jax.random.key(0), base)
    tokens = jax.random.randint(jax.random.key(1),
                                (base.batch, base.seq_len + 1), 0,
                                base.vocab_size)

    def measure_cfg(cfg):
        import dataclasses
        c = dataclasses.replace(base, grad_bucket_layers=cfg["layers"])
        step = spmd.make_train_step(mesh, c)
        return _chain(step, (params, tokens), args.k)
    return "grad_bucket_layers", key, cands, measure_cfg


def _tune_grouped_ffn(args):
    """Grouped expert-FFN grid blocks (ops/grouped_matmul.py, ISSUE
    15), one matmul at a time: the consult is keyed by a matmul's own
    shape (``params.grouped_ffn_key`` over experts x capacity x K x N),
    so the search measures ``[E, C, d] @ [E, d, n]``, the gate and up
    projection of (--experts x --capacity x --d x --n); the down
    projection is the same search with --d and --n swapped.  --fmt
    rides in the key (bf16 optima never answer int8/fp8 consults).
    The default candidates start from the shape's own ``tile_plan``:
    whole dimensions and lane-multiple divisors for each row block,
    beside the powers of two searched before."""
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.ops import grouped_matmul as gm
    from dlnetbench_tpu.ops import pallas_common

    e, c, d, h = args.experts, args.capacity, args.d, args.n
    fmt = None if args.fmt == "none" else args.fmt
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    x = jax.random.normal(jax.random.key(0), (e, c, d), dt)
    w = jax.random.normal(jax.random.key(1), (e, d, h), dt) * 0.02
    scales = {}
    if fmt:
        w, sw = gm.quantize_experts(w, fmt)
        scales = dict(sx=gm.scale_from_amax(gm.expert_amax(x), fmt),
                      sw=sw, fmt=fmt)
    key = tparams.grouped_ffn_key(e, c, d, h, fmt or "none", x.dtype)
    names = ("block_c", "block_n", "block_k")
    cands = _parse_candidates(args.candidates, 3, names)
    if cands is None:
        limit = pallas_common.DEFAULT_VMEM_LIMIT_MB * 2 ** 20
        rows = (512, 256, 128)
        grid = [tuple(gm.tile_plan(c, d, h, x.dtype.itemsize,
                                   quantized=fmt is not None).values())]
        grid += [(bc, bn, bk) for bc in rows
                 for bn in gm.blocks_of(h, pallas_common.LANES)[:3]
                 for bk in gm.blocks_of(d, pallas_common.LANES)[:2]]
        grid += [(bc, bn, bk) for bc in rows for bn in (1024, 512)
                 for bk in (512, 256)]
        # as the kernel will run them, each once, those that fit
        fitted = dict.fromkeys(
            (pallas_common.fit_block(c, bc), pallas_common.fit_block(h, bn),
             pallas_common.fit_block(d, bk)) for bc, bn, bk in grid)
        cands = [dict(zip(names, blk)) for blk in fitted
                 if gm.tile_bytes(*blk, x.dtype.itemsize,
                                   fmt is not None) <= limit]

    def measure_cfg(cfg):
        return _chain(lambda xx: gm.grouped_matmul(xx, w, **scales, **cfg),
                      (x,), args.k)
    return "grouped_ffn", key, cands, measure_cfg


def _run_tune(args) -> int:
    db_root = args.db or tparams.db_dir()
    if not db_root:
        print("tune: no DB — pass --db DIR or set "
              f"${tparams.ENV_DB_DIR}", file=sys.stderr)
        return 2
    builders = {
        "quantized_matmul": lambda: _tune_quantized_matmul(args),
        "flash_fwd": lambda: _tune_flash(args, "fwd"),
        "flash_bwd": lambda: _tune_flash(args, "bwd"),
        "splash_fwd": lambda: _tune_splash(args, "fwd"),
        "splash_bwd": lambda: _tune_splash(args, "bwd"),
        "paged_attention": lambda: _tune_paged_attention(args),
        "paged_attention_quant":
            lambda: _tune_paged_attention_quant(args),
        "grouped_ffn": lambda: _tune_grouped_ffn(args),
        "tp_overlap_chunks": lambda: _tune_tp_overlap_chunks(args),
        "grad_bucket_layers": lambda: _tune_grad_bucket_layers(args),
    }
    op, key, cands, measure_cfg = builders[args.op]()
    if not cands:
        # the built-in grids filter by shape divisibility (e.g. the
        # flash grids need --seq divisible by one of their blocks) —
        # name the fix instead of letting run_search raise opaquely
        print(f"tune: no applicable candidates for --op {args.op} at "
              f"this shape (the default grid's blocks must divide the "
              f"sequence/shape dims) — adjust the shape flags or pass "
              f"an explicit --candidates grid", file=sys.stderr)
        return 2
    hw = tparams.hw_key()
    print(f"tune: {op} key={key} hw={hw} — {len(cands)} candidates, "
          f"seed {args.seed}, {args.rounds} rounds of K={args.k} chains",
          file=sys.stderr)
    db = TuningDB(db_root)

    # one compiled closure per candidate, built lazily and kept for its
    # rounds only (the search calls measure(config) once per round)
    compiled: dict[str, object] = {}

    def measure(cfg):
        ck = json.dumps(cfg, sort_keys=True)
        if ck not in compiled:
            compiled[ck] = measure_cfg(cfg)
        return compiled[ck]()

    res = tune_and_commit(db, op, key, hw, cands, measure,
                          seed=args.seed, rounds=args.rounds, k=args.k,
                          log=lambda m: print(m, file=sys.stderr))
    print(json.dumps(res["record"]))
    print(f"tune: committed {res['config']} "
          f"(median {res['band']['value'] * 1e3:.3f} ms, "
          f"{res['pruned']} candidate(s) pruned) -> {db.path}",
          file=sys.stderr)
    return 0


def _run_show(args) -> int:
    db_root = args.db or tparams.db_dir()
    if not db_root:
        print("show: no DB — pass --db DIR or set "
              f"${tparams.ENV_DB_DIR}", file=sys.stderr)
        return 2
    db = TuningDB(db_root)
    records = db.load()
    for rec in records.values():
        print(json.dumps(rec))
    print(f"{len(records)} record(s) in {db.path}", file=sys.stderr)
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="python -m dlnetbench_tpu.tuning",
                                     description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("tune", help="seeded search + commit on this "
                                    "backend")
    t.add_argument("--op", required=True, choices=OPS)
    t.add_argument("--db", default=None,
                   help=f"DB directory (default: ${tparams.ENV_DB_DIR})")
    t.add_argument("--candidates", default=None,
                   help="explicit grid, ';'-separated tuples (per-op "
                        "arity); default: the op's built-in grid")
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--rounds", type=int, default=3,
                   help="K-chains per surviving candidate")
    t.add_argument("-k", type=int, default=8,
                   help="step dispatches per fence chain")
    # shape flags (per-op subsets)
    t.add_argument("--tokens", type=int, default=256)
    t.add_argument("--d", type=int, default=256)
    t.add_argument("--n", type=int, default=256)
    t.add_argument("--fmt", default="int8",
                   choices=["int8", "float8", "none"],
                   help="quant format; 'none' (grouped_ffn only) "
                        "measures the master-dtype kernel")
    t.add_argument("--experts", type=int, default=8,
                   help="grouped_ffn: expert count E")
    t.add_argument("--capacity", type=int, default=256,
                   help="grouped_ffn: dispatch slots per expert C")
    t.add_argument("--batch", type=int, default=1)
    t.add_argument("--seq", type=int, default=1024)
    t.add_argument("--heads", type=int, default=4)
    t.add_argument("--kv_heads", type=int, default=4)
    t.add_argument("--head_dim", type=int, default=128)
    t.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    t.add_argument("--window", type=int, default=0,
                   help="splash ops: sliding-window width (0 = off)")
    t.add_argument("--seg_avg", type=int, default=0,
                   help="splash ops: seeded segment plan's average "
                        "document length (0 = off)")
    t.add_argument("--seg_seed", type=int, default=0)
    t.add_argument("--pages", type=int, default=8)
    t.add_argument("--page_size", type=int, default=8)
    t.add_argument("--layers", type=int, default=4)
    t.add_argument("--tp", type=int, default=0,
                   help="mesh size for the multi-device ops (0 = all "
                        "devices)")
    s = sub.add_parser("show", help="list the DB's records")
    s.add_argument("--db", default=None)
    args = parser.parse_args(argv)
    if args.cmd == "tune":
        if args.fmt == "none" and args.op != "grouped_ffn":
            # every other --fmt consumer is a quantized kernel — fail
            # as a tidy usage error, not a ValueError from inside it
            parser.error(f"--fmt none is only meaningful for "
                         f"--op grouped_ffn (the master-dtype grouped "
                         f"kernel); --op {args.op} needs int8/float8")
        return _run_tune(args)
    return _run_show(args)


if __name__ == "__main__":
    raise SystemExit(main())
