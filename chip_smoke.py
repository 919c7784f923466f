#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: every default phase
    python chip_smoke.py --chips 4    one four-chip host: the mesh phases only
    python chip_smoke.py --tiny       rehearsal sizes (runs the phases on the
                                      CPU mesh too, then fails: no chip)

It drives the main paths once through the entry points a user calls
(``cli.main``, ``bench_step.build``, ``run_serving``) at the published widths
of llama3_8b / mixtral_8x7b, with depth cut to what one 16 GB chip and the
run's time limit allow (every cut is printed), weights and tokens made from a
seed.  One process touches JAX: the chip belongs to one process at a time.

Every phase prints one JSON line (name, shapes, cuts, compile and run seconds,
the checks that passed).  A phase that fails makes the exit status non-zero.
The LAST line, printed only when every phase passed on a TPU, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Records and anything too long for a line go under ``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------- sizes

@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase reads about how big to run.  ``FULL`` is the
    chip; ``TINY`` is the CPU rehearsal (same code, same order)."""
    seed: int = 0
    dtype: str = "bfloat16"
    # kernels: tokens x embed x ff, attention seq/heads
    k_tokens: int = 2048
    k_embed: int = 4096
    k_ff: int = 14336
    k_seq: int = 2048
    k_heads: int = 32
    k_kv_heads: int = 8
    k_head_dim: int = 128
    k_experts: int = 8
    k_capacity: int = 512
    # train: None = the bench shape of models/bench_step.py
    train_card: dict | None = None
    train_batch: int = 2
    train_k: int = 4
    lr: float = 1.0
    ref_card: dict = dataclasses.field(default_factory=lambda: dict(
        embed_dim=256, num_heads=2, num_kv_heads=1, ff_dim=512,
        seq_len=256, num_decoder_blocks=2, vocab_size=512))
    ref_batch: int = 2
    ref_k: int = 3
    # proxies (cli.main): the stat files and scales
    dp_model: str = "gpt2_xl_16_bfloat16"
    fsdp_model: str = "gpt2_l_16_bfloat16"
    size_scale: float = 1.0
    time_scale: float = 1.0
    # serve: llama3_8b widths, depth cut
    s_card: str = "llama3_8b"
    s_layers: int = 16
    s_var_layers: int = 2
    s_shape: dict | None = None       # overrides the card (tiny only)
    s_slots: int = 16
    s_page: int = 16
    s_max_seq: int = 1024
    s_chunk: int = 128
    s_requests: int = 32
    s_var_requests: int = 12
    s_prompt: tuple = (200, 600)
    s_output: tuple = (16, 48)
    s_rate: float = 16.0
    s_parity_requests: int = 6
    s_multi_n: int = 8
    # moe: mixtral_8x7b widths, depth cut
    m_card: str = "mixtral_8x7b"
    m_shape: dict | None = None
    m_layers: int = 1
    m_batch: int = 1
    m_seq: int = 2048
    m_k: int = 2
    m_requests: int = 8
    # hybrid: every kind of layer of models/hybrid.py once or twice
    h_shape: dict = dataclasses.field(default_factory=lambda: dict(
        embed_dim=256, num_heads=4, num_kv_heads=2, ff_dim=512,
        vocab_size=512, ssm_inner=512, ssm_state=16, ssm_conv=4,
        ssm_dt_rank=16, sliding_window=128))
    h_seq: int = 512
    h_k: int = 3
    h_lr: float = 0.1
    # latent_moe: the published head widths (192-lane scores beside
    # 128-lane values), half the experts held
    l_shape: dict = dataclasses.field(default_factory=lambda: dict(
        hidden_size=256, num_attention_heads=2, intermediate_size=512,
        moe_intermediate_size=128, kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, vocab_size=512))
    l_seq: int = 512
    # linear_moe: the published lanes a head (128 of the rule's keys and
    # values, 256 of the gated attention's with 64 rotated), one period
    # of two layers, half the experts held
    q_shape: dict = dataclasses.field(default_factory=lambda: dict(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        head_dim=256, intermediate_size=512, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=128,
        linear_value_head_dim=128, vocab_size=512))
    q_seq: int = 1024
    # conv_moe: the published 64 lanes a head, one dense conv layer and
    # the period [attention, conv, conv, conv], every expert held
    v_shape: dict = dataclasses.field(default_factory=lambda: dict(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=1,
        intermediate_size=512, moe_intermediate_size=128, vocab_size=512))
    v_seq: int = 1024
    # sparse_linear_ops: the two ops of the sparse-and-linear hybrid at
    # its cell's shapes (32 query heads over 2 of 128 lanes, 16384
    # tokens, the published selection); the sparse gradients against a
    # dense reference at a length one chip holds one group of
    o_seq: int = 16384
    o_heads: int = 32
    o_kv_heads: int = 2
    o_head_dim: int = 128
    o_sizes: tuple = (32, 16, 64, 64, 2048, 1, 8192)
    o_rule_seq: int = 1024
    o_grad_seq: int = 4096
    o_grad_sizes: tuple = (32, 16, 64, 16, 512, 1, 2048)
    o_rows: int = 256
    # swa_moe: the published 128 lanes a head in groups of seven, one
    # period [full, window, window, window], a quarter of the experts held
    w_shape: dict = dataclasses.field(default_factory=lambda: dict(
        hidden_size=256, num_attention_heads=7, num_key_value_heads=1,
        head_dim=128, sliding_window_size=256, moe_ffn_hidden_size=128,
        vocab_size=512))
    w_seq: int = 1024
    # headgate_moe: the published 128 lanes a head, window layers in
    # groups of nine and full layers in groups of six over one key/value
    # head, a leading dense layer and the period [window, window,
    # window, full], a quarter of the experts held
    g_shape: dict = dataclasses.field(default_factory=lambda: dict(
        hidden_size=256, num_attention_heads=6, num_key_value_heads=1,
        head_dim=128, sliding_window=256, intermediate_size=512,
        moe_intermediate_size=128, shared_expert_intermediate_size=128,
        vocab_size=512))
    g_window_heads: int = 9
    g_seq: int = 1024
    # four chips
    c4_fsdp_model: str = "llama3_8b_16_bfloat16"
    c4_fsdp_scale: float = 0.125
    c4_h3d_model: str = "llama3_8b_16_bfloat16"
    c4_h3d_scale: float = 0.125
    c4_time_scale: float = 0.02
    c4_layers: int = 1
    c4_batch: int = 4
    c4_seq: int = 2048
    c4_serve_layers: int = 2


FULL = Sizes()

_TINY_SHAPE = dict(embed_dim=64, num_heads=8, num_kv_heads=4, ff_dim=128,
                   vocab_size=256)
TINY = Sizes(
    o_seq=256, o_heads=8, o_kv_heads=2, o_head_dim=16,
    o_sizes=(8, 4, 16, 4, 32, 1, 64), o_rule_seq=96, o_grad_seq=128,
    o_grad_sizes=(8, 4, 16, 4, 32, 1, 64), o_rows=32,
    dtype="float32",
    k_tokens=128, k_embed=128, k_ff=256, k_seq=128, k_heads=4,
    k_kv_heads=2, k_head_dim=32, k_experts=4, k_capacity=32,
    train_card=dict(_TINY_SHAPE, seq_len=128, num_decoder_blocks=2),
    train_k=3,
    ref_card=dict(_TINY_SHAPE, seq_len=128, num_decoder_blocks=2),
    dp_model="gpt2_l_16_bfloat16", size_scale=1e-5, time_scale=1e-4,
    s_layers=2, s_var_layers=2, s_shape=_TINY_SHAPE, s_slots=4, s_page=8,
    s_max_seq=64, s_chunk=16, s_requests=6, s_var_requests=4,
    s_prompt=(10, 30), s_output=(3, 6), s_rate=200.0,
    s_parity_requests=3, s_multi_n=4,
    m_shape=dict(_TINY_SHAPE, num_experts=4, top_k=2),
    m_seq=128, m_requests=3,
    h_shape=dict(_TINY_SHAPE, ssm_inner=128, ssm_state=16, ssm_conv=4,
                 ssm_dt_rank=4, sliding_window=16),
    h_seq=128,
    l_shape=dict(hidden_size=64, num_attention_heads=4,
                 intermediate_size=128, moe_intermediate_size=32,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, vocab_size=256),
    l_seq=128,
    q_shape=dict(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=32, intermediate_size=128,
                 moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, linear_num_key_heads=2,
                 linear_num_value_heads=4, linear_key_head_dim=16,
                 linear_value_head_dim=16, vocab_size=256),
    q_seq=128,
    v_shape=dict(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=128,
                 moe_intermediate_size=32, vocab_size=256),
    v_seq=128,
    w_shape=dict(hidden_size=64, num_attention_heads=7,
                 num_key_value_heads=1, head_dim=16, sliding_window_size=32,
                 moe_ffn_hidden_size=32, vocab_size=256),
    w_seq=128,
    g_shape=dict(hidden_size=64, num_attention_heads=6,
                 num_key_value_heads=1, head_dim=16, sliding_window=32,
                 intermediate_size=128, moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, vocab_size=256),
    g_seq=128,
    c4_fsdp_scale=1e-5, c4_h3d_scale=1e-5, c4_time_scale=1e-4, c4_batch=4,
    c4_seq=128,
)


# ------------------------------------------------------- plain reference

def reference_losses(params, tokens, cfg, k: int, lr: float) -> list:
    """K steps of SGD on the next-token loss of the dense gated decoder
    (RMSNorm, RoPE, grouped-query causal attention, SwiGLU), written in
    plain float32 ``jax.numpy`` with no code of the package: what the
    train step's numbers are held against."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def norm(t, w):
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-6) * w

    def loss_fn(p):
        x = p["embed"][tokens[:, :-1]]
        b, s, d = x.shape
        inv = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2, dtype=f32) / dh))
        ang = jnp.arange(s, dtype=f32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rope(t):
            t1, t2 = t[..., :dh // 2], t[..., dh // 2:]
            return jnp.concatenate([t1 * cos - t2 * sin,
                                    t1 * sin + t2 * cos], -1)

        causal = jnp.tril(jnp.ones((s, s), bool))
        for li in range(cfg.num_layers):
            lp = {n: w[li] for n, w in p["layers"].items()}
            y = norm(x, lp["norm1"])
            q = rope((y @ lp["wq"]).reshape(b, s, h, dh))
            kk = rope((y @ lp["wk"]).reshape(b, s, hkv, dh))
            vv = (y @ lp["wv"]).reshape(b, s, hkv, dh)
            kk = jnp.repeat(kk, h // hkv, axis=2)
            vv = jnp.repeat(vv, h // hkv, axis=2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(f32(dh))
            sc = jnp.where(causal, sc, -jnp.inf)
            sc = jnp.exp(sc - jnp.max(sc, -1, keepdims=True))
            pr = sc / jnp.sum(sc, -1, keepdims=True)
            att = jnp.einsum("bhqk,bkhd->bqhd", pr, vv).reshape(b, s, d)
            x = x + att @ lp["wo"]
            y = norm(x, lp["norm2"])
            g = y @ lp["w_gate"]
            x = x + ((g / (1.0 + jnp.exp(-g))) * (y @ lp["w_up"])) \
                @ lp["w_down"]
        logits = norm(x, p["final_norm"]) @ p["head"]
        m = jnp.max(logits, -1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), -1))
        tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(lse - tgt)

    p = jax.tree.map(lambda a: a.astype(f32), params)
    step = jax.jit(jax.value_and_grad(loss_fn))
    out = []
    with jax.default_matmul_precision("highest"):
        for _ in range(k):
            loss, g = step(p)
            p = jax.tree.map(lambda a, b: a - lr * b, p, g)
            out.append(float(loss))
    return out


# --------------------------------------------------------------- helpers

def rel_err(got, ref) -> float:
    """||got - ref|| / ||ref|| in float32 — the comparison the kernel
    tests of this repo use."""
    import jax.numpy as jnp
    g, r = got.astype(jnp.float32), ref.astype(jnp.float32)
    return float(jnp.linalg.norm(g - r)
                 / jnp.maximum(jnp.linalg.norm(r), 1e-9))


def close(checks: dict, name: str, got, ref, tol: float) -> None:
    import jax.numpy as jnp
    require(bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))),
            f"{name}: non-finite values")
    require(got.shape == ref.shape, f"{name}: shape {got.shape} "
            f"!= reference {ref.shape}")
    err = rel_err(got, ref)
    checks[name] = {"rel_err": round(err, 6), "tol": tol}
    require(err <= tol, f"{name}: relative error {err:.4g} > {tol}")


def on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def card_from(shape: dict, name: str):
    from dlnetbench_tpu.core.model_card import ModelCard, MoEParams
    shape = dict(shape)
    moe = None
    if "num_experts" in shape:
        moe = MoEParams(shape.pop("num_experts"), shape.pop("top_k"))
    shape.setdefault("seq_len", 0)
    return ModelCard(name=name, gated_mlp=True, moe_params=moe, **shape)


def memory_now() -> dict:
    """HBM in use and the peak so far, where the backend reports them."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: round(stats[k] / 2**30, 2)
            for k in ("bytes_in_use", "peak_bytes_in_use") if k in stats}


def load_record(path: Path) -> tuple[dict, object]:
    """The last record of ``path``, and the DataFrame the analysis layer
    makes of it (the record must parse through it)."""
    from dlnetbench_tpu.metrics.parser import (load_records,
                                               records_to_dataframe)
    records = load_records(path)
    df = records_to_dataframe(records[-1:])
    require(len(df) > 0, f"{path.name}: record parsed to no rows")
    return records[-1], df


# --------------------------------------------------------- phase: kernels

def phase_kernels(sz: Sizes) -> dict:
    """Every Pallas kernel family once, against its XLA reference: the
    kernels compile for the chip in tests/test_chip_compile_*.py, and
    here they execute and agree."""
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu import ops
    from dlnetbench_tpu.ops import grouped_matmul as gm
    from dlnetbench_tpu.ops import quantized_matmul as qmm
    from dlnetbench_tpu.ops.attention_mask import MaskSpec
    from dlnetbench_tpu.ops.fp8 import fp8_dot
    from dlnetbench_tpu.ops.int8 import int8_dot
    from dlnetbench_tpu.serving.kv_cache import paged_attention_decode

    f32, bf16 = jnp.float32, jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(sz.seed), 40))
    checks: dict = {}

    def rnd(shape, scale=1.0, dtype=bf16):
        return (jax.random.normal(next(keys), shape, f32) * scale
                ).astype(dtype)

    # flash / splash attention, forward and backward, against the dense
    # XLA attention the dispatcher falls back to
    b, s, h, hkv, dh = 1, sz.k_seq, sz.k_heads, sz.k_kv_heads, sz.k_head_dim
    q, k, v = rnd((b, s, h, dh)), rnd((b, s, hkv, dh)), rnd((b, s, hkv, dh))
    wgt = rnd((b, s, h, dh), dtype=f32)
    for label, mask in (("flash", None),
                        ("splash_window", MaskSpec(window=s // 4)),
                        ("splash_segments",
                         MaskSpec(seg_avg=s // 4, seg_seed=sz.seed))):
        def run(impl, mask=mask):
            def f(q, k, v):
                out = ops.attention(q, k, v, causal=True, impl=impl,
                                    mask=mask)
                return jnp.sum(out.astype(f32) * wgt), out
            (_, out), grads = jax.jit(jax.value_and_grad(
                f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            return out, grads
        out_k, g_k = run("flash")
        out_x, g_x = run("xla")
        close(checks, f"{label}_fwd", out_k, out_x, 2e-2)
        for n, a, r in zip("qkv", g_k, g_x):
            close(checks, f"{label}_d{n}", a, r, 3e-2)

    # fused-quantization matmul (dynamic scale) against the composed dot
    t, d, f = sz.k_tokens, sz.k_embed, sz.k_ff
    x, w = rnd((t, d)), rnd((d, f), d ** -0.5)
    for fmt, ref_dot, tol in (("int8", int8_dot, 1e-3),
                              ("float8", fp8_dot, 1e-2)):
        y = jax.jit(lambda x, w, fmt=fmt: qmm.fused_dot(x, w, fmt))(x, w)
        close(checks, f"fused_matmul_{fmt}", y, jax.jit(ref_dot)(x, w), tol)

    # grouped (per-expert) matmul with counts, bf16 and fused int8
    e, c = sz.k_experts, sz.k_capacity
    counts = jnp.asarray([(c * (i + 1)) // e for i in range(e)], jnp.int32)
    live = (jnp.arange(c)[None, :] < counts[:, None])[..., None]
    xe = jnp.where(live, rnd((e, c, d)), 0).astype(bf16)
    we = rnd((e, d, f), d ** -0.5)
    ref = jnp.where(live, jnp.einsum("ecd,edf->ecf", xe.astype(f32),
                                     we.astype(f32)), 0.0)
    close(checks, "grouped_matmul_bf16",
          jax.jit(lambda x, w, n: gm.grouped_matmul(x, w, counts=n)
                  )(xe, we, counts), ref, 1e-2)
    wq, sw = gm.quantize_experts(we, "int8")
    sx = qmm.scale_from_amax(gm.expert_amax(xe), "int8")
    close(checks, "grouped_matmul_int8",
          jax.jit(lambda x, w, n, sx, sw: gm.grouped_matmul(
              x, w, counts=n, sx=sx, sw=sw, fmt="int8")
                  )(xe, wq, counts, sx, sw), ref, 3e-2)

    # paged decode attention at the serving layout: the quantized
    # kernel (int8, fp8) and jax's own kernel against the gather math
    bs, pages, page, pmax = 4, 32, sz.s_page, 8
    lengths = jnp.asarray([pmax * page * 5 // 16, pmax * page,
                           page, pmax * page * 9 // 16], jnp.int32)
    pidx = (jnp.arange(bs * pmax, dtype=jnp.int32).reshape(bs, pmax) * 7
            ) % pages
    qd = rnd((bs, h, dh), dh ** -0.5, f32)
    for fmt, qdt in (("int8", jnp.int8), ("float8", jnp.float8_e4m3fn)):
        kq = rnd((hkv, pages, page, dh), 40.0, f32).astype(qdt)
        vq = rnd((hkv, pages, page, dh), 40.0, f32).astype(qdt)
        ks = jnp.abs(rnd((hkv, pages), 0.02, f32)) + 1e-4
        vs = jnp.abs(rnd((hkv, pages), 0.02, f32)) + 1e-4
        ref = paged_attention_decode(qd, kq, vq, lengths, pidx, k_scale=ks,
                                     v_scale=vs, fmt=fmt, impl="gather")
        for ppcb in (1, 2, 8):
            got = paged_attention_decode(
                qd, kq, vq, lengths, pidx, k_scale=ks, v_scale=vs, fmt=fmt,
                impl="pallas", pages_per_compute_block=ppcb)
            close(checks, f"quant_paged_attention_{fmt}_ppcb{ppcb}", got,
                  ref, 2e-2)
    if on_tpu():   # jax's kernel has no interpret mode
        kp, vp = rnd((hkv, pages, page, dh), dtype=f32), \
            rnd((hkv, pages, page, dh), dtype=f32)
        ref = paged_attention_decode(qd, kp, vp, lengths, pidx,
                                     impl="gather")
        for ppcb in (1, 2, 4, 8):
            got = paged_attention_decode(qd, kp, vp, lengths, pidx,
                                         impl="pallas",
                                         pages_per_compute_block=ppcb)
            close(checks, f"paged_attention_ppcb{ppcb}", got, ref, 2e-2)
    else:
        checks["paged_attention"] = "not run: jax's kernel needs a TPU"
    return {"shapes": {"tokens": t, "embed": d, "ff": f, "seq": s,
                       "heads": [h, hkv, dh], "experts": [e, c]},
            "checks": checks}


# ----------------------------------------------------------- phase: train

def phase_train(sz: Sizes) -> dict:
    """The real-compute train step of models/bench_step.py through the
    AOT executor: first at a small size against the float32 reference,
    then at the bench shape for a few optimizer steps."""
    import jax
    import numpy as np

    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step

    def compile_step(fn, carry, tokens, opts=None):
        t0 = time.perf_counter()
        prog = executor.CompiledProgram(executor.Program(
            fn=fn, args=(carry, tokens),
            donate_argnums=bench_step.DONATE_ARGNUMS,
            compiler_options=opts))
        return prog, time.perf_counter() - t0

    # the same step, small, flash kernels forced, against plain float32
    fn, carry, tokens, _, cfg = bench_step.build(
        sz.ref_k, card=card_from(sz.ref_card, "smoke_ref"),
        batch=sz.ref_batch, lr=sz.lr, attention_impl="flash")
    want = reference_losses(carry, tokens, cfg, sz.ref_k, sz.lr)
    prog, _ = compile_step(fn, carry, tokens)
    got = [float(v) for v in prog()[1]]
    tol_first, tol_drop = 0.02, 0.35
    require(abs(got[0] - want[0]) <= tol_first * want[0],
            f"small step: first loss {got[0]} vs float32 reference "
            f"{want[0]} (tolerance {tol_first} relative)")
    drop_got, drop_want = got[0] - got[-1], want[0] - want[-1]
    require(drop_want > 0 and abs(drop_got - drop_want)
            <= tol_drop * drop_want,
            f"small step: loss fell {drop_got} over {sz.ref_k} steps, "
            f"reference {drop_want} (tolerance {tol_drop} relative)")
    del prog, carry
    gc.collect()

    # the bench shape
    card = card_from(sz.train_card, "smoke_train") if sz.train_card else None
    fn, carry, tokens, card, cfg = bench_step.build(
        sz.train_k, card=card, batch=sz.train_batch, lr=sz.lr)
    opts = ({"xla_tpu_scoped_vmem_limit_kib": "32768"} if on_tpu()
            else None)   # bench.py's per-compile option
    prog, compile_s = compile_step(fn, carry, tokens, opts)
    del carry
    kernels = prog.as_text().count("tpu_custom_call")
    if on_tpu():
        # impl="auto" falls back to dense XLA attention in silence
        # where the shape does not qualify: the flash kernels (one
        # forward, two backward, per layer) must be in what compiled
        require(kernels >= 3,
                f"compiled train step holds {kernels} tpu_custom_call: "
                f"the flash kernels are not in it")
    t0 = time.perf_counter()
    losses = np.asarray(jax.block_until_ready(prog()[1]), np.float32)
    run_s = time.perf_counter() - t0
    require(bool(np.all(np.isfinite(losses))), f"losses {losses}")
    require(bool(np.all(np.diff(losses) < 0)),
            f"loss does not fall over {sz.train_k} steps: {losses}")
    full = bench_step.bench_card()
    return {
        "shapes": {"batch": sz.train_batch, "seq": cfg.seq_len,
                   "embed": cfg.embed_dim,
                   "heads": [cfg.num_heads, cfg.num_kv_heads],
                   "ff": cfg.ff_dim, "vocab": cfg.vocab_size,
                   "layers": cfg.num_layers, "steps": sz.train_k,
                   "lr": sz.lr},
        "cut": (f"llama3_8b widths; depth 32 -> {cfg.num_layers} and "
                f"vocab 128256 -> {cfg.vocab_size} (the BENCH_r05 shape: "
                f"what fits 16 GB without remat)"
                if card.embed_dim == full.embed_dim else "tiny rehearsal"),
        "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
        "tpu_custom_calls": kernels,
        "memory_analysis": prog.memory_analysis,
        "losses": [round(float(v), 4) for v in losses],
        "reference": {"small_losses": [round(v, 4) for v in got],
                      "float32_losses": [round(v, 4) for v in want],
                      "tol_first_rel": tol_first, "tol_drop_rel": tol_drop},
        "checks": "small step within tolerance of the float32 reference; "
                  "losses finite and falling; flash kernels compiled in",
    }


# ----------------------------------------------------------- phase: proxy

def run_cli(argv: list[str], out: Path) -> dict:
    """One ``python -m dlnetbench_tpu.cli`` invocation in this process;
    returns its record (parsed through the analysis layer's parser)."""
    from dlnetbench_tpu import cli
    out.unlink(missing_ok=True)
    t0 = time.perf_counter()
    rc = cli.main([*argv, "--no_topology", "--out", str(out)])
    require(rc == 0, f"cli {argv[0]} returned {rc}")
    rec, df = load_record(out)
    rec["_wall_s"] = time.perf_counter() - t0
    rec["_columns"] = len(df.columns)
    return rec


def timer_median_us(rec: dict, name: str) -> float:
    return float(rec["ranks"][0]["summary"][name]["value"])


def burn_check(rec: dict, requested_us: float) -> dict:
    """Requested compute time (the stat file's, what the reference
    sleeps) against what the calibrated burn chain measured: the
    compute-only variant runs the burns and nothing else."""
    measured = timer_median_us(rec, "compute_time")
    ratio = measured / requested_us
    # below 10 ms the reading is dispatch, not burn (the tiny rehearsal)
    require(requested_us < 1e4 or 0.5 <= ratio <= 2.0,
            f"burn calibration: requested {requested_us:.0f} us, "
            f"measured {measured:.0f} us (ratio {ratio:.2f})")
    return {"requested_us": round(requested_us, 1),
            "measured_us": round(measured, 1), "ratio": round(ratio, 3),
            "ns_per_iter": rec["global"]["burn_ns_per_iter"]}


def phase_proxy(sz: Sizes) -> dict:
    """``cli dp`` and ``cli fsdp`` on the largest committed stat files
    whose buffers fit one chip at the scales given."""
    common = ["--size_scale", str(sz.size_scale), "--time_scale",
              str(sz.time_scale), "--buffer_dtype", "stats", "-w", "1",
              "-r", "3"]
    dp = run_cli(["dp", "--model", sz.dp_model, "--num_buckets", "8",
                  *common], OUT_DIR / "proxy_dp.jsonl")
    g = dp["global"]
    dp_burn = burn_check(dp, g["fwd_us"] + 8 * g["bwd_us_per_bucket"])
    gc.collect()
    fsdp = run_cli(["fsdp", "--model", sz.fsdp_model, "--num_units", "8",
                    *common], OUT_DIR / "proxy_fsdp.jsonl")
    g2 = fsdp["global"]
    fsdp_burn = burn_check(
        fsdp, 8 * (g2["fwd_us_per_unit"] + g2["bwd_us_per_unit"]))
    return {
        "shapes": {"dp": {"model": sz.dp_model, "buckets": 8,
                          "buffer_bytes": sum(g["bucket_bytes"])},
                   "fsdp": {"model": sz.fsdp_model, "units": 8,
                            "buffer_bytes": 8 * g2["shard_bytes"]},
                   "size_scale": sz.size_scale,
                   "time_scale": sz.time_scale},
        "cut": ("of the committed stat files only gpt2_xl (dp: four "
                "copies of 3.1 GB — the buffers and one donated clone "
                "for each of full/compute/comm) and gpt2_l (fsdp: "
                "shards, gathered units and three clones) fit 16 GB at "
                "size_scale 1; llama3_8b's gradients alone are 16 GB"),
        "compile_s": round(sum(
            sum(r["global"]["compile_ms"].values())
            for r in (dp, fsdp)) / 1e3, 1),
        "run_s": round(dp["_wall_s"] + fsdp["_wall_s"], 1),
        "dp": {"runtime_us": timer_median_us(dp, "runtimes"),
               "burn": dp_burn, "host_rtt_us": g["host_rtt_us"]},
        "fsdp": {"runtime_us": timer_median_us(fsdp, "runtimes"),
                 "burn": fsdp_burn},
        "checks": "both records parse through records_to_dataframe; "
                  "measured burn within [0.5, 2.0] of requested",
    }


# ----------------------------------------------------------- phase: serve

def model_config(card_name: str, shape: dict | None, layers: int,
                 max_seq: int, dtype: str):
    from dlnetbench_tpu.core.model_card import load_model_card
    from dlnetbench_tpu.models.transformer import TransformerConfig
    card = card_from(shape, card_name) if shape else \
        load_model_card(card_name)
    return dataclasses.replace(
        TransformerConfig.from_card(card, seq_len=max_seq,
                                    num_layers=layers),
        dtype=dtype, moe_capacity_factor=1.0), card


def serving_config(sz: Sizes, **over):
    from dlnetbench_tpu.serving.scheduler import ServingConfig
    return ServingConfig(
        slots=sz.s_slots, page_size=sz.s_page,
        num_pages=sz.s_slots * (sz.s_max_seq // sz.s_page),
        max_seq_len=sz.s_max_seq, prefill_chunk=sz.s_chunk,
        slo_ttft_ms=5000.0, slo_tpot_ms=500.0, **over)


def arrival_plan(sz: Sizes, n: int):
    from dlnetbench_tpu.serving.arrivals import ArrivalPlan
    return ArrivalPlan(kind="poisson", rate_rps=sz.s_rate, num_requests=n,
                       seed=sz.seed, prompt_len=list(sz.s_prompt),
                       output_len=list(sz.s_output))


def serve_once(model_cfg, cfg, plan, params, out: Path) -> dict:
    """``cli serve``'s run: ``run_serving`` then the record emitter."""
    from dlnetbench_tpu.metrics.emit import emit_result
    from dlnetbench_tpu.serving.scheduler import run_serving
    out.unlink(missing_ok=True)
    t0 = time.perf_counter()
    result = run_serving(model_cfg, cfg, plan, params=params)
    emit_result(result, path=str(out))
    rec, _ = load_record(out)
    srv = rec["global"]["serving"]
    require(srv["completed"] == plan.num_requests,
            f"{srv['completed']} of {plan.num_requests} requests completed")
    for name in ("ttft_ms", "tpot_ms", "e2e_ms"):
        p99 = srv[name]["p99"]
        require(p99 is not None and p99 == p99 and p99 > 0,
                f"serving {name} p99 is {p99}")
    return {"wall_s": round(time.perf_counter() - t0, 1),
            "compile_ms": rec["global"]["compile_ms"],
            "completed": srv["completed"],
            "tokens_per_s": srv.get("tokens_per_s"),
            "ttft_p99_ms": srv["ttft_ms"]["p99"],
            "tpot_p99_ms": srv["tpot_ms"]["p99"]}


def engine_streams(model_cfg, cfg, requests, params):
    """Token streams of ``requests`` through one engine (the class
    ``run_serving`` drives), and how many kernels its decode program
    holds: on a TPU every attention path but ``gather`` must hold one."""
    from dlnetbench_tpu.serving.scheduler import Engine
    eng = Engine(model_cfg, cfg, params=params)
    kernels = eng.decode_program.as_text().count("tpu_custom_call")
    if cfg.attn_impl != "gather" and on_tpu():
        require(kernels >= 1, f"decode program (attn_impl="
                f"{cfg.attn_impl!r}, cache {cfg.cache_dtype}) holds no "
                f"tpu_custom_call: the paged kernel is not in it")
    done, _ = eng.run(requests)
    require(len(done) == len(requests), "parity run left requests behind")
    return {rid: list(t) for rid, t in eng.token_streams.items()}, kernels


def near_tie_parity(model_cfg, params, requests, got: dict, ref: dict,
                    tie_tol: float) -> dict:
    """Greedy tokens of the kernel path against the gather path, to
    kernel tolerance: streams are equal, or at the first difference both
    tokens lie within ``tie_tol`` of the top logit of the full forward
    (``models/transformer.forward`` on prompt + reference stream) — a
    near tie, which the kernels' stated tolerance may flip.  What
    follows a flip has another context and is not compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlnetbench_tpu.models import transformer as tfm
    from dlnetbench_tpu.serving.decode import prompt_tokens_for
    fwd = None
    equal = flips = compared = 0
    worst = 0.0
    for req in requests:
        a, b = got[req.rid], ref[req.rid]
        require(len(a) == len(b) == req.output_len,
                f"request {req.rid}: stream lengths {len(a)}/{len(b)}, "
                f"asked {req.output_len}")
        diff = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                    None)
        compared += len(a) if diff is None else diff + 1
        if diff is None:
            equal += 1
            continue
        flips += 1
        if fwd is None:
            fwd = jax.jit(lambda p, t: tfm.forward(p, t, model_cfg))
        ctx = np.zeros((1, model_cfg.seq_len), np.int32)
        prompt = np.asarray(prompt_tokens_for(req, model_cfg.vocab_size))
        n = req.prompt_len + diff
        ctx[0, :req.prompt_len] = prompt
        ctx[0, req.prompt_len:n] = b[:diff]
        logits = np.asarray(fwd(params, jnp.asarray(ctx))[0, n - 1],
                            np.float32)
        gap = float(logits.max() - min(logits[a[diff]], logits[b[diff]]))
        worst = max(worst, gap)
        require(gap <= tie_tol,
                f"request {req.rid} token {diff}: {a[diff]} vs "
                f"{b[diff]} differ by {gap:.3f} in logit from the top "
                f"(near-tie tolerance {tie_tol})")
    return {"requests": len(requests), "equal_streams": equal,
            "near_tie_flips": flips, "tokens_compared": compared,
            "worst_gap": round(worst, 4), "tie_tol": tie_tol}


def serve_variant(sz: Sizes, label: str, model_cfg, params, n_req: int,
                  ref_over: dict, tie_tol: float | None, **over) -> dict:
    """One serving configuration: the whole plan through ``run_serving``,
    then its first requests through the same engine and through the
    reference engine (``ref_over`` on top of ``over``).  ``tie_tol``
    None wants identical streams, a number allows near ties."""
    plan = arrival_plan(sz, n_req)
    cfg = serving_config(sz, **over)
    res = serve_once(model_cfg, cfg, plan, params,
                     OUT_DIR / f"serve_{label}.jsonl")
    gc.collect()
    reqs = plan.sample()[:sz.s_parity_requests]
    got, kernels = engine_streams(model_cfg, cfg, reqs, params)
    gc.collect()
    ref, _ = engine_streams(model_cfg,
                            serving_config(sz, **{**over, **ref_over}),
                            reqs, params)
    gc.collect()
    if tie_tol is None:
        require(got == ref, f"{label}: streams differ from the "
                f"reference engine's ({ref_over})")
        parity = "token-identical"
    else:
        parity = near_tie_parity(model_cfg, params, reqs, got, ref, tie_tol)
    res.update(layers=model_cfg.num_layers, tpu_custom_calls=kernels,
               reference=ref_over, parity=parity)
    return res


def phase_serve(sz: Sizes) -> dict:
    """``cli serve`` semantics at llama3_8b widths: bf16 with the Pallas
    paged-attention path, then the int8 cache and the fused multi-step
    loop (separate compiled programs) at a smaller depth."""
    import jax

    from dlnetbench_tpu.models.transformer import init_params
    init = jax.jit(init_params, static_argnums=1)
    gather = {"attn_impl": "gather"}

    # bf16, attn_impl="auto": jax's Pallas paged attention against the
    # gather math
    model_cfg, card = model_config(sz.s_card, sz.s_shape, sz.s_layers,
                                   sz.s_max_seq, sz.dtype)
    params = init(jax.random.key(sz.seed), model_cfg)
    out = {"bf16": serve_variant(sz, "bf16", model_cfg, params,
                                 sz.s_requests, gather, 0.05)}
    del params
    gc.collect()

    model_cfg, _ = model_config(sz.s_card, sz.s_shape, sz.s_var_layers,
                                sz.s_max_seq, sz.dtype)
    params = init(jax.random.key(sz.seed), model_cfg)
    # the int8 cache: quant_paged_attention against the dequantizing
    # gather; the near-tie bar is wider by the cache's stated tolerance
    out["int8"] = serve_variant(sz, "int8", model_cfg, params,
                                sz.s_var_requests, gather, 0.3,
                                cache_dtype="int8")
    # the fused multi-step loop: token-identical to the 1-step engine
    # on the same kernel path, by construction
    out["multi_step"] = serve_variant(
        sz, "multi_step", model_cfg, params, sz.s_var_requests,
        {"multi_step_n": 1}, None, multi_step_n=sz.s_multi_n)

    pool = serving_config(sz)
    return {
        "shapes": {"embed": model_cfg.embed_dim,
                   "heads": [model_cfg.num_heads, model_cfg.num_kv_heads],
                   "ff": model_cfg.ff_dim, "vocab": model_cfg.vocab_size,
                   "dtype": sz.dtype, "slots": pool.slots,
                   "page_size": pool.page_size, "num_pages": pool.num_pages,
                   "max_seq_len": pool.max_seq_len,
                   "prefill_chunk": pool.prefill_chunk,
                   "requests": sz.s_requests, "prompt_len": sz.s_prompt,
                   "output_len": sz.s_output},
        "cut": (f"{sz.s_card} widths; depth {card.num_layers or 'n/a'} -> "
                f"{sz.s_layers} (bf16): a layer is 0.41 GiB of weights, "
                f"0.06 of pages and 0.17 of temporaries in the gather "
                f"engine it is compared with, beside 2 GiB of embedding "
                f"and head — 12.1 GiB by the compiler's count, and 20 "
                f"layers reach 14.7 of 15.75; -> {sz.s_var_layers} for "
                f"the two variants, cut by this run's time limit"),
        **out,
        "checks": "all requests complete with finite latencies; records "
                  "parse; decode programs hold the paged kernels; greedy "
                  "tokens equal the gather path's up to near ties; "
                  "multi-step equals 1-step",
    }


# ------------------------------------------------------------- phase: moe

def phase_moe(sz: Sizes) -> dict:
    """The 8-expert top-2 twin at mixtral_8x7b widths: a train step
    through the grouped Pallas kernels (ops/grouped_matmul.py) and MoE
    decode through the serving engine."""
    import jax
    import numpy as np

    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.core.model_card import load_model_card
    from dlnetbench_tpu.models import bench_step
    from dlnetbench_tpu.models.transformer import init_params

    def step_losses(card, batch, k, **over):
        fn, carry, tokens, _, cfg = bench_step.build(
            k, card=card, batch=batch, lr=sz.lr, **over)
        t0 = time.perf_counter()
        prog = executor.CompiledProgram(executor.Program(
            fn=fn, args=(carry, tokens),
            donate_argnums=bench_step.DONATE_ARGNUMS))
        compile_s = time.perf_counter() - t0
        del carry
        t0 = time.perf_counter()
        losses = np.asarray(jax.block_until_ready(prog()[1]), np.float32)
        return (losses, cfg, compile_s, time.perf_counter() - t0,
                prog.as_text().count("tpu_custom_call"))

    # grouped kernels against the XLA einsum dispatch, small
    small = card_from(dict(sz.ref_card, num_experts=4, top_k=2),
                      "smoke_moe_ref")
    l_grp = step_losses(small, sz.ref_batch, 1, moe_impl="grouped",
                        attention_impl="flash")[0]
    l_xla = step_losses(small, sz.ref_batch, 1, moe_impl="sparse",
                        attention_impl="flash")[0]
    tol = 0.01
    require(abs(float(l_grp[0]) - float(l_xla[0]))
            <= tol * float(l_xla[0]),
            f"small MoE step: grouped loss {l_grp[0]} vs einsum "
            f"{l_xla[0]} (tolerance {tol} relative)")
    gc.collect()

    base = card_from(sz.m_shape, "smoke_moe") if sz.m_shape else \
        load_model_card(sz.m_card)
    card = dataclasses.replace(base, seq_len=sz.m_seq,
                               num_decoder_blocks=sz.m_layers)
    losses, cfg, compile_s, run_s, kernels = step_losses(
        card, sz.m_batch, sz.m_k, moe_impl="grouped")
    require(bool(np.all(np.isfinite(losses))), f"losses {losses}")
    require(bool(np.all(np.diff(losses) < 0)),
            f"loss does not fall: {losses}")
    if on_tpu():
        require(kernels >= 3, f"compiled MoE step holds {kernels} "
                f"tpu_custom_call: the grouped kernels are not in it")
    gc.collect()

    # MoE decode through the engine
    model_cfg, _ = model_config(sz.m_card, sz.m_shape, sz.m_layers,
                                sz.s_max_seq, sz.dtype)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.key(sz.seed), model_cfg)
    decode = serve_once(model_cfg, serving_config(sz),
                        arrival_plan(sz, sz.m_requests), params,
                        OUT_DIR / "serve_moe.jsonl")
    return {
        "shapes": {"embed": cfg.embed_dim,
                   "heads": [cfg.num_heads, cfg.num_kv_heads],
                   "ff": cfg.ff_dim, "vocab": cfg.vocab_size,
                   "experts": [cfg.num_experts, cfg.top_k],
                   "layers": cfg.num_layers, "batch": sz.m_batch,
                   "seq": cfg.seq_len, "steps": sz.m_k},
        "cut": (f"{sz.m_card} widths; depth {base.num_layers} -> "
                f"{sz.m_layers}: one layer's eight experts are 2.6 GiB "
                f"in bf16; the step holds them, their update and the "
                f"float32 expert backward, and while it compiles the "
                f"caller's copy sits beside the executor's donated one"),
        "compile_s": round(compile_s, 1), "run_s": round(run_s, 2),
        "tpu_custom_calls": kernels,
        "losses": [round(float(v), 4) for v in losses],
        "small_grouped_vs_einsum": [float(l_grp[0]), float(l_xla[0]), tol],
        "decode": decode,
        "checks": "grouped kernels agree with the einsum dispatch (small); "
                  "losses finite and falling; MoE decode completes",
    }


# ---------------------------------------------------------- phase: hybrid

HYBRID_KINDS = ("mamba", "window", "mamba", "window", "mamba", "full",
                "gmu", "cross", "gmu", "cross")


def phase_hybrid(sz: Sizes) -> dict:
    """The hybrid decoder (models/hybrid.py: Mamba, window, full and
    cross differential attention, gated memory units) through the same
    step builder and executor as ``phase_train``, with the scan and
    attention kernels forced, against the benchmark's plain float32
    reference of the same model on the same seeded weights."""
    import jax

    from benchmarks import reference_hybrid, weights_hybrid
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.core.model_card import ModelCard
    from dlnetbench_tpu.models import bench_step, hybrid

    shape = dict(sz.h_shape)
    card = ModelCard(name="smoke_hybrid", seq_len=sz.h_seq,
                     num_decoder_blocks=len(HYBRID_KINDS), gated_mlp=True,
                     tied_embeddings=True, layer_kinds=HYBRID_KINDS,
                     differential_attention=True, **shape)
    cfg = hybrid.HybridConfig.from_card(
        card, dtype=sz.dtype, remat=True, attention_impl="flash",
        scan_impl="pallas", loss_row_block=sz.h_seq // 2)
    arch = weights_hybrid.arch_of({
        "num_attention_heads": card.num_heads,
        "num_key_value_heads": card.kv_heads,
        "hidden_size": card.embed_dim, "intermediate_size": card.ff_dim,
        "vocab_size": card.vocab_size, "layer_kinds": HYBRID_KINDS,
        "num_hidden_layers": len(HYBRID_KINDS),
        "sliding_window": card.sliding_window, "layer_norm_eps": 1e-5,
        "torch_dtype": sz.dtype,
        "assumed": {k: shape[k] for k in ("ssm_inner", "ssm_state",
                                          "ssm_conv", "ssm_dt_rank")}})

    def make_params():
        return weights_hybrid.make_params(arch, sz.seed)
    tokens = weights_hybrid.make_token_pool(
        sz.seed, 1, 2, sz.h_seq + 1, card.vocab_size)[0]
    want = reference_hybrid.sgd_steps(
        make_params, [tokens] * sz.h_k, arch, sz.h_lr)["losses"]
    prog = executor.CompiledProgram(executor.Program(
        fn=bench_step.make_train_k(cfg, sz.h_k, sz.h_lr),
        args=(make_params(), tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS))
    kernels = prog.as_text().count("tpu_custom_call")
    if on_tpu():
        # two scan kernels a mamba layer and three attention kernels a
        # call, each at least once
        require(kernels >= 5, f"compiled hybrid step holds {kernels} "
                              f"tpu_custom_call")
    got = [float(v) for v in jax.block_until_ready(prog()[1])]
    tol_first, tol_drop = 0.02, 0.35
    require(abs(got[0] - want[0]) <= tol_first * want[0],
            f"hybrid step: first loss {got[0]} vs float32 reference "
            f"{want[0]} (tolerance {tol_first} relative)")
    drop_got, drop_want = got[0] - got[-1], want[0] - want[-1]
    require(drop_want > 0 and abs(drop_got - drop_want)
            <= tol_drop * drop_want,
            f"hybrid step: loss fell {drop_got} over {sz.h_k} steps, "
            f"reference {drop_want} (tolerance {tol_drop} relative)")
    return {"shapes": {**shape, "seq": sz.h_seq, "batch": 2,
                       "layer_kinds": list(HYBRID_KINDS),
                       "steps": sz.h_k, "lr": sz.h_lr},
            "tpu_custom_calls": kernels,
            "memory_analysis": prog.memory_analysis,
            "losses": [round(v, 4) for v in got],
            "float32_losses": [round(v, 4) for v in want],
            "checks": "first loss and the loss's fall within tolerance "
                      "of benchmarks/reference_hybrid.py; scan and "
                      "attention kernels compiled in"}


def expert_step_checks(what: str, prog, want: dict, steps: int):
    """Run an expert decoder's chained train steps once and hold them to
    the float32 reference's: no row past the bound, the first loss, the
    loss's fall over ``steps`` and the first step's selections within
    tolerance.  Returns (losses, routing, the selections' gap)."""
    import jax

    from benchmarks.runners import train_latent_moe
    _, (losses, routing) = jax.block_until_ready(prog())
    got = [float(v) for v in losses]
    require(int(routing["past_bound"].sum()) == 0
            and int(routing["routed"][0]) > 0,
            f"{what} step: routing {routing}")
    gap = train_latent_moe.selection_gap(
        jax.device_get(routing["choices"][0]), want["chosen"])
    tol_first, tol_drop, tol_gap = 0.02, 0.35, 0.05
    require(abs(got[0] - want["losses"][0])
            <= tol_first * want["losses"][0],
            f"{what} step: first loss {got[0]} vs float32 "
            f"reference {want['losses'][0]} (tolerance {tol_first})")
    drop_got = got[0] - got[-1]
    drop_want = want["losses"][0] - want["losses"][-1]
    require(drop_want > 0 and abs(drop_got - drop_want)
            <= tol_drop * drop_want,
            f"{what} step: loss fell {drop_got} over "
            f"{steps} steps, reference {drop_want} (tolerance "
            f"{tol_drop} relative)")
    require(gap <= tol_gap, f"{what} step: {gap} of the "
                            f"(token, choice) pairs differ from the "
                            f"reference's (tolerance {tol_gap})")
    return got, routing, gap


# ------------------------------------------------------ phase: latent_moe

def phase_latent_moe(sz: Sizes) -> dict:
    """The latent-attention expert decoder (models/hybrid.py: ``mla``
    layers, a dense layer and then routed experts beside a shared one,
    4 of the router's 8 experts held) through the same step builder and
    executor as ``phase_train``, with the attention kernels forced at
    their two widths, against the benchmark's plain float32 reference of
    the same model and the same share on the same seeded weights."""
    import jax

    from benchmarks import reference_latent_moe, weights_latent_moe
    from benchmarks.runners import train_latent_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step

    config = {
        **sz.l_shape, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 4, "published": {"n_routed_experts": 8},
        "num_experts_per_tok": 3, "n_shared_experts": 2,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
        "rope_theta": 800000, "q_lora_rank": None, "n_group": 1,
        "topk_group": 1, "rope_scaling": None, "moe_layer_freq": 1,
        "scoring_func": "sigmoid", "norm_topk_prob": True,
        "tie_word_embeddings": False, "attention_bias": False,
        "torch_dtype": sz.dtype,
        "assumed": {"first_held_expert": 2, "router_bias_scale": 0.1}}
    arch = weights_latent_moe.arch_of(config)
    slots = 2 * sz.l_seq        # every row of the batch: no bound to reach
    cfg = train_latent_moe.config_of(
        arch, sz.l_seq, slots, remat=True, attention_impl="flash",
        loss_row_block=sz.l_seq)

    def make_params():
        return weights_latent_moe.make_params(arch, sz.seed)
    tokens = weights_latent_moe.make_token_pool(
        sz.seed, 1, 2, sz.l_seq + 1, arch["vocab_size"])[0]
    want = reference_latent_moe.sgd_steps(
        make_params, [tokens] * sz.h_k, arch, sz.h_lr)
    prog = executor.CompiledProgram(executor.Program(
        fn=bench_step.make_train_k(cfg, sz.h_k, sz.h_lr),
        args=(make_params(), tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS))
    kernels = prog.as_text().count("tpu_custom_call")
    if on_tpu():
        # three attention kernels a layer, three grouped matmuls an
        # expert layer, each at least once
        require(kernels >= 6, f"compiled latent-attention step holds "
                              f"{kernels} tpu_custom_call")
    got, routing, gap = expert_step_checks("latent-attention", prog, want, sz.h_k)
    return {"shapes": {**sz.l_shape, "seq": sz.l_seq, "batch": 2,
                       "experts": 8, "held": [2, 4], "top_k": 3,
                       "slots": slots, "steps": sz.h_k, "lr": sz.h_lr},
            "tpu_custom_calls": kernels,
            "memory_analysis": prog.memory_analysis,
            "losses": [round(v, 4) for v in got],
            "float32_losses": [round(v, 4) for v in want["losses"]],
            "rows_routed_to_held": int(routing["routed"][0]),
            "selection_gap": gap,
            "checks": "first loss, the loss's fall and the selections "
                      "within tolerance of "
                      "benchmarks/reference_latent_moe.py; no row past "
                      "the bound; attention and expert kernels compiled "
                      "in"}


# ------------------------------------------------------ phase: linear_moe

def phase_linear_moe(sz: Sizes) -> dict:
    """The linear-attention expert decoder (models/hybrid.py: a ``gdn``
    and a ``gated`` layer, routed experts beside a gated shared one, 4
    of the router's 8 experts held) through the same step builder and
    executor as ``phase_train``, the rule's Pallas sweep and the
    attention kernels forced, against the benchmark's plain float32
    reference (the token recurrence) on the same seeded weights."""
    import jax

    from benchmarks import reference_linear_moe, weights_linear_moe
    from benchmarks.runners import train_linear_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step

    config = {
        **sz.q_shape, "num_hidden_layers": 2, "full_attention_interval": 2,
        "num_experts": 4, "published": {"num_experts": 8},
        "num_experts_per_tok": 3, "linear_conv_kernel_dim": 4,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "rms_norm_eps": 1e-6, "mlp_only_layers": [],
        "decoder_sparse_step": 1, "norm_topk_prob": True,
        "rope_scaling": None, "use_sliding_window": False,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "torch_dtype": sz.dtype,
        "assumed": {"first_held_expert": 2, "decay_max": 16.0}}
    arch = weights_linear_moe.arch_of(config)
    slots = 2 * sz.q_seq        # every row of the batch: no bound to reach
    cfg = train_linear_moe.config_of(
        arch, sz.q_seq, slots, remat=True, attention_impl="flash",
        rule_impl="pallas", loss_row_block=sz.q_seq)

    def make_params():
        return weights_linear_moe.make_params(arch, sz.seed)
    tokens = weights_linear_moe.make_token_pool(
        sz.seed, 1, 2, sz.q_seq + 1, arch["vocab_size"])[0]
    want = reference_linear_moe.sgd_steps(
        make_params, [tokens] * sz.h_k, arch, sz.h_lr)
    prog = executor.CompiledProgram(executor.Program(
        fn=bench_step.make_train_k(cfg, sz.h_k, sz.h_lr),
        args=(make_params(), tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS))
    text = prog.as_text()
    kernels = text.count("tpu_custom_call")
    if on_tpu():
        # the rule's two sweeps, three attention kernels, three grouped
        # matmuls a layer, each at least once
        require(kernels >= 8 and "gdr_fwd" in text and "gdr_bwd" in text,
                f"compiled linear-attention step holds {kernels} "
                f"tpu_custom_call")
    got, routing, gap = expert_step_checks("linear-attention", prog, want, sz.h_k)
    return {"shapes": {**sz.q_shape, "seq": sz.q_seq, "batch": 2,
                       "layers": list(arch["layer_kinds"]), "experts": 8,
                       "held": [2, 4], "top_k": 3, "slots": slots,
                       "steps": sz.h_k, "lr": sz.h_lr},
            "tpu_custom_calls": kernels,
            "memory_analysis": prog.memory_analysis,
            "losses": [round(v, 4) for v in got],
            "float32_losses": [round(v, 4) for v in want["losses"]],
            "rows_routed_to_held": int(routing["routed"][0]),
            "selection_gap": gap,
            "checks": "first loss, the loss's fall and the selections "
                      "within tolerance of "
                      "benchmarks/reference_linear_moe.py (the token "
                      "recurrence); no row past the bound; the rule's, "
                      "the attention's and the experts' kernels "
                      "compiled in"}


# -------------------------------------------------------- phase: conv_moe

def phase_conv_moe(sz: Sizes) -> dict:
    """The short-convolution expert decoder (models/hybrid.py: ``conv``
    layers and a ``gated`` layer without a gate, a leading dense layer
    and then all 8 of the router's experts held, the head tied) through
    the same step builder and executor as ``phase_train``, the attention
    kernels forced at 64 lanes, against the benchmark's plain float32
    reference on the same seeded weights."""
    from benchmarks import reference_conv_moe, weights_conv_moe
    from benchmarks.runners import train_conv_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step

    kinds = ["conv", "full_attention", "conv", "conv", "conv"]
    config = {
        **sz.v_shape, "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": kinds, "num_experts": 8, "num_experts_per_tok": 4,
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1, "rope_theta": 1000000,
        "torch_dtype": sz.dtype,
        "assumed": {"first_held_expert": 0, "router_bias_scale": 0.01}}
    arch = weights_conv_moe.arch_of(config)
    slots = 2 * sz.v_seq        # every row of the batch: no bound to reach
    cfg = train_conv_moe.config_of(
        arch, sz.v_seq, slots, remat=True, attention_impl="flash",
        loss_row_block=sz.v_seq)

    def make_params():
        return weights_conv_moe.make_params(arch, sz.seed)
    tokens = weights_conv_moe.make_token_pool(
        sz.seed, 1, 2, sz.v_seq + 1, arch["vocab_size"])[0]
    want = reference_conv_moe.sgd_steps(
        make_params, [tokens] * sz.h_k, arch, sz.h_lr)
    prog = executor.CompiledProgram(executor.Program(
        fn=bench_step.make_train_k(cfg, sz.h_k, sz.h_lr),
        args=(make_params(), tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS))
    kernels = prog.as_text().count("tpu_custom_call")
    if on_tpu():
        # three attention kernels, three grouped matmuls an expert
        # layer, each at least once
        require(kernels >= 6, f"compiled short-convolution step holds "
                              f"{kernels} tpu_custom_call")
    got, routing, gap = expert_step_checks("short-convolution", prog, want,
                                           sz.h_k)
    return {"shapes": {**sz.v_shape, "seq": sz.v_seq, "batch": 2,
                       "layers": list(arch["layer_kinds"]), "experts": 8,
                       "held": [0, 8], "top_k": 4, "slots": slots,
                       "steps": sz.h_k, "lr": sz.h_lr},
            "tpu_custom_calls": kernels,
            "memory_analysis": prog.memory_analysis,
            "losses": [round(v, 4) for v in got],
            "float32_losses": [round(v, 4) for v in want["losses"]],
            "rows_routed_to_held": int(routing["routed"][0]),
            "selection_gap": gap,
            "checks": "first loss, the loss's fall and the selections "
                      "within tolerance of "
                      "benchmarks/reference_conv_moe.py; no row past the "
                      "bound; attention and expert kernels compiled in"}


# --------------------------------------------------------- phase: swa_moe

def phase_swa_moe(sz: Sizes) -> dict:
    """The window-and-full attention expert decoder (models/hybrid.py:
    a ``nope`` layer and three ``swa`` layers, seven query heads over one
    key/value head, the router on the layer's input, ReLU-gated experts
    of which 2 of the router's 8 are held, the head untied) through the
    same step builder and executor as ``phase_train``, the dense and the
    block-sparse attention kernels forced, against the benchmark's plain
    float32 reference on the same seeded weights."""
    from benchmarks import reference_swa_moe, weights_swa_moe
    from benchmarks.runners import train_swa_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step

    layout = [0, 1, 1, 1]
    config = {
        **sz.w_shape, "num_hidden_layers": 4, "rope_layout": layout,
        "sliding_window_layout": layout, "moe_num_primary_experts": 2,
        "published": {"moe_num_primary_experts": 8},
        "moe_num_active_primary_experts": 3,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_scaling": None,
        "tie_word_embeddings": False, "torch_dtype": sz.dtype,
        "assumed": {"first_held_expert": 2}}
    arch = weights_swa_moe.arch_of(config)
    slots = 2 * sz.w_seq        # every row of the batch: no bound to reach
    cfg = train_swa_moe.config_of(
        arch, sz.w_seq, slots, remat=True, attention_impl="flash",
        loss_row_block=sz.w_seq)

    def make_params():
        return weights_swa_moe.make_params(arch, sz.seed)
    tokens = weights_swa_moe.make_token_pool(
        sz.seed, 1, 2, sz.w_seq + 1, arch["vocab_size"])[0]
    want = reference_swa_moe.sgd_steps(
        make_params, [tokens] * sz.h_k, arch, sz.h_lr)
    prog = executor.CompiledProgram(executor.Program(
        fn=bench_step.make_train_k(cfg, sz.h_k, sz.h_lr),
        args=(make_params(), tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS))
    kernels = prog.as_text().count("tpu_custom_call")
    if on_tpu():
        # three attention kernels of either kind, three grouped matmuls
        # and the counted backward's four a layer, each at least once
        require(kernels >= 13, f"compiled window-and-full step holds "
                               f"{kernels} tpu_custom_call")
    got, routing, gap = expert_step_checks("window-and-full", prog, want,
                                           sz.h_k)
    return {"shapes": {**sz.w_shape, "seq": sz.w_seq, "batch": 2,
                       "layers": list(arch["layer_kinds"]), "experts": 8,
                       "held": list(arch["held"]), "top_k": 3,
                       "slots": slots, "steps": sz.h_k, "lr": sz.h_lr},
            "tpu_custom_calls": kernels,
            "memory_analysis": prog.memory_analysis,
            "losses": [round(v, 4) for v in got],
            "float32_losses": [round(v, 4) for v in want["losses"]],
            "rows_routed_to_held": int(routing["routed"][0]),
            "selection_gap": gap,
            "checks": "first loss, the loss's fall and the selections "
                      "within tolerance of "
                      "benchmarks/reference_swa_moe.py; no row past the "
                      "bound; window, full and expert kernels compiled in"}


# ---------------------------------------------------- phase: headgate_moe

def phase_headgate_moe(sz: Sizes) -> dict:
    """The head-gated window-and-full attention expert decoder
    (models/hybrid.py: a ``gated`` layer with a dense MLP, three ``swa``
    layers at nine query heads over one key/value head and a ``gated``
    layer at six in stacks of their own, RoPE on every lane of a window
    layer and YaRN on half the lanes of a full one, one sigmoid gate a
    head, softmax-routed experts times 2.5 of which 2 of the router's 8
    are held beside a plain shared one, the head untied) through the
    same step builder and executor as ``phase_train``, the dense and the
    block-sparse attention kernels forced, against the benchmark's plain
    float32 reference on the same seeded weights."""
    from benchmarks import reference_headgate_moe, weights_headgate_moe
    from benchmarks.runners import train_headgate_moe
    from dlnetbench_tpu.core import executor
    from dlnetbench_tpu.models import bench_step

    kinds = ["full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"]
    full = sz.g_shape["num_attention_heads"]
    config = {
        **sz.g_shape, "num_hidden_layers": 5, "layer_types": kinds,
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "mlp_only_layers": [0], "gating_types": ["per_head"] * 5,
        "gating": "per-head",
        "num_attention_heads_per_layer": [
            full if k == "full_attention" else sz.g_window_heads
            for k in kinds],
        "num_experts": 2, "published": {"num_experts": 8},
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "moe_routed_scaling_factor": 2.5,
        "moe_router_logit_softcapping": 0,
        "moe_apply_router_weight_on_input": False,
        "attention_bias": False, "rms_norm_eps": 1e-6,
        "rope_parameters": {
            # the original positions inside the sequence, so that the
            # ramp and the factor both act on it
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": sz.g_seq // 4,
                "beta_slow": 1, "beta_fast": 32,
                "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "tie_word_embeddings": False, "torch_dtype": sz.dtype,
        "assumed": {"first_held_expert": 2}}
    arch = weights_headgate_moe.arch_of(config)
    slots = 2 * sz.g_seq        # every row of the batch: no bound to reach
    cfg = train_headgate_moe.config_of(
        arch, sz.g_seq, slots, remat=True, attention_impl="flash",
        loss_row_block=sz.g_seq)

    def make_params():
        return weights_headgate_moe.make_params(arch, sz.seed)
    tokens = weights_headgate_moe.make_token_pool(
        sz.seed, 1, 2, sz.g_seq + 1, arch["vocab_size"])[0]
    want = reference_headgate_moe.sgd_steps(
        make_params, [tokens] * sz.h_k, arch, sz.h_lr)
    prog = executor.CompiledProgram(executor.Program(
        fn=bench_step.make_train_k(cfg, sz.h_k, sz.h_lr),
        args=(make_params(), tokens),
        donate_argnums=bench_step.DONATE_ARGNUMS))
    kernels = prog.as_text().count("tpu_custom_call")
    if on_tpu():
        # the attention kernels of either kind, three grouped matmuls
        # and the counted backward's four a layer, each at least once
        require(kernels >= 13, f"compiled head-gated step holds "
                               f"{kernels} tpu_custom_call")
    got, routing, gap = expert_step_checks("head-gated", prog, want, sz.h_k)
    return {"shapes": {**sz.g_shape, "window_heads": sz.g_window_heads,
                       "seq": sz.g_seq, "batch": 2,
                       "layers": list(arch["layer_kinds"]), "experts": 8,
                       "held": list(arch["held"]), "top_k": 3,
                       "slots": slots, "steps": sz.h_k, "lr": sz.h_lr},
            "tpu_custom_calls": kernels,
            "memory_analysis": prog.memory_analysis,
            "losses": [round(v, 4) for v in got],
            "float32_losses": [round(v, 4) for v in want["losses"]],
            "rows_routed_to_held": int(routing["routed"][0]),
            "selection_gap": gap,
            "checks": "first loss, the loss's fall and the selections "
                      "within tolerance of "
                      "benchmarks/reference_headgate_moe.py; no row past "
                      "the bound; window, full and expert kernels "
                      "compiled in"}


def phase_sparse_linear_ops(sz: Sizes) -> dict:
    """The two ops of the sparse-and-linear hybrid at its cell's shapes
    (``ops/lightning_attention.py``, ``ops/sparse_attention.py``), the
    Pallas kernels forced.  The lightning rule: the kernel pair against
    the same chunks as a ``lax.scan`` at the cell's length, output and
    three gradients, and against the token recurrence at a length whose
    states fit.  The sparse layer: the selection at the cell's length
    (every token's list holds block 0 and its own block and as many
    blocks as exist, up to ``topk``), the forward kernel there against
    dense masked softmax on sampled query rows, and all three gradients
    of one group against the dense reference at a length it fits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dlnetbench_tpu.ops import lightning_attention as la
    from dlnetbench_tpu.ops import sparse_attention as sa

    f32, dt = jnp.float32, jnp.dtype(sz.dtype)
    s, h, hkv, d = sz.o_seq, sz.o_heads, sz.o_kv_heads, sz.o_head_dim
    keys = jax.random.split(jax.random.key(sz.seed), 8)
    checks: dict = {}

    def draw(key, *shape):
        return jax.random.normal(key, shape, f32).astype(dt)

    # --- the lightning rule
    decay = la.head_log_decay(h, 1, 32)
    scale = d ** -0.5
    q, k, v, w = (draw(kk, 1, s, h, d) for kk in keys[:4])

    def rule(impl, fn=la.lightning_attention):
        def loss(q, k, v):
            args = (impl,) if impl else ()
            o = fn(q, k, v, decay, scale, *args)
            return jnp.sum(o.astype(f32) * w[:, :q.shape[1]].astype(f32)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))
    (_, o_k), g_k = rule("pallas")(q, k, v)
    (_, o_x), g_x = rule("xla")(q, k, v)
    close(checks, "lightning o, kernels vs scan", o_k, o_x, 1e-2)
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_x):
        close(checks, f"lightning {name}, kernels vs scan", a, b, 1e-2)
    short = tuple(t[:, :sz.o_rule_seq] for t in (q, k, v))
    (_, o_k), g_k = rule("pallas")(*short)
    (_, o_r), g_r = rule(None, la.reference_rule)(
        *(t.astype(f32) for t in short))
    close(checks, "lightning o vs recurrence", o_k, o_r, 3e-2)
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_r):
        close(checks, f"lightning {name} vs recurrence", a, b, 3e-2)

    # --- the selection and the sparse forward at the cell's length
    sizes = sa.SparseSizes(*sz.o_sizes)
    q = draw(keys[4], 1, s, h, d)
    k, v = (draw(kk, 1, s, hkv, d) for kk in keys[5:7])
    blocks = jax.jit(lambda q, k: sa.select_blocks(q, k, sizes))(q, k)
    lists = np.asarray(blocks)[0]                       # [S, Hkv, topk]
    own = np.arange(s) // sizes.block_size
    require(bool(((lists == 0).any(-1)).all()
                 and (lists == own[:, None, None]).any(-1).all()),
            "a list without block 0 or the token's own block")
    require(bool(((lists >= 0).sum(-1)
                  == np.minimum(own + 1, sizes.topk)[:, None]).all()
                 and (lists <= own[:, None, None]).all()),
            "a list of another length than the visible blocks allow, or "
            "a block after the token")
    out, counted = jax.jit(lambda q, k, v, b: (
        lambda vis: (sa.block_sparse_attention(q, k, v, vis),
                     sa.counters(b, vis)))(
        sa.plan_visits(b, sizes.block_size, q.dtype)))(q, k, v, blocks)
    rows = np.arange(sz.o_rows) * (s // sz.o_rows) + s // sz.o_rows - 1

    @jax.jit
    def sampled(q, k, v, blocks):
        """Dense masked softmax of the sampled query rows."""
        member = sa.membership(blocks[:, rows], s // sizes.block_size,
                               f32)[0]                  # [Hkv, n, nblk]
        seen = (jnp.repeat(member, sizes.block_size, -1) > 0) \
            & (jnp.arange(s)[None, None, :] <= rows[None, :, None])
        qg = q[0, rows].astype(f32).reshape(len(rows), hkv, h // hkv, d)
        sc = jnp.einsum("ngqd,kgd->gnqk", qg, k[0].astype(f32),
                        precision="highest") * scale
        pr = jax.nn.softmax(jnp.where(seen[:, :, None, :], sc, -jnp.inf),
                            axis=-1)
        o = jnp.einsum("gnqk,kgd->ngqd", pr, v[0].astype(f32),
                       precision="highest")
        return o.reshape(len(rows), h, d)
    close(checks, "sparse o on sampled rows vs dense masked softmax",
          out[0, rows], sampled(q, k, v, blocks), 2e-2)
    counted = jax.device_get(counted)

    # --- the sparse gradients, one group, at a length a reference fits
    gs, gsizes = sz.o_grad_seq, sa.SparseSizes(*sz.o_grad_sizes)
    group = h // hkv
    q, w = (draw(kk, 1, gs, group, d) for kk in (keys[4], keys[7]))
    k, v = (draw(kk, 1, gs, 1, d) for kk in keys[5:7])
    blocks = sa.select_blocks(q, k, gsizes)

    def attn(fn):
        def loss(q, k, v):
            o = fn(q, k, v, blocks, gsizes.block_size)
            return jnp.sum(o.astype(f32) * w.astype(f32)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))
    (_, o_k), g_k = attn(lambda q, k, v, lists, block: (
        sa.block_sparse_attention(q, k, v, sa.plan_visits(
            lists, block, q.dtype))))(q, k, v)
    (_, o_r), g_r = attn(sa.reference_attention)(
        *(t.astype(f32) for t in (q, k, v)))
    close(checks, "sparse o, one group vs dense reference", o_k, o_r, 2e-2)
    for name, a, b in zip(("dq", "dk", "dv"), g_k, g_r):
        close(checks, f"sparse {name}, one group vs dense reference", a, b,
              3e-2)
    return {"shapes": {"seq": s, "heads": h, "kv_heads": hkv,
                       "head_dim": d, "sizes": list(sz.o_sizes),
                       "rule_seq": sz.o_rule_seq, "grad_seq": gs,
                       "grad_sizes": list(sz.o_grad_sizes),
                       "sampled_rows": sz.o_rows},
            "selected": int(counted["selected"]),
            "visited": int(counted["visited"]),
            "kernel_checks": checks,
            "checks": "lightning kernels against the scan of chunks and "
                      "the token recurrence; every list holds block 0, "
                      "the token's own block and min(visible, topk) "
                      "blocks; sparse forward against dense masked "
                      "softmax on sampled rows; sparse gradients of one "
                      "group against the dense reference"}


# ------------------------------------------------------ four-chip phases

def all_device_ids() -> set:
    import jax
    return {d.id for d in jax.devices()[:4]}


def require_four_devices(rec: dict) -> dict:
    """The record's mesh holds four distinct devices."""
    ids = [r["device_id"] for r in rec["ranks"]]
    require(rec["mesh"]["num_devices"] == 4 and len(set(ids)) == 4,
            f"mesh of {rec['mesh']['num_devices']} devices, ids {ids}")
    return {"axes": rec["mesh"]["axes"], "device_ids": ids,
            "device_order": rec["mesh"].get("device_order", "topology")}


def require_buffers_everywhere(bundle, what: str) -> int:
    """Every buffer the proxy's full step runs on has an addressable
    shard on each of the four devices."""
    import jax
    want = all_device_ids()
    leaves = jax.tree.leaves(bundle.full.example_args)
    for leaf in leaves:
        have = {s.device.id for s in leaf.addressable_shards}
        require(have == want, f"{what}: a buffer of shape {leaf.shape} "
                f"lives on devices {sorted(have)}, not {sorted(want)}")
    return len(leaves)


def phase_mesh_proxies(sz: Sizes) -> dict:
    """``cli fsdp`` and ``cli hybrid_3d --num_stages 2 --tp 2`` over all
    four chips."""
    import jax
    import jax.numpy as jnp

    from dlnetbench_tpu.core.model_card import (arch_name_from_stats_name,
                                                load_model_card)
    from dlnetbench_tpu.core.model_stats import load_model_stats
    from dlnetbench_tpu.proxies import fsdp, hybrid_3d
    from dlnetbench_tpu.proxies.base import ProxyConfig
    tail = ["--time_scale", str(sz.c4_time_scale), "--buffer_dtype", "stats",
            "-w", "1", "-r", "3", "--devices", "4"]
    rec_f = run_cli(["fsdp", "--model", sz.c4_fsdp_model, "--num_units",
                     "8", "--size_scale", str(sz.c4_fsdp_scale), *tail],
                    OUT_DIR / "c4_fsdp.jsonl")
    gc.collect()
    rec_h = run_cli(["hybrid_3d", "--model", sz.c4_h3d_model,
                     "--num_stages", "2", "--num_microbatches", "4",
                     "--tp", "2", "--size_scale", str(sz.c4_h3d_scale),
                     *tail], OUT_DIR / "c4_hybrid_3d.jsonl")
    gc.collect()
    # where the buffers live: the same builders the CLI calls, small
    small = ProxyConfig(size_scale=1e-5, time_scale=1e-4)
    devs = jax.devices()[:4]
    n_f = require_buffers_everywhere(
        fsdp.build(load_model_stats(sz.c4_fsdp_model), 8, small,
                   devices=devs, dtype=jnp.bfloat16), "fsdp")
    n_h = require_buffers_everywhere(
        hybrid_3d.build(
            load_model_stats(sz.c4_h3d_model),
            load_model_card(arch_name_from_stats_name(sz.c4_h3d_model)),
            small, num_stages=2, num_microbatches=4, tp=2, devices=devs,
            dtype=jnp.bfloat16), "hybrid_3d")
    return {
        "shapes": {"fsdp": {"model": sz.c4_fsdp_model,
                            "size_scale": sz.c4_fsdp_scale,
                            "shard_bytes": rec_f["global"]["shard_bytes"],
                            "unit_bytes": rec_f["global"]["unit_bytes"]},
                   "hybrid_3d": {"model": sz.c4_h3d_model,
                                 "size_scale": sz.c4_h3d_scale,
                                 "stages": 2, "tp": 2, "microbatches": 4},
                   "time_scale": sz.c4_time_scale},
        "cut": ("size_scale 0.125 of llama3_8b, the largest that fits: "
                "each device holds the gathered units (2 GB), its shards "
                "with three donated clones (2 GB), and the all-gather "
                "variant's 15 gathered results (3.75 GB) — about 8 GB of "
                "16, and 0.25 would need 16; time_scale 0.02 of a 15.6 s "
                "step, because this phase is about the collectives"),
        "fsdp": {"mesh": require_four_devices(rec_f), "buffers": n_f,
                 "runtime_us": timer_median_us(rec_f, "runtimes"),
                 "allgather_us": timer_median_us(rec_f, "allgather_time"),
                 "reduce_scatter_us": timer_median_us(
                     rec_f, "reduce_scatter_time")},
        "hybrid_3d": {"mesh": require_four_devices(rec_h), "buffers": n_h,
                      "runtime_us": timer_median_us(rec_h, "runtimes")},
        "run_s": round(rec_f["_wall_s"] + rec_h["_wall_s"], 1),
        "checks": "meshes hold four distinct devices; every proxy buffer "
                  "has a shard on each; records parse",
    }


def phase_spmd(sz: Sizes) -> dict:
    """The real-math SPMD step (models/spmd.py) on dp=2 x tp=2, blocking
    and with the decomposed/bucketed overlap paths, against the same
    step on one device: same seed, same tokens."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dlnetbench_tpu.core.model_card import load_model_card
    from dlnetbench_tpu.models import spmd
    from dlnetbench_tpu.parallel.mesh import make_grid_mesh
    shape = sz.s_shape or dataclasses.asdict(load_model_card(sz.s_card))
    cfg = spmd.SpmdConfig(
        vocab_size=shape["vocab_size"], embed_dim=shape["embed_dim"],
        num_heads=shape["num_heads"], num_kv_heads=shape["num_kv_heads"],
        ff_dim=shape["ff_dim"], num_layers=sz.c4_layers,
        seq_len=sz.c4_seq, num_experts=2, top_k=2, capacity_factor=1.0,
        batch=sz.c4_batch, num_microbatches=2, dtype=sz.dtype)
    params = jax.jit(spmd.init_params, static_argnums=1)(
        jax.random.key(sz.seed), cfg)
    tokens = jax.random.randint(jax.random.key(sz.seed + 1),
                                (cfg.batch, cfg.seq_len + 1), 0,
                                cfg.vocab_size)
    devs = jax.devices()[:4]
    out = {}

    def one(label, mesh, cfg, params, tokens):
        t0 = time.perf_counter()
        step = spmd.make_train_step(mesh, cfg)
        compiled = step.lower(params, tokens).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        new_params, loss = compiled(params, tokens)
        loss = float(jax.block_until_ready(loss))
        out[label] = {"loss": round(loss, 5),
                      "compile_s": round(compile_s, 1),
                      "run_s": round(time.perf_counter() - t0, 2)}
        if mesh.devices.size == 4:
            text = compiled.as_text()
            out[label]["collectives"] = {
                n: text.count(f" {n}(") + text.count(f" {n}-start(")
                for n in ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute")}
            leaf = new_params["layers"]["w_gate"]
            have = {s.device.id for s in leaf.addressable_shards}
            require(have == all_device_ids(),
                    f"{label}: updated expert weights live on {have}")
        del new_params
        gc.collect()
        return loss

    l1 = one("one_device", make_grid_mesh(dp=1, pp=1, tp=1,
                                          devices=devs[:1]), cfg,
             params, tokens)
    # onto the mesh, each shard where the step wants it; the whole
    # copy on the first device goes
    mesh4 = make_grid_mesh(dp=2, pp=1, tp=2, devices=devs)
    params4 = jax.device_put(params, spmd.param_shardings(mesh4))
    tokens4 = jax.device_put(tokens, NamedSharding(mesh4, P("dp", None)))
    del params
    gc.collect()
    l4 = one("dp2_tp2", mesh4, cfg, params4, tokens4)
    l4o = one("dp2_tp2_overlap", mesh4, dataclasses.replace(
        cfg, tp_overlap="decomposed", grad_sync="bucketed"), params4,
        tokens4)
    tol = 5e-3 if sz.dtype == "float32" else 2e-2
    for label, got in (("dp2_tp2", l4), ("dp2_tp2_overlap", l4o)):
        require(got == got and abs(got - l1) <= tol * abs(l1),
                f"{label}: loss {got} vs one device {l1} "
                f"(tolerance {tol} relative)")
    return {
        "shapes": {"embed": cfg.embed_dim,
                   "heads": [cfg.num_heads, cfg.num_kv_heads],
                   "ff": cfg.ff_dim, "vocab": cfg.vocab_size,
                   "experts": [cfg.num_experts, cfg.top_k],
                   "layers": cfg.num_layers, "batch": cfg.batch,
                   "seq": cfg.seq_len, "mesh": {"dp": 2, "pp": 1, "tp": 2}},
        "cut": (f"{sz.s_card} widths; depth 32 -> {cfg.num_layers}, two "
                f"experts (the fewest tp=2 shards), both chosen so no "
                f"token drops: the one-device step it is compared with "
                f"holds every weight, float32 gradient accumulators and "
                f"the undonated update (9.3 GiB at one layer, 18.4 at "
                f"two, by the compiler's count)"),
        **out, "tol_rel": tol,
        "checks": "sharded losses equal the one-device loss within "
                  "tolerance; updated weights have shards on all four",
    }


def phase_kv_shard(sz: Sizes) -> dict:
    """``run_serving`` with the KV heads sharded over four chips against
    one chip: the same greedy tokens."""
    import jax

    from dlnetbench_tpu.models.transformer import init_params
    model_cfg, _ = model_config(sz.s_card, sz.s_shape, sz.c4_serve_layers,
                                sz.s_max_seq, sz.dtype)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.key(sz.seed), model_cfg)
    # world=4 with it: the record's ranks are the mesh's devices
    res = serve_variant(sz, "c4_kv_shard", model_cfg, params,
                        sz.s_var_requests, {"world": 1, "kv_shard": 1},
                        0.05, world=4, kv_shard=4)
    return {"shapes": {"layers": sz.c4_serve_layers, "kv_heads_per_chip":
                       model_cfg.num_kv_heads // 4,
                       "requests": sz.s_var_requests},
            "kv_shard_4": res,
            "checks": "all requests complete; tokens equal the one-chip "
                      "engine's up to near ties"}


# ------------------------------------------------------------------ main

ONE_CHIP = (("kernels", phase_kernels), ("train", phase_train),
            ("proxy", phase_proxy), ("serve", phase_serve),
            ("moe", phase_moe), ("hybrid", phase_hybrid),
            ("latent_moe", phase_latent_moe),
            ("linear_moe", phase_linear_moe),
            ("conv_moe", phase_conv_moe),
            ("swa_moe", phase_swa_moe),
            ("headgate_moe", phase_headgate_moe),
            ("sparse_linear_ops", phase_sparse_linear_ops))
FOUR_CHIPS = (("mesh_proxies", phase_mesh_proxies), ("spmd", phase_spmd),
              ("kv_shard", phase_kv_shard))


def run_phases(phases, sz: Sizes, emit=print) -> bool:
    """Run ``phases`` in order, one JSON line each; True if all passed.
    A failure is recorded with its traceback and the run goes on: one
    call to the chip should say everything that is wrong."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    ok = True
    for name, fn in phases:
        line: dict = {"phase": name}
        t0 = time.perf_counter()
        try:
            line.update(fn(sz))
            line["ok"] = True
        except Exception as e:   # the boundary that must keep running
            traceback.print_exc(file=sys.stderr)
            line.update(ok=False,
                        error=f"{type(e).__name__}: {e}"[:600])
            ok = False
        line["seconds"] = round(time.perf_counter() - t0, 1)
        line["hbm_gib"] = memory_now()
        emit(json.dumps(line))
        gc.collect()
    return ok


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh phases on one four-chip host, and "
                         "what they are compared with, and nothing else")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes; also runs the phases off TPU "
                         "(and then fails: there was no chip)")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset, for finding a fault")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu" and not args.tiny:
        print(f"chip_smoke: jax found no TPU (devices: {device}); the "
              f"full-size phases run on the chip only", file=sys.stderr)
        return 1
    if len(dev) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {len(dev)} device(s)",
              file=sys.stderr)
        return 1

    from dlnetbench_tpu.core.executor import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    cache = {"hits": 0, "misses": 0}

    def count(event: str, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            cache[event.rsplit("_", 1)[1]] += 1
    jax.monitoring.register_event_listener(count)
    phases = ONE_CHIP if args.chips == 1 else FOUR_CHIPS
    if args.phases:
        want = args.phases.split(",")
        unknown = set(want) - {n for n, _ in phases}
        if unknown:
            ap.error(f"unknown phases {sorted(unknown)}")
        phases = tuple(p for p in phases if p[0] in want)
    print(json.dumps({"phase": "start", "device": device,
                      "sizes": "tiny" if args.tiny else "full",
                      "compile_cache_dir": cache_dir}), flush=True)
    t0 = time.perf_counter()
    ok = run_phases(phases, TINY if args.tiny else FULL,
                    emit=lambda s: print(s, flush=True))
    jax.monitoring.unregister_event_listener(count)
    print(json.dumps({"phase": "end", "ok": ok,
                      "seconds": round(time.perf_counter() - t0, 1),
                      "compile_cache": cache}), flush=True)
    if not ok or device["platform"] != "tpu":
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
