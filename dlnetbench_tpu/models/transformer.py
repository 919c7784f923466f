"""Decoder-family transformer (llama / gpt2 variants), pure JAX.

Architecture is read off a ``ModelCard``: ``gated_mlp`` selects
SwiGLU+RMSNorm+RoPE (llama/minerva/mixtral family) vs GELU+LayerNorm+learned
positions (gpt2 family); ``num_kv_heads`` gives GQA; ``moe_params`` turns
every layer's MLP into a dense-dispatch MoE (Mixtral-style).  Layers are
stacked on a leading axis and executed with ``lax.scan`` so compile time is
O(1) in depth and XLA sees one fused block body.

This is the compute that the reference only *simulates* (usleep from
roofline stat files); here the same cards drive real math, so measured step
times can be compared against the roofline predictions (see bench.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from dlnetbench_tpu import ops
from dlnetbench_tpu.core.model_card import ModelCard
from dlnetbench_tpu.metrics.spans import scope
from dlnetbench_tpu.models import layers as L


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    embed_dim: int
    num_heads: int
    num_kv_heads: int
    ff_dim: int
    num_layers: int
    seq_len: int
    gated: bool              # SwiGLU+RMSNorm+RoPE vs GELU+LayerNorm+learned
    max_positions: int       # learned positions (gpt2 family), 0 = RoPE
    num_experts: int = 1
    top_k: int = 1
    tied_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = False      # jax.checkpoint each block: recompute activations
                             # in backward instead of storing S x S residuals
    attention_impl: str = "auto"   # ops.attention dispatch: auto | flash | xla
    attention_window: int = 0      # sliding-window attention: each token
                             # attends its W most recent tokens (itself
                             # included); 0 = full causal.  Dispatches
                             # the block-sparse splash kernels on TPU
                             # (ops/attention_mask.py MaskSpec) and the
                             # dense-masked reference on the CPU mesh
    attention_seg_avg: int = 0     # document-segment masking: tokens are
                             # partitioned into documents by the seeded
                             # segment plan (splitmix64 lengths around
                             # this average); attention never crosses a
                             # document boundary.  0 = off
    attention_seg_seed: int = 0    # the segment plan's seed (a plan IS
                             # (seed, avg): replayable, committable)
    scan_layers: bool = True       # lax.scan over the layer stack (O(1)
                             # compile time in depth); False unrolls the
                             # Python loop — measured ~5% faster at 4 layers
                             # on v5e (no dynamic-slice save/restore of
                             # per-layer activations), at O(depth) compile
    logits_f32: bool = True        # emit f32 logits (training-grade CE
                             # numerics); False keeps them bf16 — halves
                             # the [B, S, V] logits traffic for benches
    mlp_dtype: str = "bfloat16"    # "float8" runs the (dense) MLP matmuls
                             # in e4m3 with per-tensor dynamic scales and
                             # bf16 master weights (ops/fp8.py; measured
                             # r5: native on the MXU at 0.70 of fp8 peak
                             # in isolation — the r3/r4 "upcast" verdict
                             # was an HBM-residency artifact);
                             # "int8" likewise via ops/int8.py — 0.98 of
                             # the 2x int8 peak in isolation and a
                             # measured 1.087x END-TO-END step win at
                             # the headline's no-remat config (494.3 vs
                             # 537.5 ms, r5 docs/PERF.md — needs the
                             # fused swiglu_int8 VJP);
                             # backward stays in the master dtype
                             # (straight-through) for both
    moe_impl: str = "dense"        # "dense" (every expert computes every
                             # selected token — exact, E/k x the FLOPs),
                             # "sparse" (capacity-based dispatch, GShard
                             # style: ~k*cf*T*ffn FLOPs, over-capacity
                             # tokens dropped — the production semantics)
                             # or "grouped" (sparse routing with the
                             # expert FFN as Pallas grouped-matmul
                             # kernels, ops/grouped_matmul.py — blocks
                             # past an expert's kept-token count are
                             # skipped; ISSUE 15)
    moe_capacity_factor: float = 1.25
    int8_backward: str = "master"  # mlp_dtype="int8" backward mode:
                             # "master" = straight-through bf16 (the
                             # conservative default); "switchback" =
                             # the dx-side matmuls (dh, dx) also
                             # quantized to int8, dW stays master —
                             # a RECIPE change, opt-in; loss-drift
                             # measured in docs/studies/int8_step_r5
    quant_fusion: str = "composed" # low-precision MLP matmul impl
                             # (mlp_dtype float8/int8 only): "composed"
                             # = quantization as separate XLA passes
                             # (amax reduce, rescale/cast, post-matmul
                             # sa*sb — each an HBM round trip);
                             # "fused" = the Pallas kernels in
                             # ops/quantized_matmul.py, which quantize
                             # the activation tile in VMEM and apply
                             # sa*sb in the epilogue (the r6 attack on
                             # the fp8 chain's 0.56-of-peak and the
                             # int8 step's quantization overhead)

    def __post_init__(self):
        if self.attention_window < 0 or self.attention_seg_avg < 0:
            raise ValueError(
                f"attention_window={self.attention_window} / "
                f"attention_seg_avg={self.attention_seg_avg} must be "
                f">= 0 (0 = off)")
        if self.moe_impl not in ("dense", "sparse", "grouped"):
            raise ValueError(f"unknown moe_impl {self.moe_impl!r}; "
                             f"expected 'dense', 'sparse' or 'grouped'")
        if self.mlp_dtype not in ("bfloat16", "float8", "int8"):
            raise ValueError(f"unknown mlp_dtype {self.mlp_dtype!r}; "
                             f"expected 'bfloat16', 'float8' or 'int8'")
        if self.int8_backward not in ("master", "switchback"):
            raise ValueError(
                f"unknown int8_backward {self.int8_backward!r}; "
                f"expected 'master' or 'switchback'")
        if self.int8_backward != "master" and self.mlp_dtype != "int8":
            raise ValueError(
                "int8_backward='switchback' requires mlp_dtype='int8'")
        if self.mlp_dtype != "bfloat16" and (self.num_experts > 1
                                             or not self.gated):
            raise ValueError(
                f"mlp_dtype={self.mlp_dtype!r} currently covers the "
                f"dense SwiGLU path only")
        if self.quant_fusion not in ("composed", "fused"):
            raise ValueError(f"unknown quant_fusion {self.quant_fusion!r}; "
                             f"expected 'composed' or 'fused'")
        if self.quant_fusion == "fused" and self.mlp_dtype == "bfloat16":
            raise ValueError(
                "quant_fusion='fused' requires mlp_dtype='float8' or "
                "'int8' (there is nothing to quantize in bf16)")
        if self.quant_fusion == "fused" and self.int8_backward != "master":
            raise ValueError(
                "quant_fusion='fused' covers the master-dtype "
                "(straight-through) backward only; SwitchBack's "
                "quantized dx dots are a composed-path recipe")

    @classmethod
    def from_card(cls, card: ModelCard, *, seq_len: int | None = None,
                  num_layers: int | None = None,
                  vocab_size: int | None = None) -> "TransformerConfig":
        """Build from an architecture card, optionally overriding size knobs
        (tests and single-chip benches shrink seq/layers/vocab)."""
        if card.is_vit:
            raise ValueError(f"{card.name} is a ViT card; use models.vit")
        return cls(
            vocab_size=vocab_size or card.vocab_size or 32000,
            embed_dim=card.embed_dim,
            num_heads=card.num_heads,
            num_kv_heads=card.kv_heads,
            ff_dim=card.ff_dim,
            num_layers=num_layers or card.num_layers,
            seq_len=seq_len or card.seq_len,
            gated=card.gated_mlp,
            max_positions=card.max_position_embeddings,
            num_experts=card.num_experts,
            top_k=card.top_k,
            tied_embeddings=card.tied_embeddings,
        )

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def mask_spec(self):
        """The attention ``MaskSpec`` these knobs declare, or ``None``
        for the dense-causal default (ops.attention's mask=None path —
        bit-identical to the pre-mask harness)."""
        from dlnetbench_tpu.ops.attention_mask import MaskSpec
        return MaskSpec.from_knobs(self.attention_window,
                                   self.attention_seg_avg,
                                   self.attention_seg_seed)



def init_params(key, cfg: TransformerConfig) -> dict:
    d, dh = cfg.embed_dim, cfg.head_dim
    dkv = cfg.num_kv_heads * dh
    h, L_, v = cfg.ff_dim, cfg.num_layers, cfg.vocab_size
    dt = cfg.jdtype
    s_d = 1.0 / math.sqrt(d)
    s_h = 1.0 / math.sqrt(h)
    keys = iter(jax.random.split(key, 16))

    layer = {
        "wq": L.init_dense(next(keys), (L_, d, d), s_d, dt),
        "wk": L.init_dense(next(keys), (L_, d, dkv), s_d, dt),
        "wv": L.init_dense(next(keys), (L_, d, dkv), s_d, dt),
        "wo": L.init_dense(next(keys), (L_, d, d), s_d, dt),
        "norm1": jnp.ones((L_, d), dt),
        "norm2": jnp.ones((L_, d), dt),
    }
    if not cfg.gated:
        layer.update({
            "norm1_b": jnp.zeros((L_, d), dt),
            "norm2_b": jnp.zeros((L_, d), dt),
            "w_in": L.init_dense(next(keys), (L_, d, h), s_d, dt),
            "b_in": jnp.zeros((L_, h), dt),
            "w_out": L.init_dense(next(keys), (L_, h, d), s_h, dt),
            "b_out": jnp.zeros((L_, d), dt),
        })
    elif cfg.num_experts > 1:
        e = cfg.num_experts
        layer.update({
            "w_router": L.init_dense(next(keys), (L_, d, e), s_d, dt),
            "w_gate": L.init_dense(next(keys), (L_, e, d, h), s_d, dt),
            "w_up": L.init_dense(next(keys), (L_, e, d, h), s_d, dt),
            "w_down": L.init_dense(next(keys), (L_, e, h, d), s_h, dt),
        })
    else:
        layer.update({
            "w_gate": L.init_dense(next(keys), (L_, d, h), s_d, dt),
            "w_up": L.init_dense(next(keys), (L_, d, h), s_d, dt),
            "w_down": L.init_dense(next(keys), (L_, h, d), s_h, dt),
        })

    params = {
        "embed": L.init_dense(next(keys), (v, d), 1.0, dt),
        "layers": layer,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.gated:
        params["final_norm_b"] = jnp.zeros((d,), dt)
    if cfg.max_positions:
        params["pos_embed"] = L.init_dense(next(keys), (cfg.max_positions, d),
                                    0.01, dt)
    if not cfg.tied_embeddings:
        params["head"] = L.init_dense(next(keys), (d, v), s_d, dt)
    return params


def _block(cfg: TransformerConfig, x, lp, positions):
    """One decoder block; x: [B, S, D], lp: this layer's param slice.

    The block wears the step's scopes (``spans.SCOPES``): ``attn`` up to
    its residual add; then ``mlp`` over norm2, the dense MLP and its
    residual, or the four ``moe.*`` (norm2 lies in ``moe.router``, the
    residual in ``moe.combine``)."""
    b, s, d = x.shape
    with scope("attn"):
        if cfg.gated:
            y = L.rmsnorm(x, lp["norm1"])
        else:
            y = L.layernorm(x, lp["norm1"], lp["norm1_b"])
        q = jnp.dot(y, lp["wq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = jnp.dot(y, lp["wk"]).reshape(b, s, cfg.num_kv_heads,
                                         cfg.head_dim)
        v = jnp.dot(y, lp["wv"]).reshape(b, s, cfg.num_kv_heads,
                                         cfg.head_dim)
        if not cfg.max_positions:  # RoPE family
            q, k = L.rope(q, k, positions)
        att = ops.attention(q, k, v, causal=True, impl=cfg.attention_impl,
                            mask=cfg.mask_spec).reshape(b, s, d)
        x = x + jnp.dot(att, lp["wo"])

    if cfg.gated and cfg.num_experts > 1:
        with scope("moe.router"):
            y = L.rmsnorm(x, lp["norm2"])
        if cfg.moe_impl == "dense":
            moe = L.moe_dense
        elif cfg.moe_impl == "grouped":
            from dlnetbench_tpu.models.moe import moe_grouped
            moe = functools.partial(
                moe_grouped,
                capacity_factor=cfg.moe_capacity_factor)
        else:
            moe = functools.partial(
                L.moe_sparse,
                capacity_factor=cfg.moe_capacity_factor)
        y2 = moe(y.reshape(b * s, d), lp["w_router"],
                 lp["w_gate"], lp["w_up"], lp["w_down"],
                 cfg.top_k).reshape(b, s, d)
        with scope("moe.combine"):
            return x + y2

    with scope("mlp"):
        if cfg.gated:
            y = L.rmsnorm(x, lp["norm2"])
            if cfg.mlp_dtype in ("float8", "int8"):
                mlp_fn = functools.partial(
                    L.quantized_swiglu, mlp_dtype=cfg.mlp_dtype,
                    quant_fusion=cfg.quant_fusion,
                    int8_backward=cfg.int8_backward)
            else:
                mlp_fn = L.swiglu
            y2 = mlp_fn(y, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            y = L.layernorm(x, lp["norm2"], lp["norm2_b"])
            y2 = L.gelu_mlp(y, lp["w_in"], lp["b_in"], lp["w_out"],
                            lp["b_out"])
        return x + y2


def forward(params: dict, tokens, cfg: TransformerConfig):
    """tokens [B, S] int32 -> logits [B, S, V]."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    with scope("embed"):
        x = params["embed"][tokens]
        if cfg.max_positions:
            x = x + params["pos_embed"][positions][None]

    block = _block
    if cfg.remat:
        block = jax.checkpoint(_block, static_argnums=(0,))

    if cfg.scan_layers:
        def body(carry, lp):
            return block(cfg, carry, lp, positions), None

        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for li in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[li], params["layers"])
            x = block(cfg, x, lp, positions)
    with scope("head_loss"):
        if cfg.gated:
            x = L.rmsnorm(x, params["final_norm"])
        else:
            x = L.layernorm(x, params["final_norm"],
                            params["final_norm_b"])
        head = params["embed"].T if cfg.tied_embeddings else params["head"]
        return jnp.dot(x, head,
                       preferred_element_type=(
                           jnp.float32 if cfg.logits_f32 else x.dtype))


def loss_fn(params: dict, tokens, cfg: TransformerConfig):
    """Next-token cross-entropy on a [B, S+1] token batch."""
    logits = forward(params, tokens[:, :-1], cfg)
    with scope("head_loss"):
        return L.cross_entropy(logits, tokens[:, 1:])
