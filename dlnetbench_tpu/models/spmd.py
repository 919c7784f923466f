"""Fully-sharded training step: dp x pp x tp(+sp,+ep) on one mesh.

This is the real-compute counterpart of the hybrid proxies — one manual
``shard_map`` program over a (dp, pp, tp) mesh implementing, with actual
math, every parallelism family the proxies replay as traffic (SURVEY.md
§2.5) plus the sequence dimension the reference lacks:

  dp  batch sharding; gradient psum over the dp axis
      (the proxies' dp allreduce, reference dp.cpp:87-106)
  pp  GPipe: layers split into stages, microbatches streamed with
      ``ppermute``; stage s works on microbatch t-s at tick t, bubbles
      masked (the hybrid_2d schedule, reference hybrid_2d.cpp:90-169)
  tp  Megatron attention/head sharding: column-parallel QKV, row-parallel
      output proj with psum_scatter (the hybrid_3d TP allreduces,
      reference hybrid_3d.cpp:142-148)
  sp  Megatron-style sequence parallelism: activations between blocks are
      sequence-sharded over the tp axis; all_gather to enter attention,
      psum_scatter to leave (no reference counterpart — SURVEY.md §5.7)
  ep  GShard/Mixtral expert parallelism: capacity-based top-k dispatch via
      one-hot matmuls, experts sharded over the tp axis, all_to_all to
      dispatch and combine (the hybrid_3d_moe A2As, reference
      hybrid_3d_moe.cpp:161-165)

Backward is ``jax.grad`` *through the collectives* (XLA transposes
ppermute/psum/all_to_all), then gradients are psum'd over every mesh axis
a parameter is replicated on.  ``chip_smoke.py --chips 4`` runs this
step on four chips against the single-device step; tests/test_spmd.py
runs it on the virtual CPU mesh.

r7 overlap layer (docs/PERF.md round 7): ``tp_overlap="decomposed"``
replaces the blocking TP collectives with ppermute-pipelined collective
matmuls (ops/collective_matmul.py, forward and backward);
``grad_sync="bucketed"`` streams the DP grad psums per layer group in
reverse-layer order during backward instead of one end-of-step psum; and
``make_train_step(variant=...)`` provides the compute-only / comm-only
legs of the proxy tier's A/B decomposition for the real step, feeding
the measured overlap-fraction metric (metrics/stats.overlap_fraction,
models/overlap_bench.py).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from dlnetbench_tpu.utils.jax_compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dlnetbench_tpu import ops
from dlnetbench_tpu.models import layers as Lyr
from dlnetbench_tpu.ops import collective_matmul as CM
from dlnetbench_tpu.ops import sequence_parallel as SP
from dlnetbench_tpu.parallel import collectives as col
from dlnetbench_tpu.parallel.mesh import AXIS_DP, AXIS_PP, AXIS_TP, make_grid_mesh

_F32 = jnp.float32

# A/B decomposition variants of the train step (proxies/base.py timing
# protocol applied to the real-compute tier): "compute" strips every
# collective (local shape-preserving stand-ins), "comm" strips the heavy
# math (broadcast stubs with the same dataflow edges) — so the measured
# overlap fraction (metrics/stats.overlap_fraction) has its Tc and Tm.
VARIANTS = ("full", "compute", "comm")


@dataclasses.dataclass(frozen=True)
class SpmdConfig:
    vocab_size: int = 128
    embed_dim: int = 64
    num_heads: int = 4
    num_kv_heads: int = 4
    ff_dim: int = 128
    num_layers: int = 4          # total; split over pp
    seq_len: int = 32            # split over tp (sequence parallelism)
    num_experts: int = 4         # split over tp (expert parallelism)
    top_k: int = 2
    capacity_factor: float = 2.0
    batch: int = 8               # split over dp
    num_microbatches: int = 2
    lr: float = 0.1
    dtype: str = "float32"       # bfloat16 on real TPU
    attention_impl: str = "auto"   # ops.attention dispatch: auto | flash | xla
    mlp_int8: bool = False       # run the three expert matmuls in int8
                             # (per-tensor scales, int32 MXU accumulation,
                             # straight-through backward —
                             # ops/int8.py int8_dot_batched): the r5
                             # single-chip 1.087x step win extended to
                             # the EP-sharded MoE path; the dispatch/
                             # combine all_to_alls and the router stay
                             # master-dtype
    # How attention handles the sequence sharding on the tp axis:
    #   megatron  gather the sequence, shard the heads (2 collectives per
    #             block: all_gather in, psum_scatter out) — the reference's
    #             hybrid_3d TP pattern re-expressed (hybrid_3d.cpp:142-148)
    #   ring      keep the sequence sharded; rotate KV around the axis with
    #             ppermute, online-softmax merge (ops/sequence_parallel.py)
    #             — heads replicated, attention weights replicated over tp
    #   ulysses   all_to_all to head-sharding and back; full-sequence local
    #             attention in between (flash kernel eligible)
    sp_mode: str = "megatron"
    # Long-context attention-mask knobs (ISSUE 10; the TransformerConfig
    # trio mirrored): a sliding window and/or a seeded document-segment
    # plan.  megatron/ulysses modes apply the mask on the gathered
    # sequence (splash kernels on TPU, dense-masked reference on the
    # CPU mesh); ring mode additionally SKIPS whole ring hops whose
    # (my queries x remote keys) tile the mask kills — the ppermute
    # still runs, and the skipped-hop fraction is reported via
    # ``ring_hop_stats`` (the overlap-fraction metric's sibling).
    attention_window: int = 0
    attention_seg_avg: int = 0
    attention_seg_seed: int = 0
    # How the TP-block collectives execute (megatron QKV/out projections
    # and the vocab-parallel head):
    #   none        blocking all_gather / psum_scatter around plain dots
    #   decomposed  ppermute-pipelined collective matmuls
    #               (ops/collective_matmul.py): the gather/scatter is
    #               broken into ring chunks interleaved with the
    #               dependent matmul, forward AND backward (custom VJPs)
    tp_overlap: str = "none"
    # row chunks per ring block (overlap grain).  None = consult the
    # tuning DB (dlnetbench_tpu/tuning, keyed per shape x tp x chip)
    # and fall back to the frozen default 2 on a miss — an explicit
    # int ALWAYS wins (resolve_tuned; resolved in make_train_step)
    tp_overlap_chunks: int | None = None
    # DP gradient sync schedule:
    #   monolithic  one psum of the whole grad tree after backward
    #   bucketed    per-layer-group psums issued in reverse-layer order,
    #               chained with collectives.tie so each bucket's sync
    #               streams as soon as its grads materialize (ZeRO/FSDP
    #               bucketing, the dp proxy's schedule made real)
    grad_sync: str = "monolithic"
    # local layers per bucket.  None = tuning-DB consult, frozen
    # default 1 on a miss; explicit ints always win (resolve_tuned)
    grad_bucket_layers: int | None = None
    # --- ISSUE 15: expert-parallel MoE knobs -------------------------
    # How the EP dispatch/combine all-to-alls execute:
    #   monolithic  blocking lax.all_to_all pair around the expert FFN
    #               (the pre-ISSUE-15 spelling, bit-identical)
    #   decomposed  ppermute chunk loop fused with the expert FFN
    #               (ops/moe_dispatch.a2a_expert_ffn): each peer
    #               block's dispatch hop / expert compute / combine
    #               hop interleave, forward AND backward (custom VJP)
    moe_a2a: str = "monolithic"
    # FFN capacity-axis chunks per peer block (decomposed overlap
    # grain — the moe sibling of tp_overlap_chunks)
    moe_chunks: int = 1
    # Token-drop determinism (models/moe.py): None keeps the legacy
    # per-rank arrival-order drop (bit-identical); an int switches to
    # the seeded priority over GLOBAL token ids, which (with
    # moe_group_tokens) makes the kept/dropped set identical across
    # shard counts — the dryrun's token-identical-routing bar
    moe_drop_seed: int | None = None
    # Capacity-group size in tokens (0 = this rank's whole per-tick
    # buffer, the legacy semantics).  Must divide the sequence shard
    # (seq_len/tp) so groups never straddle shard boundaries
    moe_group_tokens: int = 0
    # Expert FFN implementation: "einsum" (XLA batched einsums, the
    # legacy spelling) | "grouped" (Pallas grouped-matmul kernels,
    # ops/grouped_matmul.py — block shapes a tuning-DB site)
    moe_ffn_impl: str = "einsum"
    # Fused-quantization recipe for the grouped expert FFN ("none" |
    # "int8" | "float8"): per-expert dynamic scales quantize the
    # activation tile in the kernel's VMEM prologue (the PR-3 recipe);
    # requires moe_ffn_impl="grouped" and excludes mlp_int8 (two
    # quant recipes on one matmul would measure neither)
    moe_ffn_quant: str = "none"

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def resolve_tuned(self, dp: int, pp: int, tp: int) -> "SpmdConfig":
        """Concrete overlap-grain / bucket-size knobs: explicit user
        values pass through untouched; ``None`` fields consult the
        tuning DB (dlnetbench_tpu/tuning — frozen after first consult)
        and fall back to the frozen defaults (chunks=2, bucket=1) on a
        miss, so an empty DB is bit-identical to the pre-tuning
        harness.  A knob whose mode is off (``tp_overlap='none'`` /
        ``grad_sync='monolithic'``) resolves straight to its default
        WITHOUT a consult — the compiled program doesn't depend on it,
        and a logged "hit" on an inert knob would stamp tuned
        provenance onto a bit-identical-to-untuned run.  Returns self
        when nothing needed resolving."""
        chunks, bucket = self.tp_overlap_chunks, self.grad_bucket_layers
        if chunks is None or bucket is None:
            from dlnetbench_tpu import tuning

            def positive(field):
                def check(cfg):
                    v = cfg.get(field)
                    if not isinstance(v, int) or v < 1:
                        raise ValueError(f"{field}={v!r} is not a "
                                         f"positive int")
                return check
            if chunks is None:
                if self.tp_overlap != "decomposed":
                    chunks = 2   # inert knob: frozen default, no consult
                else:
                    chunks = tuning.consult(
                        "tp_overlap_chunks",
                        tuning.params.tp_overlap_chunks_key(
                            self.embed_dim, self.ff_dim, self.seq_len,
                            tp, self.dtype),
                        {"chunks": 2},
                        validate=positive("chunks"))["chunks"]
            if bucket is None:
                if self.grad_sync != "bucketed":
                    bucket = 1   # inert knob: frozen default, no consult
                else:
                    bucket = tuning.consult(
                        "grad_bucket_layers",
                        tuning.params.grad_bucket_layers_key(
                            self.num_layers, dp, pp, self.embed_dim,
                            self.ff_dim),
                        {"layers": 1},
                        validate=positive("layers"))["layers"]
        if (chunks, bucket) == (self.tp_overlap_chunks,
                                self.grad_bucket_layers):
            return self
        return dataclasses.replace(self, tp_overlap_chunks=chunks,
                                   grad_bucket_layers=bucket)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def mask_spec(self):
        """The attention MaskSpec these knobs declare, or None for the
        dense-causal default (bit-identical pre-mask behavior) — the
        shared TransformerConfig mapping (MaskSpec.from_knobs)."""
        from dlnetbench_tpu.ops.attention_mask import MaskSpec
        return MaskSpec.from_knobs(self.attention_window,
                                   self.attention_seg_avg,
                                   self.attention_seg_seed)

    def ring_hop_stats(self, tp: int) -> dict:
        """Skipped-hop accounting for sp_mode='ring' on a tp-wide ring
        (host-side, plan-derived — the record stamps it next to the
        mask globals; ops/attention_mask.ring_skipped_hop_fraction)."""
        from dlnetbench_tpu.ops import attention_mask as amask
        frac = amask.ring_skipped_hop_fraction(self.mask_spec,
                                               self.seq_len, tp)
        return {"ring_hops": tp * tp,
                "ring_skipped_hop_fraction": round(frac, 6)}

    def validate(self, dp: int, pp: int, tp: int) -> None:
        # ring keeps all heads local, so head divisibility only binds the
        # modes that shard heads over tp (megatron statically, ulysses via
        # its all_to_all)
        heads_sharded = self.sp_mode in ("megatron", "ulysses")
        checks = [
            (self.sp_mode in ("megatron", "ring", "ulysses"),
             f"unknown sp_mode {self.sp_mode!r}"),
            (self.tp_overlap in ("none", "decomposed"),
             f"unknown tp_overlap {self.tp_overlap!r}"),
            (self.tp_overlap_chunks is None or self.tp_overlap_chunks >= 1,
             "tp_overlap_chunks < 1"),
            (self.grad_sync in ("monolithic", "bucketed"),
             f"unknown grad_sync {self.grad_sync!r}"),
            (self.grad_bucket_layers is None or
             self.grad_bucket_layers >= 1, "grad_bucket_layers < 1"),
            (self.attention_window >= 0, "attention_window < 0"),
            (self.attention_seg_avg >= 0, "attention_seg_avg < 0"),
            (self.moe_a2a in ("monolithic", "decomposed"),
             f"unknown moe_a2a {self.moe_a2a!r}"),
            (self.moe_chunks >= 1, "moe_chunks < 1"),
            (self.moe_group_tokens >= 0, "moe_group_tokens < 0"),
            (self.moe_group_tokens == 0
             or (self.seq_len // tp) % self.moe_group_tokens == 0,
             f"moe_group_tokens {self.moe_group_tokens} must divide "
             f"the sequence shard {self.seq_len // tp} (groups may "
             f"not straddle shard boundaries)"),
            (self.moe_ffn_impl in ("einsum", "grouped"),
             f"unknown moe_ffn_impl {self.moe_ffn_impl!r}"),
            (self.moe_ffn_quant in ("none", "int8", "float8"),
             f"unknown moe_ffn_quant {self.moe_ffn_quant!r}"),
            (self.moe_ffn_quant == "none"
             or self.moe_ffn_impl == "grouped",
             "moe_ffn_quant requires moe_ffn_impl='grouped' (the "
             "fused recipes live in the grouped kernel)"),
            (not (self.mlp_int8 and self.moe_ffn_impl == "grouped"),
             "mlp_int8 and moe_ffn_impl='grouped' are two quant "
             "recipes on one matmul — pick one"),
            (self.num_layers % pp == 0, "layers % pp != 0"),
            (self.batch % (dp * self.num_microbatches) == 0,
             "batch % (dp*microbatches) != 0"),
            (self.seq_len % tp == 0, "seq_len % tp != 0 (sp sharding)"),
            (not heads_sharded or self.num_heads % tp == 0,
             "heads % tp != 0"),
            (not heads_sharded or self.num_kv_heads % tp == 0,
             "kv_heads % tp != 0"),
            (self.num_experts % tp == 0, "experts % tp != 0 (ep sharding)"),
            (self.vocab_size % tp == 0, "vocab % tp != 0 (parallel head)"),
        ]
        for ok, what in checks:
            if not ok:
                raise ValueError(f"SpmdConfig invalid for mesh "
                                 f"({dp},{pp},{tp}): {what}")


# --------------------------------------------------------------------- #
# Parameter init + sharding specs (GLOBAL shapes; specs map to the mesh)
# --------------------------------------------------------------------- #


def init_params(key, cfg: SpmdConfig) -> dict:
    d, dh = cfg.embed_dim, cfg.head_dim
    dkv = cfg.num_kv_heads * dh
    h, L, v, e = cfg.ff_dim, cfg.num_layers, cfg.vocab_size, cfg.num_experts
    dt = cfg.jdtype
    s_d, s_h = 1.0 / math.sqrt(d), 1.0 / math.sqrt(h)
    ks = iter(jax.random.split(key, 16))
    return {
        "embed": Lyr.init_dense(next(ks), (v, d), 1.0, dt),
        "layers": {
            "wq": Lyr.init_dense(next(ks), (L, d, d), s_d, dt),
            "wk": Lyr.init_dense(next(ks), (L, d, dkv), s_d, dt),
            "wv": Lyr.init_dense(next(ks), (L, d, dkv), s_d, dt),
            "wo": Lyr.init_dense(next(ks), (L, d, d), s_d, dt),
            "norm1": jnp.ones((L, d), dt),
            "norm2": jnp.ones((L, d), dt),
            "w_router": Lyr.init_dense(next(ks), (L, d, e), s_d, dt),
            "w_gate": Lyr.init_dense(next(ks), (L, e, d, h), s_d, dt),
            "w_up": Lyr.init_dense(next(ks), (L, e, d, h), s_d, dt),
            "w_down": Lyr.init_dense(next(ks), (L, e, h, d), s_h, dt),
        },
        "final_norm": jnp.ones((d,), dt),
        "head": Lyr.init_dense(next(ks), (d, v), s_d, dt),
    }


def param_specs(sp_mode: str = "megatron") -> dict:
    """PartitionSpec per leaf: layer stack over pp; Megatron TP on qkv/o
    (megatron mode) or attention weights replicated over tp (ring/ulysses,
    which shard activations, not weights); experts over tp (ep); parallel
    head over tp on vocab."""
    if sp_mode == "megatron":
        wq = wk = wv = P(AXIS_PP, None, AXIS_TP)   # column parallel
        wo = P(AXIS_PP, AXIS_TP, None)             # row parallel
    else:
        wq = wk = wv = wo = P(AXIS_PP, None, None)
    return {
        "embed": P(),                              # replicated
        "layers": {
            "wq": wq,
            "wk": wk,
            "wv": wv,
            "wo": wo,
            "norm1": P(AXIS_PP, None),
            "norm2": P(AXIS_PP, None),
            "w_router": P(AXIS_PP, None, None),
            "w_gate": P(AXIS_PP, AXIS_TP, None, None),   # expert sharded
            "w_up": P(AXIS_PP, AXIS_TP, None, None),
            "w_down": P(AXIS_PP, AXIS_TP, None, None),
        },
        "final_norm": P(),
        "head": P(None, AXIS_TP),                  # parallel vocab head
    }


def param_shardings(mesh: Mesh, sp_mode: str = "megatron") -> dict:
    """NamedSharding per parameter — e.g. a checkpoint-restore template
    (utils/checkpoint.py) that lands each shard on its mesh device."""
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                        param_specs(sp_mode),
                        is_leaf=lambda x: isinstance(x, P))


def _replicated_axes(spec: P) -> tuple:
    """Mesh axes (excluding dp, which every grad is already mean-reduced
    over) that a parameter is replicated across — its gradient must be
    psum'd over exactly these."""
    used = {a for part in spec if part
            for a in ((part,) if isinstance(part, str) else part)}
    return tuple(a for a in (AXIS_PP, AXIS_TP) if a not in used)


# --------------------------------------------------------------------- #
# Per-device (shard_map) forward
# --------------------------------------------------------------------- #
def _local_a2a(x, tp: int, split_axis: int, concat_axis: int):
    """Shape-equivalent local stand-in for a tiled all_to_all (compute
    A/B variant: same output shape, zero wire traffic)."""
    parts = jnp.split(x, tp, axis=split_axis)
    return jnp.concatenate(parts, axis=concat_axis)


def _moe_block(cfg: SpmdConfig, tp: int, y, lp, gids, comm_on=True,
               compute_on=True):
    """y: [mb, S/tp, d] local tokens; experts sharded over tp (EP).

    ``gids``: [mb, S/tp] GLOBAL token ids — the seeded drop priority's
    domain (models/moe.py), so routing is identical however the batch
    is sharded.  Routing dispatches through ``models/moe.dispatch``
    (legacy knobs delegate to ``layers.moe_dispatch`` bit-identically),
    which gathers the local tokens' rows into ``ein`` and returns the
    routing as a ``layers.MoePlan`` of indices; ``layers.moe_combine``
    gathers the experts' rows back through the same plan.
    The a2a pair runs blocking (``moe_a2a="monolithic"``) or as the
    ppermute chunk loop fused with the expert FFN
    (``"decomposed"`` — ops/moe_dispatch.a2a_expert_ffn, the
    hybrid_3d_moe dispatch/combine A2As overlapped)."""
    from dlnetbench_tpu.models import moe as MoE
    from dlnetbench_tpu.ops import moe_dispatch as MD
    mb, s_loc, d = y.shape
    t = mb * s_loc
    x2 = y.reshape(t, d)
    # the shared expert-FFN dispatch point (models/moe.py) with this
    # config's switches bound: einsum (bit-identical legacy spelling,
    # incl. the r5 mlp_int8 recipe) or the grouped Pallas kernels
    ffn = partial(
        MoE.expert_ffn, impl=cfg.moe_ffn_impl,
        quant=None if cfg.moe_ffn_quant == "none" else cfg.moe_ffn_quant,
        mlp_int8=cfg.mlp_int8)
    if compute_on:
        ein, plan, gate = MoE.dispatch(
            x2, lp["w_router"], cfg.num_experts, cfg.top_k,
            cfg.capacity_factor, drop_seed=cfg.moe_drop_seed,
            group_tokens=cfg.moe_group_tokens, gids=gids.reshape(t))
    else:   # comm variant: dispatch stubbed, buffer shapes preserved
        g = cfg.moe_group_tokens or t
        c_total = (t // g) * MoE.group_capacity(
            g, cfg.top_k, cfg.num_experts, cfg.capacity_factor)
        ein = CM.comm_stub((cfg.num_experts, c_total, d), x2.dtype, x2,
                           lp["w_router"])
        plan = gate = None
    if cfg.moe_a2a == "decomposed" and tp > 1:
        # dispatch a2a + expert FFN + combine a2a as ONE fused
        # ppermute chunk loop — each peer block's hops overlap the
        # blocks already computing, forward and backward
        out = MD.a2a_expert_ffn(
            ein.astype(cfg.jdtype), lp["w_gate"], lp["w_up"],
            lp["w_down"], AXIS_TP, ffn, chunks=cfg.moe_chunks,
            fake_compute=not compute_on, fake_comm=not comm_on)
    else:
        # EP all_to_all: [E, C, d] -> [E/tp, C*tp, d] (each rank gets
        # its experts' tokens from every peer — the hybrid_3d_moe
        # dispatch A2A)
        if tp > 1:
            ein = (lax.all_to_all(ein, AXIS_TP, split_axis=0,
                                  concat_axis=1, tiled=True) if comm_on
                   else _local_a2a(ein, tp, 0, 1))
        ein = ein.astype(cfg.jdtype)
        if not compute_on:
            out = CM.comm_stub(ein.shape, _F32, ein, lp["w_gate"],
                               lp["w_up"], lp["w_down"])
        else:
            out = ffn(ein, lp["w_gate"], lp["w_up"], lp["w_down"])
        if tp > 1:  # combine A2A (reverse reshard)
            out = (lax.all_to_all(out, AXIS_TP, split_axis=1,
                                  concat_axis=0, tiled=True) if comm_on
                   else _local_a2a(out, tp, 1, 0))
    if compute_on:
        y2 = Lyr.moe_combine(out, plan, gate)
    else:
        y2 = CM.comm_stub((t, d), _F32, out)
    return y2.reshape(mb, s_loc, d).astype(y.dtype)


def _stage_block(cfg: SpmdConfig, tp: int, x, lp, positions, gids,
                 comm_on=True, compute_on=True):
    """One decoder block under TP+SP; x: [mb, S/tp, d] sequence-sharded.

    ``positions``: the GLOBAL positions matching the sequence length rope
    sees — the full [S] in megatron mode (rope runs after the gather),
    this shard's [S/tp] slice in ring/ulysses mode (rope runs locally).
    ``gids``: [mb, S/tp] global token ids for the seeded MoE drop
    priority (models/moe.py — shard-layout invariant routing).
    """
    mb, s_loc, d = x.shape
    dh = cfg.head_dim
    decomposed = cfg.tp_overlap == "decomposed"

    y = Lyr.rmsnorm(x, lp["norm1"])
    if cfg.sp_mode == "megatron" and tp > 1:
        # gather the full sequence, shard the heads (Megatron SP)
        h_loc = cfg.num_heads // tp
        hkv_loc = cfg.num_kv_heads // tp
        qw, kvw = h_loc * dh, hkv_loc * dh
        if decomposed:
            # collective matmul: the gather rides the QKV projection as
            # ppermute-pipelined chunks (one fused weight so a single
            # ring serves all three column-parallel projections —
            # concatenated ONCE per step outside the layer scan by
            # local_loss, not per layer per microbatch here)
            qkv = CM.all_gather_matmul(
                y, lp["w_qkv"], AXIS_TP, gather_axis=1,
                chunks=cfg.tp_overlap_chunks,
                fake_compute=not compute_on, fake_comm=not comm_on)
            s_full = qkv.shape[1]
            q = qkv[..., :qw].reshape(mb, s_full, h_loc, dh)
            k = qkv[..., qw:qw + kvw].reshape(mb, s_full, hkv_loc, dh)
            v = qkv[..., qw + kvw:].reshape(mb, s_full, hkv_loc, dh)
        else:
            y = (lax.all_gather(y, AXIS_TP, axis=1, tiled=True)
                 if comm_on else jnp.concatenate([y] * tp, axis=1))
            s_full = y.shape[1]
            if compute_on:
                q = jnp.dot(y, lp["wq"]).reshape(mb, s_full, h_loc, dh)
                k = jnp.dot(y, lp["wk"]).reshape(mb, s_full, hkv_loc, dh)
                v = jnp.dot(y, lp["wv"]).reshape(mb, s_full, hkv_loc, dh)
            else:
                q = CM.comm_stub((mb, s_full, h_loc, dh), y.dtype, y,
                                 lp["wq"])
                k = CM.comm_stub((mb, s_full, hkv_loc, dh), y.dtype, y,
                                 lp["wk"])
                v = CM.comm_stub((mb, s_full, hkv_loc, dh), y.dtype, y,
                                 lp["wv"])
        if compute_on:
            q, k = Lyr.rope(q, k, positions)
            att = ops.attention(q, k, v, causal=True,
                                impl=cfg.attention_impl,
                                mask=cfg.mask_spec).reshape(
                mb, s_full, d // tp)
        else:
            att = CM.comm_stub((mb, s_full, d // tp), q.dtype, q, k, v)
        if decomposed:
            # reduce partials and scatter back to sequence shards, the
            # ring way: each hop overlaps the next block's partial matmul
            out = CM.matmul_reduce_scatter(
                att, lp["wo"], AXIS_TP, scatter_axis=1,
                chunks=cfg.tp_overlap_chunks,
                fake_compute=not compute_on, fake_comm=not comm_on)
        else:
            out = (jnp.dot(att, lp["wo"]) if compute_on
                   else CM.comm_stub((mb, s_full, d), att.dtype, att,
                                     lp["wo"]))         # partial sums
            # reduce partials and scatter back to sequence shards
            out = (lax.psum_scatter(out, AXIS_TP, scatter_dimension=1,
                                    tiled=True) if comm_on
                   else lax.slice_in_dim(out, 0, s_loc, axis=1))
    elif not compute_on:
        # comm variant reaching here means tp == 1 (the megatron-only
        # variant guard): the block has no collectives at all — stub it
        out = CM.comm_stub((mb, s_loc, d), x.dtype, y, lp["wq"],
                           lp["wo"])
    else:
        # sequence stays sharded: project this shard with ALL heads
        # (attention weights replicated over tp in these modes)
        q = jnp.dot(y, lp["wq"]).reshape(mb, s_loc, cfg.num_heads, dh)
        k = jnp.dot(y, lp["wk"]).reshape(mb, s_loc, cfg.num_kv_heads, dh)
        v = jnp.dot(y, lp["wv"]).reshape(mb, s_loc, cfg.num_kv_heads, dh)
        q, k = Lyr.rope(q, k, positions)
        if tp > 1 and cfg.sp_mode == "ring":
            att = SP.ring_attention(q, k, v, AXIS_TP, causal=True,
                                    spec=cfg.mask_spec)
        elif tp > 1 and cfg.sp_mode == "ulysses":
            att = SP.ulysses_attention(q, k, v, AXIS_TP, causal=True,
                                       impl=cfg.attention_impl,
                                       spec=cfg.mask_spec)
        else:   # tp == 1: plain local attention
            att = ops.attention(q, k, v, causal=True,
                                impl=cfg.attention_impl,
                                mask=cfg.mask_spec)
        out = jnp.dot(att.reshape(mb, s_loc, d), lp["wo"])
    x = x + out

    y = Lyr.rmsnorm(x, lp["norm2"])
    return x + _moe_block(cfg, tp, y, lp, gids, comm_on, compute_on)


def _vocab_parallel_ce(logits_loc, targets, tp: int, vocab: int,
                       comm_on=True):
    """Megatron-style vocab-parallel cross entropy.

    ``logits_loc``: [..., V/tp] — this rank's vocab shard of the logits for
    the FULL (gathered) token set; ``targets``: [...] global vocab ids.
    Softmax normalization and the target logit are assembled with
    pmax/psum over the tp axis; every rank returns the same scalar.
    """
    v_loc = logits_loc.shape[-1]
    shard = lax.axis_index(AXIS_TP)
    lg = logits_loc.astype(_F32)
    # the max shift is numerical stabilization only — constant wrt autodiff
    m = jnp.max(lax.stop_gradient(lg), axis=-1)
    gmax = lax.pmax(m, AXIS_TP) if comm_on else m
    sumexp = jnp.sum(jnp.exp(lg - gmax[..., None]), axis=-1)
    denom = lax.psum(sumexp, AXIS_TP) if comm_on else sumexp
    local_t = targets - shard * v_loc
    in_range = (local_t >= 0) & (local_t < v_loc)
    tval = jnp.take_along_axis(
        lg, jnp.clip(local_t, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
    tval = jnp.where(in_range, tval, 0.0)
    if comm_on:
        tval = lax.psum(tval, AXIS_TP)
    return jnp.mean(jnp.log(denom) + gmax - tval)


def _bucketed_grad_sync(cfg: SpmdConfig, grads: dict, specs: dict,
                        dp: int, pp: int):
    """ZeRO/FSDP-style bucketed DP grad sync: per-layer-group psums in
    reverse-layer order (later layers' grads materialize first in
    backward), each bucket ``tie``-d to the previous bucket's result so
    XLA streams the syncs during backward instead of fusing them into
    one end-of-step collective.  Elementwise-identical math to the
    monolithic path (psum commutes with slicing)."""
    def sync_leaf(g, sp):
        g = lax.psum(g, AXIS_DP) / dp
        rep = _replicated_axes(sp)
        return lax.psum(g, rep) if rep else g

    is_p = lambda x: isinstance(x, P)  # noqa: E731
    dep = None

    def sync_part(part, spec_part):
        nonlocal dep
        if dep is not None:
            part = jax.tree.map(lambda g: col.tie(g, dep), part)
        out = jax.tree.map(sync_leaf, part, spec_part, is_leaf=is_p)
        dep = jax.tree.leaves(out)[0]
        return out

    # head + final_norm first: their grads are ready at the start of
    # backward; then layer groups last-to-first; embed's grads complete
    # only when backward finishes, so its bucket goes last
    tail = sync_part({"head": grads["head"],
                      "final_norm": grads["final_norm"]},
                     {"head": specs["head"],
                      "final_norm": specs["final_norm"]})
    layers_local = cfg.num_layers // pp
    step_l = min(cfg.grad_bucket_layers, layers_local)
    bounds = list(range(0, layers_local, step_l)) + [layers_local]
    slices = {}
    for b in reversed(range(len(bounds) - 1)):
        lo, hi = bounds[b], bounds[b + 1]
        part = {k: v[lo:hi] for k, v in grads["layers"].items()}
        slices[b] = sync_part(part, specs["layers"])
    head_bucket = sync_part({"embed": grads["embed"]},
                            {"embed": specs["embed"]})
    layers = {k: jnp.concatenate([slices[b][k]
                                  for b in range(len(bounds) - 1)], axis=0)
              for k in grads["layers"]}
    return {"embed": head_bucket["embed"], "layers": layers,
            "final_norm": tail["final_norm"], "head": tail["head"]}


def make_train_step(mesh: Mesh, cfg: SpmdConfig, variant: str = "full"):
    dp, pp, tp = (mesh.devices.shape[mesh.axis_names.index(a)]
                  for a in (AXIS_DP, AXIS_PP, AXIS_TP))
    # tuned-or-default knob resolution FIRST (explicit values pass
    # through; dlnetbench_tpu/tuning) so everything below — including
    # validate — sees concrete ints
    cfg = cfg.resolve_tuned(dp, pp, tp)
    cfg.validate(dp, pp, tp)
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of {VARIANTS}")
    comm_on = variant != "compute"
    compute_on = variant != "comm"
    if variant != "full" and cfg.sp_mode != "megatron":
        raise ValueError(
            "A/B decomposition variants are defined for sp_mode='megatron' "
            "(ring/ulysses interleave comm and compute inside "
            "ops/sequence_parallel.py, where the split has no meaning)")
    specs = param_specs(cfg.sp_mode)
    mb_size = cfg.batch // (dp * cfg.num_microbatches)
    m = cfg.num_microbatches

    def local_loss(params_loc, tokens_loc):
        """Per-device pipeline forward; tokens_loc: [B/dp, S+1]."""
        stage = lax.axis_index(AXIS_PP)
        tp_idx = lax.axis_index(AXIS_TP)
        s_loc = cfg.seq_len // tp
        inputs = tokens_loc[:, :-1].reshape(m, mb_size, cfg.seq_len)
        targets = tokens_loc[:, 1:].reshape(m, mb_size, cfg.seq_len)
        # rope positions: full sequence in megatron mode (rope follows the
        # gather), this shard's global slice in ring/ulysses mode
        if cfg.sp_mode == "megatron":
            positions = jnp.arange(cfg.seq_len)
        else:
            positions = tp_idx * s_loc + jnp.arange(s_loc)

        layers_xs = params_loc["layers"]
        if (cfg.tp_overlap == "decomposed" and cfg.sp_mode == "megatron"
                and tp > 1):
            # fuse the stacked column-parallel QKV weights ONCE per step
            # (autodiff splits the grad back through the concat): doing
            # this inside the scan body would copy the full QKV weight
            # per layer per microbatch — XLA cannot hoist a concat of
            # loop-carried slices out of a differentiated scan
            layers_xs = {**layers_xs,
                         "w_qkv": jnp.concatenate(
                             [layers_xs["wq"], layers_xs["wk"],
                              layers_xs["wv"]], axis=-1)}

        def run_stage(x, gids):
            def body(carry, lp):
                return _stage_block(cfg, tp, carry, lp, positions,
                                    gids, comm_on, compute_on), None
            out, _ = lax.scan(body, x, layers_xs)
            return out

        ticks = m + pp - 1
        x_carry = jnp.zeros((mb_size, s_loc, cfg.embed_dim), cfg.jdtype)
        loss_sum = jnp.zeros((), _F32)
        for t in range(ticks):
            mb_me = t - stage                       # my microbatch this tick
            mb_c = jnp.clip(mb_me, 0, m - 1)
            valid = (mb_me >= 0) & (mb_me < m)
            inp = lax.dynamic_index_in_dim(inputs, mb_c, 0, keepdims=False)
            # sequence shard for SP: my slice of the sequence
            inp_loc = lax.dynamic_slice_in_dim(inp, tp_idx * s_loc, s_loc, 1)
            emb = params_loc["embed"][inp_loc]      # [mb, S/tp, d]
            x_in = jnp.where(stage == 0, emb, x_carry)
            # global token ids of this rank's (microbatch, seq-shard)
            # block — the seeded MoE drop priority's domain: the same
            # token gets the same id on every mesh shape
            dp_idx = lax.axis_index(AXIS_DP)
            rows = (dp_idx * (cfg.batch // dp) + mb_c * mb_size
                    + jnp.arange(mb_size, dtype=jnp.int32))
            gids = (rows[:, None] * cfg.seq_len
                    + tp_idx * s_loc
                    + jnp.arange(s_loc, dtype=jnp.int32)[None, :])
            x_out = run_stage(x_in, gids)
            # last stage: loss for this tick's microbatch
            xh = Lyr.rmsnorm(x_out, params_loc["final_norm"])
            tgt = lax.dynamic_index_in_dim(targets, mb_c, 0, keepdims=False)
            if tp > 1:
                # gather the sequence so every rank scores all tokens
                # against its vocab shard, then vocab-parallel CE
                if cfg.tp_overlap == "decomposed":
                    # the gather rides the parallel-head projection as a
                    # decomposed collective matmul
                    logits_loc = CM.all_gather_matmul(
                        xh, params_loc["head"], AXIS_TP, gather_axis=1,
                        chunks=cfg.tp_overlap_chunks,
                        fake_compute=not compute_on,
                        fake_comm=not comm_on,
                        preferred_element_type=_F32)
                else:
                    xh = (lax.all_gather(xh, AXIS_TP, axis=1, tiled=True)
                          if comm_on
                          else jnp.concatenate([xh] * tp, axis=1))
                    logits_loc = (
                        jnp.dot(xh, params_loc["head"],
                                preferred_element_type=_F32) if compute_on
                        else CM.comm_stub(
                            xh.shape[:-1] + (params_loc["head"].shape[-1],),
                            _F32, xh, params_loc["head"]))
                # divided by tp: every tp rank computes the same replicated
                # scalar, so each seeds 1/tp of the cotangent — the psum
                # transposes inside the CE then deliver exactly 1 in total
                mb_loss = _vocab_parallel_ce(logits_loc, tgt, tp,
                                             cfg.vocab_size,
                                             comm_on) / tp
            else:
                logits = jnp.dot(xh, params_loc["head"],
                                 preferred_element_type=_F32)
                mb_loss = Lyr.cross_entropy(logits, tgt)
            is_last = stage == pp - 1
            loss_sum = loss_sum + jnp.where(valid & is_last, mb_loss, 0.0)
            # stream activations to the next stage
            if pp > 1 and comm_on:
                perm = [(i, i + 1) for i in range(pp - 1)]
                x_carry = lax.ppermute(x_out, AXIS_PP, perm)
            else:
                x_carry = x_out
        # LOCAL loss (nonzero only on the last stage; 1/tp share per tp
        # rank).  Deliberately NOT psum'd here: a psum inside the
        # differentiated function transposes to a broadcast that double
        # counts every rank's unit cotangent seed (grads would scale by
        # the axis size).  step_local psums the value for reporting.
        return loss_sum / m

    def step_local(params_loc, tokens_loc):
        loss, grads = jax.value_and_grad(local_loss)(params_loc, tokens_loc)
        if not comm_on:
            # compute variant: no sync, no loss reassembly — values are
            # wrong by construction, only the wall time is consumed
            new_params = jax.tree.map(
                lambda p_, g: p_ - cfg.lr * g.astype(p_.dtype),
                params_loc, grads)
            return new_params, loss
        if cfg.grad_sync == "bucketed":
            grads = _bucketed_grad_sync(cfg, grads, specs, dp, pp)
        else:
            # grad sync: psum over dp (data parallel, mean) ...
            grads = jax.tree.map(lambda g: lax.psum(g, AXIS_DP) / dp, grads)
            # ... and over every axis the param is replicated on
            # (transpose of the implicit broadcast in the manual-sharding
            # forward)
            grads = jax.tree.map(
                lambda g, sp: lax.psum(g, _replicated_axes(sp))
                if _replicated_axes(sp) else g,
                grads, specs, is_leaf=lambda x: isinstance(x, P))
        # reassemble the replicated loss value for reporting: sum the
        # last-stage / per-tp-rank shares, mean over dp groups
        loss = lax.psum(loss, (AXIS_PP, AXIS_TP))
        loss = lax.psum(loss, AXIS_DP) / dp
        new_params = jax.tree.map(lambda p_, g: p_ - cfg.lr * g.astype(p_.dtype),
                                  params_loc, grads)
        return new_params, loss

    in_specs = (specs, P(AXIS_DP, None))
    out_specs = (specs, P())
    fn = shard_map(step_local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def factor_mesh(n_devices: int) -> tuple[int, int, int]:
    """(dp, pp, tp) for an n-device dry run: prefer 2-way pp and tp."""
    tp = 2 if n_devices % 2 == 0 else 1
    pp = 2 if n_devices % (2 * tp) == 0 else 1
    dp = n_devices // (pp * tp)
    return dp, pp, tp


def build(n_devices: int | None = None, cfg: SpmdConfig | None = None,
          devices=None):
    """Convenience: mesh + params + tokens + jitted step."""
    devices = devices if devices is not None else jax.devices()
    n = n_devices or len(devices)
    dp, pp, tp = factor_mesh(n)
    mesh = make_grid_mesh(dp=dp, pp=pp, tp=tp, devices=devices[:n])
    cfg = cfg or SpmdConfig()
    step = make_train_step(mesh, cfg)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1),
                                (cfg.batch, cfg.seq_len + 1), 0,
                                cfg.vocab_size)
    return mesh, cfg, step, params, tokens
