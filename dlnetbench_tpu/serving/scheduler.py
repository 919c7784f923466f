"""Continuous-batching engine: the serving schedule worth reproducing.

The Orca/vLLM loop, measured honestly on the wall clock: requests
arrive on an OPEN-LOOP schedule (serving/arrivals.py — arrivals never
wait for the server), are admitted from the queue into free decode
slots whenever pages for their worst case (prompt + output) can be
reserved, prefill either as a separate phase at admit time or
inline-chunked one chunk per engine step, decode one token per active
slot per step over the paged KV cache, and evict on completion.  A
saturated engine builds a queue; TTFT p99 blows up — the knee
``examples/pod_study.py --serving`` sweeps for.

The host/device state split (ISSUE 11): the engine keeps HOST-side
scheduling state — the arrival queue, pending list, page free-list,
per-request stamps — while decode-phase slot state (last tokens,
positions, active/done bits, remaining budgets, block tables) lives on
DEVICE between syncs (``serving/device_state.py``) whenever
``multi_step_n > 1`` or speculative decode is on: one fused
``lax.while_loop`` program runs up to N decode steps (or draft/verify
rounds) per host dispatch, and the host crosses the boundary only at
admission points, every crossing a recorded timer.  ``multi_step_n=1``
without speculation keeps the classic one-dispatch-per-token engine
bit-identically (the loop program is not even built — locked by test).

Fault composition (the payoff of riding the existing record schema):
``run_serving`` takes the SAME fault plan the training tier uses —
``delay``/``jitter`` events sleep at engine-step boundaries inside the
measured loop (a straggler decode step inflates every in-flight
request's latency, which is what a straggler does to a serving fleet),
and a ``crash`` under policy ``shrink`` costs capacity: the engine
loses the dead rank's share of decode slots, in-flight requests are
re-queued on a rebuilt (recompiled — priced) engine with their ORIGINAL
arrival stamps, so the disruption lands in their latency and the
record's SLO-goodput timeline shows the dip and the recovery arc
(the segmentation mirrors ``faults/policy.run_faulted``).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from dlnetbench_tpu.core import executor
from dlnetbench_tpu.metrics import spans, telemetry
from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                               init_params)
from dlnetbench_tpu.serving import decode as D
from dlnetbench_tpu.serving import metrics as M
from dlnetbench_tpu.serving import requeue
from dlnetbench_tpu.serving.arrivals import ArrivalPlan, Request
from dlnetbench_tpu.serving.kv_cache import (CACHE_DTYPES, CacheConfig,
                                             PagedKVCache,
                                             device_buffers)

PREFILL_MODES = ("separate", "inline")


@dataclasses.dataclass
class ServingConfig:
    """Engine knobs (docs/SERVING.md documents the trade-offs)."""
    slots: int = 4              # decode slots = max continuous batch
    page_size: int = 8          # tokens per KV page
    num_pages: int = 64         # physical pages shared by all slots
    max_seq_len: int = 64       # per-request cap (prompt + output)
    prefill: str = "separate"   # "separate" (drain at admit) | "inline"
    prefill_chunk: int = 16     # prompt tokens per prefill program call
    slo_ttft_ms: float = 500.0
    slo_tpot_ms: float = 200.0
    world: int = 1              # capacity ranks (fault-shrink unit):
                                # slots are split evenly across ranks,
                                # a crashed rank takes its share down
    attn_impl: str = "auto"     # kv_cache.paged_attention_decode impl
    kv_shard: int = 1           # >1: shard_map along GQA KV heads over
                                # the first kv_shard devices
    multi_step_n: int = 1       # decode steps fused per host dispatch
                                # (ISSUE 11): 1 = the classic one-
                                # dispatch-per-token engine, BIT-
                                # identical by construction (the loop
                                # program is not even built); >1 runs
                                # up to N steps inside one compiled
                                # lax.while_loop with slot state on
                                # device, host sync at admission
                                # boundaries only
    adaptive_n: bool = True     # cap N by the shortest remaining
                                # output among active slots + queue
                                # pressure, so a fused loop never
                                # starves an admissible request (TTFT
                                # guard; docs/SERVING.md)
    speculative: bool = False   # self-drafting speculative decode
                                # inside the fused loop: draft spec_k
                                # tokens, verify in ONE batched target
                                # pass, accept on device — lossless
                                # under greedy (serving/speculative.py)
    spec_k: int = 4             # draft tokens per verify round
    drafter: str = "ngram"      # "ngram" (per-slot bigram table) |
                                # "truncated" (first drafter_layers
                                # layers of the target + shared head)
    drafter_layers: int = 1     # truncated drafter depth (must be
                                # < num_layers; checked at build)
    temperature: float = 0.0    # ISSUE 19: softmax temperature for
                                # on-device seeded sampling.  0.0 =
                                # greedy argmax (the sampler is not
                                # even built — bit-identical engine);
                                # > 0 samples every generated token
                                # in-graph, keyed by (sample_seed,
                                # request rid, position) — stateless,
                                # so N-step == 1-step bit-identically
                                # and crash re-queues replay tokens
    top_k: int = 0              # keep the k highest logits before the
                                # draw (0 = off; needs temperature>0)
    top_p: float = 1.0          # nucleus cutoff in (0, 1]; 1.0 = off
                                # (needs temperature > 0)
    sample_seed: int = 0        # the sampling stream seed (run
                                # identity — COMPARABLE at merge)
    grammar: str = ""           # "" = unconstrained; "json" masks
                                # every generated token through the
                                # JSON-mode automaton
                                # (serving/sampling.compile_grammar);
                                # composes with speculative (out-of-
                                # grammar drafts auto-reject) and
                                # with prefix_sharing
    cache_dtype: str = "bf16"   # paged-KV pool storage (ISSUE 12):
                                # "bf16" = unquantized (pools in the
                                # model dtype — the quant path is not
                                # even built, bit-identical engine);
                                # "int8"/"fp8" = quantized pools with
                                # per-page-per-head f32 scales — ~2x
                                # the pages per pool byte of a bf16
                                # cache (~4x of f32 CPU-mesh pools)
    prefix_sharing: bool = False  # cross-request prefix sharing
                                # (ISSUE 12): a radix trie over prompt
                                # tokens maps a new request's shared
                                # prefix onto a RESIDENT sequence's
                                # physical pages (refcounted, copy-on-
                                # write at the divergence page);
                                # admission charges only unshared
                                # pages and the shared prefix skips
                                # prefill (the TTFT win)
    moe_skew: float = 0.0       # ISSUE 15: seeded expert-skew
                                # injection — added to the router
                                # logits of a MoE model's decode path
                                # (serving/moe_decode.skew_bias), the
                                # imbalance-shaped sibling of the
                                # fault plans' seeded delays.  0.0 =
                                # no bias built (bit-identical
                                # routing).  COMPARABLE via
                                # serving_config: a skewed run must
                                # never merge with a balanced one
    moe_skew_seed: int = 0      # which experts the skew favors
    warmup_requests: int = 8    # run_serving drives this many synthetic
                                # requests through the engine BEFORE the
                                # measured run (0 disables): first-call
                                # dispatch/allocator warm-in must not
                                # ride the measured latencies — the
                                # run_proxy warmup discipline applied to
                                # the serving loop
    disaggregate: bool = False  # ISSUE 16: split the run into a
                                # prefill replica and a decode replica
                                # on DISJOINT device subsets
                                # (serving/disagg.run_disagg) — prompts
                                # prefill into the prefill replica's
                                # local pool and the finished pages
                                # migrate decode-ward in their stored
                                # dtype.  COMPARABLE at merge: a
                                # disaggregated record never merges
                                # with a monolithic one
    prefill_ranks: int = 1      # disaggregate: device ranks
                                # [0, prefill_ranks) hold the prefill
                                # replica (fault-shrink unit, like
                                # world ranks on the monolithic engine)
    decode_ranks: int = 1       # disaggregate: ranks [prefill_ranks,
                                # world) hold the decode replica;
                                # world must equal their sum
    migration_chunk_pages: int = 8  # pages per migration-channel
                                # chunk transfer (the PR-4 decomposed
                                # chunk-loop granularity on the
                                # page-migration wire)

    def validate(self) -> "ServingConfig":
        if self.prefill not in PREFILL_MODES:
            raise ValueError(f"serving: prefill must be one of "
                             f"{PREFILL_MODES}, got {self.prefill!r}")
        for name in ("slots", "page_size", "num_pages", "max_seq_len",
                     "prefill_chunk", "world", "kv_shard"):
            if getattr(self, name) < 1:
                raise ValueError(f"serving: {name} must be >= 1")
        if self.max_seq_len % self.page_size:
            raise ValueError("serving: max_seq_len must be a multiple "
                             "of page_size (block tables are "
                             "page-granular)")
        if self.num_pages < self.max_seq_len // self.page_size:
            raise ValueError(
                f"serving: num_pages {self.num_pages} cannot hold even "
                f"one max_seq_len request "
                f"({self.max_seq_len // self.page_size} pages) — the "
                f"admission gate would starve the queue head forever")
        if not self.disaggregate and self.slots % self.world:
            # disaggregate replaces this with the per-replica rule
            # below: each replica's fault-shrink unit is its OWN rank
            # share, and world = prefill_ranks + decode_ranks need not
            # divide the slot count (e.g. slots=4 on a 2p+1d world)
            raise ValueError("serving: slots must divide evenly across "
                             "world ranks (the fault-shrink unit)")
        if self.multi_step_n < 1:
            raise ValueError(f"serving: multi_step_n must be >= 1, "
                             f"got {self.multi_step_n}")
        if self.cache_dtype not in CACHE_DTYPES:
            raise ValueError(f"serving: unknown cache_dtype "
                             f"{self.cache_dtype!r} (one of "
                             f"{CACHE_DTYPES})")
        if self.speculative and self.cache_dtype != "bf16":
            raise ValueError(
                f"serving: speculative decode supports the bf16 cache "
                f"only — cache_dtype={self.cache_dtype!r} re-quantizes "
                f"pages on every draft/verify overwrite and has no "
                f"stated parity bar (docs/SERVING.md 'Cache density')")
        # ISSUE 19: the ONE sampling validator (check_spec_config
        # pattern) — the same call cli.py runs at arg-parse time, so
        # invalid combos fail identically in both places.  Speculative
        # sampling is LOSSLESS now (rejection-sampling acceptance);
        # what it needs is a drafter with a distribution.
        from dlnetbench_tpu.serving.sampling import check_sampling_config
        check_sampling_config(
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, sample_seed=self.sample_seed,
            grammar=self.grammar, speculative=self.speculative,
            drafter=self.drafter)
        if self.moe_skew < 0:
            raise ValueError(f"serving: moe_skew must be >= 0, got "
                             f"{self.moe_skew}")
        if self.speculative:
            from dlnetbench_tpu.serving.speculative import DRAFTERS
            if self.spec_k < 1:
                raise ValueError(f"serving: spec_k must be >= 1, got "
                                 f"{self.spec_k}")
            if self.drafter not in DRAFTERS:
                raise ValueError(
                    f"serving: unknown drafter {self.drafter!r} "
                    f"(one of {DRAFTERS})")
        if self.disaggregate:
            if self.prefill_ranks < 1 or self.decode_ranks < 1:
                raise ValueError(
                    "serving: disaggregate needs prefill_ranks >= 1 "
                    "and decode_ranks >= 1 — each phase is a replica")
            if self.world != self.prefill_ranks + self.decode_ranks:
                raise ValueError(
                    f"serving: disaggregate splits world into disjoint "
                    f"replica meshes — world {self.world} must equal "
                    f"prefill_ranks {self.prefill_ranks} + decode_ranks "
                    f"{self.decode_ranks}")
            if self.slots % self.prefill_ranks \
                    or self.slots % self.decode_ranks:
                raise ValueError(
                    f"serving: disaggregate needs slots {self.slots} "
                    f"divisible by prefill_ranks {self.prefill_ranks} "
                    f"AND decode_ranks {self.decode_ranks} (each "
                    f"replica's fault-shrink unit is its own rank "
                    f"share)")
            if self.speculative:
                raise ValueError(
                    "serving: speculative + disaggregate is refused — "
                    "the draft/verify ngram state has no stated parity "
                    "story across a page migration")
            if self.prefix_sharing:
                raise ValueError(
                    "serving: prefix_sharing + disaggregate is refused "
                    "— refcounted shared pages live in ONE pool and "
                    "cannot migrate by reference across replicas")
            if self.kv_shard > 1:
                raise ValueError(
                    "serving: kv_shard + disaggregate is refused — the "
                    "migration channel moves single-device pools; a "
                    "sharded pool would need a per-shard wire")
            if self.prefill == "inline":
                raise ValueError(
                    "serving: disaggregate implies separate-phase "
                    "prefill (the prefill replica has no decode slots "
                    "to interleave with) — prefill='inline' is a "
                    "contradiction, not a knob setting")
            if self.migration_chunk_pages < 1:
                raise ValueError(
                    f"serving: migration_chunk_pages must be >= 1, "
                    f"got {self.migration_chunk_pages}")
        return self


class _SlotState:
    """One in-flight request's host-side state."""

    def __init__(self, req: Request, admitted_s: float):
        self.req = req
        self.admitted_s = admitted_s
        self.prompt = None          # jnp [prompt_len] int32, lazy
        self.prefill_done = 0       # prompt tokens already cached
        self.generated = 0
        self.last_token = 0
        self.first_token_s: float | None = None
        self.gstate = 0             # grammar-automaton state after the
        #                             last generated token (ISSUE 19;
        #                             stays 0 when unconstrained)


class Engine:
    """One serving engine instance over a fixed slot/page capacity.

    The decode step and the prefill-chunk program are AOT-compiled at
    construction (``core/executor.CompiledStep`` — compile cost
    recorded in ``global_meta``, never inside the measured loop); the
    KV page pools are donated and rebound functionally each call."""

    # subclass hook (serving/disagg._PrefillReplica): a replica that
    # never decodes skips building the decode program entirely —
    # compile cost and pool-sized executable state must not ride a
    # phase that will never dispatch it
    _decode_needed = True

    def __init__(self, model_cfg: TransformerConfig,
                 cfg: ServingConfig, *, params=None, devices=None,
                 mesh=None):
        self.model_cfg = D.check_config(model_cfg)
        self.cfg = cfg.validate()
        if cfg.disaggregate:
            raise ValueError(
                "serving: a disaggregated config drives TWO engines — "
                "use serving/disagg.run_disagg, not Engine/run_serving")
        self.devices = (list(devices) if devices is not None
                        else jax.devices()[:max(cfg.world,
                                                cfg.kv_shard)])
        if len(self.devices) < cfg.world:
            raise ValueError(
                f"serving: world {cfg.world} needs {cfg.world} devices, "
                f"have {len(self.devices)}")
        self.cache_cfg = CacheConfig(
            num_layers=model_cfg.num_layers,
            num_kv_heads=model_cfg.num_kv_heads,
            head_dim=model_cfg.head_dim,
            num_pages=cfg.num_pages, page_size=cfg.page_size,
            max_seqs=cfg.slots,
            max_pages_per_seq=cfg.max_seq_len // cfg.page_size,
            dtype=model_cfg.dtype, cache_dtype=cfg.cache_dtype)
        self._quant = self.cache_cfg.quantized
        if mesh is None and cfg.kv_shard > 1:
            from dlnetbench_tpu.parallel.mesh import make_flat_mesh
            if model_cfg.num_kv_heads % cfg.kv_shard:
                raise ValueError(
                    f"serving: kv_shard {cfg.kv_shard} must divide "
                    f"num_kv_heads {model_cfg.num_kv_heads}")
            # the mesh comes from THIS engine's device set — a shrink
            # rebuild over the survivors must never keep sharding onto
            # the dead rank's device (refused loudly when too few
            # survivors remain to hold the shard)
            if len(self.devices) < cfg.kv_shard:
                raise ValueError(
                    f"serving: kv_shard {cfg.kv_shard} needs "
                    f"{cfg.kv_shard} devices, engine has "
                    f"{len(self.devices)} — a shrunk world cannot keep "
                    f"the KV shard; lower kv_shard with it")
            mesh = make_flat_mesh(devices=self.devices[:cfg.kv_shard],
                                  axis="kv")
        if mesh is not None and "kv" not in mesh.axis_names:
            raise ValueError("serving: the KV-shard mesh must name its "
                             "axis 'kv' (sharded_paged_attention's "
                             "specs)")
        self.mesh = mesh
        self.params = params if params is not None else init_params(
            jax.random.key(0), model_cfg)
        self.meta: dict = {}
        # the host/device state split (ISSUE 11): multi_step_n == 1 and
        # no speculation keeps the CLASSIC engine — same single-step
        # program, same per-token dispatch, bit-identical by
        # construction (the loop program is not even built); otherwise
        # the decode path is ONE fused program (lax.while_loop) with
        # slot state device-resident between admission syncs
        self._loop_mode = cfg.multi_step_n > 1 or cfg.speculative
        self._decode = self._loop = None
        # ISSUE 15: MoE decode — per-expert token batching with
        # overflow rounds inside both decode paths; the seeded skew
        # bias is an engine-build constant (serving/moe_decode.py)
        self._moe = model_cfg.num_experts > 1
        if self._moe and cfg.speculative:
            raise ValueError(
                "serving: speculative decode covers dense models only "
                "— the draft/verify overwrite cycle has no stated "
                "parity story through the MoE overflow rounds")
        self._moe_bias = None
        if self._moe:
            from dlnetbench_tpu.serving.moe_decode import skew_bias
            self._moe_bias = skew_bias(model_cfg.num_experts,
                                       cfg.moe_skew, cfg.moe_skew_seed)
        # ISSUE 19: the device sampler is an engine-build constant —
        # knobs + compiled grammar tables closed over every decode
        # program.  None when greedy/unconstrained: the sampler-less
        # programs are byte-identical to pre-ISSUE-19 builds.
        from dlnetbench_tpu.serving import sampling as SMP
        scfg = SMP.check_sampling_config(
            temperature=cfg.temperature, top_k=cfg.top_k,
            top_p=cfg.top_p, sample_seed=cfg.sample_seed,
            grammar=cfg.grammar, speculative=cfg.speculative,
            drafter=cfg.drafter)
        self._sampler = (SMP.DeviceSampler(scfg,
                                           model_cfg.vocab_size)
                         if scfg.enabled else None)
        with spans.span("build", what="serving engine"):
            if self._loop_mode:
                if cfg.speculative:
                    from dlnetbench_tpu.serving import speculative as S
                    S.check_spec_config(
                        model_cfg, spec_k=cfg.spec_k,
                        drafter=cfg.drafter,
                        drafter_layers=cfg.drafter_layers)
                    loop_fn = S.make_spec_decode_loop(
                        model_cfg, self.cache_cfg, cfg.multi_step_n,
                        spec_k=cfg.spec_k, drafter=cfg.drafter,
                        drafter_layers=cfg.drafter_layers,
                        attn_impl=cfg.attn_impl, mesh=mesh,
                        sampler=self._sampler)
                    carries = (1, 2, 3, 4)  # pools + packed state +
                    #                          ngram table
                else:
                    loop_fn = D.make_multi_step_decode(
                        model_cfg, self.cache_cfg, cfg.multi_step_n,
                        attn_impl=cfg.attn_impl, mesh=mesh,
                        moe_bias=self._moe_bias,
                        sampler=self._sampler)
                    # pools (+ scale arrays on a quantized cache) +
                    # packed state — all loop carries
                    carries = (tuple(range(1, 6)) if self._quant
                               else (1, 2, 3))
                self._loop = executor.CompiledLoop(
                    loop_fn, self._loop_example_args(),
                    carry_argnums=carries)
            elif self._decode_needed:
                self._decode = executor.CompiledStep(
                    D.make_decode_step(model_cfg, self.cache_cfg,
                                       attn_impl=cfg.attn_impl,
                                       mesh=mesh,
                                       moe_bias=self._moe_bias,
                                       sampler=self._sampler),
                    self._decode_example_args(),
                    donate_argnums=self._pool_argnums)
            self._prefill = executor.CompiledStep(
                D.make_prefill_chunk(model_cfg, self.cache_cfg,
                                     cfg.prefill_chunk,
                                     moe_bias=self._moe_bias,
                                     sampler=self._sampler),
                self._prefill_example_args(),
                donate_argnums=self._pool_argnums)
        decode_prog = self.decode_program
        decode_name = "decode_loop" if self._loop_mode else "decode_step"
        self.meta["compile_ms"] = {
            "prefill_chunk": self._prefill.stats["compile_ms"]}
        self.meta["aot"] = {
            "prefill_chunk": {k: v for k, v in self._prefill.stats.items()
                              if k != "compile_ms"}}
        if decode_prog is not None:
            self.meta["compile_ms"][decode_name] = \
                decode_prog.stats["compile_ms"]
            self.meta["aot"][decode_name] = {
                k: v for k, v in decode_prog.stats.items()
                if k != "compile_ms"}
        # live windowed metrics stream (serving/metrics.LiveMetricsWriter
        # or None) — attached by bench --live-metrics / run_serving;
        # survives _reset_state so a warm round and the measured run
        # share one stream
        self.live = None
        self._reset_state()

    @property
    def decode_program(self):
        """The compiled decode program this engine dispatches: the
        fused loop, or the 1-step program."""
        return self._loop if self._loop_mode else self._decode

    # ---- construction helpers ----------------------------------------
    @property
    def _pool_argnums(self) -> tuple:
        """Positional argnums of the pool buffers in every program
        signature: (k, v) or (k, v, k_scale, v_scale) — the donated,
        functionally-rebound set."""
        return (1, 2, 3, 4) if self._quant else (1, 2)

    def _pools(self):
        """Fresh zeroed page pools (+ scale arrays on a quantized
        cache), pre-placed with the KV-head-sharded layout when a mesh
        is in play: the AOT executables are lowered against THESE
        shardings and their outputs keep them, so every later call sees
        exactly the sharding it was compiled for (an AOT program never
        auto-reshards — the /verify catch that motivated this
        helper)."""
        bufs = device_buffers(self.cache_cfg)
        if self.mesh is None:
            return bufs
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        pool_s = NamedSharding(self.mesh, P(None, "kv", None, None,
                                            None))
        scale_s = NamedSharding(self.mesh, P(None, "kv", None))
        out = [jax.device_put(bufs[0], pool_s),
               jax.device_put(bufs[1], pool_s)]
        for sc in bufs[2:]:
            out.append(jax.device_put(sc, scale_s))
        return tuple(out)

    def _pool_avals(self):
        """Abstract stand-ins for the page pools at lowering time —
        ``jax.jit(...).lower`` takes ShapeDtypeStructs, so the example
        args need not ALLOCATE two extra full-size pool pairs (the
        largest buffers in the tier; on a memory-tight chip the
        redundant copies could OOM a config the steady-state engine
        fits).  Carries the same sharding ``_pools`` places."""
        cc = self.cache_cfg
        shape = (cc.num_layers, cc.num_kv_heads, cc.num_pages,
                 cc.page_size, cc.head_dim)
        pool_s = scale_s = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            pool_s = NamedSharding(self.mesh,
                                   P(None, "kv", None, None, None))
            scale_s = NamedSharding(self.mesh, P(None, "kv", None))
        aval = jax.ShapeDtypeStruct(shape, cc.pool_jnp_dtype,
                                    sharding=pool_s)
        if not self._quant:
            return aval, aval
        saval = jax.ShapeDtypeStruct(shape[:3], jnp.float32,
                                     sharding=scale_s)
        return aval, aval, saval, saval

    def _pool_args(self) -> tuple:
        """The engine's CURRENT pool buffers, in signature order."""
        if self._quant:
            return (self.k_pages, self.v_pages, self.k_scale,
                    self.v_scale)
        return (self.k_pages, self.v_pages)

    def _adopt_pools(self, outs):
        """Rebind the engine's pool references from a program's leading
        outputs; returns the remaining outputs."""
        n = len(self._pool_argnums)
        if self._quant:
            (self.k_pages, self.v_pages, self.k_scale,
             self.v_scale) = outs[:n]
        else:
            self.k_pages, self.v_pages = outs[:n]
        return outs[n:]

    def _decode_example_args(self):
        cc = self.cache_cfg
        b = cc.max_seqs
        args = (self.params, *self._pool_avals(),
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b, cc.max_pages_per_seq), jnp.int32),
                jnp.zeros((b,), bool))
        if self._sampler is not None:
            # ISSUE 19: per-slot request uids + grammar states
            args += (jnp.zeros((b,), jnp.int32),
                     jnp.zeros((b,), jnp.int32))
        return args

    def _prefill_example_args(self):
        cc = self.cache_cfg
        args = (self.params, *self._pool_avals(),
                jnp.zeros((self.cfg.prefill_chunk,), jnp.int32),
                jnp.int32(0), jnp.int32(0),
                jnp.zeros((cc.max_pages_per_seq,), jnp.int32))
        if self._sampler is not None:
            args += (jnp.int32(0),)   # ISSUE 19: the request uid
        return args

    def _loop_example_args(self):
        """Abstract args for the fused decode-loop program (the
        CompiledLoop contract: pools + slot-state carries lead, then
        the read-only block tables, then the dynamic trip count)."""
        cc = self.cache_cfg
        b = cc.max_seqs
        args = (self.params, *self._pool_avals(),
                jnp.zeros((D.STATE_ROWS, b), jnp.int32))  # packed state
        if self.cfg.speculative:
            args += (jnp.zeros((b, self.model_cfg.vocab_size),
                               jnp.int32),)   # ngram table
        args += (jnp.zeros((b, cc.max_pages_per_seq), jnp.int32),
                 jnp.int32(1))                # n_steps / n_rounds
        return args

    def _reset_state(self):
        self.cache = PagedKVCache(self.cache_cfg)
        self.k_scale = self.v_scale = None
        self._adopt_pools(self._pools())
        self._cow_fns = None   # lazily-jitted page-copy programs
        self.concurrent_peak = 0
        self._prompt_memo: dict[int, object] = {}
        self.slots: list[_SlotState | None] = [None] * self.cfg.slots
        self.completed: list[M.Completed] = []
        self.queue: deque[Request] = deque()
        self.pending: list[Request] = []
        self.engine_steps = 0
        self.queue_depth_max = 0
        self._occupancy_samples: list[int] = []
        # ISSUE 11 instrumentation + device-resident slot state.  All
        # host-side bookkeeping — the 1-step path's MATH is untouched.
        self.dstate = None
        if self._loop_mode:
            from dlnetbench_tpu.serving.device_state import \
                DeviceDecodeState
            self.dstate = DeviceDecodeState(
                self.cfg.slots, self.cache_cfg.max_pages_per_seq,
                vocab=(self.model_cfg.vocab_size if self.cfg.speculative
                       else None))
        self.token_streams: dict[int, list[int]] = {}
        self._host_dispatch_us: list[float] = []
        self._dispatches = 0
        self._device_steps = 0
        self._device_time_s = 0.0    # ALL compiled-call legs (prefill
        #                              included) — attribution's
        #                              measured-compute basis
        self._decode_device_s = 0.0  # decode dispatches only — the
        #                              per-step basis the dispatch-
        #                              floor solve divides by
        self._tokens_emitted = 0
        self._drafted = 0
        self._accepted = 0
        # ISSUE 15 MoE imbalance telemetry: per-expert routed-token
        # totals, per-dispatch overflow-round counts (decode and
        # prefill tracked SEPARATELY — their capacity regimes differ,
        # so mixing them would let prompt length move the decode
        # rounds_mean the imbalance study grids by), and the last
        # dispatch's snapshot for the flight ring.  _moe_pending holds
        # intermediate prefill chunks' (load, rounds) DEVICE arrays:
        # converting them eagerly would fence every chunk, violating
        # the _prefill_one fence contract — they fold at the
        # prompt-completing chunk's existing fence
        self._moe_load = (np.zeros(self.model_cfg.num_experts,
                                   np.int64) if self._moe else None)
        self._moe_rounds: list[int] = []
        self._moe_prefill_rounds: list[int] = []
        self._moe_pending: list[tuple] = []
        self._moe_last: dict = {}
        self._step_ewma_s = 0.0
        # disaggregation (ISSUE 16): the driver sets this to the
        # engine-clock second the next migrated sequence is expected
        # to arrive; _pick_n_steps caps the fused trip count so a
        # handoff never waits out a full N-step loop.  None (always,
        # on a monolithic engine) keeps _pick_n_steps bit-identical.
        self._migration_eta_s: float | None = None
        self._n_scalars: dict[int, jax.Array] = {}
        # flight recorder (ISSUE 14): refreshed per run; None (the
        # default) keeps the engine step bit-identical and
        # allocation-free — the telemetry branch is never entered
        self._tele = telemetry.current()
        if self._tele is not None:
            # new run = new step-time baseline: without this, the first
            # steps of a structurally different run (a fused-N engine
            # after a 1-step engine in a bench A/B) would band-escape
            # the PREVIOUS run's walls and fire a bogus step_time
            # anomaly on a clean benchmark
            self._tele.reset_walls("serving")
        if self.live is not None:
            self.live.reset_run()  # the engine clock restarts at 0

    # ---- the loop ----------------------------------------------------
    def run(self, requests: list[Request], *, injector=None,
            t_origin: float | None = None
            ) -> tuple[list[M.Completed], float]:
        """Drive the engine until every request completes; returns
        ``(completed, wall_s)``.  ``t_origin`` anchors the admission
        clock — a fault-segmented continuation passes the FIRST
        segment's origin so arrival stamps stay on one timeline.  A
        scripted ``RankFailure``/``RankPreempted`` from the injector
        propagates with all progress retained on the engine
        (``drain_unfinished`` hands the leftovers to the rebuilt
        engine)."""
        self._reset_state()
        for r in requests:
            if r.prompt_len + r.output_len > self.cfg.max_seq_len:
                raise ValueError(
                    f"serving: request {r.rid} needs "
                    f"{r.prompt_len + r.output_len} tokens > max_seq_len "
                    f"{self.cfg.max_seq_len}")
        self.queue = deque(sorted(requests, key=lambda r: r.arrival_s))
        self._t0 = time.monotonic() if t_origin is None else t_origin
        while self.queue or self.pending or any(
                s is not None for s in self.slots):
            now = self._now()
            self._admit_arrivals(now)
            if not any(s is not None for s in self.slots) \
                    and not self.pending:
                # idle: sleep to the next arrival (open loop — the
                # engine must not busy-spin the clock forward)
                if self.queue:
                    dt = self.queue[0].arrival_s - self._now()
                    if dt > 0:
                        time.sleep(dt)
                continue
            if injector is not None:
                injector.before_step()  # faults land INSIDE the loop
            self._step()
        wall = self._now()
        return self.completed, wall

    def drain_unfinished(self) -> list[Request]:
        """Everything not completed, for a fault-segmented continuation:
        in-flight requests lose their decode progress (their cache dies
        with this engine) but KEEP their arrival stamps — the rebuilt
        engine redoes their work and the disruption lands in their
        measured latency.  Slots and pages are freed."""
        leftovers = [s.req for s in self.slots if s is not None]
        if self._loop_mode and any(s is not None for s in self.slots):
            # the drain IS a sync boundary: deactivate the in-flight
            # slots device-side too, so a reused engine's next flush
            # starts from an all-idle carry
            self.dstate.pull()
        for i, s in enumerate(self.slots):
            if s is not None:
                self.cache.free(i)
                self.slots[i] = None
                if self._loop_mode:
                    self.dstate.evict(i)
        leftovers += self.pending
        leftovers += list(self.queue)
        self.pending, self.queue = [], deque()
        return sorted(leftovers, key=lambda r: r.arrival_s)

    # ---- internals ---------------------------------------------------
    def _now(self) -> float:
        return time.monotonic() - self._t0

    def _admit_arrivals(self, now: float) -> None:
        while self.queue and self.queue[0].arrival_s <= now:
            self.pending.append(self.queue.popleft())
        self.queue_depth_max = max(self.queue_depth_max,
                                   len(self.pending))
        for i in range(self.cfg.slots):
            if not self.pending:
                break
            if self.slots[i] is not None:
                continue
            req = self.pending[0]
            prompt = self._prompt_of(req)
            # admission control: reserve the WORST CASE (prompt +
            # output) so a running sequence can never OOM mid-decode.
            # With prefix sharing the plan charges only UNSHARED pages
            # (fully-matched prefix pages map by reference; the
            # divergence page's copy-on-write copy is pre-charged).
            # A disaggregated prefill replica overrides the token
            # count to prompt-only — its pool never decodes.
            plan = self.cache.plan_admission(
                self._admission_tokens(req),
                prompt if self.cfg.prefix_sharing else None)
            if plan.need_pages > self.cache.free_pages:
                break  # FIFO: do not starve the head by admitting later
            self.pending.pop(0)
            cow_dst = self.cache.admit(i, plan)
            if cow_dst is not None:
                # COW resolved eagerly at the admission sync boundary:
                # the divergence page's prefix rows are copied into the
                # private page BEFORE any prefill/decode write lands
                self._cow_copy(plan.cow_src, cow_dst)
            st = _SlotState(req, admitted_s=self._now())
            st.prompt = prompt
            # the shared prefix is already cached — prefill resumes at
            # the divergence point (the TTFT win prefix sharing buys)
            st.prefill_done = plan.shared_tokens
            self.slots[i] = st
            self.concurrent_peak = max(
                self.concurrent_peak,
                sum(1 for s in self.slots if s is not None))
            if self.cfg.prefill == "separate":
                # drain the whole prompt now (the separate-phase mode:
                # prefill monopolizes the engine while it runs, which
                # is the interference inline chunking exists to cut)
                while self.slots[i] is not None \
                        and st.prefill_done < req.prompt_len:
                    self._prefill_one(i, st)

    def _admission_tokens(self, req: Request) -> int:
        """Tokens to reserve pages for at admission — the worst case
        (prompt + output).  The disaggregated prefill replica overrides
        this to ``prompt_len``: decode happens on the OTHER replica's
        pool, and reserving output pages here would halve the prefill
        pool's admission capacity for nothing."""
        return req.prompt_len + req.output_len

    def _prompt_of(self, req: Request):
        """Request -> prompt tokens, memoized: a blocked queue head is
        re-planned every engine iteration and must not regenerate (or
        re-hash) its prompt each time."""
        toks = self._prompt_memo.get(req.rid)
        if toks is None:
            toks = D.prompt_tokens_for(req, self.model_cfg.vocab_size)
            self._prompt_memo[req.rid] = toks
        return toks

    def _cow_copy(self, src: int, dst: int) -> None:
        """Device-side page copy for an admission-time COW: the shared
        page's rows (and, on a quantized cache, its scales) land in the
        newly charged private page.  One tiny jitted program, traced
        once per array rank; runs at the admission boundary, never
        inside the compiled decode programs."""
        if self._cow_fns is None:
            self._cow_fns = jax.jit(
                lambda a, s, d: a.at[:, :, d].set(a[:, :, s]),
                donate_argnums=(0,))
        f = self._cow_fns
        s, d = jnp.int32(src), jnp.int32(dst)
        self.k_pages = f(self.k_pages, s, d)
        self.v_pages = f(self.v_pages, s, d)
        if self._quant:
            self.k_scale = f(self.k_scale, s, d)
            self.v_scale = f(self.v_scale, s, d)

    def _prefill_one(self, slot: int, st: _SlotState) -> float:
        """One prefill chunk; returns the compiled-call wall seconds
        (the device leg of the host_dispatch_us decomposition).

        Fence honesty: only the PROMPT-COMPLETING chunk fences (its
        ``int(nxt)`` is load-bearing — the TTFT token).  Intermediate
        chunks return dispatch-acknowledged wall only; fencing each
        would stall the host once per chunk and leave the device idle
        between chunks for timing's sake.  Dispatch is async, so their
        queued compute completes inside a LATER fenced
        window — in separate-prefill mode that is still the admission
        phase (the final chunk's fence), but in inline mode it can be
        the next decode dispatch, which is why the bench A/B and the
        dispatch-floor solve use separate-mode prefill
        (``dispatch_decomposition`` documents the caveat)."""
        c = self.cfg.prefill_chunk
        start = st.prefill_done
        n = min(c, st.req.prompt_len - start)
        # pad on the HOST: a jnp dynamic-length slice here would cache
        # one compiled dispatch per distinct tail length
        chunk_np = np.zeros((c,), np.int32)
        chunk_np[:n] = st.prompt[start:start + n]
        chunk = jnp.asarray(chunk_np)
        row = jnp.asarray(self.cache.block_tables[slot])
        t0 = time.perf_counter()
        extra = (() if self._sampler is None
                 else (jnp.int32(st.req.rid),))
        outs = self._prefill(
            self.params, *self._pool_args(), chunk,
            jnp.int32(start), jnp.int32(n), row, *extra)
        if self._moe:
            # stash the DEVICE arrays — no np.asarray here, an
            # intermediate chunk must not fence (the contract above);
            # they fold at the completing chunk's int(nxt) fence,
            # which orders after every prior chunk on the stream
            nxt, load, rounds = self._adopt_pools(outs)
            self._moe_pending.append((load, rounds))
        else:
            (nxt,) = self._adopt_pools(outs)
        st.prefill_done += n
        self.cache.append(slot, n)
        dev_s = 0.0
        if st.prefill_done >= st.req.prompt_len:
            # the chunk completing the prompt produces the request's
            # FIRST generated token — its TTFT stamp
            st.last_token = int(nxt)  # the fence: device work done here
            self._fold_moe_pending()
            dev_s = time.perf_counter() - t0
            st.generated = 1
            if (self._sampler is not None
                    and self._sampler.grammar is not None):
                # grammar state AFTER the TTFT token (the device-side
                # loop picks up from here)
                st.gstate = self._sampler.host_advance(
                    self._sampler.start_state, st.last_token)
            st.first_token_s = self._now()
            self.token_streams.setdefault(st.req.rid, []).append(
                st.last_token)
            if self.cfg.prefix_sharing:
                # the prompt is fully cached: publish its pages so
                # later arrivals can share them (prompt only —
                # generated tokens are request-specific)
                self.cache.publish(slot, st.prompt)
            self._maybe_finish(slot, st)
            if self.slots[slot] is st:
                # entering the decode phase: seed the device-resident
                # slot state (loop mode's admission sync boundary)
                self._activate_decode_slot(slot, st)
        else:
            dev_s = time.perf_counter() - t0
        self._device_time_s += dev_s
        return dev_s

    def _activate_decode_slot(self, slot: int, st: _SlotState) -> None:
        """Loop mode: a slot finished prefill — push its decode state
        to the device mirrors (flushed, priced, at the next dispatch)."""
        if not self._loop_mode:
            return
        ds = self.dstate
        ds.pull()  # sync boundary: refresh before mutating (priced)
        ngram_row = None
        if ds.ngram_table is not None:
            from dlnetbench_tpu.serving.speculative import seed_ngram_row
            ngram_row = seed_ngram_row(st.prompt, st.last_token,
                                       self.model_cfg.vocab_size)
        ds.admit(slot, last_token=st.last_token,
                 position=int(self.cache.lengths[slot]),
                 remaining=st.req.output_len - st.generated,
                 seq_limit=st.req.prompt_len + st.req.output_len,
                 block_row=self.cache.block_tables[slot],
                 ngram_row=ngram_row,
                 uid=st.req.rid, grammar_state=st.gstate)

    def admit_prefilled(self, req: Request, *, last_token: int,
                        admitted_s: float, first_token_s: float,
                        generated: int, pending_send,
                        channel) -> bool:
        """Disaggregation (ISSUE 16): admit a sequence whose prompt was
        prefilled on the OTHER replica.  Reserves the worst case
        (prompt + output) like any admission, rebuilds lengths/block
        tables to exactly the monolithic post-prefill state
        (``lengths = prompt_len``; the first generated token is NOT
        cached — decode writes it at position prompt_len, same as
        ``_prefill_one``'s contract), scatters the migrated pages into
        this pool's allocation, and seeds the decode slot.  The stamps
        (arrival, admission, TTFT) travel WITH the sequence — they
        were taken prefill-side at the existing stamp points.  Returns
        False when no slot or pages are free (the driver retries at
        the next sync boundary)."""
        slot = next((i for i, s in enumerate(self.slots) if s is None),
                    None)
        if slot is None:
            return False
        plan = self.cache.plan_admission(req.prompt_len
                                         + req.output_len)
        if plan.need_pages > self.cache.free_pages:
            return False
        self.cache.admit(slot, plan)
        # the migrated payload covers exactly the prompt's pages;
        # advancing the length makes append/decode see the monolithic
        # post-prefill state
        self.cache.append(slot, req.prompt_len)
        s = self.cfg.page_size
        n_pages = (req.prompt_len + s - 1) // s
        dst_ids = self.cache.block_tables[slot][:n_pages]
        self._adopt_pools(channel.scatter(self._pool_args(),
                                          pending_send, dst_ids))
        st = _SlotState(req, admitted_s=admitted_s)
        st.prompt = self._prompt_of(req)
        st.prefill_done = req.prompt_len
        st.generated = generated
        st.last_token = last_token
        st.first_token_s = first_token_s
        if (self._sampler is not None
                and self._sampler.grammar is not None):
            # migration happens at the TTFT boundary (generated == 1):
            # the automaton has consumed exactly the first token
            st.gstate = self._sampler.host_advance(
                self._sampler.start_state, st.last_token)
        self.slots[slot] = st
        self.concurrent_peak = max(
            self.concurrent_peak,
            sum(1 for s_ in self.slots if s_ is not None))
        self._maybe_finish(slot, st)
        if self.slots[slot] is st:
            self._activate_decode_slot(slot, st)
        return True

    def _step(self) -> None:
        """One engine step: inline prefill chunks first (one per
        prefilling slot), then decode — one token per active slot
        (classic mode) or up to N fused device steps (loop mode).
        Either way ``host_dispatch_us`` records the step wall MINUS
        the compiled-call wall: the marshalling/bookkeeping/dispatch
        overhead the fused loop exists to amortize (ISSUE 11
        satellite — the A/B's measured before-number)."""
        tele = self._tele
        if tele is None and self.live is None:
            # the zero-overhead path: no clock read, no dict built,
            # no branch into the sampling below (ISSUE 14 disabled
            # contract — locked by tests/test_telemetry.py)
            if self._loop_mode:
                self._step_fused()
            else:
                self._step_single()
            return
        t0 = time.perf_counter()
        sync0 = (self.dstate.sync_total_us() if self.dstate is not None
                 else 0.0)
        if self._loop_mode:
            self._step_fused()
        else:
            self._step_single()
        self._sample_step((time.perf_counter() - t0) * 1e6, sync0)

    def _sample_step(self, wall_us: float, sync0: float) -> None:
        """One flight-ring sample per engine step (ISSUE 14): the
        serving tier's per-step TIME SERIES — queue depth, admitted
        concurrency, KV occupancy/fragmentation, prefix hit rate, spec
        acceptance, decode sync-crossing cost — plus the band-aware
        step-time detector feed and the rolling-window SLO breach
        check (``serving/metrics.rolling_slo_breach``, the
        goodput_timeline windowing applied live)."""
        tele = self._tele
        now = self._now()
        step = self.engine_steps
        if tele is not None:
            cs = self.cache.stats()
            fields = {
                "phase": "engine_step",
                "step_wall_us": round(wall_us, 1),
                "queue_depth": len(self.pending),
                "active_slots": sum(1 for s in self.slots
                                    if s is not None),
                "kv_occupancy": cs["occupancy"],
                "kv_fragmentation": cs["fragmentation"],
            }
            prefix = cs.get("prefix")
            if prefix:
                fields["prefix_hit_rate"] = prefix["hit_rate"]
            if self.cfg.speculative and self._drafted:
                fields["spec_acceptance"] = round(
                    self._accepted / self._drafted, 4)
            if self._moe and self._moe_last:
                # expert-imbalance telemetry (ISSUE 15): the last
                # dispatch's overflow rounds + load imbalance ride
                # the flight ring next to queue depth
                fields.update(self._moe_last)
            if self.dstate is not None:
                fields["sync_us"] = round(
                    self.dstate.sync_total_us() - sync0, 1)
            tele.record("serving", step=step, **fields)
            tele.observe_step_wall("serving", wall_us, step=step)
            # bounded tail: completions append in finish order, so the
            # trailing window is a suffix — scanning the whole list
            # every step would put an O(completed) cost inside the very
            # step wall being measured
            breach = M.rolling_slo_breach(
                self.completed[-64:], slo_ttft_ms=self.cfg.slo_ttft_ms,
                slo_tpot_ms=self.cfg.slo_tpot_ms, now_s=now)
            if breach is not None:
                tele.trigger("slo", step=step, detail={
                    **breach,
                    "slo": {"ttft_ms": self.cfg.slo_ttft_ms,
                            "tpot_ms": self.cfg.slo_tpot_ms}})
        if self.live is not None:
            self.live.maybe_emit(self, now)

    def _step_preamble(self) -> tuple[list[int], float]:
        """The per-step work BOTH decode paths share (one definition —
        the A/B pairing depends on the baselines never desyncing):
        inline prefill chunks, the decode-phase slot list, occupancy
        sampling, the step count.  Returns ``(decode_ix, prefill
        device seconds)``."""
        dev_s = 0.0
        for i, st in enumerate(self.slots):
            if st is not None and st.prefill_done < st.req.prompt_len:
                dev_s += self._prefill_one(i, st)
        decode_ix = [i for i, st in enumerate(self.slots)
                     if st is not None
                     and st.prefill_done >= st.req.prompt_len]
        self._occupancy_samples.append(len(decode_ix))
        self.engine_steps += 1
        return decode_ix, dev_s

    def _step_single(self) -> None:
        self._step_complete(self._step_dispatch())

    def _step_fused(self) -> None:
        """Loop mode: ONE fused device program runs up to N decode
        steps with slot state resident on device; the host syncs only
        here — admission updates flushed in, the per-sync token block
        pulled out, both priced (device_state.py)."""
        self._step_complete(self._step_dispatch())

    # ---- the dispatch/complete split (ISSUE 16) ----------------------
    # Both decode paths are split at the async-dispatch boundary: the
    # DISPATCH phase marshals inputs and launches the compiled program
    # WITHOUT fencing; the COMPLETE phase fences the outputs and runs
    # the host postprocess.  The monolithic engine calls them
    # back-to-back (_step_single/_step_fused above) — same statements
    # in the same order, bit-identical math AND timing attribution.
    # The disaggregated driver opens the window: while the decode
    # replica's program runs on its device, the prefill replica's
    # chunks and the page-migration sends run on the OTHER device —
    # the measured interference reduction the disagg study prices.

    def _step_dispatch(self) -> dict | None:
        """Preamble + program launch, no fence.  Returns the in-flight
        step context for ``_step_complete``, or None when no slot is in
        the decode phase (nothing was dispatched)."""
        if self._loop_mode:
            return self._dispatch_fused()
        return self._dispatch_single()

    def _step_complete(self, ctx: dict | None) -> float:
        """Fence the dispatched step's outputs and run the host
        postprocess; returns the step's decode device-leg seconds (the
        compute arm of the disagg driver's overlap measurement)."""
        if ctx is None:
            return 0.0
        if ctx["fused"]:
            return self._complete_fused(ctx)
        return self._complete_single(ctx)

    def _dispatch_single(self) -> dict | None:
        t_step = time.perf_counter()
        decode_ix, dev_s = self._step_preamble()
        if not decode_ix:
            return None
        b = self.cfg.slots
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        for i in decode_ix:
            st = self.slots[i]
            tokens[i] = st.last_token
            positions[i] = int(self.cache.lengths[i])
            active[i] = True
        extra = ()
        if self._sampler is not None:
            uids = np.zeros((b,), np.int32)
            gst = np.zeros((b,), np.int32)
            for i in decode_ix:
                uids[i] = self.slots[i].req.rid
                gst[i] = self.slots[i].gstate
            extra = (jnp.asarray(uids), jnp.asarray(gst))
        t0 = time.perf_counter()
        outs = self._decode(
            self.params, *self._pool_args(),
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(self.cache.block_tables), jnp.asarray(active),
            *extra)
        rest = self._adopt_pools(outs)
        return {"fused": False, "t_step": t_step, "t0": t0,
                "dev_s": dev_s, "decode_ix": decode_ix, "rest": rest}

    def _complete_single(self, ctx: dict) -> float:
        decode_ix, dev_s = ctx["decode_ix"], ctx["dev_s"]
        if self._moe:
            nxt, load, rounds = ctx["rest"]
            self._record_moe(load, rounds)
        else:
            (nxt,) = ctx["rest"]
        nxt = np.asarray(nxt)        # the fence rides the device leg
        t1 = time.perf_counter()
        leg = t1 - ctx["t0"]
        dev_s += leg
        self._device_time_s += leg
        self._decode_device_s += leg
        self._dispatches += 1
        self._device_steps += 1
        for i in decode_ix:
            st = self.slots[i]
            self.cache.append(i)          # the fed token is now cached
            st.last_token = int(nxt[i])
            if (self._sampler is not None
                    and self._sampler.grammar is not None):
                # the per-token fence IS the grammar transition point
                # in classic mode — host-side, same automaton table
                st.gstate = self._sampler.host_advance(
                    st.gstate, st.last_token)
            st.generated += 1
            self._tokens_emitted += 1
            self.token_streams.setdefault(st.req.rid, []).append(
                st.last_token)
            self._maybe_finish(i, st)
        self._host_dispatch_us.append(
            max(0.0, (time.perf_counter() - ctx["t_step"] - dev_s))
            * 1e6)
        return leg

    def _dispatch_fused(self) -> dict | None:
        t_step = time.perf_counter()
        sync0 = self.dstate.sync_total_us()
        decode_ix, dev_s = self._step_preamble()
        if not decode_ix:
            return None
        ds = self.dstate
        n = self._pick_n_steps(decode_ix)
        carries = ds.carries()            # flushes if dirty (priced)
        bt = ds.block_tables_device()
        t0 = time.perf_counter()
        outs = self._loop(self.params, *self._pool_args(),
                          *carries, bt, self._n_scalar(n))
        new_carries, extras = self._loop.split(outs)
        ds.rebind(self._adopt_pools(new_carries))
        return {"fused": True, "t_step": t_step, "t0": t0,
                "sync0": sync0, "dev_s": dev_s,
                "decode_ix": decode_ix, "extras": extras}

    def _complete_fused(self, ctx: dict) -> float:
        decode_ix, dev_s = ctx["decode_ix"], ctx["dev_s"]
        extras = ctx["extras"]
        if self.cfg.speculative:
            toks, cnts, steps, drafted, accepted = extras
        elif self._moe:
            toks, cnts, steps, moe_load, moe_rounds = extras
            self._record_moe(moe_load, moe_rounds)
        else:
            toks, cnts, steps = extras
        # the per-sync results (token block, counts, stats): np.asarray
        # is the FENCE, so [t0, t2) is the device leg as one unit —
        # priced into device_us only (sync_d2h_us prices the mirror
        # pull()s; pricing this interval into both channels would
        # double-count it against the wall)
        toks = np.asarray(toks)
        cnts = np.asarray(cnts)
        steps = int(steps)
        if self.cfg.speculative:
            self._drafted += int(drafted)
            self._accepted += int(accepted)
        t2 = time.perf_counter()
        leg = t2 - ctx["t0"]
        dev_s += leg
        self._device_time_s += leg
        self._decode_device_s += leg
        self._dispatches += 1
        self._device_steps += steps
        if steps > 0:
            per_step = leg / steps
            self._step_ewma_s = (per_step if not self._step_ewma_s else
                                 0.5 * self._step_ewma_s
                                 + 0.5 * per_step)
        for i in decode_ix:
            st = self.slots[i]
            m = int(cnts[i])
            if m == 0:
                continue
            self.cache.append(i, m)   # all fed tokens, one batched call
            stream = toks[i, :m].tolist()
            st.generated += m
            st.last_token = stream[-1]
            self._tokens_emitted += m
            self.token_streams.setdefault(st.req.rid, []).extend(stream)
            self._maybe_finish(i, st)
        # exclude in-step sync time: flush/pull are priced in their own
        # channels and each crossing must count against the wall ONCE
        # (serving_host_us sums host_dispatch + both sync channels)
        sync_s = (self.dstate.sync_total_us() - ctx["sync0"]) * 1e-6
        self._host_dispatch_us.append(
            max(0.0, (time.perf_counter() - ctx["t_step"] - dev_s
                      - sync_s))
            * 1e6)
        return leg

    def _record_moe(self, load, rounds) -> None:
        """Fold one DECODE dispatch's MoE stats (device outputs riding
        the same fence as the tokens) into the run accumulators and
        the last-dispatch snapshot the flight ring samples."""
        load = np.asarray(load, np.int64)
        rounds = int(rounds)
        self._moe_load += load
        self._moe_rounds.append(rounds)
        total = float(load.sum())
        if total > 0:
            frac = load / total
            imb = float(frac.max()) / max(float(frac.mean()), 1e-12)
        else:
            imb = 1.0
        self._moe_last = {"moe_rounds": rounds,
                          "moe_imbalance": round(imb, 4)}

    def _fold_moe_pending(self) -> None:
        """Fold the stashed prefill chunks' MoE stats.  Called under a
        fence that already covers them (the completing chunk's TTFT
        token, or record assembly), so the np.asarray conversions here
        cost a copy, never a wait.  Prefill rounds accumulate apart
        from decode rounds — prefill capacity is sized over the chunk,
        decode capacity over the slot batch, and the decode
        rounds_mean column must not move with prompt length."""
        for load, rounds in self._moe_pending:
            self._moe_load += np.asarray(load, np.int64)
            self._moe_prefill_rounds.append(int(rounds))
        self._moe_pending.clear()

    def moe_block(self) -> dict | None:
        """The record's MoE-imbalance block (ISSUE 15): measured
        per-expert load distribution (prefill + decode routing — the
        router is the router), its imbalance (max/mean), and the
        DECODE overflow-round stats that turned imbalance into latency
        (prefill rounds reported apart: their capacity is sized over
        the chunk, not the slot batch).  None on dense engines —
        pre-MoE records are byte-identical."""
        if not self._moe:
            return None
        self._fold_moe_pending()   # a drained mid-prefill slot's stats
        total = float(self._moe_load.sum())
        load = (self._moe_load / total if total > 0
                else np.zeros_like(self._moe_load, float))
        rounds = self._moe_rounds
        pf = self._moe_prefill_rounds
        mean = max(float(load.mean()), 1e-12)
        return {
            "num_experts": int(self.model_cfg.num_experts),
            "top_k": int(self.model_cfg.top_k),
            "capacity_factor": float(
                self.model_cfg.moe_capacity_factor),
            "skew": self.cfg.moe_skew,
            "skew_seed": self.cfg.moe_skew_seed,
            "expert_load": [round(float(v), 6) for v in load],
            "load_imbalance": round(float(load.max()) / mean, 4),
            "rounds_mean": (round(sum(rounds) / len(rounds), 3)
                            if rounds else 0.0),
            "rounds_p99": (round(M.percentile(rounds, 99), 3)
                           if rounds else 0.0),
            "dispatches": len(rounds),
            "prefill_rounds_mean": (round(sum(pf) / len(pf), 3)
                                    if pf else 0.0),
            "prefill_dispatches": len(pf),
        }

    def _n_scalar(self, n: int):
        """Cached device scalar for the dynamic trip count (a fresh
        jnp.int32 per dispatch is a measurable host cost at decode
        rates)."""
        s = self._n_scalars.get(n)
        if s is None:
            s = self._n_scalars[n] = jnp.int32(n)
        return s

    def _pick_n_steps(self, decode_ix: list[int]) -> int:
        """Adaptive N (ISSUE 11 satellite): the fused loop must never
        starve an admissible request.  Cap the trip count by the
        SHORTEST remaining output among active slots whenever work is
        waiting (the loop then returns exactly when the first slot can
        free capacity), and by the measured steps-until-next-arrival
        when the queue's head would land mid-loop.  A slot mid-prefill
        (inline mode) caps at 1 — the one-chunk-per-engine-step
        interleaving contract."""
        n = self.cfg.multi_step_n
        if not self.cfg.adaptive_n:
            return max(1, n)
        if any(st is not None and st.prefill_done < st.req.prompt_len
               for st in self.slots):
            return 1
        if n <= 1:
            return max(1, n)
        rem_min = min(self.slots[i].req.output_len
                      - self.slots[i].generated for i in decode_ix)
        # disaggregation (ISSUE 16): the decode replica has no arrival
        # queue of its own — its "next arrival" is the next migrated
        # sequence, whose ETA the driver maintains.  Cap the trip
        # count the same way the queue-head cap does, so a finished
        # handoff waits at most ~one device step for a free sync
        # boundary instead of a full N-step loop.  None (always, on a
        # monolithic engine) leaves every path below bit-identical.
        eta = self._migration_eta_s
        if eta is not None:
            dt = eta - self._now()
            est = self._step_ewma_s
            if est > 0 and dt < n * est:
                n = max(1, min(n, max(1, int(dt / est) + 1)))
        if self.pending:
            return max(1, min(n, rem_min))
        if self.queue:
            dt = self.queue[0].arrival_s - self._now()
            est = self._step_ewma_s
            if est > 0 and dt < n * est:
                steps_until = max(1, int(dt / est) + 1)
                return max(1, min(n, rem_min, steps_until))
        if eta is not None:
            return max(1, min(n, rem_min))
        return n

    def _maybe_finish(self, slot: int, st: _SlotState) -> None:
        if st.generated < st.req.output_len:
            return
        now = self._now()
        self.completed.append(M.Completed(
            rid=st.req.rid, arrival_s=st.req.arrival_s,
            admitted_s=st.admitted_s, first_token_s=st.first_token_s,
            finish_s=now, prompt_len=st.req.prompt_len,
            output_len=st.req.output_len))
        self.cache.free(slot)
        self.slots[slot] = None

    # ---- record assembly ---------------------------------------------
    def batch_occupancy_mean(self) -> float:
        if not self._occupancy_samples:
            return 0.0
        return sum(self._occupancy_samples) / len(self._occupancy_samples)

    def decode_loop_block(self) -> dict:
        """The record's dispatch-decomposition block (ISSUE 11): how
        many device decode steps each host dispatch amortized, what
        each host crossing cost, and the speculative acceptance stats.
        Present in BOTH modes — the 1-step engine's block (steps per
        dispatch = 1, per-step host_dispatch_us) is the measured
        before-number the A/B flips against."""
        d = self._dispatches
        hd = self._host_dispatch_us
        block = {
            "multi_step_n": self.cfg.multi_step_n,
            "adaptive_n": self.cfg.adaptive_n,
            "speculative": self.cfg.speculative,
            "dispatches": d,
            "device_steps": self._device_steps,
            "steps_per_dispatch": (round(self._device_steps / d, 3)
                                   if d else 0.0),
            "tokens_per_sync": (round(self._tokens_emitted / d, 3)
                                if d else 0.0),
            "device_us": {"total": round(self._device_time_s * 1e6, 1)},
            "decode_device_us": {
                "total": round(self._decode_device_s * 1e6, 1)},
            "host_dispatch_us": {
                "total": round(sum(hd), 1),
                "p50": round(M.percentile(hd, 50), 1) if hd else 0.0,
                "mean": round(sum(hd) / len(hd), 1) if hd else 0.0,
                "n": len(hd)},
        }
        if self.dstate is not None:
            block.update(self.dstate.sync_stats())
        if self.cfg.speculative:
            block["spec"] = {
                "k": self.cfg.spec_k,
                "drafter": self.cfg.drafter,
                **({"drafter_layers": self.cfg.drafter_layers}
                   if self.cfg.drafter == "truncated" else {}),
                "drafted": self._drafted,
                "accepted": self._accepted,
                "acceptance_rate": (round(self._accepted
                                          / self._drafted, 4)
                                    if self._drafted else 0.0),
            }
        return block

    def global_meta(self, plan: ArrivalPlan) -> dict:
        from dlnetbench_tpu.parallel.mesh import (describe_mesh,
                                                  make_flat_mesh)
        cfg = self.cfg
        return {
            "proxy": "serving",
            "model": (f"decode_d{self.model_cfg.embed_dim}"
                      f"_l{self.model_cfg.num_layers}"
                      f"_h{self.model_cfg.num_heads}"
                      f"kv{self.model_cfg.num_kv_heads}"
                      f"_v{self.model_cfg.vocab_size}"),
            "world_size": cfg.world,
            "arrival_plan": plan.to_dict(),
            # comparable global (ISSUE 12): records from differently-
            # quantized caches must never merge — metrics/merge refuses
            # a mismatch exactly like a mismatched fault plan
            "kv_cache_dtype": cfg.cache_dtype,
            # comparable global (ISSUE 19): sampled runs carry their
            # full draw identity — records with different temperature/
            # top_k/top_p/seed/grammar must never merge (draws are
            # keyed by (seed, uid, position); mixing seeds would
            # average incomparable token streams).  Absent on greedy
            # runs so pre-sampling records stay byte-identical.
            **({"sampling": {"temperature": cfg.temperature,
                             "top_k": cfg.top_k,
                             "top_p": cfg.top_p,
                             "sample_seed": cfg.sample_seed,
                             "grammar": cfg.grammar}}
               if self._sampler is not None else {}),
            "serving_config": {
                "slots": cfg.slots, "page_size": cfg.page_size,
                "num_pages": cfg.num_pages,
                "max_seq_len": cfg.max_seq_len,
                "pool_bytes": self.cache_cfg.pool_bytes,
                "cache_dtype": cfg.cache_dtype,
                "prefix_sharing": cfg.prefix_sharing,
                "prefill": cfg.prefill,
                "prefill_chunk": cfg.prefill_chunk,
                "kv_shard": cfg.kv_shard,
                "multi_step_n": cfg.multi_step_n,
                "adaptive_n": cfg.adaptive_n,
                "speculative": cfg.speculative,
                **({"spec_k": cfg.spec_k, "drafter": cfg.drafter}
                   if cfg.speculative else {}),
                # the skew KNOBS are run identity (serving_config is
                # comparable): a skewed run never merges with a
                # balanced one, exactly like mismatched fault plans
                **({"moe_experts": self.model_cfg.num_experts,
                    "moe_top_k": self.model_cfg.top_k,
                    "moe_capacity_factor":
                        self.model_cfg.moe_capacity_factor,
                    "moe_skew": cfg.moe_skew,
                    "moe_skew_seed": cfg.moe_skew_seed}
                   if self._moe else {}),
            },
            "mesh": describe_mesh(make_flat_mesh(devices=self.devices)),
            **self.meta,
        }


def run_serving(model_cfg: TransformerConfig, cfg: ServingConfig,
                plan: ArrivalPlan, *, fault_plan=None, params=None,
                devices=None, live_metrics=None):
    """One measured serving run -> ``ProxyResult`` (-> ``metrics.emit``).

    Clean runs drive one engine.  With ``fault_plan``: delay/jitter
    events sleep at step boundaries inside the loop; a crash under
    policy ``shrink`` segments the run like ``faults/policy.run_faulted``
    segments a training run — detection measured at the catch, the
    engine rebuilt over the survivor ranks' slot share (recompile
    priced into ``recovery_ms``), unfinished requests re-queued with
    their original arrival stamps, and the record stamps
    ``degraded_world``/``fault_*`` so the analysis layer reads serving
    faults exactly like training faults."""
    engine = Engine(model_cfg, cfg, params=params, devices=devices)
    if live_metrics is not None:
        # path or writer: the windowed live JSONL stream (ISSUE 14
        # satellite; serving/metrics.LiveMetricsWriter)
        engine.live = (live_metrics if hasattr(live_metrics,
                                               "maybe_emit")
                       else M.LiveMetricsWriter(live_metrics))
    requests = plan.sample()
    if cfg.warmup_requests > 0:
        # warm-in: saturating synthetic mini-workload, discarded — the
        # measured run starts with hot dispatch paths (run_proxy's
        # warmup phase, serving-shaped)
        p_len = min(cfg.prefill_chunk + 1, cfg.max_seq_len - 2)
        warm = [Request(rid=-1 - i, arrival_s=0.0, prompt_len=p_len,
                        output_len=2)
                for i in range(cfg.warmup_requests)]
        with spans.span("warmup", what="serving engine",
                        reps=len(warm)):
            engine.run(warm)
    injector = None
    if fault_plan is not None:
        from dlnetbench_tpu.faults.inject import FaultInjector
        fault_plan.validate()
        injector = FaultInjector(fault_plan, world=cfg.world)

    meta = engine.global_meta(plan)
    extra: dict = {}
    try:
        with spans.span("serving_run", requests=len(requests)):
            completed, wall = engine.run(requests, injector=injector)
        final = engine
    except Exception as e:
        # capacity shrink: the dead rank takes its slot share down.
        # Mirrors faults/policy.run_faulted's segmentation: detect,
        # rebuild (recompile priced), finish degraded.  The detection
        # stamp, fault trigger and survivor set are the shared arc
        # (serving/requeue.py — re-raises non-shrinkable faults).
        detection_ms, survivors = requeue.detect_shrink(
            e, injector=injector, fault_plan=fault_plan,
            world=cfg.world, step=engine.engine_steps)
        if not survivors:
            raise
        leftovers = requeue.requeue_unfinished(engine)
        done0 = list(engine.completed)
        t_origin = engine._t0
        steps0 = engine.engine_steps
        occ0 = list(engine._occupancy_samples)
        qmax0 = engine.queue_depth_max
        t0 = time.monotonic()
        shrunk = dataclasses.replace(
            cfg, world=len(survivors),
            slots=cfg.slots // cfg.world * len(survivors))
        with spans.span("serving_rebuild", survivors=len(survivors)):
            engine2 = Engine(model_cfg, shrunk, params=params,
                             devices=[engine.devices[r]
                                      for r in survivors])
        engine2.live = engine.live  # the stream outlives the shrink
        recovery_ms = (time.monotonic() - t0) * 1e3
        done1, wall = requeue.run_requeued(
            engine2, leftovers, injector=injector, t_origin=t_origin)
        completed = done0 + done1
        final = engine2
        final.engine_steps += steps0
        final._occupancy_samples = occ0 + final._occupancy_samples
        final.queue_depth_max = max(qmax0, final.queue_depth_max)
        final.concurrent_peak = max(engine.concurrent_peak,
                                    final.concurrent_peak)
        meta["mesh"] = engine2.global_meta(plan)["mesh"]
        extra = {"detection_ms": round(detection_ms, 3),
                 "recovery_ms": round(recovery_ms, 3),
                 "degraded_world": survivors,
                 "degraded_slots": shrunk.slots}

    # measured MoE imbalance block (ISSUE 15): stamped from the FINAL
    # engine AFTER the measured run (a crash-shrink continuation's
    # stats are the degraded engine's); volatile at merge like every
    # measurement; absent on dense engines
    moe_blk = final.moe_block()
    if moe_blk is not None:
        meta["moe"] = moe_blk
    meta["serving"] = M.serving_block(
        completed, plan, slo_ttft_ms=cfg.slo_ttft_ms,
        slo_tpot_ms=cfg.slo_tpot_ms, wall_s=wall,
        engine_steps=final.engine_steps,
        cache_stats=final.cache.stats(),
        queue_depth_max=final.queue_depth_max,
        batch_occupancy_mean=final.batch_occupancy_mean(),
        decode_loop=final.decode_loop_block(),
        admitted_peak=final.concurrent_peak)
    if cfg.prefix_sharing:
        # record globals (ISSUE 12 acceptance: a sharing run must
        # stamp its measured hit rate and bytes saved).  VOLATILE in
        # merge: residency at admission time depends on wall-clock
        # arrival vs engine speed, so the counts can differ across
        # hosts/reruns of one plan (metrics/merge.py)
        pstats = final.cache.stats().get("prefix", {})
        meta["prefix_hit_rate"] = pstats.get("hit_rate", 0.0)
        meta["prefix_bytes_saved"] = pstats.get("bytes_saved", 0)
    if cfg.speculative and final._sampler is not None:
        # VOLATILE at merge (metrics/merge.py): the measured
        # acceptance-vs-temperature point for THIS run — acceptance is
        # a measurement (it varies with params/load), unlike the
        # `sampling` identity block above
        meta["spec_acceptance_by_temp"] = M.acceptance_by_temp([
            (cfg.temperature,
             (final._accepted / final._drafted
              if final._drafted else 0.0))])
    if fault_plan is not None:
        meta["fault_plan"] = fault_plan.to_dict()
        meta["fault_policy"] = fault_plan.policy
        meta["fault_injected_delay_us"] = round(
            injector.injected_delay_us, 1)
    meta.update(extra)
    return M.build_result(completed, plan, meta)
