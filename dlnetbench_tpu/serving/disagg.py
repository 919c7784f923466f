"""Disaggregated prefill/decode serving (ISSUE 16): the
DistServe/Splitwise split, measured honestly on one harness run.

The monolithic ``scheduler.Engine`` interleaves compute-bound, bursty
prefill with memory-bound, steady decode on one device — every
admitted prompt steals decode steps and inflates in-flight requests'
TPOT (the interference ``examples/pod_study.py --serving`` measures at
the knee).  This module splits the run into TWO engines on DISJOINT
device subsets of the same harness world:

* ranks ``[0, prefill_ranks)`` — a prefill replica that admits from
  the shared arrival queue, reserves PROMPT-ONLY pages, and drains
  each prompt into its local pool (producing the TTFT token at the
  existing ``_prefill_one`` stamp);
* ranks ``[prefill_ranks, world)`` — a decode replica that receives
  finished sequences over the page-migration channel
  (``serving/page_migration.py``: pages + scales contiguous in their
  STORED int8/fp8 dtype, chunk-loop transfers) and decodes them to
  completion.

The overlap is real, not narrated: the decode replica's fused program
is DISPATCHED without fencing (``Engine._step_dispatch``), the
migration send runs on the prefill device while the decode device
computes, and the fence closes both (``_step_complete``) — the
classic async-dispatch overlap, measured as comm-solo / compute-solo /
together legs and reduced through ``stats.overlap_fraction`` like
every collective A/B in this repo.  The decode replica's adaptive-N
trip count is capped at the next expected migration arrival
(``Engine._pick_n_steps`` ETA cap) so a finished handoff never waits
out a full N-step loop.

Token parity is the bar: both replicas run the SAME compiled program
families over the SAME weights, the migrated pages are bit-identical
to what a monolithic engine would have written locally (stored dtype +
scales move verbatim), and the decode replica rebuilds
lengths/block-tables to exactly the monolithic post-prefill state —
so greedy output is token-identical to ``run_serving`` per cache
dtype (locked by tests/test_disagg.py for bf16 AND int8).

Faults compose: a crash under policy ``shrink`` takes down ONE
replica's rank share.  A dead prefill rank re-queues mid-prefill
requests (original arrival stamps kept) onto a rebuilt, smaller
prefill replica while the decode replica's in-flight sequences keep
streaming — TTFT p99 blows up while TPOT holds, a scenario the
monolithic engine cannot express.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque

import jax

from dlnetbench_tpu.metrics import spans
from dlnetbench_tpu.models.transformer import (TransformerConfig,
                                               init_params)
from dlnetbench_tpu.serving import metrics as M
from dlnetbench_tpu.serving import requeue
from dlnetbench_tpu.serving.arrivals import ArrivalPlan, Request
from dlnetbench_tpu.serving.page_migration import MigrationChannel
from dlnetbench_tpu.serving.scheduler import Engine, ServingConfig


class _PrefillReplica(Engine):
    """The prefill-phase engine: admits with PROMPT-ONLY page
    reservations (its pool never decodes) and, where the monolithic
    engine would activate a decode slot, hands the finished sequence
    to the migration queue instead.  ``_decode_needed = False`` skips
    the decode program build — a replica that never dispatches decode
    must not pay its compile or carry its executable."""

    _decode_needed = False

    def _reset_state(self):
        super()._reset_state()
        # (slot, _SlotState) pairs whose prompt is fully cached,
        # awaiting a migration send — the handoff queue the driver
        # drains.  The slot stays occupied (its pages are the payload)
        # until the send is dispatched.
        self._handoff: list = []

    def _admission_tokens(self, req: Request) -> int:
        return req.prompt_len

    def _activate_decode_slot(self, slot, st) -> None:
        self._handoff.append((slot, st))

    def pump(self, budget: int = 2) -> None:
        """One driver-loop iteration's prefill work: dispatch up to
        ``budget`` chunks, oldest admission first.  Intermediate
        chunks never fence (``_prefill_one``'s contract), so each
        costs ~one host dispatch and its COMPUTE runs on the prefill
        device underneath the decode trips the driver overlaps it
        with; by the time the prompt-completing chunk's ``int(nxt)``
        first-token fence lands, the earlier chunks have been cooking
        for several trips.  The budget is the load-bearing part: an
        unbounded pump under an admission backlog queues the whole
        backlog's prefill compute at once (measured: ~16 ms for four
        48-token prompts), and every decode fence behind it absorbs
        that queue — the same head-of-line blocking the monolithic
        inline engine suffers, just relocated.  Draining whole
        prompts at admission instead (separate mode) stalls the
        shared host thread for the full prompt wall per arrival while
        the decode replica emits nothing."""
        mids = [(st.admitted_s, i, st)
                for i, st in enumerate(self.slots)
                if st is not None
                and st.prefill_done < st.req.prompt_len]
        mids.sort()
        for _, i, st in mids:
            while budget and st.prefill_done < st.req.prompt_len:
                self._prefill_one(i, st)
                budget -= 1
            if not budget:
                break


class DisaggServer:
    """One disaggregated serving run: a prefill replica, a decode
    replica, and the migration channel between their pools.  Drives
    both engines from one host thread (the single-controller harness)
    — the decode program's async dispatch window is where prefill and
    migration work hide."""

    def __init__(self, model_cfg: TransformerConfig,
                 cfg: ServingConfig, *, params=None, devices=None,
                 prefill_slots: int | None = None,
                 decode_slots: int | None = None):
        cfg.validate()
        if not cfg.disaggregate:
            raise ValueError("disagg: DisaggServer needs "
                             "cfg.disaggregate=True — a monolithic "
                             "config belongs to run_serving")
        self.model_cfg = model_cfg
        self.cfg = cfg
        devs = (list(devices) if devices is not None
                else jax.devices()[:cfg.world])
        if len(devs) < cfg.world:
            raise ValueError(
                f"disagg: world {cfg.world} "
                f"(prefill {cfg.prefill_ranks} + decode "
                f"{cfg.decode_ranks}) needs {cfg.world} devices, have "
                f"{len(devs)} — the replica meshes must be disjoint")
        self.devices = devs[:cfg.world]
        self.prefill_devices = self.devices[:cfg.prefill_ranks]
        self.decode_devices = self.devices[cfg.prefill_ranks:]
        if params is None:
            params = init_params(jax.random.key(0), model_cfg)
        p_slots = cfg.slots if prefill_slots is None else prefill_slots
        d_slots = cfg.slots if decode_slots is None else decode_slots
        # inline mode on the prefill replica: admission must NOT drain
        # the prompt (separate mode's admission-time drain would stall
        # the shared driver thread for the prompt's full device wall);
        # the driver pumps chunks dispatch-only under the decode window
        pcfg = dataclasses.replace(
            cfg, disaggregate=False, world=cfg.prefill_ranks,
            slots=p_slots, multi_step_n=1, prefill="inline")
        dcfg = dataclasses.replace(
            cfg, disaggregate=False, world=cfg.decode_ranks,
            slots=d_slots, prefill="separate")
        # each replica's programs/pools are built UNDER its device so
        # the AOT executables target it; the weights are copied once
        # per replica (same values — parity is unaffected)
        with jax.default_device(self.prefill_devices[0]):
            self.prefill = _PrefillReplica(
                model_cfg, pcfg,
                params=jax.device_put(params, self.prefill_devices[0]),
                devices=self.prefill_devices)
        with jax.default_device(self.decode_devices[0]):
            self.decode = Engine(
                model_cfg, dcfg,
                params=jax.device_put(params, self.decode_devices[0]),
                devices=self.decode_devices)
        self.channel = MigrationChannel(
            self.decode.cache_cfg, self.decode_devices[0],
            chunk_pages=cfg.migration_chunk_pages)
        # sent-and-fenced payloads awaiting a free decode slot/pages:
        # (PendingSend, handoff meta) in prefill-completion order
        self._ready: deque = deque()
        self._handoff_ewma_s = 0.0

    # ---- device contexts ---------------------------------------------
    def _pctx(self):
        return jax.default_device(self.prefill_devices[0])

    def _dctx(self):
        return jax.default_device(self.decode_devices[0])

    # ---- the driver loop ---------------------------------------------
    def run(self, requests: list[Request], *, injector=None,
            t_origin: float | None = None
            ) -> tuple[list[M.Completed], float]:
        """Drive both replicas until every request completes; returns
        ``(completed, wall_s)`` on the shared admission clock.  Same
        contract as ``Engine.run`` (t_origin anchors a fault-segmented
        continuation; a scripted RankFailure propagates with progress
        retained on both engines)."""
        pe, de = self.prefill, self.decode
        for r in requests:
            if r.prompt_len + r.output_len > self.cfg.max_seq_len:
                raise ValueError(
                    f"serving: request {r.rid} needs "
                    f"{r.prompt_len + r.output_len} tokens > "
                    f"max_seq_len {self.cfg.max_seq_len}")
        with self._pctx():
            pe._reset_state()
        with self._dctx():
            de._reset_state()
        self.channel.reset()
        self._ready.clear()
        pe.queue = deque(sorted(requests, key=lambda r: r.arrival_s))
        t0 = time.monotonic() if t_origin is None else t_origin
        pe._t0 = t0
        de._t0 = t0
        while (pe.queue or pe.pending or pe._handoff or self._ready
               or any(s is not None for s in pe.slots)
               or any(s is not None for s in de.slots)):
            now = pe._now()
            if injector is not None:
                injector.before_step()  # faults land INSIDE the loop
            with self._pctx():
                pe._admit_arrivals(now)
            de_active = any(s is not None for s in de.slots)
            if de_active:
                self._decode_step()
            else:
                # decode idle: nothing to hide behind — chunks pump
                # unoverlapped and the fenced send IS the comm-solo
                # overlap leg
                with self._pctx():
                    pe.pump()
                if pe._handoff:
                    self._ready.append(
                        self._send_next(overlapped=False))
            # land arrived payloads at the sync boundary (never while
            # a decode dispatch holds the pool buffers in flight)
            while self._ready:
                pending, meta = self._ready[0]
                with self._dctx():
                    ok = de.admit_prefilled(
                        meta["req"], last_token=meta["last_token"],
                        admitted_s=meta["admitted_s"],
                        first_token_s=meta["first_token_s"],
                        generated=meta["generated"],
                        pending_send=pending, channel=self.channel)
                if not ok:
                    break  # no slot/pages: retry next boundary
                self._ready.popleft()
            self._update_eta()
            if (not pe.pending and not pe._handoff and not self._ready
                    and not any(s is not None for s in pe.slots)
                    and not any(s is not None for s in de.slots)
                    and pe.queue):
                # idle: sleep to the next arrival (open loop — the
                # engine must not busy-spin the clock forward)
                dt = pe.queue[0].arrival_s - pe._now()
                if dt > 0:
                    time.sleep(dt)
        wall = pe._now()
        return pe.completed + de.completed, wall

    def _decode_step(self) -> None:
        """One decode-replica step with the migration overlap window:
        dispatch the decode program (no fence), pump the prefill
        replica's chunks and run the next handoff's send on the prefill
        device while the decode device computes, then fence both.  The
        three overlap legs land in the channel; the engine's own
        telemetry sampling (SLO breach triggers, live stream) rides the
        step exactly as in ``Engine._step``."""
        pe, de, ch = self.prefill, self.decode, self.channel
        tele_on = de._tele is not None or de.live is not None
        t_w = time.perf_counter()
        sync0 = (de.dstate.sync_total_us()
                 if tele_on and de.dstate is not None else 0.0)
        with self._dctx():
            ctx = de._step_dispatch()
        with self._pctx():
            pe.pump()   # chunk dispatches ride under the decode trip
        sent = None
        if ctx is not None and pe._handoff:
            sent = self._send_next(overlapped=True)
        with self._dctx():
            de._step_complete(ctx)
        if sent is not None:
            sent[0].wait()  # decode fenced first: the together window
            ch.note_both(time.perf_counter() - t_w)
            self._ready.append(sent)
        elif ctx is not None:
            # compute-solo leg: a decode window with no send in flight
            ch.note_compute_solo(time.perf_counter() - t_w)
        if tele_on:
            de._sample_step((time.perf_counter() - t_w) * 1e6, sync0)

    def _send_next(self, *, overlapped: bool):
        """Dispatch the oldest handoff's page migration.  The gather
        captures the prefill pool buffers at dispatch, so the slot's
        pages return to the allocator immediately — the runtime orders
        the device reads before any reuse write."""
        pe, de = self.prefill, self.decode
        slot, st = pe._handoff.pop(0)
        s = pe.cfg.page_size
        n_pages = (st.req.prompt_len + s - 1) // s
        ids = [int(p) for p in pe.cache.block_tables[slot][:n_pages]]
        with self._pctx():
            pending = self.channel.send(
                pe._pool_args(), ids, fence=not overlapped,
                overlapped=overlapped)
        pe.cache.free(slot)
        pe.slots[slot] = None
        done_s = pe._now()
        lat = max(0.0, done_s - st.admitted_s)
        self._handoff_ewma_s = (lat if not self._handoff_ewma_s
                                else 0.5 * self._handoff_ewma_s
                                + 0.5 * lat)
        if de._tele is not None:
            # migration provenance in the flight ring: a stalled
            # handoff is visible next to the decode step walls when an
            # anomaly dumps the window (docs/OBSERVABILITY.md)
            de._tele.record(
                "migration", step=de.engine_steps, pages=len(ids),
                bytes=self.channel.bytes_for_pages(len(ids)),
                overlapped=overlapped,
                queue_depth=len(pe._handoff))
        meta = {"req": st.req, "last_token": st.last_token,
                "admitted_s": st.admitted_s,
                "first_token_s": st.first_token_s,
                "generated": st.generated}
        return (pending, meta)

    def _update_eta(self) -> None:
        """Feed the decode replica's adaptive-N cap: when is the next
        migrated sequence expected?  Ready/handoff work means NOW (the
        loop should sync at the first opportunity) — but ONLY while a
        decode slot is free to land it.  With every slot occupied the
        payload cannot land before a sequence completes, and the
        rem_min cap already times that boundary exactly; a dt~0 ETA
        there would force 1-step trips that slow the very completions
        the payload is waiting on (a measured saturation death spiral:
        full slots -> n=1 -> slower decode -> fuller slots).  An inf
        ETA keeps the rem_min cap armed without the dt clamp.
        Admitted-but-unserved arrivals add the measured handoff
        latency; a future queue head adds it on top of its arrival
        time."""
        pe, de = self.prefill, self.decode
        now = pe._now()
        if self._ready or pe._handoff:
            eta = (now if any(s is None for s in de.slots)
                   else math.inf)
        elif pe.pending or any(s is not None for s in pe.slots):
            eta = now + self._handoff_ewma_s
        elif pe.queue:
            eta = pe.queue[0].arrival_s + self._handoff_ewma_s
        else:
            eta = None
        de._migration_eta_s = eta

    # ---- fault segmentation ------------------------------------------
    def drain_unfinished(self) -> list[Request]:
        """Everything not completed, across BOTH replicas and the
        channel, for a crash-shrink continuation: mid-prefill and
        handoff-pending requests come off the prefill replica, sent-
        but-unadmitted payloads are abandoned (their pages' work is
        redone — the disruption lands in their latency), and the
        decode replica's in-flight sequences lose their migrated pages
        exactly like a monolithic drain.  Arrival stamps are KEPT."""
        pe, de = self.prefill, self.decode
        left = pe.drain_unfinished()
        pe._handoff.clear()
        left += [meta["req"] for _p, meta in self._ready]
        self._ready.clear()
        left += de.drain_unfinished()
        return sorted(left, key=lambda r: r.arrival_s)

    # ---- record assembly ---------------------------------------------
    @property
    def token_streams(self) -> dict:
        """Per-request greedy streams, prefill-side TTFT token first —
        the token-parity surface against a monolithic engine's
        ``token_streams``."""
        out = {rid: list(toks)
               for rid, toks in self.prefill.token_streams.items()}
        for rid, toks in self.decode.token_streams.items():
            out.setdefault(rid, []).extend(toks)
        return out

    def engine_steps(self) -> int:
        return self.prefill.engine_steps + self.decode.engine_steps

    def global_meta(self, plan: ArrivalPlan) -> dict:
        from dlnetbench_tpu.parallel.mesh import (describe_mesh,
                                                  make_flat_mesh)
        cfg = self.cfg
        meta = self.decode.global_meta(plan)
        meta["world_size"] = cfg.world
        # COMPARABLE global (not in merge._VOLATILE_GLOBALS, by
        # design): a disaggregated record must never merge with a
        # monolithic one — the serving block's latency decomposition
        # means something different on each
        meta["disaggregated"] = True
        meta["serving_config"].update({
            "slots": cfg.slots,
            "disaggregate": True,
            "prefill_ranks": cfg.prefill_ranks,
            "decode_ranks": cfg.decode_ranks,
            "prefill_slots": self.prefill.cfg.slots,
            "decode_slots": self.decode.cfg.slots,
            "migration_chunk_pages": cfg.migration_chunk_pages,
        })
        meta["mesh"] = describe_mesh(
            make_flat_mesh(devices=self.devices))
        cm = dict(meta.get("compile_ms", {}))
        for k, v in self.prefill.meta.get("compile_ms", {}).items():
            cm[f"prefill_replica_{k}"] = v
        meta["compile_ms"] = cm
        return meta


def run_disagg(model_cfg: TransformerConfig, cfg: ServingConfig,
               plan: ArrivalPlan, *, fault_plan=None, params=None,
               devices=None, live_metrics=None):
    """One measured disaggregated serving run -> ``ProxyResult`` —
    the ``run_serving`` contract (warmup, fault segmentation, record
    stamping) over the two-replica server.

    Crash under policy ``shrink``: the victim rank identifies its
    replica by range (``rank < prefill_ranks`` is a prefill rank).
    The WHOLE server is rebuilt over the survivors with the dead
    rank's slot share removed from ITS replica only; unfinished
    requests re-queue with original arrival stamps and the migration
    stats of both segments fold into one record."""
    cfg.validate()
    if params is None:
        params = init_params(jax.random.key(0), model_cfg)
    server = DisaggServer(model_cfg, cfg, params=params,
                          devices=devices)
    if live_metrics is not None:
        server.decode.live = (
            live_metrics if hasattr(live_metrics, "maybe_emit")
            else M.LiveMetricsWriter(live_metrics))
    requests = plan.sample()
    if cfg.warmup_requests > 0:
        p_len = min(cfg.prefill_chunk + 1, cfg.max_seq_len - 2)
        warm = [Request(rid=-1 - i, arrival_s=0.0, prompt_len=p_len,
                        output_len=2)
                for i in range(cfg.warmup_requests)]
        with spans.span("warmup", what="disagg engines",
                        reps=len(warm)):
            server.run(warm)
    injector = None
    if fault_plan is not None:
        from dlnetbench_tpu.faults.inject import FaultInjector
        fault_plan.validate()
        injector = FaultInjector(fault_plan, world=cfg.world)

    meta = server.global_meta(plan)
    extra: dict = {}
    try:
        with spans.span("serving_run", requests=len(requests)):
            completed, wall = server.run(requests, injector=injector)
        final = server
    except Exception as e:
        # the shared crash-shrink head (serving/requeue.py): detection
        # stamp, fault trigger, survivor set — re-raises non-shrinkable
        # faults.  The replica tag rides the trigger as caller detail.
        detection_ms, survivors = requeue.detect_shrink(
            e, injector=injector, fault_plan=fault_plan,
            world=cfg.world, step=server.engine_steps(),
            detail={"replica": ("prefill"
                                if (getattr(e, "rank", 0) or 0)
                                < cfg.prefill_ranks else "decode")})
        p_surv = [r for r in survivors if r < cfg.prefill_ranks]
        d_surv = [r for r in survivors if r >= cfg.prefill_ranks]
        if not p_surv or not d_surv:
            # a disaggregated run needs BOTH phases alive — losing a
            # whole replica is unrecoverable under shrink
            raise
        leftovers = requeue.requeue_unfinished(server)
        done0 = server.prefill.completed + server.decode.completed
        t_origin = server.prefill._t0
        steps0 = server.engine_steps()
        occ0 = list(server.decode._occupancy_samples)
        qmax0 = server.prefill.queue_depth_max
        peak0 = server.decode.concurrent_peak
        sends0 = list(server.channel._sends)
        legs0 = (list(server.channel._compute_solo_s),
                 list(server.channel._both_s))
        p_slots = (server.prefill.cfg.slots // cfg.prefill_ranks
                   * len(p_surv))
        d_slots = (server.decode.cfg.slots // cfg.decode_ranks
                   * len(d_surv))
        t0 = time.monotonic()
        shrunk = dataclasses.replace(
            cfg, world=len(survivors), prefill_ranks=len(p_surv),
            decode_ranks=len(d_surv), slots=d_slots)
        with spans.span("serving_rebuild", survivors=len(survivors)):
            server2 = DisaggServer(
                model_cfg, shrunk, params=params,
                devices=[server.devices[r] for r in survivors],
                prefill_slots=p_slots, decode_slots=d_slots)
        server2.decode.live = server.decode.live
        recovery_ms = (time.monotonic() - t0) * 1e3
        done1, wall = requeue.run_requeued(
            server2, leftovers, injector=injector, t_origin=t_origin)
        completed = done0 + done1
        final = server2
        final.decode.engine_steps += steps0
        final.decode._occupancy_samples = \
            occ0 + final.decode._occupancy_samples
        final.prefill.queue_depth_max = max(
            qmax0, final.prefill.queue_depth_max)
        final.decode.concurrent_peak = max(
            peak0, final.decode.concurrent_peak)
        # both segments' migrations are ONE run's wire traffic
        final.channel._sends[:0] = sends0
        final.channel._compute_solo_s[:0] = legs0[0]
        final.channel._both_s[:0] = legs0[1]
        meta["mesh"] = server2.global_meta(plan)["mesh"]
        extra = {"detection_ms": round(detection_ms, 3),
                 "recovery_ms": round(recovery_ms, 3),
                 "degraded_world": survivors,
                 "degraded_slots": d_slots}

    moe_blk = final.decode.moe_block()
    if moe_blk is not None:
        meta["moe"] = moe_blk
    meta["serving"] = M.serving_block(
        completed, plan, slo_ttft_ms=cfg.slo_ttft_ms,
        slo_tpot_ms=cfg.slo_tpot_ms, wall_s=wall,
        engine_steps=final.engine_steps(),
        cache_stats=final.decode.cache.stats(),
        queue_depth_max=final.prefill.queue_depth_max,
        batch_occupancy_mean=final.decode.batch_occupancy_mean(),
        decode_loop=final.decode.decode_loop_block(),
        admitted_peak=final.decode.concurrent_peak,
        migration=final.channel.stats_block())
    if fault_plan is not None:
        meta["fault_plan"] = fault_plan.to_dict()
        meta["fault_policy"] = fault_plan.policy
        meta["fault_injected_delay_us"] = round(
            injector.injected_delay_us, 1)
    meta.update(extra)
    return M.build_result(completed, plan, meta)
