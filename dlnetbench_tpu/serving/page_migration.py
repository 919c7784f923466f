"""KV-page migration channel: prefill pool -> decode pool, in the
STORED dtype.

The disaggregated engine (serving/disagg.py) finishes a prompt on the
prefill replica and must hand its KV pages to the decode replica.
This module is that wire, built in the spirit of the PR-4 decomposed
chunk loops: the sequence's pages move as a small host-driven loop of
contiguous chunk transfers, each chunk one ``jax.device_put`` of a
gathered ``[L, Hkv, chunk, S, Dh]`` slab — the single-controller
harness's honest inter-device transport — with the per-page-per-head
f32 scales riding alongside in their own slab.  On a quantized cache
the payload stays int8/fp8 END TO END: the slab is gathered from the
stored pool, moved, and scattered into the destination pool without
ever widening to bf16, so the wire bytes are the quantized pool's
bytes (scales included) and decode-side math is BIT-IDENTICAL to a
monolithic engine that wrote the same pages locally — the token-parity
bar rests on this.

Byte accounting is CLOSED FORM, not measured: a migrated page costs
exactly ``CacheConfig.page_bytes`` (k+v payload rows plus, when
quantized, the 2 * L * Hkv f32 scales) — the same algebra the
kv-density A/B prices pools with, so ``migration_bytes`` in a record
cross-checks against ``pool_bytes`` by construction.  The bf16
equivalent (what the same pages would cost unquantized, no scales) is
kept next to it so the record states its own compression ratio.

Overlap: sends are dispatched either FENCED (solo — the comm-only leg)
or UNFENCED under an in-flight decode dispatch (the overlapped leg).
The channel only records the raw legs; ``overlap_block`` reduces them
through ``metrics/stats.overlap_fraction`` — the SAME A/B overlap
definition every collective in this repo ships — and emits NaN unless
both solo legs AND an overlapped sample were measured (an overlap
number synthesized from one leg would be fiction).
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from dlnetbench_tpu.metrics import stats
from dlnetbench_tpu.serving.kv_cache import CacheConfig
from dlnetbench_tpu.serving.metrics import percentile


def bf16_equiv_page_bytes(cfg: CacheConfig) -> int:
    """What one page's k+v payload would cost stored as bf16 with no
    scale arrays — the denominator of the migration compression ratio
    (the quantized wire moves ``cfg.page_bytes`` against this)."""
    return (2 * cfg.num_layers * cfg.num_kv_heads * cfg.page_size
            * cfg.head_dim * 2)


@dataclasses.dataclass
class SendRecord:
    """One sequence's migration: closed-form bytes, measured wall."""
    pages: int
    bytes: int
    wall_ms: float
    chunks: int
    overlapped: bool   # dispatched under an in-flight decode program


class PendingSend:
    """An unfenced send: the payload slabs are device futures on the
    destination.  ``wait()`` fences and closes the timing window —
    called by the driver AFTER the overlapped decode dispatch fences,
    so the recorded wall covers dispatch -> arrival like any
    async-collective measurement."""

    def __init__(self, channel: "MigrationChannel", slabs: tuple,
                 page_ids: list[int], t0: float, overlapped: bool):
        self._channel = channel
        self.slabs = slabs
        self.page_ids = list(page_ids)
        self._t0 = t0
        self._overlapped = overlapped
        self._record: SendRecord | None = None

    def wait(self) -> SendRecord:
        if self._record is None:
            for slab in self.slabs:
                for arr in slab:
                    arr.block_until_ready()
            ch = self._channel
            rec = SendRecord(
                pages=len(self.page_ids),
                bytes=ch.bytes_for_pages(len(self.page_ids)),
                wall_ms=(time.perf_counter() - self._t0) * 1e3,
                chunks=len(self.slabs),
                overlapped=self._overlapped)
            ch._sends.append(rec)
            self._record = rec
        return self._record


class MigrationChannel:
    """Pages (+ scales) from a source pool to ``dst_device``, moved as
    a chunk loop in the stored dtype.  One channel per disaggregated
    engine pair; its accumulated sends become the record's
    ``migration`` block."""

    def __init__(self, cache_cfg: CacheConfig, dst_device, *,
                 chunk_pages: int = 8):
        if chunk_pages < 1:
            raise ValueError(
                f"page migration: chunk_pages must be >= 1, got "
                f"{chunk_pages}")
        self.cfg = cache_cfg
        self.dst_device = dst_device
        self.chunk_pages = int(chunk_pages)
        self._sends: list[SendRecord] = []
        # overlap legs (seconds): decode-only walls come from the
        # disagg driver (it owns the decode dispatch window)
        self._compute_solo_s: list[float] = []
        self._both_s: list[float] = []
        # gather/scatter are tiny jitted index programs, traced once —
        # they run at handoff boundaries, never inside the compiled
        # decode/prefill programs
        self._gather = jax.jit(lambda pool, ids: pool[:, :, ids])
        self._scatter = jax.jit(
            lambda pool, ids, slab: pool.at[:, :, ids].set(slab),
            donate_argnums=(0,))

    def reset(self) -> None:
        """Clear the accumulated sends and overlap legs (a new measured
        run starts from zero) — the jitted gather/scatter programs are
        kept, so a warm round's traces survive into the measured one."""
        self._sends.clear()
        self._compute_solo_s.clear()
        self._both_s.clear()

    # ---- closed-form byte accounting ---------------------------------
    def bytes_for_pages(self, n_pages: int) -> int:
        """Wire bytes for ``n_pages`` — exactly ``n * page_bytes``
        (scales included when quantized): the record's byte field is
        the pool algebra, cross-checkable, not a transport guess."""
        return int(n_pages) * self.cfg.page_bytes

    def bf16_equiv_bytes(self, n_pages: int) -> int:
        return int(n_pages) * bf16_equiv_page_bytes(self.cfg)

    # ---- the wire ----------------------------------------------------
    def send(self, pools: tuple, page_ids, *, fence: bool = True,
             overlapped: bool = False) -> "PendingSend":
        """Move ``page_ids`` (source-pool physical ids) to the
        destination device.  ``pools`` is the source engine's pool
        tuple — ``(k, v)`` or ``(k, v, k_scale, v_scale)`` — and the
        payload slabs keep that structure and its dtypes: a quantized
        pool's pages cross the wire as int8/fp8 plus f32 scales, never
        as bf16.

        Returns the ``PendingSend`` either way (``scatter`` consumes
        it): ``fence=True`` blocks first, recording the solo comm leg;
        ``fence=False`` leaves the slabs in flight for the driver to
        ``wait()`` after the decode dispatch it overlapped."""
        ids = [int(p) for p in page_ids]
        if not ids:
            raise ValueError("page migration: empty page list — a "
                             "zero-page send is a scheduler bug, not "
                             "a transfer")
        t0 = time.perf_counter()
        slabs = []
        for lo in range(0, len(ids), self.chunk_pages):
            chunk = jnp.asarray(np.asarray(ids[lo:lo + self.chunk_pages],
                                           np.int32))
            moved = tuple(
                jax.device_put(self._gather(pool, chunk),
                               self.dst_device)
                for pool in pools)
            slabs.append(moved)
        pending = PendingSend(self, tuple(slabs), ids, t0,
                              overlapped=overlapped)
        if fence:
            pending.wait()
        return pending

    def scatter(self, dst_pools: tuple, pending: PendingSend,
                dst_page_ids) -> tuple:
        """Land a fenced send's slabs in the destination pools at
        ``dst_page_ids`` (the decode cache's allocation for this
        sequence, positional: source page k -> dst page k).  Returns
        the rebound pool tuple (pools are donated, functional-update
        style, like every pool program in the engine)."""
        dst = [int(p) for p in dst_page_ids]
        if len(dst) != len(pending.page_ids):
            raise ValueError(
                f"page migration: {len(pending.page_ids)} pages sent "
                f"but {len(dst)} destination pages allocated — the "
                f"block tables would desync from the payload")
        pools = tuple(dst_pools)
        off = 0
        for slab in pending.slabs:
            n = int(slab[0].shape[2])
            ids = jnp.asarray(np.asarray(dst[off:off + n], np.int32))
            pools = tuple(self._scatter(pool, ids, part)
                          for pool, part in zip(pools, slab))
            off += n
        return pools

    # ---- overlap legs (driver-fed) -----------------------------------
    def note_compute_solo(self, wall_s: float) -> None:
        """A decode dispatch window with NO send in flight (the
        compute-only leg)."""
        self._compute_solo_s.append(float(wall_s))

    def note_both(self, wall_s: float) -> None:
        """A decode dispatch window that covered an in-flight send,
        measured dispatch -> both fenced (the together leg)."""
        self._both_s.append(float(wall_s))

    # ---- the record block --------------------------------------------
    def overlap(self) -> float:
        """Median-leg overlap fraction, or NaN: the metric exists only
        when the comm-solo, compute-solo AND together legs were all
        measured this run — anything less and the A/B decomposition
        has a missing arm."""
        comm = [r.wall_ms * 1e-3 for r in self._sends
                if not r.overlapped]
        if not comm or not self._compute_solo_s or not self._both_s:
            return float("nan")
        med = stats.summarize
        tm = med(comm)["value"]
        tc = med(self._compute_solo_s)["value"]
        tb = med(self._both_s)["value"]
        return stats.overlap_fraction([tb], [tc], [tm])[0]

    def stats_block(self) -> dict | None:
        """The serving record's ``migration`` sub-block; None when the
        channel never carried a sequence (a monolithic run's record is
        byte-identical to pre-disagg)."""
        if not self._sends:
            return None
        pages = sum(r.pages for r in self._sends)
        walls = [r.wall_ms for r in self._sends]
        ov = self.overlap()
        return {
            "sends": len(self._sends),
            "pages": pages,
            "bytes": self.bytes_for_pages(pages),
            "bf16_equiv_bytes": self.bf16_equiv_bytes(pages),
            "bytes_ratio_vs_bf16": round(
                self.bytes_for_pages(pages)
                / max(1, self.bf16_equiv_bytes(pages)), 4),
            "chunk_pages": self.chunk_pages,
            "ms": {"total": round(sum(walls), 3),
                   "p50": round(percentile(walls, 50), 3),
                   "mean": round(sum(walls) / len(walls), 3),
                   "n": len(walls)},
            "overlap": (round(ov, 4) if not math.isnan(ov)
                        else float("nan")),
            "overlapped_sends": sum(1 for r in self._sends
                                    if r.overlapped),
        }
