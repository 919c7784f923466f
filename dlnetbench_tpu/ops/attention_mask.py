"""Block-sparse attention masks: declarative specs -> per-block verdicts.

The repo's attention paths were dense-causal only: every kernel paid the
full S x S score grid and masked half of it to -inf — masked-out but
still-paid MXU work, growing as S^2.  This module is the HOST-side mask
layer the splash kernels (ops/flash_attention.py), the sparse ring
attention (ops/sequence_parallel.py) and the serving prefill
(serving/decode.py) all consume:

* ``MaskSpec`` — a tiny declarative, hashable spec: ``causal``,
  ``sliding window(W)`` (each query attends its W most recent keys,
  itself included), and ``document segments`` from a SEEDED segment-id
  plan (splitmix64, the fault/arrival-plan generator — the plan is
  replayable from ``(seg_seed, seg_avg)`` alone), intersected freely.
* ``row_intervals`` — the load-bearing observation: for every spec this
  module admits, the allowed keys of a query row form ONE contiguous
  interval ``[lo[q], hi[q]]``, and both bounds are non-decreasing in
  ``q``.  Everything downstream (block verdicts, ring-hop verdicts,
  the in-kernel partial-block mask, the serving page window) is
  interval arithmetic on those two arrays — never an S x S
  materialization, which at S=64k would be the 4-billion-entry matrix
  this layer exists to avoid.
* ``BlockMask`` — per (q-block, kv-block) verdicts {skip, full,
  partial} precomputed on host from the intervals, plus the transposed
  (per-kv-block) visit ranges the dk/dv kernel needs, the widest visit
  range each way (the length of the kernels' minor grid axis) and the
  ``sparsity_fraction`` stat the bench/record layer stamps.
* ``ring_hop_work`` — the same verdict at ring-hop granularity: an
  [n, n] table saying whether shard ``me``'s queries see shard
  ``src``'s keys at all; hops whose whole tile is SKIP never run their
  compute leg (ops/sequence_parallel.py).

``dense_mask`` builds the equivalent boolean S x S mask for the
CPU-mesh reference path (ops/xla_attention.py applies it densely),
which is what every parity test checks the sparse paths against.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from dlnetbench_tpu.utils.seeded import splitmix64

# BlockMask verdicts
SKIP, PARTIAL, FULL = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Declarative attention-mask spec; hashable, so it rides as a
    static argument through ``jax.custom_vjp`` / ``functools.lru_cache``.

    window=W (W > 0): query q attends keys in ``(q - W, q]`` — the W
    most recent, itself included; requires ``causal`` (a non-causal
    sliding window has no consumer in this repo and would break the
    contiguous-interval property the block math relies on when
    intersected with segments).  seg_avg > 0 turns on the seeded
    document-segment plan: token positions are partitioned into
    documents whose lengths are splitmix64 draws around ``seg_avg``,
    and attention never crosses a document boundary."""
    causal: bool = True
    window: int = 0          # 0 = unbounded
    seg_avg: int = 0         # 0 = no segment structure (tokens)
    seg_seed: int = 0

    def __post_init__(self):
        if self.window < 0 or self.seg_avg < 0:
            raise ValueError(f"MaskSpec: window={self.window} / "
                             f"seg_avg={self.seg_avg} must be >= 0")
        if self.window and not self.causal:
            raise ValueError("MaskSpec: window requires causal=True "
                             "(non-causal sliding windows are not "
                             "supported)")
        if not (self.causal or self.seg_avg):
            raise ValueError("MaskSpec: the trivial all-allowed mask "
                             "has no sparse path — use causal=False "
                             "attention directly")

    @property
    def is_plain_causal(self) -> bool:
        """True when the spec is exactly the dense-causal default."""
        return self.causal and not self.window and not self.seg_avg

    def label(self) -> str:
        """Stable human/record key: 'causal', 'causal&window(512)',
        'causal&seg(avg=64,seed=0)', ..."""
        parts = []
        if self.causal:
            parts.append("causal")
        if self.window:
            parts.append(f"window({self.window})")
        if self.seg_avg:
            parts.append(f"seg(avg={self.seg_avg},seed={self.seg_seed})")
        return "&".join(parts)

    def to_dict(self) -> dict:
        return {"causal": self.causal, "window": self.window,
                "seg_avg": self.seg_avg, "seg_seed": self.seg_seed}

    @classmethod
    def from_dict(cls, d: dict) -> "MaskSpec":
        return cls(causal=bool(d.get("causal", True)),
                   window=int(d.get("window", 0)),
                   seg_avg=int(d.get("seg_avg", 0)),
                   seg_seed=int(d.get("seg_seed", 0)))

    @classmethod
    def from_knobs(cls, window: int, seg_avg: int,
                   seg_seed: int) -> "MaskSpec | None":
        """The config-knob trio (TransformerConfig / SpmdConfig
        ``attention_window``/``attention_seg_avg``/``attention_seg_seed``)
        -> spec, or None when both are off (the dense-causal default —
        bit-identical pre-mask behavior).  The ONE mapping both configs
        share, so their mask semantics can never drift apart."""
        if not (window or seg_avg):
            return None
        return cls(causal=True, window=window, seg_avg=seg_avg,
                   seg_seed=seg_seed)


@functools.lru_cache(maxsize=64)
def segment_ids(seg_seed: int, seg_avg: int, s: int) -> np.ndarray:
    """[S] int32 document ids from the seeded plan: lengths are
    splitmix64 draws uniform in [max(1, avg/2), avg + avg/2] (the
    arrival-plan length-range convention), ids monotone from 0.
    Deterministic in (seed, avg, S) — the plan is the JSON-able pair,
    not the array."""
    if seg_avg <= 0:
        raise ValueError(f"segment_ids: seg_avg={seg_avg} must be > 0")
    lo, hi = max(1, seg_avg // 2), seg_avg + seg_avg // 2
    state = (seg_seed * 0x9E3779B9 + 0xD1B54A32D192ED03) & ((1 << 64) - 1)
    ids = np.empty(s, np.int32)
    pos = doc = 0
    while pos < s:
        v, state = splitmix64(state)
        length = lo + v % (hi - lo + 1)
        ids[pos:pos + length] = doc
        pos += length
        doc += 1
    return ids


@functools.lru_cache(maxsize=64)
def row_intervals(spec: MaskSpec, s: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-query allowed-key interval: ([S] lo, [S] hi), inclusive.

    Both arrays are non-decreasing (causal hi=q; window lo=q-W+1;
    segment bounds step monotonically), which is what makes every
    block-level union of row intervals itself contiguous — the property
    the verdict math and the ring-hop plan rely on."""
    q = np.arange(s, dtype=np.int64)
    lo = np.zeros(s, np.int64)
    hi = (q if spec.causal else np.full(s, s - 1, np.int64)).copy()
    if spec.window:
        lo = np.maximum(lo, q - spec.window + 1)
    if spec.seg_avg:
        ids = segment_ids(spec.seg_seed, spec.seg_avg, s).astype(np.int64)
        # first/last position of each row's document
        starts = np.searchsorted(ids, ids, side="left")
        ends = np.searchsorted(ids, ids, side="right") - 1
        lo = np.maximum(lo, starts)
        hi = np.minimum(hi, ends)
    if not np.all(lo <= hi):
        raise AssertionError("row_intervals: empty row interval — every "
                             "admitted spec keeps q in its own interval")
    return lo, hi


def dense_mask(spec: MaskSpec, s: int) -> np.ndarray:
    """[S, S] bool, mask[q, k] = k allowed for q — the CPU-mesh
    reference the sparse paths are parity-tested against.  O(S^2):
    reference/tests only; the sparse paths never call this."""
    lo, hi = row_intervals(spec, s)
    k = np.arange(s, dtype=np.int64)
    return (k[None, :] >= lo[:, None]) & (k[None, :] <= hi[:, None])


def allowed(spec: MaskSpec, q_pos, k_pos, seg_ids=None):
    """Traceable (jnp-broadcasting) mask predicate over POSITION arrays
    — the one definition of the mask semantics for code that works on
    dynamic positions (ring attention's per-hop tiles, the serving
    prefill's cache+chunk window).  ``q_pos``/``k_pos`` broadcast
    against each other; ``seg_ids`` must be given (a [S]-indexable
    array) when the spec has segments."""
    import jax.numpy as jnp
    m = True
    if spec.causal:
        m = q_pos >= k_pos
    if spec.window:
        m = m & (q_pos - k_pos < spec.window)
    if spec.seg_avg:
        if seg_ids is None:
            raise ValueError("allowed: spec has segments but no seg_ids "
                             "array was provided")
        seg_ids = jnp.asarray(seg_ids)
        m = m & (seg_ids[q_pos] == seg_ids[k_pos])
    return m


def sparsity_fraction(spec: MaskSpec, s: int) -> float:
    """Fraction of the S x S score grid that is MASKED (0.5 for plain
    causal as S -> inf).  Exact, from the row intervals."""
    lo, hi = row_intervals(spec, s)
    return float(1.0 - (hi - lo + 1).sum() / (s * s))


@dataclasses.dataclass(frozen=True)
class BlockMask:
    """Per-block verdicts for one (spec, S, block_q, block_k) choice —
    everything the splash kernels prefetch, as host numpy int32:

    q_first_k/q_last_k   [nq]  kv-block visit range per q block (the
                               fwd/dq kernels walk it; blocks outside
                               issue no DMA and no MXU work)
    kv_first_q/kv_last_q [nk]  q-block visit range per kv block (the
                               dk/dv kernel, whose minor axis walks q)
    q_visits / kv_visits int   the widest of each: the most kv blocks a
                               q block visits, the most q blocks a kv
                               block does.  The kernels' minor grid axis
                               is this long and step r of row i names
                               block first[i] + r, so a band costs its
                               own width in grid steps and not S / block
                               (a step that does nothing still costs a
                               step: 0.17 us of the forward kernel and
                               0.33 of the dk/dv kernel on the v5e, for
                               69 192 of 73 728 steps at window 512 in
                               blocks of 512, S = 16 384, 72 heads)
    blk_lo_max/blk_hi_min [nq] max(lo)/min(hi) over the block's rows —
                               a kv block j is FULL for q block i iff
                               blk_lo_max[i] <= j*bk and
                               blk_hi_min[i] >= (j+1)*bk - 1 (full
                               blocks skip the in-register mask apply)
    lo/hi                [S]   the row intervals (the in-kernel partial
                               mask: k in [lo[q], hi[q]])
    """
    spec: MaskSpec
    seq_len: int
    block_q: int
    block_k: int
    q_first_k: np.ndarray
    q_last_k: np.ndarray
    kv_first_q: np.ndarray
    kv_last_q: np.ndarray
    q_visits: int
    kv_visits: int
    blk_lo_max: np.ndarray
    blk_hi_min: np.ndarray
    lo: np.ndarray
    hi: np.ndarray

    @property
    def nq(self) -> int:
        return self.seq_len // self.block_q

    @property
    def nk(self) -> int:
        return self.seq_len // self.block_k

    @property
    def visited(self) -> int:
        """The (q block, kv block) pairs that are not SKIP: what a grid
        over this mask has to do, whichever axis is minor."""
        return int((self.q_last_k - self.q_first_k + 1).sum())

    def verdicts(self) -> np.ndarray:
        """[nq, nk] uint8 verdict table (SKIP/PARTIAL/FULL) — derived
        from the interval arrays; tests and stats, not the kernels
        (which consume the arrays directly)."""
        j = np.arange(self.nk, dtype=np.int64)
        visit = ((j[None, :] >= self.q_first_k[:, None])
                 & (j[None, :] <= self.q_last_k[:, None]))
        full = ((self.blk_lo_max[:, None] <= j[None, :] * self.block_k)
                & (self.blk_hi_min[:, None]
                   >= (j[None, :] + 1) * self.block_k - 1))
        out = np.where(visit, np.where(full, FULL, PARTIAL), SKIP)
        return out.astype(np.uint8)

    def stats(self) -> dict:
        """Block-level work accounting: the expected-speedup side of
        the bench line's measured speedup-vs-sparsity ratio."""
        v = self.verdicts()
        total = v.size
        skipped = int((v == SKIP).sum())
        return {
            "blocks_total": total,
            "blocks_skipped": skipped,
            "blocks_full": int((v == FULL).sum()),
            "blocks_partial": int((v == PARTIAL).sum()),
            "block_skip_fraction": round(skipped / total, 6),
            "sparsity_fraction": round(
                sparsity_fraction(self.spec, self.seq_len), 6),
        }


@functools.lru_cache(maxsize=64)
def block_mask(spec: MaskSpec, s: int, block_q: int,
               block_k: int) -> BlockMask:
    """Precompute the BlockMask for (spec, S, blocks) — pure interval
    arithmetic, O(S + nq*nk) host work, cached (the same mask serves
    every layer and both fwd/bwd trace sites)."""
    if s % block_q or s % block_k:
        raise ValueError(f"block_mask: blocks ({block_q}, {block_k}) "
                         f"do not divide seq_len {s}")
    lo, hi = row_intervals(spec, s)
    nq, nk = s // block_q, s // block_k
    lo_b = lo.reshape(nq, block_q)
    hi_b = hi.reshape(nq, block_q)
    # row-interval unions per q block are contiguous (monotone bounds):
    # the kv blocks to visit span [min(lo)//bk, max(hi)//bk]
    q_first_k = (lo_b.min(axis=1) // block_k).astype(np.int32)
    q_last_k = (hi_b.max(axis=1) // block_k).astype(np.int32)
    # transposed: the q rows that see key k are [searchsorted(hi, k),
    # searchsorted(lo, k, right) - 1] (monotone bounds again); per kv
    # block take the union over its first/last key
    k_lo = np.arange(nk, dtype=np.int64) * block_k
    k_hi = k_lo + block_k - 1
    kv_first_q = (np.searchsorted(hi, k_lo, side="left")
                  // block_q).astype(np.int32)
    kv_last_q = ((np.searchsorted(lo, k_hi, side="right") - 1)
                 // block_q).astype(np.int32)
    if not (np.all(kv_first_q <= kv_last_q)
            and np.all(kv_first_q >= 0)):
        raise AssertionError("block_mask: empty kv-block q range — "
                             "admitted specs leave no orphan key")
    return BlockMask(
        spec=spec, seq_len=s, block_q=block_q, block_k=block_k,
        q_first_k=q_first_k, q_last_k=q_last_k,
        kv_first_q=kv_first_q, kv_last_q=kv_last_q,
        q_visits=int((q_last_k - q_first_k).max()) + 1,
        kv_visits=int((kv_last_q - kv_first_q).max()) + 1,
        blk_lo_max=lo_b.max(axis=1).astype(np.int32),
        blk_hi_min=hi_b.min(axis=1).astype(np.int32),
        lo=lo.astype(np.int32), hi=hi.astype(np.int32))


def ring_hop_work(spec: MaskSpec | None, s: int, n: int) -> np.ndarray:
    """[n, n] bool: does ring shard ``me``'s query range see shard
    ``src``'s key range at all?  ``work[me, src]`` False = the whole
    (S/n x S/n) tile is masked and the hop's compute leg can be
    skipped (the ppermute still runs — the collective schedule stays
    identical).  ``spec=None`` means plain causal (the default every
    caller had before masks existed): work iff src <= me."""
    me = np.arange(n)
    if spec is None:
        return me[None, :] <= me[:, None]   # src <= me
    if s % n:
        raise ValueError(f"ring_hop_work: seq_len {s} % shards {n} != 0")
    bm = block_mask(spec, s, s // n, s // n)
    return bm.verdicts() != SKIP


def ring_skipped_hop_fraction(spec: MaskSpec | None, s: int,
                              n: int) -> float:
    """Fraction of the n^2 ring (shard, hop) compute legs the mask
    skips — the sparse-ring analogue of the overlap-fraction metric
    (nonzero even for plain causal: the strictly-future hops)."""
    work = ring_hop_work(spec, s, n)
    return float(1.0 - work.mean())


def record_globals(spec: MaskSpec, s: int, *, n_shards: int | None = None
                   ) -> dict:
    """The mask's record-schema globals: COMPARABLE by design (not in
    metrics/merge._VOLATILE_GLOBALS), so records measured under
    different masks refuse to merge exactly like mismatched fault or
    arrival plans — a different mask IS a different run.  Scalars, so
    metrics/parser hoists them to plain DataFrame columns."""
    out = {"attention_mask": spec.label(),
           "mask_sparsity": round(sparsity_fraction(spec, s), 6)}
    if n_shards is not None:
        out["ring_skipped_hop_fraction"] = round(
            ring_skipped_hop_fraction(spec, s, n_shards), 6)
    return out
