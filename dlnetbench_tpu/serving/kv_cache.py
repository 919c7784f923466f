"""Block-table paged KV cache + the paged-attention decode path.

The cache is split along the host/device line the way a real serving
engine splits it:

* ``PagedKVCache`` (host) — the page allocator: a free list over
  ``num_pages`` physical pages, per-slot block tables mapping logical
  token positions to (page, slot-in-page), allocate/append/free, and
  occupancy/fragmentation stats.  Pure numpy bookkeeping; nothing here
  touches a device.  ISSUE 12 grows it two serving-density levers:

  - **refcounts + prefix sharing**: every physical page carries a
    refcount, and a radix-style trie over prompt token ids
    (``_PrefixTrie``) lets a new request whose prompt shares a prefix
    with a RESIDENT, fully-prefilled sequence map its block-table
    entries onto the same physical pages.  Fully-covered pages are
    shared by reference (admission charges only the UNSHARED pages);
    a partially-covered boundary page is shared copy-on-write — the
    sequence will write into it (remaining prompt or its first decode
    token), so admission resolves the COW eagerly: a private page is
    charged to the reservation and the engine copies the prefix rows
    device-side.  ``append`` asserts it never grows into a page with
    refcount > 1 — block tables must never alias a written page.
  - **quantized pools**: ``CacheConfig.cache_dtype`` selects int8 or
    fp8(e4m3) page pools with a per-page-per-head f32 scale array
    beside each pool (``[L, Hkv, num_pages]``) — ~2x the pages per
    pool byte of a bf16 cache, ~4x of an f32 one.  ``"bf16"`` (the
    default label for the UNQUANTIZED cache — pools stay in the
    model's compute dtype) builds none of the quant machinery, so the
    dense path is bit-identical to the pre-ISSUE-12 cache.

* device page buffers — ``k_pages``/``v_pages`` arrays of shape
  ``[layers, kv_heads, num_pages, page_size, head_dim]`` (the layout
  the Pallas TPU ``paged_attention`` kernel consumes per layer),
  created by ``device_buffers`` and threaded FUNCTIONALLY through the
  compiled decode/prefill programs (serving/decode.py) — the engine
  rebinds them from program outputs, the executor donates them.
  Quantized configs add ``k_scale``/``v_scale`` arrays riding the same
  functional thread (written beside every page write, donated carries
  of the fused loop like the pools themselves).

Quantized cache writes go through ``quant_write_span``: the touched
page is re-quantized against a FRESH amax over its valid rows (masked
to the sequence's own content, so page reuse can never inherit a stale
scale), sharing ``scale_from_amax``/``quantize_tensor``'s ``_cast_q``
definitions with ops/quantized_matmul.py — one spelling of the scale
math across the repo's quant recipes.

``paged_attention_decode`` dispatches the per-layer decode attention:
the Pallas ``jax.experimental.pallas.ops.tpu.paged_attention`` kernel
on a TPU backend, and a dense gather-attention fallback (gather the
sequence's pages into a contiguous [T, d] view, mask by length) on the
CPU mesh — the same backend split ``ops/pallas_common.interpret_mode``
gates every kernel in ops/ on, so the whole serving tier is
unit-testable on a laptop.  With scale arrays the dispatch routes to
``ops/paged_attention_quant.quant_paged_attention`` (pages gathered
QUANTIZED — int8/fp8 through HBM, never round-tripped as bf16 — and
dequantized in the kernel's VMEM prologue against the prefetched
scales) or a dequantizing gather fallback off-TPU.
``sharded_paged_attention`` wraps either impl in ``shard_map`` sharded
along GQA KV heads (the SNIPPETS.md [3] recipe): KV pages are
partitioned by head, query heads follow their group, and no collective
is needed until the output projection.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from dlnetbench_tpu.ops import pallas_common
from dlnetbench_tpu.utils.jax_compat import shard_map

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

_F32 = jnp.float32

# cache dtypes: "bf16" labels the UNQUANTIZED pool (stored in the
# model's own compute dtype — float32 on the CPU mesh, bf16 on chip),
# where none of the quant machinery is even built.  The quantized
# formats map onto ops/quantized_matmul's recipe table, so the scale
# definitions (and the int8/fp8 tolerance story) are shared.
CACHE_DTYPES = ("bf16", "int8", "fp8")
_QUANT_FMT = {"int8": "int8", "fp8": "float8"}
_QUANT_JNP = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}

# stated decode-parity tolerance bars, per recipe (ISSUE 12): max
# absolute error of the paged-attention output vs the bf16 cache on
# unit-scale activations.  int8 carries ~1/254 per-element rounding
# plus bounded fresh-amax requant drift; fp8(e4m3) carries ~6%
# relative per element, softmax-averaged down.  Tests, the bench
# kv_density_ab line and the committed study all enforce THESE bars —
# one spelling of the tolerance story (observed on the CPU mesh:
# int8 ~0.01, fp8 ~0.08).
QUANT_DECODE_TOL = {"int8": 0.05, "fp8": 0.15}


class CacheOOM(RuntimeError):
    """The free list is empty — the admission-control contract was
    violated (the scheduler must reserve a request's worst-case pages
    at admit time, so a running sequence can always append)."""


@dataclasses.dataclass
class CacheConfig:
    num_layers: int
    num_kv_heads: int
    head_dim: int
    num_pages: int           # physical pages shared by every slot
    page_size: int           # tokens per page
    max_seqs: int            # decode slots (the block table's rows)
    max_pages_per_seq: int   # block-table width = max seq len / page_size
    dtype: str = "float32"
    cache_dtype: str = "bf16"   # "bf16" (unquantized, pools in `dtype`)
    #                             | "int8" | "fp8" (e4m3) — quantized
    #                             pools + per-page-per-head f32 scales

    @property
    def max_seq_len(self) -> int:
        return self.max_pages_per_seq * self.page_size

    @property
    def quantized(self) -> bool:
        return self.cache_dtype != "bf16"

    @property
    def quant_fmt(self) -> str | None:
        """The ops/quantized_matmul format name, or None when dense."""
        return _QUANT_FMT.get(self.cache_dtype)

    @property
    def pool_jnp_dtype(self):
        return (_QUANT_JNP[self.cache_dtype] if self.quantized
                else jnp.dtype(self.dtype))

    # ---- pool-bytes accounting (the honest "same pool bytes" axis of
    # the density A/B: scale arrays COUNT — a quantized pool that got
    # its scales for free would overstate the capacity win)
    @property
    def page_bytes(self) -> int:
        """Device bytes ONE physical page costs across both pools:
        k+v payload rows plus, when quantized, the per-page-per-head
        scale entries."""
        payload = (2 * self.num_layers * self.num_kv_heads
                   * self.page_size * self.head_dim
                   * jnp.dtype(self.pool_jnp_dtype).itemsize)
        scales = (2 * self.num_layers * self.num_kv_heads * 4
                  if self.quantized else 0)
        return payload + scales

    @property
    def pool_bytes(self) -> int:
        """Total device bytes of the paged cache (pools + scales)."""
        return self.num_pages * self.page_bytes

    def validate(self) -> "CacheConfig":
        for name in ("num_layers", "num_kv_heads", "head_dim",
                     "num_pages", "page_size", "max_seqs",
                     "max_pages_per_seq"):
            if getattr(self, name) < 1:
                raise ValueError(f"kv cache: {name} must be >= 1")
        if self.cache_dtype not in CACHE_DTYPES:
            raise ValueError(
                f"kv cache: unknown cache_dtype {self.cache_dtype!r} "
                f"(one of {CACHE_DTYPES})")
        # the loud-refusal guard, extended to every cache dtype
        # (ISSUE 12 satellite): a pool that cannot hold even ONE
        # max-seq-len request starves the admission gate forever —
        # quantized configs hit this exactly like dense ones when a
        # byte budget (scale arrays included) converts to too few pages
        if self.num_pages < self.max_pages_per_seq:
            raise ValueError(
                f"kv cache: num_pages {self.num_pages} cannot hold even "
                f"one max-seq request ({self.max_pages_per_seq} pages "
                f"at cache_dtype={self.cache_dtype}, "
                f"{self.page_bytes} B/page incl. scales) — the "
                f"admission gate would starve the queue head forever")
        return self


def pages_for_pool_bytes(budget_bytes: int, cfg: CacheConfig) -> int:
    """How many physical pages a byte budget buys under ``cfg``'s
    geometry and cache dtype — the equal-pool-bytes axis of the density
    A/B (scale arrays priced in via ``page_bytes``).  The returned
    count still has to pass ``validate``'s one-request guard; a budget
    too small for that fails THERE, loudly."""
    if budget_bytes < 1:
        raise ValueError(f"pages_for_pool_bytes: budget {budget_bytes}")
    return max(1, budget_bytes // cfg.page_bytes)


# ---------------------------------------------------------------------
# prefix trie (host): prompt token ids -> resident physical pages


class _TrieNode:
    """One cached page's worth of prompt tokens.  ``key`` is the token
    tuple the page holds (length == page_size for interior nodes; a
    shorter tuple is a partial boundary page, shareable copy-on-write
    up to its length).  Children may overlap in prefix (a loose radix:
    lookup scans the few children of a node for the best match)."""

    __slots__ = ("key", "page", "parent", "children")

    def __init__(self, key: tuple, page: int, parent):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: dict[tuple, _TrieNode] = {}


class _PrefixTrie:
    """Radix-style trie over PUBLISHED prompt pages.  Non-owning: a
    node exists exactly while its physical page has readers (the
    allocator removes the node when the refcount hits zero), so a
    lookup can never hand out a freed page."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _TrieNode((), -1, None)
        self._node_of_page: dict[int, _TrieNode] = {}

    def match(self, tokens) -> tuple[int, list[int], int | None]:
        """Longest shared prefix of ``tokens`` against the published
        pages: ``(shared_tokens, full_page_ids, partial_page_id)``.
        ``full_page_ids`` are the fully-covered physical pages (in
        column order); ``partial_page_id`` is the boundary page whose
        first ``shared_tokens % page_size`` rows match (None when the
        match is page-aligned)."""
        toks = tuple(int(t) for t in tokens)
        node, pos, full = self.root, 0, []
        s = self.page_size
        while len(toks) - pos >= s:
            child = node.children.get(toks[pos:pos + s])
            if child is None:
                break
            full.append(child.page)
            node = child
            pos += s
        # partial boundary: the child sharing the longest token-level
        # prefix with the remaining tokens (>= 1 token to be worth a
        # copy-on-write share)
        rest = toks[pos:]
        best_len, best_page = 0, None
        for key, child in node.children.items():
            n = 0
            for a, b in zip(key, rest):
                if a != b:
                    break
                n += 1
            if n > best_len:
                best_len, best_page = n, child.page
        if best_len > 0:
            return pos + best_len, full, best_page
        return pos, full, None

    def publish(self, tokens, pages: list[int]) -> None:
        """Register a fully-prefilled prompt's pages.  Idempotent: a
        path already present (the publisher shared it) is left alone —
        first publisher wins, content is identical by construction."""
        toks = tuple(int(t) for t in tokens)
        s = self.page_size
        node, pos, col = self.root, 0, 0
        while pos < len(toks):
            key = toks[pos:pos + s]
            child = node.children.get(key)
            if child is None:
                page = pages[col]
                if page in self._node_of_page:
                    # this physical page already backs another path
                    # node (a shared page republished under a longer
                    # prompt): never double-register
                    child = self._node_of_page[page]
                    if child.key != key:
                        break
                else:
                    child = _TrieNode(key, page, node)
                    node.children[key] = child
                    self._node_of_page[page] = child
            node = child
            pos += len(key)
            col += 1
            if len(key) < s:      # partial tail published; path ends
                break

    def drop_page(self, page: int) -> None:
        """The page's refcount hit zero: unlink its node.  Holders of a
        child page always hold the parent too (they matched the whole
        path), so a dying node can have no live children."""
        node = self._node_of_page.pop(page, None)
        if node is not None and node.parent is not None:
            node.parent.children.pop(node.key, None)


@dataclasses.dataclass
class AdmissionPlan:
    """What admitting one request costs and shares (``plan_admission``
    -> ``admit``): the UNSHARED page charge, the matched prefix, and
    the eager copy-on-write source for a partially-shared boundary
    page (the engine performs the device copy)."""
    n_tokens: int
    need_pages: int
    shared_tokens: int = 0
    shared_pages: list = dataclasses.field(default_factory=list)
    cow_src: int | None = None       # physical page to copy from
    cow_rows: int = 0                # valid prefix rows in cow_src


class PagedKVCache:
    """Host-side page allocator + block tables (one row per decode
    slot).  Page 0 is a real, allocatable page; block-table padding
    also points at 0 — harmless, every consumer masks by length.

    Every physical page carries a refcount; prefix sharing maps one
    page into several block tables and a page returns to the free list
    exactly when its LAST reader frees it.  ``append`` refuses to grow
    into a page with refcount > 1 (a shared page is read-only; writes
    land only after the admission-time copy-on-write)."""

    def __init__(self, cfg: CacheConfig):
        self.cfg = cfg.validate()
        self._free: list[int] = list(range(cfg.num_pages - 1, -1, -1))
        self._ref = np.zeros((cfg.num_pages,), np.int32)
        self.block_tables = np.zeros(
            (cfg.max_seqs, cfg.max_pages_per_seq), np.int32)
        self.lengths = np.zeros((cfg.max_seqs,), np.int32)
        self._pages_of: list[list[int]] = [[] for _ in range(cfg.max_seqs)]
        self.peak_pages_in_use = 0
        self.trie = _PrefixTrie(cfg.page_size)
        # prefix-sharing stats (ride the record via stats())
        self.admissions = 0
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_shared_tokens = 0
        self.prefix_pages_shared = 0
        self.prefix_bytes_saved = 0
        self.cow_copies = 0

    # ---- allocator ---------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.cfg.num_pages - len(self._free)

    def can_fit(self, n_tokens: int) -> bool:
        need = -(-n_tokens // self.cfg.page_size)
        return need <= len(self._free)

    def prefix_match_len(self, prompt_tokens) -> int:
        """Read-only trie probe (ISSUE 18): how many of this prompt's
        leading tokens are already RESIDENT in this pool's published
        pages.  The fleet router's prefix-affinity placement signal —
        the replica with the longest match serves the prompt with the
        fewest prefill chunks and zero cross-replica page motion.
        Capped at ``prompt_len - 1`` exactly like ``plan_admission``
        (the final prompt token always re-prefills), and deliberately
        NOT counted in ``prefix_lookups``/hit stats: a routing probe
        across N replicas is not an admission and must not dilute the
        per-pool hit rate the density study reports."""
        if prompt_tokens is None or len(prompt_tokens) < 2:
            return 0
        matched, _full, _partial = self.trie.match(
            np.asarray(prompt_tokens)[: len(prompt_tokens) - 1])
        return int(matched)

    def plan_admission(self, n_tokens: int,
                       prompt_tokens=None) -> AdmissionPlan:
        """Price one admission.  With ``prompt_tokens`` (prefix sharing
        on) the trie is consulted: fully-matched pages are shared by
        reference and the charge covers only the unshared pages — the
        partially-matched boundary page is charged (its copy-on-write
        private copy) but its prefix rows skip prefill.  The match is
        capped at ``prompt_len - 1``: the final prompt token always
        re-prefills, because its forward pass produces the request's
        FIRST generated token (the TTFT stamp)."""
        s = self.cfg.page_size
        total = -(-n_tokens // s)
        if total > self.cfg.max_pages_per_seq:
            raise ValueError(
                f"kv cache: {n_tokens} tokens need {total} pages > "
                f"max_pages_per_seq {self.cfg.max_pages_per_seq}")
        if prompt_tokens is None or len(prompt_tokens) < 2:
            return AdmissionPlan(n_tokens=n_tokens, need_pages=total)
        self.prefix_lookups += 1
        matched, full_pages, partial_page = self.trie.match(
            np.asarray(prompt_tokens)[: len(prompt_tokens) - 1])
        full = len(full_pages)
        partial = matched - full * s
        if partial <= 0:
            partial_page = None
            matched = full * s
        return AdmissionPlan(
            n_tokens=n_tokens, need_pages=total - full,
            shared_tokens=matched, shared_pages=list(full_pages),
            cow_src=partial_page, cow_rows=partial)

    def admit(self, slot: int, plan: AdmissionPlan) -> int | None:
        """Execute an admission plan on an empty slot: shared pages by
        reference (refcount bump), the rest freshly allocated — the
        boundary-page private copy included.  Returns the physical COW
        DESTINATION page when the plan carries one (the engine copies
        ``plan.cow_src``'s rows into it device-side) or None.
        ``lengths[slot]`` starts at ``shared_tokens`` — that content is
        already cached."""
        if self._pages_of[slot]:
            raise ValueError(f"kv cache: slot {slot} already allocated")
        if plan.need_pages > len(self._free):
            raise CacheOOM(
                f"kv cache: need {plan.need_pages} pages, "
                f"{len(self._free)} free — admission control must gate "
                f"on the plan (can_fit() for the no-sharing path)")
        total = -(-plan.n_tokens // self.cfg.page_size)
        self.admissions += 1
        cow_dst = None
        for i in range(total):
            if i < len(plan.shared_pages):
                page = plan.shared_pages[i]
                self._ref[page] += 1
            else:
                page = self._free.pop()
                self._ref[page] = 1
                if i == len(plan.shared_pages) and plan.cow_src is not None:
                    cow_dst = page
            self._pages_of[slot].append(page)
            self.block_tables[slot, i] = page
        self.lengths[slot] = plan.shared_tokens
        if plan.shared_tokens > 0:
            self.prefix_hits += 1
            self.prefix_shared_tokens += plan.shared_tokens
            self.prefix_pages_shared += len(plan.shared_pages)
            self.prefix_bytes_saved += (len(plan.shared_pages)
                                        * self.cfg.page_bytes)
        if cow_dst is not None:
            self.cow_copies += 1
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        return cow_dst

    def allocate(self, slot: int, n_tokens: int) -> None:
        """Reserve pages for ``n_tokens`` on an empty slot (admission:
        the scheduler reserves prompt+output worst case up front, so
        ``append`` can never OOM mid-sequence).  The no-sharing path —
        ``plan_admission``/``admit`` with no prompt tokens."""
        self.admit(slot, self.plan_admission(n_tokens))

    def append(self, slot: int, n: int = 1) -> None:
        """Advance the slot's length by ``n`` tokens (the device write
        happened inside the compiled step); grows into the reserved
        pages — exceeding the reservation is a scheduler bug, and so is
        writing into a page another sequence still reads (COW must have
        replaced it at admission)."""
        s = self.cfg.page_size
        old_len = int(self.lengths[slot])
        new_len = old_len + n
        if new_len > len(self._pages_of[slot]) * s:
            raise CacheOOM(
                f"kv cache: slot {slot} grew to {new_len} tokens past "
                f"its {len(self._pages_of[slot])}-page reservation")
        for col in range(old_len // s, (new_len - 1) // s + 1):
            page = self._pages_of[slot][col]
            if self._ref[page] > 1:
                raise RuntimeError(
                    f"kv cache: slot {slot} wrote into shared page "
                    f"{page} (refcount {int(self._ref[page])}) — a "
                    f"shared page is read-only; copy-on-write must "
                    f"have replaced it at admission")
        self.lengths[slot] = new_len

    def free(self, slot: int) -> None:
        for page in self._pages_of[slot]:
            self._ref[page] -= 1
            if self._ref[page] == 0:
                self._free.append(page)
                self.trie.drop_page(page)
            elif self._ref[page] < 0:  # pragma: no cover - invariant
                raise RuntimeError(
                    f"kv cache: page {page} refcount went negative")
        self._pages_of[slot] = []
        self.block_tables[slot, :] = 0
        self.lengths[slot] = 0

    def publish(self, slot: int, prompt_tokens) -> None:
        """Register the slot's fully-prefilled PROMPT pages in the
        trie so later arrivals can share them.  Only the prompt is
        published — generated tokens are request-specific."""
        toks = np.asarray(prompt_tokens)
        n = -(-len(toks) // self.cfg.page_size)
        self.trie.publish(toks, self._pages_of[slot][:n])

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    # ---- stats (ride the serving record block) -----------------------
    def stats(self) -> dict:
        """Occupancy = fraction of physical pages in use; fragmentation
        = fraction of ALLOCATED token capacity holding no token (the
        cost of page-granular allocation + worst-case reservation;
        shared pages count once).  Prefix-sharing counters ride along
        whenever a lookup happened."""
        cap = self.pages_in_use * self.cfg.page_size
        toks = int(self.lengths.sum())
        out = {
            "num_pages": self.cfg.num_pages,
            "page_size": self.cfg.page_size,
            "cache_dtype": self.cfg.cache_dtype,
            "pool_bytes": self.cfg.pool_bytes,
            "pages_in_use": self.pages_in_use,
            "peak_pages_in_use": self.peak_pages_in_use,
            "admissions": self.admissions,
            "occupancy": round(self.pages_in_use / self.cfg.num_pages, 4),
            "peak_occupancy": round(
                self.peak_pages_in_use / self.cfg.num_pages, 4),
            "fragmentation": (round(max(cap - toks, 0) / cap, 4)
                              if cap else 0.0),
        }
        if self.prefix_lookups:
            out["prefix"] = {
                "lookups": self.prefix_lookups,
                "hits": self.prefix_hits,
                # per ADMISSION, not per lookup: a blocked queue
                # head is re-planned every engine iteration and must
                # not dilute the rate
                "hit_rate": round(self.prefix_hits
                                  / max(self.admissions, 1), 4),
                "shared_tokens": self.prefix_shared_tokens,
                "pages_shared": self.prefix_pages_shared,
                "bytes_saved": self.prefix_bytes_saved,
                "cow_copies": self.cow_copies,
            }
        return out


def device_buffers(cfg: CacheConfig):
    """Zeroed K/V page pools: ``[L, H_kv, num_pages, page_size, Dh]``
    (the Pallas kernel's per-layer layout, stacked over layers).
    Dense configs return ``(k, v)`` exactly as before ISSUE 12;
    quantized configs return ``(k, v, k_scale, v_scale)`` with the
    per-page-per-head f32 scale arrays (``[L, H_kv, num_pages]``,
    initialized to 1.0 — a zeroed page dequantizes to zeros)."""
    shape = (cfg.num_layers, cfg.num_kv_heads, cfg.num_pages,
             cfg.page_size, cfg.head_dim)
    dt = cfg.pool_jnp_dtype
    k, v = jnp.zeros(shape, dt), jnp.zeros(shape, dt)
    if not cfg.quantized:
        return k, v
    sshape = shape[:3]
    return k, v, jnp.ones(sshape, _F32), jnp.ones(sshape, _F32)


# ---------------------------------------------------------------------
# quantized page writes (the decode/prefill cache-write epilogue)


def quant_write_span(pages, scales, li: int, new, positions, write_ok,
                     block_tables, *, fmt: str, page_size: int,
                     num_pages: int):
    """Write a span of tokens into a QUANTIZED page pool: token ``j``
    of slot ``b`` lands at position ``positions[b] + j`` (gated by
    ``write_ok[b, j]``), and every touched page is re-quantized against
    a FRESH amax over its valid rows — the already-cached prefix
    (dequantized at the old scale) plus the new rows, masked to the
    sequence's own content so a reused page can never inherit garbage
    into its scale.  Shares ``scale_from_amax``/``_cast_q`` with
    ops/quantized_matmul.py (the PR-3 recipes — one scale spelling).

    pages: ``[L, Hkv, P, S, Dh]`` quantized; scales: ``[L, Hkv, P]``
    f32; new: ``[B, K, Hkv, Dh]`` master dtype; positions: ``[B]``;
    write_ok: ``[B, K]``; block_tables: ``[B, pmax]``.  Returns the
    updated ``(pages, scales)``.  Slots (or whole page columns) with
    no enabled write scatter out-of-bounds and drop — an inactive
    slot's stale block table is never touched."""
    from dlnetbench_tpu.ops.quantized_matmul import (_cast_q,
                                                     scale_from_amax)
    b, k1 = write_ok.shape
    s = page_size
    pmax = block_tables.shape[1]
    j_idx = jnp.arange(k1, dtype=jnp.int32)
    tok_pos = positions[:, None] + j_idx[None, :]            # [B, K]
    tok_col = tok_pos // s
    row_of_tok = tok_pos % s
    rows = jnp.arange(s, dtype=jnp.int32)
    # static bound on distinct page columns one span can touch
    ncols = (k1 + s - 2) // s + 1
    for c in range(ncols):
        col = positions // s + c                             # [B]
        in_col = (tok_col == col[:, None]) & write_ok        # [B, K]
        any_w = jnp.any(in_col, axis=1)                      # [B]
        page = jnp.take_along_axis(
            block_tables, jnp.clip(col, 0, pmax - 1)[:, None],
            axis=1)[:, 0]                                    # [B]
        w_page = jnp.where(any_w, page, num_pages)           # OOB drop
        pc = jnp.minimum(page, num_pages - 1)                # gather ok
        oldq = pages[li][:, pc]                              # [H,B,S,D]
        olds = scales[li][:, pc]                             # [H,B]
        deq = oldq.astype(_F32) * olds[:, :, None, None]
        old_valid = rows[None, :] < jnp.clip(
            positions - col * s, 0, s)[:, None]              # [B, S]
        base = jnp.where(old_valid[None, :, :, None], deq, 0.0)
        onehot = (in_col[:, :, None]
                  & (rows[None, None, :] == row_of_tok[:, :, None]))
        new_rows = jnp.einsum("bks,bkhd->hbsd",
                              onehot.astype(_F32), new.astype(_F32))
        new_mask = jnp.any(onehot, axis=1)                   # [B, S]
        pagef = jnp.where(new_mask[None, :, :, None], new_rows, base)
        amax = jnp.max(jnp.abs(pagef), axis=(2, 3))          # [H, B]
        scale = scale_from_amax(amax, fmt)
        q = _cast_q(pagef / scale[:, :, None, None], fmt)
        # jax scatter puts advanced-index dims FIRST: the slice shape
        # of ``[li, :, w_page]`` is [B, Hkv, S, Dh], so the head-major
        # page tile transposes on the way in (a silent wrong-data
        # broadcast when B == Hkv — caught by the parity tests)
        pages = pages.at[li, :, w_page].set(
            jnp.swapaxes(q, 0, 1), mode="drop")
        scales = scales.at[li, :, w_page].set(scale.T, mode="drop")
    return pages, scales


def dequant_gathered(pages_g, scales_g):
    """Gathered quantized pages -> f32: ``pages_g`` [..., pages, S, Dh]
    times the matching [..., pages] scales (broadcast over rows)."""
    return pages_g.astype(_F32) * scales_g[..., None, None]


# ---------------------------------------------------------------------
# decode attention over the page pool


def _gather_attention(q, k_pages, v_pages, lengths, page_indices,
                      k_scale=None, v_scale=None):
    """Dense fallback: gather each sequence's pages contiguous, mask by
    length, fp32 softmax.  ``q`` arrives PRE-SCALED (both impls share
    the convention; the Pallas kernel applies no sm_scale either).
    With scale arrays the gathered pages are dequantized first — the
    CPU-mesh form of the quantized decode path.

    q: [B, Hq, Dh]; k/v_pages: [Hkv, P, S, Dh]; lengths: [B] (valid
    tokens incl. the one just written); page_indices: [B, Pmax];
    k/v_scale: [Hkv, P] f32 or None."""
    hkv = k_pages.shape[0]
    s = k_pages.shape[2]
    # [Hkv, B, Pmax, S, Dh] -> [B, Hkv, Pmax, S, Dh]
    k = jnp.moveaxis(k_pages[:, page_indices], 0, 1).astype(jnp.float32)
    v = jnp.moveaxis(v_pages[:, page_indices], 0, 1).astype(jnp.float32)
    if k_scale is not None:
        k = k * jnp.moveaxis(k_scale[:, page_indices], 0, 1)[..., None,
                                                             None]
        v = v * jnp.moveaxis(v_scale[:, page_indices], 0, 1)[..., None,
                                                             None]
    b, _, pmax, _, dh = k.shape
    k = k.reshape(b, hkv, pmax * s, dh)
    v = v.reshape(b, hkv, pmax * s, dh)
    g = q.shape[1] // hkv
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhtd->bhgt", qg, k)
    mask = jnp.arange(pmax * s)[None, :] < lengths[:, None]  # [B, T]
    scores = jnp.where(mask[:, None, None, :], scores, MASK_VALUE)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p, v)
    return out.reshape(b, hkv * g, dh).astype(q.dtype)


def resolve_pages_per_compute_block(q, k_pages, page_indices,
                                    pages_per_compute_block: int | None,
                                    fmt: str | None = None) -> int:
    """The Pallas kernel's ``pages_per_compute_block`` knob: an
    EXPLICIT value always wins and must divide the per-sequence page
    count exactly (an experiment knob fails loud — a silently adjusted
    block would record a time for a config nobody asked for); ``None``
    consults the tuning DB (dlnetbench_tpu/tuning, keyed per cache
    geometry x chip) and falls back to the historical default
    ``fit_block(pages_per_seq, min(pages_per_seq, 8))`` bit-identically
    on a miss (ISSUE 9 satellite — this replaces the old inline
    hard-code).  With ``fmt`` the QUANTIZED kernel is the consumer —
    its own DB site (op ``paged_attention_quant``, format in the key):
    dequant changes the kernel's arithmetic intensity, so a dense
    optimum must never answer a quantized consult (ISSUE 12)."""
    pages_per_seq = page_indices.shape[1]
    if pages_per_compute_block is not None:
        p = pages_per_compute_block
        if not isinstance(p, int) or p < 1 or pages_per_seq % p:
            raise ValueError(
                f"paged_attention: pages_per_compute_block={p!r} does "
                f"not divide pages_per_seq {pages_per_seq}")
        return p
    default = pallas_common.fit_block(pages_per_seq,
                                      min(pages_per_seq, 8))
    from dlnetbench_tpu import tuning

    def check(cfg: dict) -> None:
        p = cfg.get("pages_per_compute_block")
        if not isinstance(p, int) or p < 1 or pages_per_seq % p:
            raise ValueError(
                f"pages_per_compute_block={p!r} does not divide "
                f"pages_per_seq {pages_per_seq}")
    b, hq, dh = q.shape
    hkv, _, page_size, _ = k_pages.shape
    if fmt is None:
        op = "paged_attention"
        key = tuning.params.paged_attention_key(pages_per_seq,
                                                page_size, b, hq, hkv,
                                                dh)
    else:
        op = "paged_attention_quant"
        key = tuning.params.paged_attention_quant_key(
            pages_per_seq, page_size, b, hq, hkv, dh, fmt)
    cfg = tuning.consult(
        op, key, {"pages_per_compute_block": default}, validate=check)
    return cfg["pages_per_compute_block"]


def paged_attention_decode(q, k_pages, v_pages, lengths, page_indices,
                           *, k_scale=None, v_scale=None,
                           fmt: str | None = None, impl: str = "auto",
                           pages_per_compute_block: int | None = None):
    """One decode step's attention for a batch of slots.  ``impl``:
    ``auto`` picks the Pallas TPU kernel on a TPU backend and the dense
    gather fallback elsewhere (the ``pallas_common`` backend split);
    ``pallas``/``gather`` force a path.  ``q`` must be pre-scaled by
    ``head_dim**-0.5`` — neither impl applies a softmax scale.

    With ``k_scale``/``v_scale`` (+``fmt``) the pools are QUANTIZED:
    ``pallas`` routes to the dequantizing kernel
    (ops/paged_attention_quant — pages gathered in their quantized
    dtype, dequantized in the VMEM prologue against the prefetched
    per-page scales) and ``gather`` dequantizes the gathered pages in
    XLA — the CPU-mesh fallback per the pallas_common backend split.

    ``pages_per_compute_block`` sizes the kernel's per-grid-lane page
    block (tuning-consulted when None — see
    ``resolve_pages_per_compute_block``; the quantized kernel is its
    own DB site).  The dense gather fallback computes the
    mathematically identical full attention regardless of blocking, so
    results are block-invariant by construction on both impls
    (tests/test_serving.py parity)."""
    quant = k_scale is not None
    if quant and fmt is None:
        raise ValueError("paged_attention_decode: scale arrays need "
                         "fmt ('int8'|'float8')")
    if impl == "auto":
        impl = "gather" if pallas_common.interpret_mode() else "pallas"
    if impl == "gather":
        if pages_per_compute_block is not None:
            # validate even on the path that ignores it: a bad explicit
            # knob must fail identically on every backend, not only
            # where the Pallas kernel happens to run
            resolve_pages_per_compute_block(
                q, k_pages, page_indices, pages_per_compute_block,
                fmt=fmt if quant else None)
        return _gather_attention(q, k_pages, v_pages, lengths,
                                 page_indices, k_scale, v_scale)
    if impl != "pallas":
        raise ValueError(f"paged_attention_decode: unknown impl "
                         f"{impl!r} (auto|pallas|gather)")
    if quant:
        from dlnetbench_tpu.ops.paged_attention_quant import \
            quant_paged_attention
        return quant_paged_attention(
            q, k_pages, v_pages, k_scale, v_scale, lengths,
            page_indices, fmt=fmt,
            pages_per_compute_block=resolve_pages_per_compute_block(
                q, k_pages, page_indices, pages_per_compute_block,
                fmt=fmt))
    from jax.experimental.pallas.ops.tpu.paged_attention import \
        paged_attention
    return paged_attention(
        q, k_pages, v_pages, lengths.astype(jnp.int32),
        page_indices.astype(jnp.int32),
        pages_per_compute_block=resolve_pages_per_compute_block(
            q, k_pages, page_indices, pages_per_compute_block))


def sharded_paged_attention(mesh, axis: str = "kv",
                            impl: str = "auto",
                            pages_per_compute_block: int | None = None,
                            quantized: bool = False,
                            fmt: str | None = None):
    """Shard the decode attention along GQA KV heads via ``shard_map``
    (the SNIPPETS.md [3] recipe): KV pages partition by head
    (``P(axis, None, None, None)``), query heads follow their group
    (``P(None, axis, None)``), lengths/block tables replicate.  Each
    shard attends over its own heads only — embarrassingly parallel, no
    collective until the caller's output projection (jit inserts the
    resharding there).  Requires ``num_kv_heads % axis_size == 0``.
    With ``quantized`` the callable takes the scale arrays after the
    pools (sharded along the same head axis — a head's pages and its
    scales live together)."""
    from jax.sharding import PartitionSpec as P

    if not quantized:
        def fn(q, k_pages, v_pages, lengths, page_indices):
            return paged_attention_decode(
                q, k_pages, v_pages, lengths, page_indices, impl=impl,
                pages_per_compute_block=pages_per_compute_block)

        return shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, axis, None), P(axis, None, None, None),
                      P(axis, None, None, None), P(), P()),
            out_specs=P(None, axis, None),
            check_vma=False)

    def qfn(q, k_pages, v_pages, k_scale, v_scale, lengths,
            page_indices):
        return paged_attention_decode(
            q, k_pages, v_pages, lengths, page_indices,
            k_scale=k_scale, v_scale=v_scale, fmt=fmt, impl=impl,
            pages_per_compute_block=pages_per_compute_block)

    return shard_map(
        qfn, mesh=mesh,
        in_specs=(P(None, axis, None), P(axis, None, None, None),
                  P(axis, None, None, None), P(axis, None),
                  P(axis, None), P(), P()),
        out_specs=P(None, axis, None),
        check_vma=False)
