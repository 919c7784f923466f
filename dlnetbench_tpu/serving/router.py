"""Fleet front-end router: which replica gets the next request.

The router is the ONLY fleet-level scheduling decision (ISSUE 18) —
per-replica admission control is untouched, the router just picks a
queue.  Every policy is seeded and replayable: the same plan + seed +
policy produces the same request->replica assignment on any machine
(the assignment log is part of the record's provenance, and the replay
test locks it).

Policies (``ROUTING_POLICIES``):

  round_robin     — the baseline: cycle over the active replicas in
                    index order.  No RNG draws, no load signal.
  p2c             — power-of-two-choices: draw TWO distinct active
                    replicas from the router's splitmix64 stream
                    (utils/seeded.Rng — the same generator every
                    committed plan uses), route to the one with the
                    lower live load score, first draw wins ties.  The
                    classic balanced-allocations result: max load drops
                    from O(log n / log log n) to O(log log n) vs random
                    placement, at two probes per request.
  prefix_affinity — consult each active replica's radix trie
                    (``PagedKVCache.prefix_match_len`` — a read-only
                    probe that never touches the pool's hit-rate
                    counters) and route to the replica holding the
                    longest shared-prefix page run; ties (including
                    the no-match case) fall back to p2c.  A FULL
                    replica — every slot spoken for by resident or
                    already-queued work — bounces to p2c even on a
                    match, so affinity can never starve a request
                    behind one hot replica while others sit idle.

Load score: ``len(queue) + len(pending) + occupied slots`` — everything
the replica has accepted but not finished, the signal a front-end can
actually observe without touching the engine's measured loop.

Replayability note: p2c consumes exactly two draws per routed request
(none when only one replica is active), round_robin consumes zero, and
prefix_affinity consumes two only on its fallback path.  Routing is
timing-sensitive by design — live load scores ARE the policy — so the
locked determinism tests use plans whose arrivals all land at t=0: the
whole batch routes before any engine step, and the router-visible state
evolves identically run over run.
"""
from __future__ import annotations

from dlnetbench_tpu.utils.seeded import Rng

ROUTING_POLICIES = ("round_robin", "p2c", "prefix_affinity")


class Router:
    """Seeded request->replica router over ``num_replicas`` queues.

    The fleet driver calls ``pick`` once per routed request with the
    CURRENT engine list and active index set; the router returns a
    replica index and keeps its own provenance: the full assignment
    log, per-replica counts, the chosen-replica load-score samples
    (the fleet block's load histogram), and the affinity accounting
    (hits, bounces, migration-free prefix tokens reused)."""

    def __init__(self, policy: str, num_replicas: int, *, seed: int = 0):
        if policy not in ROUTING_POLICIES:
            raise ValueError(f"router: unknown policy {policy!r} "
                             f"(one of {ROUTING_POLICIES})")
        if num_replicas < 1:
            raise ValueError(f"router: num_replicas must be >= 1, got "
                             f"{num_replicas}")
        self.policy = policy
        self.num_replicas = num_replicas
        self.seed = seed
        self.reset()

    def reset(self) -> None:
        """Back to the initial state — fresh RNG stream, empty log.
        The fleet warmup drives synthetic requests through the SAME
        router; the measured run must start from the seeded origin or
        the warmup count would shift every measured draw."""
        self._rng = Rng(self.seed)
        self._rr_next = 0
        self.assignments: list[tuple[int, int]] = []   # (rid, replica)
        self.counts = [0] * self.num_replicas
        self.load_samples: list[int] = []  # chosen replica's score
        self.affinity_hits = 0
        self.affinity_bounces = 0
        self.prefix_reuse_tokens = 0

    # ---- the load signal ---------------------------------------------
    @staticmethod
    def load_score(engine) -> int:
        """Accepted-but-unfinished work: routed-not-yet-admitted queue,
        pending (due, waiting for a slot), and occupied slots."""
        return (len(engine.queue) + len(engine.pending)
                + sum(1 for s in engine.slots if s is not None))

    @staticmethod
    def _is_full(engine) -> bool:
        """Every slot spoken for by resident or queued work — the
        affinity bounce condition (routing here queues the request
        behind a hot replica; p2c spreads it instead)."""
        return Router.load_score(engine) >= engine.cfg.slots

    # ---- policies ----------------------------------------------------
    def _round_robin(self, active: list[int]) -> int:
        active_set = set(active)
        for _ in range(self.num_replicas):
            r = self._rr_next % self.num_replicas
            self._rr_next += 1
            if r in active_set:
                return r
        raise RuntimeError("router: no active replica")  # caller's bug

    def _p2c(self, active: list[int], engines) -> int:
        if len(active) == 1:
            return active[0]
        n = len(active)
        i = self._rng.uniform_int(0, n - 1)
        j = self._rng.uniform_int(0, n - 2)
        if j >= i:
            j += 1  # second draw over the OTHER n-1 replicas
        a, b = active[i], active[j]
        # strict <: the first draw wins ties, so the stream alone
        # determines the pick when scores agree
        return b if self.load_score(engines[b]) \
            < self.load_score(engines[a]) else a

    def _prefix_affinity(self, active: list[int], engines,
                         prompt_tokens) -> int:
        best, best_len = None, 0
        for r in active:
            m = engines[r].cache.prefix_match_len(prompt_tokens)
            if m > best_len:
                best, best_len = r, m
        if best is None:
            # no replica holds any of this prompt — a tie, not a
            # bounce: fall through to p2c placement
            return self._p2c(active, engines)
        if self._is_full(engines[best]):
            self.affinity_bounces += 1
            return self._p2c(active, engines)
        self.affinity_hits += 1
        self.prefix_reuse_tokens += best_len
        return best

    # ---- the decision ------------------------------------------------
    def pick(self, req, engines, active: list[int], *,
             prompt_tokens=None) -> int:
        """Route one request; returns the chosen replica's GLOBAL
        index.  ``active`` lists the currently-live replica indices in
        ascending order; ``engines[r]`` must be live for every r in
        ``active``.  ``prompt_tokens`` feeds the affinity probe (only
        consulted under prefix_affinity)."""
        if not active:
            raise RuntimeError("router: no active replica to route to")
        if self.policy == "round_robin":
            r = self._round_robin(active)
        elif self.policy == "p2c":
            r = self._p2c(active, engines)
        else:
            r = self._prefix_affinity(active, engines, prompt_tokens)
        self.assignments.append((req.rid, r))
        self.counts[r] += 1
        self.load_samples.append(self.load_score(engines[r]))
        return r

    # ---- record assembly ---------------------------------------------
    def load_histogram(self) -> list[int]:
        """Counts of the chosen replica's load score at each routing
        decision, indexed by score — the fleet block's picture of how
        loaded the picked queues were (a good policy keeps the mass at
        low scores)."""
        if not self.load_samples:
            return []
        hist = [0] * (max(self.load_samples) + 1)
        for s in self.load_samples:
            hist[s] += 1
        return hist

    def affinity_hit_rate(self) -> float:
        routed = len(self.assignments)
        return round(self.affinity_hits / routed, 4) if routed else 0.0
