"""Seeded empirical search over candidate configs.

ATLAS-style: measure, compare, commit — except every comparison here is
band-aware (``metrics/stats``) because a single 3-sample chain on this
harness's backends is one draw from a noisy distribution, not a result.

Discipline:

* **Seeded order.**  Candidates are visited in a splitmix64-shuffled
  order (``utils/seeded.splitmix64`` — the SAME generator the fault
  and arrival plans use, golden-value-matched to the native tier), so a
  search is replayable from ``(candidates, seed)`` alone and two
  processes given the same seed measure in the same order.
* **K-chained fence timing.**  ``measure(config)`` is supplied by the
  caller and must return ONE per-iteration seconds sample per call —
  the convention of ``utils/timing.time_chain`` (K dispatches under one
  fence), which every bench line already uses.  The driver owns warmup/
  compile; a sample must never include them.
* **Band-aware pruning.**  After TWO rounds, a candidate whose whole
  observed band so far lands strictly above the incumbent winner's
  measured band (``bands_overlap`` is False and it is slower) has its
  remaining rounds skipped.  Two samples, not one: the harness's own
  noise model (``metrics/stats.py``) documents bimodal draws where a
  single sample can land far above a candidate's floor — wall-clock
  noise only ever inflates, so the min of two draws is the sound
  pruning statistic; anything band-ambiguous gets its full rounds.
  Noise must cost measurement time, never a wrong winner.
* **The winner ships with its band.**  ``commit`` writes the winning
  config AND its measured ``{value, best, band, n}`` into the DB — the
  evidence rides the record, downstream consults can show it.
"""
from __future__ import annotations

from dlnetbench_tpu.metrics import stats as stats_mod
from dlnetbench_tpu.utils.seeded import Rng
from dlnetbench_tpu.tuning.db import TuningDB


def seeded_order(n: int, seed: int) -> list[int]:
    """Fisher–Yates over ``range(n)`` driven by the shared splitmix64
    stream — deterministic per seed, identical across tiers."""
    rng = Rng(seed)
    order = list(range(n))
    for i in range(n - 1, 0, -1):
        j = rng.uniform_int(0, i)
        order[i], order[j] = order[j], order[i]
    return order


def run_search(candidates: list[dict], measure, *, seed: int = 0,
               rounds: int = 3, prune: bool = True, log=None) -> dict:
    """Measure every candidate (in seeded order), return
    ``{"config", "band", "trials", "pruned", "seed", "rounds"}``.

    ``measure(config) -> float`` — one per-iteration seconds sample per
    call (one K-chain).  Raises ``ValueError`` on an empty candidate
    list; a ``measure`` that raises aborts the search (the caller owns
    degrading that to a skip — a half-searched DB commit would be a
    lie)."""
    if not candidates:
        raise ValueError("run_search: no candidates")
    if rounds < 1:
        raise ValueError("run_search: rounds must be >= 1")
    best: tuple[dict, dict] | None = None   # (summary, config)
    trials: list[dict] = []
    pruned = 0
    for idx in seeded_order(len(candidates), seed):
        cfg = dict(candidates[idx])
        probe = [float(measure(cfg))
                 for _ in range(min(2, rounds))]
        # prune only on TWO disjoint-worse samples: a single draw can
        # hit a slow mode (stats.py's bimodality note) while
        # the candidate's floor beats the incumbent — noise inflates
        # only, so min(two draws) > the incumbent's whole band is the
        # sound "cannot win" signal; rounds < 3 leaves nothing to skip
        if prune and best is not None and rounds >= 3 and \
                min(probe) > best[0]["value"] and \
                stats_mod.bands_overlap([min(probe), min(probe)],
                                        best[0]["band"]) is False:
            trials.append({"config": cfg,
                           "summary": stats_mod.summarize(probe),
                           "pruned": True})
            pruned += 1
            if log:
                log(f"  pruned {cfg} after {len(probe)} rounds "
                    f"(best {min(probe) * 1e3:.3f} ms > band "
                    f"{best[0]['band']})")
            continue
        samples = probe + [float(measure(cfg))
                           for _ in range(rounds - len(probe))]
        summary = stats_mod.summarize(samples)
        trials.append({"config": cfg, "summary": summary,
                       "pruned": False})
        if best is None or summary["value"] < best[0]["value"]:
            best = (summary, cfg)
        if log:
            log(f"  measured {cfg}: {summary['value'] * 1e3:.3f} ms "
                f"band {[round(v * 1e3, 3) for v in summary['band']]}")
    assert best is not None
    return {"config": best[1], "band": best[0], "trials": trials,
            "pruned": pruned, "seed": seed, "rounds": rounds}


def tune_and_commit(db: TuningDB, op: str, key: str, hw: str,
                    candidates: list[dict], measure, *, seed: int = 0,
                    rounds: int = 3, k: int | None = None,
                    log=None) -> dict:
    """``run_search`` then commit the winner (with its measured band and
    the search's provenance meta) under ``(op, key, hw)``.  Returns the
    search result with the committed record under ``"record"``."""
    res = run_search(candidates, measure, seed=seed, rounds=rounds,
                     log=log)
    meta = {"seed": seed, "rounds": rounds,
            "candidates": len(candidates), "pruned": res["pruned"]}
    if k is not None:
        meta["reps_per_fence"] = k
    res["record"] = db.put(op, key, hw, res["config"], band=res["band"],
                           meta=meta)
    return res
