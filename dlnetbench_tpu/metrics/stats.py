"""Artifact-grade sample summaries: every short-chain stat self-describes.

DLNetBench's contract is "the artifact is the result" — but a single
number from a 3-sample chain is not a result, it is one draw from a
distribution that host-clock timings have shown to be bimodal (a host
or chip moving between throughput states).  This module is the ONE
definition of how such samples ship:

    {"value": median, "best": min, "band": [lo, hi], "n": N}

* ``value`` — the median, the figure downstream comparisons use;
* ``best`` — the minimum, the least-noise observation (host jitter
  only ever inflates a wall-clock sample);
* ``band`` — the full observed range.  With n this small, percentiles
  would be theater; the honest statement is "samples fell in here";
* ``n`` — how many samples back the claim.

``flag_low_mode`` mirrors ``bench._flag_above_peak``: a physically
suspicious reading must never ship unannotated.  When the best sample
sits far below the median, the samples straddle two modes (a fast and
a slow throughput state) and the median is a mixture statistic, not
a central tendency — the line is stamped with a ``note`` saying so.

Used by bench.py's auxiliary JSON lines, by ``metrics.emit``'s
schema-v2 per-timer summaries, and available to any analysis that
wants one consistent band convention.
"""
from __future__ import annotations

import statistics

# best/value ratio below which the samples are declared bimodal: the
# fastest observation is >30% under the median, which honest unimodal
# wall-clock noise (inflation-only) does not produce
LOW_MODE_RATIO = 0.7


def summarize(samples: list[float], ndigits: int | None = None) -> dict:
    """``{"value": median, "best": min, "band": [lo, hi], "n": N}`` for a
    list of same-unit samples.  Empty input summarizes to zeros with
    n=0 rather than raising — emitters must not die on a timer that
    never fired."""
    if not samples:
        return {"value": 0.0, "best": 0.0, "band": [0.0, 0.0], "n": 0}
    vals = [float(v) for v in samples]
    out = {
        "value": statistics.median(vals),
        "best": min(vals),
        "band": [min(vals), max(vals)],
        "n": len(vals),
    }
    if ndigits is not None:
        out["value"] = round(out["value"], ndigits)
        out["best"] = round(out["best"], ndigits)
        out["band"] = [round(v, ndigits) for v in out["band"]]
    return out


def overlap_fraction(full, compute, comm) -> list[float]:
    """Measured communication–compute overlap from the A/B decomposition
    (proxies/base.py: full / compute-only / comm-only variants), per
    matched sample:

        overlap_i = (Tc_i + Tm_i - T_both_i) / min(Tc_i, Tm_i)

    1.0 = the shorter leg is fully hidden behind the longer; 0.0 = fully
    serialized (T_both = Tc + Tm); negative = interference (running
    together is SLOWER than back-to-back — contention for the same
    HBM/ICI resources).  Values are not clamped: an out-of-[0, 1]
    reading is a measurement statement, and the band convention
    (``summarize``) is how it ships.  Samples whose min leg is ~0 —
    below 0.1% of the largest leg, e.g. a time_chain sample nearly
    cancelled by the RTT subtraction — yield 0.0 (nothing to hide; an
    unbounded ratio from a degenerate denominator must never dominate a
    summary mean)."""
    out = []
    for f, c, m in zip(full, compute, comm):
        denom = min(c, m)
        if denom <= 0 or denom <= 1e-3 * max(f, c, m):
            out.append(0.0)
        else:
            out.append((c + m - f) / denom)
    return out


def bands_overlap(a, b) -> bool | None:
    """Do two ``[lo, hi]`` bands overlap?  ``None`` when either side is
    missing/malformed — the caller (the regression sentinel) treats an
    unknown overlap as "bands cannot veto", falling back to its
    %-threshold alone.  Two bands that merely touch DO overlap: with
    n=3 samples the band edges are observations, and sharing one is
    exactly the "indistinguishable from noise" case the bands exist to
    name."""
    try:
        alo, ahi = float(a[0]), float(a[1])
        blo, bhi = float(b[0]), float(b[1])
    except (TypeError, ValueError, IndexError):
        return None
    return blo <= ahi and alo <= bhi


def flag_low_mode(line: dict, ratio: float = LOW_MODE_RATIO) -> dict:
    """Annotate a summary-carrying dict whose samples straddle two modes.

    Operates on the ``value``/``best`` keys (any unit) so it applies to
    a raw ``summarize`` result and to a bench JSON line alike; appends
    to an existing ``note`` (e.g. the above-peak flag) instead of
    clobbering it."""
    value = line.get("value") or 0.0
    best = line.get("best")
    n = line.get("n", 0)
    if best is None or n < 2 or value <= 0:
        return line
    if best < ratio * value:
        note = (f"bimodal samples: best {best:g} is "
                f"{100 * (1 - best / value):.0f}% below the median over "
                f"n={n} — the median mixes two modes (host "
                f"throughput states); read [band] not value")
        line["note"] = f"{line['note']}; {note}" if line.get("note") else note
    return line
