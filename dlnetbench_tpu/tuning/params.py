"""TunedParams: the consult layer every tunable site goes through.

Contract (ISSUE 9):

* **Disabled by default.**  With ``DLNB_TUNING_DB_DIR`` unset, every
  ``consult`` returns its caller's default untouched and logs nothing —
  untuned behavior is bit-identical to the pre-tuning harness, which is
  what lets the tier-1 suite lock today's defaults as the contract.
* **Frozen after first consult.**  jax's jit cache is not keyed on this
  DB: a DB edit between traces of an already-compiled function would
  silently time a stale block config.  So the FIRST consult of a
  ``(op, key, hw)`` is cached for the process lifetime; later consults
  — including retraces — see the same answer even if the file changed.
  Sweeping tuned values means a fresh process per DB state.
* **Explicit values always win.**  Sites only consult when the caller
  passed no explicit value (``block_q=None``, ``tp_overlap_chunks=None``,
  ...); an explicit argument bypasses the DB entirely, for
  reproducibility.
* **Every consult is logged** (hit or miss) into a process-global map
  that ``metrics/emit`` stamps into ``global.tuning`` — a record always
  says which configs it ran under, which came from the DB, and with
  what measured band they were elected (``provenance``).

The canonical key builders live here too, so a tuning CLI commit and a
model-path consult can never disagree on key spelling.
"""
from __future__ import annotations

import os
import threading

from dlnetbench_tpu.tuning.db import TuningDB

ENV_DB_DIR = "DLNB_TUNING_DB_DIR"

_lock = threading.Lock()
# (op, key, hw) -> frozen consult entry (process lifetime)
_CACHE: dict[tuple[str, str, str], dict] = {}
# "op|key" -> provenance entry (what emit stamps)
_LOG: dict[str, dict] = {}


def db_dir() -> str | None:
    """The opted-in DB directory, or None (tuning disabled)."""
    return os.environ.get(ENV_DB_DIR) or None


def enabled() -> bool:
    return db_dir() is not None


def canonical_key(**parts) -> str:
    """Sorted ``k=v`` comma-join: one spelling per shape key, whoever
    builds it (consult site or tune CLI)."""
    return ",".join(f"{k}={parts[k]}" for k in sorted(parts))


def hw_key() -> str:
    """This process's chip key: the roofline preset key for TPU kinds
    (shared with bench/attribution via ``hw_key_for_device_kind``), the
    jax backend name otherwise (``cpu`` on the virtual mesh — CPU-tuned
    records must never be consulted on a chip, and vice versa)."""
    import jax

    from dlnetbench_tpu.core.hardware import hw_key_for_device_kind
    return (hw_key_for_device_kind(jax.devices()[0].device_kind)
            or jax.default_backend())


def consult(op: str, key: str, default: dict, validate=None) -> dict:
    """The tuned config for ``(op, key)`` on this chip, or ``default``.

    ``default`` is returned untouched (copied) when tuning is disabled
    or the DB has no entry; on a hit the DB's config is overlaid on the
    default (unknown DB keys ride along, missing ones keep their
    default).  ``validate(config)`` — if given — runs on HIT configs
    and must raise ``ValueError`` on an inapplicable one (wrong divisor
    for this shape, ...): a tuned experiment knob fails loud."""
    if not enabled():
        return dict(default)
    hw = hw_key()
    ck = (op, key, hw)
    with _lock:
        ent = _CACHE.get(ck)
        if ent is None:
            db = TuningDB(db_dir())
            rec = db.get(op, key, hw)
            if rec is not None:
                ent = {"config": {**default, **rec.get("config", {})},
                       "hit": True, "db_path": str(db.path)}
                if rec.get("band") is not None:
                    ent["tuned_band"] = rec["band"]
            else:
                ent = {"config": dict(default), "hit": False,
                       "db_path": str(db.path)}
            _CACHE[ck] = ent
            _LOG[f"{op}|{key}"] = ent
    cfg = dict(ent["config"])
    if validate is not None and ent["hit"]:
        try:
            validate(cfg)
        except ValueError as e:
            raise ValueError(
                f"tuning db entry for ({op!r}, {key!r}, {hw_key()!r}) is "
                f"inapplicable: {e} — re-tune or remove the record "
                f"({ent['db_path']})") from e
    return cfg


def provenance() -> dict | None:
    """The ``global.tuning`` block: ``{db_dir, hits, misses, sites}``
    over every consult this process made, or None when none happened
    (records from untuned/disabled runs carry no block — v2-compatible
    by construction)."""
    with _lock:
        if not _LOG:
            return None
        hits = sum(1 for e in _LOG.values() if e["hit"])
        sites = {k: {kk: e[kk] for kk in
                     ("config", "hit", "tuned_band", "db_path") if kk in e}
                 for k, e in sorted(_LOG.items())}
    return {"db_dir": db_dir(), "hits": hits,
            "misses": len(sites) - hits, "sites": sites}


def reset(clear_env: bool = False) -> None:
    """Drop the frozen consult cache + log (tests and the tune CLI,
    which must re-consult what it just committed)."""
    with _lock:
        _CACHE.clear()
        _LOG.clear()
    if clear_env:
        os.environ.pop(ENV_DB_DIR, None)


# ------------------------------------------------------- key builders
# One spelling per op: the consult sites AND the tune CLI build their
# keys through these, so a committed record can never miss on a
# formatting mismatch.

def quantized_matmul_key(t: int, k: int, n: int, fmt: str,
                         xdtype) -> str:
    return canonical_key(t=t, k=k, n=n, fmt=fmt, xdtype=str(xdtype))


def flash_fwd_key(b: int, s: int, hq: int, hkv: int, dh: int,
                  causal: bool, dtype) -> str:
    return canonical_key(b=b, s=s, hq=hq, hkv=hkv, dh=dh,
                         causal=bool(causal), dtype=str(dtype))


def flash_bwd_key(b: int, s: int, hq: int, hkv: int, dh: int,
                  causal: bool, dtype) -> str:
    # same fields as fwd (the kernels share shapes) but a distinct op
    # name keys the record — fwd and bwd optima need not coincide
    return flash_fwd_key(b, s, hq, hkv, dh, causal, dtype)


def splash_key(b: int, s: int, hq: int, hkv: int, dh: int,
               mask_label: str, dtype) -> str:
    """Block-sparse (splash) attention blocks — ops "splash_fwd" and
    "splash_bwd" share the key shape.  The MASK rides in the key (the
    ``MaskSpec.label()`` spelling): sparsity changes which blocks even
    run, so a window(1024) optimum must never answer a segment-mask
    consult, and neither may a dense flash record."""
    return canonical_key(b=b, s=s, hq=hq, hkv=hkv, dh=dh,
                         mask=mask_label, dtype=str(dtype))


def paged_attention_key(pages_per_seq: int, page_size: int, b: int,
                        hq: int, hkv: int, dh: int) -> str:
    return canonical_key(pages_per_seq=pages_per_seq,
                         page_size=page_size, b=b, hq=hq, hkv=hkv, dh=dh)


def paged_attention_quant_key(pages_per_seq: int, page_size: int,
                              b: int, hq: int, hkv: int, dh: int,
                              fmt: str) -> str:
    """The QUANTIZED paged-attention decode kernel
    (ops/paged_attention_quant, op name "paged_attention_quant") —
    same geometry fields as the dense kernel plus the quant format:
    in-prologue dequant changes the kernel's arithmetic intensity, so
    a dense optimum must never answer a quantized consult and int8/fp8
    optima are distinct records (ISSUE 12)."""
    return canonical_key(pages_per_seq=pages_per_seq,
                         page_size=page_size, b=b, hq=hq, hkv=hkv,
                         dh=dh, fmt=fmt)


def grouped_ffn_key(e: int, c: int, d: int, h: int, fmt: str,
                    xdtype) -> str:
    """The grouped expert-FFN kernel's grid blocks
    (ops/grouped_matmul.py, op name "grouped_ffn") — keyed by the
    dispatch-buffer geometry (experts x capacity x embed x ff) plus
    the quant format ("none" for master-dtype): in-prologue quant
    changes the kernel's arithmetic intensity, so bf16 optima must
    never answer int8/fp8 consults (ISSUE 15)."""
    return canonical_key(e=e, c=c, d=d, h=h, fmt=fmt,
                         xdtype=str(xdtype))


def tp_overlap_chunks_key(embed: int, ff: int, seq: int, tp: int,
                          dtype: str) -> str:
    return canonical_key(embed=embed, ff=ff, seq=seq, tp=tp,
                         dtype=str(dtype))


def grad_bucket_layers_key(num_layers: int, dp: int, pp: int,
                           embed: int, ff: int) -> str:
    return canonical_key(num_layers=num_layers, dp=dp, pp=pp,
                         embed=embed, ff=ff)
