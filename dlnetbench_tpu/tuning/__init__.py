"""Persistent seeded autotuner for the harness's tunable knobs
(ISSUE 9 / ROADMAP item 4).

Three layers:

* ``db``     — ``TuningDB``: the per-(op, canonical shape key, chip)
  JSON-lines store, schema-versioned, atomic-rename writes, bounded
  claim/retry for concurrent writers.  Lives wherever
  ``DLNB_TUNING_DB_DIR`` points — beside the PR-1 compile cache by
  convention, so warm state travels as one directory.
* ``search`` — the splitmix64-seeded measure/prune/commit driver:
  K-chained fence timing, band-aware pruning (``stats.bands_overlap``),
  winner committed WITH its measured band.
* ``params`` — ``consult``: what the tunable sites call.  Disabled-by-
  default (env unset -> caller defaults, bit-identical untuned
  behavior), frozen after first consult per key (jit's cache is not
  keyed on the DB, so a later edit would time a stale config),
  explicit values always win, every consult logged for record
  provenance
  (``metrics/emit`` stamps ``global.tuning``).

Tunable sites wired (each falls back to today's exact default on a
miss): flash-attention fwd/bwd block shapes (``ops/flash_attention``),
quantized/fused-swiglu grid blocks (``ops/quantized_matmul``),
``SpmdConfig.tp_overlap_chunks`` / ``grad_bucket_layers``
(``models/spmd``), and paged-attention ``pages_per_compute_block``
(``serving/kv_cache``).

CLI: ``python -m dlnetbench_tpu.tuning tune --op ... --db DIR`` runs
the seeded search on this backend and commits; ``show`` lists the DB.
``make check-tuning`` proves search -> commit -> consult -> hit end to
end on a tiny CPU shape in seconds.
"""
from dlnetbench_tpu.tuning.db import (DB_FILENAME, SCHEMA_VERSION,
                                      TuningDB)
from dlnetbench_tpu.tuning.params import (ENV_DB_DIR, canonical_key,
                                          consult, db_dir, enabled,
                                          hw_key, provenance, reset)
from dlnetbench_tpu.tuning.search import (run_search, seeded_order,
                                          tune_and_commit)

__all__ = [
    "DB_FILENAME", "SCHEMA_VERSION", "TuningDB",
    "ENV_DB_DIR", "canonical_key", "consult", "db_dir", "enabled",
    "hw_key", "provenance", "reset",
    "run_search", "seeded_order", "tune_and_commit",
]
