"""Environment-variable parsing shared by the bench shape knobs
(``models/bench_step.py``) and ``bench.py``."""
from __future__ import annotations

import os


def env_float(name: str, default: float) -> float:
    """Env override parsed defensively: a malformed value falls back to
    the default instead of killing the run."""
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def env_int(name: str, default: int) -> int:
    """Integer twin of ``env_float``, same defensive contract (the
    DLNB_BENCH_* shape knobs and DLNB_BENCH_K share this one parser)."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default
