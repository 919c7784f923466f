"""The single import point for the jax names whose home has moved
between releases.

Every module imports ``shard_map`` and ``axis_size`` from HERE instead
of from jax, so the next move is a one-file change; a guard test
(tests/test_guard_imports.py) rejects direct ``shard_map`` imports
elsewhere.  The installation is jax 0.9: ``jax.shard_map`` takes its
replication check as ``check_vma=``.
"""
from __future__ import annotations

from jax import shard_map
from jax.lax import axis_size

__all__ = ["axis_size", "shard_map"]
