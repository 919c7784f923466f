"""Shared shims for every Pallas TPU kernel in ops/.

Before this module existed, ``_interpret()``, ``_compiler_params`` and
the fp32 constant were copy-pasted per kernel file; a fix to any of them (e.g. the interpret-mode
gate growing a force-override for debugging) had to be applied N times.
Everything here is the single definition the kernel files import.

The reference has no kernels at all (its compute tier is roofline
``usleep``); this module exists because the rebuild's real-compute tier
keeps growing Pallas kernels and they must all make the same
backend/VMEM decisions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# fp32: the accumulation / epilogue dtype of every kernel (MXU
# accumulators, online-softmax state, quantization scales)
F32 = jnp.float32

# Default Mosaic VMEM cap for the matmul-family kernels: raised above
# the 16 MiB default so 1-2k-wide blocks keep double-buffering headroom
# on v5e/v5p (128 MiB physical VMEM).  flash_attention uses a tighter
# 64 MiB cap (its kernels hold more live blocks per lane).
DEFAULT_VMEM_LIMIT_MB = 100


def interpret_mode() -> bool:
    """True when Pallas kernels must run under ``interpret=True`` — any
    non-TPU backend, which is how the CPU-mesh tier-1 lane unit-tests
    every kernel without hardware."""
    return jax.default_backend() != "tpu"


def compiler_params(dimension_semantics,
                    vmem_limit_mb: int = DEFAULT_VMEM_LIMIT_MB):
    """Mosaic params shared by the kernels: per-kernel dimension
    semantics (``"parallel"`` outer axes let Mosaic pipeline DMA across
    grid rows; accumulator-carrying minor axes must be
    ``"arbitrary"``), VMEM cap in MiB."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=vmem_limit_mb * 1024 * 1024)


# At and beyond this size a degenerate block choice stops being a perf
# wrinkle and becomes a pathology: a 64k+ dim tiled below one lane width
# means a >= 512-program grid of sub-MXU blocks (or, for the attention
# dispatcher, a silent fall-through to an S^2 dense path).  Mirrors
# flash_attention.LONG_SEQ — ISSUE 10 satellite.
LONG_DIM = 64 * 1024

# TPU lane width: the smallest block that still fills an MXU/VPU lane
# tile (flash_attention._LANES is this same constant)
LANES = 128


def fit_block(dim: int, block: int) -> int:
    """Largest power-of-two-halving of ``block`` that divides ``dim`` —
    the block-shrinking idiom every matmul-family wrapper used inline
    (``while dim % block: block //= 2``).  Raises if even block=1 does
    not divide (dim <= 0), and refuses a long dim (>= 64k) whose only
    fitting blocks are sub-lane-width: at that size the degenerate grid
    is always a config bug, not a fallback (ISSUE 10 satellite — name
    the dim instead of silently degrading)."""
    if dim <= 0:
        raise ValueError(f"fit_block: non-positive dim {dim}")
    while dim % block:
        block //= 2
    if dim >= LONG_DIM and block < LANES:
        raise ValueError(
            f"fit_block: dim {dim} >= {LONG_DIM} admits no block "
            f">= the {LANES}-wide lane tile (best fit {block}) — a "
            f"sub-lane grid at this size is a config bug; pad the dim "
            f"to a multiple of {LANES}")
    return block
