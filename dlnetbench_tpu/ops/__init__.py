"""Hot-op kernels (Pallas where it pays).

The reference has no kernel layer at all — its "compute" is ``usleep``
(reference cpp/data_parallel/dp.cpp:93).  The rebuild's real-compute tier
does real math, so the FLOP-dominant op — attention — gets a TPU-native
blockwise (flash) kernel here: online-softmax tiles sized to VMEM, MXU
matmuls with fp32 accumulation, and a custom VJP so long sequences never
materialize the S x S score matrix in HBM.

``attention`` is the dispatcher the model families call: it routes to the
Pallas kernel when the backend and shapes support it and otherwise falls
back to the plain-XLA einsum implementation (ops/xla_attention.py), which
is also the numerical reference in tests.
"""
from __future__ import annotations

import jax

from dlnetbench_tpu.ops import attention_mask as _M
from dlnetbench_tpu.ops.flash_attention import (
    LONG_SEQ,
    flash_attention,
    flash_supported,
    splash_attention,
    splash_supported,
)
from dlnetbench_tpu.ops.xla_attention import xla_attention

__all__ = ["attention", "flash_attention", "flash_supported",
           "splash_attention", "xla_attention"]

# Measured on a v5e chip (llama3_8b-shaped 4-layer train step, remat on):
# flash loses ~2% at S=1024 (attention is a sliver of the step and the
# recomputed fwd kernel costs more than XLA's fused softmax) and wins 18%
# at S=2048 / 29% at S=4096.  "auto" only picks flash where it pays.
_AUTO_MIN_SEQ = 2048


def _dense_mask_np(spec: _M.MaskSpec, s: int):
    """Host-side dense mask for the reference path.  Deliberately NOT
    cached: jit tracing already folds it into the compiled computation
    once per shape, and pinning [S, S] bool arrays for the process
    lifetime would only duplicate XLA's copy (the underlying row
    intervals ARE cached — rebuilding is one O(S^2) broadcast)."""
    return _M.dense_mask(spec, s)


def attention(q, k, v, causal: bool, impl: str = "auto", mask=None,
              block_q: int | None = None, block_k: int | None = None):
    """q: [B, S, Hq, Dh], k: [B, S, Hkv, Dh], v: [B, S, Hkv, Dv] ->
    [B, S, Hq, Dv].  Dv is Dh everywhere but in latent attention, whose
    scores run over a wider head than its values; the masked
    (block-sparse) kernels keep one width.

    impl: "flash" (Pallas kernel, error if unsupported shape),
    "xla" (einsum reference), or "auto" (flash on TPU when the shape
    qualifies, xla otherwise — CPU interpret-mode flash is for tests).

    ``mask`` (a ``MaskSpec``, ops/attention_mask.py) turns on the
    block-sparse path: "flash" dispatches the splash kernels (skipped
    blocks cost no DMA/MXU work), "xla" applies the SAME mask densely
    (the CPU-mesh reference the sparse paths are parity-tested
    against).  The spec's ``causal`` must agree with the ``causal``
    argument — a silent disagreement would A/B two different maths.

    ``block_q`` / ``block_k`` go to the Pallas kernels where they run
    (a window narrower than the default blocks skips nothing unless the
    blocks are as narrow); the dense paths have no blocks.
    """
    blocks = {} if block_q is None and block_k is None else {
        "block_q": block_q, "block_k": block_k}
    s = q.shape[1]
    if mask is not None:
        if mask.causal != causal:
            raise ValueError(
                f"attention: mask spec {mask.label()!r} has "
                f"causal={mask.causal} but the call says causal={causal}")
        if mask.is_plain_causal:
            mask = None   # the dense-causal default IS this mask
    if impl == "xla":
        if mask is not None:
            return xla_attention(q, k, v, causal=causal,
                                dense_mask=_dense_mask_np(mask, s))
        return xla_attention(q, k, v, causal=causal)
    if impl == "flash":
        if mask is not None:
            return splash_attention(q, k, v, mask, **blocks)
        return flash_attention(q, k, v, causal=causal, **blocks)
    if impl != "auto":
        raise ValueError(f"unknown attention impl {impl!r}")
    supported = (flash_supported if mask is None
                 else splash_supported)(q, k, v)  # raises at S>=64k w/o blocks
    if (jax.default_backend() == "tpu" and s >= _AUTO_MIN_SEQ
            and supported):
        if mask is not None:
            return splash_attention(q, k, v, mask, **blocks)
        return flash_attention(q, k, v, causal=causal, **blocks)
    if s >= LONG_SEQ:
        # the dense fallback at 64k+ materializes the S^2 score matrix
        # — never a sane degradation (ISSUE 10 satellite: fail loud,
        # naming the length; impl="xla" stays available explicitly)
        raise ValueError(
            f"attention: impl='auto' refuses the dense fallback at "
            f"seq_len {s} >= {LONG_SEQ} (the S^2 score matrix would "
            f"materialize); use the flash/splash path on TPU or pass "
            f"impl='xla' explicitly")
    if mask is not None:
        return xla_attention(q, k, v, causal=causal,
                            dense_mask=_dense_mask_np(mask, s))
    return xla_attention(q, k, v, causal=causal)
