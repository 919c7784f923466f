"""Operations and bytes of the selective scan of every mamba layer of
one training step in which each layer is recomputed in the backward:
the forward, one recomputation of it, and the backward, from shapes
alone.

Counted as the recurrence needs them, for one (step, channel, state)
element: the forward is ``delta * A``, ``exp``, two products and a sum
for the state, a product and a sum for the output (7), and for one
(step, channel) the input's ``delta * u`` and the skip (3).  The backward
walks the states last to first: the step's decay again (2), the state
from the one before it (3), the output's part of the state's gradient
(2), and the five gradients that are sums of products with it (B, C, the
input, delta, A: 12) and the gradient handed to the step before (1),
with (6) for a (step, channel).  That the backward first recomputes a
chunk's states from the kept boundary is how an implementation bounds
its memory, not what the arithmetic needs: a kernel is charged for it.
Bytes are each operand read or written once in its stored dtype: the
forward reads u, delta (float32), B, C and writes s; the backward reads
those and ds and writes du, ddelta, dB, dC, dA.

The scan is elementwise: it runs on the TPU's vector unit, for which
``peaks.json`` has no row.  A share of the roofline computed from these
counts against the bf16 matmul peak and the HBM rate is therefore a
floor's share: the bytes bound it (some 1.3 GB a layer at E=5120,
T=8192 against 0.02 TFLOP), and the vector unit's own rate, far under
the MXU's, is what a kernel is really up against.
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, ssm_inner: int, ssm_state: int,
         layer_kinds, dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == "mamba")
    te = batch * seq * ssm_inner
    tn = batch * seq * ssm_state
    n = ssm_state
    forward = te * (7 * n + 3)
    backward = te * (20 * n + 6)
    fwd_bytes = te * (2 * dtype_bytes + 4) + 2 * tn * dtype_bytes
    bwd_bytes = (te * (3 * dtype_bytes + 4) + 2 * tn * dtype_bytes
                 + te * (dtype_bytes + 4) + 2 * tn * dtype_bytes
                 + ssm_inner * n * 4)
    return {"flops": layers * (2 * forward + backward),
            "bytes": layers * (2 * fwd_bytes + bwd_bytes)}
