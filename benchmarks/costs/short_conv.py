"""Operations and bytes of the gated short convolution (the two
products and the depthwise causal convolution between a conv layer's
projections) of every conv layer of one training step in which each
layer is recomputed in the backward: the forward, one recomputation of
it, and the backward, from shapes alone.

Counted for one (token, lane) of the model's width.  The forward is
``b * u`` (1), a product with each tap and their sum (2 K - 1) and ``c *
h`` (1): 7 at the published three taps.  The backward is given the three
streams and the cotangent and nothing else, so it makes ``z`` and ``h``
again (2 K), then ``dy * c`` and ``dy * h`` (2), the convolution's
transpose (2 K - 1), ``dz * u`` and ``dz * b`` (2) and the taps'
gradient, a product and a sum a tap (2 K): 21.  Bytes are each operand
read or written once in the stored dtype: a forward pass reads the three
streams and writes one; the backward reads them and the cotangent and
writes the three streams' cotangent (the taps and their gradient are K
rows).

The chain is elementwise: it runs on the TPU's vector unit, for which
``peaks.json`` has no row, and the bytes bind by two orders (0.5 GB a
layer at D=2048, T=8192 against 0.6 GFLOP).  A share of the roofline
computed from these counts is therefore a floor's share, as
``costs/selective_scan.py``'s is.
"""
from __future__ import annotations


def cost(*, batch: int, seq: int, embed_dim: int, short_conv: int,
         layer_kinds, dtype_bytes: int = 2, **_) -> dict:
    layers = sum(1 for k in layer_kinds if k == "conv")
    lanes = batch * seq * embed_dim
    k = short_conv
    forward = lanes * (2 * k + 1)
    backward = lanes * (6 * k + 3)
    taps = k * embed_dim * dtype_bytes
    fwd_bytes = 4 * lanes * dtype_bytes + taps
    bwd_bytes = 7 * lanes * dtype_bytes + 2 * taps
    return {"flops": layers * (2 * forward + backward),
            "bytes": layers * (2 * fwd_bytes + bwd_bytes)}
