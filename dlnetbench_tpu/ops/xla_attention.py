"""The plain-XLA einsum attention: what ``ops.attention`` falls back to
where no Pallas kernel runs (``impl="xla"``, short sequences, the CPU
mesh), and the numerical reference the kernels are tested against.  It
pays the full S x S grid by design.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def xla_attention(q, k, v, causal: bool, dense_mask=None):
    """q: [B, S, Hq, Dh], k: [B, S, Hkv, Dh], v: [B, S, Hkv, Dv] (GQA
    broadcast; Dv may differ from Dh).  Softmax in fp32.

    ``dense_mask`` (an [S, S] bool, True = attend — built by
    ops/attention_mask.dense_mask) replaces the causal tril when given:
    it already encodes the causal half, so the two are never composed.
    This is the reference path the block-sparse kernels are
    parity-tested against."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    q = q.reshape(b, s, hkv, group, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=_F32)
    scores = scores / jnp.sqrt(jnp.asarray(dh, _F32))
    if dense_mask is not None:
        mask = jnp.asarray(dense_mask, bool)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    elif causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=_F32)
    return out.reshape(b, s, hq, v.shape[3]).astype(v.dtype)
