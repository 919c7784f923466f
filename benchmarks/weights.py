"""Seeded weights, made by the benchmark and handed to both sides.

One jitted call makes the whole tree on the device, in the dtype the
configuration states, in the layout the program's gated decoder reads
(``embed``, ``layers/{wq,wk,wv,wo,norm1,norm2,w_gate,w_up,w_down[,
w_router]}`` stacked on a leading layer axis, ``final_norm``, ``head``).
The layout is the program's interface; the values are the benchmark's:
normal draws scaled by 1/sqrt(fan-in) (1 for the embedding), norm
weights 1.  The reference is given the same tree and nothing else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def arch_of(config: dict, *, capacity_factor: float = 1.25) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names)."""
    heads = config["num_attention_heads"]
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": config["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim", config["hidden_size"] // heads),
        "ff_dim": config["intermediate_size"],
        "num_layers": config["num_hidden_layers"],
        "num_experts": config.get("num_local_experts", 1),
        "top_k": config.get("num_experts_per_tok", 1),
        "capacity_factor": capacity_factor,
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def seed_key(seed: int):
    """A key from any whole number, also one beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def shapes(arch: dict) -> dict:
    """{leaf path: (shape, scale)}; scale None means ones."""
    d, f, v = arch["embed_dim"], arch["ff_dim"], arch["vocab_size"]
    n, e = arch["num_layers"], arch["num_experts"]
    dkv = arch["num_kv_heads"] * arch["head_dim"]
    dq = arch["num_heads"] * arch["head_dim"]
    s_d, s_f = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    ex = (e,) if e > 1 else ()
    out = {
        "embed": ((v, d), 1.0),
        "layers/wq": ((n, d, dq), s_d),
        "layers/wk": ((n, d, dkv), s_d),
        "layers/wv": ((n, d, dkv), s_d),
        "layers/wo": ((n, dq, d), 1.0 / math.sqrt(dq)),
        "layers/norm1": ((n, d), None),
        "layers/norm2": ((n, d), None),
        "layers/w_gate": ((n, *ex, d, f), s_d),
        "layers/w_up": ((n, *ex, d, f), s_d),
        "layers/w_down": ((n, *ex, f, d), s_f),
        "final_norm": ((d,), None),
        "head": ((d, v), s_d),
    }
    if e > 1:
        out["layers/w_router"] = ((n, d, e), s_d)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, arch_items):
    arch = dict(arch_items)
    dt = jnp.dtype(arch["dtype"])
    spec = shapes(arch)
    keys = jax.random.split(key, len(spec))
    tree: dict = {"layers": {}}
    for k, (name, (shape, scale)) in zip(keys, sorted(spec.items())):
        leaf = (jnp.ones(shape, dt) if scale is None else
                (jax.random.normal(k, shape, jnp.float32) * scale)
                .astype(dt))
        if name.startswith("layers/"):
            tree["layers"][name.split("/", 1)[1]] = leaf
        else:
            tree[name] = leaf
    return tree


def make_params(arch: dict, seed: int):
    return _make(seed_key(seed), tuple(sorted(arch.items())))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _tokens(key, n, batch, seq, vocab):
    return jax.random.randint(key, (n, batch, seq), 0, vocab, jnp.int32)


def make_token_pool(seed: int, n: int, batch: int, seq: int,
                    vocab: int) -> list:
    """``n`` batches of ``batch`` rows that all differ, as a list of
    device arrays: the training feed."""
    pool = _tokens(jax.random.fold_in(seed_key(seed), 0x7e57), n, batch,
                   seq, vocab)
    return [pool[i] for i in range(n)]
