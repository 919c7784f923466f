"""Seeded weights of the sparse-and-linear hybrid decoder
(``minicpm_sala_*`` configurations), made by the benchmark and handed to
both sides (``benchmarks/weights.py`` does the same for the gated
decoder; the token pool is that module's).

One jitted call makes the whole tree on the device in the layout the
program's ``models/hybrid.py`` reads: ``embed``, ``head`` ([V, D],
untied), ``final_norm`` and three groups stacked on a leading axis:
``block`` (both norms and the SwiGLU of every layer), ``gated`` (the
sparse layers' attention: ``wq`` [D, H, 2 dh] carries each head's query
and, beside it, the head's lanes of the output gate's projection ``W_z``;
``wk``, ``wv``, ``wo`` and the two norms a head) and ``lightning`` (the
lightning layers': ``wq``, ``wk``, ``wv``, the gate's ``wz``, ``wo`` and
the three norms a head).  The layout is the program's interface; the
values are the benchmark's: normal draws scaled by 1/sqrt(fan-in), the
embedding by 1, norm weights 1; norms are float32 whatever the
configuration's dtype.

TWO kinds of leaf are not one plain draw, each so that ``correct`` (a
comparison of NORMS, a leaf at a time, against the larger of the leaf's
and the median leaf's) measures the program:

* A sparse layer's ``q_norm`` and ``k_norm`` start at ``SHARP`` and not
  at 1, so a score ``q . k / sqrt(d)`` has deviation ``SHARP^2`` = 1.82
  over the keys and not 1.  With deviation 1 the softmax over a late
  token's thousands of keys is all but uniform, the layer's output and
  the gradients of its six leaves are a fifth of the median leaf's, and
  kernels that attend EVERY earlier key read the same norms as kernels
  that attend the selected blocks: the planted fault ``dense_for_sparse``
  read ``grad_norm_gap`` 0.0066 where the sound runs read 0.0022-0.0067
  (my chip runs, PR 50, call D), so no limit could see the kernels' mask.
  Sharper, a token's mass lies on fewer keys, which the selection keeps
  or drops, and the leaves weigh what the median leaf does.  Read on the
  chip at the timed size (my chip run, PR 50, call E, seed 2147494701;
  the sound program, then the kernels' ``_tile_mask`` without its
  membership test, ``grad_norm_gap`` / ``delta_norm_gap``): at 1.35
  0.0041 / 0.0046 and 0.065 / 0.061; at 1.6 0.0045 / 0.0056 (up to
  0.0095 / 0.0085 over four seeds, at the sparse layer's ``wv`` and
  ``wk``) and 0.160 / 0.179; at 1.9 0.0104 / 0.0089 and 0.145 / 0.130.
  1.35 is taken: the fault reads three times the limit while the sparse
  layer's leaves are no noisier than the lightning layers' output norms,
  whose readings the other controls' limits have to clear.  Trained
  norms a head are not 1 either.
* A lightning layer's ``wk`` is ``KEY_MIX`` of that layer's ``wq`` draw
  and ``sqrt(1 - KEY_MIX^2)`` of a draw of its own.  With two
  independent draws a token's score with itself, ``q_t . k_t /
  sqrt(d)``, is N(0, 1), and a head that decays fast (``lambda`` 0.43 in
  head 0) sees little else: its output is then that scalar times
  ``v_t``, as near zero as the scalar is, and the RMSNorm over the
  head's output (eps 1e-6) amplifies the gradient of such a (token,
  head) by up to a thousand.  A handful of them among 16384 x 32 x 3
  carry a tenth to all of a step's gradient norm in ``wq`` / ``wk`` and
  upstream, and no two precisions agree on them: 5 of 12 seeds read
  ``grad_norm_gap`` 0.014-0.88 and ``delta_norm_gap`` up to 1.49 where
  the other 7 read under 0.007 (my chip runs, PR 50, call B; PERF.md
  section 6).  Mixed, a token's own score is ``sqrt(d) (KEY_MIX +
  sqrt(1 - KEY_MIX^2) N(0, 1 / d))`` = 9.05 +- 0.6, fifteen deviations
  from zero, and ``q`` is not ``k``: a rule that swapped the two would
  read otherwise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import make_token_pool, seed_key  # noqa: F401

F32_LEAVES = frozenset({"norm1", "norm2", "final_norm", "q_norm", "k_norm",
                        "o_norm"})
# a layer's kind, and its stack of weights, by its entry of mixer_types
KIND_OF = {"minicpm4": "sparse", "lightning-attn": "lightning"}
GROUP_OF = {"sparse": "gated", "lightning": "lightning"}
# the docstring's two bullets: where a sparse layer's norms a head start,
# and the share of another leaf's draw in a leaf's own
SHARP = 1.35
KEY_MIX = {"lightning/wk": ("lightning/wq", 0.8)}
SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
               "window_size", "init_blocks", "dense_len")


def arch_of(config: dict) -> dict:
    """The sizes both sides need, from a configuration file's keys (the
    published ``config.json`` names; what it lacks under ``assumed``).
    The depth the residual's scale and the decay read is the PUBLISHED
    one (``published.num_hidden_layers``), whatever this file's is."""
    unsupported = {k: config.get(k) for k, ok in (
        ("attn_use_rope", (False,)), ("lightning_use_rope", (True,)),
        ("lightning_scale", ("1/sqrt(d)",)), ("qk_norm", (True,)),
        ("use_output_gate", (True,)), ("use_output_norm", (True,)),
        ("attn_use_output_gate", (True,)), ("attention_bias", (False,)),
        ("hidden_act", ("silu",)), ("tie_word_embeddings", (False,)))
        if config.get(k) not in ok}
    layers, mixers = config["num_hidden_layers"], config["mixer_types"]
    if (unsupported or len(mixers) != layers or set(mixers) - set(KIND_OF)
            or config["lightning_nkv"] != config["lightning_nh"]):
        raise ValueError(
            f"neither side computes {unsupported or 'these mixer_types'}: "
            f"minicpm4 and lightning-attn layers, lightning keys and "
            f"values at its queries' head count")
    published = config.get("published", {}).get("num_hidden_layers", layers)
    hidden = config["hidden_size"]
    return {
        "vocab_size": config["vocab_size"],
        "embed_dim": hidden,
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "lightning_heads": config["lightning_nh"],
        "lightning_dim": config["lightning_head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "num_layers": layers,
        "published_layers": published,
        "layer_kinds": tuple(KIND_OF[m] for m in mixers),
        "ff_dim": config["intermediate_size"],
        "embed_scale": float(config["scale_emb"]),
        "residual_scale": config["scale_depth"] / math.sqrt(published),
        "logit_scale": config["dim_model_base"] / hidden,
        "sparse_sizes": tuple(config["assumed"]["sparse_config"][k]
                              for k in SPARSE_KEYS),
        "eps": config["rms_norm_eps"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def shapes(arch: dict) -> dict:
    """{"group/leaf" or "leaf": (shape, init)}; ``init`` is the scale of
    normal draws, or ("all", the value every entry starts at)."""
    d, f, v = arch["embed_dim"], arch["ff_dim"], arch["vocab_size"]
    nl = arch["num_layers"]
    h, hkv, dh = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    lh, ld = arch["lightning_heads"], arch["lightning_dim"]
    ns = arch["layer_kinds"].count("sparse")
    nt = arch["layer_kinds"].count("lightning")
    s_d = 1.0 / math.sqrt(d)
    out = {
        "embed": ((v, d), 1.0),
        "head": ((v, d), s_d),
        "final_norm": ((d,), ("all", 1.0)),
        "block/norm1": ((nl, d), ("all", 1.0)),
        "block/norm2": ((nl, d), ("all", 1.0)),
        "block/w_gate": ((nl, d, f), s_d),
        "block/w_up": ((nl, d, f), s_d),
        "block/w_down": ((nl, f, d), 1.0 / math.sqrt(f)),
    }
    if ns:
        out.update({
            "gated/wq": ((ns, d, 2 * h * dh), s_d),
            "gated/wk": ((ns, d, hkv * dh), s_d),
            "gated/wv": ((ns, d, hkv * dh), s_d),
            "gated/q_norm": ((ns, dh), ("all", SHARP)),
            "gated/k_norm": ((ns, dh), ("all", SHARP)),
            "gated/wo": ((ns, h * dh, d), 1.0 / math.sqrt(h * dh))})
    if nt:
        out.update({
            "lightning/wq": ((nt, d, lh * ld), s_d),
            "lightning/wk": ((nt, d, lh * ld), s_d),
            "lightning/wv": ((nt, d, lh * ld), s_d),
            "lightning/wz": ((nt, d, lh * ld), s_d),
            "lightning/q_norm": ((nt, ld), ("all", 1.0)),
            "lightning/k_norm": ((nt, ld), ("all", 1.0)),
            "lightning/o_norm": ((nt, ld), ("all", 1.0)),
            "lightning/wo": ((nt, lh * ld, d), 1.0 / math.sqrt(lh * ld))})
    return out


def num_params(arch: dict) -> int:
    return sum(math.prod(shape) for shape, _ in shapes(arch).values())


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, arch_items):
    arch = dict(arch_items)
    dtype = jnp.dtype(arch["dtype"])
    spec = shapes(arch)
    tree: dict = {}
    keys = dict(zip(sorted(spec), jax.random.split(key, len(spec))))

    def draw(name):
        return jax.random.normal(keys[name], spec[name][0], jnp.float32)
    for name, (shape, init) in sorted(spec.items()):
        group, _, leaf = name.rpartition("/")
        dt = jnp.float32 if leaf in F32_LEAVES else dtype
        if isinstance(init, tuple):
            value = jnp.full(shape, init[1], dt)
        elif name in KEY_MIX:
            other, share = KEY_MIX[name]
            value = ((share * draw(other) + math.sqrt(1.0 - share ** 2)
                      * draw(name)) * init).astype(dt)
        else:
            value = (draw(name) * init).astype(dt)
        (tree.setdefault(group, {}) if group else tree)[leaf] = value
    return tree


def make_params(arch: dict, seed: int):
    return _make(seed_key(seed), tuple(sorted(arch.items())))
