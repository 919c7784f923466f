"""The headline bench train step, in ONE place.

``bench.py`` (the driver-run headline) and
``examples/xla_knob_study.py`` (the compiler-knob sweep) must measure
the SAME program — a sweep winner tuned for a drifted copy of the step
would be adopted into a different program than it was measured on.
Both build their step through this module, and both execute it through
the AOT engine (``core/executor.py``) with the params/optimizer-state
carry donated (``DONATE_ARGNUMS``): compile time is recorded out of
band, and the optimizer update reuses the param buffers in place
(aliasing visible in the recorded ``memory_analysis``).

Recipe rationale (shapes, remat, scan, logits dtype, VMEM option) is
documented at the call site in bench.py, where the measured history
lives.
"""
from __future__ import annotations

import dataclasses

import jax

from dlnetbench_tpu.utils.env import env_int

# Shape knobs, frozen at import (jit's cache is not keyed on the
# environment): the driver's headline shape by default; DLNB_BENCH_*
# overrides let the sentinel lane (Makefile `check-bench`,
# tests/test_sentinel.py) run the EXACT bench.py pipeline — headline
# compile, stat bands, --check — on a tiny CPU-feasible model.  Every
# consumer imports these constants, so a run's shape is one coherent
# choice, never a mix.
BATCH = env_int("DLNB_BENCH_BATCH", 2)
SEQ = env_int("DLNB_BENCH_SEQ", 6144)
LAYERS = env_int("DLNB_BENCH_LAYERS", 4)
VOCAB = env_int("DLNB_BENCH_VOCAB", 32768)
# 0 = the llama3_8b card's own dims
EMBED = env_int("DLNB_BENCH_EMBED", 0)
FF = env_int("DLNB_BENCH_FF", 0)
HEADS = env_int("DLNB_BENCH_HEADS", 0)
# kv heads default to HEADS when that is overridden (a tiny lane model
# wants kv == q); set this too to keep a GQA ratio under a HEADS
# override instead of silently converting the card to MHA
KV_HEADS = env_int("DLNB_BENCH_KV_HEADS", 0)

# which train_k argument the AOT call sites donate: the params /
# optimizer-state carry (argument 0); tokens are read-only
DONATE_ARGNUMS = (0,)


def bench_card():
    from dlnetbench_tpu.core.model_card import ModelCard, load_model_card
    base = load_model_card("llama3_8b")
    return ModelCard(name="llama3_8b_bench",
                     embed_dim=EMBED or base.embed_dim,
                     num_heads=HEADS or base.num_heads,
                     num_kv_heads=KV_HEADS or HEADS or base.num_kv_heads,
                     ff_dim=FF or base.ff_dim,
                     seq_len=SEQ, num_decoder_blocks=LAYERS,
                     vocab_size=VOCAB, gated_mlp=True)


def bench_cfg(card, **overrides):
    from dlnetbench_tpu.models import transformer as tfm
    return dataclasses.replace(tfm.TransformerConfig.from_card(card),
                               scan_layers=False, logits_f32=False,
                               **overrides)


def make_train_k(cfg, k: int, lr: float = 1e-3):
    """K optimizer steps chained in one program: every dispatch costs
    host latency that a real training loop, which keeps the device
    queue full, never serializes on; chaining K steps amortises it, so
    the reading is the DEVICE's.  ``train_k(params, tokens)`` returns
    ``(params, losses[k])``; for a model with expert layers
    ``(params, (losses[k], routing))``, each entry of ``routing``
    (``models/hybrid.ROUTING``: three counters and the selections)
    with a leading [k]; a model whose sparse attention layers select
    returns their ``hybrid.SELECTION`` in the same dict.

    ``lr`` is the SGD step: at the bench's 1e-3 the bf16 weights barely
    move (the headline measures time); a caller that wants to see the
    loss fall over the chain passes a larger one."""
    from dlnetbench_tpu.metrics.spans import scope
    from dlnetbench_tpu.models import hybrid
    from dlnetbench_tpu.models import transformer as tfm

    # the one step builder for every model family: a config names its
    # model by its type
    counts = isinstance(cfg, hybrid.HybridConfig) and cfg.returns_aux
    loss_fn = (hybrid.loss_and_routing if counts
               else hybrid.loss_fn if isinstance(cfg, hybrid.HybridConfig)
               else tfm.loss_fn)

    def sgd(p, g):
        with scope("optimizer"):
            return jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype),
                                p, g)

    def train_k(p, t):
        def body(p, _):
            loss, g = jax.value_and_grad(loss_fn, has_aux=counts)(
                p, t, cfg)
            return sgd(p, g), loss
        return jax.lax.scan(body, p, None, length=k)
    return train_k


def build(k: int = 10, *, card=None, batch: int = BATCH, lr: float = 1e-3,
          **cfg_overrides):
    """(train_k_fn, params, tokens, card, cfg) at the bench shape, or at
    ``card``'s and ``batch`` where given (``chip_smoke.py`` checks the
    same step at a small size against a float32 reference)."""
    from dlnetbench_tpu.models import transformer as tfm
    card = card or bench_card()
    cfg = bench_cfg(card, **cfg_overrides)
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1),
                                (batch, cfg.seq_len + 1), 0,
                                cfg.vocab_size)
    return make_train_k(cfg, k, lr), params, tokens, card, cfg
