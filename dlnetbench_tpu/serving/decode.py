"""The decode-path transformer: prefill/decode split over the paged
KV cache.

Shares weights (``models/transformer.init_params``) and math (RoPE,
RMSNorm, SwiGLU — the gated/llama family) with the training forward, so
decode output is bit-checkable against ``transformer.forward`` on the
same token prefix (tests/test_serving.py does exactly that).  Two
programs cover serving:

* ``make_decode_step``   — ONE token per active decode slot, full-batch
  (shape ``[slots]``, inactive slots masked by dropping their cache
  writes): project q/k/v for the fed token, write k/v into the slot's
  current page, run paged attention over everything cached, MLP, and
  greedy-sample the next token.  This is the program the engine runs
  every step of the continuous-batching loop — AOT-compiled via
  ``core/executor.CompiledStep`` with the page pools donated.
* ``make_prefill_chunk`` — one sequence, one CHUNK of its prompt
  (static chunk length, ``n_valid`` masking): writes the chunk's K/V
  into the slot's pages and attends causally over cache + chunk.
  ``scheduler`` drives it either to completion at admit time (separate
  prefill phase) or one chunk per engine step (inline-chunked).
* ``make_multi_step_decode`` — ISSUE 11's tentpole: N decode steps
  fused into ONE compiled program via ``lax.while_loop``, slot state
  (last tokens, positions, active flags, per-slot remaining budgets)
  carried ON DEVICE between steps, so the host pays one dispatch per N
  tokens instead of one per token.  The loop body is the SAME
  ``_step_tokens`` math the single-step program runs (token parity
  with the 1-step engine is a locked test), the trip count is dynamic
  (``n_steps`` operand + all-slots-done early exit), and a slot that
  exhausts its budget mid-loop deactivates itself without a host
  round-trip.  ``serving/speculative.py`` builds the draft/verify loop
  on the same body.

Only the dense gated (SwiGLU + RMSNorm + RoPE) config is supported —
the same subset every low-precision path in this repo covers first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.models.transformer import TransformerConfig
from dlnetbench_tpu.serving.kv_cache import (CacheConfig,
                                             dequant_gathered,
                                             paged_attention_decode,
                                             quant_write_span,
                                             sharded_paged_attention)
from dlnetbench_tpu.utils.seeded import Rng

_F32 = jnp.float32


def check_config(cfg: TransformerConfig,
                 decode: bool = False) -> TransformerConfig:
    if not cfg.gated or cfg.max_positions:
        raise ValueError(
            "serving decode covers the gated (SwiGLU+RMSNorm+RoPE) "
            "family only — non-gated / learned-position configs have "
            "no decode path yet")
    if cfg.attention_seg_avg:
        raise ValueError(
            "serving decode supports sliding-window attention masks "
            "only (attention_window); document-segment masks have no "
            "serving path — a request is one document")
    if decode and cfg.attention_window:
        # the decode step attends the FULL cached history (the paged
        # kernel has no lower-bound mask), so generating under a
        # window config would silently use different attention
        # semantics than the windowed prefill/training — refuse until
        # a lower-bound-aware paged kernel exists
        raise ValueError(
            "serving decode has no sliding-window path yet (the paged "
            "attention kernel attends the full cache): "
            "attention_window covers the PREFILL chunk only — decode "
            "under a window config would silently diverge from the "
            "training mask")
    return cfg


def _rope_decode(q, k, positions, theta=10000.0):
    """RoPE with a PER-ELEMENT position (decode: every slot sits at its
    own sequence offset).  q: [B, H, Dh], k: [B, Hkv, Dh],
    positions: [B].  Same split-halves convention as ``layers.rope``."""
    dh = q.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=_F32) / dh))
    angles = positions.astype(_F32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]   # [B, 1, Dh/2]
    sin = jnp.sin(angles)[:, None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(_F32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos],
                               axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _attn_fn(cache_cfg: CacheConfig, attn_impl: str, mesh):
    """One uniform internal attention signature for both cache forms:
    ``attn(q, k_l, v_l, ks_l, vs_l, lengths, block_tables)`` — the
    scale slices are ``None`` on the dense cache (where the underlying
    call is EXACTLY the pre-ISSUE-12 dispatch)."""
    quant = cache_cfg.quantized
    fmt = cache_cfg.quant_fmt
    if mesh is not None:
        sharded = sharded_paged_attention(mesh, impl=attn_impl,
                                          quantized=quant, fmt=fmt)
        if quant:
            return sharded
        return (lambda q, k, v, ks, vs, lengths, bt:
                sharded(q, k, v, lengths, bt))
    if quant:
        return (lambda q, k, v, ks, vs, lengths, bt:
                paged_attention_decode(q, k, v, lengths, bt,
                                       k_scale=ks, v_scale=vs, fmt=fmt,
                                       impl=attn_impl))
    return (lambda q, k, v, ks, vs, lengths, bt:
            paged_attention_decode(q, k, v, lengths, bt,
                                   impl=attn_impl))


def _split_pools(cache_cfg: CacheConfig, pools: tuple):
    """``(k_pages, v_pages, k_scale, v_scale)`` with None scales on the
    dense cache — the one unpacking both step bodies share."""
    if cache_cfg.quantized:
        return pools
    k_pages, v_pages = pools
    return k_pages, v_pages, None, None


def _step_tokens(cfg: TransformerConfig, cache_cfg: CacheConfig, attn,
                 params, pools, tokens, positions, write_ok,
                 block_tables, *, layers: int | None = None,
                 moe_bias=None, sampler=None, uids=None, gstate=None,
                 return_logits: bool = False):
    """ONE batched single-token step over the paged cache — the math
    both the single-step program and the fused multi-step loop body run
    (sharing the definition is what makes N-step-vs-1-step token parity
    a structural property, not a numerics hope).

    ``pools`` is ``(k_pages, v_pages)`` on the dense cache (the exact
    pre-ISSUE-12 program) or ``(k_pages, v_pages, k_scale, v_scale)``
    on a quantized one, where each cache write re-quantizes its page
    against a fresh amax (``kv_cache.quant_write_span``) and the
    attention dispatch dequantizes on read.  ``write_ok`` [B] gates the
    k/v cache write (inactive slots write nowhere: out-of-bounds page
    index + ``drop`` mode; their next_token is garbage the caller
    masks).  Attention covers ``positions + 1`` tokens (write-then-
    read: the fed token's k/v land first).  ``layers`` truncates the
    stack — the speculative TRUNCATED drafter is literally the first
    ``layers`` layers of the target plus the shared final-norm/head
    (serving/speculative.py); ``None`` runs the full depth.

    MoE configs (``cfg.num_experts > 1`` — ISSUE 15) run the MLP as
    per-expert token batches with overflow rounds
    (``serving/moe_decode.moe_mlp_rounds``; ``moe_bias`` is the seeded
    skew-injection knob) and the return value grows a third element:
    ``(pools, next_tokens, (expert_load [E], rounds))`` summed over
    the layer stack — the imbalance telemetry the engine records.

    SAMPLING (ISSUE 19): with a ``serving/sampling.DeviceSampler``,
    ``next_tokens`` is the seeded counter-keyed draw instead of the
    argmax — keyed by ``(sample_seed, uids[b], positions[b], lane)``,
    i.e. the FED position is the counter, so every program built on
    this body (1-step, fused N-step, spec verify) draws bit-identical
    tokens at the same stream position.  ``gstate`` [B] is the
    grammar-automaton state used to mask logits; TRANSITIONS are the
    caller's job (the fused loop advances its state row in-carry, the
    classic engine advances host-side at the fence).  ``sampler=None``
    is the byte-identical pre-ISSUE-19 greedy path.  ``return_logits``
    appends the raw logits to the return (the speculative drafter
    needs the distribution, not just a token; mutually exclusive with
    MoE, which spec refuses anyway)."""
    b = tokens.shape[0]
    scale = cfg.head_dim ** -0.5
    page_size = cache_cfg.page_size
    num_pages = cache_cfg.num_pages
    quant = cache_cfg.quantized
    k_pages, v_pages, k_scale, v_scale = _split_pools(cache_cfg, pools)
    x = params["embed"][tokens]                      # [B, D]
    page_col = positions // page_size
    page_id = jnp.take_along_axis(block_tables, page_col[:, None],
                                  axis=1)[:, 0]
    w_pages = jnp.where(write_ok, page_id, num_pages)  # OOB -> drop
    slots = positions % page_size
    att_lengths = positions + 1
    depth = cfg.num_layers if layers is None else layers
    moe = cfg.num_experts > 1
    moe_load = jnp.zeros((cfg.num_experts,), jnp.int32) if moe else None
    moe_rounds = jnp.int32(0)
    for li in range(depth):
        lp = jax.tree.map(lambda a: a[li], params["layers"])
        y = L.rmsnorm(x, lp["norm1"])
        q = jnp.dot(y, lp["wq"]).reshape(b, cfg.num_heads,
                                         cfg.head_dim)
        k = jnp.dot(y, lp["wk"]).reshape(b, cfg.num_kv_heads,
                                         cfg.head_dim)
        v = jnp.dot(y, lp["wv"]).reshape(b, cfg.num_kv_heads,
                                         cfg.head_dim)
        q, k = _rope_decode(q, k, positions)
        # write-then-read: the new token's k/v land in the page pool
        # first, so attention covers it like every cached token
        if quant:
            k_pages, k_scale = quant_write_span(
                k_pages, k_scale, li, k[:, None], positions,
                write_ok[:, None], block_tables,
                fmt=cache_cfg.quant_fmt, page_size=page_size,
                num_pages=num_pages)
            v_pages, v_scale = quant_write_span(
                v_pages, v_scale, li, v[:, None], positions,
                write_ok[:, None], block_tables,
                fmt=cache_cfg.quant_fmt, page_size=page_size,
                num_pages=num_pages)
        else:
            k_pages = k_pages.at[li, :, w_pages, slots, :].set(
                k, mode="drop")
            v_pages = v_pages.at[li, :, w_pages, slots, :].set(
                v, mode="drop")
        att = attn(q * scale, k_pages[li], v_pages[li],
                   k_scale[li] if quant else None,
                   v_scale[li] if quant else None, att_lengths,
                   block_tables)
        x = x + jnp.dot(att.reshape(b, cfg.embed_dim), lp["wo"])
        y = L.rmsnorm(x, lp["norm2"])
        if moe:
            from dlnetbench_tpu.serving.moe_decode import (
                decode_capacity, moe_mlp_rounds)
            cap = decode_capacity(b, cfg.top_k, cfg.num_experts,
                                  cfg.moe_capacity_factor)
            y2, load_l, rounds_l = moe_mlp_rounds(
                y, lp["w_router"], lp["w_gate"], lp["w_up"],
                lp["w_down"], top_k=cfg.top_k, capacity=cap,
                bias=moe_bias, active=write_ok)
            moe_load = moe_load + load_l
            moe_rounds = moe_rounds + rounds_l
            x = x + y2
        else:
            x = x + L.swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"])
    x = L.rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tied_embeddings else params["head"]
    logits = jnp.dot(x, head, preferred_element_type=_F32)
    if sampler is None:
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        next_tokens = sampler.draw_tokens(logits, uids, positions,
                                          gstate)
    pools_out = ((k_pages, v_pages, k_scale, v_scale) if quant
                 else (k_pages, v_pages))
    if moe:
        return pools_out, next_tokens, (moe_load, moe_rounds)
    if return_logits:
        return pools_out, next_tokens, logits
    return pools_out, next_tokens


def make_decode_step(cfg: TransformerConfig, cache_cfg: CacheConfig,
                     *, attn_impl: str = "auto", mesh=None,
                     moe_bias=None, sampler=None):
    """``decode_step(params, k_pages, v_pages, tokens, positions,
    block_tables, active) -> (k_pages, v_pages, next_tokens)``.

    tokens/positions/active: ``[slots]`` (int32/int32/bool); a slot's
    ``position`` is the cache index its token is written at (= tokens
    already cached), so attention covers ``position + 1`` tokens.
    Inactive slots write nowhere (out-of-bounds page index + ``drop``
    mode) and their next_token is garbage the engine ignores.

    On a QUANTIZED cache (ISSUE 12) the signature grows the scale
    arrays after the pools — ``decode_step(params, k_pages, v_pages,
    k_scale, v_scale, tokens, positions, block_tables, active) ->
    (k_pages, v_pages, k_scale, v_scale, next_tokens)`` — threaded
    functionally exactly like the pools themselves.  The dense
    signature (and its compiled program) is untouched.

    MoE configs (ISSUE 15) append the per-step imbalance stats to the
    outputs — ``(..., next_tokens, expert_load, rounds)`` — and take
    the seeded ``moe_bias`` skew knob (serving/moe_decode.py).

    With a ``sampler`` (serving/sampling.DeviceSampler — ISSUE 19)
    the signature grows two trailing operands: ``decode_step(...,
    active, uids, gstate)`` — per-slot request uids (the draw key) and
    grammar-automaton states (the logit mask; grammar transitions stay
    HOST-side here, since the classic engine fences every token
    anyway).  The sampler-less signature and program are untouched."""
    check_config(cfg, decode=True)
    attn = _attn_fn(cache_cfg, attn_impl, mesh)
    moe = cfg.num_experts > 1

    def _run(params, pools, tokens, positions, block_tables, active,
             uids=None, gstate=None):
        out = _step_tokens(cfg, cache_cfg, attn, params, pools, tokens,
                           positions, active, block_tables,
                           moe_bias=moe_bias, sampler=sampler,
                           uids=uids, gstate=gstate)
        if moe:
            pools, nxt, (load, rounds) = out
            return (*pools, nxt, load, rounds)
        pools, nxt = out
        return (*pools, nxt)

    if sampler is not None:
        if cache_cfg.quantized:
            def decode_step(params, k_pages, v_pages, k_scale, v_scale,
                            tokens, positions, block_tables, active,
                            uids, gstate):
                return _run(params,
                            (k_pages, v_pages, k_scale, v_scale),
                            tokens, positions, block_tables, active,
                            uids, gstate)
            return decode_step

        def decode_step(params, k_pages, v_pages, tokens, positions,
                        block_tables, active, uids, gstate):
            return _run(params, (k_pages, v_pages), tokens, positions,
                        block_tables, active, uids, gstate)
        return decode_step

    if cache_cfg.quantized:
        def decode_step(params, k_pages, v_pages, k_scale, v_scale,
                        tokens, positions, block_tables, active):
            return _run(params, (k_pages, v_pages, k_scale, v_scale),
                        tokens, positions, block_tables, active)
        return decode_step

    def decode_step(params, k_pages, v_pages, tokens, positions,
                    block_tables, active):
        return _run(params, (k_pages, v_pages), tokens, positions,
                    block_tables, active)

    return decode_step


# rows of the packed device slot-state carry ([6, slots] int32 — ONE
# array crosses the host<->device boundary per sync direction, not
# six; device_state.py mirrors the same layout).  ISSUE 19 grew the
# block past 4 rows: STATE_UID carries the request id every sampled
# draw is keyed by, STATE_GRAMMAR the per-slot grammar-automaton
# state.  Both rows ride (as zeros) even in greedy engines — the loop
# carries them untouched, so greedy token streams are unchanged.
(STATE_LAST, STATE_POS, STATE_REM, STATE_LIMIT, STATE_UID,
 STATE_GRAMMAR) = 0, 1, 2, 3, 4, 5
STATE_ROWS = 6


def make_multi_step_decode(cfg: TransformerConfig,
                           cache_cfg: CacheConfig, n_max: int, *,
                           attn_impl: str = "auto", mesh=None,
                           moe_bias=None, sampler=None):
    """The device-resident fused decode loop (ISSUE 11 tentpole).

    ``multi_step(params, k_pages, v_pages, state, block_tables,
    n_steps) -> (k_pages, v_pages, state, tokens_out, counts,
    steps_run)``.

    Runs up to ``min(n_steps, n_max)`` decode steps inside ONE compiled
    program (``lax.while_loop`` — dynamic trip count, so an adaptive
    ``n_steps`` needs no recompile and the loop exits early the moment
    every slot is done).  Slot state lives in the packed ``state``
    carry (``[6, slots]`` int32 — rows ``STATE_LAST`` the token each
    slot feeds next, ``STATE_POS`` the cache write index = tokens
    cached, ``STATE_REM`` output tokens still owed, ``STATE_LIMIT``
    the prompt+output reservation cap, ``STATE_UID`` the request id
    sampled draws key by, ``STATE_GRAMMAR`` the grammar-automaton
    state — the last two carried untouched when greedy/unconstrained).
    ``remaining > 0`` IS the
    active/done bit: a slot whose budget hits 0 deactivates itself
    in-loop, stops writing the cache, and waits for the host to evict
    it at the next sync.  ``tokens_out[b, j]`` holds slot ``b``'s j-th
    generated token of this call, ``counts[b]`` how many are valid,
    and ``steps_run`` the loop trips actually executed (the host's
    steps-per-dispatch metric).  Per step each active slot feeds one
    token and generates one, so ``positions`` advances exactly
    ``counts`` — the host-side page-table ``append`` is one batched
    call per SYNC, not per token.

    The loop body is ``_step_tokens`` — the same math
    ``make_decode_step`` runs — so the N-step greedy token stream
    equals the 1-step engine's exactly (locked by test).  On a
    QUANTIZED cache the scale arrays join the loop carry right after
    the pools (``multi_step(params, k_pages, v_pages, k_scale,
    v_scale, state, ...)``) — same write sequence as the 1-step
    quantized engine, so N-step-vs-1-step parity holds per cache
    dtype.

    MoE configs (ISSUE 15) run the per-expert batched MLP inside the
    loop body and append the ACCUMULATED imbalance stats to the
    outputs — ``(..., steps_run, expert_load, rounds)`` summed over
    the loop trips — so one host sync still carries the whole
    dispatch window's telemetry.

    With a ``sampler`` (ISSUE 19) each in-loop step draws via the
    counter-keyed sampler (uid row + fed position — NO PRNG state in
    the carry, which is exactly why N-step sampling is bit-identical
    to 1-step and adaptive ``n_steps`` still recompiles nothing) and
    the body advances the ``STATE_GRAMMAR`` row through the automaton
    after each accepted token.  The signature is UNCHANGED — the state
    block already carries everything sampling needs."""
    check_config(cfg, decode=True)
    if n_max < 1:
        raise ValueError(f"multi_step_decode: n_max must be >= 1, "
                         f"got {n_max}")
    attn = _attn_fn(cache_cfg, attn_impl, mesh)
    n_pools = 4 if cache_cfg.quantized else 2
    moe = cfg.num_experts > 1

    def _multi_step(params, pools, state, block_tables, n_steps):
        b = state.shape[1]
        n = jnp.minimum(n_steps.astype(jnp.int32), n_max)
        out0 = jnp.zeros((b, n_max), jnp.int32)
        counts0 = jnp.zeros((b,), jnp.int32)
        load0 = jnp.zeros((cfg.num_experts,), jnp.int32)
        rounds0 = jnp.int32(0)

        def cond(carry):
            i, st = carry[0], carry[1 + n_pools]
            return (i < n) & jnp.any(st[STATE_REM] > 0)

        def body(carry):
            i = carry[0]
            pc = carry[1:1 + n_pools]
            st, out, cnt, load, rounds = carry[1 + n_pools:]
            last, pos, rem = (st[STATE_LAST], st[STATE_POS],
                              st[STATE_REM])
            act = rem > 0
            step_out = _step_tokens(cfg, cache_cfg, attn, params, pc,
                                    last, pos, act, block_tables,
                                    moe_bias=moe_bias, sampler=sampler,
                                    uids=st[STATE_UID],
                                    gstate=st[STATE_GRAMMAR])
            if moe:
                pc, nxt, (load_s, rounds_s) = step_out
                load = load + load_s
                rounds = rounds + rounds_s
            else:
                pc, nxt = step_out
            # append each active slot's token at its own count index;
            # inactive slots aim past the buffer edge and drop
            idx = jnp.where(act, cnt, n_max)
            out = out.at[jnp.arange(b), idx].set(nxt, mode="drop")
            step = act.astype(jnp.int32)
            st = st.at[STATE_LAST].set(jnp.where(act, nxt, last))
            st = st.at[STATE_POS].set(pos + step)
            st = st.at[STATE_REM].set(rem - step)
            if sampler is not None and sampler.trans_dev is not None:
                g = st[STATE_GRAMMAR]
                st = st.at[STATE_GRAMMAR].set(
                    jnp.where(act, sampler.advance(g, nxt), g))
            cnt = cnt + step
            return (i + 1, *pc, st, out, cnt, load, rounds)

        final = lax.while_loop(
            cond, body,
            (jnp.int32(0), *pools, state, out0, counts0, load0,
             rounds0))
        i = final[0]
        pc = final[1:1 + n_pools]
        st, out, cnt, load, rounds = final[1 + n_pools:]
        if moe:
            return (*pc, st, out, cnt, i, load, rounds)
        return (*pc, st, out, cnt, i)

    if cache_cfg.quantized:
        def multi_step(params, k_pages, v_pages, k_scale, v_scale,
                       state, block_tables, n_steps):
            return _multi_step(params,
                               (k_pages, v_pages, k_scale, v_scale),
                               state, block_tables, n_steps)
        return multi_step

    def multi_step(params, k_pages, v_pages, state, block_tables,
                   n_steps):
        return _multi_step(params, (k_pages, v_pages), state,
                           block_tables, n_steps)

    return multi_step


def make_prefill_chunk(cfg: TransformerConfig, cache_cfg: CacheConfig,
                       chunk: int, *, moe_bias=None, sampler=None):
    """``prefill_chunk(params, k_pages, v_pages, tokens, start, n_valid,
    block_row) -> (k_pages, v_pages, next_token)``.

    One sequence, one chunk: ``tokens`` is ``[chunk]`` (padded),
    ``start`` the sequence offset of its first token, ``n_valid`` how
    many entries are real.  The chunk's K/V are written into the pages
    ``block_row`` maps, attention is causal over cache + chunk, and
    ``next_token`` is the greedy continuation after the LAST valid
    token — meaningful only on the chunk that completes the prompt
    (that token IS the request's first generated token; its TTFT
    stamp).

    With ``cfg.attention_window = W`` the prefill is SPARSE (ISSUE 10
    satellite): the chunk's queries can only see keys in ``(q-W, q]``,
    so the gather walks just the ``ceil((W-1+chunk)/page) + 1`` pages
    that window can touch instead of all ``max_pages_per_seq`` — the
    score grid shrinks from ``[C, pmax*page]`` to ``[C, pages_w*page]``
    — and the mask comes from the SAME builder the training paths use
    (ops/attention_mask.allowed with the equivalent MaskSpec), so a
    sliding-window model config prefills with the training mask
    semantics exactly (token-parity-tested against the dense path).

    QUANTIZED caches (ISSUE 12) add the scale arrays after the pools
    (``prefill_chunk(params, k_pages, v_pages, k_scale, v_scale,
    ...)``): chunk writes re-quantize their pages against a fresh amax
    (``kv_cache.quant_write_span``) and the gathered pages dequantize
    before the score matmul; the dense signature/program is
    untouched.

    With a ``sampler`` (ISSUE 19) the signature grows ONE trailing
    ``uid`` scalar operand (the request id) and the TTFT token becomes
    the seeded draw keyed by ``(sample_seed, uid, start + last)`` —
    the fed position of the last prompt token, i.e. the same counter
    convention as every decode program, so the whole stream is one
    consistent key sequence.  The grammar state for the FIRST
    generated token is the automaton's start state (the synthetic
    prompt is not grammar-conformant; the grammar constrains GENERATED
    tokens only)."""
    check_config(cfg)
    scale = cfg.head_dim ** -0.5
    page_size = cache_cfg.page_size
    num_pages = cache_cfg.num_pages
    pmax = cache_cfg.max_pages_per_seq
    quant = cache_cfg.quantized
    window = cfg.attention_window
    spec = None
    pages_w = pmax
    if window:
        from dlnetbench_tpu.ops.attention_mask import MaskSpec
        spec = MaskSpec(causal=True, window=window)
        # pages the window can reach from any chunk query: the span
        # (q-W, q] over the chunk covers W-1+chunk positions, plus one
        # page for alignment slack
        pages_w = min(pmax, -(-(window - 1 + chunk) // page_size) + 1)

    def _prefill(params, pools, tokens, start, n_valid, block_row,
                 uid=None):
        k_pages, v_pages, k_scale, v_scale = _split_pools(cache_cfg,
                                                          pools)
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        valid = jnp.arange(chunk) < n_valid
        x = params["embed"][tokens]                        # [C, D]
        page_col = jnp.minimum(positions // page_size, pmax - 1)
        page_id = block_row[page_col]
        w_pages = jnp.where(valid, page_id, num_pages)     # OOB -> drop
        slots = positions % page_size
        last = jnp.maximum(n_valid - 1, 0)
        moe_load = (jnp.zeros((cfg.num_experts,), jnp.int32)
                    if cfg.num_experts > 1 else None)
        moe_rounds = jnp.int32(0)
        for li in range(cfg.num_layers):
            lp = jax.tree.map(lambda a: a[li], params["layers"])
            y = L.rmsnorm(x, lp["norm1"])
            q = jnp.dot(y, lp["wq"]).reshape(chunk, cfg.num_heads,
                                             cfg.head_dim)
            k = jnp.dot(y, lp["wk"]).reshape(chunk, cfg.num_kv_heads,
                                             cfg.head_dim)
            v = jnp.dot(y, lp["wv"]).reshape(chunk, cfg.num_kv_heads,
                                             cfg.head_dim)
            # layers.rope wants [B, S, H, Dh] + positions [S]
            q, k = L.rope(q[None], k[None], positions)
            q, k = q[0], k[0]
            if quant:
                k_pages, k_scale = quant_write_span(
                    k_pages, k_scale, li, k[None], start[None],
                    valid[None], block_row[None],
                    fmt=cache_cfg.quant_fmt, page_size=page_size,
                    num_pages=num_pages)
                v_pages, v_scale = quant_write_span(
                    v_pages, v_scale, li, v[None], start[None],
                    valid[None], block_row[None],
                    fmt=cache_cfg.quant_fmt, page_size=page_size,
                    num_pages=num_pages)
            else:
                k_pages = k_pages.at[li, :, w_pages, slots, :].set(
                    k, mode="drop")
                v_pages = v_pages.at[li, :, w_pages, slots, :].set(
                    v, mode="drop")
            # causal attention over cache + chunk: gather the pages the
            # mask can reach (ALL of them when no window; just the
            # window span otherwise — pages beyond it are provably
            # masked, so their DMA and score columns are skipped),
            # chunk included (just written), mask per key position
            if window:
                first_page = jnp.maximum(
                    start - (window - 1), 0) // page_size
                pcols = first_page + jnp.arange(pages_w)
                # clamp the LOOKUP only: an overshooting column's key
                # positions exceed every query (causal-masked), so the
                # duplicated page it reads contributes nothing
                rows = block_row[jnp.clip(pcols, 0, pmax - 1)]
                k_pos = (pcols[:, None] * page_size
                         + jnp.arange(page_size)[None, :]).reshape(-1)
            else:
                rows = block_row
                k_pos = jnp.arange(pmax * page_size)
            if quant:
                kseq = dequant_gathered(k_pages[li][:, rows],
                                        k_scale[li][:, rows])
                vseq = dequant_gathered(v_pages[li][:, rows],
                                        v_scale[li][:, rows])
            else:
                kseq = k_pages[li][:, rows].astype(_F32)
                vseq = v_pages[li][:, rows].astype(_F32)
            hkv, npg, _, dh = kseq.shape   # [Hkv, pages_w, page, Dh]
            t = npg * page_size
            kseq = kseq.reshape(hkv, t, dh)
            vseq = vseq.reshape(hkv, t, dh)
            g = cfg.num_heads // hkv
            qg = (q * scale).reshape(chunk, hkv, g, dh).astype(_F32)
            scores = jnp.einsum("chgd,htd->hgct", qg, kseq)
            if spec is not None:
                from dlnetbench_tpu.ops.attention_mask import allowed
                keep = allowed(spec, positions[:, None],
                               k_pos[None, :])             # [C, T]
            else:
                keep = k_pos[None, :] <= positions[:, None]
            from dlnetbench_tpu.serving.kv_cache import MASK_VALUE
            scores = jnp.where(keep[None, None], scores, MASK_VALUE)
            p = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("hgct,htd->chgd", p, vseq)
            att = att.reshape(chunk, cfg.embed_dim).astype(x.dtype)
            x = x + jnp.dot(att, lp["wo"])
            y = L.rmsnorm(x, lp["norm2"])
            if cfg.num_experts > 1:
                from dlnetbench_tpu.serving.moe_decode import (
                    decode_capacity, moe_mlp_rounds)
                cap = decode_capacity(chunk, cfg.top_k,
                                      cfg.num_experts,
                                      cfg.moe_capacity_factor)
                y2, load_l, rounds_l = moe_mlp_rounds(
                    y, lp["w_router"], lp["w_gate"], lp["w_up"],
                    lp["w_down"], top_k=cfg.top_k, capacity=cap,
                    bias=moe_bias, active=valid)
                moe_load = moe_load + load_l
                moe_rounds = moe_rounds + rounds_l
                x = x + y2
            else:
                x = x + L.swiglu(y, lp["w_gate"], lp["w_up"],
                                 lp["w_down"])
        x = L.rmsnorm(x, params["final_norm"])
        head = params["embed"].T if cfg.tied_embeddings else params["head"]
        logits = jnp.dot(x[last], head, preferred_element_type=_F32)
        if sampler is None:
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            # the TTFT draw: counter = fed position of the LAST valid
            # prompt token; grammar state = automaton start (batch of
            # one through the shared batched draw)
            g0 = jnp.full((1,), sampler.start_state, jnp.int32)
            next_token = sampler.draw_tokens(
                logits[None], jnp.reshape(uid, (1,)).astype(jnp.int32),
                (start + last)[None], g0)[0]
        pools_out = ((k_pages, v_pages, k_scale, v_scale) if quant
                     else (k_pages, v_pages))
        if cfg.num_experts > 1:
            return pools_out, next_token, (moe_load, moe_rounds)
        return pools_out, next_token

    moe = cfg.num_experts > 1

    def _wrap(params, pools, tokens, start, n_valid, block_row,
              uid=None):
        out = _prefill(params, pools, tokens, start, n_valid,
                       block_row, uid)
        if moe:
            pools, nxt, (load, rounds) = out
            return (*pools, nxt, load, rounds)
        pools, nxt = out
        return (*pools, nxt)

    if sampler is not None:
        if quant:
            def prefill_chunk(params, k_pages, v_pages, k_scale,
                              v_scale, tokens, start, n_valid,
                              block_row, uid):
                return _wrap(params,
                             (k_pages, v_pages, k_scale, v_scale),
                             tokens, start, n_valid, block_row, uid)
            return prefill_chunk

        def prefill_chunk(params, k_pages, v_pages, tokens, start,
                          n_valid, block_row, uid):
            return _wrap(params, (k_pages, v_pages), tokens, start,
                         n_valid, block_row, uid)
        return prefill_chunk

    if quant:
        def prefill_chunk(params, k_pages, v_pages, k_scale, v_scale,
                          tokens, start, n_valid, block_row):
            return _wrap(params, (k_pages, v_pages, k_scale, v_scale),
                         tokens, start, n_valid, block_row)
        return prefill_chunk

    def prefill_chunk(params, k_pages, v_pages, tokens, start, n_valid,
                      block_row):
        return _wrap(params, (k_pages, v_pages), tokens, start,
                     n_valid, block_row)

    return prefill_chunk


def prompt_tokens(rid: int, prompt_len: int, vocab_size: int):
    """Deterministic synthetic prompt for request ``rid`` (the serving
    analogue of the proxies' seeded buffers): the workload is
    replayable from the arrival plan alone.  splitmix64 on the host —
    a ``jax.random.randint`` here would jit-compile once per distinct
    prompt length, a hidden multi-hundred-ms admission stall."""
    import numpy as np

    rng = Rng((rid + 1) * 0x9E3779B9)
    return np.fromiter((rng.uniform_int(0, vocab_size - 1)
                        for _ in range(prompt_len)),
                       dtype=np.int32, count=prompt_len)


def prompt_tokens_for(req, vocab_size: int):
    """The request's full prompt: when the arrival plan stamped a
    shared system-prompt prefix (``Request.prefix_id``/``prefix_len``,
    serving/arrivals.py — ISSUE 12), the first ``prefix_len`` tokens
    come from the PREFIX POOL's seeded stream (the same tokens for
    every request drawing that prefix — which is what makes them
    page-shareable), the tail from the request's own ``rid`` stream.
    Without a prefix this is exactly ``prompt_tokens``."""
    import numpy as np

    if getattr(req, "prefix_id", -1) < 0 or req.prefix_len <= 0:
        return prompt_tokens(req.rid, req.prompt_len, vocab_size)
    n_pre = min(req.prefix_len, req.prompt_len)
    rng = Rng((req.prefix_id + 1) * 0xC2B2AE3D)
    pre = np.fromiter((rng.uniform_int(0, vocab_size - 1)
                       for _ in range(n_pre)),
                      dtype=np.int32, count=n_pre)
    tail = prompt_tokens(req.rid, req.prompt_len, vocab_size)
    return np.concatenate([pre, tail[n_pre:]])
