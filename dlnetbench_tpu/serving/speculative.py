"""Self-drafting speculative decode inside the fused device loop.

The multi-step loop (serving/decode.make_multi_step_decode) already
buys one host dispatch per N tokens; speculative decode buys MORE
tokens per device step: draft ``k`` tokens cheaply, verify all ``k``
in ONE batched target pass, accept the longest matching prefix plus
the target's bonus token — entirely on device, inside the same
``lax.while_loop``, so a round emits between 1 and ``k + 1`` tokens
for roughly the device cost of one wide step.

Under greedy acceptance this is LOSSLESS: the verify pass computes the
target model's own greedy continuation at every drafted position, and
only drafts that MATCH it are kept — the emitted stream is exactly the
1-step greedy stream whatever the drafter proposes (locked by test).
Under SAMPLING (ISSUE 19) it is lossless too, via rejection-sampling
acceptance against the filtered target distribution (accept draft t
with probability min(1, p(t)/q(t)), residual resample at the first
reject, bonus draw on full acceptance — see make_spec_decode_loop;
the chi-square distribution-equality test in tests/test_sampling.py
is the parity lock).  One basis caveat: the verify pass runs the
dense-gather
attention math (the Pallas ``paged_attention`` kernel is single-query
and cannot serve K1 positions), so the parity lock is EXACT where the
1-step engine shares that math — the CPU mesh, or ``attn_impl=
"gather"`` on chip.  Against the on-chip Pallas 1-step path the two
argmaxes agree to kernel-parity tolerance (``chip_smoke.py``'s
pallas-vs-gather checks bound it), not bit-exactly — a near-tie in the
logits can diverge.  The drafter only moves the ACCEPTANCE RATE, i.e.
throughput:

* ``ngram``     — a per-slot bigram table ``[slots, vocab]`` on device:
  ``table[s, t]`` is the token that last followed ``t`` in slot ``s``'s
  stream (host seeds it from the prompt at admission; the loop updates
  it from emitted tokens).  Drafting is ``k`` chained table lookups —
  near-zero device cost, so even modest acceptance wins.
* ``truncated`` — the first ``drafter_layers`` layers of the target
  plus the shared final-norm/head (self-drafting: no second model, no
  extra weights).  Layer-truncated activations are exact for the
  layers they run, so the drafter writes the SAME k/v the verify pass
  would for layers ``< drafter_layers`` — the overlap is idempotent,
  and rejected positions are overwritten on the next round's feed.

Cache discipline mirrors the engine's admission contract: a fed token
writes k/v only while ``position < seq_limit`` (the slot's
prompt+output page reservation) — draft overshoot beyond the budget
writes nowhere, and every token the accept logic can USE is provably
inside the reservation (``emit <= remaining``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from dlnetbench_tpu.models import layers as L
from dlnetbench_tpu.models.transformer import TransformerConfig
from dlnetbench_tpu.serving.decode import (_attn_fn, _rope_decode,
                                           _step_tokens, check_config)
from dlnetbench_tpu.serving.kv_cache import MASK_VALUE, CacheConfig

_F32 = jnp.float32

DRAFTERS = ("ngram", "truncated")


def check_spec_config(cfg: TransformerConfig, *, spec_k: int,
                      drafter: str, drafter_layers: int) -> None:
    """Speculative knobs the model shape must also agree with (the
    ServingConfig-level checks live in scheduler.ServingConfig)."""
    if cfg.num_experts > 1:
        raise ValueError(
            "speculative: MoE models are not supported — the "
            "draft/verify overwrite cycle has no stated parity story "
            "through the MoE overflow rounds (ISSUE 15)")
    if spec_k < 1:
        raise ValueError(f"speculative: spec_k must be >= 1, got "
                         f"{spec_k}")
    if drafter not in DRAFTERS:
        raise ValueError(f"speculative: unknown drafter {drafter!r} "
                         f"(one of {DRAFTERS})")
    if drafter == "truncated" and not (
            1 <= drafter_layers < cfg.num_layers):
        raise ValueError(
            f"speculative: truncated drafter needs 1 <= drafter_layers "
            f"< num_layers ({cfg.num_layers}), got {drafter_layers} — "
            f"a full-depth drafter is the target itself (no draft "
            f"speedup, double the cost)")


def _verify_tokens(cfg: TransformerConfig, cache_cfg: CacheConfig,
                   params, k_pages, v_pages, tokens, positions,
                   write_ok, block_tables, *,
                   return_logits: bool = False):
    """The batched multi-token TARGET pass: feed ``tokens`` [B, K1]
    starting at cache index ``positions`` [B] per slot, write their k/v
    (where ``write_ok`` [B, K1] allows), attend causally over
    cache + fed tokens, and return the greedy continuation after EVERY
    fed position — ``out[b, j]`` is the target's next token given
    ``tokens[b, :j+1]``, which is all the accept rule needs.

    One dispatch-free pass costs ~K1x a single decode step on the MXU
    but verifies K1 positions — the speculative trade.  Attention is
    the dense gather form (length-masked fp32 softmax over the slot's
    gathered pages — kv_cache._gather_attention's math extended to K1
    queries); the Pallas decode kernel is single-query and does not
    apply.

    ``return_logits`` (ISSUE 19) appends the raw ``[B, K1, vocab]``
    logits to the return — the rejection-sampling accept pass needs
    the full target distribution at every drafted position, not just
    its argmax."""
    b, k1 = tokens.shape
    page_size = cache_cfg.page_size
    num_pages = cache_cfg.num_pages
    pmax = block_tables.shape[1]
    scale = cfg.head_dim ** -0.5
    hkv = cfg.num_kv_heads
    g = cfg.num_heads // hkv
    pos2 = positions[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]
    x = params["embed"][tokens]                       # [B, K1, D]
    page_col = jnp.minimum(pos2 // page_size, pmax - 1)
    page_id = jnp.take_along_axis(block_tables, page_col, axis=1)
    w_pages = jnp.where(write_ok, page_id, num_pages)  # OOB -> drop
    slots = pos2 % page_size
    t_len = pmax * page_size
    k_pos = jnp.arange(t_len, dtype=jnp.int32)
    keep = k_pos[None, None, :] <= pos2[:, :, None]    # [B, K1, T]
    for li in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[li], params["layers"])
        y = L.rmsnorm(x, lp["norm1"])
        q = jnp.dot(y, lp["wq"]).reshape(b, k1, cfg.num_heads,
                                         cfg.head_dim)
        k = jnp.dot(y, lp["wk"]).reshape(b, k1, hkv, cfg.head_dim)
        v = jnp.dot(y, lp["wv"]).reshape(b, k1, hkv, cfg.head_dim)
        qf, kf = _rope_decode(
            q.reshape(b * k1, cfg.num_heads, cfg.head_dim),
            k.reshape(b * k1, hkv, cfg.head_dim), pos2.reshape(-1))
        q = qf.reshape(b, k1, cfg.num_heads, cfg.head_dim)
        k = kf.reshape(b, k1, hkv, cfg.head_dim)
        k_pages = k_pages.at[li, :, w_pages, slots, :].set(
            k, mode="drop")
        v_pages = v_pages.at[li, :, w_pages, slots, :].set(
            v, mode="drop")
        # gather the slot's whole page row (stale/garbage tail masked
        # by the per-query causal length, same as _gather_attention)
        kseq = jnp.moveaxis(k_pages[li][:, block_tables], 0, 1)
        vseq = jnp.moveaxis(v_pages[li][:, block_tables], 0, 1)
        kseq = kseq.reshape(b, hkv, t_len, cfg.head_dim).astype(_F32)
        vseq = vseq.reshape(b, hkv, t_len, cfg.head_dim).astype(_F32)
        qg = (q * scale).reshape(b, k1, hkv, g,
                                 cfg.head_dim).astype(_F32)
        scores = jnp.einsum("bjhgd,bhtd->bhgjt", qg, kseq)
        scores = jnp.where(keep[:, None, None], scores, MASK_VALUE)
        p = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum("bhgjt,bhtd->bjhgd", p, vseq)
        att = att.reshape(b, k1, cfg.embed_dim).astype(x.dtype)
        x = x + jnp.dot(att, lp["wo"])
        y = L.rmsnorm(x, lp["norm2"])
        x = x + L.swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"])
    x = L.rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tied_embeddings else params["head"]
    logits = jnp.dot(x, head, preferred_element_type=_F32)
    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, K1]
    if return_logits:
        return k_pages, v_pages, out, logits
    return k_pages, v_pages, out


def _draft_ngram(table, last_tokens, k: int):
    """k chained bigram lookups per slot: [B, vocab] table, [B] seed."""
    b = table.shape[0]
    rows = jnp.arange(b)
    drafts = []
    prev = last_tokens
    for _ in range(k):
        prev = table[rows, prev]
        drafts.append(prev)
    return jnp.stack(drafts, axis=1)                      # [B, k]


def make_spec_decode_loop(cfg: TransformerConfig,
                          cache_cfg: CacheConfig, n_max: int, *,
                          spec_k: int, drafter: str,
                          drafter_layers: int = 1,
                          attn_impl: str = "auto", mesh=None,
                          sampler=None):
    """The fused draft/verify/accept loop (ISSUE 11 tentpole, spec
    flavor).

    ``spec_loop(params, k_pages, v_pages, state, ngram_table,
    block_tables, n_rounds) -> (k_pages, v_pages, state, ngram_table,
    tokens_out, counts, rounds_run, drafted, accepted)`` — ``state``
    is the packed ``[6, slots]`` int32 carry (decode.STATE_* rows;
    ``remaining > 0`` is the active bit, ``STATE_LIMIT`` the per-slot
    reservation cap the write guard enforces).

    Per round, per active slot: draft ``spec_k`` tokens, verify them
    in one batched target pass, emit ``min(accept + 1, remaining)``
    target tokens (the accepted prefix IS the target's own greedy
    stream; the +1 is the bonus token from the first mismatched
    position), advance position/remaining by the same amount (fed ==
    emitted, so the host-side page append stays one batched call per
    sync).  ``tokens_out`` is ``[B, n_max * (spec_k + 1)]`` — the
    worst-case all-accepted capacity; ``counts`` says how much is
    real.  ``drafted``/``accepted`` accumulate the RAW acceptance
    stats (pre-clamp — the drafter's quality, not the budget's), which
    ride the record as the acceptance-rate metric.

    With a ``sampler`` (ISSUE 19) the loop runs LOSSLESS speculative
    SAMPLING — standard rejection-sampling acceptance against the
    target distribution instead of greedy exact-match:

    * drafts are SAMPLED from the truncated drafter's own filtered
      distribution ``q_j`` (``LANE_DRAFT`` keyed uniforms; the ngram
      drafter is refused here — it proposes tokens with no
      distribution, and the accept rule needs ``q``);
    * draft ``j`` is accepted iff ``u_j · q_j(d_j) < p_j(d_j)``
      (``LANE_ACCEPT``) where ``p_j`` is the FILTERED target
      distribution at that position — exactly the min(1, p/q) accept
      probability, strict so a zero-target-probability draft (e.g.
      out-of-grammar) can NEVER be accepted;
    * the first rejected position resamples from the normalized
      residual ``max(p - q, 0)`` (``LANE_RESID``; falls back to ``p``
      itself when the residual is empty, which happens exactly when
      ``q`` dominates ``p`` nowhere — e.g. identical one-hots at
      temperature 0);
    * full acceptance draws the bonus token from ``p_k``
      (``LANE_TOKEN`` at the bonus position — the same key the
      non-spec sampler would use there).

    The emitted-stream distribution provably equals the unfused
    single-step sampler's (the chi-square parity lock in
    tests/test_sampling.py); ``temperature == 0`` distributions are
    one-hots, so the rule degenerates to exact-match greedy and the
    greedy parity lock still holds.  Grammar states ride
    ``STATE_GRAMMAR`` and advance along the EMITTED tokens; target
    probs are masked per-position through the draft chain's automaton
    states, so constrained + speculative composes for free."""
    check_config(cfg, decode=True)
    check_spec_config(cfg, spec_k=spec_k, drafter=drafter,
                      drafter_layers=drafter_layers)
    if sampler is not None and drafter != "truncated":
        # mirrored at sampling.check_sampling_config — rejection
        # sampling needs q(draft), which only the truncated drafter has
        raise ValueError(
            "spec_decode_loop: speculative sampling requires drafter "
            f"probs — drafter {drafter!r} has no distribution; use "
            "drafter='truncated'")
    if cache_cfg.quantized:
        # the ServingConfig-level refusal, mirrored at the builder:
        # the verify pass overwrites drafter rows and every overwrite
        # re-quantizes the page — a parity bar for that write cycling
        # has not been stated, so the combination is refused loudly
        # rather than shipped untested (docs/SERVING.md)
        raise ValueError(
            "speculative decode supports the bf16 cache only — "
            f"cache_dtype={cache_cfg.cache_dtype!r} re-quantizes "
            "pages on every draft/verify overwrite and has no stated "
            "parity bar; run speculative on the dense cache")
    if n_max < 1:
        raise ValueError(f"spec_decode_loop: n_max must be >= 1, "
                         f"got {n_max}")
    attn = _attn_fn(cache_cfg, attn_impl, mesh)
    k1 = spec_k + 1
    cap = n_max * k1

    from dlnetbench_tpu.serving.decode import (STATE_GRAMMAR,
                                               STATE_LAST, STATE_LIMIT,
                                               STATE_POS, STATE_REM,
                                               STATE_UID)
    from dlnetbench_tpu.serving.sampling import (LANE_ACCEPT,
                                                 LANE_DRAFT,
                                                 LANE_RESID,
                                                 LANE_TOKEN)

    def spec_loop(params, k_pages, v_pages, state, ngram_table,
                  block_tables, n_rounds):
        b = state.shape[1]
        rows = jnp.arange(b)
        n = jnp.minimum(n_rounds.astype(jnp.int32), n_max)
        out0 = jnp.zeros((b, cap), jnp.int32)
        counts0 = jnp.zeros((b,), jnp.int32)

        def cond(carry):
            i, _, _, st = carry[0], carry[1], carry[2], carry[3]
            return (i < n) & jnp.any(st[STATE_REM] > 0)

        def body(carry):
            (i, kp, vp, st, table, out, cnt, drafted,
             accepted) = carry
            last, pos, rem, limits = (st[STATE_LAST], st[STATE_POS],
                                      st[STATE_REM], st[STATE_LIMIT])
            act = rem > 0
            uids = st[STATE_UID]
            g0 = st[STATE_GRAMMAR]
            q_list, q_at_draft = [], []
            # ---- draft k tokens per slot
            if drafter == "ngram":
                drafts = _draft_ngram(table, last, spec_k)
            else:
                dkp, dvp = kp, vp
                prev, dpos, ds = last, pos, []
                gd = g0
                for _ in range(spec_k):
                    ok = act & (dpos < limits)
                    if sampler is None:
                        (dkp, dvp), prev = _step_tokens(
                            cfg, cache_cfg, attn, params, (dkp, dvp),
                            prev, dpos, ok, block_tables,
                            layers=drafter_layers)
                    else:
                        # SAMPLE the draft from the drafter's own
                        # filtered distribution q_j (grammar-masked
                        # through the draft chain's automaton states)
                        (dkp, dvp), _, dlog = _step_tokens(
                            cfg, cache_cfg, attn, params, (dkp, dvp),
                            prev, dpos, ok, block_tables,
                            layers=drafter_layers, return_logits=True)
                        qj = sampler.probs(dlog, gd)
                        u_d = sampler.u01(uids, dpos, LANE_DRAFT)
                        prev = sampler.draw_from_probs(qj, u_d)
                        q_list.append(qj)
                        q_at_draft.append(qj[rows, prev])
                        gd = sampler.advance(gd, prev)
                    ds.append(prev)
                    dpos = dpos + 1
                kp, vp = dkp, dvp
                drafts = jnp.stack(ds, axis=1)
            # ---- one batched target pass over [last, drafts]
            fed = jnp.concatenate([last[:, None], drafts], axis=1)
            pos2 = pos[:, None] + jnp.arange(k1, dtype=jnp.int32)
            write_ok = act[:, None] & (pos2 < limits[:, None])
            if sampler is None:
                kp, vp, tgt = _verify_tokens(cfg, cache_cfg, params,
                                             kp, vp, fed, pos,
                                             write_ok, block_tables)
                # greedy accept: longest prefix where draft == target
                match = (drafts == tgt[:, :spec_k]).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                emit = jnp.where(act, jnp.minimum(acc + 1, rem), 0)
                etoks = tgt
            else:
                kp, vp, tgt, vlogits = _verify_tokens(
                    cfg, cache_cfg, params, kp, vp, fed, pos,
                    write_ok, block_tables, return_logits=True)
                # grammar state BEFORE emitting at index j = start
                # state advanced through drafts[:j]
                gs = [g0]
                for j in range(spec_k):
                    gs.append(sampler.advance(gs[j], drafts[:, j]))
                p_js = [sampler.probs(vlogits[:, j], gs[j])
                        for j in range(k1)]
                # rejection-sampling accept: u·q(d) < p(d), strict —
                # an out-of-grammar draft has p(d) == 0 and can never
                # pass, whatever u
                p_at_draft = jnp.stack(
                    [p_js[j][rows, drafts[:, j]]
                     for j in range(spec_k)], axis=1)
                q_d = jnp.stack(q_at_draft, axis=1)
                u_acc = jnp.stack(
                    [sampler.u01(uids, pos + j, LANE_ACCEPT)
                     for j in range(spec_k)], axis=1)
                accept = (u_acc * q_d < p_at_draft).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
                emit = jnp.where(act, jnp.minimum(acc + 1, rem), 0)
                # emitted token at index j: the draft while j < acc,
                # the residual resample at the first reject, the bonus
                # draw from p_k after full acceptance
                cols = []
                for j in range(k1):
                    if j < spec_k:
                        resid = jnp.maximum(p_js[j] - q_list[j], 0.0)
                        z = jnp.sum(resid, axis=-1, keepdims=True)
                        rdist = jnp.where(
                            z > 0, resid / jnp.maximum(z, 1e-30),
                            p_js[j])
                        u_r = sampler.u01(uids, pos + j, LANE_RESID)
                        r_j = sampler.draw_from_probs(rdist, u_r)
                        cols.append(jnp.where(j < acc, drafts[:, j],
                                              r_j))
                    else:
                        u_b = sampler.u01(uids, pos + spec_k,
                                          LANE_TOKEN)
                        cols.append(sampler.draw_from_probs(
                            p_js[spec_k], u_b))
                etoks = jnp.stack(cols, axis=1)
            # ---- append emitted tokens at each slot's count
            for j in range(k1):
                w = act & (j < emit)
                idx = jnp.where(w, cnt + j, cap)
                out = out.at[rows, idx].set(etoks[:, j], mode="drop")
            # ---- ngram table learns every emitted (prev -> next) pair
            if drafter == "ngram":
                prevs = jnp.concatenate([last[:, None],
                                         etoks[:, :spec_k]], axis=1)
                vocab = table.shape[1]
                for j in range(k1):
                    w = act & (j < emit)
                    row = jnp.where(w, prevs[:, j], vocab)
                    table = table.at[rows, row].set(etoks[:, j],
                                                    mode="drop")
            st = st.at[STATE_LAST].set(jnp.where(
                act, etoks[rows, jnp.maximum(emit - 1, 0)], last))
            st = st.at[STATE_POS].set(pos + emit)
            st = st.at[STATE_REM].set(rem - emit)
            if sampler is not None and sampler.trans_dev is not None:
                # grammar state advances along the EMITTED tokens only
                g_new = g0
                for j in range(k1):
                    g_new = jnp.where(j < emit,
                                      sampler.advance(g_new,
                                                      etoks[:, j]),
                                      g_new)
                st = st.at[STATE_GRAMMAR].set(g_new)
            cnt = cnt + emit
            drafted = drafted + jnp.sum(jnp.where(act, spec_k, 0))
            accepted = accepted + jnp.sum(jnp.where(act, acc, 0))
            return (i + 1, kp, vp, st, table, out, cnt, drafted,
                    accepted)

        (i, kp, vp, st, table, out, cnt, drafted,
         accepted) = lax.while_loop(
            cond, body,
            (jnp.int32(0), k_pages, v_pages, state, ngram_table, out0,
             counts0, jnp.int32(0), jnp.int32(0)))
        return kp, vp, st, table, out, cnt, i, drafted, accepted

    return spec_loop


def seed_ngram_row(prompt_tokens, first_token: int, vocab: int):
    """The host half of the ngram drafter: a fresh ``[vocab]`` bigram
    row for a newly admitted slot, seeded from the prompt (plus the
    prefill's first generated token continuing the last prompt token)
    so round one drafts from real context instead of zeros.  Called by
    the engine at admission — part of the priced h2d sync."""
    import numpy as np
    row = np.zeros((vocab,), np.int32)
    toks = np.append(np.asarray(prompt_tokens, np.int32),
                     np.int32(first_token))
    # repeated-index assignment keeps the LAST write — the most recent
    # continuation, matching the device-side sequential update rule
    row[toks[:-1]] = toks[1:]
    return row
