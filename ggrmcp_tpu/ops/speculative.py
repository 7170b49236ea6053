"""Speculative decoding: a small draft model proposes gamma tokens per
round; the target model verifies ALL of them in ONE parallel forward —
the TPU-shaped trade: gamma sequential target decode steps (small,
latency-bound matmuls) become one (gamma+1)-token forward that keeps
the MXU busy, plus a cheap draft loop.

Two per-row acceptance modes share one program:

- Greedy (temperature 0): exact-match — a proposed token is accepted
  iff the target's argmax at that position equals it, so the emitted
  sequence is IDENTICAL to target-only greedy decoding regardless of
  draft quality (a correctness invariant tests/test_spec_batch.py pins
  down).
- Sampled (temperature > 0): standard rejection sampling (Leviathan et
  al. 2023; Chen et al. 2023) — the draft SAMPLES proposal x from its
  filtered distribution q, the proposal is accepted with probability
  min(1, p(x)/q(x)) against the target's distribution p, and on the
  first rejection the correction token is sampled from the residual
  normalize(max(p - q, 0)). The emitted tokens are then distributed
  EXACTLY as target-only sampling (lossless in distribution, not
  bitwise).

`spec_tick` is one fixed-shape draft/verify round over the continuous
batcher's slot pool (serving/batching.py `_tick_spec*`): KV caches are
carried with explicit per-row length accounting (rollback on rejection
= set the length counter; stale KV beyond it is masked by the causal
attention window).

No reference analogue (the Go gateway executes no models); this is a
serving-plane throughput component like ops/quant.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def spec_tick(
    target_forward,  # (tokens [B, W], cache) -> (logits [B, W, V], cache)
    draft_forward,  # same contract against the draft slot-pool cache
    prev: jnp.ndarray,  # [B] last COMMITTED token (position L-1)
    cur: jnp.ndarray,  # [B] pending token at position L (KV not written)
    tcache,  # target slot-pool cache, per-row length L
    dcache,  # draft slot-pool cache, per-row length L-1 (re-feed invariant)
    gamma: int,
    seeds: jnp.ndarray,  # [B] uint32 per-row seeds
    step,  # scalar int32, unique per tick (RNG stream tag)
    temps: jnp.ndarray,  # [B] (0 = greedy exact-match row)
    ks: jnp.ndarray,  # [B]
    ps: jnp.ndarray,  # [B]
    gstate: jnp.ndarray,  # [B] grammar DFA state (0 = unconstrained)
    g_allow: jnp.ndarray,  # [S, V] bool shared grammar allow table
    g_trans: jnp.ndarray,  # [S, V] int32 shared transition table
    j_len=None,  # [S] int32 forced-run lengths (None: no jump seeding)
    j_tokens=None,  # [S, J] int32 forced-run token ids
):
    """One FIXED-SHAPE draft/verify round over a continuous-batcher slot
    pool (the tick body when a draft is configured, serving/batching.py).

    Per round: the draft proposes `gamma` tokens (first feed is
    [prev, cur] so `prev` rewrites its own KV slot — the draft cache
    stays one position behind the target's), then the target verifies
    [cur, d_1..d_gamma] in ONE (gamma+1)-position forward against the
    shared cache. Variable advance WITHOUT dynamic shapes: every row
    writes all gamma+1 target positions every round and only the length
    POINTER advances by the accepted count — rejected positions are
    dead under the causal length mask and get overwritten next round,
    so rollback is pointer arithmetic, not a rolled scatter.

    Acceptance is per row inside one program:
      * temperature 0 — exact match against the target's (grammar-
        masked) argmax: emitted tokens are bitwise what the plain tick
        would emit;
      * temperature > 0 — rejection sampling over the per-row
        temp→top-k→top-p FILTERED p and q (filtered_logprobs applies
        the identical filter to both, which is what keeps the sampler
        lossless for filtered distributions);
      * constrained rows — the DFA allow-mask is applied to the draft's
        proposal distribution AND every verify position, with states
        advanced along the proposal path, so the emitted sequence obeys
        the grammar exactly as the plain masked tick would.

    Jump seeding (grammar.jump_max > 0; docs/structured_output.md
    "Jump-ahead"): when the forced-run tables are passed, a proposal
    position whose DFA state forces exactly one token takes that token
    straight from the table instead of sampling it — a forced run is a
    free 100%-acceptance draft prefix. The allow-mask already leaves a
    single finite logit in forced states, so the override changes no
    emitted token (and no acceptance outcome); it makes the forced
    prefix table-driven rather than argmax-recovered, and q(x)=1 for
    forced positions holds exactly by construction.

    Parked (inactive) rows run junk like the plain tick; the host drops
    their tokens and admission re-stamps their state on slot reuse.

    Returns (emit [B, gamma+1], count [B], tcache, dcache, prev', cur',
    gstate'): `emit[i, :count[i]]` are row i's tokens this round
    (d_1..d_a, correction); count = a+1 in [1, gamma+1].
    """
    from ggrmcp_tpu.ops.sampling import filtered_logprobs

    tlen0 = tcache.length
    dlen0 = dcache.length
    sampled = temps > 0.0
    base = jax.vmap(jax.random.PRNGKey)(
        jnp.asarray(seeds, jnp.uint32).astype(jnp.int32)
    )
    keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, step)

    def fold(tag):
        return jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, tag)

    def propose(logits, state, tag):
        """Grammar-masked draft proposal: filtered-q Gumbel draw for
        sampled rows, masked argmax for greedy rows. Returns
        (token [B], qlogp [B, V])."""
        masked = jnp.where(
            g_allow[state], logits.astype(jnp.float32), -jnp.inf
        )
        qlogp = filtered_logprobs(masked, temps, ks, ps)
        g = jax.vmap(
            # graftlint: disable=sharded-sampling -- draft PROPOSAL noise: rejection sampling is lossless for ANY q draw, so mesh-variance here shifts only the acceptance rate, never the emitted distribution; greedy rows bypass it entirely (test_tp spec bit-identity)
            lambda k: jax.random.gumbel(k, (masked.shape[-1],))
        )(fold(tag))
        samp = jnp.argmax(qlogp + g, axis=-1)
        tok = jnp.where(sampled, samp, jnp.argmax(masked, axis=-1)).astype(
            jnp.int32
        )
        if j_len is not None:
            # Forced-prefix seeding: a forced state's single admissible
            # token comes straight from the run table — the free
            # 100%-acceptance draft (identical to the masked draw, by
            # the single-finite-logit argument above).
            tok = jnp.where(
                j_len[state] > 0, j_tokens[state, 0], tok
            ).astype(jnp.int32)
        return tok, qlogp

    def advance(state, tok):
        return jnp.take_along_axis(
            g_trans[state], tok[:, None], axis=-1
        )[:, 0]

    # --- draft proposes gamma tokens --------------------------------------
    two = jnp.stack([prev, cur], axis=1)  # [B, 2]
    dlogits, dcache = draft_forward(two, dcache)
    d1, q1 = propose(dlogits[:, -1], gstate, 1)
    s1 = advance(gstate, d1)

    if gamma > 1:

        def draft_step(carry, j):
            tok, state, dc = carry
            lg, dc = draft_forward(tok[:, None], dc)
            nxt, q = propose(lg[:, -1], state, 1 + j)
            return (nxt, advance(state, nxt), dc), (nxt, q, state)

        (_, s_gamma, dcache), (rest, q_rest, s_rest) = jax.lax.scan(
            draft_step, (d1, s1, dcache), jnp.arange(1, gamma)
        )
        proposals = jnp.concatenate([d1[:, None], rest.T], axis=1)
        qlogp = jnp.moveaxis(
            jnp.concatenate([q1[None], q_rest], axis=0), 0, 1
        )  # [B, gamma, V]
        # states[:, j] = DFA state BEFORE the token at verify position
        # j (s_0 = gstate); states[:, gamma] = after all gamma proposals.
        states = jnp.concatenate(
            [gstate[None], s_rest, s_gamma[None]], axis=0
        ).T  # [B, gamma+1]
    else:
        proposals = d1[:, None]
        qlogp = q1[:, None]
        states = jnp.stack([gstate, s1], axis=1)

    # --- target verifies in ONE (gamma+1)-position forward ----------------
    verify_in = jnp.concatenate([cur[:, None], proposals], axis=1)
    vlogits, tcache = target_forward(verify_in, tcache)  # [B, gamma+1, V]
    vmask = g_allow[states]  # [B, gamma+1, V]
    vmasked = jnp.where(vmask, vlogits.astype(jnp.float32), -jnp.inf)
    tgt_greedy = jnp.argmax(vmasked, axis=-1).astype(jnp.int32)
    plogp = jax.vmap(
        lambda l: filtered_logprobs(l, temps, ks, ps),
        in_axes=1, out_axes=1,
    )(vmasked)  # [B, gamma+1, V]

    u = jax.vmap(
        # graftlint: disable=sharded-sampling -- [gamma]-shaped accept uniforms: no sharding spec ever maps a mesh axis to the gamma dim, so the draw is replicated and bit-identical on any mesh (the hazard is vocab-shaped noise)
        lambda k: jax.random.uniform(k, (gamma,))
    )(fold(700))
    logp_x = jnp.take_along_axis(
        plogp[:, :gamma], proposals[:, :, None], axis=2
    )[:, :, 0]
    logq_x = jnp.take_along_axis(
        qlogp, proposals[:, :, None], axis=2
    )[:, :, 0]
    match = jnp.where(
        sampled[:, None],
        jnp.log(u) < (logp_x - logq_x),
        proposals == tgt_greedy[:, :gamma],
    )
    a = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)  # [0..gamma]

    # Correction at position a: masked argmax for greedy rows; residual
    # normalize(max(p − q, 0)) for sampled rows (p directly after a full
    # acceptance). Everything stays inside the filtered+masked support.
    corr_greedy = jnp.take_along_axis(tgt_greedy, a[:, None], axis=1)[:, 0]
    p_a = jnp.take_along_axis(plogp, a[:, None, None], axis=1)[:, 0]
    q_a = jnp.take_along_axis(
        qlogp, jnp.clip(a, 0, gamma - 1)[:, None, None], axis=1
    )[:, 0]
    resid = jnp.maximum(jnp.exp(p_a) - jnp.exp(q_a), 0.0)
    resid = jnp.where((a == gamma)[:, None], jnp.exp(p_a), resid)
    mask_a = jnp.take_along_axis(vmask, a[:, None, None], axis=1)[:, 0]
    resid = jnp.where(mask_a, resid, 0.0)
    # Roundoff guard: a numerically all-zero residual row (p == q to
    # float precision at a rejected position) falls back to p itself —
    # the Gumbel argmax must never land on a zero-mass (or grammar-
    # disallowed) token for lack of any positive-mass candidate.
    resid = jnp.where(
        resid.sum(axis=-1, keepdims=True) > 1e-12, resid, jnp.exp(p_a)
    )
    g2 = jax.vmap(
        # graftlint: disable=sharded-sampling -- [V]-shaped residual draw of a lossless rejection sampler: the emitted distribution is exact on any mesh; bit-level cross-mesh identity is only claimed for greedy rows, which never reach this draw
        lambda k: jax.random.gumbel(k, (resid.shape[-1],))
    )(fold(900))
    corr_samp = jnp.argmax(jnp.log(resid + 1e-30) + g2, axis=-1).astype(
        jnp.int32
    )
    correction = jnp.where(sampled, corr_samp, corr_greedy)

    # --- emit [d_1..d_a, correction]; pointer-advance both caches ---------
    idx = jnp.arange(gamma + 1)[None, :]
    emit = jnp.where(
        idx < a[:, None],
        jnp.pad(proposals, ((0, 0), (0, 1))),
        jnp.where(idx == a[:, None], correction[:, None], 0),
    )
    count = a + 1
    tcache = tcache._replace(length=tlen0 + 1 + a)
    dcache = dcache._replace(length=dlen0 + 1 + a)
    prev2 = jnp.where(
        a == 0, cur,
        jnp.take_along_axis(
            proposals, jnp.maximum(a - 1, 0)[:, None], axis=1
        )[:, 0],
    )
    s_a = jnp.take_along_axis(states, a[:, None], axis=1)[:, 0]
    gstate2 = advance(s_a, correction)
    return emit, count, tcache, dcache, prev2, correction, gstate2
