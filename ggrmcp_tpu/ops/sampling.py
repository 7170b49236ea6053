"""Token sampling: greedy, temperature, top-k, top-p — all shapes
static, fully jittable (no data-dependent Python control flow), so the
decode step compiles once and stays on device.

The dynamic path reads its rows' parameters before it works over the
vocabulary (`lax.cond` inside the one program): a call whose rows are
all greedy takes the argmax alone, one whose sampling rows set neither
top-k nor top-p draws without the sort, and one whose live rows all sit
in grammar state 0 reads neither grammar table. The tokens and states
are the same bits either way — a batch with one row that needs the
work does all of it, for every row, as before.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SamplingConfig(NamedTuple):
    """Static sampling knobs (hashable → usable as a jit static arg)."""

    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0  # 0 → disabled
    top_p: float = 1.0  # 1 → disabled


def _samples(temperature: jnp.ndarray) -> jnp.ndarray:  # [B] bool
    """Rows that draw (the complement of sample_dynamic's greedy test,
    so a NaN temperature still counts as drawing)."""
    return ~(temperature <= 0.0)


def _invcdf_pick(u: jnp.ndarray, logits: jnp.ndarray) -> jnp.ndarray:
    """Categorical draw by CDF inversion from a per-row SCALAR uniform:
    token = #{i : cdf_i < u·mass}. Exactly the categorical distribution
    — and, unlike jax.random.categorical over the [V] axis, MESH-
    INVARIANT: categorical generates a [V]-shaped noise tensor whose
    random-bit assignment follows the array's partitioning, so a
    vocab-sharded logits row (column-parallel lm_head under tensor-
    parallel serving) draws a DIFFERENT token than the same row
    replicated. A scalar uniform per row is produced element-wise from
    the row's key (threefry is positionally fixed for elementwise
    shapes), so the draw is identical on 1 chip and any mesh
    (tests/test_tp.py sampled-row identity)."""
    probs = jax.nn.softmax(logits, axis=-1)
    cdf = jnp.cumsum(probs, axis=-1)
    mass = cdf[..., -1:]  # ~1.0; guards fp shortfall at the tail
    return jnp.sum(cdf < u[..., None] * mass, axis=-1).astype(jnp.int32)


def sample(
    logits: jnp.ndarray,  # [B, V]
    key: jax.Array,
    cfg: SamplingConfig,
) -> jnp.ndarray:  # [B] int32
    """Sample next tokens. Greedy when temperature == 0."""
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / cfg.temperature
    if cfg.top_k > 0:
        logits = _mask_top_k(logits, cfg.top_k)
    if cfg.top_p < 1.0:
        logits = _mask_top_p(logits, cfg.top_p)
    # Per-row scalar uniforms + CDF inversion (mesh-invariant draw —
    # see _invcdf_pick; folding the row index keeps rows independent).
    rows = jnp.arange(logits.shape[0])
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, rows)
    u = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
    return _invcdf_pick(u, logits)


def dynamic_support_mask(
    logits: jnp.ndarray,  # [B, V]
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
) -> jnp.ndarray:  # [B, V] bool
    """Tokens `sample_dynamic` can draw under the given per-row params
    — exposed so tests/test_sampling.py can hold the dynamic path to
    the STATIC path's boundary semantics (sample() = temperature scale,
    then top-k, then top-p over the top-k-renormalized distribution)
    without sampling-based set reconstruction. The grammar mask
    composes upstream of this (masked_sample_dynamic): disallowed
    tokens arrive as -inf and can never enter the kept set with a
    finite threshold.

    The sort runs only when some SAMPLING row asks for an order
    (top_k > 0 or top_p < 1); otherwise every row's support is
    everything, which is what the sorted threshold gives those rows. A
    greedy row never reads its support (its token is the argmax), so
    its row of the mask means nothing."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    # Temperature scales BEFORE the nucleus test, like the static
    # path's warper order (and HF's): top-p is a statement about the
    # distribution actually sampled from.
    safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / safe_temp

    def sorted_threshold(scaled):
        sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]  # desc
        rank = jnp.arange(v)[None, :]
        # top-k: keep ranks < k (k==0 → keep all)
        k = jnp.where(top_k[:, None] > 0, top_k[:, None], v)
        keep_k = rank < k
        # top-p over the distribution RENORMALIZED within the top-k kept
        # tokens — the static path applies _mask_top_p to the already
        # top-k-masked logits. With top_k disabled this is a no-op.
        probs = jax.nn.softmax(
            jnp.where(keep_k, sorted_logits, -jnp.inf), axis=-1
        )
        cumulative = jnp.cumsum(probs, axis=-1)
        # keep while mass before < p. p >= 1 disables the test OUTRIGHT
        # (static parity): the arithmetic form alone drops tail tokens
        # whose probability rounds below float32 eps, because
        # cumulative - probs lands exactly on 1.0 there.
        keep_p = (
            (cumulative - probs) < jnp.minimum(top_p, 1.0)[:, None]
        ) | (top_p[:, None] >= 1.0)
        keep = keep_k & keep_p
        keep = keep.at[:, 0].set(True)  # always ≥ 1 token
        # threshold = smallest kept logit per row
        kept_count = keep.sum(axis=-1, keepdims=True)
        threshold = jnp.take_along_axis(
            sorted_logits, kept_count - 1, axis=-1
        )
        return scaled >= threshold

    ordered = _samples(temperature) & ((top_k > 0) | (top_p < 1.0))
    return jax.lax.cond(
        jnp.any(ordered),
        sorted_threshold,
        lambda scaled: jnp.ones(scaled.shape, bool),
        scaled,
    )


def sample_dynamic(
    logits: jnp.ndarray,  # [B, V]
    seeds: jnp.ndarray,  # [B] uint32/int — per-request seeds
    step: jnp.ndarray,  # scalar int — decode step
    temperature: jnp.ndarray,  # [B] float; 0 → greedy
    top_k: jnp.ndarray,  # [B] int; 0 → disabled
    top_p: jnp.ndarray,  # [B] float; ≥1 → disabled
) -> jnp.ndarray:  # [B] int32
    """Per-row sampling with *traced* parameters — the continuous-batching
    path, where each slot carries its own sampling config and seed.
    One full sort per row replaces static top-k/top-p masking, and runs
    only when a sampling row set top-k or top-p (dynamic_support_mask);
    a call with no sampling row at all is the argmax and nothing else."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(logits):
        support = dynamic_support_mask(logits, temperature, top_k, top_p)
        safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = jnp.where(support, logits / safe_temp, -jnp.inf)

        def row_uniform(seed):
            # One SCALAR uniform per row (elementwise threefry): the draw
            # is identical whether the row's logits are replicated or
            # vocab-sharded over a tensor mesh — jax.random.categorical's
            # [V]-shaped noise is NOT (see _invcdf_pick).
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            return jax.random.uniform(key, ())

        u = jax.vmap(row_uniform)(seeds)
        sampled = _invcdf_pick(u, scaled)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(
        jnp.any(_samples(temperature)), draw, lambda _: greedy, logits
    )


def masked_sample_dynamic(
    logits: jnp.ndarray,  # [B, V]
    seeds: jnp.ndarray,  # [B]
    step: jnp.ndarray,  # scalar
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
    state: jnp.ndarray,  # [B] int32 — per-row grammar state (0 = none)
    allow: jnp.ndarray,  # [S, V] bool — shared grammar allow-mask
    trans: jnp.ndarray,  # [S, V] int32 — shared transition table
    live: jnp.ndarray | None = None,  # [B] bool — None: every row is
) -> tuple[jnp.ndarray, jnp.ndarray]:  # (tokens [B], next state [B])
    """Grammar-constrained per-row sampling: disallowed tokens are
    masked to -inf BEFORE temperature/top-k/top-p (the categorical's
    softmax renormalizes over the survivors), then each row's grammar
    state advances through the transition table — a gather, so the
    constrained step stays inside the jitted tick with no host
    round-trip. State 0 is the universal accept-all state
    (grammar/runtime.py): unconstrained rows pass through with
    bit-identical numerics (where(True, x, -inf) == x), which is what
    lets mixed batches share one compiled function.

    Neither table is read unless a LIVE row holds a state: a call whose
    live rows all sit in state 0 passes the logits through and returns
    the states it was given (allow[0] is all true and trans[0] all
    zero, so those are the gathers' results). `live` is the tick's
    active mask: a parked slot's device state keeps its stale value
    until the slot is admitted again, and must not bring the tables in
    — on the plain branch it keeps that stale value, as it did."""
    logits = logits.astype(jnp.float32)
    held = state != 0
    constrained = jnp.any(held if live is None else held & live)
    masked = jax.lax.cond(
        constrained,
        lambda x: jnp.where(allow[state], x, -jnp.inf),
        lambda x: x,
        logits,
    )
    tokens = sample_dynamic(masked, seeds, step, temperature, top_k, top_p)
    nxt = jax.lax.cond(
        constrained,
        lambda t: jnp.take_along_axis(trans[state], t[:, None], axis=-1)[:, 0],
        lambda t: state,
        tokens,
    )
    return tokens, nxt


def forced_run_lookup(
    state: jnp.ndarray,        # [B] int32 — per-row grammar state
    jump_len: jnp.ndarray,     # [S] int32 — forced-run length per state
    jump_tokens: jnp.ndarray,  # [S, J] int32 — run token ids
    jump_states: jnp.ndarray,  # [S, J] int32 — absolute states along the run
    jump_ok: jnp.ndarray,      # [B] bool — per-slot jump enable
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-row forced-run gather for the jump-ahead tick
    (docs/structured_output.md "Jump-ahead"): returns
    (run_len [B], run_tokens [B, J], landing [B]). run_len is 0 for
    unconstrained rows (state 0 has no forced run) and for rows with
    jump_ok=False (parked slots, jump-degraded requests — the
    grammar_jump_fail fallback), which collapses the jump to plain
    one-token constrained decoding for that row. landing is the
    absolute DFA state after consuming the run (= state when run_len
    is 0) — the state the post-run sample is masked under. Pure
    gathers over the fixed-shape arena tables: shape-invariant across
    any schema mix."""
    length = jnp.where(jump_ok, jump_len[state], 0)
    run_tokens = jump_tokens[state]  # [B, J]
    landing = jnp.where(
        length > 0,
        jnp.take_along_axis(
            jump_states[state],
            jnp.maximum(length - 1, 0)[:, None], axis=-1,
        )[:, 0],
        state,
    )
    return length, run_tokens, landing


def _mask_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    k = min(k, logits.shape[-1])
    threshold = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < threshold, -jnp.inf, logits)


def _mask_top_p(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus sampling: keep the smallest prefix of the sorted
    distribution with cumulative mass ≥ p."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    # keep tokens while the mass *before* them is < p (always ≥ 1 token)
    keep_sorted = (cumulative - probs) < p
    cutoff = jnp.sum(keep_sorted, axis=-1, keepdims=True)  # [B, 1]
    threshold = jnp.take_along_axis(sorted_logits, cutoff - 1, axis=-1)
    return jnp.where(logits < threshold, -jnp.inf, logits)
