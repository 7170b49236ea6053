"""The learned sparse-attention indexer (DeepSeek-Sparse-Attention),
as the two families that have one share it (models/mla_moe.py's
`deepseek_v32` members over a latent plane, models/keye.py over K and V
per head): what a layer's indexer makes of a step's tokens, the index
scores of the step's queries over the cached indexer keys, and the
exact selection. Which plane holds the keys, where the queries come
from and how much of a key RoPE turns are the caller's."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ggrmcp_tpu.ops.rope import apply_rope


def indexer_inputs(q_src, normed, lp, positions, *, heads: int, width: int,
                   rope_dim: int, theta: float, scaling, eps: float):
    """What the sparse-attention indexer of a layer makes of the step's
    tokens: its queries `[B, S, heads, width]` (from `q_src`: the
    compressed queries where the model has q-compression, else the
    normed hidden states), ONE key a token `[B, S, width]` (LayerNorm
    with weight and bias; this is what the cache's indexer plane keeps)
    and the heads' weights `[B, S, heads]` float32, already times
    `heads^-0.5 width^-0.5`. RoPE, with the attention's frequencies
    (`theta`, `scaling`), turns the first `rope_dim` values of queries
    and keys, as half-split pairs."""
    b, s, _ = normed.shape
    f32 = jnp.float32

    def rot(t):  # [B, S, N, width]
        head = apply_rope(t[..., :rope_dim], positions, theta, scaling)
        return jnp.concatenate([head, t[..., rope_dim:]], axis=-1)

    q_i = rot((q_src @ lp["idx_wq"]).reshape(b, s, heads, width))
    k = (normed @ lp["idx_wk"]).astype(f32)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + eps)
    k = k * lp["idx_k_norm"].astype(f32) + lp["idx_k_bias"].astype(f32)
    k_i = rot(k.astype(normed.dtype)[:, :, None])[:, :, 0]
    w_i = (normed @ lp["idx_ww"]).astype(f32) * (heads**-0.5 * width**-0.5)
    return q_i, k_i, w_i


def index_scores(q_i, w_i, fetch, n_blocks, block: int, s_keys: int,
                 q_pos, kv_len):
    """`I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])` for the
    step's queries over the cached indexer keys, float32 `[B, S,
    s_keys]`: reduced over the heads block of keys by block of keys
    (`fetch(i)` -> `[B, block, width]`), so the `[.., heads, block]`
    scores of one block are all that ever exists. -inf where a query
    may not see (after its position `q_pos`, past the row's `kv_len`,
    in blocks the walk does not reach)."""
    b, s = q_i.shape[:2]

    def body(i, buf):
        per_head = jnp.einsum(
            "bshd,bkd->bshk", q_i, fetch(i),
            preferred_element_type=jnp.float32)
        blk = (jax.nn.relu(per_head) * w_i[..., None]).sum(2)
        return jax.lax.dynamic_update_slice(buf, blk, (0, 0, i * block))

    scores = jax.lax.fori_loop(
        0, n_blocks, body, jnp.full((b, s, s_keys), -jnp.inf, jnp.float32))
    k_pos = jnp.arange(s_keys)[None, None, :]
    return jnp.where(
        (k_pos <= q_pos[:, :, None]) & (k_pos < kv_len[:, None, None]),
        scores, -jnp.inf)


def kth_largest(scores, k: int):
    """The `k`-th largest value of the last axis, float32 `[.., 1]`,
    exactly and without a sort: bisection over the unsigned image of
    the scores that keeps their order (every bit of a negative flipped,
    the sign bit of the others set; -inf lies below every finite one).
    From the top bit down, a bit stays set where `k` keys still reach
    the value so far with it: 32 compare-and-count reads of the row in
    place of a sort of it (on the chip 1.2 ms against 18.2 for 512 x
    32,768: PERF.md, PR 38; two or four bits a read were no faster).
    Of a -0.0 and a +0.0 that tie either may come back; a row with
    fewer than `k` finite scores gives -inf."""
    u32 = jnp.uint32
    bits = jax.lax.bitcast_convert_type(scores, u32)
    sign = u32(1 << 31)
    key = jnp.where(bits >= sign, ~bits, bits | sign)

    def settle(i, cut):  # cut [.., 1]: the bits above 31 - i are final
        higher = cut | (sign >> i.astype(u32))
        reached = (key >= higher).sum(-1, keepdims=True) >= k
        return jnp.where(reached, higher, cut)

    cut = jax.lax.fori_loop(
        0, 32, settle, jnp.zeros(scores.shape[:-1] + (1,), u32))
    return jax.lax.bitcast_convert_type(
        jnp.where(cut >= sign, cut ^ sign, ~cut), jnp.float32)


def selection_mask(scores, topk: int, reach=None):
    """`[.., s_keys]` bool: each query's `topk` largest scores, ties
    to the lower position, exactly; every finite score where a query
    sees fewer (-inf marks what it may not see). The cut is the
    `topk`-th largest score (`kth_largest`: counted, not sorted).
    `reach` (a traced scalar) promises that keys from it on are all
    -inf: the passes then read the narrowest of a few halved widths
    that holds the rest (their cost is the width's), and nothing runs
    at 0."""
    def exact(scores):
        thr = kth_largest(scores, topk)
        above = scores > thr
        tied = (scores == thr) & (scores > -jnp.inf)
        need = topk - above.sum(-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied, axis=-1) <= need))

    if reach is None:
        return exact(scores)
    s_keys = scores.shape[-1]
    widths = [s_keys]
    while widths[0] // 2 > topk:
        widths.insert(0, widths[0] // 2)

    def upto(width):
        def branch(scores):
            mask = exact(scores[..., :width])
            return jnp.pad(
                mask, ((0, 0),) * (mask.ndim - 1) + ((0, s_keys - width),))
        return branch

    index = jnp.where(
        reach <= 0, 0,
        1 + sum((reach > w).astype(jnp.int32) for w in widths[:-1]))
    return jax.lax.switch(
        index,
        [lambda scores: jnp.zeros(scores.shape, bool)]
        + [upto(w) for w in widths], scores)


def selection_counts(ran, real, chosen, scored):
    """What a sparse attention path saw itself, int32 [3] (the last
    three of `mla_moe.ROUTING_STATS`): entries of the selections that
    name a key, keys the indexer scored for those queries, and the
    queries that selected. `ran` [B, S]: the queries that selected,
    `real` those of them that count (`valid`'s rows, or None); `chosen`
    and `scored` [B, S, n]: the selection's entries that name a key and
    the keys with an index score."""
    ran = ran if real is None else ran & real
    return jnp.stack([
        (chosen & ran[..., None]).sum(), (scored & ran[..., None]).sum(),
        ran.sum()]).astype(jnp.int32)
