"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context support (SURVEY.md §5.7 — absent in the reference, a
first-class design axis here). Two standard schemes over the mesh's
`sequence` axis, both expressed with shard_map + XLA collectives (never
hand-rolled transport):

- **Ring attention**: Q stays put; K/V blocks rotate around the ring
  via `ppermute` while each device accumulates its queries' attention
  with the online-softmax merge (the FlashAttention recurrence across
  devices). Communication overlaps compute; peak memory is one K/V
  block. Right choice when sequence ≫ heads.

- **Ulysses**: `all_to_all` re-shards [B, S/n, H, D] → [B, S, H/n, D],
  runs ordinary local attention over full sequences with a head slice,
  then re-shards back. Cheaper collectives for moderate S when the head
  count divides the axis.

Both reduce to plain attention when the sequence axis has size 1.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ggrmcp_tpu.ops.attention import NEG_INF, attention_xla

_SEQ_SPEC = P(None, "sequence", None, None)


def _ring_local(
    q: jnp.ndarray,  # [B, Sl, H, D] local query block
    k: jnp.ndarray,  # [B, Sl, H, D] local key block (starts at home)
    v: jnp.ndarray,
    axis_name: str,
    n: int,
    causal: bool,
    window: Optional[int] = None,
):
    b, sl, h, d = q.shape
    my_idx = jax.lax.axis_index(axis_name)
    scale = d**-0.5
    qf = q.astype(jnp.float32) * scale

    q_pos = my_idx * sl + jnp.arange(sl)  # [Sl] global query positions

    m0 = jnp.full((b, h, sl, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sl, 1), jnp.float32)
    acc0 = jnp.zeros((b, sl, h, d), jnp.float32)
    # Mark the accumulators as varying over the ring axis so the scan
    # carry types line up (shard_map varying-axis typing).
    m0, l0, acc0 = jax.lax.pcast(
        (m0, l0, acc0), (axis_name,), to="varying"
    )
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        k_cur, v_cur, m, l, acc = carry
        # After `step` rotations we hold the block that started at
        # device (my_idx - step) mod n.
        src = (my_idx - step) % n
        k_pos = src * sl + jnp.arange(sl)  # [Sl] global key positions
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
            if window is not None:
                # Sliding window (Mistral): positions are GLOBAL, so
                # the window mask composes with block rotation exactly
                # as on one device; fully-out-of-window key blocks
                # contribute nothing through the online-softmax merge.
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)  # [B,H,Sq,Sk]
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha.transpose(0, 2, 1, 3) + pv
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, m_new, l_new, acc_new

    _, _, m, l, acc = jax.lax.fori_loop(0, n, body, (k, v, m0, l0, acc0))
    l_t = jnp.maximum(l, 1e-30).transpose(0, 2, 1, 3)  # [B,Sq,H,1]
    return (acc / l_t).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S sharded over the sequence axis
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = True,
    axis: str = "sequence",
    window: Optional[int] = None,
) -> jnp.ndarray:
    assert window is None or causal, "sliding window requires causal"
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes.get(axis, 1)
    if n <= 1:
        return attention_xla(q, k, v, causal=causal, window=window)
    if q.shape[1] % n != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by {axis} axis {n}"
        )
    fn = jax.shard_map(
        functools.partial(
            _ring_local, axis_name=axis, n=n, causal=causal, window=window
        ),
        mesh=mesh,
        in_specs=(_SEQ_SPEC, _SEQ_SPEC, _SEQ_SPEC),
        out_specs=_SEQ_SPEC,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all head/sequence re-sharding)
# ---------------------------------------------------------------------------


def _ulysses_local(
    q: jnp.ndarray,  # [B, Sl, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool,
    window: Optional[int] = None,
):
    # [B, Sl, H, D] → [B, S, H/n, D]: gather sequence, scatter heads.
    def seq_to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # Full sequences are local after the gather, so global positions ==
    # local positions and the ordinary window mask applies unchanged.
    out = attention_xla(qh, kh, vh, causal=causal, window=window)
    return heads_to_seq(out)


def ulysses_attention(
    q: jnp.ndarray,  # [B, S, H, D] — S sharded over the sequence axis
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = True,
    axis: str = "sequence",
    window: Optional[int] = None,
) -> jnp.ndarray:
    assert window is None or causal, "sliding window requires causal"
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = sizes.get(axis, 1)
    if n <= 1:
        return attention_xla(q, k, v, causal=causal, window=window)
    if q.shape[2] % n != 0:
        raise ValueError(f"head count {q.shape[2]} not divisible by {axis}={n}")
    if q.shape[1] % n != 0:
        raise ValueError(f"sequence {q.shape[1]} not divisible by {axis}={n}")
    fn = jax.shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis, causal=causal, window=window
        ),
        mesh=mesh,
        in_specs=(_SEQ_SPEC, _SEQ_SPEC, _SEQ_SPEC),
        out_specs=_SEQ_SPEC,
    )
    return fn(q, k, v)
