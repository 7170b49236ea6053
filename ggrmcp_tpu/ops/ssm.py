"""The selective state-space recurrence (Mamba-1) of one layer, as the
hybrid family runs it (models/jamba.py): a chunk's scan and a decode
step's update. Both are XLA; `dispatch_counts["ssm_scan"]` /
`["ssm_step"]` count the programs traced with each.

A row's state is `h` `[N, C]` float32 (N state values a channel, C =
`d_inner` channels, the channels on the lanes) and the recurrence is

    h_t = exp(dt_t A) * h_{t-1} + (dt_t u_t) B_t^T,   y_t = C_t h_t

with `A` `[N, C]` negative, `dt_t`, `u_t` `[C]`, `B_t`, `C_t` `[N]`.
A position whose `dt` is 0 leaves `h` where it was (`exp(0) = 1`, the
input term 0): that is how padding past a row's true length is kept
out of the state, the caller zeroes `dt` there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ggrmcp_tpu.ops.attention import dispatch_counts

# Positions a block of the chunk scan: one page of the paged cache, so
# that the state at every page boundary of a chunk that starts on one is
# a carry of the scan (the snapshots of serving/pages.py are taken there).
SCAN_BLOCK = 16


def ssm_step(h, u, dt, a, b_t, c_t):
    """One position a row: `h` [B, N, C] float32, `u`, `dt` [B, C],
    `b_t`, `c_t` [B, N]. Returns (y [B, C] float32, h)."""
    dispatch_counts["ssm_step"] += 1
    f32 = jnp.float32
    dt, u = dt.astype(f32), u.astype(f32)
    h = jnp.exp(dt[:, None, :] * a[None]) * h + (
        (dt * u)[:, None, :] * b_t.astype(f32)[:, :, None])
    return jnp.einsum("bnc,bn->bc", h, c_t.astype(f32)), h


def ssm_scan(h, u, dt, a, b_m, c_m, block: int = SCAN_BLOCK):
    """A chunk: `h` [B, N, C] float32 enters, `u`, `dt` [B, S, C],
    `b_m`, `c_m` [B, S, N]. The scan walks the chunk in blocks of
    `block` positions (the steps of a block unrolled, the blocks a
    `lax.scan`), so nothing of `[S, N, C]` is ever held: a block's
    `[block, N, C]` at most. Returns (y [B, S, C] float32, h after the
    last position, hs [S / block, B, N, C]: h after each block). S is
    padded up to a whole block with dt 0, which moves nothing."""
    dispatch_counts["ssm_scan"] += 1
    f32 = jnp.float32
    bsz, s, c = u.shape
    pad = -s % block
    nb = (s + pad) // block

    def blocks(t):  # [B, S, W] -> [nb, block, B, W], time-major
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return t.reshape(bsz, nb, block, t.shape[-1]).transpose(1, 2, 0, 3)

    def one(h, xs):
        u_b, dt_b, b_b, c_b = xs
        ys = []
        for t in range(block):
            h = jnp.exp(dt_b[t][:, None, :] * a[None]) * h + (
                (dt_b[t] * u_b[t])[:, None, :] * b_b[t][:, :, None])
            ys.append(jnp.einsum("bnc,bn->bc", h, c_b[t]))
        return h, (jnp.stack(ys), h)

    h, (y, hs) = jax.lax.scan(
        one, h, (blocks(u), blocks(dt), blocks(b_m), blocks(c_m)))
    y = y.transpose(2, 0, 1, 3).reshape(bsz, nb * block, c)[:, :s]
    return y, h, hs
