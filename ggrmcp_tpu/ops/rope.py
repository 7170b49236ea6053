"""Rotary position embeddings (RoPE), functional and jit-friendly.

Used by the Llama-family models. Frequencies are computed on the fly
from static shapes (cheap, fuses into the surrounding jit) so no state
is carried; positions are explicit so the same code serves prefill
(positions 0..S) and decode (a single absolute position per sequence).
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp


def rope_freqs(
    head_dim: int,
    theta: float = 10000.0,
    scaling: Optional[tuple] = None,
) -> jnp.ndarray:
    """Inverse frequencies for half the head dim: [head_dim // 2].

    `scaling`: optional Llama-3-style long-context frequency scaling as
    a hashable 4-tuple (factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings) — tuple, not dict, so model
    configs carrying it stay usable as jit static args.
    Long-wavelength (low-freq) components are slowed by `factor`, short
    wavelengths untouched, and a linear ramp blends between the two
    cutoffs — the published llama3 `rope_type` rule that Llama-3.1+
    checkpoints require for correct logits.

    A tuple that starts with "yarn" is the YaRN rule instead (`yarn`
    below): ("yarn", factor, original_max_position_embeddings,
    beta_fast, beta_slow).
    """
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta**exponent)
    if scaling and scaling[0] == "yarn":
        return yarn(freqs, head_dim, theta, *scaling[1:])
    if scaling:
        factor, low, high, orig = (float(v) for v in scaling)
        wavelen = 2.0 * math.pi / freqs
        ramp = (orig / wavelen - low) / (high - low)  # <0 long, >1 short
        smooth = jnp.clip(ramp, 0.0, 1.0)
        freqs = (1.0 - smooth) * freqs / factor + smooth * freqs
    return freqs


def yarn(freqs, head_dim, theta, factor, orig, beta_fast, beta_slow):
    """YaRN frequencies (the published `rope_scaling.type: "yarn"`):
    pair i keeps `f_i` below the correction dimension that `beta_fast`
    rotations over the original context give, takes `f_i / factor`
    above the one `beta_slow` gives, and a linear ramp blends between
    them. cos / sin stay unscaled (`mscale` equal to `mscale_all_dim`);
    the softmax scale carries the rest (`yarn_softmax_gain`)."""

    def correction(rotations):
        return head_dim * math.log(orig / (rotations * 2.0 * math.pi)) / (
            2.0 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_softmax_gain(scaling: Optional[tuple]) -> float:
    """What a YaRN model multiplies its softmax scale by:
    `(0.1 ln(factor) + 1) ** 2` with `mscale_all_dim` 1; 1.0 without
    YaRN."""
    if not scaling or scaling[0] != "yarn" or scaling[1] <= 1:
        return 1.0
    return (0.1 * math.log(scaling[1]) + 1.0) ** 2


def apply_rope(
    x: jnp.ndarray,  # [..., seq, num_heads, head_dim]
    positions: jnp.ndarray,  # [..., seq]
    theta: float = 10000.0,
    scaling: Optional[tuple] = None,
) -> jnp.ndarray:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) by position-dependent
    angles. Computed in float32 and cast back (bf16-safe)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, scaling)  # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    x_f32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x_f32, 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)
