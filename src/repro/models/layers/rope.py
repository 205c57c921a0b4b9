"""Rotary position embeddings, including dual-theta (Gemma-3 local/global)
and YaRN scaling (DeepSeek-V2)."""
from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, ``0.1 * mscale * ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, orig_max: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """DeepSeek-V2's YaRN frequencies: the plain ones (``inv_extra``) kept
    for the high-frequency pairs, divided by ``factor`` (``inv_inter``) for
    the low ones, with a linear ramp between the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over ``orig_max`` positions."""
    def corr(n):
        return dim * math.log(orig_max / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = extra / factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    m = 1.0 - ramp
    return inter * (1.0 - m) + extra * m


def rope_tables(positions: jnp.ndarray, dim: int, theta: float, yarn=None):
    """cos/sin tables for given positions. positions: (...,) int; dim even.
    ``yarn``: (factor, original max positions, beta_fast, beta_slow), or
    None for plain rope. The tables are not rescaled: YaRN's
    ``mscale / mscale_all_dim`` is 1 where the two are equal, as in
    DeepSeek-V2."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    else:
        inv = yarn_inv_freq(dim, theta, *yarn)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (split-half convention).

    x: (..., S, H, D). cos/sin: (S, D/2) shared across batch, or
    (B, S, D/2) per-example (decode). A head axis is inserted at -2 and
    leading axes broadcast.
    """
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = jnp.expand_dims(cos, -2)  # (..., S, 1, D/2)
    s = jnp.expand_dims(sin, -2)
    while c.ndim < x1.ndim:
        c, s = c[None], s[None]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)
