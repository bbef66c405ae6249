"""Shared primitive layers: RMSNorm, RoPE, SwiGLU MLP, embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope_freqs(head_dim: int, theta: float, yarn=None) -> jax.Array:
    """(head_dim//2,) inverse frequencies; with ``yarn`` (a
    :class:`~repro.configs.base.YarnConfig`), YaRN's: interpolated by
    ``factor`` below the ramp between ``beta_fast`` and ``beta_slow``
    rotations, extrapolated above it (arXiv:2309.00071 §3.2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if yarn is None:
        return inv

    def dim_of(rotations):      # the dimension that turns ``rotations`` times
        return head_dim * math.log(
            yarn.original_max_position / (rotations * 2 * math.pi)) / (
                2 * math.log(theta))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., S, H, hd), positions: broadcastable to (..., S).  With
    ``yarn``, YaRN's frequencies and cos/sin scaled by its
    ``attention_factor``."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta, yarn)                 # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv        # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                  # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def embed(tokens: jax.Array, table: jax.Array, scale: bool) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.sqrt(jnp.asarray(table.shape[1], x.dtype))
    return x


def unembed(x: jax.Array, table_or_head: jax.Array, tied: bool) -> jax.Array:
    if tied:   # table: (V, D)
        return jnp.einsum("...d,vd->...v", x, table_or_head)
    return jnp.einsum("...d,dv->...v", x, table_or_head)
