"""Shared model layers: norms, rotary embeddings, token embedding."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    with jax.named_scope("norm"):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + eps)
                * scale.astype(jnp.float32)).astype(dtype)


def init_rms_norm(d: int, dtype=jnp.float32) -> jax.Array:
    return jnp.ones((d,), dtype)


def rope_freqs(hd: int, theta: float) -> jax.Array:
    """(hd/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding.

    x: (B, S, H, hd); positions: (B, S) int32.  Rotates pairs (even, odd).
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float,
    sections: tuple[float, ...] = (0.25, 0.375, 0.375),
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): three position streams (t, h, w) drive
    disjoint sections of the rotary dimensions.

    x: (B, S, H, hd); positions: (B, S, 3) int32.
    """
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(hd, theta)  # (half,)
    bounds = []
    acc = 0
    for frac in sections[:-1]:
        acc += int(round(frac * half))
        bounds.append(acc)
    # section id per rotary dim
    sec = jnp.zeros((half,), jnp.int32)
    for i, b in enumerate(bounds):
        sec = jnp.where(jnp.arange(half) >= b, i + 1, sec)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),  # (B, S, 3)
        jnp.broadcast_to(sec[None, None, :], positions.shape[:2] + (half,)),
        axis=-1,
    )  # (B, S, half): the position stream each rotary dim listens to
    ang = pos * freqs  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def init_embedding(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return jax.random.normal(key, (vocab, d), dtype) * (1.0 / d) ** 0.5
