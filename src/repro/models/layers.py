"""Shared building blocks: norms, RoPE, embeddings, dense FFN."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: (..., seq, n_heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def hold_parked(active, new: jnp.ndarray, old: jnp.ndarray) -> jnp.ndarray:
    """``new`` where the (B,) ``active`` mask is set, else ``old``.

    A layer applies it to exactly what its decode step writes (one K/V entry
    per row, or the new recurrent state), so a parked slot's cache stays
    bitwise unchanged without a pass over the whole pool.  ``active=None``
    writes every row."""
    if active is None:
        return new
    with jax.named_scope("cache_mask"):
        return jnp.where(active.reshape((-1,) + (1,) * (new.ndim - 1)),
                         new, old)


def cache_write(buf: jnp.ndarray, new: jnp.ndarray, pos,
                active=None) -> jnp.ndarray:
    """Write decode entries ``new`` (B, S, ...) into a (B, T, ...) cache.

    ``pos`` is one offset shared by every row, or (B,) per-row positions
    (continuous batching, S == 1).  Rows clear in the (B,) ``active`` mask
    keep what ``buf`` held at the written positions (``hold_parked``)."""
    new = new.astype(buf.dtype)
    if jnp.ndim(pos) == 1:
        bi = jnp.arange(buf.shape[0])
        new = new[:, 0]
        if active is not None:
            new = hold_parked(active, new, buf[bi, pos])
        return buf.at[bi, pos].set(new)
    at = (0, pos) + (0,) * (buf.ndim - 2)
    if active is not None:
        new = hold_parked(active, new,
                          jax.lax.dynamic_slice(buf, at, new.shape))
    return jax.lax.dynamic_update_slice(buf, new, at)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 style logit soft-capping: cap·tanh(x/cap)."""
    return cap * jnp.tanh(x / cap)


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU FFN: down( silu(x·gate) ⊙ (x·up) )."""
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", g * u, w_down)


def embed(tokens: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Logits via the (possibly tied) output table: (..., d) → (..., V)."""
    return jnp.einsum("...d,vd->...v", x, table)


def init_dense(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(jnp.bfloat16)
