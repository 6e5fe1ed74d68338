"""Plain building blocks of the references: f32 math at the highest matmul
precision, and the int8 and fp8 rounding the controls compute in.

Nothing here imports the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fake_int8(x, axis):
    """Symmetric int8 rounding of ``x`` with one scale per slice along
    ``axis`` (the reduction axis of the matmul it feeds)."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def fake_fp8(x, axis):
    """Rounding of ``x`` to fp8 (e4m3) with one scale per slice along
    ``axis`` that maps its largest magnitude to e4m3's largest, 448."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUND = {"int8": fake_int8, "fp8": fake_fp8}


def mm(x, w, quant=None):
    """``x @ w`` in f32 (``w`` is (in, out)); with ``quant`` ("int8" or
    "fp8") both operands are first rounded to it: activations per row,
    weights per output column."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = ROUND[quant](x, -1)
        w = ROUND[quant](w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def rms_norm(x, scale, eps):
    """x / rms(x) * (1 + scale), in f32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def normal(key, shape, std, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
