"""Pure-jnp oracle for the fused sketched-decode kernel.

Composes the two existing oracles around the asymmetric transform:

    q      = hidden @ proj                      # (B, d')
    idx    = lsh_hash_ref(q, w, b)              # (B, L)
    logits = sketch_head_ref(sketch, idx)       # (B, V)

The fused kernel must match this composition exactly on the indices (same
integer mix) and within float tolerance on the logits.  Quantized storage
passes ``scale``/``quant`` straight through to the sketch-head oracle,
which materializes the dequantized f32 array (oracle only — the kernel
keeps dequant in-register, DESIGN.md §12).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import HASH_PRECISION
from repro.kernels.lsh_hash.ref import lsh_hash_ref
from repro.kernels.sketch_head.ref import sketch_head_ref


def fused_decode_ref(
    hidden: jnp.ndarray,     # (B, d) f32/bf16
    proj: jnp.ndarray,       # (d, d') f32
    w: jnp.ndarray,          # (L, K, d') f32
    b: jnp.ndarray,          # (L, K) f32
    sketch: jnp.ndarray,     # (L, R, V) f32 | (Lstore, R, V) int8 (quant)
    bandwidth: float,
    n_buckets: int,
    row_salt: jnp.ndarray | None = None,   # (L,) uint32 global-row fold salts
    scale: jnp.ndarray | None = None,      # (L, R) f32 when quantized
    quant: str | None = None,              # None | "int8" | "int4"
) -> jnp.ndarray:            # (B, V)
    q = jnp.matmul(hidden.astype(jnp.float32), proj,
                   precision=HASH_PRECISION)
    idx = lsh_hash_ref(q, w, b, bandwidth, n_buckets, row_salt=row_salt)
    return sketch_head_ref(sketch, idx, scale, quant)
