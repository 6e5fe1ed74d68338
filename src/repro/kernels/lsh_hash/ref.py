"""Pure-jnp oracle for the fused LSH hash kernel.

Computes, for a batch of queries, the (B, L) int32 bucket indices of the
concatenated p-stable LSH bank:

    proj   = x @ w^T + b          # (B, L·K)
    codes  = floor(proj / r)      # int32 sub-hash codes
    idx    = fold_K(codes) mod R  # universal rehash of the K codes per row

Must match repro.core.lsh.L2LSH.hash bit-for-bit (same mixing constants).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.lsh import _fold_subhashes
from repro.kernels.common import HASH_PRECISION


def lsh_hash_ref(
    x: jnp.ndarray,      # (B, d) float32
    w: jnp.ndarray,      # (L, K, d) float32
    b: jnp.ndarray,      # (L, K) float32
    bandwidth: float,
    n_buckets: int,
    row_salt: jnp.ndarray | None = None,  # (L,) uint32 global-row fold salts
) -> jnp.ndarray:        # (B, L) int32
    proj = jnp.einsum("bd,lkd->blk", x, w,
                      precision=HASH_PRECISION)
    codes = jnp.floor((proj + b) / bandwidth).astype(jnp.int32)
    return _fold_subhashes(codes, n_buckets, salt=row_salt)
