"""Fused LSH-hash Pallas kernel: projection (MXU) + floor + K-fold rehash.

TPU mapping (DESIGN.md §3): the edge-oriented 'add/sub only' sparse hash of
the paper becomes a dense bf16/f32 matmul on the MXU — a (Bt, d)·(d, L·K)
tile — followed by VPU-side quantization and integer mixing, all inside one
kernel so the (B, L·K) projection never round-trips to HBM.

Tiling:
  grid = (B / Bt,)
  x:    (Bt, d)    VMEM  block
  w:    (L·K, d)   VMEM  (whole bank resident; L·K·d ≤ ~6k·128 floats ≈ 3 MB)
  b:    (1, L·K)   VMEM
  out:  (Bt, L)    VMEM

The K sub-hash codes of each row are folded with the same Carter–Wegman-style
integer mix as repro.core.lsh._fold_subhashes (bit-exact parity is asserted
in tests against ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (HASH_PRECISION, interpret_default,
                                  pad_axis, round_up)

_MIX_A = 1103515245


def _mix_codes(codes: jnp.ndarray, k: int, n_buckets: int,
               salt: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fold (..., L, K) uint32 codes → (..., L) indices. Mirrors core.lsh
    bit-for-bit, including the golden-ratio per-row salt.  ``salt`` ((L,)
    uint32) overrides the local-row default — required when the caller only
    holds a row *slice* of the bank (the sharded fused-decode path), since
    the salt is a function of the global row index."""
    if salt is None:
        salt = (jax.lax.broadcasted_iota(jnp.uint32, codes.shape[:-1],
                                         codes.ndim - 2)
                * jnp.uint32(0x9E3779B9))
    acc = jnp.broadcast_to(salt, codes.shape[:-1]).astype(jnp.uint32)
    for i in range(k):
        acc = acc * jnp.uint32(_MIX_A & 0xFFFFFFFF) + codes[..., i] + jnp.uint32(i * 97 + 13)
        acc = acc ^ (acc >> 16)
        acc = acc * jnp.uint32(0x45D9F3B)
        acc = acc ^ (acc >> 16)
    return (acc % jnp.uint32(n_buckets)).astype(jnp.int32)


def _lsh_hash_kernel(x_ref, w_ref, b_ref, out_ref, *, k: int, n_buckets: int,
                     bandwidth: float, n_rows: int):
    x = x_ref[...]                       # (Bt, d)
    w = w_ref[...]                       # (L*K, d)
    b = b_ref[...]                       # (1, L*K)
    # MXU: (Bt, d) @ (d, L*K)
    proj = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=HASH_PRECISION,
        preferred_element_type=jnp.float32,
    )                                    # (Bt, L*K)
    codes = jnp.floor((proj + b) / bandwidth).astype(jnp.int32).astype(jnp.uint32)
    codes = codes.reshape(codes.shape[0], n_rows, k)
    out_ref[...] = _mix_codes(codes, k, n_buckets)


def lsh_hash_pallas(
    x: jnp.ndarray,          # (B, d) f32
    w: jnp.ndarray,          # (L, K, d) f32
    b: jnp.ndarray,          # (L, K) f32
    *,
    bandwidth: float,
    n_buckets: int,
    block_b: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:            # (B, L) int32
    if interpret is None:
        interpret = interpret_default()
    n_batch, d = x.shape
    n_rows, k, _ = w.shape

    w2 = w.reshape(n_rows * k, d)
    b2 = b.reshape(1, n_rows * k)

    xp = pad_axis(x, 0, block_b)
    bp = xp.shape[0]
    grid = (bp // block_b,)

    out = pl.pallas_call(
        functools.partial(
            _lsh_hash_kernel, k=k, n_buckets=n_buckets,
            bandwidth=bandwidth, n_rows=n_rows,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((n_rows * k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, n_rows * k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_rows), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, n_rows), jnp.int32),
        interpret=interpret,
    )(xp, w2, b2)
    return out[:n_batch]
