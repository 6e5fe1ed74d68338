"""Fused sketched-decode Pallas kernel: projection → hash → sketch gather.

The two-kernel decode path (repro.kernels.lsh_hash then
repro.kernels.sketch_head) materializes the ``(B, L)`` int32 bucket-index
tensor in HBM between the calls; at serving batch sizes that round trip —
write + re-read of B·L·4 bytes plus a kernel-launch boundary — is pure
overhead on a path that is otherwise a handful of tiny matmuls.  This kernel
fuses the whole sketched head (DESIGN.md §4) into a single ``pallas_call``:

  1. asymmetric transform   q = h · A            (MXU, (Bt, d)·(d, d'))
  2. p-stable hash          proj = q · Wᵀ + b    (MXU, (Bt, d')·(d', L·K))
                            idx  = mix(floor(proj / r))        (VPU)
  3. shared-index gather    logits = onehot(idx) · S / L       (MXU)

Tiling (DESIGN.md §3):

  grid = (B / Bt, V / Vt)
  h:      (Bt, d)       VMEM
  A:      (d, d')       VMEM  (whole transform resident)
  w:      (L·K, d')     VMEM  (whole hash bank resident)
  b:      (1, L·K)      VMEM
  sketch: (L, R, Vt)    VMEM  — vocab-tiled exactly like sketch_head
  out:    (Bt, Vt)      VMEM

Vt defaults to ``common.vocab_tile`` for the stored count block: the widest
tile whose double-buffered (L, R, Vt) block fits the VMEM budget — 512 lanes
for f32 counts at L=128, R=16, 2048 for int8, 4096 for packed int4.

Steps 1–2 are recomputed per vocab tile: they cost Bt·d·d' + Bt·d'·L·K
MXU FLOPs — orders of magnitude below the step-3 gather contraction — and
recomputation is what lets the index tensor live entirely in registers/VMEM
instead of HBM.  Bit-exact index parity with the two-kernel path is asserted
in tests (same Carter–Wegman mix, same golden-ratio row salt).

Quantized storage (``quant``, DESIGN.md §12): HBM holds the sketch as int8
or packed int4 (two L-rows per byte on axis 0) plus (L, R) f32 scales; the
step-3 gather folds the scales into the one-hot left operand so dequantized
f32 counts exist only as MXU operands, never in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (HASH_PRECISION, interpret_default,
                                  pad_axis, unpack_int4_rows, vocab_tile)
from repro.kernels.lsh_hash.kernel import _mix_codes


def _fused_decode_kernel(h_ref, a_ref, w_ref, b_ref, salt_ref, sketch_ref,
                         *rest, k: int, n_buckets: int, bandwidth: float,
                         n_rows: int, quant: str | None = None):
    out_ref = rest[-1]
    # Cast up front so bf16 hiddens follow the oracle's f32 arithmetic.
    h = h_ref[...].astype(jnp.float32)    # (Bt, d)
    a = a_ref[...]                        # (d, d')
    w = w_ref[...]                        # (L*K, d')
    b = b_ref[...]                        # (1, L*K)
    salt = salt_ref[...][0]               # (L,) uint32 global-row fold salts
    vals = sketch_ref[...]                # (L, R, Vt) f32 | (Lstore, R, Vt) i8
    bt = h.shape[0]
    l = n_rows

    # 1. asymmetric transform (MXU).
    q = jax.lax.dot_general(
        h, a, (((1,), (0,)), ((), ())), precision=HASH_PRECISION,
        preferred_element_type=jnp.float32,
    )                                     # (Bt, d')
    # 2. hash projection (MXU) + quantize + K-fold rehash (VPU).
    proj = jax.lax.dot_general(
        q, w, (((1,), (1,)), ((), ())), precision=HASH_PRECISION,
        preferred_element_type=jnp.float32,
    )                                     # (Bt, L*K)
    codes = jnp.floor((proj + b) / bandwidth).astype(jnp.int32).astype(jnp.uint32)
    codes = codes.reshape(bt, n_rows, k)
    idx = _mix_codes(codes, k, n_buckets, salt=salt)  # (Bt, L)

    # 3. shared-index gather as a one-hot MXU contraction (row-mean over L).
    if quant is not None:
        scale = rest[0][...]              # (L, R) f32
        if quant == "int4":
            vals = unpack_int4_rows(vals, l)
        vals = vals.astype(jnp.float32)
    r, vt = vals.shape[1], vals.shape[2]
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (bt, l, r), 2)
    onehot = (iota_r == idx[:, :, None]).astype(jnp.float32)
    if quant is not None:
        # Row scales fold into the one-hot: each MXU term is exactly
        # scale·q, term-wise equal to the ref dequant product.
        onehot = onehot * scale[None, :, :]
    out_ref[...] = jax.lax.dot_general(
        onehot.reshape(bt, l * r), vals.reshape(l * r, vt),
        (((1,), (0,)), ((), ())), precision=HASH_PRECISION,
        preferred_element_type=jnp.float32,
    ) * (1.0 / l)


def fused_decode_pallas(
    hidden: jnp.ndarray,     # (B, d) f32/bf16 — final backbone hiddens
    proj: jnp.ndarray,       # (d, d') f32 — asymmetric transform A
    w: jnp.ndarray,          # (L, K, d') f32 — hash bank
    b: jnp.ndarray,          # (L, K) f32 — hash offsets
    sketch: jnp.ndarray,     # (L, R, V) f32 | (Lstore, R, V) int8 (quant)
    *,
    bandwidth: float,
    n_buckets: int,
    scale: jnp.ndarray | None = None,      # (L, R) f32 when quantized
    quant: str | None = None,              # None | "int8" | "int4"
    block_b: int = 8,
    block_v: int | None = None,            # None: common.vocab_tile
    interpret: bool | None = None,
    row_salt: jnp.ndarray | None = None,   # (L,) uint32 global-row fold salts
) -> jnp.ndarray:            # (B, V) f32 logits
    if interpret is None:
        interpret = interpret_default()
    n_batch, d = hidden.shape
    d_proj = proj.shape[1]
    n_rows, k, _ = w.shape
    l_store, r, v = sketch.shape
    if block_v is None:
        block_v = vocab_tile(l_store * r, sketch.dtype.itemsize, v)

    w2 = w.reshape(n_rows * k, d_proj)
    b2 = b.reshape(1, n_rows * k)
    if row_salt is None:
        from repro.core.lsh import row_salts
        row_salt = row_salts(n_rows)
    salt2 = row_salt.reshape(1, n_rows)

    hp = pad_axis(hidden, 0, block_b)
    sketchp = pad_axis(sketch, 2, block_v)
    bp, vp = hp.shape[0], sketchp.shape[2]
    grid = (bp // block_b, vp // block_v)

    in_specs = [
        pl.BlockSpec((block_b, d), lambda i, j: (i, 0)),
        pl.BlockSpec((d, d_proj), lambda i, j: (0, 0)),
        pl.BlockSpec((n_rows * k, d_proj), lambda i, j: (0, 0)),
        pl.BlockSpec((1, n_rows * k), lambda i, j: (0, 0)),
        pl.BlockSpec((1, n_rows), lambda i, j: (0, 0)),
        pl.BlockSpec((l_store, r, block_v), lambda i, j: (0, 0, j)),
    ]
    operands = [hp, proj, w2, b2, salt2, sketchp]
    if quant is not None:
        in_specs.append(pl.BlockSpec((n_rows, r), lambda i, j: (0, 0)))
        operands.append(scale)

    out = pl.pallas_call(
        functools.partial(
            _fused_decode_kernel, k=k, n_buckets=n_buckets,
            bandwidth=bandwidth, n_rows=n_rows, quant=quant,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, vp), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:n_batch, :v]
