"""Sketched LM-head Pallas kernel — per-class RACE estimate for decode.

This is the framework integration of the paper's technique (DESIGN.md §4):
at decode time the dense d_model×V logit matmul (2·d·V FLOPs/token) is
replaced by an L-row sketch lookup shared across all V classes
(L·V adds/token; L ≪ 2·d).

The class-sharing layout (L, R, V) turns the per-class gather into a single
(1, L·R)·(L·R, Vt) one-hot contraction per vocab tile — an MXU matvec whose
left operand has exactly L nonzeros.  VMEM tiling:

  grid = (B / Bt, V / Vt)
  idx:    (Bt, L)       VMEM
  sketch: (L, R, Vt)    VMEM  — vocab-tiled; Vt defaults to
                               ``common.vocab_tile``, the widest tile whose
                               double-buffered block fits the VMEM budget
                               (512 lanes for f32 at L=128, R=16).
  out:    (Bt, Vt)      VMEM

Quantized storage (DESIGN.md §12): with ``quant`` set, HBM holds the count
array as int8 (per-row symmetric) or packed int4 (two L-rows per byte along
axis 0) plus tiny (L, R) f32 scales.  Dequantization never round-trips
through HBM — each VMEM tile is consumed directly by folding the row scales
into the one-hot left operand:

  out = (onehot ⊙ scale) · q_f32        (term-wise equal to scale·q gather)

so the f32 counts exist only as MXU operands; HBM traffic stays at the
int8/int4 byte width (the whole point of the bytes_ratio claim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (HASH_PRECISION, interpret_default,
                                  pad_axis, unpack_int4_rows, vocab_tile)


def _sketch_head_kernel(idx_ref, sketch_ref, *rest, quant=None):
    out_ref = rest[-1]
    idx = idx_ref[...]          # (Bt, L)
    vals = sketch_ref[...]      # (L, R, Vt) f32 | (Lstore, R, Vt) int8
    bt, l = idx.shape

    if quant is not None:
        scale = rest[0][...]    # (L, R) f32
        if quant == "int4":
            vals = unpack_int4_rows(vals, l)      # nibbles → (L, R, Vt) int32
        vals = vals.astype(jnp.float32)
    r, vt = vals.shape[1], vals.shape[2]

    # One-hot over (L, R) flattened: (Bt, L·R) with exactly L nonzeros per
    # row.  Row scales fold into the one-hot (values {0, scale[l, r]}), so
    # each MXU term is exactly scale·q — bitwise the ref dequant product.
    iota_r = jax.lax.broadcasted_iota(jnp.int32, (bt, l, r), 2)
    onehot = (iota_r == idx[:, :, None]).astype(jnp.float32)
    if quant is not None:
        onehot = onehot * scale[None, :, :]
    # MXU: (Bt, L·R) @ (L·R, Vt) — the row-mean over L reads.
    out_ref[...] = jax.lax.dot_general(
        onehot.reshape(bt, l * r), vals.reshape(l * r, vt),
        (((1,), (0,)), ((), ())), precision=HASH_PRECISION,
        preferred_element_type=jnp.float32,
    ) * (1.0 / l)


def sketch_head_pallas(
    sketch: jnp.ndarray,     # (L, R, V) f32 | (Lstore, R, V) int8 (quant)
    idx: jnp.ndarray,        # (B, L) int32
    scale: jnp.ndarray | None = None,   # (L, R) f32 when quantized
    *,
    quant: str | None = None,           # None | "int8" | "int4"
    block_b: int = 8,
    block_v: int | None = None,         # None: common.vocab_tile
    interpret: bool | None = None,
) -> jnp.ndarray:            # (B, V)
    if interpret is None:
        interpret = interpret_default()
    l = idx.shape[1]
    l_store, r, v = sketch.shape
    n_batch = idx.shape[0]
    if block_v is None:
        block_v = vocab_tile(l_store * r, sketch.dtype.itemsize, v)

    idxp = pad_axis(idx, 0, block_b)
    sketchp = pad_axis(sketch, 2, block_v)
    bp, vp = idxp.shape[0], sketchp.shape[2]
    grid = (bp // block_b, vp // block_v)

    in_specs = [
        pl.BlockSpec((block_b, l), lambda i, j: (i, 0)),
        pl.BlockSpec((l_store, r, block_v), lambda i, j: (0, 0, j)),
    ]
    operands = [idxp, sketchp]
    if quant is not None:
        in_specs.append(pl.BlockSpec((l, r), lambda i, j: (0, 0)))
        operands.append(scale)

    out = pl.pallas_call(
        functools.partial(_sketch_head_kernel, quant=quant),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, vp), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:n_batch, :v]
