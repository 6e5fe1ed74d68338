"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU via ``interpret=True`` — the kernel body runs in Python with
identical semantics.  ``interpret_default()`` picks interpret mode on the CPU
backend only; any other non-TPU backend is an error, never a silent
interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


#: VMEM the double-buffered count-array block of a decode kernel may take:
#: half of the 16 MiB a v5e kernel gets by default, leaving the rest for the
#: dequantized working tile, the resident hash bank and the output block.
#: Compiling for a v5e shows the boundary: the (L=128, R=16) block fits at
#: 8 MiB double-buffered and runs out of VMEM at 16 MiB, for every count dtype.
COUNT_BLOCK_VMEM_BYTES = 8 * 2**20

LANES = 128

#: Precision of every hash projection and count contraction, in the kernels
#: and their oracles alike.  At default precision a TPU contracts f32 operands
#: in one bf16 pass: projections near a bucket edge would hash elsewhere than
#: in the f32 oracle, and gathered counts would lose their low bits.  On the
#: CPU this changes nothing.
HASH_PRECISION = jax.lax.Precision.HIGHEST


def interpret_default() -> bool:
    """Compile kernels on TPU, interpret them on CPU, refuse anything else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; the default "
        f"JAX backend is {backend!r}")


def vocab_tile(block_rows: int, itemsize: int, v: int) -> int:
    """Vocab tile for a count array whose (rows, Vt) block is double-buffered.

    The largest multiple of 128 lanes that divides V (padded to 128) and
    keeps two ``block_rows × Vt × itemsize`` blocks inside
    :data:`COUNT_BLOCK_VMEM_BYTES`.  Dividing V matters: a tile that does not
    would pad (copy) the whole count array on every call.
    """
    vp = round_up(v, LANES)
    cap = COUNT_BLOCK_VMEM_BYTES // (2 * block_rows * itemsize)
    tile = max(LANES, min(cap, vp) // LANES * LANES)
    while vp % tile:
        tile -= LANES
    return tile


def mesh_axis_size(mesh, name: str) -> int:
    """Size of mesh axis ``name``; 1 when ``mesh`` is None or lacks the axis."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def pad_axis(x: jnp.ndarray, axis: int, multiple: int, value=0) -> jnp.ndarray:
    """Pad ``axis`` of ``x`` up to the next multiple (TPU tile alignment)."""
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def select_tenant_rows(per_tenant: jnp.ndarray,
                       tenant_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-slot tenant gather: ``out[b] = per_tenant[tenant_ids[b], b]``.

    ``per_tenant`` is a (T, B, …) stack of full-batch outputs, one per
    resident tenant, each computed by the *unmodified* single-tenant code
    path; ``tenant_ids`` is the (B,) int32 slot→tenant binding.  The gather
    is arithmetic-free (``take_along_axis`` moves bits, it never re-reduces),
    so row ``b`` of the result is bitwise identical to running tenant
    ``tenant_ids[b]``'s head alone — the per-slot head binding costs no
    parity (DESIGN.md §14).
    """
    idx = tenant_ids.reshape((1, -1) + (1,) * (per_tenant.ndim - 2))
    idx = idx.astype(jnp.int32)
    return jnp.take_along_axis(per_tenant, idx, axis=0)[0]


def pack_int4_rows(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4-valued int8 rows pairwise along axis 0: (N, …) → (⌈N/2⌉, …).

    Byte ``i`` holds row ``2i`` in its low nibble and row ``2i+1`` in its
    high nibble (odd N gets a zero pad row).  Packing along the *leading*
    axis — not the trailing lane axis — keeps the minor (V) dimension of the
    sketch count arrays intact, so the quantized decode kernels tile V
    exactly like the f32 kernels and the true row count is always
    recoverable from the (B, L) index / (L, K, d') hash-bank shapes (no
    ambiguity at odd V; DESIGN.md §12).

    Args:
      q: int8 array with values in [-8, 7]; axis 0 is the packed axis.

    Returns:
      int8 array of packed bytes, shape ``(⌈N/2⌉, …)``.
    """
    if q.shape[0] % 2:
        q = pad_axis(q, 0, 2)
    lo = q[0::2].astype(jnp.uint8) & jnp.uint8(0x0F)
    hi = q[1::2].astype(jnp.uint8) & jnp.uint8(0x0F)
    return jax.lax.bitcast_convert_type(
        lo | (hi << jnp.uint8(4)), jnp.int8)


def unpack_int4_rows(packed: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """Inverse of :func:`pack_int4_rows`: (⌈N/2⌉, …) bytes → (n_rows, …) int32.

    Widens the bytes to int32 first (Mosaic has no int8 shifts), then
    sign-extends each nibble with the (x << 28) >> 28 arithmetic-shift trick
    and interleaves low/high back to row order; ``n_rows`` slices off the pad
    row of an odd-N pack.  The values are exact, returned as int32.  Cheap
    enough to run inside a kernel body per tile — the dequantized values
    never touch HBM.
    """
    x = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(x, 28), 28)
    hi = jnp.right_shift(x, 4)
    rows = jnp.stack([lo, hi], axis=1).reshape(-1, *packed.shape[1:])
    return rows[:n_rows]
