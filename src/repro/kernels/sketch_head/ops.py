"""Public wrapper for the sketched LM head (registry-dispatched).

``mesh=`` enables the sharded decode path (DESIGN.md §9): the (L, R, V)
count arrays are partitioned over the mesh's ``model`` axis on the
repetition axis L, every shard runs the same kernel on its local rows, and
the per-shard partial means finish with a single ``psum`` of the (B, V)
logits.  Falls back to the single-device path when L does not divide the
``model`` axis size.

Quantized storage (``quant="int8"|"int4"``, DESIGN.md §12) threads the
(L, R) f32 ``scale`` alongside the integer count array; under the mesh the
scales partition with their rows (``P("model", None)``).  int4 packs two
L-rows per byte, so its storage axis is ⌈L/2⌉ — the sharded path
additionally requires shard boundaries to land on byte boundaries
(L/msize even) and falls back to the replicated path otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import registry
from repro.kernels.common import mesh_axis_size, select_tenant_rows
from repro.kernels.sketch_head.kernel import sketch_head_pallas
from repro.kernels.sketch_head.ref import sketch_head_ref


@registry.register("sketch_head", "pallas")
@partial(jax.jit, static_argnames=("quant", "block_b", "block_v"))
def _pallas(sketch, idx, scale=None, *, quant=None, block_b, block_v):
    return sketch_head_pallas(sketch, idx, scale, quant=quant,
                              block_b=block_b, block_v=block_v)


@registry.register("sketch_head", "ref")
@partial(jax.jit, static_argnames=("quant", "block_b", "block_v"))
def _ref(sketch, idx, scale=None, *, quant=None, block_b, block_v):
    del block_b, block_v  # tiling is a pallas concern
    return sketch_head_ref(sketch, idx, scale, quant)


def sketch_head_logits(
    sketch: jnp.ndarray,   # (L, R, V) f32 | (Lstore, R, V) int8 when quant
    idx: jnp.ndarray,      # (B, L)
    *,
    scale: Optional[jnp.ndarray] = None,   # (L, R) f32 when quantized
    quant: Optional[str] = None,           # None | "int8" | "int4"
    block_b: int = 8,
    block_v: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    backend: Optional[str] = None,
    mesh=None,
    tenant_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Estimate (B, V) logits from precomputed bucket indices.

    Args:
      sketch: the per-class RACE count arrays — (L, R, V) f32, or for
        ``quant`` the int8 carrier ((L, R, V) int8 / (⌈L/2⌉, R, V) packed
        int4 bytes).
      idx: (B, L) int32 bucket indices from ``lsh_hash``.
      scale: (L, R) f32 per-row dequantization scales (required iff
        ``quant`` is set).
      quant: ``None`` (f32 counts), ``"int8"`` or ``"int4"`` — static.
      block_b / block_v: pallas VMEM tile sizes (``block_v=None`` sizes the
        vocab tile from the VMEM budget, ``common.vocab_tile``).
      use_pallas: deprecated pallas/ref switch (prefer ``backend``).
      backend: kernel registry backend (``"pallas"`` / ``"ref"``); ``None``
        resolves through the registry default.
      mesh: a ``jax.sharding.Mesh`` with a ``model`` axis to run the
        row-sharded psum path; ``None`` (default) is the single-device path.
      tenant_ids: (B,) int32 per-slot tenant indices for the multi-tenant
        path (DESIGN.md §14).  When set, ``sketch`` is (T, L, R, V),
        ``scale`` (T, L, R), and ``idx`` (T, B, L) — each tenant's own hash
        bank produced the indices, so the stack carries one full-batch
        index tensor per tenant.  Every tenant evaluates through this same
        single-tenant path (shard_map psum included) and row ``b`` is
        selected from tenant ``tenant_ids[b]``'s stack arithmetic-free.

    Returns:
      (B, V) f32 logit estimates (the row-mean over L sketch reads).
    """
    if (scale is None) != (quant is None):
        raise ValueError("quant and scale must be passed together "
                         f"(quant={quant!r}, scale is "
                         f"{'None' if scale is None else 'set'})")
    if tenant_ids is not None:
        if idx.ndim != 3 or idx.shape[0] != sketch.shape[0]:
            raise ValueError(
                f"tenant_ids needs a (T, B, L) index stack matching the "
                f"(T, …) sketch bank; got idx {idx.shape} vs sketch "
                f"{sketch.shape}")
        per_tenant = jnp.stack([
            sketch_head_logits(
                sketch[t], idx[t],
                scale=None if scale is None else scale[t], quant=quant,
                block_b=block_b, block_v=block_v, use_pallas=use_pallas,
                backend=backend, mesh=mesh)
            for t in range(sketch.shape[0])])
        return select_tenant_rows(per_tenant, tenant_ids)
    impl = registry.resolve("sketch_head", backend, use_pallas)
    l = idx.shape[1]
    l_store = sketch.shape[0]
    msize = mesh_axis_size(mesh, "model")
    shardable = msize > 1 and l % msize == 0 and l_store % msize == 0
    if quant == "int4":
        # Byte-aligned shards only: no pad row, even true rows per shard.
        shardable = shardable and 2 * l_store == l
    if shardable:
        l_shard = l // msize
        # Keep the batch sharded over data when it divides (decode caches
        # already are): each device reads only its rows' indices and the
        # psum moves (B/d, V), not (B, V).
        dsize = mesh_axis_size(mesh, "data")
        bspec = "data" if dsize > 1 and idx.shape[0] % dsize == 0 else None

        if quant is None:
            def local(sk, ix):
                part = impl(sk, ix, block_b=block_b, block_v=block_v)
                return jax.lax.psum(part * (l_shard / l), "model")
            in_specs = (P("model", None, None), P(bspec, "model"))
            operands = (sketch, idx)
        else:
            def local(sk, ix, sc):
                part = impl(sk, ix, sc, quant=quant,
                            block_b=block_b, block_v=block_v)
                return jax.lax.psum(part * (l_shard / l), "model")
            in_specs = (P("model", None, None), P(bspec, "model"),
                        P("model", None))
            operands = (sketch, idx, scale)

        # check_vma=False: pallas_call has no replication rule; the psum
        # makes the output replicated over model by construction.
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=in_specs,
            out_specs=P(bspec, None), check_vma=False)(*operands)
    return impl(sketch, idx, scale, quant=quant,
                block_b=block_b, block_v=block_v)
