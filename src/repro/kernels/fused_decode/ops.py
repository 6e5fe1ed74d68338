"""Public wrapper for the fused sketched-decode kernel (registry-dispatched).

``mesh=`` enables the sharded decode path (DESIGN.md §9): hash params and
count arrays are partitioned over the mesh's ``model`` axis on the
repetition axis L, each shard runs the whole fused kernel (transform →
hash → gather) on its local L/m repetitions, and the per-shard partial
means finish with a single ``psum`` of the (B, V) logits — one collective
per decode step.  Falls back to the single-device path when L does not
divide the ``model`` axis size.

Quantized storage (``quant="int8"|"int4"``, DESIGN.md §12) threads the
(L, R) f32 ``scale`` alongside the integer count array; under the mesh the
scales partition with their rows (``P("model", None)``).  int4 packs two
L-rows per byte on axis 0, so the sharded path additionally requires shard
boundaries on byte boundaries (L/msize even) and falls back otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import registry
from repro.kernels.common import mesh_axis_size, select_tenant_rows
from repro.kernels.fused_decode.kernel import fused_decode_pallas
from repro.kernels.fused_decode.ref import fused_decode_ref


@registry.register("fused_decode", "pallas")
@partial(jax.jit, static_argnames=("bandwidth", "n_buckets", "quant",
                                   "block_b", "block_v"))
def _pallas(hidden, proj, w, b, sketch, scale=None, *, bandwidth, n_buckets,
            quant=None, block_b, block_v, row_salt=None):
    return fused_decode_pallas(hidden, proj, w, b, sketch,
                               bandwidth=bandwidth, n_buckets=n_buckets,
                               scale=scale, quant=quant,
                               block_b=block_b, block_v=block_v,
                               row_salt=row_salt)


@registry.register("fused_decode", "ref")
@partial(jax.jit, static_argnames=("bandwidth", "n_buckets", "quant",
                                   "block_b", "block_v"))
def _ref(hidden, proj, w, b, sketch, scale=None, *, bandwidth, n_buckets,
         quant=None, block_b, block_v, row_salt=None):
    del block_b, block_v  # tiling is a pallas concern
    return fused_decode_ref(hidden, proj, w, b, sketch, bandwidth, n_buckets,
                            row_salt=row_salt, scale=scale, quant=quant)


def fused_decode_logits(
    hidden: jnp.ndarray,     # (B, d_model) — final backbone hiddens
    proj: jnp.ndarray,       # (d_model, d') asymmetric transform A
    w: jnp.ndarray,          # (L, K, d') hash projections
    b: jnp.ndarray,          # (L, K) hash offsets
    sketch: jnp.ndarray,     # (L, R, V) f32 | (Lstore, R, V) int8 when quant
    *,
    bandwidth: float,
    n_buckets: int,
    scale: Optional[jnp.ndarray] = None,   # (L, R) f32 when quantized
    quant: Optional[str] = None,           # None | "int8" | "int4"
    block_b: int = 8,
    block_v: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    backend: Optional[str] = None,
    mesh=None,
    tenant_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Sketched (B, V) logits in one kernel: transform → hash → gather.

    Args:
      hidden: (B, d_model) final backbone hidden states.
      proj: (d_model, d') asymmetric transform.
      w / b: (L, K, d') / (L, K) p-stable hash bank.
      sketch: (L, R, V) per-class RACE count arrays (int8 carrier under
        ``quant``: (L, R, V) int8 or (⌈L/2⌉, R, V) packed int4 bytes).
      bandwidth / n_buckets: static LSH family parameters.
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
        path (DESIGN.md §14).  When set, every head operand carries a
        leading tenant axis T — proj (T, d, d'), w (T, L, K, d'),
        b (T, L, K), sketch (T, L, R, V), scale (T, L, R) — each resident
        tenant's logits are computed over the full batch by this *same*
        single-tenant path (shard_map psum included), and row ``b`` is
        selected from tenant ``tenant_ids[b]``'s stack arithmetic-free, so
        per-slot heads cost no bitwise parity.

    Returns:
      (B, V) f32 logit estimates.
    """
    if (scale is None) != (quant is None):
        raise ValueError("quant and scale must be passed together "
                         f"(quant={quant!r}, scale is "
                         f"{'None' if scale is None else 'set'})")
    if tenant_ids is not None:
        per_tenant = jnp.stack([
            fused_decode_logits(
                hidden, proj[t], w[t], b[t], sketch[t],
                bandwidth=bandwidth, n_buckets=n_buckets,
                scale=None if scale is None else scale[t], quant=quant,
                block_b=block_b, block_v=block_v, use_pallas=use_pallas,
                backend=backend, mesh=mesh)
            for t in range(w.shape[0])])
        return select_tenant_rows(per_tenant, tenant_ids)
    impl = registry.resolve("fused_decode", backend, use_pallas)
    kw = dict(bandwidth=bandwidth, n_buckets=n_buckets, quant=quant,
              block_b=block_b, block_v=block_v)
    l = w.shape[0]               # true repetition count (storage may pack)
    l_store = sketch.shape[0]
    msize = mesh_axis_size(mesh, "model")
    shardable = msize > 1 and l % msize == 0 and l_store % msize == 0
    if quant == "int4":
        # Byte-aligned shards only: no pad row, even true rows per shard.
        shardable = shardable and 2 * l_store == l
    if shardable:
        l_shard = l // msize
        # Keep the batch sharded over data when it divides (decode caches
        # already are): each device transforms/hashes only its rows and the
        # psum moves (B/d, V), not (B, V).
        dsize = mesh_axis_size(mesh, "data")
        bspec = "data" if dsize > 1 and hidden.shape[0] % dsize == 0 else None

        def local(h, pj, ws, bs, sk, *sc):
            # The hash fold is salted by the *global* row index; a shard
            # holding rows [i·L/m, (i+1)·L/m) must hash with those salts.
            from repro.core.lsh import row_salts
            start = jax.lax.axis_index("model") * l_shard
            part = impl(h, pj, ws, bs, sk, *sc,
                        row_salt=row_salts(l_shard, start), **kw)
            return jax.lax.psum(part * (l_shard / l), "model")

        in_specs = [P(bspec, None), P(None, None), P("model", None, None),
                    P("model", None), P("model", None, None)]
        operands = [hidden, proj, w, b, sketch]
        if quant is not None:
            in_specs.append(P("model", None))
            operands.append(scale)

        # check_vma=False: pallas_call has no replication rule; the psum
        # makes the output replicated over model by construction.
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(bspec, None), check_vma=False)(*operands)
    return impl(hidden, proj, w, b, sketch, scale, **kw)
