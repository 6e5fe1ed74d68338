"""Per-layer block dispatch: init + forward for every block kind.

A *layer* = mixer (attention / MLA / mamba / rwkv time-mix / cross-attn)
followed by an FFN (dense SwiGLU or MoE), pre-norm residual style.  The
layer's parameter tree and cache tree depend only on its ``kind`` and its
position-in-pattern (which fixes the FFN kind), so layers at the same
pattern position can be stacked and scanned over periods (model.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models import rwkv as rwkv_mod
from repro.models.config import ModelConfig
from repro.models.layers import hold_parked, init_dense, rms_norm
from repro.models.moe import init_moe, moe_ffn
from repro.sharding.ctx import constrain

ATTN_KINDS = ("attn", "attn_local", "attn_global", "xattn")


def _attn_cfg(cfg: ModelConfig, kind: str):
    a = cfg.attention
    if kind == "attn_global":
        return dataclasses.replace(a, window=None)
    if kind == "attn_local":
        assert a.window is not None, "attn_local requires attention.window"
        return a
    if kind == "xattn":
        return dataclasses.replace(a, window=None, use_rope=False)
    return a


def init_layer(key, cfg: ModelConfig, kind: str, ffn: str) -> dict:
    """Parameters for one layer of the given kind + ffn ('dense'|'moe'|'none')."""
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    params: dict = {"norm1": jnp.zeros((d,), jnp.float32)}
    if kind in ATTN_KINDS:
        params["mixer"] = attn_mod.init_attention(k1, d, _attn_cfg(cfg, kind))
    elif kind == "mla":
        params["mixer"] = mla_mod.init_mla(k1, d, cfg.mla)
    elif kind == "mamba":
        params["mixer"] = mamba_mod.init_mamba(k1, d, cfg.mamba)
    elif kind == "rwkv":
        params["mixer"] = rwkv_mod.init_rwkv(k1, d, cfg.d_ff)
    else:
        raise ValueError(f"unknown block kind {kind}")

    if kind != "rwkv":  # rwkv's channel-mix is its FFN (inside mixer params)
        params["norm2"] = jnp.zeros((d,), jnp.float32)
        if ffn == "moe":
            params["ffn"] = init_moe(k2, d, cfg.moe)
        else:
            kg, ku, kd = jax.random.split(k3, 3)
            params["ffn"] = {
                "w_gate": init_dense(kg, (d, cfg.d_ff)),
                "w_up": init_dense(ku, (d, cfg.d_ff)),
                "w_down": init_dense(kd, (cfg.d_ff, d)),
            }
    else:
        params["norm2"] = jnp.zeros((d,), jnp.float32)
    return params


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int):
    """Decode cache pytree for one layer (None for cacheless kinds)."""
    if kind == "xattn":
        return None  # encoder K/V recomputed from the (small) encoder states
    if kind in ATTN_KINDS:
        return attn_mod.init_cache(batch, max_seq, _attn_cfg(cfg, kind))
    if kind == "mla":
        return mla_mod.init_mla_cache(batch, max_seq, cfg.mla)
    if kind == "mamba":
        return mamba_mod.init_mamba_cache(batch, cfg.d_model, cfg.mamba)
    if kind == "rwkv":
        return rwkv_mod.init_rwkv_cache(batch, cfg.d_model)
    raise ValueError(kind)


def slot_insert_cache(kind: str, cache, src, slots):
    """Slot-wise insert for one layer's cache (dispatch on block kind)."""
    if cache is None:
        return None
    if kind in ATTN_KINDS:
        return attn_mod.slot_insert(cache, src, slots)
    if kind == "mla":
        return mla_mod.slot_insert(cache, src, slots)
    if kind == "mamba":
        return mamba_mod.slot_insert(cache, src, slots)
    if kind == "rwkv":
        return rwkv_mod.slot_insert(cache, src, slots)
    raise ValueError(kind)


def cache_needs_snapshot(cfg: ModelConfig, kind: str, cache) -> bool:
    """True when a speculative rollback must keep per-step history of this
    layer's cache (DESIGN.md §11).

    Recurrent state (mamba / rwkv) has no positional axis to rewind.  A
    rolling SWA ring is positional but *destructive*: a draft step's write at
    ``pos % size`` overwrites the previous lap's entry, which is still inside
    the attention window after a rollback — so the ring needs snapshots too.
    Plain KV / MLA caches are append-only and masked by position
    (``k_pos < cache_pos + 1``), so rewinding the position counter alone
    makes stale draft writes invisible; they return False.
    """
    if cache is None:
        return False
    if kind in ("mamba", "rwkv"):
        return True
    if kind in ATTN_KINDS:
        a = _attn_cfg(cfg, kind)
        # Mirrors the decode-path ring test: size = min(max_seq, window).
        return bool(a.window) and a.window <= cache.k.shape[1]
    return False


def slot_reset_cache(kind: str, cache, slots):
    """Slot-wise reset for one layer's cache (dispatch on block kind)."""
    if cache is None:
        return None
    if kind in ATTN_KINDS:
        return attn_mod.slot_reset(cache, slots)
    if kind == "mla":
        return mla_mod.slot_reset(cache, slots)
    if kind == "mamba":
        return mamba_mod.slot_reset(cache, slots)
    if kind == "rwkv":
        return rwkv_mod.slot_reset(cache, slots)
    raise ValueError(kind)


# -- paged cache dispatch (DESIGN.md §13) ----------------------------------
#
# The paged pool splits per family: attention/MLA caches have a sequence
# axis and live as (num_pages, page_size, …) arenas addressed through a
# page table; mamba/RWKV state is constant-size per slot and stays in a
# plain (n_slots, …) *state* tree under the ordinary slot ops.  A layer
# contributes to exactly one of the two trees (None in the other), which is
# what lets ``model._map_layer_caches`` walk both with the same machinery.


def paged_geometry(cfg: ModelConfig, kind: str, max_seq: int):
    """Sequence-axis geometry of one layer's paged cache.

    Returns ``(size, ring)`` — the per-slot cache length and whether decode
    writes roll (``pos % size``) — or None for kinds with nothing to page
    (cacheless xattn, constant-size mamba/RWKV state).
    """
    if kind == "xattn" or kind in ("mamba", "rwkv"):
        return None
    if kind in ATTN_KINDS:
        a = _attn_cfg(cfg, kind)
        size = min(max_seq, a.window) if a.window else max_seq
        return size, bool(a.window) and a.window <= size
    if kind == "mla":
        return max_seq, False
    raise ValueError(kind)


def init_paged_layer_cache(cfg: ModelConfig, kind: str, num_pages: int,
                           page_size: int):
    """Page-arena leaf for one layer (None for unpaged kinds)."""
    if kind == "xattn" or kind in ("mamba", "rwkv"):
        return None
    if kind in ATTN_KINDS:
        return attn_mod.init_paged_cache(num_pages, page_size,
                                         _attn_cfg(cfg, kind))
    return mla_mod.init_paged_cache(num_pages, page_size, cfg.mla)


def init_paged_state_cache(cfg: ModelConfig, kind: str, n_slots: int):
    """Recurrent-state leaf for one layer (None for paged/cacheless kinds)."""
    if kind == "mamba":
        return mamba_mod.init_mamba_cache(n_slots, cfg.d_model, cfg.mamba)
    if kind == "rwkv":
        return rwkv_mod.init_rwkv_cache(n_slots, cfg.d_model)
    return None


def _wpos(cfg: ModelConfig, kind: str, pos, max_seq: int):
    """Ring-adjusted per-slot write index (mirrors the decode-step branch)."""
    size, ring = paged_geometry(cfg, kind, max_seq)
    return pos % size if ring else pos


def paged_view_cache(cfg: ModelConfig, kind: str, cache, pt, max_seq: int):
    """Gather one layer's per-slot contiguous view from its page arena."""
    if cache is None:
        return None
    size, _ = paged_geometry(cfg, kind, max_seq)
    if kind in ATTN_KINDS:
        return attn_mod.paged_view(cache, pt, size)
    return mla_mod.paged_view(cache, pt, size)


def paged_commit_cache(cfg: ModelConfig, kind: str, cache, view, pt, pos,
                       max_seq: int):
    """Scatter the decode-written position of ``view`` back into the arena."""
    if cache is None:
        return None
    wpos = _wpos(cfg, kind, pos, max_seq)
    if kind in ATTN_KINDS:
        return attn_mod.paged_commit(cache, view, pt, wpos)
    return mla_mod.paged_commit(cache, view, pt, wpos)


def paged_insert_cache(kind: str, cache, src, pt_rows):
    """Scatter freshly prefilled rows into newly mapped pages."""
    if cache is None:
        return None
    if kind in ATTN_KINDS:
        return attn_mod.paged_insert(cache, src, pt_rows)
    return mla_mod.paged_insert(cache, src, pt_rows)


def paged_copy_pages(kind: str, cache, src_ids, dst_ids):
    """Copy whole pages ``src_ids → dst_ids`` (COW fork; (0,0) pads no-op)."""
    if cache is None:
        return None
    return type(cache)(*(leaf.at[dst_ids].set(leaf[src_ids])
                         for leaf in cache))


def apply_layer(
    params: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    kind: str,
    ffn: str,
    *,
    encoder_states: Optional[jnp.ndarray] = None,
    cache: Any = None,
    cache_pos: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Apply one layer. Returns (x, new_cache, aux_loss).

    ``active`` (B,) bool, at decode: rows left clear keep their cache
    bitwise unchanged — each family holds back its own write."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    # Sequence parallelism (Korthikanti et al.): the residual stream — and
    # with it every remat-saved layer boundary — lives sequence-sharded over
    # TP; XLA inserts the AG before attention/FFN and the RS after.  Cuts
    # saved-activation memory by tp× (§Perf iter 6).  Decode (s=1) drops
    # the constraint automatically.  MoE layers opt out: grouped routing
    # over a seq-sharded stream degenerates into all-to-all storms
    # (measured 1.6e12 → 6.4e12 coll bytes on deepseek; §Perf iter 6b).
    seq = "tp" if ffn != "moe" else None
    x = constrain(x, "dp", seq, None)
    h = rms_norm(x, params["norm1"], eps)

    if kind == "rwkv":
        prev = cache.tm_prev if cache is not None else None
        st = cache.state if cache is not None else None
        with jax.named_scope("mixer"):
            delta, tm_last, new_state = rwkv_mod.rwkv_time_mix(
                params["mixer"], h, prev=prev, state0=st)
        x = x + delta
        h2 = rms_norm(x, params["norm2"], eps)
        cm_prev = cache.cm_prev if cache is not None else None
        with jax.named_scope("ffn"):
            delta2, cm_last = rwkv_mod.rwkv_channel_mix(
                params["mixer"], h2, prev=cm_prev)
        new_cache = None
        if cache is not None:
            new_cache = jax.tree.map(
                lambda n, o: hold_parked(active, n.astype(o.dtype), o),
                rwkv_mod.RWKVCache(tm_last, cm_last, new_state), cache)
        return x + delta2, new_cache, aux

    with jax.named_scope("mixer"):
        if kind in ("attn", "attn_local", "attn_global"):
            delta, new_cache = attn_mod.attention(
                params["mixer"], h, positions, _attn_cfg(cfg, kind),
                cache=cache, cache_pos=cache_pos, active=active)
        elif kind == "xattn":
            delta, new_cache = attn_mod.attention(
                params["mixer"], h, positions, _attn_cfg(cfg, kind),
                kv_source=encoder_states)
        elif kind == "mla":
            delta, new_cache = mla_mod.mla_attention(
                params["mixer"], h, positions, cfg.mla,
                cache=cache, cache_pos=cache_pos, active=active)
        elif kind == "mamba":
            delta, new_cache = mamba_mod.mamba_block(
                params["mixer"], h, cfg.mamba, cache=cache, active=active)
        else:
            raise ValueError(kind)

    x = x + constrain(delta, "dp", seq, None)
    h2 = rms_norm(x, params["norm2"], eps)
    with jax.named_scope("ffn"):
        if ffn == "moe":
            delta2, aux = moe_ffn(params["ffn"], h2, cfg.moe)
        else:
            # Megatron pattern: d_ff intermediate pinned to TP shards, so
            # the partitioner emits exactly one AR (after w_down), never a
            # contraction-sharded d_ff-wide AR.
            f = params["ffn"]
            g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h2, f["w_gate"]))
            u = jnp.einsum("bsd,df->bsf", h2, f["w_up"])
            g = constrain(g, "dp", None, "tp")
            u = constrain(u, "dp", None, "tp")
            delta2 = jnp.einsum("bsf,fd->bsd", g * u, f["w_down"])
    return x + constrain(delta2, "dp", seq, None), new_cache, aux
