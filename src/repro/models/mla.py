"""Multi-head Latent Attention (DeepSeek-V2/V3).

K/V are compressed into a shared latent ``c_kv ∈ R^{kv_lora_rank}`` plus a
decoupled RoPE key ``k_rope ∈ R^{qk_rope_head_dim}``; queries go through a
low-rank bottleneck ``q_lora_rank``.  The decode cache stores only
``(c_kv, k_rope)`` per position — (512+64) floats for DeepSeek-V3 instead of
2·128·128 for vanilla MHA: a 57× KV-memory compression.  That compressed
cache is why the long_500k cell is runnable for deepseek-v3 (DESIGN.md §5).

Decode uses the standard MLA absorption trick: since
``k_nope = c_kv · W_uk`` and score = q_nopeᵀk_nope, we fold ``W_uk`` into the
query (``q̃ = W_ukᵀ q_nope``) and attend directly over the latent cache —
never materializing per-head K/V for past positions.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import MLAConfig
from repro.models.layers import apply_rope, cache_write, init_dense


class MLACache(NamedTuple):
    c_kv: jnp.ndarray    # (B, S_max, kv_lora_rank)
    k_rope: jnp.ndarray  # (B, S_max, qk_rope_head_dim)


def init_mla(key, d_model: int, cfg: MLAConfig) -> dict:
    ks = jax.random.split(key, 6)
    return {
        "w_dq": init_dense(ks[0], (d_model, cfg.q_lora_rank)),
        "w_uq": init_dense(ks[1], (cfg.q_lora_rank, cfg.n_heads * cfg.qk_head_dim)),
        "w_dkv": init_dense(ks[2], (d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
        "w_uk": init_dense(ks[3], (cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_head_dim)),
        "w_uv": init_dense(ks[4], (cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim)),
        "w_o": init_dense(ks[5], (cfg.n_heads * cfg.v_head_dim, d_model)),
    }


def init_mla_cache(batch: int, max_seq: int, cfg: MLAConfig,
                   dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype),
    )


def slot_insert(cache: MLACache, src: MLACache, slots: jnp.ndarray) -> MLACache:
    """Copy batch rows of a freshly prefilled latent cache into pool ``slots``."""
    return MLACache(
        cache.c_kv.at[slots].set(src.c_kv.astype(cache.c_kv.dtype)),
        cache.k_rope.at[slots].set(src.k_rope.astype(cache.k_rope.dtype)))


def slot_reset(cache: MLACache, slots: jnp.ndarray) -> MLACache:
    """Zero rows ``slots`` — bitwise identical to fresh ``init_mla_cache`` rows."""
    return MLACache(cache.c_kv.at[slots].set(0), cache.k_rope.at[slots].set(0))


# -- paged variants (DESIGN.md §13) ----------------------------------------
# Same arena/page-table scheme as attention.paged_*; the latent cache has no
# head axis, just (num_pages, page_size, rank) leaves.  MLA never rolls a
# ring, so the commit write index is always the raw position counter.


def init_paged_cache(num_pages: int, page_size: int, cfg: MLAConfig,
                     dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        jnp.zeros((num_pages, page_size, cfg.kv_lora_rank), dtype),
        jnp.zeros((num_pages, page_size, cfg.qk_rope_head_dim), dtype))


def paged_view(cache: MLACache, pt: jnp.ndarray, size: int) -> MLACache:
    """Gather per-slot contiguous latent rows from the page arena (unmapped
    table entries read the reserved zero page → fresh-cache bytes)."""
    ps = cache.c_kv.shape[1]
    npp = -(-size // ps)

    def g(pages):
        v = pages[pt[:, :npp]]                       # (B, npp, ps, r)
        return v.reshape(pt.shape[0], npp * ps, *pages.shape[2:])[:, :size]

    return MLACache(g(cache.c_kv), g(cache.k_rope))


def paged_commit(cache: MLACache, view: MLACache, pt: jnp.ndarray,
                 wpos: jnp.ndarray) -> MLACache:
    """Scatter the decode-written position back into the arena (``wpos`` is
    the per-slot position counter — MLA caches never ring)."""
    ps = cache.c_kv.shape[1]
    bi = jnp.arange(pt.shape[0])
    phys = pt[bi, wpos // ps]
    off = wpos % ps
    return MLACache(
        cache.c_kv.at[phys, off].set(
            view.c_kv[bi, wpos].astype(cache.c_kv.dtype)),
        cache.k_rope.at[phys, off].set(
            view.k_rope[bi, wpos].astype(cache.k_rope.dtype)))


def paged_insert(cache: MLACache, src: MLACache,
                 pt_rows: jnp.ndarray) -> MLACache:
    """Scatter freshly prefilled latent rows into newly mapped pages."""
    ps = cache.c_kv.shape[1]
    size = src.c_kv.shape[1]
    npp = -(-size // ps)

    def s(pages, rows):
        pad = npp * ps - size
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad)) + ((0, 0),) * (rows.ndim - 2))
        rows = rows.reshape(rows.shape[0], npp, ps, *rows.shape[2:])
        return pages.at[pt_rows[:, :npp]].set(rows.astype(pages.dtype))

    return MLACache(s(cache.c_kv, src.c_kv), s(cache.k_rope, src.k_rope))


_NEG_INF = -1e30


def mla_attention(
    params: dict,
    x: jnp.ndarray,             # (B, S, d)
    positions: jnp.ndarray,     # (S,)
    cfg: MLAConfig,
    *,
    rope_theta: float = 10000.0,
    cache: Optional[MLACache] = None,
    cache_pos: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,    # (B,) decode rows to write
) -> Tuple[jnp.ndarray, Optional[MLACache]]:
    b, s, d = x.shape
    h = cfg.n_heads

    # Query path: low-rank down + up, split nope/rope parts.
    q = jnp.einsum("bsd,dr->bsr", x, params["w_dq"])
    q = jnp.einsum("bsr,re->bse", q, params["w_uq"]).reshape(
        b, s, h, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, rope_theta)

    # KV path: shared latent + decoupled rope key.
    dkv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
    c_kv, k_rope = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, rope_theta)[:, :, 0, :]

    if cache is None:
        # Prefill/train: materialize per-head K/V from the latent (absorption
        # only wins at decode) and reuse the blockwise online-softmax
        # attention so 32k-prefill memory stays O(S · chunk).
        from repro.models.attention import _attend_chunked, _attend_full, _CHUNK_THRESHOLD
        from repro.models.config import AttentionConfig

        k_nope = jnp.einsum("bsr,re->bse", c_kv, params["w_uk"]).reshape(
            b, s, h, cfg.qk_nope_head_dim)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, h, cfg.qk_rope_head_dim))], axis=-1)
        v = jnp.einsum("bsr,re->bse", c_kv, params["w_uv"]).reshape(
            b, s, h, cfg.v_head_dim)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # Pad V up to the QK head dim so the flash recurrence is square.
        pad_v = cfg.qk_head_dim - cfg.v_head_dim
        if pad_v > 0:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad_v)))
        acfg = AttentionConfig(n_heads=h, n_kv_heads=h, head_dim=cfg.qk_head_dim,
                               use_rope=False)
        attend = _attend_chunked if s > _CHUNK_THRESHOLD else _attend_full
        o = attend(q_full, k_full, v, positions, positions, acfg)
        o = o[..., : cfg.v_head_dim].reshape(b, s, h * cfg.v_head_dim)
        return jnp.einsum("bse,ed->bsd", o, params["w_o"]), None

    # Decode against the latent cache.  Per-slot cache_pos (continuous-
    # batching engine): each sequence owns a cache row with its own position
    # counter; single-token steps only.
    per_slot = jnp.ndim(cache_pos) == 1
    if per_slot and s != 1:
        raise NotImplementedError(
            "per-slot cache_pos supports single-token decode only; "
            "prefill into a fresh cache and slot_insert it instead")
    c_all = cache_write(cache.c_kv, c_kv, cache_pos, active)
    r_all = cache_write(cache.k_rope, k_rope, cache_pos, active)
    new_cache = MLACache(c_all, r_all)
    end = (cache_pos[:, None] if per_slot else cache_pos) + s
    k_pos = jnp.arange(c_all.shape[1])
    k_pos = jnp.where(k_pos < end, k_pos,
                      jnp.iinfo(jnp.int32).max)              # (T,) or (B, T)

    # Absorption: fold W_uk into the query → attend over the latent directly.
    w_uk = params["w_uk"].reshape(cfg.kv_lora_rank, h, cfg.qk_nope_head_dim)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))     # (B,S,H,kv_rank)
    scale = cfg.qk_head_dim ** -0.5
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, c_all.astype(jnp.float32))
        + jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                     r_all.astype(jnp.float32))
    ) * scale
    if positions.ndim == 2 or k_pos.ndim == 2:
        # Per-sequence positions: (B, S) vs (B, T) → (B, 1, S, T) mask.
        p2 = positions if positions.ndim == 2 else positions[None]
        k2 = k_pos if k_pos.ndim == 2 else k_pos[None]
        mask = (p2[:, :, None] >= k2[:, None, :])[:, None]
    else:
        mask = (positions[:, None] >= k_pos[None, :])[None, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)

    # Attend over the latent, then up-project per head (absorbed W_uv).
    o_lat = jnp.einsum("bhst,btr->bshr", probs, c_all.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(cfg.kv_lora_rank, h, cfg.v_head_dim)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv.astype(jnp.float32))
    o = o.reshape(b, s, h * cfg.v_head_dim).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, params["w_o"]), new_cache
