"""Causal self-attention: GQA/MHA, sliding-window, softcap, RoPE, KV cache.

Two execution regimes:

* **train/prefill** — for short sequences a single masked einsum; for long
  sequences (> ``_CHUNK_THRESHOLD``) a *blockwise online-softmax* scan over KV
  chunks (flash-attention recurrence in pure JAX) so peak memory is
  O(Sq · chunk) instead of O(Sq · Sk).  This is what makes the 32k-prefill
  cells lower within HBM.
* **decode** — one query token against a KV cache laid out
  ``(B, S_max, n_kv, head_dim)``; sliding-window archs keep a rolled cache of
  size ``window`` (bounded memory ⇒ long_500k eligibility).

GQA is realized by reshaping queries to (kv_groups, q_per_kv) and broadcasting
K/V — no repeat-materialization.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import AttentionConfig
from repro.models.layers import apply_rope, cache_write, init_dense, softcap
from repro.sharding.ctx import constrain, logical_axis_size

_CHUNK_THRESHOLD = 8192
_KV_CHUNK = 1024
_NEG_INF = -1e30


def init_attention(key, d_model: int, cfg: AttentionConfig) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, (d_model, cfg.n_heads * cfg.head_dim)),
        "wk": init_dense(kk, (d_model, cfg.n_kv_heads * cfg.head_dim)),
        "wv": init_dense(kv, (d_model, cfg.n_kv_heads * cfg.head_dim)),
        "wo": init_dense(ko, (cfg.n_heads * cfg.head_dim, d_model)),
    }


class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, S_max, n_kv, head_dim)
    v: jnp.ndarray  # (B, S_max, n_kv, head_dim)


def init_cache(batch: int, max_seq: int, cfg: AttentionConfig,
               dtype=jnp.bfloat16) -> KVCache:
    size = min(max_seq, cfg.window) if cfg.window else max_seq
    shape = (batch, size, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def slot_insert(cache: KVCache, src: KVCache, slots: jnp.ndarray) -> KVCache:
    """Copy batch rows of ``src`` into rows ``slots`` of the pooled cache.

    ``src`` is a freshly prefilled cache (same ``max_seq``/ring size as the
    pool) holding one row per admitted request; the per-slot position
    counter the engine keeps equals the request's own token count, so a
    rolling SWA ring inserted this way stays phase-consistent.
    """
    return KVCache(cache.k.at[slots].set(src.k.astype(cache.k.dtype)),
                   cache.v.at[slots].set(src.v.astype(cache.v.dtype)))


def slot_reset(cache: KVCache, slots: jnp.ndarray) -> KVCache:
    """Zero rows ``slots`` — bitwise identical to a fresh ``init_cache`` row."""
    return KVCache(cache.k.at[slots].set(0), cache.v.at[slots].set(0))


# -- paged variants (DESIGN.md §13) ----------------------------------------
#
# The paged pool replaces the per-slot ``(B, size, …)`` rows with a shared
# ``(num_pages, page_size, …)`` arena addressed through a host-side page
# table.  Page 0 is reserved all-zero, so gathering an unmapped table entry
# reproduces a fresh ``init_cache`` row bitwise — the gathered view feeds
# the *same* compiled decode step as the contiguous engine.


def init_paged_cache(num_pages: int, page_size: int, cfg: AttentionConfig,
                     dtype=jnp.bfloat16) -> KVCache:
    shape = (num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def paged_view(cache: KVCache, pt: jnp.ndarray, size: int) -> KVCache:
    """Gather per-slot contiguous rows from the page arena.

    ``pt`` is the (B, npp_max) page table; this family reads its first
    ``ceil(size / page_size)`` entries.  Unmapped (0) entries gather the
    reserved zero page, so the result is byte-equal to a contiguous pool
    row at the same decode position.
    """
    ps = cache.k.shape[1]
    npp = -(-size // ps)

    def g(pages):
        v = pages[pt[:, :npp]]                       # (B, npp, ps, kv, dh)
        return v.reshape(pt.shape[0], npp * ps, *pages.shape[2:])[:, :size]

    return KVCache(g(cache.k), g(cache.v))


def paged_commit(cache: KVCache, view: KVCache, pt: jnp.ndarray,
                 wpos: jnp.ndarray) -> KVCache:
    """Scatter the one position decode wrote back into the arena.

    ``wpos`` (B,) is the ring-adjusted write index the decode step used
    (``pos % size`` for rolling SWA, ``pos`` otherwise) — computed by the
    dispatch layer, which knows this family's ring geometry.  Slots whose
    write page is unmapped (retired/inactive — masked decode reverted their
    update) scatter gathered zeros onto the zero page: a no-op.
    """
    ps = cache.k.shape[1]
    bi = jnp.arange(pt.shape[0])
    phys = pt[bi, wpos // ps]
    off = wpos % ps
    return KVCache(
        cache.k.at[phys, off].set(view.k[bi, wpos].astype(cache.k.dtype)),
        cache.v.at[phys, off].set(view.v[bi, wpos].astype(cache.v.dtype)))


def paged_insert(cache: KVCache, src: KVCache, pt_rows: jnp.ndarray) -> KVCache:
    """Scatter freshly prefilled rows into newly mapped pages.

    ``src`` is the same fresh contiguous cache ``slot_insert`` takes, one
    row per admitted request; ``pt_rows`` are those requests' page-table
    rows.  Rows past the prompt are still zero after prefill (ring rebuild
    included), so unmapped trailing entries scatter zeros onto page 0.
    """
    ps = cache.k.shape[1]
    size = src.k.shape[1]
    npp = -(-size // ps)

    def s(pages, rows):
        pad = npp * ps - size
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad)) + ((0, 0),) * (rows.ndim - 2))
        rows = rows.reshape(rows.shape[0], npp, ps, *rows.shape[2:])
        return pages.at[pt_rows[:, :npp]].set(rows.astype(pages.dtype))

    return KVCache(s(cache.k, src.k), s(cache.v, src.v))


def _scores_mask(scores: jnp.ndarray, q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                 window: Optional[int]) -> jnp.ndarray:
    """Apply causal (+ optional sliding-window) mask to (..., Sq, Sk) scores.

    Positions are either shared across the batch (``(Sq,)`` / ``(Sk,)``) or
    per-sequence (``(B, Sq)`` / ``(B, Sk)`` — continuous-batching decode,
    where every cache slot carries its own position counter).
    """
    if q_pos.ndim == 2 or k_pos.ndim == 2:
        q2 = q_pos if q_pos.ndim == 2 else q_pos[None]
        k2 = k_pos if k_pos.ndim == 2 else k_pos[None]
        causal = q2[:, :, None] >= k2[:, None, :]
        if window is not None:
            causal &= (q2[:, :, None] - k2[:, None, :]) < window
        # scores: (B, n_kv, groups, Sq, Sk) — broadcast over the head axes.
        return jnp.where(causal[:, None, None], scores, _NEG_INF)
    causal = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        causal &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(causal, scores, _NEG_INF)


def _attend_full(q, k, v, q_pos, k_pos, cfg: AttentionConfig):
    """Masked full attention. q: (B,Sq,Hq,dh), k/v: (B,Sk,Hkv,dh)."""
    b, sq, hq, dh = q.shape
    groups = hq // cfg.n_kv_heads
    qg = q.reshape(b, sq, cfg.n_kv_heads, groups, dh)
    scale = dh ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if cfg.logit_softcap:
        scores = softcap(scores, cfg.logit_softcap)
    scores = _scores_mask(scores, q_pos, k_pos, cfg.window)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def _attend_chunked(q, k, v, q_pos, k_pos, cfg: AttentionConfig,
                    chunk: int = _KV_CHUNK):
    """Online-softmax blockwise attention over KV chunks (flash recurrence)."""
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    groups = hq // cfg.n_kv_heads
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    n_chunks = k.shape[1] // chunk
    qg = (q.astype(jnp.float32) * dh ** -0.5).reshape(b, sq, cfg.n_kv_heads, groups, dh)

    kc = k.reshape(b, n_chunks, chunk, cfg.n_kv_heads, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, cfg.n_kv_heads, dh).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(n_chunks, chunk)

    def step(carry, inputs):
        m_prev, s_prev, o_prev = carry  # (b,kv,g,sq), same, (b,sq,kv,g,dh)
        kb, vb, pb = inputs
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb.astype(jnp.float32))
        if cfg.logit_softcap:
            scores = softcap(scores, cfg.logit_softcap)
        scores = _scores_mask(scores, q_pos, pb, cfg.window)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        s_new = s_prev * corr + jnp.sum(p, axis=-1)
        o_new = o_prev * corr.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
            "bkgqs,bskd->bqkgd", p, vb.astype(jnp.float32)
        )
        return (m_new, s_new, o_new), None

    m0 = jnp.full((b, cfg.n_kv_heads, groups, sq), _NEG_INF, jnp.float32)
    s0 = jnp.zeros((b, cfg.n_kv_heads, groups, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, cfg.n_kv_heads, groups, dh), jnp.float32)
    (m, s, o), _ = jax.lax.scan(step, (m0, s0, o0), (kc, vc, pc))
    out = o / jnp.maximum(s, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def _attend_banded(q, k, v, q_pos, k_pos, cfg: AttentionConfig,
                   chunk: int = _KV_CHUNK):
    """Sliding-window attention with banded blocking (§Perf iteration 3).

    Scans over query chunks; each chunk attends only to its KV band
    ``[qc_start − W, qc_end)`` (static size W+chunk), so FLOPs are
    S·(W+chunk)·d per head instead of the full S² rectangle — 6.4× fewer
    for mixtral's W=4096 at S=32k.  Correctness rides on the causal+window
    mask; the band provably covers every in-window key.
    """
    b, s, hq, dh = q.shape
    w = cfg.window
    band = w + chunk
    pad_q = (-s) % chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q),
                        constant_values=jnp.iinfo(jnp.int32).max // 2)
    # Left-pad KV by W so every band slice is in range with static size.
    k = jnp.pad(k, ((0, 0), (w, 0), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (w, 0), (0, 0), (0, 0)))
    k_pos = jnp.pad(k_pos, (w, 0), constant_values=jnp.iinfo(jnp.int32).max)
    n_chunks = q.shape[1] // chunk
    qc = q.reshape(b, n_chunks, chunk, hq, dh).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(n_chunks, chunk)

    def step(_, inputs):
        i, qb, pb = inputs
        start = i * chunk            # == (qc_start − W) + W of padded KV
        kb = jax.lax.dynamic_slice(k, (0, start, 0, 0),
                                   (b, band, k.shape[2], dh))
        vb = jax.lax.dynamic_slice(v, (0, start, 0, 0),
                                   (b, band, v.shape[2], dh))
        kp = jax.lax.dynamic_slice(k_pos, (start,), (band,))
        return None, _attend_full(qb, kb, vb, pb, kp, cfg)

    _, out = jax.lax.scan(step, None,
                          (jnp.arange(n_chunks), qc, pc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, hq, dh)
    return out[:, :s]


def attention(
    params: dict,
    x: jnp.ndarray,                       # (B, S, d_model)
    positions: jnp.ndarray,               # (S,)
    cfg: AttentionConfig,
    *,
    kv_source: Optional[jnp.ndarray] = None,   # encoder states for cross-attn
    cache: Optional[KVCache] = None,
    cache_pos: Optional[jnp.ndarray] = None,   # scalar: #tokens already cached
    active: Optional[jnp.ndarray] = None,      # (B,) decode rows to write
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Full attention block. Returns (output, updated_cache).

    At decode, rows clear in ``active`` leave their cache entry as it was
    (``layers.cache_write``)."""
    b, s, _ = x.shape
    src = kv_source if kv_source is not None else x
    # Query heads pinned to TP shards (head-parallel attention); KV heads
    # follow if divisible (constrain drops the axis otherwise — GQA with
    # n_kv < tp runs with replicated KV, the standard fallback).
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    q = constrain(q, "dp", None, "tp", None)
    k = jnp.einsum("bsd,de->bse", src, params["wk"]).reshape(
        b, src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    k = constrain(k, "dp", None, "tp", None)
    v = jnp.einsum("bsd,de->bse", src, params["wv"]).reshape(
        b, src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = constrain(v, "dp", None, "tp", None)

    if kv_source is not None:
        # Cross-attention: no positions, no mask, no cache.
        scale = cfg.head_dim ** -0.5
        groups = cfg.n_heads // cfg.n_kv_heads
        qg = (q.astype(jnp.float32) * scale).reshape(
            b, s, cfg.n_kv_heads, groups, cfg.head_dim)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
        out = out.reshape(b, s, cfg.n_heads * cfg.head_dim).astype(x.dtype)
        return jnp.einsum("bse,ed->bsd", out, params["wo"]), None

    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if (cache is not None and s > 1 and cfg.window
            and cfg.window <= cache.k.shape[1]):
        # Bulk prefill into a rolling SWA cache.  A single dynamic_update_slice
        # can neither wrap around the ring nor exceed its length, and early
        # query tokens must attend to keys that later tokens will overwrite —
        # so attend over (old ring ∪ new tokens), then rebuild the ring with
        # the last `size` absolute positions via a gather.
        size = cache.k.shape[1]
        j = jnp.arange(size)
        # Absolute position held by slot j before the write: the largest
        # t ≡ j (mod size) with t < cache_pos (negative ⇒ never written).
        t_old = cache_pos - 1 - ((cache_pos - 1 - j) % size)
        k_pos = jnp.concatenate(
            [jnp.where(t_old >= 0, t_old, jnp.iinfo(jnp.int32).max),
             positions])
        k_cat = jnp.concatenate([cache.k.astype(k.dtype), k], axis=1)
        v_cat = jnp.concatenate([cache.v.astype(v.dtype), v], axis=1)
        # Long prompts: online-softmax over KV chunks — never materialize
        # the (Sq, size+Sq) score rectangle (same thresholds as cacheless).
        attend = (_attend_chunked if s > min(_CHUNK_THRESHOLD,
                                             cfg.window + _KV_CHUNK)
                  else _attend_full)
        out = attend(q, k_cat, v_cat, positions, k_pos, cfg)
        # After the write, slot j holds the largest t ≡ j (mod size) with
        # t < cache_pos + s; keep the old value where that t predates the
        # new tokens.
        t_new = cache_pos + s - 1 - ((cache_pos + s - 1 - j) % size)
        rel = jnp.clip(t_new - cache_pos, 0, s - 1)
        is_new = (t_new >= cache_pos)[None, :, None, None]
        new_cache = KVCache(
            jnp.where(is_new, jnp.take(k, rel, axis=1).astype(cache.k.dtype),
                      cache.k),
            jnp.where(is_new, jnp.take(v, rel, axis=1).astype(cache.v.dtype),
                      cache.v))
        out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
        out = constrain(out, "dp", None, "tp")
        return jnp.einsum("bse,ed->bsd", out, params["wo"]), new_cache
    if cache is not None and jnp.ndim(cache_pos) == 1:
        # Per-slot decode (continuous-batching engine): every sequence owns
        # one cache row and its own position counter, so the write index and
        # the key positions are per-batch.  Single-token steps only — bulk
        # prefill of a new request runs with a scalar cache_pos into a fresh
        # cache and is copied in via ``slot_insert``.
        if s != 1:
            raise NotImplementedError(
                "per-slot cache_pos supports single-token decode only; "
                "prefill into a fresh cache and slot_insert it instead")
        size = cache.k.shape[1]
        ring = bool(cfg.window) and cfg.window <= size
        slot = cache_pos % size if ring else cache_pos      # (B,)
        ck = cache_write(cache.k, k, slot, active)
        cv = cache_write(cache.v, v, slot, active)
        new_cache = KVCache(ck, cv)
        i = jnp.arange(size)[None, :]
        if ring:
            # Ring buffer: same pointer arithmetic as the scalar path, per row.
            base = (cache_pos - slot)[:, None]
            k_pos = jnp.where(i <= slot[:, None], i + base, i + base - size)
            k_pos = jnp.where(k_pos >= 0, k_pos, jnp.iinfo(jnp.int32).max)
        else:
            k_pos = jnp.where(i < cache_pos[:, None] + 1, i,
                              jnp.iinfo(jnp.int32).max)
        out = _attend_full(q, ck, cv, positions, k_pos, cfg)
    elif cache is not None:
        # Decode: append the s new tokens into the (possibly rolling) cache.
        size = cache.k.shape[1]
        if cfg.window and cfg.window <= size:
            slot = cache_pos % size  # rolling ring buffer for SWA
        else:
            slot = cache_pos
        ck = cache_write(cache.k, k, slot, active)
        cv = cache_write(cache.v, v, slot, active)
        new_cache = KVCache(ck, cv)
        k_all, v_all = ck, cv
        if cfg.window and cfg.window <= size:
            # Ring buffer: absolute position of slot i is recovered from the
            # write pointer; stale slots are masked by the causal check.
            k_pos = jnp.where(
                jnp.arange(size) <= slot,
                jnp.arange(size) + (cache_pos - slot),
                jnp.arange(size) + (cache_pos - slot) - size,
            )
            k_pos = jnp.where(k_pos >= 0, k_pos, jnp.iinfo(jnp.int32).max)
        else:
            k_pos = jnp.arange(k_all.shape[1])
            k_pos = jnp.where(k_pos < cache_pos + s, k_pos,
                              jnp.iinfo(jnp.int32).max)
        # Decode (s=1) attends densely; a bulk prefill over a long prompt
        # switches to the online-softmax chunked path (cacheless threshold).
        attend = _attend_chunked if s > _CHUNK_THRESHOLD else _attend_full
        out = attend(q, k_all, v_all, positions, k_pos, cfg)
    else:
        k_pos = positions
        # Train/prefill: expand GQA KV to full heads ONLY when the KV head
        # count can't shard over TP (n_kv % tp != 0) — expansion makes
        # attention cleanly head-parallel at the cost of transient
        # (rematerialized) KV; when KV heads divide TP they shard directly.
        # Decode always keeps grouped GQA (the cache dominates memory).
        groups = cfg.n_heads // cfg.n_kv_heads
        if groups > 1 and cfg.n_kv_heads % max(logical_axis_size("tp"), 1):
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
            k = constrain(k, "dp", None, "tp", None)
            v = constrain(v, "dp", None, "tp", None)
            cfg_full = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
        else:
            cfg_full = cfg
        if cfg.window is not None and s > cfg.window + _KV_CHUNK:
            out = _attend_banded(q, k, v, positions, k_pos, cfg_full)
        elif s > _CHUNK_THRESHOLD:
            out = _attend_chunked(q, k, v, positions, k_pos, cfg_full)
        else:
            out = _attend_full(q, k, v, positions, k_pos, cfg_full)

    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = constrain(out, "dp", None, "tp")
    return jnp.einsum("bse,ed->bsd", out, params["wo"]), new_cache
