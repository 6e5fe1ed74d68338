"""Decoder backbone: embedding → (prologue + scanned periods) → head.

Compile-size discipline: the layer stack is executed as ``lax.scan`` over
*periods* of the block pattern, so the lowered HLO contains one copy of each
pattern position regardless of depth (61-layer DeepSeek lowers as 1 MLA body
+ 3 prologue layers).  Parameters of the scanned layers carry a leading
``n_periods`` axis; decode caches are stacked the same way and threaded
through the scan.

Train mode rematerializes each period body (``jax.checkpoint``) — the
standard memory/compute trade for long-sequence training.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.models.config import ModelConfig
from repro.models.layers import embed, init_dense, rms_norm, softcap, unembed
from repro.sharding.ctx import constrain


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kh, kp, ks = jax.random.split(key, 4)
    params: dict = {
        "embed": init_dense(ke, (cfg.vocab_size, cfg.d_model), scale=0.02),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = init_dense(kh, (cfg.vocab_size, cfg.d_model), scale=0.02)

    # Unscanned prologue layers.
    prologue = []
    for i in range(cfg.n_dense_prologue):
        kp, sub = jax.random.split(kp)
        prologue.append(blocks.init_layer(sub, cfg, cfg.pattern[0], "dense"))
    if prologue:
        params["prologue"] = prologue

    # Scanned periods: one stacked tree per pattern position.
    period_params = {}
    for j, kind in enumerate(cfg.pattern):
        ffn = cfg.ffn_kind(cfg.n_dense_prologue + j)
        ks, sub = jax.random.split(ks)
        keys = jax.random.split(sub, cfg.n_periods)
        period_params[f"pos{j}"] = jax.vmap(
            lambda k: blocks.init_layer(k, cfg, kind, ffn)
        )(keys)
    params["periods"] = period_params
    return params


def init_decode_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    cache: dict = {}
    if cfg.n_dense_prologue:
        cache["prologue"] = [
            blocks.init_layer_cache(cfg, cfg.pattern[0], batch, max_seq)
            for _ in range(cfg.n_dense_prologue)
        ]
    periods = {}
    for j, kind in enumerate(cfg.pattern):
        one = blocks.init_layer_cache(cfg, kind, batch, max_seq)
        periods[f"pos{j}"] = (
            None if one is None
            else jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_periods, *x.shape)).copy(), one)
        )
    cache["periods"] = periods
    return cache


def _map_layer_caches(cfg: ModelConfig, fn, *caches):
    """Apply ``fn(kind, *layer_caches)`` over every layer cache of the trees.

    Prologue caches have their natural (B, ...) layout; scanned-period caches
    carry a leading ``n_periods`` axis, handled by vmapping ``fn`` over it.
    Walks the same structure ``init_decode_cache`` builds.
    """
    out: dict = {}
    if "prologue" in caches[0]:
        kind = cfg.pattern[0]
        out["prologue"] = [
            fn(kind, *(c["prologue"][i] for c in caches))
            for i in range(cfg.n_dense_prologue)
        ]
    periods = {}
    for j, kind in enumerate(cfg.pattern):
        layer = tuple(c["periods"][f"pos{j}"] for c in caches)
        periods[f"pos{j}"] = (
            None if layer[0] is None
            else jax.vmap(functools.partial(fn, kind))(*layer))
    out["periods"] = periods
    return out


def cache_slot_insert(cfg: ModelConfig, pool: dict, src: dict,
                      slots: jnp.ndarray) -> dict:
    """Insert the batch rows of a freshly prefilled cache into pool ``slots``.

    ``src`` comes from ``init_decode_cache(cfg, G, max_seq)`` + a bulk
    prefill of G admitted prompts (same ``max_seq`` as the pool); row i goes
    into pool slot ``slots[i]``.  Rows of other slots are untouched
    (bitwise), which is what makes mid-decode admission safe.
    """
    return _map_layer_caches(
        cfg, lambda kind, c, s: blocks.slot_insert_cache(kind, c, s, slots),
        pool, src)


def cache_expand_rows(cfg: ModelConfig, cache: dict, inv: jnp.ndarray) -> dict:
    """Gather batch rows ``inv`` of every layer cache — (G_unique, …) →
    (G, …).  Used by the admission dedupe: a group's unique prompts prefill
    once and the filled rows are expanded back to one per request.  Goes
    through ``_map_layer_caches`` because the batch axis sits behind the
    scanned ``n_periods`` axis on period leaves."""
    return _map_layer_caches(
        cfg,
        lambda kind, c: (None if c is None
                         else jax.tree.map(lambda x: x[inv], c)),
        cache)


def cache_slot_reset(cfg: ModelConfig, pool: dict, slots: jnp.ndarray) -> dict:
    """Zero pool ``slots`` — bitwise identical to freshly initialized rows."""
    return _map_layer_caches(
        cfg, lambda kind, c: blocks.slot_reset_cache(kind, c, slots), pool)


# --------------------------------------------------------------------------
# paged decode cache (DESIGN.md §13)
#
# The paged pool is a *split* pair of trees with the same layer structure as
# ``init_decode_cache``:
#
# * ``pages``  — (num_pages, page_size, …) arenas for layers whose cache has
#   a sequence axis (attention/MLA); None at recurrent/cacheless positions.
# * ``state``  — plain (n_slots, …) rows for recurrent layers (mamba/RWKV);
#   None at paged/cacheless positions.
#
# A decode tick gathers per-slot views from ``pages`` through the page
# table, merges in ``state`` (pure host-side structure surgery — no copies),
# runs the SAME compiled decode step as the contiguous engine on the merged
# tree, then commits the written position back to ``pages`` and re-extracts
# ``state``.  The split exists because decode donates its cache argument:
# recurrent leaves passed through a gather jit unchanged would alias the
# pool's buffers, and donation would free them under it.
# --------------------------------------------------------------------------

_RECURRENT_KINDS = ("mamba", "rwkv")


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> dict:
    """Page-arena tree: one (num_pages, page_size, …) arena per paged layer
    (scanned periods carry the usual leading ``n_periods`` axis); a single
    page id addresses the same physical page in every arena."""
    cache: dict = {}
    if cfg.n_dense_prologue:
        cache["prologue"] = [
            blocks.init_paged_layer_cache(cfg, cfg.pattern[0], num_pages,
                                          page_size)
            for _ in range(cfg.n_dense_prologue)
        ]
    periods = {}
    for j, kind in enumerate(cfg.pattern):
        one = blocks.init_paged_layer_cache(cfg, kind, num_pages, page_size)
        periods[f"pos{j}"] = (
            None if one is None
            else jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_periods, *x.shape)).copy(), one)
        )
    cache["periods"] = periods
    return cache


def init_paged_state(cfg: ModelConfig, n_slots: int) -> dict:
    """Recurrent-state tree: (n_slots, …) rows for mamba/RWKV layers only."""
    cache: dict = {}
    if cfg.n_dense_prologue:
        cache["prologue"] = [
            blocks.init_paged_state_cache(cfg, cfg.pattern[0], n_slots)
            for _ in range(cfg.n_dense_prologue)
        ]
    periods = {}
    for j, kind in enumerate(cfg.pattern):
        one = blocks.init_paged_state_cache(cfg, kind, n_slots)
        periods[f"pos{j}"] = (
            None if one is None
            else jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_periods, *x.shape)).copy(), one)
        )
    cache["periods"] = periods
    return cache


def paged_gather_cache(cfg: ModelConfig, pages: dict, pt: jnp.ndarray,
                       max_seq: int) -> dict:
    """Gather per-slot contiguous views from every page arena (unmapped
    table entries read the reserved zero page → fresh-cache bytes)."""
    return _map_layer_caches(
        cfg,
        lambda kind, c: blocks.paged_view_cache(cfg, kind, c, pt, max_seq),
        pages)


def paged_commit_cache(cfg: ModelConfig, pages: dict, view: dict,
                       pt: jnp.ndarray, pos: jnp.ndarray,
                       max_seq: int) -> dict:
    """Scatter the position each active slot just wrote in ``view`` back
    into the arenas (ring-adjusted per layer family)."""
    return _map_layer_caches(
        cfg,
        lambda kind, c, v: blocks.paged_commit_cache(cfg, kind, c, v, pt,
                                                     pos, max_seq),
        pages, view)


def paged_insert_cache(cfg: ModelConfig, pages: dict, src: dict,
                       pt_rows: jnp.ndarray) -> dict:
    """Scatter freshly prefilled cache rows into newly mapped pages
    (``src`` is the same tree ``cache_slot_insert`` takes)."""
    return _map_layer_caches(
        cfg,
        lambda kind, c, s: blocks.paged_insert_cache(kind, c, s, pt_rows),
        pages, src)


def paged_copy_pages(cfg: ModelConfig, pages: dict, src_ids: jnp.ndarray,
                     dst_ids: jnp.ndarray) -> dict:
    """Copy whole pages across every arena (COW fork).  Padding the id
    vectors with (0, 0) makes the batch shape static — copying the zero
    page onto itself is a no-op."""
    return _map_layer_caches(
        cfg,
        lambda kind, c: blocks.paged_copy_pages(kind, c, src_ids, dst_ids),
        pages)


def merge_paged_view(cfg: ModelConfig, view: dict, state: dict) -> dict:
    """Splice gathered paged views and recurrent state rows into one full
    cache tree (host-side structure surgery — the merged tree references
    the same buffers, byte-equal to the contiguous engine's pool)."""
    out: dict = {}
    if "prologue" in view:
        out["prologue"] = [
            v if v is not None else s
            for v, s in zip(view["prologue"], state["prologue"])
        ]
    out["periods"] = {
        key: (v if v is not None else state["periods"][key])
        for key, v in view["periods"].items()
    }
    return out


def extract_paged_state(cfg: ModelConfig, cache: dict) -> dict:
    """Select the recurrent-state half of a full cache tree (pure structural
    selection — no copies; the leaves stay the decode step's outputs)."""
    out: dict = {}
    if "prologue" in cache:
        keep = cfg.pattern[0] in _RECURRENT_KINDS
        out["prologue"] = [c if keep else None for c in cache["prologue"]]
    out["periods"] = {
        f"pos{j}": (cache["periods"][f"pos{j}"]
                    if kind in _RECURRENT_KINDS else None)
        for j, kind in enumerate(cfg.pattern)
    }
    return out


def extract_state_rows(cfg: ModelConfig, cache: dict, row: int) -> dict:
    """Slice one batch row of the recurrent leaves of a freshly prefilled
    cache — the constant-size state a prefix-cache entry stores."""
    state = extract_paged_state(cfg, cache)
    out: dict = {}
    if "prologue" in state:
        out["prologue"] = [
            None if c is None else jax.tree.map(lambda x: x[row:row + 1], c)
            for c in state["prologue"]
        ]
    out["periods"] = {
        key: (None if c is None
              else jax.tree.map(lambda x: x[:, row:row + 1], c))
        for key, c in state["periods"].items()
    }
    return out


def cache_snapshot(cfg: ModelConfig, cache: dict) -> dict:
    """The per-step rollback state speculative decode must keep (§11).

    Returns a tree of the same layer structure as ``cache`` where every leaf
    that cannot be rewound by position alone (recurrent mamba/rwkv state,
    rolling SWA rings — ``blocks.cache_needs_snapshot``) is the layer's
    current cache, and every positionally-rewindable layer is an empty
    ``()`` placeholder.  Stacked over the draft scan, these snapshots let
    ``cache_rollback`` commit the exact post-step-``m`` state.
    """
    def pick(kind, c):
        return c if blocks.cache_needs_snapshot(cfg, kind, c) else ()

    return _map_layer_caches(cfg, pick, cache)


def cache_rollback(cfg: ModelConfig, cache: dict, snap: dict) -> dict:
    """Commit a speculative block: merge a selected step's snapshot leaves
    back over the draft-final ``cache``.

    Snapshot-kind layers take the snapshot (the bitwise state after the
    accepted step); positional layers keep the draft-final buffers — their
    stale entries beyond the rewound position counter are masked by the
    ``k_pos < cache_pos + 1`` decode check and overwritten before they can
    ever be attended (models/attention.py, models/mla.py).
    """
    def merge(kind, c, s):
        return s if blocks.cache_needs_snapshot(cfg, kind, c) else c

    return _map_layer_caches(cfg, merge, cache, snap)


@jax.named_scope("head")
def dense_verify_logits(params: dict, hidden: jnp.ndarray,
                        cfg: ModelConfig) -> jnp.ndarray:
    """``forward()``'s dense unembed tail on externally-carried hiddens.

    ``hidden`` is the f32 output of ``return_hidden=True`` — it round-trips
    exactly to the bf16 final-norm activations it came from (bf16→f32 is
    injective), so casting back to the table dtype reproduces the very
    einsum ``forward`` would have run.  A 2-D (B, d) input is lifted to the
    (B, 1, d) decode shape before the contraction: XLA's 2-D matmul is *not*
    bitwise-identical to the 3-D einsum rows, and bitwise parity with the
    in-forward path is the whole point (tests/test_spec_decode.py).  A 3-D
    (K, B, d) block — the stacked hiddens of a speculative draft scan — maps
    row-for-row to the per-step logits.
    """
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    squeeze = hidden.ndim == 2
    if squeeze:
        hidden = hidden[:, None, :]
    logits = unembed(hidden.astype(table.dtype), table).astype(jnp.float32)
    logits = constrain(logits, "dp", None, "tp")  # vocab-parallel logits
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits[:, 0] if squeeze else logits


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _period_body(cfg: ModelConfig, x, positions, period_params, period_cache,
                 encoder_states, cache_pos, active):
    """Apply one period (all pattern positions). Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    for j, kind in enumerate(cfg.pattern):
        ffn = cfg.ffn_kind(cfg.n_dense_prologue + j)
        layer_cache = None if period_cache is None else period_cache.get(f"pos{j}")
        x, nc, a = blocks.apply_layer(
            period_params[f"pos{j}"], x, positions, cfg, kind, ffn,
            encoder_states=encoder_states, cache=layer_cache,
            cache_pos=cache_pos, active=active)
        new_cache[f"pos{j}"] = nc
        aux = aux + a
    return x, new_cache, aux


def forward(
    params: dict,
    tokens: jnp.ndarray,                     # (B, S) int32
    cfg: ModelConfig,
    *,
    encoder_states: Optional[jnp.ndarray] = None,   # (B, T_enc, d) stub frontend
    cache: Optional[dict] = None,
    cache_pos: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,    # (B,) bool: decode rows to write
    remat: bool = True,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, Optional[dict], jnp.ndarray]:
    """Run the backbone. Returns (logits, new_cache, aux_loss).

    ``active`` masks a decode step's cache writes: every layer holds back
    the write of a row left clear, so that row's cache comes back bitwise
    unchanged (``layers.hold_parked``).  ``None`` writes every row.

    ``return_hidden=True`` stops after the final norm and returns the
    (B, S, d_model) f32 hidden states in place of logits — the input the
    Representer-Sketch head consumes instead of the dense unembed
    (repro.core.sketch_lm_head / repro.kernels.fused_decode).
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = embed(tokens, params["embed"]) * jnp.asarray(
            cfg.d_model ** 0.5, jnp.bfloat16)
        x = constrain(x, "dp", None, None)
    if cache_pos is None:
        positions = jnp.arange(s)
        cache_pos_v = jnp.zeros((), jnp.int32)
    elif jnp.ndim(cache_pos) == 1:
        # Per-slot position counters (continuous-batching decode): every
        # sequence is at its own depth, so RoPE angles and attention masks
        # become (B, S)-shaped.
        positions = cache_pos[:, None] + jnp.arange(s)[None, :]
        cache_pos_v = cache_pos
    else:
        positions = cache_pos + jnp.arange(s)
        cache_pos_v = cache_pos

    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    # Prologue (unscanned).
    if "prologue" in params:
        pcaches = (cache or {}).get("prologue", [None] * cfg.n_dense_prologue)
        new_p = []
        for i, lp in enumerate(params["prologue"]):
            x, nc, a = blocks.apply_layer(
                lp, x, positions, cfg, cfg.pattern[0], "dense",
                encoder_states=encoder_states, cache=pcaches[i],
                cache_pos=cache_pos_v, active=active)
            new_p.append(nc)
            aux = aux + a
        if cache is not None:
            new_cache["prologue"] = new_p

    # Scanned periods.  The stacked cache rides in the carry and each period
    # writes its own layer back in place: scanned in and out, it would be
    # two buffers, and XLA would copy the whole pool into the second one
    # every step.
    def body(carry, scanned):
        xc, auxc, pcs = carry
        pp, i = scanned
        pc = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, i, keepdims=False), pcs)
        xc, nc, a = _period_body(cfg, xc, positions, pp, pc,
                                 encoder_states, cache_pos_v, active)
        if pcs is not None:
            pcs = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
                pcs, nc)
        return (xc, auxc + a, pcs), None

    if remat and cache is None:
        body = jax.checkpoint(body)

    (x, aux, period_cache), _ = jax.lax.scan(
        body, (x, aux, (cache or {}).get("periods")),
        (params["periods"], jnp.arange(cfg.n_periods)))
    if cache is not None:
        new_cache["periods"] = period_cache

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return (x.astype(jnp.float32),
                (new_cache if cache is not None else None), aux)
    with jax.named_scope("head"):
        table = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = unembed(x, table).astype(jnp.float32)
        logits = constrain(logits, "dp", None, "tp")  # vocab-parallel logits
        if cfg.final_logit_softcap:
            logits = softcap(logits, cfg.final_logit_softcap)
    return logits, (new_cache if cache is not None else None), aux


# --------------------------------------------------------------------------
# losses / steps
# --------------------------------------------------------------------------

def lm_loss(
    params: dict,
    tokens: jnp.ndarray,       # (B, S)
    labels: jnp.ndarray,       # (B, S) — next-token targets, -1 = masked
    cfg: ModelConfig,
    *,
    encoder_states: Optional[jnp.ndarray] = None,
    aux_coef: float = 0.01,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, _, aux = forward(params, tokens, cfg, encoder_states=encoder_states)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    # Vocab-parallel cross-entropy: never gather the (B, S, V) logits.
    # logsumexp reduces over the sharded vocab axis (small all-reduce of
    # (B, S) stats); the label logit is picked with an iota==label mask that
    # the SPMD partitioner keeps sharded — no 26 GB take_along_axis gather.
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    iota_v = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    label_logit = jnp.sum(
        jnp.where(iota_v == safe[..., None], logits, 0.0), axis=-1)
    nll = lse - label_logit
    denom = jnp.maximum(jnp.sum(valid), 1)
    ce = jnp.sum(jnp.where(valid, nll, 0.0)) / denom
    loss = ce + aux_coef * aux
    return loss, {"ce": ce, "aux": aux}


def decode_step(
    params: dict,
    cache: dict,
    tokens: jnp.ndarray,       # (B, 1) — the newest token
    pos: jnp.ndarray,          # int32 tokens-already-cached: scalar, or (B,)
                               # per-slot counters (continuous batching)
    cfg: ModelConfig,
    *,
    encoder_states: Optional[jnp.ndarray] = None,
    active: Optional[jnp.ndarray] = None,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, dict]:
    """One decode step: returns (logits (B, V), updated cache).

    ``active`` (B,) bool keeps the cache rows of parked slots bitwise
    unchanged (``forward``).  ``return_hidden=True`` returns the
    (B, d_model) final hidden instead of logits — the dense unembed is
    skipped entirely so a sketched head can replace it (the paper's serving
    hot path).
    """
    out, new_cache, _ = forward(
        params, tokens, cfg, encoder_states=encoder_states,
        cache=cache, cache_pos=pos, active=active, remat=False,
        return_hidden=return_hidden)
    return out[:, -1], new_cache
