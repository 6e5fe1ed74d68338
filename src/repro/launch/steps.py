"""Jittable train / prefill / serve steps + abstract input specs per cell.

``input_specs(arch, shape)`` returns ShapeDtypeStruct stand-ins for every
input of the step that the (arch × shape) cell lowers — weak-type-correct,
shardable, and allocation-free, so the dry-run can ``.lower().compile()``
the production mesh without any device memory.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.heads import DenseHead, LogitHead, SketchHead
from repro.configs import SHAPES, get_config
from repro.models.config import ModelConfig, SketchHeadConfig
from repro.models.model import (decode_step, forward, init_decode_cache,
                                init_model, lm_loss)
from repro.optim.adamw import AdamWState, OptimizerConfig, adamw_update, init_adamw


# --------------------------------------------------------------------------
# steps
# --------------------------------------------------------------------------

def opt_config_for(cfg: ModelConfig, **kw) -> OptimizerConfig:
    """Default optimizer config per arch: above ~100B params use lean state
    (671B-class can't hold f32 master+moments on 16 GB chips) and
    8-way gradient accumulation (bounds activation transients)."""
    from repro.models.config import param_count
    big = param_count(cfg) > 100e9
    # accum sweep on deepseek-v3 train (§Perf iter 7): temp 307→77 GB going
    # 1→8, but FSDP expert weights re-gather once per microbatch, so
    # collective bytes rise 2.06e12→5.55e12 and bytes-accessed 4.5→7.4e13.
    # accum=2 keeps most of the transient relief at ~1.3× collective cost.
    kw.setdefault("grad_accum", 2 if big else 1)
    return OptimizerConfig(lean=big, **kw)


def train_step(params, opt_state: AdamWState, batch: Dict[str, jnp.ndarray],
               cfg: ModelConfig, opt_cfg: OptimizerConfig):
    """One optimizer step. batch: tokens, labels[, encoder_states].

    With ``opt_cfg.grad_accum > 1`` the batch is split into microbatches
    along the batch axis and gradients are accumulated in a ``lax.scan`` —
    activation transients shrink by the accumulation factor while the
    optimizer sees the same global batch.
    """
    accum = opt_cfg.grad_accum

    def loss_fn(p, mb):
        return lm_loss(p, mb["tokens"], mb["labels"], cfg,
                       encoder_states=mb.get("encoder_states"))

    if accum == 1:
        (loss, parts), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
    else:
        micro = jax.tree.map(
            lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
            batch)

        def acc_step(carry, mb):
            gacc, lacc, pacc = carry
            (l, pr), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            gacc = jax.tree.map(lambda a, b: a + b / accum, gacc, g)
            pacc = jax.tree.map(lambda a, b: a + b / accum, pacc, pr)
            return (gacc, lacc + l / accum, pacc), None

        zeros_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                               params)
        zeros_p = {"ce": jnp.zeros(()), "aux": jnp.zeros(())}
        (grads, loss, parts), _ = jax.lax.scan(
            acc_step, (zeros_g, jnp.zeros(()), zeros_p), micro)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)

    new_params, new_opt, opt_metrics = adamw_update(grads, opt_state, opt_cfg,
                                                    params=params)
    metrics = {"loss": loss, **parts, **opt_metrics}
    return new_params, new_opt, metrics


def _constrain_cache(cache, mesh):
    """Pin a (traced) decode cache to its per-leaf mesh sharding.

    Applied inside the jitted serve fns so prefill / decode / slot ops
    *preserve* cache shardings step over step instead of letting the SPMD
    partitioner drift (or worse, gather a slot pool to one device).
    """
    from repro.sharding.rules import cache_shardings
    return jax.lax.with_sharding_constraint(
        cache, cache_shardings(cache, mesh))


def prefill_step(params, tokens, cfg: ModelConfig,
                 encoder_states=None, cache=None, mesh=None):
    """Context ingestion: forward pass returning last-position logits.

    Without a cache this is the abstract dry-run shape (logits only).  With
    ``cache`` it is the serving bulk prefill: the whole (B, P) prompt runs in
    one forward pass that fills the decode cache, and ``(last_logits,
    new_cache)`` is returned — replacing P per-token decode steps.  With
    ``mesh`` the filled cache is constrained to the serving cache shardings
    (batch over data axes, features over model).
    """
    if cache is None:
        logits, _, _ = forward(params, tokens, cfg,
                               encoder_states=encoder_states, remat=False)
        return logits[:, -1]
    logits, new_cache, _ = forward(
        params, tokens, cfg, encoder_states=encoder_states,
        cache=cache, cache_pos=jnp.zeros((), jnp.int32), remat=False)
    if mesh is not None:
        new_cache = _constrain_cache(new_cache, mesh)
    return logits[:, -1], new_cache


def _legacy_sketch_spec(sketch_cfg, fused, params=None) -> SketchHead:
    """The single legacy (sketch_cfg, fused) → SketchHead mapping.

    Every deprecation shim funnels through here so the mapping cannot drift
    between call sites.  Serving frozen arrays without their config was a
    crash before the redesign; keep it a hard error rather than silently
    hashing with default bandwidth/buckets (which emits wrong tokens).
    """
    if sketch_cfg is None:
        raise ValueError(
            "legacy sketch-head params were passed without sketch_cfg; the "
            "frozen arrays are unusable without their SketchHeadConfig — "
            "pass head=repro.api.SketchHead(cfg=..., params=...) instead")
    return SketchHead(cfg=sketch_cfg,
                      backend="fused" if fused in (None, True)
                      else "two_kernel",
                      params=params)


def resolve_legacy_serving_kwargs(head, sampler, sketch_params, sketch_cfg,
                                  fused, greedy, seed, caller: str):
    """Map the pre-redesign serving kwargs (sketch head params/cfg +
    ``fused``/``greedy``/``seed``) onto (LogitHead, Sampler) for one release
    of grace.  Shared by generate(), the engine, and make_engine."""
    from repro.api.sampler import Sampler

    if (sketch_params is None and sketch_cfg is None and fused is None
            and greedy is None and seed is None):
        return head, sampler
    warnings.warn(
        f"the legacy {caller} kwargs (sketch head params/cfg, fused=, "
        f"greedy=, seed=) are deprecated; pass "
        f"head=repro.api.SketchHead(...) and sampler=repro.api.Sampler(...) "
        f"instead", DeprecationWarning, stacklevel=3)
    if head is None and (sketch_params is not None or sketch_cfg is not None):
        head = _legacy_sketch_spec(sketch_cfg, fused, sketch_params)
    if sampler is None and (greedy is not None or seed is not None):
        sampler = (Sampler() if greedy in (None, True)
                   else Sampler(temperature=1.0, seed=seed or 0))
    return head, sampler


def _resolve_head_shim(head, head_params, sketch_head, sketch_cfg, fused):
    """Map the pre-redesign ``sketch_head=/sketch_cfg=/fused=`` kwargs onto
    a (LogitHead spec, runtime params) pair.  One release of grace."""
    if sketch_head is None and sketch_cfg is None and fused is None:
        return head or DenseHead(), head_params
    warnings.warn(
        "serve_step(sketch_head=, sketch_cfg=, fused=) is deprecated; pass "
        "head=repro.api.SketchHead(cfg=..., backend=...) and "
        "head_params=<frozen arrays> instead", DeprecationWarning,
        stacklevel=3)
    if head is None and (sketch_head is not None or sketch_cfg is not None):
        head = _legacy_sketch_spec(sketch_cfg, fused)
    if head_params is None:
        head_params = sketch_head
    return head or DenseHead(), head_params


def serve_step(params, cache, tokens, pos, cfg: ModelConfig,
               encoder_states=None, head: Optional[LogitHead] = None,
               head_params=None, active=None, mesh=None,
               return_hidden: bool = False, sketch_head=None,
               sketch_cfg: Optional[SketchHeadConfig] = None, fused=None):
    """One decode step (one new token per sequence against the cache).

    ``head`` is a :class:`repro.api.heads.LogitHead` *spec* (hashable —
    close over it via functools.partial before jit).  A ``DenseHead`` (the
    default) takes the backbone's own unembed logits.  A head with
    ``needs_hidden`` (e.g. ``SketchHead``) skips the dense h·Wᵀ matmul
    entirely: the backbone returns the final hidden and the head produces
    the (B, V) logits on its configured backend (``fused`` — one Pallas
    call, ``two_kernel``, or ``ref``); its frozen arrays arrive as the
    runtime argument ``head_params``.  The old ``sketch_head=/sketch_cfg=/
    fused=`` kwargs still work behind a DeprecationWarning.

    Continuous batching: ``pos`` may be per-slot (B,) counters, and
    ``active`` a (B,) bool mask — cache rows of inactive (free/padded) slots
    are kept bitwise unchanged, so a parked SWA ring does not advance and a
    parked state does not decay while the slot waits for a new request.
    Each layer holds back a parked row's write where it writes its cache
    (one K/V entry, or the new recurrent state); nothing passes over the
    whole pool.  Callers ignore a parked row's logits.

    Sharded serving: ``mesh`` (static; threaded by ``jitted_serve_fns``)
    routes stateful heads through their shard_map path and re-constrains the
    updated cache to the serving cache shardings every step.

    ``return_hidden=True`` additionally returns the (B, d_model) final
    hidden as a third element — the input a speculative verify pass consumes
    (DESIGN.md §11).  A ``DenseHead`` under this flag produces its logits
    via ``dense_verify_logits`` on that hidden, bitwise-identical to the
    in-backbone unembed it normally takes.
    """
    head, head_params = _resolve_head_shim(head, head_params, sketch_head,
                                           sketch_cfg, fused)
    hidden = None
    if not head.needs_hidden and not return_hidden:
        logits, new_cache = decode_step(params, cache, tokens, pos, cfg,
                                        encoder_states=encoder_states,
                                        active=active)
    else:
        from repro.models.layers import softcap

        hidden, new_cache = decode_step(params, cache, tokens, pos, cfg,
                                        encoder_states=encoder_states,
                                        active=active, return_hidden=True)
        if head.needs_hidden:
            with jax.named_scope("head"):
                logits = head.apply(head_params, hidden, mesh=mesh)
                if cfg.final_logit_softcap:
                    logits = softcap(logits, cfg.final_logit_softcap)
        else:
            from repro.models.model import dense_verify_logits
            logits = dense_verify_logits(params, hidden, cfg)
    if mesh is not None:
        new_cache = _constrain_cache(new_cache, mesh)
    if return_hidden:
        return logits, new_cache, hidden
    return logits, new_cache


class PagedServeFns(tuple):
    """Jitted paged-pool ops for one (cfg, mesh, max_seq, page_size) spec
    (DESIGN.md §13).  ``gather(pages, pt)`` materializes per-slot views;
    ``commit(pages, view, pt, pos)`` scatters the decode-written position
    back; ``insert(pages, src, pt_rows)`` lands freshly prefilled rows;
    ``page_copy(pages, src_ids, dst_ids)`` forks COW pages.  Everything but
    ``gather`` **donates** the arena — rebind to the returned tree.
    """

    def __new__(cls, gather, commit, insert, page_copy, page_size, max_seq):
        self = super().__new__(cls, (gather, commit, insert, page_copy))
        self.gather, self.commit = gather, commit
        self.insert, self.page_copy = insert, page_copy
        self.page_size, self.max_seq = page_size, max_seq
        return self


class ServeFns(tuple):
    """The jitted serving callables for one (cfg, head, mesh, chunk) spec.

    Unpacks as the legacy 4-tuple ``(prefill, decode, insert, reset)``;
    the on-device K-step decode loop is the extra ``megastep`` attribute
    (``None`` at ``decode_chunk=1`` — the bitwise-parity host-loop default)
    and the speculative two-head megastep is ``spec_megastep`` (``None``
    unless requested via ``spec_decode=K``).  With ``paged=True`` the
    ``paged_ops`` attribute carries the :class:`PagedServeFns` arena ops —
    the core decode itself stays the *same* compiled executable, fed the
    gathered view (that identity is the bitwise-parity argument).
    ``decode`` / ``insert`` / ``reset`` / ``megastep`` / ``spec_megastep``
    **donate** their cache/pool argument: the passed-in cache is consumed
    and callers must rebind to the returned one (launch/decode_loop.py).
    """

    def __new__(cls, prefill, decode, insert, reset, megastep=None,
                spec_megastep=None, paged_ops=None):
        self = super().__new__(cls, (prefill, decode, insert, reset))
        self.prefill, self.decode = prefill, decode
        self.insert, self.reset = insert, reset
        self.megastep = megastep
        self.spec_megastep = spec_megastep
        self.paged_ops = paged_ops
        return self


def jitted_serve_fns(cfg: ModelConfig, head: Optional[LogitHead] = None,
                     fused=None, *, mesh=None, sampler=None,
                     decode_chunk: int = 1, spec_decode: int = 0,
                     eos_id: Optional[int] = None, paged: bool = False,
                     page_size: int = 16, max_seq: Optional[int] = None):
    """Jitted (prefill, decode, slot_insert, slot_reset[, megastep]) for one
    serving config.  Memoized on ``(cfg, head spec, mesh, sampler,
    decode_chunk, eos_id)`` — all hashable — so every ``generate()`` call
    and every engine instance for the same spec reuses one compile cache; a
    fresh ``jax.jit(partial(...))`` per call would recompile each time.  The
    head's frozen arrays are *not* part of the key: pass them per call as
    ``head_params``.

    ``decode`` and the slot ops **donate** their cache/pool argument —
    the update happens in place instead of copying the full cache per
    token — so a cache passed in is consumed; rebind to the returned one.
    With ``decode_chunk > 1`` (needs ``sampler``), the returned struct's
    ``megastep`` is the on-device K-step decode loop
    (``launch.decode_loop.jitted_megastep``) fusing that sampler and the
    ``eos_id`` retirement into one ``lax.scan`` dispatch.

    With ``spec_decode = K > 0`` (needs ``sampler``; mutually exclusive with
    ``decode_chunk > 1``), the returned struct's ``spec_megastep`` is the
    speculative two-head megastep
    (``launch.decode_loop.jitted_spec_megastep``): the ``head`` drafts K
    tokens through the backbone and one batched dense pass verifies the
    block, emitting a stream bitwise-identical to pure dense decode
    (DESIGN.md §11).

    With ``mesh``, every returned fn is mesh-aware: prefill/decode constrain
    their output cache to the serving cache shardings, stateful heads run
    their shard_map path, and the slot ops preserve the pool's shardings
    across insert/reset instead of letting rows gather to one device —
    donation aliases buffers shard-for-shard under the same constraints.

    With ``paged=True`` (needs ``max_seq``; host decode loop only, so
    mutually exclusive with ``decode_chunk > 1`` and ``spec_decode``), the
    returned struct's ``paged_ops`` carries the jitted page-arena ops
    (:class:`PagedServeFns`); the core four fns are unchanged — the paged
    engine feeds the *same* compiled decode the gathered view.

    Accepts the pre-redesign ``(cfg, sketch_cfg, fused)`` calling convention
    behind a DeprecationWarning.
    """
    if isinstance(head, SketchHeadConfig) or fused is not None:
        warnings.warn(
            "jitted_serve_fns(cfg, sketch_cfg, fused) is deprecated; pass a "
            "repro.api LogitHead spec instead", DeprecationWarning,
            stacklevel=2)
        sketch_cfg = head if isinstance(head, SketchHeadConfig) else None
        head = (_legacy_sketch_spec(sketch_cfg, fused)
                if sketch_cfg is not None else DenseHead())
    head = (head or DenseHead()).without_params()
    if decode_chunk < 1:
        raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
    if decode_chunk > 1 and sampler is None:
        raise ValueError("decode_chunk > 1 fuses sampling into the decode "
                         "scan; pass sampler=repro.api.Sampler(...)")
    if spec_decode < 0:
        raise ValueError(f"spec_decode must be >= 0, got {spec_decode}")
    if spec_decode and decode_chunk > 1:
        raise ValueError("spec_decode and decode_chunk > 1 are mutually "
                         "exclusive: the speculative megastep already "
                         "advances up to K tokens per dispatch")
    if spec_decode and sampler is None:
        raise ValueError("spec_decode fuses sampling into the draft/verify "
                         "scan; pass sampler=repro.api.Sampler(...)")
    if spec_decode and getattr(head, "per_tenant", False):
        raise ValueError("spec_decode and per-tenant heads are mutually "
                         "exclusive: the draft/verify megastep re-reads the "
                         "head inside its scan and cannot re-gather per-slot "
                         "tenant bindings mid-draft")
    if paged:
        if decode_chunk > 1:
            raise ValueError("paged serving gathers/commits pages around "
                             "each host decode step; decode_chunk > 1 (the "
                             "on-device megastep) is not supported yet")
        if spec_decode:
            raise ValueError("paged serving and spec_decode are mutually "
                             "exclusive: the draft/verify megastep manages "
                             "its own contiguous pool")
        if max_seq is None:
            raise ValueError("paged=True needs max_seq= to size the "
                             "per-slot page tables")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
    # The four core fns don't depend on (sampler, decode_chunk, eos_id), so
    # they memoize on (cfg, head, mesh) alone — a new sampler spec must not
    # recompile the model steps.  The megasteps have their own memo caches in
    # decode_loop.py keyed on the full spec.
    fns = _jitted_serve_fns(cfg, head, mesh)
    if paged:
        return ServeFns(*fns, None, None,
                        _paged_serve_fns(cfg, mesh, max_seq, page_size))
    if decode_chunk == 1 and not spec_decode:
        return fns   # the memoized instance itself (stable identity)
    if spec_decode:
        from repro.launch.decode_loop import jitted_spec_megastep
        return ServeFns(*fns, None,
                        jitted_spec_megastep(cfg, head, sampler, spec_decode,
                                             mesh=mesh, eos_id=eos_id,
                                             masked=True))
    from repro.launch.decode_loop import jitted_megastep
    return ServeFns(*fns, jitted_megastep(cfg, head, sampler, decode_chunk,
                                          mesh=mesh, eos_id=eos_id,
                                          masked=True))


# Every jitted serving program is a named function, never a bare
# ``functools.partial``: its name is the program's (``jit_<name>``) in the
# HLO, on a device trace's ``XLA Modules`` line and in xprof, where a
# partial would show as ``jit__unknown`` (DESIGN.md §15).

@functools.lru_cache(maxsize=None)
def _jitted_serve_fns(cfg: ModelConfig, head: LogitHead, mesh=None):
    prefill, insert, reset = _jitted_head_free_fns(cfg, mesh)

    def decode(params, cache, tokens, pos, **kw):
        return serve_step(params, cache, tokens, pos, cfg, head=head,
                          mesh=mesh, **kw)

    return ServeFns(prefill, jax.jit(decode, donate_argnums=(1,)), insert,
                    reset)


@functools.lru_cache(maxsize=None)
def _jitted_head_free_fns(cfg: ModelConfig, mesh=None):
    """Prefill and the slot ops never meet the logit head: one compile per
    (cfg, mesh), shared by every head served over that backbone."""
    from repro.models.model import cache_slot_insert, cache_slot_reset

    def constrain(cache):
        return cache if mesh is None else _constrain_cache(cache, mesh)

    def prefill(params, tokens, **kw):
        return prefill_step(params, tokens, cfg, mesh=mesh, **kw)

    def slot_insert(pool, src, slots):
        return constrain(cache_slot_insert(cfg, pool, src, slots))

    def slot_reset(pool, slots):
        return constrain(cache_slot_reset(cfg, pool, slots))

    return (jax.jit(prefill), jax.jit(slot_insert, donate_argnums=(0,)),
            jax.jit(slot_reset, donate_argnums=(0,)))


@functools.lru_cache(maxsize=None)
def fresh_cache_fn(cfg: ModelConfig, mesh=None):
    """Jitted ``fresh_cache(batch, max_seq)``: an empty decode cache for a
    prefill batch, made on the device in one program (on a mesh, in the
    serving cache shardings)."""
    def fresh_cache(batch, max_seq):
        cache = init_decode_cache(cfg, batch, max_seq)
        return cache if mesh is None else _constrain_cache(cache, mesh)

    return jax.jit(fresh_cache, static_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _paged_serve_fns(cfg: ModelConfig, mesh, max_seq: int, page_size: int):
    """Jitted page-arena ops, memoized per (cfg, mesh, max_seq, page_size).

    Head-independent: the arena never meets the logit head, so every head
    spec over the same backbone shares one compile cache.  ``gather`` is the
    only non-donating op (the arena must survive it — the view is a copy);
    ``commit`` / ``insert`` / ``page_copy`` donate the arena and the caller
    rebinds.  Under a mesh, views are constrained to the contiguous cache
    shardings (so the shared decode executable sees identical layouts) and
    arenas to ``page_pool_shardings``.
    """
    from repro.models.model import (paged_commit_cache, paged_copy_pages,
                                    paged_gather_cache, paged_insert_cache)

    def constrain_pages(pages):
        if mesh is None:
            return pages
        from repro.sharding.rules import page_pool_shardings
        return jax.lax.with_sharding_constraint(
            pages, page_pool_shardings(pages, mesh))

    def gather(pages, pt):
        view = paged_gather_cache(cfg, pages, pt, max_seq)
        return view if mesh is None else _constrain_cache(view, mesh)

    def commit(pages, view, pt, pos):
        return constrain_pages(
            paged_commit_cache(cfg, pages, view, pt, pos, max_seq))

    def insert(pages, src, pt_rows):
        return constrain_pages(paged_insert_cache(cfg, pages, src, pt_rows))

    def page_copy(pages, src_ids, dst_ids):
        return constrain_pages(paged_copy_pages(cfg, pages, src_ids, dst_ids))

    return PagedServeFns(
        jax.jit(gather),
        jax.jit(commit, donate_argnums=(0,)),
        jax.jit(insert, donate_argnums=(0,)),
        jax.jit(page_copy, donate_argnums=(0,)),
        page_size, max_seq)


@functools.lru_cache(maxsize=None)
def expand_rows_fn(cfg: ModelConfig):
    """Jitted ``model.cache_expand_rows`` for one config (admission dedupe:
    expand a deduped prefill's cache rows back to one per request)."""
    from repro.models.model import cache_expand_rows

    def expand_rows(cache, inv):
        return cache_expand_rows(cfg, cache, inv)

    return jax.jit(expand_rows)


# --------------------------------------------------------------------------
# abstract inputs
# --------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(functools.partial(init_model, cfg=cfg),
                          jax.random.PRNGKey(0))


def abstract_opt_state(cfg: ModelConfig, lean: bool = False):
    params = abstract_params(cfg)
    return jax.eval_shape(functools.partial(init_adamw, lean=lean), params)


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.eval_shape(
        functools.partial(init_decode_cache, cfg, batch, max_seq))


def input_specs(arch: str, shape: str, *, smoke: bool = False) -> Dict[str, Any]:
    """ShapeDtypeStructs for one (arch × shape) dry-run cell.

    Returns a dict with 'kind' ∈ {train, prefill, decode} and the abstract
    arrays each step consumes.
    """
    cfg = get_config(arch, smoke=smoke)
    seq, batch, kind = SHAPES[shape]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)

    out: Dict[str, Any] = {"kind": kind, "cfg": cfg, "seq": seq, "batch": batch}
    enc = (bf16(batch, cfg.n_encoder_tokens, cfg.d_model)
           if cfg.n_encoder_tokens else None)
    if kind == "train":
        out["batch_inputs"] = {"tokens": i32(batch, seq), "labels": i32(batch, seq)}
        if enc is not None:
            out["batch_inputs"]["encoder_states"] = enc
    elif kind == "prefill":
        out["tokens"] = i32(batch, seq)
        out["encoder_states"] = enc
    else:  # decode: one new token against a cache of length seq
        out["tokens"] = i32(batch, 1)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
        out["cache"] = abstract_cache(cfg, batch, seq)
        out["encoder_states"] = enc
    return out
