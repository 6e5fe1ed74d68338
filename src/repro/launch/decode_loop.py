"""On-device decode megasteps: K tokens per dispatch (DESIGN.md §10).

The per-token serving loop pays three host costs the paper's cheap sketched
step cannot amortize: a Python-level jit dispatch per token, a device→host
sync to sample (the old ``np.asarray`` in the loop), and — without buffer
donation — a full decode-cache copy per step.  This module moves the loop
onto the device: one jitted **megastep** runs K decode steps as a
``jax.lax.scan`` whose carry is ``(cache, last_tok, pos, active, key)``,
with the :class:`repro.api.Sampler` (temperature / top-k / top-p, split-key
chain) and EOS → active-mask retirement fused *inside* the scan body.  Only
a ``(K, B) int32`` token block (plus the small carry vectors) ever crosses
back to the host.

Semantics are bitwise-aligned with the host loop: each scan step feeds the
previously sampled token through ``serve_step`` and samples from the
resulting logits, splitting the carried PRNG key exactly once per non-greedy
sample — the same (step, sample) sequence and the same key chain as the
``for t in range(gen_len)`` loop it replaces, so one seed reproduces the
same stream at any chunk size.  Rows that emit ``eos_id`` retire in-scan:
their later block entries hold ``pad_id`` and their cache rows freeze via
the same ``active`` mask the engine uses for parked slots: every layer
holds back a retired row's cache write (``serve_step``).

Two flavors share one implementation, specialized by the ``pos`` rank:

* **static generate** — scalar ``pos`` (all rows at the same depth),
  advancing by 1 per step regardless of retirement, matching the host
  loop's shared position counter;
* **engine** — per-slot ``(B,)`` counters advancing only where a slot is
  active, matching the engine's per-slot bookkeeping.

Megasteps donate their cache argument (``donate_argnums``), so the decode
cache is updated in place instead of copied per dispatch; callers must
treat the passed-in cache as consumed (rebind to the returned one).  On a
serving mesh the donation preserves the PR-4 sharding constraints —
``serve_step`` re-constrains the cache every scan step, so input and output
buffers alias shard-for-shard.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.heads import LogitHead
from repro.api.sampler import Sampler, _sample_impl
from repro.models.config import ModelConfig


def jitted_megastep(cfg: ModelConfig, head: LogitHead, sampler: Sampler,
                    k: int, *, mesh=None, eos_id: Optional[int] = None,
                    pad_id: int = 0, masked: bool = False):
    """The jitted K-step decode megastep for one serving spec.

    Memoized on the full hashable spec ``(cfg, head, sampler, k, mesh,
    eos_id, pad_id, masked)`` — every engine tick and every ``generate()``
    chunk for the same spec dispatches one cached executable.

    Args:
      cfg: the model config.
      head: a bare ``LogitHead`` spec (``head.without_params()``); frozen
        arrays ride along per call as ``head_params``.
      sampler: the ``Sampler`` spec fused into the scan body.
      k: scan length — decode steps (= emitted tokens) per dispatch.
      mesh: optional serving mesh; threads the shard_map head path and the
        per-step cache sharding constraint through the scan.
      eos_id: with ``masked=True``, rows that emit it retire in-scan.
      pad_id: block filler for retired rows.
      masked: carry a ``(B,)`` active mask (engine slots / EOS retirement);
        ``False`` compiles the maskless fast path (static generate without
        ``eos_id``), bitwise-matching the host loop's unmasked steps.

    Returns:
      A jitted ``megastep(params, cache, last_tok, pos, key, *,
      head_params=None, active=None, encoder_states=None)`` returning
      ``(block, cache, last_tok, pos, active, key)`` with ``block`` a
      ``(k, B) int32`` token block.  The ``cache`` argument is **donated**.

    Raises:
      ValueError: on ``k < 1`` or ``eos_id`` without ``masked``.
    """
    if k < 1:
        raise ValueError(f"megastep needs k >= 1, got {k}")
    if eos_id is not None and not masked:
        raise ValueError("eos_id retirement needs masked=True")
    # Canonical all-positional key: lru_cache would otherwise key
    # keyword and positional spellings of the same spec separately.
    return _jitted_megastep(cfg, head, sampler, k, mesh, eos_id, pad_id,
                            masked)


@functools.lru_cache(maxsize=None)
def _jitted_megastep(cfg, head, sampler, k, mesh, eos_id, pad_id, masked):
    from repro.launch.steps import serve_step

    def megastep(params, cache, last_tok, pos, key, head_params=None,
                 active=None, encoder_states=None):
        def body(carry, _):
            cache, tok, pos, active, key = carry
            logits, cache = serve_step(
                params, cache, tok[:, None], pos, cfg,
                encoder_states=encoder_states, head=head,
                head_params=head_params,
                active=active if masked else None, mesh=mesh)
            # Same math as the host loop's jitted Sampler.sample — one key
            # split per non-greedy sample, none when greedy.
            key, nxt = _sample_impl(sampler, key, logits)
            if masked:
                nxt = jnp.where(active, nxt, jnp.int32(pad_id))
            if jnp.ndim(pos):       # per-slot counters: advance rows that
                                    # decoded this step (incl. an EOS step,
                                    # matching the host engine's += 1)
                pos = pos + (active.astype(jnp.int32) if masked else 1)
            else:                   # static generate: one shared depth
                pos = pos + 1
            if masked and eos_id is not None:
                active = active & (nxt != eos_id)
            return (cache, nxt, pos, active, key), nxt

        (cache, last_tok, pos, active, key), block = jax.lax.scan(
            body, (cache, last_tok, pos, active, key), None, length=k)
        return block, cache, last_tok, pos, active, key

    return jax.jit(megastep, donate_argnums=(1,))


def jitted_spec_megastep(cfg: ModelConfig, head: LogitHead, sampler: Sampler,
                         k: int, *, mesh=None, eos_id: Optional[int] = None,
                         pad_id: int = 0, masked: bool = False):
    """The jitted speculative two-head megastep (DESIGN.md §11).

    ``head`` (normally the cheap sketch head) **drafts** ``k`` tokens
    through the backbone inside a ``lax.scan``, recording each step's final
    hidden, its pre-sample PRNG key, and a rollback snapshot of the
    non-positional cache state.  One batched **dense verify** pass —
    ``dense_verify_logits`` over the stacked hiddens, no extra backbone
    work — then replays the sampler on the recorded keys, producing the
    token pure dense decode would have drawn at every position.  Acceptance
    is *common-random-numbers rejection sampling*: a draft survives iff it
    equals the dense draw under the very randomness dense decode would have
    used, and the emitted block is always the dense draws themselves — so
    the output stream is **bitwise identical** to dense decode regardless
    of how good the draft head is; the draft only sets how many of the
    ``k`` backbone steps commit per dispatch.

    Rows commit in lockstep at ``m = min`` over active rows of
    ``min(accepted + 1, k)`` (the ``+1`` is the free bonus/correction
    token, whose verify logits are conditioned only on the matched prefix).
    The carry rewinds to the committed step: positional KV/MLA caches by
    the position counter alone, ring/recurrent layers from the recorded
    snapshots (``cache_rollback``), and the PRNG key to the post-sample key
    of step ``m - 1`` — exactly the state dense decode would hold after
    ``m`` tokens.  EOS retirement inside the committed block mirrors
    ``jitted_megastep``: later entries pad, cache rows freeze.

    Memoized on the full hashable spec like ``jitted_megastep``.

    Returns:
      A jitted ``spec_megastep(params, cache, last_tok, pos, key, *,
      head_params=None, active=None, encoder_states=None)`` returning
      ``(block, m, acc, adv, cache, last_tok, pos, active, key)`` where
      ``block`` is the (k, B) int32 verify-token block of which only rows
      ``< m`` are committed, ``acc`` (B,) counts committed accepted draft
      tokens (for acceptance-rate stats) and ``adv`` (B,) the tokens each
      row actually emitted (≤ m; less only past an in-block EOS).  The
      ``cache`` argument is **donated**.

    Raises:
      ValueError: on ``k < 1``, ``eos_id`` without ``masked``, or a
        ``DenseHead``-style spec without its own logits path when greedy
        drafting is impossible (any LogitHead works; no check needed).
    """
    if k < 1:
        raise ValueError(f"spec megastep needs k >= 1, got {k}")
    if eos_id is not None and not masked:
        raise ValueError("eos_id retirement needs masked=True")
    return _jitted_spec_megastep(cfg, head, sampler, k, mesh, eos_id, pad_id,
                                 masked)


@functools.lru_cache(maxsize=None)
def _jitted_spec_megastep(cfg, head, sampler, k, mesh, eos_id, pad_id,
                          masked):
    from repro.launch.steps import serve_step
    from repro.models.model import (cache_rollback, cache_snapshot,
                                    dense_verify_logits)

    def spec_megastep(params, cache, last_tok, pos, key, head_params=None,
                      active=None, encoder_states=None):
        pos_in = pos

        # ---- draft: k cheap-head steps through the backbone -------------
        # `active` is a closure constant for the whole draft (no carry):
        # EOS can only be declared by the verify tokens, after the scan.
        def draft_body(carry, _):
            cache, tok, pos, key = carry
            logits, cache, hidden = serve_step(
                params, cache, tok[:, None], pos, cfg,
                encoder_states=encoder_states, head=head,
                head_params=head_params,
                active=active if masked else None, mesh=mesh,
                return_hidden=True)
            pre_key = key
            key, nxt = _sample_impl(sampler, key, logits)
            if masked:
                nxt = jnp.where(active, nxt, jnp.int32(pad_id))
            if jnp.ndim(pos):
                pos = pos + (active.astype(jnp.int32) if masked else 1)
            else:
                pos = pos + 1
            return ((cache, nxt, pos, key),
                    (hidden, pre_key, key, nxt, cache_snapshot(cfg, cache)))

        (cache, _, _, _), (hiddens, pre_keys, post_keys, drafts, snaps) = \
            jax.lax.scan(draft_body, (cache, last_tok, pos_in, key), None,
                         length=k)

        # ---- verify: ONE batched dense pass over the k hiddens ----------
        # (B, k, d) layout so the sharding constraint inside
        # dense_verify_logits sees forward()'s exact (B, S, V) axes — the
        # partitioner must not treat the verify einsum differently from
        # the in-forward unembed it must match bitwise.
        dense = dense_verify_logits(params, jnp.swapaxes(hiddens, 0, 1), cfg)
        dense = jnp.swapaxes(dense, 0, 1)                   # (k, B, V)

        if sampler.is_greedy:
            verify = jnp.argmax(dense, axis=-1).astype(jnp.int32)
        else:
            # Replay the sampler on the recorded pre-sample keys: at every
            # position the committed prefix equals dense decode's, so the
            # key chain — and hence the categorical draw — is the same.
            def verify_body(_, xs):
                pre_key, logits = xs
                _, tok = _sample_impl(sampler, pre_key, logits)
                return (), tok

            _, verify = jax.lax.scan(verify_body, (), (pre_keys, dense))
        if masked:
            verify = jnp.where(active[None, :], verify, jnp.int32(pad_id))

        # ---- acceptance: longest matching prefix + bonus token ----------
        match = (drafts == verify).astype(jnp.int32)        # (k, B)
        a = jnp.cumprod(match, axis=0).sum(0)               # leading matches
        n = jnp.minimum(a + 1, k)                           # + bonus, capped
        if masked:
            n = jnp.where(active, n, k)   # parked rows don't constrain m
        m = n.min()                       # lockstep commit (global key chain)

        # ---- emission bookkeeping (mirrors jitted_megastep's EOS path) --
        steps = jnp.arange(k)[:, None]                      # (k, 1)
        if masked:
            hits = ((verify == eos_id) if eos_id is not None
                    else jnp.zeros(verify.shape, bool))
            prior = jnp.cumsum(hits.astype(jnp.int32), axis=0) \
                - hits.astype(jnp.int32)                    # EOS before i
            alive = active[None, :] & (prior == 0)
        else:
            alive = jnp.ones(verify.shape, bool)
        committed = alive & (steps < m)
        block = jnp.where(committed, verify, jnp.int32(pad_id))
        adv = committed.astype(jnp.int32).sum(0)            # emitted per row
        acc = jnp.minimum(a, adv)                           # accepted drafts
        if masked and eos_id is not None:
            active = active & ~(hits & (steps < m)).any(0)

        # ---- rewind the carry to the committed step ---------------------
        # Cache: positional layers keep the draft-final buffers (their
        # stale writes sit beyond the rewound position counter); ring and
        # recurrent layers take the snapshot recorded after draft step
        # m - 1 — whose processed inputs (last_tok, drafts[:m-1]) all
        # matched the committed stream, because m - 1 <= accepted count.
        sel = lambda s: jax.lax.dynamic_index_in_dim(s, m - 1, 0,
                                                     keepdims=False)
        cache = cache_rollback(cfg, cache, jax.tree.map(sel, snaps))
        last_tok = sel(block)
        key = sel(post_keys)              # dense decode's key after m draws
        pos = pos_in + (adv if jnp.ndim(pos_in) else m)
        return block, m, acc, adv, cache, last_tok, pos, active, key

    return jax.jit(spec_megastep, donate_argnums=(1,))


def spec_decode_chunks(params, cache, first_logits, *, cfg: ModelConfig,
                       head: LogitHead, sampler: Sampler, gen_len: int,
                       start_pos: int, spec_k: int,
                       eos_id: Optional[int] = None, pad_id: int = 0,
                       mesh=None, encoder_states=None):
    """The static-batch speculative decode loop (``generate(spec_decode=K)``).

    Mirrors :func:`decode_chunks`: the first token comes from the prefill
    logits — which are always *dense* logits, so the stream starts on the
    dense chain — then each iteration dispatches one
    :func:`jitted_spec_megastep` and commits its ``m`` verified tokens.
    ``m`` is data-dependent, so the loop syncs one scalar per dispatch (the
    same cost class as the engine's per-tick retirement sync).

    Returns ``(tokens, stats)`` with stats counting backbone draft steps
    (``decode_steps``), ``verify_calls``, ``draft_tokens`` and
    ``accepted_draft_tokens`` — acceptance rate is
    ``accepted_draft_tokens / draft_tokens``.
    """
    b = first_logits.shape[0]
    key = sampler.init_key()
    key, tok0 = sampler.sample(key, first_logits)
    tok0 = tok0.astype(jnp.int32)
    masked = eos_id is not None
    active = (tok0 != eos_id) if masked else None
    spec = head.without_params()

    blocks = [tok0[:, None]]
    last_tok, pos = tok0, jnp.asarray(start_pos, jnp.int32)
    todo = gen_len - 1
    stats = {"decode_steps": 0, "verify_calls": 0, "draft_tokens": 0,
             "accepted_draft_tokens": 0}
    while todo > 0:
        kk = min(spec_k, todo)
        fn = jitted_spec_megastep(cfg, spec, sampler, kk, mesh=mesh,
                                  eos_id=eos_id, pad_id=pad_id,
                                  masked=masked)
        block, m, acc, adv, cache, last_tok, pos, active, key = fn(
            params, cache, last_tok, pos, key, head_params=head.params,
            active=active, encoder_states=encoder_states)
        m = int(jax.device_get(m))
        blocks.append(jnp.asarray(block[:m]).T)
        stats["decode_steps"] += kk
        stats["verify_calls"] += 1
        stats["draft_tokens"] += kk * b
        stats["accepted_draft_tokens"] += int(jax.device_get(acc.sum()))
        todo -= m
        if masked and todo > 0 and not bool(jax.device_get(active.any())):
            blocks.append(jnp.full((b, todo), pad_id, jnp.int32))
            break
    return jnp.concatenate(blocks, axis=1), stats


def decode_chunks(params, cache, first_logits, *, cfg: ModelConfig,
                  head: LogitHead, sampler: Sampler, gen_len: int,
                  start_pos: int, chunk: int, eos_id: Optional[int] = None,
                  pad_id: int = 0, mesh=None, encoder_states=None):
    """The static-batch decode loop as on-device megasteps.

    Replaces ``generate()``'s per-token host loop for ``decode_chunk > 1``:
    the first token is sampled from the prefill logits (the same first key
    split as the host loop), then the remaining ``gen_len - 1`` steps run as
    ``chunk``-sized megasteps (plus one remainder-sized chunk).  When
    ``eos_id`` is set and every row retires, remaining chunks are skipped
    and the tail is padding — the host loop's early exit at chunk
    granularity.

    Args:
      params: backbone params.
      cache: the prefilled decode cache — **consumed** (donated to the
        first megastep); use the function's view of it only.
      first_logits: (B, V) last-position prefill logits.
      cfg / head / sampler / mesh / encoder_states: the serving spec, as in
        ``launch.serve.generate``.
      gen_len: total tokens to emit per row (including the first).
      start_pos: prompt length P (tokens already cached).
      chunk: megastep size K (>= 1).
      eos_id / pad_id: optional early-retirement token and filler.

    Returns:
      ``(tokens, stats)`` — (B, gen_len) int32 generated tokens (prompt
      excluded) and ``{"decode_steps": n}`` counting device decode steps.
    """
    b = first_logits.shape[0]
    key = sampler.init_key()
    key, tok0 = sampler.sample(key, first_logits)
    tok0 = tok0.astype(jnp.int32)
    masked = eos_id is not None
    active = (tok0 != eos_id) if masked else None
    spec = head.without_params()

    blocks = [tok0[:, None]]
    last_tok, pos = tok0, jnp.asarray(start_pos, jnp.int32)
    todo, steps = gen_len - 1, 0
    while todo > 0:
        k = min(chunk, todo)
        fn = jitted_megastep(cfg, spec, sampler, k, mesh=mesh,
                             eos_id=eos_id, pad_id=pad_id, masked=masked)
        block, cache, last_tok, pos, active, key = fn(
            params, cache, last_tok, pos, key, head_params=head.params,
            active=active, encoder_states=encoder_states)
        blocks.append(block.T)
        steps += k
        todo -= k
        if masked and todo > 0 and not bool(jax.device_get(active.any())):
            blocks.append(jnp.full((b, todo), pad_id, jnp.int32))
            break
    return jnp.concatenate(blocks, axis=1), {"decode_steps": steps}
