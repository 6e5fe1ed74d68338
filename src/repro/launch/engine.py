"""Continuous-batching serve engine: slotted decode cache + FIFO admission.

The static path (``launch.serve.generate``) runs one fixed batch from prefill
to the last token — a request that finishes early pads the batch until the
slowest one is done, and nothing can join mid-decode.  This engine owns a
pool of ``n_slots`` decode-cache rows and a FIFO request queue instead:

* **admit** — whenever a slot is free and a request has arrived, its prompt
  is bulk-prefilled into a *fresh* cache (the exact prefill path the static
  server uses) and the filled rows are copied into the pool via
  ``cache_slot_insert``; simultaneous arrivals with equal prompt lengths
  prefill as one batch.
* **decode** — one ``serve_step`` per engine tick advances every occupied
  slot, with per-slot position counters (each sequence is at its own depth)
  and an active-slot mask so free slots keep their cache bitwise unchanged.
  With ``decode_chunk=K`` the tick becomes an on-device *megastep*: K
  steps, sampling, and EOS retirement fused into one ``lax.scan`` dispatch
  (launch/decode_loop.py, DESIGN.md §10), clamped so no slot overshoots
  its budget.  Greedy streams are bitwise K-invariant; seeded streams are
  K-invariant unless a mid-chunk EOS delays a re-admission (a freed slot
  refills only at the chunk boundary), which shifts the shared key chain
  — reproducible per (seed, K), documented in docs/serving.md.
* **retire** — a sequence leaves individually on EOS or its own
  ``max_new_tokens``; the slot is ``cache_slot_reset`` to a fresh (bitwise
  zero) row and immediately reusable on the next tick.

The request queue is a heap ordered on (arrival, submission) —
O(log n) per request — and the jitted decode/slot ops donate the pool
(no per-token cache copy; the engine always rebinds ``self.pool`` to the
returned one).

The engine is head-agnostic through the ``repro.api`` objects: any
registered ``LogitHead`` (dense unembed, fused sketch head, the two-kernel
path, …) runs through the same ``serve_step``, and token selection is a
``Sampler`` (DESIGN.md §7/§8).  Scheduling bookkeeping lives in the
pure-Python ``SlotScheduler`` and the model compute behind the small
``EngineBackend`` seam, so scheduler invariants are property-testable
without JAX in the loop (tests/test_engine_properties.py).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.heads import DenseHead, LogitHead
from repro.api.sampler import Sampler
from repro.launch.steps import (fresh_cache_fn, jitted_serve_fns,
                                resolve_legacy_serving_kwargs)
from repro.models.config import ModelConfig, SketchHeadConfig
from repro.models.model import init_decode_cache


@dataclasses.dataclass
class Request:
    """One serving request: prompt tokens + generation budget.

    ``tenant`` binds the request to a per-tenant sketch head (DESIGN.md
    §14): on a ``head_cache`` engine every request must name its tenant,
    and its slot decodes through that tenant's head for its whole lifetime.
    """
    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new_tokens: int
    arrival: int = 0            # engine tick at which the request is visible
    tenant: Optional[object] = None


class RequestQueue:
    """Arrival-ordered request queue, FIFO on ties: a binary heap keyed on
    ``(arrival, submission index)``.

    Replaces the sorted list the engine used to keep (``bisect.insort`` +
    ``list.pop(0)``): both ends of that were O(n) per request — O(n²) over a
    long arrival stream — where the heap is O(log n) push/pop.  Semantics
    are unchanged: ``pop`` returns the earliest arrival, and equal arrivals
    leave in submission order (the tie-break index), exactly the old
    insort-right behavior.
    """

    def __init__(self):
        self._heap: List[tuple] = []
        self._pushed = 0

    def push(self, req: Request) -> None:
        heapq.heappush(self._heap, (req.arrival, self._pushed, req))
        self._pushed += 1

    def peek(self) -> Request:
        return self._heap[0][2]

    def pop(self) -> Request:
        return heapq.heappop(self._heap)[2]

    def __len__(self) -> int:
        return len(self._heap)

    def __iter__(self):
        """Pending requests in pop order (sorted snapshot — O(n log n);
        for diagnostics, not the hot path)."""
        return (entry[2] for entry in sorted(self._heap))

    def __getitem__(self, i: int) -> Request:
        # Legacy list-style indexing (``engine.queue[0]``); the head is the
        # O(1) case, anything else sorts a snapshot.  Slices would silently
        # return raw heap tuples — reject them.
        if not isinstance(i, int):
            raise TypeError(f"RequestQueue indices must be int, got {i!r}")
        if i == 0:
            return self.peek()
        return sorted(self._heap)[i][2]


class SlotScheduler:
    """Slot-pool bookkeeping: admission and retirement, no model compute.

    Invariants (property-tested): a slot is never double-assigned, every
    admitted request retires exactly once, and ``n_free + n_active ==
    n_slots`` at all times.  Free slots are handed out lowest-index first so
    runs are deterministic.
    """

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self._free: List[int] = list(range(n_slots))
        self.owner: Dict[int, int] = {}       # slot -> rid
        self.retired: Dict[int, int] = {}     # rid -> retire count

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return len(self.owner)

    def active_slots(self) -> List[int]:
        return sorted(self.owner)

    def admit(self, rid: int) -> int:
        if not self._free:
            raise RuntimeError("no free slot")
        if rid in self.owner.values() or rid in self.retired:
            raise RuntimeError(f"request {rid} already admitted")
        slot = min(self._free)
        self._free.remove(slot)
        self.owner[slot] = rid
        return slot

    def retire(self, slot: int) -> int:
        rid = self.owner.pop(slot)
        self.retired[rid] = self.retired.get(rid, 0) + 1
        self._free.append(slot)
        return rid


class EngineBackend:
    """Model compute behind the engine: prefill / insert / decode / reset.

    One instance per (model, head) pair; the jitted callables are memoized
    per (config, head spec) — ``jitted_serve_fns`` — so many engines over
    the same model share compiles.
    """

    def __init__(self, params, cfg: ModelConfig, *,
                 head: Optional[LogitHead] = None, mesh=None,
                 sketch_head=None,
                 sketch_cfg: Optional[SketchHeadConfig] = None, fused=None):
        if cfg.n_encoder_tokens:
            raise NotImplementedError(
                "engine serving of encoder-conditioned archs needs "
                "per-request encoder states; use launch.serve.generate")
        head, _ = resolve_legacy_serving_kwargs(
            head, None, sketch_head, sketch_cfg, fused, None, None,
            "EngineBackend")
        self.cfg = cfg
        self.head = head or DenseHead()
        self.mesh = mesh
        if mesh is not None:
            # Serving SPMD: params per sharding/rules.py, head count arrays
            # over model; no-op when the LM facade already placed them.
            from repro.launch.mesh import place_serving_state
            params, self.head = place_serving_state(params, self.head, mesh)
        self.params = params
        self.vocab_size = cfg.vocab_size
        (self._prefill, self._decode, self._insert,
         self._reset) = jitted_serve_fns(cfg, self.head.without_params(),
                                         mesh=mesh)
        self._fresh = fresh_cache_fn(cfg, mesh)

    def _place_cache(self, cache):
        if self.mesh is None:
            return cache
        from repro.sharding.rules import cache_shardings
        return jax.device_put(cache, cache_shardings(cache, self.mesh))

    def init_pool(self, n_slots: int, max_seq: int):
        return self._place_cache(init_decode_cache(self.cfg, n_slots, max_seq))

    def prefill(self, prompts: jnp.ndarray, max_seq: int):
        """Bulk-prefill (G, P) prompts into a fresh cache → (logits, cache)."""
        fresh = self._fresh(prompts.shape[0], max_seq)
        logits, filled = self._prefill(self.params, prompts, cache=fresh)
        return np.asarray(logits), filled

    def insert(self, pool, filled, slots: np.ndarray):
        return self._insert(pool, filled, np.asarray(slots, np.int32))

    def reset(self, pool, slots: np.ndarray):
        return self._reset(pool, np.asarray(slots, np.int32))

    def decode(self, pool, tokens: np.ndarray, pos: np.ndarray,
               active: np.ndarray, head_params=None):
        """One decode step; ``head_params`` overrides the backend's bound
        head arrays (the per-tenant engine passes the HeadCache bank +
        slot binding here each tick)."""
        if head_params is None:
            head_params = self.head.params
        with TraceAnnotation("engine.decode"):
            logits, pool = self._decode(
                self.params, pool, jnp.asarray(tokens[:, None], jnp.int32),
                jnp.asarray(pos, jnp.int32), head_params=head_params,
                active=jnp.asarray(active))
        with TraceAnnotation("engine.fetch"):
            return np.asarray(logits), pool

    # -- paged pool (DESIGN.md §13) ----------------------------------------

    def _paged_fns(self, max_seq: int, page_size: int):
        return jitted_serve_fns(self.cfg, self.head.without_params(),
                                mesh=self.mesh, paged=True,
                                page_size=page_size, max_seq=max_seq).paged_ops

    def paged_geometries(self, max_seq: int):
        """Distinct (size, ring) sequence-axis geometries across this
        model's paged layer families — what the engine's write-page logic
        iterates to find the page each family writes at a position."""
        from repro.models.blocks import paged_geometry
        kinds = set(self.cfg.pattern)
        geoms = {paged_geometry(self.cfg, k, max_seq) for k in kinds}
        return sorted(g for g in geoms if g is not None)

    def init_paged(self, n_slots: int, max_seq: int, page_size: int,
                   num_pages: int):
        """Device state for the paged engine: the (pages, state) tree pair."""
        from repro.models.model import init_paged_cache, init_paged_state
        pages = init_paged_cache(self.cfg, num_pages, page_size)
        state = init_paged_state(self.cfg, n_slots)
        if self.mesh is not None:
            from repro.sharding.rules import page_pool_shardings
            pages = jax.device_put(pages,
                                   page_pool_shardings(pages, self.mesh))
            state = self._place_cache(state)
        return pages, state

    def paged_decode(self, pages, state, table: np.ndarray,
                     tokens: np.ndarray, pos: np.ndarray, active: np.ndarray,
                     *, max_seq: int, page_size: int, head_params=None):
        """One paged decode tick: gather per-slot views through the page
        table, splice in the recurrent state, run the *same* compiled decode
        step the contiguous engine uses (that identity is the bitwise-parity
        argument), then commit the written position back to the arenas and
        re-extract the state.  ``pages``/``state`` are consumed (the view —
        and with it the spliced-in state buffers — is donated to decode, and
        commit donates the arena); rebind to the returned pair."""
        from repro.models.model import extract_paged_state, merge_paged_view
        if head_params is None:
            head_params = self.head.params
        fns = self._paged_fns(max_seq, page_size)
        with TraceAnnotation("engine.decode"):
            pt = jnp.asarray(table, jnp.int32)
            posj = jnp.asarray(pos, jnp.int32)
            view = fns.gather(pages, pt)
            full = merge_paged_view(self.cfg, view, state)
            logits, new_full = self._decode(
                self.params, full, jnp.asarray(tokens[:, None], jnp.int32),
                posj, head_params=head_params, active=jnp.asarray(active))
            new_pages = fns.commit(pages, new_full, pt, posj)
            new_state = extract_paged_state(self.cfg, new_full)
        with TraceAnnotation("engine.fetch"):
            return np.asarray(logits), new_pages, new_state

    def paged_insert(self, pages, filled, pt_rows: np.ndarray, *,
                     max_seq: int, page_size: int):
        """Scatter freshly prefilled rows into newly mapped pages (``pages``
        donated; ``filled`` is also read by the state insert — not donated)."""
        fns = self._paged_fns(max_seq, page_size)
        return fns.insert(pages, filled, np.asarray(pt_rows, np.int32))

    def page_copy(self, pages, src_ids: np.ndarray, dst_ids: np.ndarray, *,
                  max_seq: int, page_size: int):
        """COW fork: copy pages ``src_ids → dst_ids`` in every arena."""
        fns = self._paged_fns(max_seq, page_size)
        return fns.page_copy(pages, np.asarray(src_ids, np.int32),
                             np.asarray(dst_ids, np.int32))

    def state_rows(self, filled, row: int):
        """One request's recurrent-state rows as a host numpy tree — what a
        prefix-cache entry stores (constant-size; no pages involved).
        ``None`` for archs with no recurrent layers."""
        from repro.models.model import extract_state_rows
        rows = extract_state_rows(self.cfg, filled, row)
        if not jax.tree_util.tree_leaves(rows):
            return None
        return jax.tree.map(lambda x: np.asarray(x), rows)

    def state_restore(self, state, entry_state, slot: int):
        """Insert a prefix entry's stored recurrent rows into one slot."""
        src = jax.tree.map(jnp.asarray, entry_state)
        return self._insert(state, src, np.asarray([slot], np.int32))

    def expand_rows(self, filled, inv: np.ndarray):
        """Expand a deduped prefill — (G_unique, …) rows → (G, …) via the
        inverse index — so slot inserts stay one-row-per-request."""
        from repro.launch.steps import expand_rows_fn
        return expand_rows_fn(self.cfg)(filled, np.asarray(inv, np.int32))

    def megastep(self, pool, tokens: np.ndarray, pos: np.ndarray,
                 active: np.ndarray, key, k: int, sampler: Sampler,
                 eos_id: Optional[int], head_params=None):
        """K decode steps + in-scan sampling/EOS retirement in one dispatch
        (launch/decode_loop.py).  ``pool`` is donated; only the (k, B) token
        block and the small carry vectors cross back to host."""
        from repro.launch.decode_loop import jitted_megastep

        if head_params is None:
            head_params = self.head.params
        fn = jitted_megastep(self.cfg, self.head.without_params(), sampler,
                             k, mesh=self.mesh, eos_id=eos_id, masked=True)
        with TraceAnnotation("engine.decode"):
            block, pool, last_tok, pos, active, key = fn(
                self.params, pool, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32), key,
                head_params=head_params, active=jnp.asarray(active))
        # np.array (not asarray): the engine mutates pos/last_tok per slot
        # on admission, and zero-copy views of jax arrays are read-only.
        with TraceAnnotation("engine.fetch"):
            return (np.asarray(block), pool, np.array(last_tok, np.int32),
                    np.array(pos, np.int32), np.asarray(active), key)

    def spec_megastep(self, pool, tokens: np.ndarray, pos: np.ndarray,
                      active: np.ndarray, key, k: int, sampler: Sampler,
                      eos_id: Optional[int]):
        """One speculative two-head dispatch: the engine's head drafts ``k``
        tokens, one batched dense pass verifies, and ``m`` lockstep-commit
        (DESIGN.md §11).  ``pool`` is donated.  Returns the (k, B) verify
        block, the committed step count ``m`` (host int), the per-slot
        accepted-draft counts, and the rewound carry."""
        from repro.launch.decode_loop import jitted_spec_megastep

        fn = jitted_spec_megastep(self.cfg, self.head.without_params(),
                                  sampler, k, mesh=self.mesh, eos_id=eos_id,
                                  masked=True)
        with TraceAnnotation("engine.decode"):
            block, m, acc, _adv, pool, last_tok, pos, active, key = fn(
                self.params, pool, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32), key,
                head_params=self.head.params, active=jnp.asarray(active))
        with TraceAnnotation("engine.fetch"):
            return (np.asarray(block), int(jax.device_get(m)),
                    np.asarray(acc), pool, np.array(last_tok, np.int32),
                    np.array(pos, np.int32), np.asarray(active), key)


class ServeEngine:
    """Continuous-batching engine over a ``backend`` and ``n_slots`` cache rows.

    ``submit()`` requests, then ``run()`` (or ``step()`` tick by tick);
    finished sequences land in ``finished[rid]`` as the generated token list
    (prompt excluded).  Token selection is the ``sampler``
    (repro.api.Sampler; greedy by default, otherwise a key chain seeded once
    — reproducible per seed).
    """

    def __init__(self, backend, n_slots: int, max_seq: int, *,
                 eos_id: Optional[int] = None,
                 sampler: Optional[Sampler] = None, decode_chunk: int = 1,
                 spec_decode: int = 0, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 head_cache=None, greedy=None, seed=None):
        _, sampler = resolve_legacy_serving_kwargs(
            None, sampler, None, None, None, greedy, seed, "ServeEngine")
        if head_cache is not None and spec_decode:
            raise ValueError("spec_decode and per-tenant heads are mutually "
                             "exclusive: the draft/verify megastep re-reads "
                             "the head inside its scan and cannot re-gather "
                             "per-slot tenant bindings mid-draft")
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if spec_decode < 0:
            raise ValueError(f"spec_decode must be >= 0, got {spec_decode}")
        if spec_decode and decode_chunk > 1:
            raise ValueError("spec_decode and decode_chunk > 1 are mutually "
                             "exclusive: the speculative megastep already "
                             "advances up to K tokens per tick")
        if spec_decode and not hasattr(backend, "spec_megastep"):
            raise ValueError("spec_decode needs a backend with a "
                             "spec_megastep (the fused draft/verify "
                             "dispatch); this backend has none")
        if paged:
            if decode_chunk > 1:
                raise ValueError("paged=True runs the host decode loop; "
                                 "decode_chunk > 1 is not supported yet")
            if spec_decode:
                raise ValueError("paged=True and spec_decode are mutually "
                                 "exclusive")
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            if not hasattr(backend, "init_paged"):
                raise ValueError("paged=True needs a backend with the paged "
                                 "pool ops (init_paged/paged_decode/…); "
                                 "this backend has none")
        self.backend = backend
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.sampler = sampler or Sampler()
        self.decode_chunk = decode_chunk
        self.spec_decode = spec_decode
        self.paged = paged
        self.page_size = page_size
        if paged:
            from repro.launch.paging import PagePool, PrefixCache
            npp = -(-max_seq // page_size)          # page-table width
            if num_pages is None:
                # Enough for every slot's full budget plus a prefix-cache
                # working set; LRU eviction absorbs the heavy tail beyond.
                num_pages = 1 + (n_slots + 8) * (npp + 1)
            self.pages, self.state = backend.init_paged(
                n_slots, max_seq, page_size, num_pages)
            self.page_pool = PagePool(num_pages, n_slots, npp)
            self.prefix = PrefixCache(self.page_pool)
            self._geoms = backend.paged_geometries(max_seq)
            self._has_state = bool(jax.tree_util.tree_leaves(self.state))
            self.pool = None
        else:
            self.pool = backend.init_pool(n_slots, max_seq)
        self.head_cache = head_cache
        self.slot_tenant: List[Optional[object]] = [None] * n_slots
        self._refresh: Dict = {}           # tenant -> f32 working head copy
        self.sched = SlotScheduler(n_slots)
        self.pos = np.zeros(n_slots, np.int32)         # tokens cached per slot
        self.last_tok = np.zeros(n_slots, np.int32)    # sampled, not yet cached
        self.remaining = np.zeros(n_slots, np.int32)   # tokens still to emit
        self.queue = RequestQueue()        # arrival-ordered, FIFO on ties
        self.outputs: Dict[int, List[int]] = {}
        self.finished: Dict[int, List[int]] = {}
        self.now = 0                                   # engine tick clock
        self._next_rid = 0
        self._rids: set[int] = set()                   # every rid ever submitted
        self._pending_reset: List[int] = []            # slots retired this tick
        self._key = self.sampler.init_key()
        self.stats = {"refreshes": 0, "publishes": 0,
                      "decode_steps": 0, "active_slot_steps": 0,
                      "admitted": 0, "retired": 0, "prefill_batches": 0,
                      "megasteps": 0, "host_syncs": 0, "verify_calls": 0,
                      "draft_tokens": 0, "accepted_draft_tokens": 0,
                      "dedup_saved": 0, "prefix_hits": 0,
                      "prefix_queries": 0, "page_allocs": 0,
                      "cow_copies": 0, "pages_in_use": 0,
                      "pages_in_use_peak": 0}

    # -- request intake ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *, arrival: int = 0,
               rid: Optional[int] = None, tenant=None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.head_cache is not None and tenant is None:
            raise ValueError("this engine serves per-tenant heads "
                             "(head_cache=); every submit needs tenant=")
        if self.head_cache is None and tenant is not None:
            raise ValueError("tenant= needs a per-tenant engine — pass "
                             "head_cache= to make_engine/ServeEngine")
        if len(prompt) + max_new_tokens > self.max_seq + 1:
            # The last sampled token is never written back to the cache.
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the engine's max_seq ({self.max_seq})")
        if rid is None:
            rid = self._next_rid
        if rid in self._rids:
            raise ValueError(f"request id {rid} already submitted")
        self._rids.add(rid)
        self._next_rid = max(self._next_rid, rid) + 1
        self.queue.push(Request(rid, prompt, max_new_tokens, arrival, tenant))
        return rid

    # -- scheduling --------------------------------------------------------

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        self._key, toks = self.sampler.sample(self._key, logits)
        self.stats["host_syncs"] += 1
        return np.asarray(toks, np.int32)

    def _pop_admission_batch(self) -> List[Request]:
        batch: List[Request] = []
        while (self.queue and self.queue.peek().arrival <= self.now
               and self.sched.n_free > len(batch)):
            batch.append(self.queue.pop())
        return batch

    @staticmethod
    def _by_len(batch: List[Request]) -> Dict[int, List[Request]]:
        by_len: Dict[int, List[Request]] = {}
        for r in batch:
            by_len.setdefault(len(r.prompt), []).append(r)
        return by_len

    def _bind_tenants(self, group: List[Request], slots: np.ndarray) -> None:
        """Pin each admitted request's tenant resident in the HeadCache and
        record the slot→tenant binding.  Runs *before* ``_finish_admit``:
        a request that retires immediately (budget 1 / first-token EOS)
        releases its pin inside ``_retire``, so acquire must come first."""
        if self.head_cache is None:
            return
        for r, s in zip(group, slots):
            self.head_cache.acquire(r.tenant)
            self.slot_tenant[int(s)] = r.tenant

    def _finish_admit(self, group: List[Request], slots: np.ndarray,
                      first: np.ndarray, plen: int) -> None:
        """Shared per-request admission bookkeeping (both pool layouts)."""
        self.stats["admitted"] += len(group)
        for i, r in enumerate(group):
            s = int(slots[i])
            self.pos[s] = plen
            self.last_tok[s] = first[i]
            self.remaining[s] = r.max_new_tokens - 1
            self.outputs[r.rid] = [int(first[i])]
            if (self.remaining[s] == 0
                    or (self.eos_id is not None
                        and int(first[i]) == self.eos_id)):
                self._retire(s)

    def _admit(self) -> None:
        """FIFO head-of-line admission into free slots; equal-length prompts
        arriving together prefill as one batch (the bulk-prefill path), and
        *identical* prompts in that batch prefill once (deduped — their
        logits/cache rows are expanded back to one per request)."""
        if self.paged:
            return self._admit_paged()
        batch = self._pop_admission_batch()
        for plen, group in self._by_len(batch).items():
            uniq: Dict[bytes, int] = {}
            rows: List[np.ndarray] = []
            inv: List[int] = []
            for r in group:
                key = r.prompt.tobytes()
                if key not in uniq:
                    uniq[key] = len(rows)
                    rows.append(r.prompt)
                inv.append(uniq[key])
            prompts = jnp.asarray(np.stack(rows))
            with TraceAnnotation("engine.prefill"):
                logits, filled = self.backend.prefill(prompts, self.max_seq)
            if len(rows) < len(group):
                inv_arr = np.asarray(inv)
                logits = logits[inv_arr]
                filled = (self.backend.expand_rows(filled, inv_arr)
                          if hasattr(self.backend, "expand_rows")
                          else jax.tree.map(lambda x: x[inv_arr], filled))
                self.stats["dedup_saved"] += len(group) - len(rows)
            # ONE sample over the full (G, V) group — the sampler splits its
            # key once per call, so deduping must not change the call count.
            with TraceAnnotation("engine.sample_first"):
                first = self._sample(logits)
            slots = np.asarray([self.sched.admit(r.rid) for r in group])
            self._bind_tenants(group, slots)
            # A slot freed by an immediate retirement earlier in this same
            # admission round may be handed out again here; drop its pending
            # reset — the insert fully overwrites the row, and a deferred
            # reset would clobber the new request's cache at end of tick.
            self._pending_reset = [s for s in self._pending_reset
                                   if s not in slots]
            with TraceAnnotation("engine.insert"):
                self.pool = self.backend.insert(self.pool, filled, slots)
            self.stats["prefill_batches"] += 1
            self._finish_admit(group, slots, first, plen)

    def _admit_paged(self) -> None:
        """Paged admission: exact-prompt prefix-cache hits map the entry's
        shared pages copy-free (COW via refcounts) and restore its stored
        recurrent state + first-token logits; misses bulk-prefill once per
        unique prompt, scatter into freshly allocated pages, and register a
        new entry.  The sampler still sees exactly one (G, V) call per
        prompt-length group, in the same group order as the contiguous
        engine — that keeps the seeded key chain aligned across layouts."""
        batch = self._pop_admission_batch()
        for plen, group in self._by_len(batch).items():
            # Classify in arrival order: hit / dup-of-miss / unique miss.
            plans = []                     # (request, kind, key, ref)
            miss_rows: List[np.ndarray] = []
            seen_miss: Dict[bytes, int] = {}
            for r in group:
                key = r.prompt.tobytes()
                entry = self.prefix.get(key)
                if entry is not None:
                    plans.append((r, "hit", key, entry))
                elif key in seen_miss:
                    plans.append((r, "dup", key, seen_miss[key]))
                    self.stats["dedup_saved"] += 1
                else:
                    seen_miss[key] = len(miss_rows)
                    miss_rows.append(r.prompt)
                    plans.append((r, "miss", key, seen_miss[key]))
            logits_u = filled = None
            if miss_rows:
                prompts = jnp.asarray(np.stack(miss_rows))
                with TraceAnnotation("engine.prefill"):
                    logits_u, filled = self.backend.prefill(prompts,
                                                            self.max_seq)
                self.stats["prefill_batches"] += 1
            # ONE sample per group over rows assembled in arrival order
            # (stored-entry logits for hits, fresh prefill rows otherwise).
            with TraceAnnotation("engine.sample_first"):
                first = self._sample(np.stack(
                    [p[3].logits if p[1] == "hit" else logits_u[p[3]]
                     for p in plans]))
            slots = np.asarray([self.sched.admit(r.rid) for r in group])
            self._bind_tenants(group, slots)
            self._pending_reset = [s for s in self._pending_reset
                                   if s not in slots]
            # Wire pages + state.  Misses first: allocate/map fresh pages,
            # one scatter for all their rows, then register prefix entries.
            n_alloc = -(-plen // self.page_size)
            miss_slots, miss_pt = [], []
            for p, slot in zip(plans, slots):
                if p[1] != "miss":
                    continue
                ids = self._alloc_pages(n_alloc)
                self.page_pool.map_slot(int(slot), ids, owned=True)
                miss_slots.append(int(slot))
                miss_pt.append(self.page_pool.table[int(slot)].copy())
            with TraceAnnotation("engine.insert"):
                if miss_slots:
                    self.pages = self.backend.paged_insert(
                        self.pages, filled, np.stack(miss_pt),
                        max_seq=self.max_seq, page_size=self.page_size)
                    if self._has_state:
                        self.state = self.backend.insert(
                            self.state, filled, np.asarray(miss_slots))
                    for p, slot in zip(plans, slots):
                        if p[1] == "miss":
                            self.prefix.register(
                                p[2], self.page_pool.slot_pages(int(slot)),
                                self.backend.state_rows(filled, p[3]),
                                logits_u[p[3]], plen)
                # Hits and dups share the entry's pages (refcounted → COW on
                # first divergent decode write) and restore its state rows.
                for p, slot in zip(plans, slots):
                    if p[1] == "miss":
                        continue
                    entry = (p[3] if p[1] == "hit"
                             else self.prefix.peek(p[2]))
                    self.page_pool.map_slot(int(slot), entry.page_ids,
                                            owned=False)
                    if entry.state is not None:
                        self.state = self.backend.state_restore(
                            self.state, entry.state, int(slot))
            self._finish_admit(group, slots, first, plen)
        self._sync_page_stats()

    def _alloc_pages(self, n: int) -> List[int]:
        """Allocate ``n`` pages, evicting LRU prefix entries until they fit."""
        while True:
            ids = self.page_pool.alloc(n)
            if ids is not None:
                return ids
            if not self.prefix.evict_lru():
                raise RuntimeError(
                    f"page pool exhausted: {n} pages requested, "
                    f"{self.page_pool.n_free} free and nothing left to "
                    f"evict — raise num_pages or lower n_slots/max_seq")

    def _ensure_write_pages(self, active_slots: List[int]) -> None:
        """Before a decode tick, make every active slot's write page private
        and mapped: unmapped → allocate; shared (refcount > 1, i.e. a prefix
        entry or sibling slot also references it) → copy-on-write fork.
        The COW here is what makes prefix sharing *correct*, not just fast —
        without it the first divergent token would corrupt siblings."""
        copies = []                         # (src, dst) page-id pairs
        for s in active_slots:
            pos = int(self.pos[s])
            idxs = {(pos % size if ring else pos) // self.page_size
                    for size, ring in self._geoms}
            for j in sorted(idxs):
                pid = int(self.page_pool.table[s, j])
                if pid == 0:
                    (new,) = self._alloc_pages(1)
                    self.page_pool.map_index(s, j, new)
                elif self.page_pool.refcount[pid] > 1:
                    (new,) = self._alloc_pages(1)
                    self.page_pool.remap(s, j, new)
                    copies.append((pid, new))
                    self.stats["cow_copies"] += 1
        if copies:
            # One fixed-shape scatter for all forks this tick, padded with
            # (0, 0) — copying the zero page onto itself is a no-op.
            cap = self.n_slots * max(1, len(self._geoms))
            assert len(copies) <= cap
            pairs = copies + [(0, 0)] * (cap - len(copies))
            self.pages = self.backend.page_copy(
                self.pages, np.asarray([p[0] for p in pairs], np.int32),
                np.asarray([p[1] for p in pairs], np.int32),
                max_seq=self.max_seq, page_size=self.page_size)

    def _sync_page_stats(self) -> None:
        self.stats["page_allocs"] = self.page_pool.page_allocs
        self.stats["pages_in_use"] = self.page_pool.pages_in_use
        self.stats["pages_in_use_peak"] = self.page_pool.peak_in_use
        self.stats["prefix_hits"] = self.prefix.hits
        self.stats["prefix_queries"] = self.prefix.queries

    def _retire(self, slot: int) -> None:
        rid = self.sched.retire(slot)
        self.finished[rid] = self.outputs[rid]
        if self.head_cache is not None and self.slot_tenant[slot] is not None:
            self.head_cache.release(self.slot_tenant[slot])
            self.slot_tenant[slot] = None
        # Resets are batched per tick (one jitted call for all retirements
        # this step) — a freed row is never read while inactive, and
        # ``slot_insert`` fully overwrites it on re-admission.
        self._pending_reset.append(slot)
        if self.paged:
            # Unmap the slot's pages (prefix entries keep shared ones alive;
            # exclusively owned ones return to the free list).
            self.page_pool.clear_slot(slot)
        self.stats["retired"] += 1

    # -- per-tenant heads (DESIGN.md §14) ----------------------------------

    def _head_params_now(self):
        """This tick's decode head params: the HeadCache bank plus the
        slot→bank-row binding (``None`` on single-tenant engines — the
        backend then serves its own bound ``head.params``).  Free slots
        point at bank row 0; their logits are masked/ignored anyway."""
        if self.head_cache is None:
            return None
        ids = np.zeros(self.n_slots, np.int32)
        for s, t in enumerate(self.slot_tenant):
            if t is not None:
                ids[s] = self.head_cache.slot(t)
        return self.head_cache.bank_params(ids)

    def refresh(self, tenant, hidden, *, targets=None, alphas=None,
                lr: float = 1.0) -> None:
        """Fold live-traffic (hidden, logit) pairs into ``tenant``'s head
        online (``kernels/race_update``; DESIGN.md §14).

        Accumulates into a host-held f32 working copy — the *shadow* buffer
        of the double-buffered scheme; in-flight and subsequent decodes keep
        reading the published bank row bitwise unchanged until
        :meth:`publish` commits.  Exactly one of ``alphas`` ((M, V) direct
        representer weights) or ``targets`` ((M, V) teacher logits for the
        residual fold, scaled by ``lr``) must be given; the tenant must be
        resident (acquired at least once).
        """
        if self.head_cache is None:
            raise ValueError("refresh needs a per-tenant engine — pass "
                             "head_cache= to make_engine/ServeEngine")
        from repro.core.sketch_lm_head import dequantize_head, refresh_head
        spec = self.backend.head
        if tenant not in self._refresh:
            self._refresh[tenant] = dequantize_head(
                self.head_cache.tenant_params(tenant), spec.quant)
        self._refresh[tenant] = refresh_head(
            self._refresh[tenant], spec.cfg, hidden,
            targets=targets, alphas=alphas, lr=lr)
        self.stats["refreshes"] += 1

    def publish(self, tenant) -> None:
        """Commit ``tenant``'s pending refreshes: re-quantize the f32
        working copy to the head's storage mode and swap it into the bank
        between ticks.  Re-quantization happens here, not per refresh —
        repeated int8/int4 round-trips would compound rounding error, so
        the shadow stays f32 until the publish."""
        if tenant not in self._refresh:
            raise ValueError(f"no pending refresh for tenant {tenant!r}; "
                             f"call engine.refresh(...) first")
        from repro.core.sketch_lm_head import quantize_head
        params = quantize_head(self._refresh.pop(tenant),
                               self.backend.head.quant)
        self.head_cache.publish(tenant, params)
        self.stats["publishes"] += 1

    # -- the engine tick ---------------------------------------------------

    def _chunk_for(self, active_slots: List[int],
                   base: Optional[int] = None) -> int:
        """The megastep length for this tick: ``base`` (``decode_chunk``, or
        the speculative draft length) clamped so no occupied slot overshoots
        its budget (its remaining tokens) and — when a slot is free to admit
        into — no queued arrival is kept waiting past its arrival tick."""
        chunk = min(base or self.decode_chunk,
                    int(min(self.remaining[s] for s in active_slots)))
        if self.queue and self.sched.n_free:
            chunk = min(chunk, max(1, self.queue.peek().arrival - self.now))
        return max(1, chunk)

    def _decode_megastep(self, active_slots: List[int], chunk: int) -> None:
        """Advance every occupied slot ``chunk`` tokens in one device
        dispatch, then walk the returned (chunk, B) block for per-slot
        retirement (EOS mid-chunk rows are frozen in-scan; their trailing
        block entries are padding and are skipped here)."""
        active = np.zeros(self.n_slots, bool)
        active[active_slots] = True
        hp = self._head_params_now()
        kw = {} if hp is None else {"head_params": hp}
        if hasattr(self.backend, "megastep"):
            (block, self.pool, self.last_tok, self.pos, _,
             self._key) = self.backend.megastep(
                self.pool, self.last_tok, self.pos, active, self._key,
                chunk, self.sampler, self.eos_id, **kw)
            # One block fetch per dispatch; the emulated path below counts
            # its per-token syncs inside _sample instead.
            self.stats["host_syncs"] += 1
        else:
            block = self._emulate_megastep(active, chunk)
        self.stats["decode_steps"] += chunk
        self.stats["megasteps"] += 1
        with TraceAnnotation("engine.emit"):
            self._emit(active_slots, block, chunk)

    def _emit(self, active_slots: List[int], block: np.ndarray,
              rows: int) -> None:
        """Append the first ``rows`` tokens of each active slot's column of
        ``block`` to its request, retiring it at its budget or on EOS (a
        retired row's later entries are padding)."""
        for s in active_slots:
            for i in range(rows):
                tok = int(block[i, s])
                self.outputs[self.sched.owner[s]].append(tok)
                self.remaining[s] -= 1
                self.stats["active_slot_steps"] += 1
                if (self.remaining[s] == 0
                        or (self.eos_id is not None and tok == self.eos_id)):
                    self._retire(s)
                    break

    def _decode_spec_megastep(self, active_slots: List[int],
                              draft_k: int) -> int:
        """One speculative tick: draft ``draft_k`` tokens through the
        engine's head, dense-verify the block, and commit the ``m``
        lockstep-accepted steps — then walk the committed rows exactly like
        ``_decode_megastep`` (EOS mid-block retires; trailing entries of a
        retired row are padding).  Returns ``m`` (the tick clock advance)."""
        active = np.zeros(self.n_slots, bool)
        active[active_slots] = True
        (block, m, acc, self.pool, self.last_tok, self.pos, _,
         self._key) = self.backend.spec_megastep(
            self.pool, self.last_tok, self.pos, active, self._key,
            draft_k, self.sampler, self.eos_id)
        self.stats["host_syncs"] += 1
        self.stats["decode_steps"] += draft_k      # backbone (draft) steps
        self.stats["megasteps"] += 1
        self.stats["verify_calls"] += 1
        self.stats["draft_tokens"] += draft_k * len(active_slots)
        self.stats["accepted_draft_tokens"] += int(acc[active_slots].sum())
        with TraceAnnotation("engine.emit"):
            self._emit(active_slots, block, m)
        return m

    def _emulate_megastep(self, active: np.ndarray, chunk: int) -> np.ndarray:
        """Host-loop emulation of the fused megastep for backends without
        one (e.g. the numpy fake in the property tests): same step→sample→
        mask→retire sequence, one backend.decode per token."""
        active = active.copy()
        block = np.zeros((chunk, self.n_slots), np.int32)
        hp = self._head_params_now()
        kw = {} if hp is None else {"head_params": hp}
        for i in range(chunk):
            step_active = active.copy()
            logits, self.pool = self.backend.decode(
                self.pool, self.last_tok, self.pos, step_active, **kw)
            nxt = np.where(step_active, self._sample(logits), 0).astype(
                np.int32)
            if self.eos_id is not None:
                active &= nxt != self.eos_id
            block[i] = nxt
            self.pos += step_active.astype(np.int32)
            self.last_tok = nxt
        return block

    def step(self) -> None:
        """One tick: admit into free slots, then decode every occupied slot
        — one token (``decode_chunk=1``, the bitwise-parity default) or a
        ``decode_chunk``-clamped megastep block.

        Each phase is a ``jax.profiler.TraceAnnotation`` span (``engine.admit``
        holding ``engine.prefill`` / ``engine.sample_first`` /
        ``engine.insert``; ``engine.decode``, ``engine.fetch``,
        ``engine.emit``, ``engine.reset``): free unless a profiler trace is
        running, and then on the device trace's clock (DESIGN.md §15)."""
        with TraceAnnotation("engine.admit"):
            self._admit()
        active_slots = self.sched.active_slots()
        advanced = 1
        if active_slots and self.spec_decode:
            draft_k = self._chunk_for(active_slots, base=self.spec_decode)
            advanced = self._decode_spec_megastep(active_slots, draft_k)
        elif active_slots and self.decode_chunk > 1:
            advanced = self._chunk_for(active_slots)
            self._decode_megastep(active_slots, advanced)
        elif active_slots:
            active = np.zeros(self.n_slots, bool)
            active[active_slots] = True
            hp = self._head_params_now()
            kw = {} if hp is None else {"head_params": hp}
            if self.paged:
                with TraceAnnotation("engine.decode"):
                    self._ensure_write_pages(active_slots)
                logits, self.pages, self.state = self.backend.paged_decode(
                    self.pages, self.state, self.page_pool.table,
                    self.last_tok, self.pos, active,
                    max_seq=self.max_seq, page_size=self.page_size, **kw)
            else:
                logits, self.pool = self.backend.decode(
                    self.pool, self.last_tok, self.pos, active, **kw)
            with TraceAnnotation("engine.sample"):
                nxt = self._sample(logits)
            self.stats["decode_steps"] += 1
            self.stats["megasteps"] += 1
            self.stats["active_slot_steps"] += len(active_slots)
            with TraceAnnotation("engine.emit"):
                for s in active_slots:
                    tok = int(nxt[s])
                    self.outputs[self.sched.owner[s]].append(tok)
                    self.pos[s] += 1
                    self.last_tok[s] = tok
                    self.remaining[s] -= 1
                    if (self.remaining[s] == 0
                            or (self.eos_id is not None
                                and tok == self.eos_id)):
                        self._retire(s)
        if self._pending_reset:
            with TraceAnnotation("engine.reset"):
                self._reset_pending()
        if self.paged:
            self._sync_page_stats()
        self.now += advanced

    def _reset_pending(self) -> None:
        """Zero the rows of every slot retired this tick, in one dispatch."""
        # Pad to a fixed (n_slots,) shape so the jitted reset compiles
        # once; duplicate indices write the same zeros, so padding with
        # the first slot is a no-op.
        slots = self._pending_reset + [self._pending_reset[0]] * (
            self.n_slots - len(self._pending_reset))
        if self.paged:
            # Pages were unmapped at retirement (the arena needs no
            # zeroing — unmapped gathers read the reserved zero page);
            # only the recurrent state rows are zeroed.
            if self._has_state:
                self.state = self.backend.reset(self.state,
                                                np.asarray(slots))
        else:
            self.pool = self.backend.reset(self.pool, np.asarray(slots))
        self._pending_reset.clear()

    def run(self) -> Dict[int, List[int]]:
        """Tick until the queue drains and every slot retires."""
        while self.queue or self.sched.n_active:
            if not self.sched.n_active and self.queue.peek().arrival > self.now:
                self.now = self.queue.peek().arrival  # idle: jump to arrival
            self.step()
        return self.finished

    @property
    def slot_utilization(self) -> float:
        """Mean fraction of slots doing useful work per decode step."""
        steps = self.stats["decode_steps"]
        return (self.stats["active_slot_steps"] / (steps * self.n_slots)
                if steps else 0.0)


def make_engine(params, cfg: ModelConfig, n_slots: int, max_seq: int, *,
                head: Optional[LogitHead] = None,
                sampler: Optional[Sampler] = None,
                eos_id: Optional[int] = None, mesh=None,
                decode_chunk: int = 1, spec_decode: int = 0,
                paged: bool = False, page_size: int = 16,
                num_pages: Optional[int] = None, head_cache=None,
                sketch_head=None, sketch_cfg: Optional[SketchHeadConfig] = None,
                fused=None, greedy=None, seed=None) -> ServeEngine:
    """Engine over a real model: the serving entry point (see launch.serve
    and the ``LM.engine`` / ``LM.serve`` facade).  ``mesh`` makes the whole
    engine SPMD-sharded: the slot pool's cache rows batch-shard over
    ``data``, head count arrays over ``model``, and the slot ops preserve
    those shardings across insert/reset (DESIGN.md §9).  ``decode_chunk=K``
    decodes K tokens per occupied slot between admission rounds in one
    on-device megastep (launch/decode_loop.py, DESIGN.md §10); the default
    1 keeps the per-token tick, bitwise-identical to the pre-megastep
    engine.  ``spec_decode=K`` makes every tick a speculative two-head
    megastep instead: the engine's ``head`` drafts K tokens and one batched
    dense pass verifies them, emitting the dense stream bitwise (DESIGN.md
    §11; mutually exclusive with ``decode_chunk > 1``).  ``paged=True``
    swaps the fixed per-slot pool for the paged arena + prefix cache
    (DESIGN.md §13): slots map ``page_size``-token pages through a
    refcounted page table, identical prompts hit the prefix cache instead
    of re-prefilling, and shared pages fork copy-on-write on the first
    divergent decode write — token streams stay bitwise identical to the
    contiguous engine.  ``head_cache=`` (a ``repro.api.HeadCache``) makes
    the engine *per-tenant* (DESIGN.md §14): ``head`` becomes the shared
    sketch spec (config/backend/quant) while each slot decodes through its
    request's tenant's arrays, paged in/out of the cache on demand; every
    ``submit`` then needs ``tenant=``, and ``engine.refresh(tenant, ...)``
    / ``engine.publish(tenant)`` fold live traffic into a tenant's head
    online.  The pre-redesign
    ``sketch_head=/sketch_cfg=/fused=/greedy=/seed=`` kwargs keep working
    behind a DeprecationWarning."""
    head, sampler = resolve_legacy_serving_kwargs(
        head, sampler, sketch_head, sketch_cfg, fused, greedy, seed,
        "make_engine")
    if head_cache is not None:
        from repro.api.heads import SketchHead
        if not isinstance(head, SketchHead):
            raise ValueError(
                "head_cache= (per-tenant serving) needs a SketchHead spec "
                f"for head=; got {type(head).__name__ if head is not None else None}")
        head = dataclasses.replace(head.without_params(), per_tenant=True)
        if head_cache.mesh is None:
            head_cache.mesh = mesh
    backend = EngineBackend(params, cfg, head=head, mesh=mesh)
    return ServeEngine(backend, n_slots, max_seq, eos_id=eos_id,
                       sampler=sampler, decode_chunk=decode_chunk,
                       spec_decode=spec_decode, paged=paged,
                       page_size=page_size, num_pages=num_pages,
                       head_cache=head_cache)
