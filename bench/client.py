"""The benchmark's client: offers a schedule to the serving engine and keeps
its own clock.

It submits each request once it is due, with ``arrival=engine.now``, at most
``admit_per_tick`` per engine step and never more than the engine has free
slots for, so every prefill batch the engine forms is one of the warmed
``(G <= admit_per_tick, P)`` shapes.  After every ``step()`` it polls
``engine.outputs`` and stamps each request's admission (the start of the
step that produced its first token), first token and last token.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Optional

import numpy as np

from bench.traffic import Schedule


@dataclasses.dataclass
class Record:
    idx: int
    prompt_len: int
    max_new: int
    due: float                       # absolute clock time it was due
    rid: Optional[int] = None
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None  # start of the step that admitted it
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    n: int = 0


class Client:
    """Drives ``engine`` with the requests of ``sched``."""

    def __init__(self, engine, sched: Schedule, *, annotate=None,
                 clock=time.perf_counter):
        self.engine = engine
        self.sched = sched
        self.clock = clock
        self.span = annotate or (lambda _name: contextlib.nullcontext())
        self.backlog = sched.mix["arrival"] == "backlog"
        self.records: Dict[int, Record] = {}
        self.by_rid: Dict[int, Record] = {}
        self.live: Dict[int, Record] = {}       # submitted, not finished
        self.next_idx = 0                       # next request to submit
        self.created = 0                        # records made so far
        self.origin: Optional[float] = None     # clock time of due_s == 0
        self.tokens = 0                         # tokens emitted so far
        self.live_positions = 0                 # attention positions decoded
        self.steps: list = []                   # (start, end, tokens) a step

    # -- submission --------------------------------------------------------

    def _record(self, idx: int) -> Record:
        req = self.sched.request(idx)
        due = self.clock() if self.backlog else self.origin + req.due_s
        rec = Record(req.idx, req.prompt_len, req.max_new, due)
        self.records[idx] = rec
        self.created = idx + 1
        return rec

    def _peek(self) -> Optional[Record]:
        """The next request not yet submitted (None before an open loop's
        origin is set)."""
        rec = self.records.get(self.next_idx)
        if rec is None:
            if not self.backlog and self.origin is None:
                return None
            rec = self._record(self.next_idx)
        return rec

    def materialize(self, now: float) -> None:
        """Open loop: make a record of every request due by ``now``, so
        that those still waiting in the client count in the tails."""
        if self.backlog or self.origin is None:
            return
        while self.created == 0 or self.records[self.created - 1].due <= now:
            self._record(self.created)

    def submit_due(self) -> int:
        """Submit what is due, within the per-step and free-slot limits."""
        eng = self.engine
        room = min(self.sched.admit_per_tick,
                   eng.sched.n_free - len(eng.queue))
        n = 0
        while n < room:
            rec = self._peek()
            now = self.clock()
            if rec is None or rec.due > now:
                break
            rec.rid = eng.submit(self.sched.tokens(rec.idx), rec.max_new,
                                 arrival=eng.now)
            rec.submit_t = now
            self.by_rid[rec.rid] = rec
            self.live[rec.rid] = rec
            self.next_idx += 1
            n += 1
        return n

    def next_due(self) -> float:
        """Clock time the next unsubmitted request is due."""
        rec = self._peek()
        return float("inf") if rec is None else rec.due

    # -- one tick ----------------------------------------------------------

    def step(self) -> None:
        with self.span("client.submit"):
            self.materialize(self.clock())
            self.submit_due()
        if not self.engine.sched.n_active and not len(self.engine.queue):
            wait = min(self.next_due() - self.clock(), 0.002)
            if wait > 0:
                with self.span("client.wait"):
                    time.sleep(wait)
            return
        t0 = self.clock()
        with self.span("engine.step"):
            self.engine.step()
        t1 = self.clock()
        with self.span("client.poll"):
            self._poll(t0, t1)

    def _poll(self, t0: float, t1: float) -> None:
        outputs, finished = self.engine.outputs, self.engine.finished
        emitted = 0
        for rid in list(self.live):
            rec = self.live[rid]
            n = len(outputs.get(rid, ()))
            if n > rec.n:
                if rec.n == 0:
                    rec.admit_t, rec.first_t = t0, t1
                # token j >= 1 is decoded at position P + j - 1 and attends
                # over P + j positions
                a, b = max(rec.n, 1), n
                if b > a:
                    self.live_positions += ((b - a) * rec.prompt_len
                                            + (a + b - 1) * (b - a) // 2)
                emitted += n - rec.n
                rec.n = n
            if rid in finished:
                rec.last_t = t1
                del self.live[rid]
        self.tokens += emitted
        self.steps.append((t0, t1, emitted))

    # -- phases ------------------------------------------------------------

    def fill(self, deadline: float) -> None:
        """Backlog only: step until every slot is busy (set-up)."""
        while (self.engine.sched.n_free > 0 and self.clock() < deadline):
            self.step()

    def run(self, seconds: float) -> tuple:
        """The measured window; returns its (start, end) clock times.

        It ends at the end of the first step that finishes after
        ``seconds``, so the window holds whole steps only."""
        t0 = self.clock()
        if self.origin is None:
            self.origin = t0
        while self.clock() < t0 + seconds:
            self.step()
        t1 = self.clock()
        self.materialize(t1)
        return t0, t1

    def counters(self) -> dict:
        st = self.engine.stats
        return {"decode_steps": st["decode_steps"],
                "active_slot_steps": st["active_slot_steps"],
                "prefill_batches": st["prefill_batches"],
                "megasteps": st["megasteps"], "host_syncs": st["host_syncs"],
                "tokens": self.tokens, "live_positions": self.live_positions,
                "prompt_tokens": sum(r.prompt_len for r in
                                     self.records.values()
                                     if r.admit_t is not None),
                "t": self.clock()}


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (numpy's linear rule); None when empty."""
    return float(np.percentile(values, q)) if len(values) else None
