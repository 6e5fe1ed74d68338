"""The one traffic generator: a mix's JSON parameters plus a seed in, a
request schedule out.

A mix file (``bench/traffic/<name>.json``) holds:

* ``arrival``: ``"backlog"`` (a closed backlog: every request is due at the
  start, and the client refills free slots) or ``"poisson"`` (an open loop at
  ``rate_per_s``);
* ``slots``, ``max_seq``: the engine the mix is offered to;
* ``admit_per_tick``: the most requests the client submits per engine step,
  which bounds the prefill shapes to ``(G <= admit_per_tick, P in buckets)``;
* ``prompt_buckets`` and ``prompt_weights``: prompt lengths and their shares;
* ``output``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
* ``block``: requests per block;
* ``schedule_seed`` (optional): fixes the order of sizes and gaps.

Every seed gets the same sizes and arrival gaps: each block of ``block``
requests holds the same multiset of prompt lengths, output lengths and gaps
(stratified quantiles of the distributions).  Their order within a block is
shuffled by ``schedule_seed`` when the mix fixes one (a replayed trace: every
run offers the same requests at the same times) and by the run's seed
otherwise.  The run's seed always draws the prompt tokens.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Set, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int
    prompt_len: int
    max_new: int
    due_s: float          # seconds after the window opens (0 for a backlog)


def _counts(weights, n: int) -> List[int]:
    """Split ``n`` into integer counts proportional to ``weights``
    (largest remainder)."""
    w = np.asarray(weights, float)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    order = np.argsort(-(exact - counts), kind="stable")
    for i in order[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def output_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified output lengths of the ``output`` spec."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        raw = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), lo, hi).astype(int)


class Schedule:
    """The requests of one mix under one seed, made lazily by index."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.block = int(mix["block"])
        self.buckets = [int(p) for p in mix["prompt_buckets"]]
        self.admit_per_tick = int(mix["admit_per_tick"])
        prompt = np.repeat(self.buckets,
                           _counts(mix["prompt_weights"], self.block))
        self._prompt = prompt
        self._out = output_lengths(mix["output"], self.block)
        if mix["arrival"] == "poisson":
            rate = float(mix["rate_per_s"])
            self._gap = -np.log1p(-_quantiles(self.block)) / rate
        elif mix["arrival"] == "backlog":
            self._gap = np.zeros(self.block)
        else:
            raise ValueError(f"unknown arrival {mix['arrival']!r}")
        self._blocks = {}
        longest = max(self.buckets) + int(mix["output"]["max"])
        if longest > int(mix["max_seq"]) + 1:
            raise ValueError(f"max_seq {mix['max_seq']} cannot hold a "
                             f"{longest}-token request")

    def _rng(self, *salt) -> np.random.Generator:
        return np.random.default_rng([self.seed, *salt])

    def _block(self, b: int):
        if b not in self._blocks:
            fixed = self.mix.get("schedule_seed")
            rng = (self._rng(0, b) if fixed is None
                   else np.random.default_rng([int(fixed), 0, b]))
            self._blocks[b] = (rng.permutation(self._prompt),
                               rng.permutation(self._out),
                               rng.permutation(self._gap))
            start = (0.0 if b == 0
                     else self._block(b - 1)[3] + self._blocks[b - 1][2].sum())
            self._blocks[b] = self._blocks[b] + (start,)
        return self._blocks[b]

    def request(self, i: int) -> Request:
        b, j = divmod(i, self.block)
        prompt, out, gap, start = self._block(b)
        due = 0.0
        if self.mix["arrival"] == "poisson":
            due = start + float(np.cumsum(gap)[j])
        return Request(i, int(prompt[j]), int(out[j]), due)

    def tokens(self, i: int) -> np.ndarray:
        """The prompt token ids of request ``i``."""
        return self._rng(1, i).integers(
            0, self.vocab, self.request(i).prompt_len, dtype=np.int32)

    def shapes(self) -> Set[Tuple[int, int]]:
        """Every (G, P) prefill shape the client can make the engine run."""
        return {(g, p) for g in range(1, self.admit_per_tick + 1)
                for p in self.buckets}

    def mean_prompt(self) -> float:
        return float(np.mean(self._prompt))

    def mean_output(self) -> float:
        return float(np.mean(self._out))


def seed_key(seed: int) -> int:
    """A 32-bit key for ``jax.random.PRNGKey`` from any whole seed."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0])


def rate_for_block(mix: dict) -> float:
    """Requests per second a block of the mix offers (0 for a backlog)."""
    if mix["arrival"] != "poisson":
        return 0.0
    gap = -np.log1p(-_quantiles(int(mix["block"]))) / float(mix["rate_per_s"])
    return len(gap) / float(gap.sum())

