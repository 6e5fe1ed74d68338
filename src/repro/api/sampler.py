"""Sampler: one hashable object for the whole decode-time sampling policy.

Replaces the ``greedy: bool`` + ``seed: int`` pair that used to thread
positionally through ``generate()`` and the engine.  A ``Sampler`` is a
frozen dataclass, so it can key jit memo caches; its ``sample`` method is
jitted once per distinct sampler.

Semantics:

* ``temperature == 0``  → greedy argmax (the default); the key is untouched.
* ``temperature > 0``   → softmax sampling at that temperature, after
  optional ``top_k`` (keep the k largest logits) and ``top_p`` (smallest
  nucleus whose probability mass ≥ p) filtering.
* The PRNG is a *key chain* seeded once from ``seed``: every step splits the
  carried key, so runs with the same seed reproduce bitwise and different
  seeds give independent streams.  ``Sampler(temperature=1.0, seed=s)``
  reproduces the pre-redesign ``greedy=False, seed=s`` token streams
  exactly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Sampler:
    """The decode-time sampling policy as one hashable spec.

    Attributes:
      temperature: 0 → greedy argmax (default); > 0 → softmax sampling.
      top_k: keep only the k largest logits (0 disables).
      top_p: keep the smallest nucleus with probability mass ≥ p
        (1.0 disables).
      seed: PRNG seed for the per-run key chain.

    Raises:
      ValueError: on a negative temperature / top_k, or top_p ∉ (0, 1].

    >>> Sampler().describe()
    'greedy'
    >>> Sampler(temperature=0.8, top_k=40, seed=1).describe()
    'sample(t=0.8,top_k=40,seed=1)'
    >>> Sampler(top_p=0)
    Traceback (most recent call last):
        ...
    ValueError: top_p must be in (0, 1], got 0
    """

    temperature: float = 0.0   # 0 → greedy
    top_k: int = 0             # 0 → no top-k filter
    top_p: float = 1.0         # 1 → no nucleus filter
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")

    @classmethod
    def greedy(cls) -> "Sampler":
        """The greedy policy (equivalent to ``Sampler()``)."""
        return cls()

    @property
    def is_greedy(self) -> bool:
        """True when ``temperature == 0`` (argmax; PRNG never consumed)."""
        return self.temperature == 0.0

    def init_key(self) -> jax.Array:
        """The root of this sampler's split-key chain (from ``seed``)."""
        return jax.random.PRNGKey(self.seed)

    def sample(self, key: jax.Array,
               logits: jnp.ndarray) -> Tuple[jax.Array, jnp.ndarray]:
        """Pick one token per row.

        Args:
          key: the carried chain key (start from :meth:`init_key`).
          logits: (B, V) logits.

        Returns:
          ``(next_key, tokens)`` — the advanced chain key (untouched when
          greedy) and (B,) int32 token ids.  Jitted once per distinct
          sampler spec.
        """
        return _jitted_sample(self)(key, jnp.asarray(logits))

    def describe(self) -> str:
        """Short human-readable policy summary (see class doctest)."""
        if self.is_greedy:
            return "greedy"
        parts = [f"t={self.temperature:g}"]
        if self.top_k:
            parts.append(f"top_k={self.top_k}")
        if self.top_p < 1.0:
            parts.append(f"top_p={self.top_p:g}")
        return f"sample({','.join(parts)},seed={self.seed})"


def _filter_logits(sampler: Sampler, logits: jnp.ndarray) -> jnp.ndarray:
    """Apply top-k then top-p in f32; untouched logits stay bitwise as-is."""
    logits = logits.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    if sampler.top_k and sampler.top_k < logits.shape[-1]:
        kth = jnp.sort(logits, axis=-1)[..., -sampler.top_k][..., None]
        logits = jnp.where(logits < kth, neg, logits)
    if sampler.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]   # descending
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with mass >= top_p (always >= 1 token):
        # a token is cut iff the mass *before* it already reached top_p.
        cut = cum - probs >= sampler.top_p
        # Threshold on the smallest *kept* logit: a cut token tied with it
        # also survives (thresholding by value cannot split ties, and
        # masking the tie would mask the kept token with it, emptying the
        # row); anything strictly below the nucleus is dropped.
        keep_min = jnp.where(cut, jnp.inf, sorted_logits).min(axis=-1,
                                                              keepdims=True)
        logits = jnp.where(logits < keep_min, neg, logits)
    return logits


@jax.named_scope("sample")
def _sample_impl(sampler: Sampler, key: jax.Array, logits: jnp.ndarray):
    if sampler.is_greedy:
        return key, jnp.argmax(logits, axis=-1).astype(jnp.int32)
    key, sub = jax.random.split(key)
    if sampler.temperature != 1.0:
        logits = logits / sampler.temperature
    if sampler.top_k or sampler.top_p < 1.0:
        logits = _filter_logits(sampler, logits)
    return key, jax.random.categorical(sub, logits).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _jitted_sample(sampler: Sampler):
    """One compiled sampler per distinct Sampler spec (hashable memo key),
    the program ``jit_sample``."""
    def sample(key, logits):
        return _sample_impl(sampler, key, logits)

    return jax.jit(sample)
