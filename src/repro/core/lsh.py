"""Locality-sensitive hash families used by Representer Sketch.

Implements the three LSH families referenced by the paper:

* :class:`L2LSH` — p-stable (Gaussian) Euclidean LSH of Datar et al. [44].
  ``h(x) = floor((w·x + b) / r)`` with ``w ~ N(0, I)``, ``b ~ U[0, r)``.
  Its collision probability is the (shift-invariant, *universal*) L2-LSH
  kernel of Lemma 2.
* :class:`SRPLSH` — sign random projections for angular similarity.
* :class:`AchlioptasL2LSH` — the database-friendly variant the paper uses at
  inference time: projection entries are ``sqrt(3)·{−1, 0, +1}`` with
  probabilities ``{1/6, 2/3, 1/6}`` so hashing costs only adds/subs on edge
  hardware.  On TPU we keep the same distribution but materialize it dense so
  the projection runs on the MXU (see DESIGN.md §3).

Every family exposes:

* ``params(key, d)`` — pytree of hash parameters for ``L`` rows × ``K``
  concatenated hashes.
* ``hash(params, x)`` — ``(..., L)`` int32 row indices in ``[0, R)`` for a
  batch of points, with the K sub-hashes combined into one index by a
  universal rehash (the "suitable transformation to Z" of §3.4).
* ``collision_probability(dist)`` — the LSH kernel ``K(x, y)`` as a function
  of distance, used by the pure-python oracle and the theory tests.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import HASH_PRECISION

# Large primes for the universal rehash that folds K sub-hash integers into a
# single table index.  Classic Carter–Wegman style mixing.
_MIX_PRIME = np.int64(2038074743)
_MIX_A = np.int64(1103515245)
_MIX_B = np.int64(12345)


def row_salts(n_rows: int, start=0) -> jnp.ndarray:
    """Golden-ratio fold salts for sketch rows ``[start, start + n_rows)``.

    The fold salt is a function of the *global* row index; sharded decode
    paths that evaluate a contiguous row slice (kernels/fused_decode's
    shard_map path) must pass the offset salts explicitly or their buckets
    diverge from the single-device hash.  ``start`` may be traced (it comes
    from ``jax.lax.axis_index`` inside shard_map).
    """
    rows = jnp.arange(n_rows, dtype=jnp.int32) + start
    return rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)


def _fold_subhashes(codes: jnp.ndarray, n_buckets: int,
                    salt: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fold ``(..., L, K)`` integer sub-hash codes into ``(..., L)`` indices.

    Carter–Wegman-style iterated affine mix in uint32, **salted by the row
    index**: each of the L sketch rows must realize an *independent* bucket
    map — without the salt, rows whose p-stable codes coincide (tiny code
    support at k=1!) collapse onto identical buckets and the sketch loses
    its i.i.d.-rows guarantee (caught by the bucket-uniformity test).
    ``salt`` overrides the default ``row_salts(L)`` (row-sharded callers).
    """
    codes = codes.astype(jnp.uint32)
    k = codes.shape[-1]
    n_rows = codes.shape[-2]
    if salt is None:
        salt = row_salts(n_rows)
    acc = jnp.broadcast_to(salt, codes.shape[:-1]).astype(jnp.uint32)
    for i in range(k):
        acc = acc * jnp.uint32(_MIX_A & 0xFFFFFFFF) + codes[..., i] + jnp.uint32(i * 97 + 13)
        acc = acc ^ (acc >> 16)
        acc = acc * jnp.uint32(0x45D9F3B)
        acc = acc ^ (acc >> 16)
    return (acc % jnp.uint32(n_buckets)).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class LSHConfig:
    """Static configuration of a concatenated LSH bank.

    Attributes:
      n_rows:    L — number of independent sketch rows.
      n_buckets: R — number of buckets (columns) per row.
      k:         number of concatenated sub-hashes per row.
      bandwidth: r — quantization width of the p-stable scheme (L2 only).
      dim:       input dimensionality d (or d' after the asymmetric transform).
    """

    n_rows: int
    n_buckets: int
    k: int
    dim: int
    bandwidth: float = 1.0


class L2LSH:
    """p-stable Euclidean LSH (Datar et al.), the paper's universal kernel."""

    def __init__(self, config: LSHConfig):
        self.config = config

    def params(self, key: jax.Array) -> dict:
        c = self.config
        kw, kb = jax.random.split(key)
        w = jax.random.normal(kw, (c.n_rows, c.k, c.dim), dtype=jnp.float32)
        b = jax.random.uniform(kb, (c.n_rows, c.k), minval=0.0, maxval=c.bandwidth)
        return {"w": w, "b": b}

    def subhash(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        """Return raw integer sub-hash codes with shape ``(..., L, K)``."""
        c = self.config
        # (..., d) @ (L, K, d) -> (..., L, K)
        proj = jnp.einsum("...d,lkd->...lk", x, params["w"],
                          precision=HASH_PRECISION)
        return jnp.floor((proj + params["b"]) / c.bandwidth).astype(jnp.int32)

    def hash(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        return _fold_subhashes(self.subhash(params, x), self.config.n_buckets)

    def collision_probability(self, dist: jnp.ndarray) -> jnp.ndarray:
        """L2-LSH kernel: P[h(x)=h(y)] as a function of c = ||x-y||_2.

        Closed form from Datar et al.:
          p(c) = 1 - 2·Phi(-r/c) - (2c / (sqrt(2π) r)) (1 - exp(-r²/(2c²)))
        Returns the K-fold power (independent concatenation).
        """
        r = self.config.bandwidth
        c = jnp.maximum(dist, 1e-9)
        t = r / c
        phi = 0.5 * (1.0 + jax.scipy.special.erf(-t / jnp.sqrt(2.0)))
        p1 = 1.0 - 2.0 * phi - (2.0 / (jnp.sqrt(2.0 * jnp.pi) * t)) * (
            1.0 - jnp.exp(-(t * t) / 2.0)
        )
        p1 = jnp.where(dist <= 1e-9, 1.0, p1)
        return jnp.clip(p1, 0.0, 1.0) ** self.config.k


class SRPLSH:
    """Sign random projection LSH; collision prob 1 − θ/π (angular kernel)."""

    def __init__(self, config: LSHConfig):
        self.config = config

    def params(self, key: jax.Array) -> dict:
        c = self.config
        w = jax.random.normal(key, (c.n_rows, c.k, c.dim), dtype=jnp.float32)
        return {"w": w}

    def subhash(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        proj = jnp.einsum("...d,lkd->...lk", x, params["w"],
                          precision=HASH_PRECISION)
        return (proj >= 0).astype(jnp.int32)

    def hash(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        # K sign bits pack exactly into an integer code; when 2^K <= R the
        # packed code *is* the bucket index (no mixing needed), otherwise mix.
        c = self.config
        bits = self.subhash(params, x)
        if 2**c.k <= c.n_buckets:
            weights = (2 ** np.arange(c.k)).astype(np.int32)
            return jnp.tensordot(bits, jnp.asarray(weights), axes=([-1], [0]))
        return _fold_subhashes(bits, c.n_buckets)

    def collision_probability(self, cos_sim: jnp.ndarray) -> jnp.ndarray:
        theta = jnp.arccos(jnp.clip(cos_sim, -1.0, 1.0))
        return (1.0 - theta / jnp.pi) ** self.config.k


class AchlioptasL2LSH(L2LSH):
    """L2 LSH with the sparse ±1 projection of Achlioptas [37].

    Entries are drawn from ``sqrt(3)·{+1, 0, −1}`` w.p. ``{1/6, 2/3, 1/6}``;
    this matches the paper's inference-time hash (add/sub only on edge
    hardware).  The projection is still a valid JL/p-stable surrogate; the
    collision probability is approximately the Gaussian one for d ≳ 30.
    """

    def params(self, key: jax.Array) -> dict:
        c = self.config
        kw, kb = jax.random.split(key)
        u = jax.random.uniform(kw, (c.n_rows, c.k, c.dim))
        w = jnp.sqrt(3.0) * (
            (u < 1.0 / 6.0).astype(jnp.float32) - (u > 5.0 / 6.0).astype(jnp.float32)
        )
        b = jax.random.uniform(kb, (c.n_rows, c.k), minval=0.0, maxval=c.bandwidth)
        return {"w": w, "b": b}


def make_lsh(kind: str, config: LSHConfig):
    if kind == "l2":
        return L2LSH(config)
    if kind == "srp":
        return SRPLSH(config)
    if kind == "achlioptas":
        return AchlioptasL2LSH(config)
    raise ValueError(f"unknown LSH kind: {kind}")
