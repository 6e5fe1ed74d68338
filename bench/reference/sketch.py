"""The Representer-Sketch logit head: made from the seed, and its plain
reference.

The head is a RACE sketch of the dense unembed, built in closed form from
``n_anchors`` Gaussian anchor hiddens: each anchor is projected by the
asymmetric transform, hashed by L rows of p-stable L2 LSH (K sub-hashes
folded into R buckets), and every (row, bucket) cell holds the mean dense
logits of the anchors that hash there.  The count array is stored as int8
with one symmetric scale per (row, bucket).  Logits of a hidden ``h`` are
the mean over rows of the cells its hash picks:

    q = h @ proj;  idx_l = fold(floor((q . w_l + b_l) / bandwidth)) mod R
    logits[v] = 1/L * sum_l scale[l, idx_l] * counts[l, idx_l, v]

``fold`` is the row-salted Carter-Wegman mix that defines the head's bucket
map; it is written out here as the head's specification.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import HI

_MIX_A = np.uint32(1103515245)


def fold(codes, n_buckets):
    """(..., L, K) int32 sub-hash codes -> (..., L) bucket indices."""
    codes = codes.astype(jnp.uint32)
    n_rows, k = codes.shape[-2], codes.shape[-1]
    salt = jnp.arange(n_rows, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    acc = jnp.broadcast_to(salt, codes.shape[:-1]).astype(jnp.uint32)
    for i in range(k):
        acc = acc * _MIX_A + codes[..., i] + jnp.uint32(i * 97 + 13)
        acc = acc ^ (acc >> 16)
        acc = acc * jnp.uint32(0x45D9F3B)
        acc = acc ^ (acc >> 16)
    return (acc % jnp.uint32(n_buckets)).astype(jnp.int32)


def buckets(head, hidden, spec):
    """(T, d) hiddens -> (T, L) bucket indices."""
    q = jnp.einsum("td,dp->tp", hidden.astype(jnp.float32), head["proj"],
                   precision=HI)
    proj = jnp.einsum("tp,lkp->tlk", q, head["w"], precision=HI)
    codes = jnp.floor((proj + head["b"]) / spec["bandwidth"])
    return fold(codes.astype(jnp.int32), spec["n_buckets"])


def make_head(key, table, spec):
    """A head from ``key`` and the (V, d) dense unembed ``table``.

    Returns the arrays in the serving layout: ``proj`` (d, d'), ``w``
    (L, K, d'), ``b`` (L, K), ``array`` (L, R, V) int8, ``scale`` (L, R).
    """
    v, d = table.shape
    n_rows, n_buckets = spec["n_rows"], spec["n_buckets"]
    kp, kw, kb, ka = jax.random.split(key, 4)
    head = {
        "proj": jax.random.normal(kp, (d, spec["proj_dim"]), jnp.float32)
        / np.sqrt(d),
        "w": jax.random.normal(kw, (n_rows, spec["k"], spec["proj_dim"]),
                               jnp.float32),
        "b": jax.random.uniform(kb, (n_rows, spec["k"]), jnp.float32, 0.0,
                                spec["bandwidth"]),
    }
    anchors = jax.random.normal(ka, (spec["n_anchors"], d), jnp.float32)
    alphas = jnp.einsum("md,vd->mv", anchors, table.astype(jnp.float32),
                        precision=HI)
    onehot = jax.nn.one_hot(buckets(head, anchors, spec), n_buckets,
                            dtype=jnp.float32)                 # (M, L, R)
    total = jnp.einsum("mlr,mv->lrv", onehot, alphas, precision=HI)
    count = jnp.maximum(onehot.sum(0), 1.0)[..., None]
    array = total / count
    scale = jnp.max(jnp.abs(array), axis=-1) / 127.0
    scale = jnp.where(scale == 0, 1.0 / 127.0, scale)
    head["array"] = jnp.clip(jnp.round(array / scale[..., None]), -127,
                             127).astype(jnp.int8)
    head["scale"] = scale
    return head


def logits(head, hidden, spec, block: int = 256):
    """Plain sketch logits of (T, d) hiddens, ``block`` rows at a time."""
    n_rows, n_buckets, v = head["array"].shape
    table = (head["array"].astype(jnp.float32)
             * head["scale"][..., None]).reshape(n_rows * n_buckets, v)
    outs = []
    for i in range(0, hidden.shape[0], block):
        idx = buckets(head, hidden[i:i + block], spec)
        onehot = jax.nn.one_hot(idx, n_buckets, dtype=jnp.float32)
        outs.append(jnp.einsum("tc,cv->tv",
                               onehot.reshape(idx.shape[0], -1), table,
                               precision=HI) / n_rows)
    return jnp.concatenate(outs)
