"""Granite-8B (llama architecture, GQA) as the program under test defines
it: weights made from the seed in its serving layout, and a plain f32
reference forward.

Each layer (pre-norm residual, ``rms(x) = x / sqrt(mean(x^2) + eps) *
(1 + scale)``):

* attention on ``h = rms(x, norm1)``: ``q = h W_q`` (n_heads x head_dim),
  ``k = h W_k``, ``v = h W_v`` (n_kv_heads x head_dim); rotary embedding on
  q and k (pairs (i, i + head_dim/2) rotated by ``pos * theta^(-i /
  (head_dim/2))``); causal softmax of ``q k^T / sqrt(head_dim)`` with each
  group of n_heads / n_kv_heads query heads sharing one key-value head;
  ``x += o W_o``;
* SwiGLU on ``h2 = rms(x, norm2)``: ``x += (silu(h2 W_gate) * h2 W_up)
  W_down``.

The input is ``embed[token] * sqrt(d_model)``, the output ``rms(x,
final_norm)``, and logits are ``hidden @ embed^T`` (tied embeddings).
Granite-3's scalar multipliers (embedding, attention, residual, logits) are
not part of the program's model and so not of this reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import HI, mm, normal, rms_norm

Q_BLOCK = 512


def make_params(key, cfg):
    """Random weights in the serving layout: bf16 matrices N(0, 1/fan_in),
    the tied embedding N(0, ``embed_std``^2) (0.02 unless the configuration
    sets it), norms at zero.

    With tied embeddings a large ``embed_std`` makes a random model repeat
    its input: the token's own embedding dominates the residual stream, so
    its logit stands tens of deviations above the rest, and every gap
    reads 0 whatever the precision."""
    d, ff, v, n = (cfg["d_model"], cfg["d_ff"], cfg["vocab_size"],
                   cfg["n_layers"])
    a = cfg["attention"]
    hq, hkv, e = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    ks = iter(jax.random.split(key, 12))

    def mat(shape):
        return normal(next(ks), (n, *shape), 1.0 / np.sqrt(shape[0]))

    f32 = jnp.float32
    return {
        "embed": normal(next(ks), (v, d), cfg.get("embed_std", 0.02)),
        "final_norm": jnp.zeros((d,), f32),
        "periods": {"pos0": {
            "norm1": jnp.zeros((n, d), f32),
            "norm2": jnp.zeros((n, d), f32),
            "mixer": {"wq": mat((d, hq * e)), "wk": mat((d, hkv * e)),
                      "wv": mat((d, hkv * e)), "wo": mat((hq * e, d))},
            "ffn": {"w_gate": mat((d, ff)), "w_up": mat((d, ff)),
                    "w_down": mat((ff, d))},
        }},
    }


def _rope(x, theta):
    """x: (N, T, H, E), positions 0..T-1."""
    t, e = x.shape[1], x.shape[-1]
    half = e // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, eps, att, quant):
    hq, hkv, e, theta = att
    n, t, d = x.shape
    h = rms_norm(x, p["norm1"], eps)
    m = p["mixer"]
    q = _rope(mm(h, m["wq"], quant).reshape(n, t, hq, e), theta)
    k = _rope(mm(h, m["wk"], quant).reshape(n, t, hkv, e), theta)
    v = mm(h, m["wv"], quant).reshape(n, t, hkv, e)
    q = q.reshape(n, t, hkv, hq // hkv, e) / np.sqrt(e)
    outs = []
    for s in range(0, t, Q_BLOCK):
        qb = q[:, s:s + Q_BLOCK]
        sc = jnp.einsum("nqkge,nske->nkgqs", qb, k, precision=HI)
        qpos = jnp.arange(s, s + qb.shape[1])[:, None]
        sc = jnp.where(qpos >= jnp.arange(t)[None, :], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("nkgqs,nske->nqkge", pr, v, precision=HI))
    o = jnp.concatenate(outs, axis=1).reshape(n, t, hq * e)
    x = x + mm(o, m["wo"], quant)
    h2 = rms_norm(x, p["norm2"], eps)
    f = p["ffn"]
    g = jax.nn.silu(mm(h2, f["w_gate"], quant)) * mm(h2, f["w_up"], quant)
    return x + mm(g, f["w_down"], quant)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4))


def hidden(params, tokens, cfg, quant=None):
    """(N, T) tokens -> (N, T, d) f32 final hiddens, one layer at a time;
    ``quant`` ("int8", "fp8") rounds every weight product's operands."""
    eps = cfg["norm_eps"]
    a = cfg["attention"]
    att = (a["n_heads"], a["n_kv_heads"], a["head_dim"],
           float(a["rope_theta"]))
    x = (jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
         .astype(jnp.float32) * np.sqrt(cfg["d_model"]))
    layers = params["periods"]["pos0"]
    for i in range(cfg["n_layers"]):
        x = _layer_jit(jax.tree.map(lambda a: a[i], layers), x, eps, att,
                       quant)
    return jax.jit(rms_norm, static_argnums=2)(x, params["final_norm"], eps)


_dense = jax.jit(lambda t, h, quant: mm(h, t.T, quant), static_argnums=2)


def dense_logits(params, hid, cfg, quant=None):
    """Dense logits of (T, d) hiddens through the tied embedding."""
    return _dense(params["embed"], hid, quant)


def unembed(params):
    return params["embed"]


# -- work from shapes (``bench/costs.py`` adds the head) ----------------------

def layer_matmul_params(cfg) -> int:
    """Weights of one layer that every token multiplies: q, k, v, o and
    the SwiGLU gate, up and down."""
    d, ff, a = cfg["d_model"], cfg["d_ff"], cfg["attention"]
    qo = 2 * d * a["n_heads"] * a["head_dim"]
    kv = 2 * d * a["n_kv_heads"] * a["head_dim"]
    return qo + kv + 3 * d * ff


def param_count(cfg) -> int:
    """All parameters of ``make_params``."""
    d, v, n = cfg["d_model"], cfg["vocab_size"], cfg["n_layers"]
    emb = v * d * (1 if cfg.get("tie_embeddings") else 2)
    return n * (layer_matmul_params(cfg) + 2 * d) + emb + d


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """Key and value bytes one position holds over all layers."""
    a = cfg["attention"]
    return 2 * cfg["n_layers"] * a["n_kv_heads"] * a["head_dim"] * itemsize


def backbone_flops(cfg, rows: int, live: int) -> float:
    """Operations of decoding ``rows`` tokens whose attention covers
    ``live`` positions in all: 2 per weight per row, and 4 x heads x
    head_dim per live position per layer (scores and weighted values)."""
    n, a = cfg["n_layers"], cfg["attention"]
    return (rows * 2.0 * n * layer_matmul_params(cfg)
            + live * n * 4.0 * a["n_heads"] * a["head_dim"])
