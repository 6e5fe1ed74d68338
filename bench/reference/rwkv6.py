"""RWKV-6 (Finch) as the program under test defines it: weights made from
the seed in its serving layout, and a plain f32 reference forward.

Each layer (pre-norm residual, ``rms(x) = x / sqrt(mean(x^2) + eps) *
(1 + scale)``):

* time-mix on ``h = rms(x, norm1)`` with the token shift ``h'`` (the
  previous position's ``h``, zeros before the first):
  ``x_i = h * mu_i + h' * (1 - mu_i)`` for i in (r, k, v, w, g);
  ``r, k, v = x_r W_r, x_k W_k, x_v W_v``; ``g = silu(x_g W_g)``;
  decay ``w_t = exp(-exp(w0 + tanh(x_w A) B))`` per channel;
  per head of 64 channels, with state ``S`` (64 x 64) from zeros:
  ``y_t = r_t (S + diag(u) k_t^T v_t)``, then ``S = diag(w_t) S + k_t^T v_t``;
  ``y`` normalised per head (mean 0, variance 1, eps 1e-5) times
  ``1 + ln_x``; ``x += (y * g) W_o``;
* channel-mix on ``h2 = rms(x, norm2)`` with its own token shift:
  ``x += sigmoid(x_r C_r) * (relu(x_k C_k)^2 C_v)``.

The input is ``embed[token] * sqrt(d_model)``, the output ``rms(x,
final_norm)``, and dense logits are ``hidden @ head^T``.  The recurrence
runs serially over positions here, not in the program's chunked form.

Departures of this block from the published Finch (arXiv:2404.05892), all
the program's own: the token-shift mix is a static ``mu`` (Finch makes it
data-dependent), norms are RMS with ``1 + scale`` (Finch: LayerNorm), and
there is no gate on the channel-mix key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import HI, mm, normal, rms_norm

HEAD = 64
LORA = 64


def make_params(key, cfg):
    """Random weights in the serving layout (bf16 matrices, f32 vectors).

    Widths from ``cfg``; matrices are N(0, 1/fan_in), the embedding and the
    head N(0, 0.02^2).  Mixing shares are uniform in [0, 1], the decay
    offsets spread over [-6, -1] per channel as Finch's initialisation
    does, the bonus ``u`` is N(0, 0.5^2) and the norms start at zero.
    """
    d, ff, v, n = (cfg["d_model"], cfg["d_ff"], cfg["vocab_size"],
                   cfg["n_layers"])
    h = d // HEAD
    ks = iter(jax.random.split(key, 24))
    f32 = jnp.float32

    def mat(shape):
        return normal(next(ks), (n, *shape), 1.0 / np.sqrt(shape[0]))

    ramp = (jnp.arange(d, dtype=f32) / (d - 1)) ** 0.7
    mixer = {
        "mu": jax.random.uniform(next(ks), (n, 5, d), f32),
        "w_r": mat((d, d)), "w_k": mat((d, d)), "w_v": mat((d, d)),
        "w_g": mat((d, d)), "w_o": mat((d, d)),
        "w0": jnp.broadcast_to(-6.0 + 5.0 * ramp, (n, d)).astype(f32),
        "w_lora_a": mat((d, LORA)),
        "w_lora_b": normal(next(ks), (n, LORA, d), 0.01),
        "u_bonus": normal(next(ks), (n, h, HEAD), 0.5, f32),
        "ln_x": jnp.zeros((n, d), f32),
        "mu_cm": jax.random.uniform(next(ks), (n, 2, d), f32),
        "cm_k": mat((d, ff)), "cm_v": mat((ff, d)), "cm_r": mat((d, d)),
    }
    return {
        "embed": normal(next(ks), (v, d), 0.02),
        "final_norm": jnp.zeros((d,), f32),
        "head": normal(next(ks), (v, d), 0.02),
        "periods": {"pos0": {"norm1": jnp.zeros((n, d), f32),
                             "norm2": jnp.zeros((n, d), f32),
                             "mixer": mixer}},
    }


def _shift(h):
    return jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """Serial WKV over positions. r, k, v, w: (N, T, H, 64); u: (H, 64)."""
    n, _, h, e = r.shape

    def step(s, inp):
        rt, kt, vt, wt = inp                       # (N, H, 64)
        y = (jnp.einsum("nhk,nhkv->nhv", rt, s, precision=HI)
             + jnp.sum(rt * u * kt, -1, keepdims=True) * vt)
        s = wt[..., None] * s + kt[..., :, None] * vt[..., None, :]
        return s, y

    tr = lambda t: jnp.moveaxis(t, 1, 0)           # noqa: E731
    s0 = jnp.zeros((n, h, e, e), jnp.float32)
    _, y = jax.lax.scan(step, s0, (tr(r), tr(k), tr(v), tr(w)))
    return jnp.moveaxis(y, 0, 1)


def _layer(p, x, eps, quant):
    n, t, d = x.shape
    h = rms_norm(x, p["norm1"], eps)
    m = p["mixer"]
    sh = _shift(h)
    mix = [h * m["mu"][i] + sh * (1.0 - m["mu"][i]) for i in range(5)]
    heads = lambda a: a.reshape(n, t, d // HEAD, HEAD)  # noqa: E731
    r = heads(mm(mix[0], m["w_r"], quant))
    k = heads(mm(mix[1], m["w_k"], quant))
    v = heads(mm(mix[2], m["w_v"], quant))
    lora = mm(jnp.tanh(mm(mix[3], m["w_lora_a"], quant)), m["w_lora_b"],
              quant)
    w = heads(jnp.exp(-jnp.exp(m["w0"] + lora)))
    g = jax.nn.silu(mm(mix[4], m["w_g"], quant))
    y = _wkv(r, k, v, w, m["u_bonus"])
    y = (y - y.mean(-1, keepdims=True)) * jax.lax.rsqrt(
        y.var(-1, keepdims=True) + 1e-5)
    y = y.reshape(n, t, d) * (1.0 + m["ln_x"])
    x = x + mm(y * g, m["w_o"], quant)
    h2 = rms_norm(x, p["norm2"], eps)
    sh2 = _shift(h2)
    xk = h2 * m["mu_cm"][0] + sh2 * (1.0 - m["mu_cm"][0])
    xr = h2 * m["mu_cm"][1] + sh2 * (1.0 - m["mu_cm"][1])
    kk = jnp.square(jax.nn.relu(mm(xk, m["cm_k"], quant)))
    return x + jax.nn.sigmoid(mm(xr, m["cm_r"], quant)) * mm(kk, m["cm_v"],
                                                             quant)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))


def hidden(params, tokens, cfg, quant=None):
    """(N, T) tokens -> (N, T, d) f32 final hiddens, one layer at a time;
    ``quant`` ("int8", "fp8") rounds every weight product's operands."""
    eps = cfg["norm_eps"]
    x = (jnp.take(params["embed"], jnp.asarray(tokens), axis=0)
         .astype(jnp.float32) * np.sqrt(cfg["d_model"]))
    layers = params["periods"]["pos0"]
    for i in range(cfg["n_layers"]):
        x = _layer_jit(jax.tree.map(lambda a: a[i], layers), x, eps, quant)
    return jax.jit(rms_norm, static_argnums=2)(x, params["final_norm"], eps)


_dense = jax.jit(lambda t, h, quant: mm(h, t.T, quant), static_argnums=2)


def dense_logits(params, hid, cfg, quant=None):
    """Dense logits of (T, d) hiddens through the untied head."""
    return _dense(params["head"], hid, quant)


def unembed(params):
    """The (V, d) dense unembed table the sketch head is made from."""
    return params["head"]


# -- work from shapes (``bench/costs.py`` adds the head) ----------------------

def layer_matmul_params(cfg) -> int:
    """Weights of one layer that every token multiplies: r, k, v, g, o,
    the decay LoRA, and the channel-mix key, value and receptance."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    return 6 * d * d + 2 * LORA * d + 2 * d * ff


def param_count(cfg) -> int:
    """All parameters of ``make_params``."""
    d, v, n = cfg["d_model"], cfg["vocab_size"], cfg["n_layers"]
    vectors = 5 * d + d + (d // HEAD) * HEAD + d + 2 * d + 2 * d
    # mu, w0, u, ln_x, mu_cm, norm1 + norm2
    emb = v * d * (1 if cfg.get("tie_embeddings") else 2)
    return n * (layer_matmul_params(cfg) + vectors) + emb + d


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    """A recurrent model keeps no per-position cache."""
    return 0


def backbone_flops(cfg, rows: int, live: int) -> float:
    """Operations of decoding ``rows`` tokens: 2 per weight per row and
    the WKV recurrence (about 5 per state entry per layer); ``live`` plays
    no part."""
    n, d = cfg["n_layers"], cfg["d_model"]
    return rows * n * (2.0 * layer_matmul_params(cfg) + 5.0 * HEAD * d)
