"""Mamba-1 selective SSM block (Jamba's recurrent component).

The selective scan ``h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t`` is evaluated
chunk-parallel: time is split into chunks of ``_SCAN_CHUNK``; a serial
``lax.scan`` carries the state across chunks while *within* a chunk the
recurrence runs as a parallel ``associative_scan`` (Blelloch) over the
(decay, increment) pairs.  This is the TPU-idiomatic mapping of the CUDA
selective-scan kernel (DESIGN.md §3): O(log chunk) depth, and the
``(B, chunk, d_inner, d_state)`` working set stays VMEM/HBM-friendly instead
of materializing the full ``(B, S, d_inner, d_state)`` tensor (which would be
~17 GB for Jamba at S=4096).

Decode keeps the constant-size state ``(B, d_inner, d_state)`` → long_500k
eligible.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import MambaConfig
from repro.models.layers import hold_parked, init_dense

_SCAN_CHUNK = 256


class MambaCache(NamedTuple):
    conv: jnp.ndarray   # (B, d_conv-1, d_inner) — rolling conv inputs
    ssm: jnp.ndarray    # (B, d_inner, d_state)  — recurrent state


def _dt_rank(d_model: int, cfg: MambaConfig) -> int:
    return cfg.dt_rank or -(-d_model // 16)


def init_mamba(key, d_model: int, cfg: MambaConfig) -> dict:
    d_in = cfg.expand * d_model
    r = _dt_rank(d_model, cfg)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": init_dense(ks[0], (d_model, 2 * d_in)),
        "conv_w": init_dense(ks[1], (cfg.d_conv, d_in)),
        "conv_b": jnp.zeros((d_in,), jnp.float32),
        "x_proj": init_dense(ks[2], (d_in, r + 2 * cfg.d_state)),
        "dt_proj": init_dense(ks[3], (r, d_in)),
        "dt_bias": jnp.zeros((d_in,), jnp.float32),
        # A is stored as -exp(a_log) (negative-real); d_skip is a skip gain.
        "a_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, cfg.d_state + 1, dtype=jnp.float32), (d_in, cfg.d_state)
        )).copy(),
        "d_skip": jnp.ones((d_in,), jnp.float32),
        "out_proj": init_dense(ks[5], (d_in, d_model)),
    }


def init_mamba_cache(batch: int, d_model: int, cfg: MambaConfig,
                     dtype=jnp.float32) -> MambaCache:
    d_in = cfg.expand * d_model
    return MambaCache(
        jnp.zeros((batch, cfg.d_conv - 1, d_in), dtype),
        jnp.zeros((batch, d_in, cfg.d_state), dtype),
    )


def slot_insert(cache: MambaCache, src: MambaCache,
                slots: jnp.ndarray) -> MambaCache:
    """Copy batch rows (rolling conv inputs + SSM state) into pool ``slots``.

    The SSM state is position-free — a row prefilled in a fresh batch-1 cache
    is exactly the state the request would have in any slot.
    """
    return MambaCache(cache.conv.at[slots].set(src.conv.astype(cache.conv.dtype)),
                      cache.ssm.at[slots].set(src.ssm.astype(cache.ssm.dtype)))


def slot_reset(cache: MambaCache, slots: jnp.ndarray) -> MambaCache:
    """Zero rows ``slots`` — bitwise identical to fresh ``init_mamba_cache``."""
    return MambaCache(cache.conv.at[slots].set(0), cache.ssm.at[slots].set(0))


# Paged serving (DESIGN.md §13): mamba state has no sequence axis — one
# constant-size row per slot — so there is nothing to page.  The recurrent
# families ride the *state* half of the split paged pool with the ordinary
# slot ops; they join prefix caching via state-row extraction instead.
paged_slot_insert = slot_insert
paged_slot_reset = slot_reset


def _selective_params(params: dict, x_conv: jnp.ndarray, d_state: int, r: int):
    """Project conv output → (Δ, B_t, C_t) selective parameters (f32)."""
    proj = jnp.einsum("...i,ie->...e", x_conv, params["x_proj"]).astype(jnp.float32)
    dt, b_sel, c_sel = jnp.split(proj, [r, r + d_state], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("...r,ri->...i", dt, params["dt_proj"].astype(jnp.float32))
        + params["dt_bias"])
    return dt, b_sel, c_sel


def mamba_block(
    params: dict,
    x: jnp.ndarray,           # (B, S, d_model)
    cfg: MambaConfig,
    *,
    cache: Optional[MambaCache] = None,
    active: Optional[jnp.ndarray] = None,    # (B,) decode rows to advance
) -> Tuple[jnp.ndarray, Optional[MambaCache]]:
    """Selective-SSM block.  Returns (output, updated_cache); rows clear in
    ``active`` keep their conv window and SSM state unchanged."""
    b, s, d_model = x.shape
    d_in = cfg.expand * d_model
    r = _dt_rank(d_model, cfg)

    xz = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    xs, z = jnp.split(xz, 2, axis=-1)  # (B, S, d_in) each

    # Depthwise causal conv over time.
    if cache is not None:
        conv_in = jnp.concatenate([cache.conv.astype(xs.dtype), xs], axis=1)
        new_conv = conv_in[:, -(cfg.d_conv - 1):, :].astype(cache.conv.dtype)
    else:
        conv_in = jnp.pad(xs, ((0, 0), (cfg.d_conv - 1, 0), (0, 0)))
        new_conv = None
    x_conv = jax.nn.silu(
        sum(conv_in[:, i : i + s, :] * params["conv_w"][i]
            for i in range(cfg.d_conv))
        + params["conv_b"]).astype(x.dtype)

    a = -jnp.exp(params["a_log"])  # (d_in, N), negative real
    init_h = (cache.ssm.astype(jnp.float32) if cache is not None
              else jnp.zeros((b, d_in, cfg.d_state), jnp.float32))

    if cache is not None and s == 1:
        dt, b_sel, c_sel = _selective_params(params, x_conv, cfg.d_state, r)
        decay = jnp.exp(dt[:, 0, :, None] * a)
        inc = (dt[:, 0, :, None] * b_sel[:, 0, None, :]
               * x_conv.astype(jnp.float32)[:, 0, :, None])
        h = init_h * decay + inc
        new_ssm = h
        y = jnp.einsum("bin,bn->bi", h, c_sel[:, 0])[:, None, :]
    else:
        chunk = min(s, _SCAN_CHUNK)
        pad = (-s) % chunk
        xc = jnp.pad(x_conv, ((0, 0), (0, pad), (0, 0)))
        n_chunks = xc.shape[1] // chunk
        # (n_chunks, B, chunk, d_in) — scan over the leading chunk axis.
        xc = xc.reshape(b, n_chunks, chunk, d_in).transpose(1, 0, 2, 3)
        # Padded positions must be state-identity: x=0 kills the increment
        # but dt=softplus(conv_b-path)>0 would still *decay* the carried
        # state once per pad step — corrupting the cache a bulk prefill
        # saves.  (Within a chunk, pad < chunk, so position 0 is real.)
        valid = (jnp.arange(n_chunks * chunk) < s).reshape(n_chunks, chunk)

        def chunk_step(h, scanned):
            x_chunk, v_chunk = scanned
            dt, b_sel, c_sel = _selective_params(params, x_chunk, cfg.d_state, r)
            decay = jnp.exp(dt[..., None] * a)                  # (B,c,d_in,N)
            inc = (dt[..., None] * b_sel[:, :, None, :]
                   * x_chunk.astype(jnp.float32)[..., None])
            m = v_chunk[None, :, None, None]
            decay = jnp.where(m, decay, 1.0)
            inc = jnp.where(m, inc, 0.0)
            inc = inc.at[:, 0].add(h * decay[:, 0])

            def combine(left, right):
                dl, il = left
                dr, ir = right
                return dl * dr, il * dr + ir

            _, states = jax.lax.associative_scan(combine, (decay, inc), axis=1)
            y_chunk = jnp.einsum("bsin,bsn->bsi", states, c_sel)
            return states[:, -1], y_chunk.astype(x.dtype)

        new_ssm, ys = jax.lax.scan(chunk_step, init_h, (xc, valid))
        y = ys.transpose(1, 0, 2, 3).reshape(b, n_chunks * chunk, d_in)[:, :s]
        y = y.astype(jnp.float32)

    y = y + x_conv.astype(jnp.float32) * params["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, params["out_proj"])

    new_cache = None
    if cache is not None:
        new_cache = MambaCache(
            hold_parked(active, new_conv, cache.conv),
            hold_parked(active, new_ssm.astype(cache.ssm.dtype), cache.ssm))
    return out, new_cache
