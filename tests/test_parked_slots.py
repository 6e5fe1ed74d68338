"""Parked slots through a masked decode step (DESIGN.md §7).

A continuous-batching step decodes every slot of the pool; the ``active``
mask says which rows hold a live request.  Each layer holds back a parked
row's cache write where it writes its cache, for every cache kind the
engine serves: a full KV cache (granite), an SWA ring that wraps (gemma2),
an MLA latent cache behind a dense prologue layer (deepseek), mamba state
beside attention (jamba) and the rwkv state.  So, for a masked step or a
masked megastep with the mask mixed:

* a parked row's cache comes back bitwise equal to what went in;
* an active row's cache and logits (tokens, for a megastep) are bitwise
  those of the same step unmasked.

The megastep's optimized HLO is also checked for a select over the whole
stacked pool, the shape a pool-wide mask takes.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.heads import DenseHead
from repro.api.sampler import Sampler
from repro.configs import get_config
from repro.launch.decode_loop import jitted_megastep
from repro.launch.steps import jitted_serve_fns
from repro.models.blocks import ATTN_KINDS
from repro.models.model import init_decode_cache, init_model

# Odd sizes, so the pool's shapes match no other array of a smoke step.
SLOTS, MAX_SEQ, PROMPT = 3, 17, 10    # 10 tokens wrap gemma2's 8-wide ring
ACTIVE = np.array([True, False, True])
ARCHS = ["granite-8b", "gemma2-27b", "deepseek-v3-671b", "jamba-v0.1-52b",
         "rwkv6-1.6b"]


@pytest.fixture(scope="module", params=ARCHS)
def mid_stream(request):
    """(cfg, params, pool, last tokens, positions) after a prefill of
    ``PROMPT`` tokens into every slot."""
    cfg = get_config(request.param, smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    prefill = jitted_serve_fns(cfg).prefill
    prompts = jax.random.randint(jax.random.PRNGKey(1), (SLOTS, PROMPT), 0,
                                 cfg.vocab_size)
    logits, pool = prefill(params, prompts,
                           cache=init_decode_cache(cfg, SLOTS, MAX_SEQ))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((SLOTS,), PROMPT, jnp.int32)
    return cfg, params, pool, tok, pos


def _rows(cache: dict, rows) -> list:
    """The leaves of ``rows`` of every layer cache, as numpy arrays
    (prologue caches batch on axis 0, scanned periods on axis 1)."""
    out = [np.asarray(x[rows]) for c in cache.get("prologue", [])
           for x in jax.tree.leaves(c)]
    return out + [np.asarray(x[:, rows])
                  for x in jax.tree.leaves(cache["periods"])]


def _assert_rows_equal(got: dict, want: dict, rows) -> None:
    for g, w in zip(_rows(got, rows), _rows(want, rows), strict=True):
        np.testing.assert_array_equal(g, w)


def _copy(tree):
    # decode and the megastep donate their cache: each call gets its own.
    return jax.tree.map(jnp.copy, tree)


def test_masked_step_holds_parked_rows(mid_stream):
    cfg, params, pool, tok, pos = mid_stream
    decode = jitted_serve_fns(cfg).decode
    masked_logits, masked = decode(params, _copy(pool), tok[:, None], pos,
                                   active=jnp.asarray(ACTIVE))
    logits, unmasked = decode(params, _copy(pool), tok[:, None], pos)
    _assert_rows_equal(masked, pool, ~ACTIVE)
    _assert_rows_equal(masked, unmasked, ACTIVE)
    np.testing.assert_array_equal(np.asarray(masked_logits)[ACTIVE],
                                  np.asarray(logits)[ACTIVE])
    # The step wrote something: an unmasked parked row moves.
    assert any(not np.array_equal(u, p) for u, p in
               zip(_rows(unmasked, ~ACTIVE), _rows(pool, ~ACTIVE)))


def _megastep(cfg, masked: bool):
    return jitted_megastep(cfg, DenseHead(), Sampler(), 2, masked=masked)


def test_masked_megastep_holds_parked_rows(mid_stream):
    cfg, params, pool, tok, pos = mid_stream
    key = Sampler().init_key()
    block, masked, *_ = _megastep(cfg, True)(
        params, _copy(pool), tok, pos, key, active=jnp.asarray(ACTIVE))
    want, unmasked, *_ = _megastep(cfg, False)(
        params, _copy(pool), tok, pos, key)
    _assert_rows_equal(masked, pool, ~ACTIVE)
    _assert_rows_equal(masked, unmasked, ACTIVE)
    np.testing.assert_array_equal(np.asarray(block)[:, ACTIVE],
                                  np.asarray(want)[:, ACTIVE])


_SELECT = re.compile(r"= \w+\[([\d,]*)\]\S* select\(")


@pytest.mark.parametrize("arch", ["granite-8b", "rwkv6-1.6b"])
def test_megastep_selects_nothing_pool_sized(arch):
    """No select in the compiled masked megastep is the size of the stacked
    pool, nor, for a positional cache, of one layer's whole cache."""
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    pool = init_decode_cache(cfg, SLOTS, MAX_SEQ)
    slots = np.zeros(SLOTS, np.int32)
    text = _megastep(cfg, True).lower(
        params, pool, slots, slots, Sampler().init_key(),
        active=ACTIVE).compile().as_text()
    stacked = {x.shape for x in jax.tree.leaves(pool["periods"])}
    one_layer = {x.shape[1:] for j, kind in enumerate(cfg.pattern)
                 if kind in ATTN_KINDS
                 for x in jax.tree.leaves(pool["periods"][f"pos{j}"])}
    selects = {tuple(int(d) for d in dims.split(",") if d)
               for dims in _SELECT.findall(text)}
    assert selects, "no select found: the pattern no longer reads the HLO"
    assert not selects & (stacked | one_layer), selects & (stacked | one_layer)
