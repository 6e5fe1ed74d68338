"""Per-kernel Pallas (interpret mode) vs pure-jnp oracle, shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_decode.ops import fused_decode_logits
from repro.kernels.fused_decode.ref import fused_decode_ref
from repro.kernels.lsh_hash.ops import lsh_hash
from repro.kernels.lsh_hash.ref import lsh_hash_ref
from repro.kernels.race_query.ops import race_query
from repro.kernels.race_query.ref import race_query_ref
from repro.kernels.race_update.ops import race_update
from repro.kernels.race_update.ref import race_update_ref
from repro.kernels.sketch_head.ops import sketch_head_logits
from repro.kernels.sketch_head.ref import sketch_head_ref


@pytest.mark.parametrize("b", [1, 7, 128, 130])
@pytest.mark.parametrize("d,l,k,r", [(8, 16, 1, 8), (64, 40, 3, 32),
                                     (17, 5, 2, 100)])
def test_lsh_hash_matches_ref(b, d, l, k, r):
    key = jax.random.PRNGKey(b * 1000 + d)
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d))
    w = jax.random.normal(kw, (l, k, d))
    bias = jax.random.uniform(kb, (l, k))
    got = lsh_hash(x, w, bias, bandwidth=1.5, n_buckets=r, block_b=32)
    want = lsh_hash_ref(x, w, bias, 1.5, r)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32
    assert bool(jnp.all((got >= 0) & (got < r)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lsh_hash_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 8)).astype(dtype)
    w = jax.random.normal(key, (4, 2, 8))
    b = jax.random.uniform(key, (4, 2))
    got = lsh_hash(x.astype(jnp.float32), w, b, bandwidth=1.0, n_buckets=8)
    assert got.shape == (16, 4)


@pytest.mark.parametrize("b,c,l,r,g", [(4, 1, 8, 4, 2), (33, 5, 40, 16, 8),
                                       (128, 2, 100, 20, 10)])
def test_race_query_matches_ref(b, c, l, r, g):
    key = jax.random.PRNGKey(b + c)
    sketch = jax.random.normal(key, (c, l, r))
    idx = jax.random.randint(key, (b, l), 0, r)
    got = race_query(sketch, idx, n_groups=g, block_b=16)
    want = race_query_ref(sketch, idx, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,c,l,r", [(10, 1, 8, 4), (300, 5, 40, 16),
                                     (257, 3, 20, 32)])
def test_race_update_matches_ref(m, c, l, r):
    key = jax.random.PRNGKey(m)
    sketch = jax.random.normal(key, (c, l, r))
    idx = jax.random.randint(key, (m, l), 0, r)
    alphas = jax.random.normal(key, (m, c))
    got = race_update(sketch, idx, alphas, block_m=64)
    want = race_update_ref(sketch, idx, alphas)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,l,r,g", [(5, 3, 24, 12, 6),    # all non-pow2
                                       (33, 2, 18, 10, 1),   # g=1: plain mean
                                       (130, 4, 50, 6, 5)])  # b > block_b
def test_race_query_pallas_vs_ref_explicit(b, c, l, r, g, dtype):
    """Explicit backend pin: the pallas kernel against the jnp oracle, both
    resolved by name — immune to REPRO_KERNEL_BACKEND / default-backend
    flips — over non-power-of-two shapes and reduced-precision sketches."""
    key = jax.random.PRNGKey(b * 7 + c)
    sketch = jax.random.normal(key, (c, l, r)).astype(dtype)
    idx = jax.random.randint(key, (b, l), 0, r)
    got = race_query(sketch, idx, n_groups=g, block_b=16, backend="pallas")
    want = race_query(sketch, idx, n_groups=g, backend="ref")
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,c,l,r", [(37, 3, 12, 6),     # all non-pow2
                                     (129, 2, 25, 10),   # m % block_m != 0
                                     (64, 5, 18, 12)])
def test_race_update_pallas_vs_ref_explicit(m, c, l, r, dtype):
    """Explicit backend pin for the construction kernel: pallas scatter-add
    vs the jnp oracle over ragged point counts and reduced precision (the
    accumulate path the distillation freeze runs)."""
    key = jax.random.PRNGKey(m * 3 + c)
    sketch = jax.random.normal(key, (c, l, r)).astype(dtype)
    idx = jax.random.randint(key, (m, l), 0, r)
    alphas = jax.random.normal(key, (m, c)).astype(dtype)
    got = race_update(sketch, idx, alphas, block_m=32, backend="pallas")
    want = race_update(sketch, idx, alphas, backend="ref")
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,l,r,v", [(2, 8, 4, 16), (9, 64, 16, 100),
                                     (16, 32, 8, 2048)])
def test_sketch_head_matches_ref(b, l, r, v):
    key = jax.random.PRNGKey(v)
    sketch = jax.random.normal(key, (l, r, v))
    idx = jax.random.randint(key, (b, l), 0, r)
    got = sketch_head_logits(sketch, idx, block_b=4, block_v=64)
    want = sketch_head_ref(sketch, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b", [1, 7, 16])
@pytest.mark.parametrize("d,dp,l,k,r,v", [(16, 8, 8, 1, 4, 32),
                                          (64, 32, 40, 3, 16, 100),
                                          (24, 16, 5, 2, 100, 2048)])
def test_fused_decode_matches_ref(b, d, dp, l, k, r, v):
    key = jax.random.PRNGKey(b * 1000 + v)
    kh, kp, kw, kb, ks = jax.random.split(key, 5)
    hidden = jax.random.normal(kh, (b, d))
    proj = jax.random.normal(kp, (d, dp)) / np.sqrt(d)
    w = jax.random.normal(kw, (l, k, dp))
    bias = jax.random.uniform(kb, (l, k))
    sketch = jax.random.normal(ks, (l, r, v))
    got = fused_decode_logits(hidden, proj, w, bias, sketch, bandwidth=1.5,
                              n_buckets=r, block_b=4, block_v=64)
    want = fused_decode_ref(hidden, proj, w, bias, sketch, 1.5, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fused_decode_matches_two_kernel_composition():
    """The fused kernel must agree with lsh_hash → sketch_head exactly on
    indices (same integer mix), hence near-exactly on logits."""
    key = jax.random.PRNGKey(42)
    kh, kp, kw, kb, ks = jax.random.split(key, 5)
    b, d, dp, l, k, r, v = 9, 32, 16, 24, 2, 8, 128
    hidden = jax.random.normal(kh, (b, d))
    proj = jax.random.normal(kp, (d, dp)) / np.sqrt(d)
    w = jax.random.normal(kw, (l, k, dp))
    bias = jax.random.uniform(kb, (l, k))
    sketch = jax.random.normal(ks, (l, r, v))
    fused = fused_decode_logits(hidden, proj, w, bias, sketch, bandwidth=2.0,
                                n_buckets=r, block_b=4, block_v=64)
    idx = lsh_hash(hidden @ proj, w, bias, bandwidth=2.0, n_buckets=r)
    two = sketch_head_logits(sketch, idx, block_b=4, block_v=64)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two),
                               rtol=1e-5, atol=1e-5)


def test_kernels_jit_and_grad_free():
    """Kernels are inference-path ops; they must compose under jit."""
    key = jax.random.PRNGKey(0)
    sketch = jax.random.normal(key, (3, 16, 8))
    idx = jax.random.randint(key, (5, 16), 0, 8)

    @jax.jit
    def f(s, i):
        return race_query(s, i, n_groups=4)

    out = f(sketch, idx)
    assert out.shape == (5, 3)


@pytest.mark.parametrize("s,win,cap,bq,bk", [
    (96, None, None, 32, 32),
    (200, 64, None, 64, 64),     # non-divisible seq + sliding window
    (128, None, 50.0, 32, 64),   # gemma2-style softcap, rectangular tiles
    (256, 32, 30.0, 128, 128),   # window + softcap combined
])
def test_flash_attention_matches_ref(s, win, cap, bq, bk):
    from repro.kernels.flash_attn.ops import flash_attention
    from repro.kernels.flash_attn.ref import flash_attention_ref

    b, h, dh = 2, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, dh))
    v = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, dh))
    got = flash_attention(q, k, v, window=win, softcap=cap,
                          block_q=bq, block_k=bk)
    want = flash_attention_ref(q, k, v, window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    from repro.kernels.flash_attn.ops import flash_attention
    from repro.kernels.flash_attn.ref import flash_attention_ref

    q = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 32)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 32)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 2, 32)).astype(dtype)
    got = flash_attention(q, k, v, block_q=32, block_k=32)
    want = flash_attention_ref(q, k, v)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,d,l,k,r", [(37, 10, 12, 1, 6),    # all non-pow2
                                       (129, 18, 25, 2, 10),  # b % block != 0
                                       (64, 24, 18, 3, 12)])  # K-fold rehash
def test_lsh_hash_pallas_vs_ref_explicit(b, d, l, k, r, dtype):
    """Explicit backend pin for the hash kernel: the pallas projection +
    floor + K-fold integer mix against the jnp oracle, both resolved by
    name — immune to REPRO_KERNEL_BACKEND / default-backend flips.  Bucket
    indices are discrete, so parity is *exact*: both paths accumulate the
    projection in f32 (``preferred_element_type``), and the mix is integer
    arithmetic with one bit-for-bit convention (kernel docstring)."""
    key = jax.random.PRNGKey(b * 11 + d)
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d)).astype(dtype)
    w = jax.random.normal(kw, (l, k, d))
    bias = jax.random.uniform(kb, (l, k)) * 1.5
    got = lsh_hash(x, w, bias, bandwidth=1.5, n_buckets=r, block_b=16,
                   backend="pallas")
    want = lsh_hash(x, w, bias, bandwidth=1.5, n_buckets=r, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32
    assert bool(jnp.all((got >= 0) & (got < r)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,win,cap,bq,bk", [
    (96, None, None, 32, 32),     # plain causal, seq % block == 0
    (200, 64, None, 64, 64),      # non-divisible seq + sliding window
    (100, None, 50.0, 32, 64),    # softcap + non-pow2 seq, rect tiles
    (144, 32, 30.0, 48, 48),      # window + softcap, non-pow2 blocks
])
def test_flash_attention_pallas_vs_ref_explicit(s, win, cap, bq, bk, dtype):
    """Explicit backend pin for attention: the pallas online-softmax tiles
    against the jnp oracle across the window/softcap feature grid and both
    serving dtypes — f32 at tight tolerance, bf16 at storage precision."""
    from repro.kernels.flash_attn.ops import flash_attention

    b, h, dh = 2, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s + bq), 3)
    q = jax.random.normal(kq, (b, s, h, dh)).astype(dtype)
    k = jax.random.normal(kk, (b, s, h, dh)).astype(dtype)
    v = jax.random.normal(kv, (b, s, h, dh)).astype(dtype)
    got = flash_attention(q, k, v, window=win, softcap=cap, block_q=bq,
                          block_k=bk, backend="pallas")
    want = flash_attention(q, k, v, window=win, softcap=cap, backend="ref")
    assert got.dtype == want.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True)])
def test_interpret_default_follows_the_backend(monkeypatch, backend,
                                               interpret):
    from repro.kernels import common

    monkeypatch.setattr(common.jax, "default_backend", lambda: backend)
    assert common.interpret_default() is interpret


def test_interpret_default_refuses_other_backends(monkeypatch):
    """No silent interpreter on an accelerator the kernels were not
    written for."""
    from repro.kernels import common

    monkeypatch.setattr(common.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        common.interpret_default()


@pytest.mark.parametrize("rows,itemsize,v,tile", [
    (128 * 16, 4, 65536, 512),     # f32 counts at L=128, R=16
    (128 * 16, 1, 65536, 2048),    # int8
    (64 * 16, 1, 65536, 4096),     # int4: L/2 packed rows
    (8 * 4, 4, 300, 384),          # small head: the whole (padded) vocab
    (128 * 16, 4, 1100, 384),      # V pads to 1152 = 3 x 384, not 512s
])
def test_vocab_tile_fits_the_budget_and_divides_v(rows, itemsize, v, tile):
    from repro.kernels.common import (COUNT_BLOCK_VMEM_BYTES, LANES,
                                      round_up, vocab_tile)

    got = vocab_tile(rows, itemsize, v)
    assert got == tile
    assert got % LANES == 0 and round_up(v, LANES) % got == 0
    assert got == LANES or 2 * rows * got * itemsize <= COUNT_BLOCK_VMEM_BYTES
