"""Optimizer: AdamW convergence, schedule, int8 error-feedback compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.adamw import (AdamWState, OptimizerConfig, adamw_update,
                               global_norm, init_adamw, lr_schedule)
from repro.optim.compress import (compress_grad_leaf, dequantize_int8,
                                  init_error_feedback, quantize_int8)


def test_adamw_converges_quadratic():
    cfg = OptimizerConfig(lr=0.1, warmup_steps=5, total_steps=200,
                          weight_decay=0.0, grad_clip=10.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = init_adamw(params)
    target = jnp.asarray([1.0, 2.0])
    for _ in range(200):
        grads = {"w": 2 * (state.master["w"] - target)}
        params, state, m = adamw_update(grads, state, cfg)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.2)


def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(jnp.asarray(s), cfg)) for s in range(101)]
    assert lrs[0] < lrs[10]                       # warmup
    assert lrs[10] == pytest.approx(1.0, rel=1e-3)
    assert lrs[100] == pytest.approx(0.1, rel=1e-2)  # cosine floor


def test_grad_clip_caps_update_norm():
    cfg = OptimizerConfig(lr=1e-3, grad_clip=1.0, warmup_steps=0,
                          total_steps=10)
    params = {"w": jnp.zeros((4,))}
    state = init_adamw(params)
    huge = {"w": jnp.full((4,), 1e6)}
    _, state2, m = adamw_update(huge, state, cfg)
    assert float(m["grad_norm"]) > 1e5
    # after clipping, first moment is bounded by clip scale
    assert float(jnp.max(jnp.abs(state2.mu["w"]))) <= 0.2


def test_bf16_params_stay_bf16():
    params = {"w": jnp.zeros((4,), jnp.bfloat16)}
    state = init_adamw(params)
    new_params, state, _ = adamw_update({"w": jnp.ones((4,), jnp.bfloat16)},
                                        state, OptimizerConfig())
    assert new_params["w"].dtype == jnp.bfloat16
    assert state.master["w"].dtype == jnp.float32


def test_quantize_roundtrip_error_bounded():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1000,)) * 3.0
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) / 2 + 1e-6


def test_error_feedback_is_lossless_in_sum():
    """Σ_t dequant(q_t) == Σ_t g_t up to one residual: EF telescopes."""
    key = jax.random.PRNGKey(1)
    g_total = jnp.zeros((64,))
    sent_total = jnp.zeros((64,))
    err = jnp.zeros((64,))
    for t in range(50):
        g = jax.random.normal(jax.random.fold_in(key, t), (64,))
        q, scale, err = compress_grad_leaf(g, err)
        sent_total = sent_total + dequantize_int8(q, scale)
        g_total = g_total + g
    # residual carried in err is the only discrepancy
    np.testing.assert_allclose(np.asarray(sent_total + err),
                               np.asarray(g_total), rtol=1e-4, atol=1e-4)


def test_compressed_psum_single_device_mesh():
    """compressed_psum under shard_map on a 1-device mesh (degenerate axis)."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import compressed_psum

    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.linspace(-1, 1, 32)}
    e = init_error_feedback(g)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
             out_specs=(P(), P()), check_vma=False)
    def f(gt, et):
        return compressed_psum(gt, et, "data")

    mean, new_e = f(g, e)
    np.testing.assert_allclose(np.asarray(mean["w"] + new_e["w"]),
                               np.asarray(g["w"]), atol=1e-5)


def test_global_norm():
    t = {"a": jnp.ones((3,)), "b": jnp.ones((4,)) * 2}
    assert float(global_norm(t)) == pytest.approx(np.sqrt(3 + 16))
