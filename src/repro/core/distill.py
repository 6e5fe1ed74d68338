"""Teacher → kernel-student distillation (the paper's §3.4 'whole recipe').

Pipeline:
  1. Train (or receive) a teacher network f_N.
  2. Fit the kernel model f_K(q) = Σ α_j K(A^T q, x_j) to f_N's *outputs*
     with MSE loss and gradient descent (Adam), M ≪ N anchors.
  3. Freeze f_K into a RepresenterSketch for deployment.

The teacher here is a plain-JAX MLP (repro.core.teacher) — the paper's
experiments all use MLPs on tabular data.  Everything is jit-compiled and
runs in minutes on CPU for the paper-scale problems.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.kernel_model import KernelModel, KernelModelConfig


@dataclasses.dataclass(frozen=True)
class DistillConfig:
    n_steps: int = 2000
    batch_size: int = 256
    lr: float = 3e-3
    weight_decay: float = 0.0
    # L1 penalty on the alphas: the sketch's bucket-collision noise floor
    # scales with Σ|α|/√R (Theorem 1's variance bound), so sparse small-mass
    # alphas directly buy estimation accuracy per unit of sketch memory.
    alpha_l1: float = 0.0


def _adam_init(params):
    return {
        "mu": jax.tree.map(jnp.zeros_like, params),
        "nu": jax.tree.map(jnp.zeros_like, params),
        "t": jnp.zeros((), jnp.int32),
    }


def _adam_update(params, grads, state, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    t = state["t"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], grads)
    mhat = jax.tree.map(lambda m: m / (1 - b1**t), mu)
    vhat = jax.tree.map(lambda v: v / (1 - b2**t), nu)
    new_params = jax.tree.map(
        lambda p, m, v: p - lr * (m / (jnp.sqrt(v) + eps) + wd * p),
        params,
        mhat,
        vhat,
    )
    return new_params, {"mu": mu, "nu": nu, "t": t}


def distill(
    key: jax.Array,
    teacher_fn: Callable[[jnp.ndarray], jnp.ndarray],
    train_x: jnp.ndarray,
    model: KernelModel,
    config: DistillConfig = DistillConfig(),
) -> Tuple[dict, Dict[str, float]]:
    """Fit ``model`` to ``teacher_fn`` on the (unlabeled) inputs ``train_x``.

    Returns the learned kernel-model params and a small metrics dict.
    The teacher's outputs are the regression targets (MSE risk), exactly as
    in Figure 1 of the paper.
    """
    k_init, k_anchor, k_loop = jax.random.split(key, 3)
    params = model.init(k_init)
    # Anchor the points on (projected) data samples — random-normal init
    # leaves whole data regions uncovered by the narrow k-fold LSH kernel
    # and the fit can collapse (observed on the phishing task).
    m = model.config.n_points
    idx = jax.random.randint(k_anchor, (m,), 0, train_x.shape[0])
    params["points"] = model.transform(params, train_x[idx])
    opt = _adam_init(params)
    targets = teacher_fn(train_x)  # soft targets — logits / regression output
    # Standardize targets for conditioning; fold the scale back into the
    # (linear) alphas afterwards.
    t_scale = jnp.maximum(jnp.std(targets), 1e-6)
    targets = targets / t_scale
    n = train_x.shape[0]

    def loss_fn(p, xb, yb):
        pred = model.apply(p, xb)
        mse = jnp.mean((pred - yb) ** 2)
        if config.alpha_l1:
            mse = mse + config.alpha_l1 * jnp.mean(jnp.abs(p["alphas"]))
        return mse

    # The data are arguments of the jitted loop: closed over, they would be
    # baked into the program as constants (760 MB for a 65k-vocab teacher),
    # too large for the persistent compilation cache to keep.
    @jax.jit
    def fit(carry, keys, xs, ys):
        def step(carry, key_step):
            p, o = carry
            idx = jax.random.randint(key_step, (config.batch_size,), 0, n)
            loss, grads = jax.value_and_grad(loss_fn)(p, xs[idx], ys[idx])
            p, o = _adam_update(p, grads, o, config.lr, config.weight_decay)
            return (p, o), loss

        return jax.lax.scan(step, carry, keys)

    keys = jax.random.split(k_loop, config.n_steps)
    (params, opt), losses = fit((params, opt), keys, train_x, targets)
    final_loss = float(
        loss_fn(params, train_x[: min(n, 4096)], targets[: min(n, 4096)])
    )
    params = dict(params, alphas=params["alphas"] * t_scale)
    return params, {
        "final_mse": final_loss,
        "first_loss": float(losses[0]),
        "last_loss": float(losses[-1]),
    }
