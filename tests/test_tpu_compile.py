"""Compile the serving path for a described TPU v5e chip, no chip needed.

Interpret mode runs a kernel body in Python and never meets the chip's
limits: VMEM per kernel, the ops Mosaic can lower, tile alignment.  These
cases compile for one chip of a described ``v5e:2x2`` topology at the
serving shapes of ``rwkv6-1.6b`` and its default sketch head (d=2048,
V=65536, L=128, R=16, K=1, d'=32, B=8): the two decode kernels for f32,
int8 and int4 counts, and the whole decode step.  Where a kernel belongs,
the compiled program must hold it (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.heads import DenseHead, SketchHead
from repro.configs import get_config
from repro.kernels import registry
from repro.kernels.fused_decode.kernel import fused_decode_pallas
from repro.kernels.sketch_head.kernel import sketch_head_pallas
from repro.launch.steps import abstract_cache, abstract_params, serve_step
from repro.models.config import SketchHeadConfig

D, V, B = 2048, 65536, 8
HEAD_CFG = SketchHeadConfig(n_rows=128, n_buckets=16, k=1, proj_dim=32,
                            bandwidth=2.0)
QUANTS = [None, "int8", "int4"]
HBM_BYTES = 16e9   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _head_shapes(sharding, quant):
    """Frozen head params as ``freeze_head`` lays them out."""
    c = HEAD_CFG
    rows = c.n_rows // 2 if quant == "int4" else c.n_rows
    head = {
        "proj": _spec(sharding, (D, c.proj_dim)),
        "w": _spec(sharding, (c.n_rows, c.k, c.proj_dim)),
        "b": _spec(sharding, (c.n_rows, c.k)),
        "array": _spec(sharding, (rows, c.n_buckets, V),
                       jnp.float32 if quant is None else jnp.int8),
    }
    if quant is not None:
        head["scale"] = _spec(sharding, (c.n_rows, c.n_buckets))
    return head


@pytest.mark.parametrize("quant", QUANTS)
def test_fused_decode_compiles(one_chip, quant):
    def fn(h, head):
        return fused_decode_pallas(
            h, head["proj"], head["w"], head["b"], head["array"],
            bandwidth=HEAD_CFG.bandwidth, n_buckets=HEAD_CFG.n_buckets,
            scale=head.get("scale"), quant=quant, interpret=False)

    compiled = jax.jit(fn).lower(_spec(one_chip, (B, D)),
                                 _head_shapes(one_chip, quant)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quant", QUANTS)
def test_sketch_head_compiles(one_chip, quant):
    def fn(idx, head):
        return sketch_head_pallas(head["array"], idx, head.get("scale"),
                                  quant=quant, interpret=False)

    idx = _spec(one_chip, (B, HEAD_CFG.n_rows), jnp.int32)
    compiled = jax.jit(fn).lower(idx, _head_shapes(one_chip, quant)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def kernels_for_tpu(monkeypatch):
    """Steer the kernels off their CPU branch: this process's backend is the
    CPU, but the program being compiled is for the described chip."""
    from repro.kernels.fused_decode import kernel as fused_kernel

    monkeypatch.setattr(fused_kernel, "interpret_default", lambda: False)
    monkeypatch.setattr(registry, "_OVERRIDE", "pallas")
    jax.clear_caches()   # no trace made with interpret=True may be reused
    yield
    jax.clear_caches()


@pytest.mark.parametrize("quant", ["dense"] + QUANTS)
def test_rwkv6_decode_step_compiles(one_chip, kernels_for_tpu, quant):
    """The engine's decode step at published width: one token for each of
    B slots, per-slot positions and an active mask, as ServeEngine calls
    it.  The dense head has no kernel; the sketch heads must hold theirs."""
    cfg = get_config("rwkv6-1.6b")
    place = functools.partial(jax.tree.map,
                              lambda a: _spec(one_chip, a.shape, a.dtype))
    params = place(abstract_params(cfg))
    cache = place(abstract_cache(cfg, B, 544))
    if quant == "dense":
        head, head_params = DenseHead(), None
    else:
        head = SketchHead(cfg=HEAD_CFG, quant=quant)
        head_params = _head_shapes(one_chip, quant)
    step = jax.jit(functools.partial(serve_step, cfg=cfg, head=head))
    compiled = step.lower(
        params, cache, _spec(one_chip, (B, 1), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), head_params=head_params,
        active=_spec(one_chip, (B,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    kernels = [line.strip() for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert bool(kernels) == (quant != "dense")
    # The benchmark finds the head kernel by its instruction name
    # (``%_pallas.<n>``): the ``head`` scope is in its op_name only.
    for line in kernels:
        assert line.startswith("%_pallas.") and "/head/" in line, line
