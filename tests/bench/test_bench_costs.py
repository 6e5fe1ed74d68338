"""Costs from shapes, against the hand counts of the configurations: the
head kernel's in ``bench/costs.py``, each family's in its reference
module."""

import json
from pathlib import Path

import pytest

from bench import costs
from bench.manifest import load_manifest, load_module

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def family(config):
    return load_module(ROOT / "bench" / "reference" /
                       f"{config['reference']}.py", config["reference"])


RWKV = cfg("rwkv6-1.6b.sketch-int8")
GRANITE = cfg("granite-8b.q9-dense")


def test_int8_count_array_is_134_mb():
    c = costs.fused_decode_cost(8, 2048, RWKV["head"], 65536)
    assert c["count_bytes"] == 128 * 16 * 65536 == 134_217_728


def test_fused_decode_bytes_and_flops_by_hand():
    b, d, v = 128, 2048, 65536
    c = costs.fused_decode_cost(b, d, RWKV["head"], v)
    want_bytes = (134_217_728 + 4 * 128 * 16
                  + 4 * (d * 32 + 128 * 1 * 32 + 128) + 4 * b * (d + v))
    assert c["bytes"] == want_bytes
    assert c["flops"] == b * (2 * d * 32 + 2 * 128 * 32 + 2 * 128 * v)
    # The count array is read once, whatever the batch.
    assert (costs.fused_decode_cost(256, d, RWKV["head"], v)["count_bytes"]
            == c["count_bytes"])


def test_granite_kv_is_36_kib_per_token():
    assert family(GRANITE).kv_bytes_per_token(GRANITE) == 36 * 1024
    assert family(RWKV).kv_bytes_per_token(RWKV) == 0


def test_granite_q9_has_2_16_billion_params():
    d, ff = 4096, 14336
    per_layer = 2 * d * d + 2 * d * 1024 + 3 * d * ff + 2 * d
    n = family(GRANITE).param_count(GRANITE)
    assert n == 9 * per_layer + 49152 * d + d == 2_164_338_688
    assert round(n / 1e9, 2) == 2.16 and round(2 * n / 1e9, 2) == 4.33


@pytest.mark.parametrize(
    "config", [json.loads((ROOT / c["file"]).read_text())
               for c in load_manifest(ROOT)["configs"]],
    ids=lambda c: c["name"])
def test_param_count_matches_the_weights_made(config):
    import jax

    ref = family(config)
    shapes = jax.eval_shape(lambda k: ref.make_params(k, config),
                            jax.random.PRNGKey(0))
    n = sum(int(x.size) for x in jax.tree.leaves(shapes))
    assert ref.param_count(config) == n


def test_granite_full_depth_matches_the_program_count():
    from repro.configs import get_config
    from repro.models.config import param_count

    full = dict(GRANITE, n_layers=36)
    assert (family(GRANITE).param_count(full)
            == param_count(get_config("granite-8b")))


def test_decode_flops_by_hand():
    d, ff, v, n = 4096, 14336, 49152, 9
    per_layer = 2 * d * 4096 + 2 * d * 1024 + 3 * d * ff
    rows, live = 48, 48 * 2000
    want = (rows * (2 * n * per_layer + 2 * d * v)
            + live * n * 4 * 32 * 128)
    assert costs.decode_flops(GRANITE, family(GRANITE), rows, live) == want
    d, ff, v = 2048, 7168, 65536
    head = 2 * d * 32 + 2 * 128 * 32 + 2 * 128 * v
    want = 128 * (2 * 24 * (6 * d * d + 2 * 64 * d + 2 * d * ff) + head
                  + 24 * 5 * 64 * d)
    assert costs.decode_flops(RWKV, family(RWKV), 128, 0) == want
