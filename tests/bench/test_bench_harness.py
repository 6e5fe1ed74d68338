"""A whole run of the harness at a size the CPU holds: it passes when sound,
and ``correct`` comes out false with the timed path broken underneath.

The runs skip the harness's look for a chip (``require_tpu=False``); the
chip check itself is tested through the command line, which must refuse
to print a result with no TPU."""

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SEED = 5
# The configurations with a smoke size and limit (bench/smoke/<config>.json);
# test_every_config_has_a_smoke_size names any without one.
CONFIGS = sorted(
    c["name"] for c in
    json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]
    if (ROOT / "bench/smoke" / f"{c['name']}.json").is_file())


@pytest.fixture(scope="module")
def root(tmp_path_factory, make_smoke_checkout):
    """A checkout whose cells are the committed configurations cut to a
    smoke size, under a small backlog."""
    return make_smoke_checkout(ROOT, tmp_path_factory.mktemp("checkout"))


def run(root, name, seed=SEED):
    return harness.run_cell(root, f"{name}.smoke", seed, 1.0, False,
                            time.perf_counter(), require_tpu=False,
                            cache=False)


@pytest.mark.parametrize("name", CONFIGS)
def test_sound_run_is_correct(root, name):
    out = run(root, name)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["check"]["compiles_in_window"]["value"] == 0
    assert out["check"]["sampled_tokens"] >= 50
    assert {"gen_tok_s", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "check"


def _stale_state(real):
    def megastep(self, pool, *a, **k):
        keep = jax.tree.map(jnp.copy, pool)
        out = real(self, pool, *a, **k)
        return (out[0], keep) + out[2:]
    return megastep


def _altered_token(real):
    def megastep(self, pool, *a, **k):
        out = real(self, pool, *a, **k)
        block = out[0].copy()
        block[0] = (block[0] + 1) % self.vocab_size
        return (block,) + out[1:]
    return megastep


def _half_the_batch(real):
    def megastep(self, pool, tokens, pos, active, *a, **k):
        active = active.copy()
        active[1::2] = False
        return real(self, pool, tokens, pos, active, *a, **k)
    return megastep


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("fault", [_stale_state, _altered_token,
                                   _half_the_batch],
                         ids=["state_unchanged", "token_altered",
                              "half_the_batch"])
def test_broken_timed_path_is_not_correct(root, name, fault, monkeypatch):
    from repro.launch.engine import EngineBackend

    monkeypatch.setattr(EngineBackend, "megastep",
                        fault(EngineBackend.megastep))
    out = run(root, name)
    assert not out["correct"], out["check"]


def test_no_chip_no_result():
    """With no TPU the command exits non-zero and prints no result."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "rwkv6.offline_decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_bare_benchmark_files_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to run: non-zero exit, no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "rwkv6.offline_decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_control_reads_wider_gaps_than_the_program(root):
    """The controls (the reference in each precision the cell's check file
    names, in the program's place) at this size: on the same sample their
    picks lie further below the f32 reference's best than the served
    tokens do, their first tokens are read too (8 of them: too few to order
    the two at this size), and the harness judges each control by the
    cell's own limits.  Whether a control fails them depends on the sample
    at this size, where the window is short; at the cell's size every
    control run failed (PERF.md).

    Only the sketch-head configuration: at this size the dense granite
    model's served tokens are nearly all the reference's argmax and so are
    the control's, so a sample can order the two either way.  Both cells'
    control readings at their own size are in PERF.md."""
    out = harness.run_cell(root, "rwkv6-1.6b.sketch-int8.smoke", SEED, 1.0,
                           False, time.perf_counter(), require_tpu=False,
                           cache=False, control=True)
    r = out["check"]["readings"]
    assert set(out["check"]["control_correct"]) == {"int8", "fp8"}
    for c in ("int8", "fp8"):
        assert r[f"{c}_mean_gap"] > r["mean_gap"], (c, r)
        assert r[f"{c}_miss_share"] >= r["miss_share"], (c, r)
        assert 0 <= r[f"{c}_first_mean_gap"] < float("inf"), (c, r)
    assert r["first_tokens"] == 8
    limits = json.loads((root / "bench/checks/rwkv6-1.6b.sketch-int8"
                         ".smoke.json").read_text())["limits"]
    for c in ("int8", "fp8"):
        judged = all(r[f"{c}_{n}"] <= v for n, v in limits.items())
        assert out["check"]["control_correct"][c] is judged, (c, r)
