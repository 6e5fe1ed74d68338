"""Dry-run smoke: lower+compile smoke-scale cells on the production meshes.

Runs in subprocesses because the 512-placeholder-device XLA flag must be set
before jax initializes (the main pytest process keeps 1 device)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(arch, shape, mesh):
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--smoke"]
    return subprocess.run(
        cmd, cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"})


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape,mesh", [
    ("granite-8b", "train_4k", "single"),
    ("granite-8b", "decode_32k", "multi"),
    ("mixtral-8x7b", "train_4k", "multi"),
    ("rwkv6-1.6b", "long_500k", "single"),
])
def test_dryrun_smoke_cell(arch, shape, mesh):
    res = _run(arch, shape, mesh)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(
        (ROOT / "results" / "dryrun" / f"{arch}__{shape}__{mesh}.json"
         ).read_text())
    assert out["flops"] > 0
    assert out["n_devices"] == (512 if mesh == "multi" else 256)
