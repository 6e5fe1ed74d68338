"""The benchmark's tests import it as the package ``bench`` from the
checkout's root; ``make_smoke_checkout`` builds a checkout whose cells run
on the CPU."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# A small backlog: every smoke cell's traffic.
SMOKE_MIX = {"arrival": "backlog", "slots": 4, "max_seq": 48,
             "admit_per_tick": 1, "prompt_buckets": [8, 16],
             "prompt_weights": [1, 1],
             "output": {"dist": "uniform", "min": 8, "max": 24}, "block": 8}


def build_smoke_checkout(src: Path, dst: Path) -> Path:
    """A checkout at ``dst`` with the benchmark of the checkout ``src``,
    whose cells are ``src``'s configurations cut to the smoke size in
    ``bench/smoke/<config>.json``: one cell ``<config>.smoke`` each, under
    ``SMOKE_MIX``, checked by the smoke file's limits and the controls of
    the configuration's first cell.  A configuration without a smoke file
    gets no cell."""
    shutil.copytree(src / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((src / "BENCHMARK.json").read_text())
    (dst / "bench/traffic/smoke.json").write_text(json.dumps(SMOKE_MIX))
    cells = man["workloads"]
    man["workloads"] = []
    for c in man["configs"]:
        smoke_file = src / "bench/smoke" / f"{c['name']}.json"
        if not smoke_file.is_file():
            continue
        smoke = json.loads(smoke_file.read_text())
        cfg = json.loads((src / c["file"]).read_text())
        cfg.update(smoke["overrides"])
        cfg["head"] = dict(cfg["head"], n_anchors=64)
        (dst / c["file"]).write_text(json.dumps(cfg))
        cell = next(w["name"] for w in cells if w["config"] == c["name"])
        committed = json.loads((src / "bench/checks" / f"{cell}.json")
                               .read_text())
        spec = dict(committed, limits=smoke["limits"])
        if "first_tokens" in committed:
            spec["first_tokens"] = 8
        (dst / "bench/checks" / f"{c['name']}.smoke.json").write_text(
            json.dumps(spec))
        man["workloads"].append({"name": f"{c['name']}.smoke",
                                 "config": c["name"], "traffic": "smoke",
                                 "chips": 1, "why": "smoke"})
    for m in man["end_to_end"] + man["per_layer"]:
        m.pop("workloads", None)
    (dst / "BENCHMARK.json").write_text(json.dumps(man))
    return dst


@pytest.fixture(scope="session")
def make_smoke_checkout():
    return build_smoke_checkout
