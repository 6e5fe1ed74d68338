"""Find a cell's pieces by name: its configuration, its traffic mix and the
readers of its metrics.

Everything is looked up under ``root`` (the checkout):

* the manifest ``BENCHMARK.json``;
* a configuration at the ``file`` its manifest entry names (JSON);
* a traffic mix at ``bench/traffic/<traffic>.json``;
* a model family's plain reference at ``bench/reference/<reference>.py``;
* a metric's reader at ``bench/metrics/<metric>.py``;
* a cell's output check (the number compared and its limit) at
  ``bench/checks/<workload>.json``;
* a configuration's CPU smoke size (the overrides that cut it, the limit of
  its smoke runs and why) at ``bench/smoke/<config>.json``, read by the
  benchmark's own tests (``tests/bench/conftest.py``), never by a run.

A later change adds a configuration, a mix or a metric as new files plus
manifest entries; no file here needs an edit for it.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_module(path: Path, name: str) -> ModuleType:
    """Import the Python file ``path`` (its name may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell ``workload`` with everything it names, loaded.

    Returns a dict with the manifest's ``cell``, ``config`` (the parsed
    configuration file), ``traffic`` (the parsed mix), ``check`` (the
    number compared and its limit), ``reference`` (the
    family module), ``end_to_end`` and ``per_layer`` (the manifest's metric
    entries that this cell reports) and ``readers`` (name -> reader module
    of each of them).
    """
    root = Path(root)
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    entry = configs[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    reference = load_module(
        root / "bench" / "reference" / f"{config['reference']}.py",
        config["reference"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in man["end_to_end"] if mine(m)]
    per_layer = [m for m in man["per_layer"] if mine(m)]
    readers = {m["name"]: load_module(
        root / "bench" / "metrics" / f"{m['name']}.py", m["name"])
        for m in e2e + per_layer}
    check = json.loads(
        (root / "bench" / "checks" / f"{workload}.json").read_text())
    return {"cell": cell, "config": config, "traffic": traffic,
            "check": check,
            "reference": reference, "end_to_end": e2e,
            "per_layer": per_layer, "readers": readers,
            "run_seconds": man["run_seconds"]}
