"""A new configuration, traffic mix and per-layer metric are files plus
manifest entries: the harness finds them by name with no edit to its own
files."""

import json
import shutil
from pathlib import Path

import pytest

from bench import costs, manifest
from bench.traffic import Schedule

ROOT = Path(__file__).resolve().parents[2]


def test_committed_manifest_loads_every_cell():
    man = manifest.load_manifest(ROOT)
    for w in man["workloads"]:
        cell = manifest.find_cell(w["name"], ROOT)
        assert cell["config"]["name"] == w["config"]
        assert cell["end_to_end"] and cell["per_layer"]
        assert "setup_s" in {m["name"] for m in cell["end_to_end"]}
        for name, reader in cell["readers"].items():
            assert callable(reader.read), name


def test_every_cell_has_a_check():
    man = manifest.load_manifest(ROOT)
    for w in man["workloads"]:
        limits = manifest.find_cell(w["name"], ROOT)["check"]["limits"]
        assert limits and all(v > 0 for v in limits.values())


def test_every_metric_has_a_reader():
    man = manifest.load_manifest(ROOT)
    for m in man["end_to_end"] + man["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_new_files_are_found_by_name(tmp_path):
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*.py")}
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/rwkv6-1.6b.sketch-int8.json")
                     .read_text())
    cfg.update(name="rwkv6-1.6b.dense", head={"kind": "dense"})
    (root / "bench/configs/rwkv6-1.6b.dense.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/chat_poisson.json").read_text())
    mix.update(arrival="poisson", rate_per_s=3.0, block=10)
    (root / "bench/traffic/chat_trickle.json").write_text(json.dumps(mix))
    (root / "bench/checks/rwkv6dense.trickle.json").write_text(
        json.dumps({"limits": {"mean_gap": 0.01}}))
    (root / "bench/metrics/answer_len.py").write_text(
        "def read(run):\n    return 42.0\n")
    # a new model family: one reference module, named by its configuration
    (root / "bench/reference/rwkv6_twin.py").write_text(
        (ROOT / "bench/reference/rwkv6.py").read_text())
    cfg["reference"] = "rwkv6_twin"
    (root / "bench/configs/rwkv6-1.6b.dense.json").write_text(
        json.dumps(cfg))
    man["configs"].append({"name": "rwkv6-1.6b.dense", "source": "x",
                           "file": "bench/configs/rwkv6-1.6b.dense.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "rwkv6dense.trickle",
                             "config": "rwkv6-1.6b.dense",
                             "traffic": "chat_trickle", "chips": 1,
                             "why": "x"})
    man["per_layer"].append({"name": "answer_len", "unit": "tokens",
                             "better": "higher", "source": "host_clock",
                             "layer": "scheduler", "moves": "ttft_p95_ms",
                             "workloads": ["rwkv6dense.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.find_cell("rwkv6dense.trickle", root)
    assert cell["config"]["head"] == {"kind": "dense"}
    assert cell["traffic"]["rate_per_s"] == 3.0
    assert cell["check"] == {"limits": {"mean_gap": 0.01}}
    assert cell["readers"]["answer_len"].read(None) == 42.0
    assert cell["reference"].__file__.endswith("rwkv6_twin.py")
    assert costs.decode_flops(cell["config"], cell["reference"], 8, 0) > 0
    # a metric without a workloads key reaches every cell that reports its
    # end-to-end metric; this cell reports ttft_p95_ms only if listed
    assert "answer_len" in {m["name"] for m in cell["per_layer"]}
    sched = Schedule(cell["traffic"], 5, cell["config"]["vocab_size"])
    assert sched.request(0).due_s > 0
    with pytest.raises(KeyError):
        manifest.find_cell("no.such.cell", root)
    assert before == {p: p.read_bytes()
                      for p in (ROOT / "bench").rglob("*.py")}


def test_harness_names_no_model_family():
    """Everything that depends on a family lives in its reference module:
    the harness's own files name none."""
    for p in (ROOT / "bench").glob("*.py"):
        text = p.read_text().lower()
        for word in ("rwkv", "granite", "attentionconfig"):
            assert word not in text, (p.name, word)


def test_cell_mixes_name_their_source():
    man = manifest.load_manifest(ROOT)
    for w in man["workloads"]:
        mix = manifest.find_cell(w["name"], ROOT)["traffic"]
        assert mix["source"].strip() and "none" not in mix["source"], w
