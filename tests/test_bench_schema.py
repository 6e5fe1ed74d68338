"""The benchmarks.schema CLI and validators (EXPERIMENTS.md §Bench schema).

The committed BENCH_*.json artifacts must validate against the current
schema version (stale artifacts fail here, not in CI archaeology), and the
CLI must check *every* path before exiting: the regression is the
multi-file invalid case — an early invalid file used to raise and skip the
rest, so CI saw one failure per run instead of the full damage report.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.schema import (SCHEMA_VERSION, main, validate_engine_record,
                               validate_serve_record)

_ROOT = Path(__file__).resolve().parents[1]
_ENGINE = _ROOT / "BENCH_engine.json"
_SERVE = _ROOT / "BENCH_sketch_serve.json"


def test_committed_artifacts_validate(capsys):
    """The checked-in artifacts match the current schema (v6: heavy_tail
    paged-vs-contiguous section with latency percentiles + paging
    counters)."""
    assert main([str(_ENGINE), str(_SERVE)]) == 0
    out = capsys.readouterr().out
    assert out.count(f"valid (schema v{SCHEMA_VERSION})") == 2


def test_engine_artifact_heavy_tail_is_real_measurement():
    """The committed heavy-tail section demonstrates the paging win, not a
    placeholder: Zipf reuse drove the hit rate past 0.5, the paged run
    prefilled strictly less than the contiguous one at equal (bitwise)
    output, and the latency percentiles are ordered."""
    ht = json.loads(_ENGINE.read_text())["heavy_tail"]
    assert ht["requests"] >= 1000
    assert ht["outputs_match"] is True
    assert ht["prefix_hit_rate"] > 0.5
    assert ht["prefill_batches"] < ht["prefill_batches_contiguous"]
    assert ht["pages_in_use_peak"] > 0
    assert 0 < ht["latency_ticks_p50"] <= ht["latency_ticks_p99"]
    for mode in ("contiguous", "paged"):
        assert ht[mode]["tokens_per_s_per_slot"] > 0


def test_heavy_tail_validation_catches_divergence_and_regression(tmp_path):
    """Schema v6 gates: a heavy_tail section claiming diverged outputs or
    more paged prefills than contiguous is rejected."""
    record = json.loads(_ENGINE.read_text())
    record["heavy_tail"]["outputs_match"] = False
    with pytest.raises(ValueError, match="outputs_match"):
        validate_engine_record(record)
    record = json.loads(_ENGINE.read_text())
    record["heavy_tail"]["prefill_batches"] = (
        record["heavy_tail"]["prefill_batches_contiguous"] + 1)
    with pytest.raises(ValueError, match="prefill_batches"):
        validate_engine_record(record)
    record = json.loads(_ENGINE.read_text())
    record["heavy_tail"]["prefix_hit_rate"] = 1.2
    with pytest.raises(ValueError, match="prefix_hit_rate"):
        validate_engine_record(record)
    record = json.loads(_ENGINE.read_text())
    del record["heavy_tail"]
    with pytest.raises(ValueError, match="heavy_tail"):
        validate_engine_record(record)


def test_engine_artifact_has_nonzero_acceptance():
    """The v4 spec sweep is real measurement, not a zeroed placeholder: the
    distilled draft head must beat the ~1/V random-agreement floor."""
    record = json.loads(_ENGINE.read_text())
    for k, run in record["spec_decode"].items():
        assert run["acceptance_rate"] > 0, f"spec_decode[{k}] zero acceptance"
        assert run["accepted_tokens_per_verify"] > 0


def test_cli_validates_every_path_and_reports_all(tmp_path, capsys):
    """Multi-file invalid case: every path is checked, every failure is
    printed, and the exit code is non-zero — the first bad file must not
    mask the rest."""
    bad_missing = tmp_path / "bad_missing.json"
    record = json.loads(_ENGINE.read_text())
    del record["static"]
    bad_missing.write_text(json.dumps(record))
    bad_parse = tmp_path / "bad_parse.json"
    bad_parse.write_text("{not json")
    good = tmp_path / "good.json"
    good.write_text(_SERVE.read_text())

    rc = main([str(bad_missing), str(good), str(bad_parse)])
    out = capsys.readouterr().out
    assert rc == 1
    assert f"{bad_missing}: INVALID" in out and "static" in out
    assert f"{bad_parse}: INVALID" in out
    assert f"{good}: valid" in out            # later files still validated
    assert "2 of 3 artifacts failed" in out


def test_cli_exit_codes_subprocess(tmp_path):
    """python -m benchmarks.schema exits 0 on valid input, 1 on any invalid
    path — the contract the CI bench-smoke job scripts against."""
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    run = lambda *paths: subprocess.run(
        [sys.executable, "-m", "benchmarks.schema", *paths],
        cwd=_ROOT, capture_output=True, text=True)
    ok = run(str(_ENGINE), str(_SERVE))
    assert ok.returncode == 0, ok.stdout + ok.stderr
    fail = run(str(_ENGINE), str(bad))
    assert fail.returncode == 1
    assert "INVALID" in fail.stdout
    assert f"{_ENGINE}: valid" in fail.stdout


def test_spec_run_range_checks():
    """Out-of-range spec stats are rejected, not just missing fields."""
    record = json.loads(_ENGINE.read_text())
    k = next(iter(record["spec_decode"]))
    record["spec_decode"][k]["acceptance_rate"] = 1.5
    with pytest.raises(ValueError, match="acceptance_rate"):
        validate_engine_record(record)

    serve = json.loads(_SERVE.read_text())
    serve["spec_decode"]["acceptance_rate"] = -0.1
    with pytest.raises(ValueError, match="acceptance_rate"):
        validate_serve_record(serve)


def test_quant_curve_required_and_checked():
    """Schema v5: the serve record must carry the full quant_curve and the
    dtype-aware bytes fields, with per-mode range checks."""
    serve = json.loads(_SERVE.read_text())
    missing = json.loads(_SERVE.read_text())
    del missing["quant_curve"]
    with pytest.raises(ValueError, match="quant_curve"):
        validate_serve_record(missing)
    for field in ("dense_bytes", "sketch_bytes", "bytes_ratio"):
        broken = json.loads(_SERVE.read_text())
        del broken[field]
        with pytest.raises(ValueError, match=field):
            validate_serve_record(broken)
    partial = json.loads(_SERVE.read_text())
    del partial["quant_curve"]["int4"]
    with pytest.raises(ValueError, match="int4"):
        validate_serve_record(partial)
    serve["quant_curve"]["int8"]["top1_agreement"] = 1.2
    with pytest.raises(ValueError, match="top1_agreement"):
        validate_serve_record(serve)


def test_serve_artifact_quant_curve_monotone():
    """The committed curve is real measurement: the f32 row is exact,
    accuracy degrades with fewer bits while the storage ratio climbs past
    the acceptance floors (≥3.9× int8, ≥7.8× int4 at bench scale)."""
    curve = json.loads(_SERVE.read_text())["quant_curve"]
    assert curve["f32"]["logit_mae"] == 0.0
    assert curve["f32"]["top1_agreement"] == 1.0
    assert curve["int8"]["logit_mae"] <= curve["int4"]["logit_mae"]
    assert curve["int8"]["top1_agreement"] >= curve["int4"]["top1_agreement"]
    assert curve["int8"]["bytes_ratio"] >= 3.9
    assert curve["int4"]["bytes_ratio"] >= 7.8


def test_version_mismatch_rejected():
    """An artifact from an older schema fails with a regenerate hint."""
    record = json.loads(_SERVE.read_text())
    record["schema_version"] = SCHEMA_VERSION - 1
    with pytest.raises(ValueError, match="schema_version"):
        validate_serve_record(record)


def test_roofline_peaks_are_keyed_by_device_kind():
    """Peaks come from a named chip; an unknown kind is an error, never a
    default."""
    from benchmarks import roofline

    assert roofline.peaks(roofline.DRYRUN_TARGET)["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
