"""The readers of per-program metrics on a hand-made traced run: the values
they should read, and nothing where the program does not name what they
read (as before the engine named its programs) or the span holds none of
it."""

from pathlib import Path

import pytest

from bench.harness import RunView
from bench.manifest import load_module

ROOT = Path(__file__).resolve().parents[2]
MS = 1e6  # nanoseconds


def reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py", name)


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, end_ms * MS, {})


def run_view(module_events, prompt_tokens=(1000, 2500), scopes=None,
             decode_steps=(100, 110)):
    """A traced run whose first device ran ``module_events``; ``modules``
    sums them per program, as ``harness.reduce_trace`` does."""
    modules = {}
    for name, s, e, _ in module_events:
        key = name.split("(")[0]
        modules[key] = modules.get(key, 0.0) + e - s
    dev = {"busy_ns": 0.0, "modules": modules,
           "module_events": module_events, "op_events": [], "ops": {},
           "idle": {}, "scopes": scopes or {}}
    trace = {"lo": 0.0, "hi": 100 * MS, "window_ns": 100 * MS,
             "devices": {"/device:TPU:0": dev}}
    counters = tuple({"prompt_tokens": n, "decode_steps": k}
                     for n, k in zip(prompt_tokens, decode_steps))
    return RunView(cfg={}, mix={"slots": 4}, ref=None, records=[],
                   window=(0.0, 0.1), counters=counters, setup_s=1.0,
                   peaks=None, trace=trace)


NAMED = [ev("jit_fresh_cache(11)", 0, 0.5), ev("jit_prefill(12)", 0.5, 3.5),
         ev("jit_sample(13)", 3.5, 3.6), ev("jit_slot_insert(14)", 3.6, 5.6),
         ev("jit_megastep(15)", 6, 46), ev("jit_slot_reset(16)", 46, 50),
         ev("jit_prefill(12)", 51, 54)]
# The same work as the programs were named before: the prefill and the
# sampler as partials, both slot ops as the inner function ``op``.
ANONYMOUS = [ev("jit__unknown(3)", 0.5, 3.5), ev("jit__unknown(4)", 3.5, 3.6),
             ev("jit_op(5)", 3.6, 5.6), ev("jit_megastep(15)", 6, 46),
             ev("jit_op(6)", 46, 50)]


def test_slot_write_ms_is_the_mean_slot_op():
    # insert 2 ms and reset 4 ms: 3 ms a write
    assert reader("slot_write_ms").read(run_view(NAMED)) == pytest.approx(3.0)


def test_prefill_us_per_token_is_prefill_time_over_new_prompt_tokens():
    # 6 ms of jit_prefill over 1500 prompt tokens: 4 us a token
    assert reader("prefill_us_per_token").read(run_view(NAMED)) == \
        pytest.approx(4.0)


@pytest.mark.parametrize("name", ["slot_write_ms", "prefill_us_per_token"])
@pytest.mark.parametrize("view", [
    run_view(ANONYMOUS),
    run_view([ev("jit_megastep(15)", 6, 46)]),
    RunView(cfg={}, mix={}, ref=None, records=[], window=(0.0, 0.1),
            counters=({"prompt_tokens": 0}, {"prompt_tokens": 10}),
            setup_s=1.0, peaks=None, trace=None),
], ids=["anonymous_programs", "megastep_only", "untraced"])
def test_nothing_to_read_reads_none(name, view):
    assert reader(name).read(view) is None


def test_prefill_without_new_prompt_tokens_reads_none():
    view = run_view(NAMED, prompt_tokens=(2500, 2500))
    assert reader("prefill_us_per_token").read(view) is None


# Self time by model scope, as ``harness.reduce_trace`` sums it: 10 decode
# steps in the span.
SCOPES = {"jit_megastep": {"jit(megastep)": 40 * MS, "head": 9 * MS,
                           "mixer": 25 * MS, "ffn": 4 * MS},
          "jit_prefill": {"head": 2 * MS, "mixer": 1 * MS}}


@pytest.mark.parametrize("name, ms", [("head_step_ms", 0.9),
                                      ("mixer_step_ms", 2.5)])
def test_scope_readers_are_megastep_scope_time_per_step(name, ms):
    assert reader(name).read(run_view(NAMED, scopes=SCOPES)) == \
        pytest.approx(ms)


@pytest.mark.parametrize("name", ["head_step_ms", "mixer_step_ms"])
@pytest.mark.parametrize("view", [
    run_view(NAMED),
    run_view(NAMED, scopes={"jit_prefill": SCOPES["jit_prefill"]}),
    run_view(NAMED, scopes=SCOPES, decode_steps=(110, 110)),
    RunView(cfg={}, mix={}, ref=None, records=[], window=(0.0, 0.1),
            counters=({"decode_steps": 0}, {"decode_steps": 10}),
            setup_s=1.0, peaks=None, trace=None),
], ids=["no_scopes", "prefill_only", "no_decode_step", "untraced"])
def test_scope_readers_read_none_without_their_scope(name, view):
    assert reader(name).read(view) is None
