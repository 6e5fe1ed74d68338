"""What a profiler trace of the serving engine can name (DESIGN.md §15).

One engine tick under ``jax.profiler`` on the CPU, at smoke size, for the
two model families the chip benchmark serves (rwkv with the fused sketch
head, granite's attention with the dense head):

* the tick's phases are ``engine.*`` host spans, nested as the engine runs
  them;
* every program the tick runs on the device has the engine's name for it,
  none the anonymous ``jit__unknown`` or ``jit_op``;
* the megastep's HLO carries the model's scopes in its debug info.
"""

import glob
import re

import jax
import numpy as np
import pytest

from repro.api.heads import DenseHead, SketchHead
from repro.api.lm import LM
from repro.api.sampler import Sampler
from repro.configs import get_config
from repro.core.sketch_lm_head import freeze_head
from repro.launch.decode_loop import jitted_megastep
from repro.models.config import SketchHeadConfig
from repro.models.model import init_decode_cache, init_model

SLOTS, MAX_SEQ, CHUNK, PROMPT = 2, 32, 2, 8
TICK = ["engine.admit", "engine.decode", "engine.fetch", "engine.emit",
        "engine.reset"]
ADMIT = ["engine.prefill", "engine.sample_first", "engine.insert"]
PROGRAMS = {"jit_fresh_cache", "jit_prefill", "jit_expand_rows",
            "jit_sample", "jit_slot_insert", "jit_slot_reset",
            "jit_megastep"}
SCOPES = ["embed", "mixer", "ffn", "cache_mask", "head", "sample"]


def _sketch_head(cfg):
    head_cfg = SketchHeadConfig(n_rows=32, n_buckets=8, k=1, proj_dim=16,
                                bandwidth=2.0)
    kp, ka, kj, kf = jax.random.split(jax.random.PRNGKey(42), 4)
    kernel = {
        "points": jax.random.normal(kp, (128, head_cfg.proj_dim)),
        "alphas": jax.random.normal(ka, (128, cfg.vocab_size)) * 0.01,
        "proj": jax.random.normal(kj, (cfg.d_model, head_cfg.proj_dim))
        / np.sqrt(cfg.d_model),
    }
    return SketchHead(cfg=head_cfg, params=freeze_head(kf, kernel, head_cfg))


@pytest.fixture(scope="module",
                params=[("rwkv6-1.6b", "sketch"), ("granite-8b", "dense")],
                ids=["rwkv6-sketch", "granite-dense"])
def served(request):
    arch, head = request.param
    cfg = get_config(arch, smoke=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    head = _sketch_head(cfg) if head == "sketch" else DenseHead()
    return cfg, params, head


def _admit_two(engine, cfg, rng):
    """Two equal prompts of one token each to emit after the first: one
    tick prefills them once (expanded back to two rows), samples their
    first tokens, inserts them, decodes one token, retires both and
    resets their slots."""
    prompt = rng.integers(0, cfg.vocab_size, PROMPT)
    for _ in range(SLOTS):
        engine.submit(prompt, 2)


@pytest.fixture(scope="module")
def traced_tick(served, tmp_path_factory):
    """(host spans named ``engine.*``, programs run) of one traced tick,
    after an untraced tick of the same shapes compiled everything."""
    cfg, params, head = served
    engine = LM(params, cfg, head).engine(SLOTS, MAX_SEQ, decode_chunk=CHUNK)
    rng = np.random.default_rng(0)
    _admit_two(engine, cfg, rng)
    engine.step()
    _admit_two(engine, cfg, rng)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    engine.step()
    # The tick's last program (the slot reset) is dispatched, not awaited:
    # without the wait it can run after the trace stops.
    jax.block_until_ready(engine.pool)
    jax.profiler.stop_trace()
    assert not engine.sched.n_active and not len(engine.queue)

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    spans, programs = [], set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
                module = dict(e.stats).get("hlo_module")
                if module:
                    programs.add(module)
    return sorted(spans, key=lambda s: s[1]), programs


def test_tick_phases_nest_as_the_engine_runs_them(traced_tick):
    spans, _ = traced_tick
    (admit,) = [s for s in spans if s[0] == "engine.admit"]
    inside = [s for s in spans if admit[1] <= s[1] and s[2] <= admit[2]
              and s is not admit]
    outside = [s for s in spans if s not in inside]
    assert [s[0] for s in inside] == ADMIT
    assert [s[0] for s in outside] == TICK
    for before, after in zip(outside, outside[1:]):
        assert before[2] <= after[1], (before, after)


def test_every_program_has_the_engines_name(traced_tick):
    _, programs = traced_tick
    assert programs == PROGRAMS
    assert not programs & {"jit__unknown", "jit_op"}


def test_megastep_hlo_carries_the_model_scopes(served):
    cfg, params, head = served
    fn = jitted_megastep(cfg, head.without_params(), Sampler(), CHUNK,
                         eos_id=None, masked=True)
    pool = init_decode_cache(cfg, SLOTS, MAX_SEQ)
    slots = np.zeros(SLOTS, np.int32)
    text = fn.lower(params, pool, slots, slots, Sampler().init_key(),
                    head_params=head.params,
                    active=np.ones(SLOTS, bool)).as_text(debug_info=True)
    assert "jit_megastep" in text
    # Locations name an op by its scope path ("cache_mask/vmap(...)"), or
    # a scope alone around the op's own location.
    scopes = {part for name in re.findall(r'loc\("([^"]*)"', text)
              for part in name.split("/")}
    assert set(SCOPES) <= scopes, set(SCOPES) - scopes
