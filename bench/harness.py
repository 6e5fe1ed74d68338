"""One run of one cell: set-up, warm-up, the measured window, the output
check and the metrics.  ``run.py`` is its command line."""

from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import check, manifest, trace_reduce
from bench.client import Client
from bench.peaks import peaks
from bench.traffic import Schedule, seed_key

TRACE_SECONDS = 10.0    # the traced end of a --trace 1 window
FILL_SECONDS = 120.0    # the most a backlog's fill may take


class NoChip(SystemExit):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts backend compiles, their seconds and persistent-cache hits
    (``jax.monitoring`` events)."""

    def __init__(self):
        from jax import monitoring

        self.n = self.seconds = self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
                     f"{dev.platform} device(s) ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def use_compile_cache(jax) -> str:
    """The program's persistent compilation cache
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``), keeping every program however
    small or fast to compile."""
    from repro.launch.compile_cache import use_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    architecture ``arch`` with every field the file also states replaced
    (a nested group, such as ``attention``, field by field), checked
    against the file afterwards."""
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    fields = {}
    for f in dataclasses.fields(base):
        if f.name == "name" or f.name not in cfg:
            continue
        value, now = cfg[f.name], getattr(base, f.name)
        if isinstance(value, dict):
            if not dataclasses.is_dataclass(now):
                raise ValueError(f"{cfg['name']}: {cfg['arch']} has no "
                                 f"{f.name} group to set")
            value = dataclasses.replace(now, **value)
        elif isinstance(value, list):
            value = tuple(value)
        fields[f.name] = value
    pc = dataclasses.replace(base, **fields)
    for k, v in fields.items():
        if getattr(pc, k) != v:
            raise ValueError(f"{cfg['name']}: {k} runs as {getattr(pc, k)}, "
                             f"the file states {v}")
    return pc


def build(jax, cfg: dict, ref, seed: int):
    """Weights and head from the seed, each in one jitted call on the
    device, in the types they are served in."""
    from repro.api.heads import DenseHead, SketchHead
    from repro.models.config import SketchHeadConfig

    from bench.reference import sketch as sketch_ref

    key = jax.random.PRNGKey(seed_key(seed))
    kw, kh = jax.random.split(key)
    params = jax.jit(lambda k: ref.make_params(k, cfg))(kw)
    spec = cfg["head"]
    if spec["kind"] == "dense":
        return params, DenseHead(), None
    arrays = jax.jit(lambda k, t: sketch_ref.make_head(k, t, spec))(
        kh, ref.unembed(params))
    head_cfg = SketchHeadConfig(
        n_rows=spec["n_rows"], n_buckets=spec["n_buckets"], k=spec["k"],
        proj_dim=spec["proj_dim"], bandwidth=spec["bandwidth"])
    head = SketchHead(cfg=head_cfg, backend=spec["backend"],
                      quant=spec["quant"], params=arrays)
    return params, head, arrays


def warm_up(engine, sched: Schedule, chunk: int, vocab: int) -> None:
    """Run every prefill shape (G <= admit_per_tick, P in buckets) and every
    megastep length 1..chunk once through the engine itself."""
    rng = np.random.default_rng(0)
    for p in sched.buckets:
        for g in range(1, sched.admit_per_tick + 1):
            for _ in range(g):
                engine.submit(rng.integers(0, vocab, p, dtype=np.int32), 1,
                              arrival=engine.now)
            engine.step()
    for k in range(1, chunk + 1):
        engine.submit(rng.integers(0, vocab, min(sched.buckets),
                                   dtype=np.int32), k + 1, arrival=engine.now)
        engine.step()
    while engine.sched.n_active or len(engine.queue):
        engine.step()


def timeline(steps, window, parts: int = 4) -> str:
    """How the window went: engine steps, their wall times, the share of
    the window spent inside them, and the tokens of each of ``parts``
    equal spans (a stall shows in one span, a slower chip in all)."""
    lo, hi = window
    steps = [s for s in steps if lo <= s[0] and s[1] <= hi]
    if not steps:
        return "no engine step in the window"
    dur = np.array([e - s for s, e, _ in steps])
    edges = np.linspace(lo, hi, parts + 1)
    toks = np.histogram([e for _, e, _ in steps], edges,
                        weights=[n for _, _, n in steps])[0]
    return (f"{len(steps)} steps, wall ms median {1e3 * np.median(dur):.1f} "
            f"p90 {1e3 * np.percentile(dur, 90):.1f} max "
            f"{1e3 * dur.max():.1f}, {100 * dur.sum() / (hi - lo):.1f} % of "
            f"the window inside steps; tokens per quarter "
            f"{[int(t) for t in toks]}")


class GcPauses:
    """Python garbage collections and their wall times, by generation
    (``gc.callbacks``): a full collection in a process holding JAX can
    stall a step."""

    def __init__(self):
        self.pauses = []            # (generation, seconds)
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def summary(self, since: int = 0) -> str:
        p = self.pauses[since:]
        full = [t for g, t in p if g == 2]
        return (f"{len(p)} collections, {len(full)} full; longest "
                f"{1e3 * max((t for _, t in p), default=0):.1f} ms, full "
                f"{1e3 * sum(full):.1f} ms in all")


def peak_bytes(jax) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]
    vals = [v for v in vals if v is not None]
    return int(max(vals)) if vals else None


@dataclasses.dataclass
class RunView:
    """What a metric reader may read."""
    cfg: dict
    mix: dict
    ref: object                   # the family's reference module
    records: list
    window: tuple                 # (start, end) clock times
    counters: tuple               # counters at the start and the end
    setup_s: float
    peaks: Optional[dict]
    trace: Optional[dict] = None  # see ``reduce_trace``

    def delta(self, key: str) -> float:
        return self.counters[1][key] - self.counters[0][key]


def reduce_trace(log_dir: str) -> dict:
    """Busy and idle device time, per-program time, kernel events and
    per-program time by model scope of the traced span (the
    ``bench.traced`` host span)."""
    tr = trace_reduce.load(trace_reduce.find_xplane(log_dir))
    spans = [h for h in tr.host if h[0].startswith(("bench.", "client.",
                                                    "engine."))]
    mark = [h for h in spans if h[0] == "bench.traced"]
    if not mark or not tr.ops:
        return {}
    lo, hi = mark[0][1], mark[0][2]
    per_dev = {}
    for dev in sorted(tr.ops):
        ops = tr.ops[dev]
        per_dev[dev] = {
            "busy_ns": trace_reduce.busy_ns(ops, lo, hi),
            "modules": trace_reduce.module_time(tr, dev, lo, hi),
            "module_events": [ev for ev in tr.modules.get(dev, [])
                              if lo <= ev[1] < hi],
            "op_events": [ev for ev in ops if lo <= ev[1] < hi],
            "ops": trace_reduce.self_time(
                [(trace_reduce.op_label(n), s, e, st)
                 for n, s, e, st in ops], lo, hi),
            "idle": trace_reduce.idle_by_host(tr, dev, lo, hi, spans),
            "scopes": trace_reduce.scope_time(ops, tr.modules.get(dev, []),
                                              lo, hi),
        }
    return {"lo": lo, "hi": hi, "window_ns": hi - lo, "devices": per_dev}


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             cache: bool = True, control: bool = False,
             keep_trace: Optional[str] = None) -> dict:
    """One run; returns the result line's dict.

    ``control`` also reads the gaps of each control the cell's check
    file names (the reference in a lower precision, in the program's
    place) on the same sample, and judges each by the check file's own
    limits, for setting those limits: benchmark runs never do.
    ``keep_trace`` copies the trace directory there before it is reduced
    and deleted."""
    cell = manifest.find_cell(workload, root)
    cfg, mix, ref = cell["config"], cell["traffic"], cell["reference"]
    import jax

    dev = device_info(jax, cell["cell"]["chips"], require_tpu)
    chip_peaks = peaks(dev["kind"]) if dev["platform"] == "tpu" else None
    if cache:
        log(f"compile cache {use_compile_cache(jax)}")
    counter = CompileCounter()
    from repro.api.lm import LM

    pc = program_config(cfg)
    params, head, head_arrays = build(jax, cfg, ref, seed)
    lm = LM(params, pc, head)
    engine = lm.engine(mix["slots"], mix["max_seq"],
                       decode_chunk=cfg["decode_chunk"])
    sched = Schedule(mix, seed, cfg["vocab_size"])
    warm_up(engine, sched, cfg["decode_chunk"], cfg["vocab_size"])
    annotate = jax.profiler.TraceAnnotation if trace else None
    client = Client(engine, sched, annotate=annotate)
    if mix["arrival"] == "backlog":
        client.fill(time.perf_counter() + FILL_SECONDS)
    jax.block_until_ready(engine.pool)
    compiles0, hits0 = counter.n, counter.cache_hits
    pauses = GcPauses()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {counter.n} compiles "
        f"({counter.seconds:.1f}s), {counter.cache_hits} cache hits")

    tdir, traced = None, None
    if trace:
        # The traced span is the window's end, when admissions and
        # retirements have reached their steady rate.
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        span = min(seconds, TRACE_SECONDS)
        if seconds > span:
            client.run(seconds - span)
        jax.profiler.start_trace(tdir)
        c0 = client.counters()
        with jax.profiler.TraceAnnotation("bench.traced"):
            t0, t1 = client.run(span)
        c1 = client.counters()
        jax.profiler.stop_trace()
        window, counters = (t0, t1), (c0, c1)
    else:
        c0 = client.counters()
        window = client.run(seconds)
        counters = (c0, client.counters())
    in_window = counter.n - compiles0
    log(f"compiles inside the window: {in_window}")
    log(f"window: {timeline(client.steps, window)}; programs loaded "
        f"from the cache {counter.cache_hits - hits0}; garbage collector "
        f"{pauses.summary()}")
    gc.callbacks.remove(pauses._on)
    mem = peak_bytes(jax)

    records = list(client.records.values())
    served = {r.rid: list(engine.outputs[r.rid][:r.n]) for r in records
              if r.n > 0}
    # Free the program's state before the reference runs.
    engine.pool = None
    del engine, lm, client
    if trace:
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        t = time.perf_counter()
        traced = reduce_trace(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.1f}s")

    spec = cell["check"]
    picks = check.pick(records, seed)
    failed = sum(1 for r in records
                 if r.last_t is not None and len(served[r.rid]) != r.max_new)
    attempted = sum(1 for r in records if r.submit_t is not None)
    limits = spec["limits"]
    controls = tuple(spec.get("controls", ("int8",))) if control else ()
    result_check = {"tokens": 0, "requests": 0}
    if picks:
        t = time.perf_counter()
        prompts = [sched.tokens(r.idx) for r in picks]
        result_check = check.gaps(ref, params, cfg, head_arrays, prompts,
                                  [served[r.rid] for r in picks], controls)
        log(f"reference over {result_check['tokens']} served tokens of "
            f"{len(picks)} requests (longest {picks[0].prompt_len} + "
            f"{picks[0].n}) in {time.perf_counter() - t:.1f}s (hiddens "
            f"{result_check['hidden_s']:.1f}s, heads "
            f"{result_check['heads_s']:.1f}s)")
    if picks and spec.get("first_tokens"):
        t = time.perf_counter()
        firsts = check.pick_first(records, seed, int(spec["first_tokens"]))
        result_check.update(check.first_gaps(
            ref, params, cfg, [sched.tokens(r.idx) for r in firsts],
            [served[r.rid][0] for r in firsts], controls))
        log(f"reference over the first tokens of {len(firsts)} requests "
            f"in {time.perf_counter() - t:.1f}s")

    def judge(prefix=""):
        got = {n: result_check.get(prefix + n) for n in limits}
        ok = all(v is not None and np.isfinite(v) and v <= limits[n]
                 for n, v in got.items())
        return ok, {n: v if v is None or np.isfinite(v) else str(v)
                    for n, v in got.items()}

    numbers_ok, compared = judge()
    correct = bool(picks) and failed == 0 and in_window == 0 and numbers_ok

    view = RunView(cfg, mix, ref, records, window, counters,
                   setup_s, chip_peaks, traced)
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        value = cell["readers"][m["name"]].read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev["memory_peak_bytes"] = mem
    if trace and traced:
        busy = [d["busy_ns"] for d in traced["devices"].values()]
        dev["busy_s"] = float(np.mean(busy)) * 1e-9
        dev["window_s"] = traced["window_ns"] * 1e-9
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if trace and traced:
        first = next(iter(traced["devices"].values()))
        out["breakdown"] = {
            "device_ops": trace_reduce.top(first["ops"]),
            "idle_gaps": trace_reduce.top(first["idle"])}
    out["check"] = {
        **{n: {"value": v, "limit": limits[n]} for n, v in compared.items()},
        "compiles_in_window": {"value": in_window, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "sampled_tokens": result_check["tokens"],
        "sampled_requests": result_check["requests"]}
    if control:
        out["check"]["readings"] = result_check
        out["check"]["control_correct"] = {c: judge(f"{c}_")[0]
                                           for c in controls}
    return out


def print_result(out: dict) -> None:
    chk = out["check"]
    for name, item in chk.items():
        if isinstance(item, dict) and "limit" in item:
            log(f"check {name}: {item['value']} (limit {item['limit']})")
    log(f"check sampled: {chk['sampled_requests']} requests, "
        f"{chk['sampled_tokens']} served tokens; correct={out['correct']}")
    print(json.dumps(out), flush=True)
