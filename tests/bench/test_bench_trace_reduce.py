"""Trace reduction: by hand on made-up events, and on a trace the profiler
records here on the CPU."""

import pytest

from bench import trace_reduce as tr


def ev(name, s, e, **stats):
    return (name, float(s), float(e), stats)


OPS = [ev("a", 0, 10, hlo_module="jit_f"), ev("b", 5, 20, hlo_module="jit_f"),
       ev("c", 30, 40, hlo_module="jit_g"), ev("fused_decode_x", 50, 55),
       ev("d", 52, 60, hlo_module="jit_g")]


def test_union_busy_and_gaps_by_hand():
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    # busy in [0, 100]: [0,20] + [30,40] + [50,60] = 40
    assert tr.busy_ns(OPS, 0, 100) == 40
    # clipped to [15, 55]: [15,20] + [30,40] + [50,55] = 20
    assert tr.busy_ns(OPS, 15, 55) == 20
    assert tr.gaps(OPS, 0, 100) == [(20, 30), (40, 50), (60, 100)]
    assert tr.gaps(OPS, 25, 45) == [(25, 30), (40, 45)]


def test_time_per_program():
    trace = tr.Trace({"d0": OPS}, {}, [])
    # no module line: programs from the ops' hlo_module stat
    assert tr.module_time(trace, "d0", 0, 100) == {"jit_f": 25.0,
                                                   "jit_g": 18.0}
    mods = [ev("jit_megastep(12)", 0, 20), ev("jit_prefill_step(3)", 30, 40),
            ev("jit_megastep(12)", 50, 60)]
    trace = tr.Trace({"d0": OPS}, {"d0": mods}, [])
    assert tr.module_time(trace, "d0", 0, 55) == {"jit_megastep": 25.0,
                                                  "jit_prefill_step": 10.0}


def test_idle_gaps_by_host_span():
    host = [ev("engine.step", 0, 45), ev("client.poll", 45, 49),
            ev("client.wait", 49, 100), ev("outer", 0, 100)]
    trace = tr.Trace({"d0": OPS}, {}, host)
    idle = tr.idle_by_host(trace, "d0", 0, 100)
    # gaps (20,30) mid 25 -> engine.step; (40,50) mid 45 -> client.poll;
    # (60,100) mid 80 -> client.wait
    assert idle == {"engine.step": 10.0, "client.poll": 10.0,
                    "client.wait": 40.0}
    assert tr.top(idle, 2, scale=1.0) == [["client.wait", 40.0],
                                          ["engine.step", 10.0]]


def test_self_time_subtracts_nested_ops():
    evs = [ev("%while.1 = (f32[2])", 0, 100), ev("%fusion.2 = f32[4]", 10, 30),
           ev("%fusion.3 = f32[4]", 40, 50), ev("%copy.4 = f32[4]", 120, 130)]
    assert tr.self_time(evs, 0, 200) == {"%while.1 = (f32[2])": 70.0,
                                         "%fusion.2 = f32[4]": 20.0,
                                         "%fusion.3 = f32[4]": 10.0,
                                         "%copy.4 = f32[4]": 10.0}
    assert sum(tr.self_time(evs, 0, 200).values()) == tr.busy_ns(evs, 0, 200)
    assert tr.self_time(evs, 25, 45) == {"%while.1 = (f32[2])": 10.0,
                                         "%fusion.2 = f32[4]": 5.0,
                                         "%fusion.3 = f32[4]": 5.0}
    assert tr.op_label("%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(%a)") \
        == "%fusion.7 = f32[8,128]"


def test_program_name():
    assert tr.program_name("jit_megastep(1234)") == "jit_megastep"
    assert tr.program_name("jit_f") == "jit_f"


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here: the XLA threads of the host plane stand in
    for a device.  Busy time, per-program sums and the annotated span are
    checked against sums taken directly over the events."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: jnp.cos(x) + 1.0)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            f(x).block_until_ready()
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    trace = tr.load(path, device_plane=lambda n: n == "/host:CPU",
                    op_line=lambda n: n.startswith("tf_XLA"),
                    module_line=lambda n: False)
    ops = [e for dev in trace.ops.values() for e in dev
           if e[3].get("hlo_module")]
    assert ops, "the CPU trace holds XLA op events"
    span = [h for h in trace.host if h[0] == "bench.traced"]
    assert len(span) == 1
    lo, hi = span[0][1], span[0][2]
    inside = [e for e in ops if e[1] >= lo and e[2] <= hi]
    assert inside
    t = tr.Trace({"cpu": ops}, {}, trace.host)
    busy = tr.busy_ns(ops, lo, hi)
    assert 0 < busy <= hi - lo
    assert busy <= sum(e[2] - e[1] for e in inside) + 1e-6
    per = tr.module_time(t, "cpu", lo, hi)
    by_hand = {}
    for name, s, e, st in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by_hand[st["hlo_module"]] = by_hand.get(st["hlo_module"], 0) + e - s
    assert per == pytest.approx(by_hand)
    assert {k for k in per if k.startswith("jit_")} >= {"jit__lambda"}
    gaps = tr.gaps(ops, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
