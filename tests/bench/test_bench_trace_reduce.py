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


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace recorded here, with an annotated span: its ``.xplane.pb``."""
    import jax
    import jax.numpy as jnp

    log_dir = tmp_path_factory.mktemp("cpu_trace")
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: jnp.cos(x) + 1.0)
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    g(x).block_until_ready()
    jax.profiler.start_trace(str(log_dir))
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            f(x).block_until_ready()
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.find_xplane(str(log_dir))


# The XLA threads of the host plane stand in for a device.
CPU_LINES = dict(device_plane=lambda n: n == "/host:CPU",
                 op_line=lambda n: n.startswith("tf_XLA"),
                 module_line=lambda n: False)


def test_recorded_cpu_trace(cpu_trace):
    """Busy time, per-program sums and the annotated span are checked
    against sums taken directly over the events."""
    trace = tr.load(cpu_trace, **CPU_LINES)
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


def profile_data_load(path, device_plane=tr.tpu_device,
                      op_line=lambda n: n == "XLA Ops",
                      module_line=lambda n: n == "XLA Modules"):
    """The op, module and host events as ``jax.profiler.ProfileData`` gives
    them (the reader ``load`` replaced, which could not see a scope)."""
    from jax.profiler import ProfileData

    def events(line, want_stats):
        return [(e.name, float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns),
                 dict(e.stats) if want_stats else {}) for e in line.events]

    ops, modules, host = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        if device_plane(plane.name):
            for line in plane.lines:
                if op_line(line.name):
                    ops.setdefault(plane.name, []).extend(events(line, True))
                elif module_line(line.name):
                    modules.setdefault(plane.name, []).extend(
                        events(line, False))
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += events(line, False)
    return tr.Trace(ops, modules, host)


def test_loader_reads_what_profile_data_reads(cpu_trace):
    """Every op, module and host event of a recorded trace, with its name,
    times and stats, equals what ``ProfileData`` gives; so every metric and
    the breakdown read as they did through ``ProfileData``."""
    for lines in (CPU_LINES, {}):
        got, want = tr.load(cpu_trace, **lines), profile_data_load(
            cpu_trace, **lines)
        assert got == want
    assert sum(map(len, got.ops.values())) == 0 < len(got.host)
    assert sum(map(len, tr.load(cpu_trace, **CPU_LINES).ops.values())) > 0


# A hand-made device trace (times in ps, read as whole ns):
#   jit_megastep [0, 100): %while.1 [0, 90) holds the head's dot [10, 30),
#     a mixer op [40, 50), a mixer op inside a nested loop [60, 80) and an
#     op with no scope [85, 87) (85.4 to 88.3 ns: offset and duration are
#     truncated each);
#   jit_prefill [150, 200): one head op [150, 190).
TF_OPS = {
    "%while.1 = (f32[2])": "jit(megastep)/while:",
    "%fusion.2 = f32[4,8]": "jit(megastep)/while/body/head/dot_general:",
    "%fusion.3 = f32[4]": "jit(megastep)/while/body/closed_call/mixer/mul:",
    "%add.4 = f32[4]": "jit(megastep)/while/body/mixer/while/body/add:",
    "%copy.5 = f32[4]": None,
    "%fusion.9 = f32[2,8]": "jit(prefill)/head/dot_general:",
}
OP_PS = [("%while.1 = (f32[2])", 0, 90_000),
         ("%fusion.2 = f32[4,8]", 10_000, 30_000),
         ("%fusion.3 = f32[4]", 40_000, 50_000),
         ("%add.4 = f32[4]", 60_000, 80_000),
         ("%copy.5 = f32[4]", 85_400, 88_300),
         ("%fusion.9 = f32[2,8]", 150_000, 190_000)]


def write_xspace(path, t0=1_000_000):
    """Write the hand-made trace, as the profiler lays one out: names,
    ``tf_op`` and serialized HLO (bytes that are no text) in the event
    metadata, times as ps offsets from a line's start; a host span
    ``bench.traced`` over all of it."""
    pb = tr.xplane_pb2()
    space = pb.XSpace()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    for i, name in enumerate(("tf_op", "device_duration_ps", "hlo_proto"),
                             start=1):
        dev.stat_metadata[i].CopyFrom(pb.XStatMetadata(id=i, name=name))
    names = ["jit_megastep(7)", "jit_prefill(3)"] + list(TF_OPS)
    for i, name in enumerate(names, start=1):
        md = dev.event_metadata[i]
        md.CopyFrom(pb.XEventMetadata(id=i, name=name))
        md.stats.add(metadata_id=3, bytes_value=b"\x80\x01\xff")
        if TF_OPS.get(name):
            md.stats.add(metadata_id=1, str_value=TF_OPS[name])
    mid = {name: i for i, name in enumerate(names, start=1)}
    mods = dev.lines.add(id=1, name="XLA Modules", timestamp_ns=t0)
    for name, s, e in (("jit_megastep(7)", 0, 100),
                       ("jit_prefill(3)", 150, 200)):
        mods.events.add(metadata_id=mid[name], offset_ps=s * 1000,
                        duration_ps=(e - s) * 1000)
    ops = dev.lines.add(id=2, name="XLA Ops", timestamp_ns=t0)
    for name, s, e in OP_PS:
        ev = ops.events.add(metadata_id=mid[name], offset_ps=s,
                            duration_ps=e - s)
        ev.stats.add(metadata_id=2, uint64_value=e - s)
    host = space.planes.add(id=2, name="/host:CPU")
    host.event_metadata[1].CopyFrom(pb.XEventMetadata(id=1,
                                                      name="bench.traced"))
    line = host.lines.add(id=1, name="python3", timestamp_ns=t0)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=300_000)
    path.parent.mkdir(parents=True)
    path.write_bytes(space.SerializeToString())


def test_scope_self_time_by_hand(tmp_path):
    from bench.harness import reduce_trace
    from bench.metrics_common import scope_ns

    path = tmp_path / "plugins" / "profile" / "1" / "t.xplane.pb"
    write_xspace(path)
    trace = tr.load(str(path))
    # the scope joins the op's stats; every other event reads as before
    assert {e[0]: e[3].get("tf_op") for e in trace.ops["/device:TPU:0"]} \
        == TF_OPS
    bare = tr.Trace(
        {d: [(n, s, e, {k: v for k, v in st.items() if k != "tf_op"})
             for n, s, e, st in evs] for d, evs in trace.ops.items()},
        trace.modules, trace.host)
    assert bare == profile_data_load(str(path))

    dev = reduce_trace(str(tmp_path))["devices"]["/device:TPU:0"]
    # self times: the loop 90 - 20 - 10 - 20 - 2 = 38; head 20; mixer 10
    # and 20; the op with no scope counts nowhere
    assert dev["scopes"] == {
        "jit_megastep": {"jit(megastep)": 88.0, "while": 88.0, "body": 50.0,
                         "head": 20.0, "dot_general": 20.0,
                         "closed_call": 10.0, "mixer": 30.0, "mul": 10.0,
                         "add": 20.0},
        "jit_prefill": {"jit(prefill)": 40.0, "head": 40.0,
                        "dot_general": 40.0}}
    assert scope_ns(dev, "jit_megastep", "head") == 20.0
    assert scope_ns(dev, "jit_megastep", "ffn") == 0.0
    assert scope_ns(dev, "jit_spec_megastep", "head") == 0.0
    assert scope_ns(None, "jit_megastep", "head") == 0.0
    # the scopes cover the self time that the breakdown counts
    assert sum(dev["ops"].values()) == 40.0 + 90.0
    assert dev["modules"] == {"jit_megastep": 100.0, "jit_prefill": 50.0}


def test_scope_path():
    assert tr.scope_path("jit(megastep)/while/body/closed_call/head/"
                         "jit(_pallas)/pallas_call:") == [
        "jit(megastep)", "while", "body", "closed_call", "head",
        "jit(_pallas)", "pallas_call"]
    assert tr.scope_path("jit(f)/mixer/dot_general:DotGeneral") == [
        "jit(f)", "mixer", "dot_general"]
