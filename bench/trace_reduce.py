"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
time per program and per operation, and the host spans in idle gaps.

An *op event* is one operation that ran on the device: by default an event
on the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane.  A *module event* is
one run of a compiled program (the ``XLA Modules`` line); where a trace has
no module line, an op's program is its ``hlo_module`` stat.  Host spans are
the events of the host plane's threads (``jax.profiler.TraceAnnotation``
names among them).  All times are nanoseconds on the trace's one clock.

An op event's stats also hold its ``tf_op`` where its event metadata has
one: the model scope path of the operation (``jax.named_scope``), such as
``jit(megastep)/while/body/closed_call/head/jit(_pallas)/pallas_call:``.
``jax.profiler.ProfileData`` does not expose metadata stats, so the file is
read as an ``XSpace`` protobuf; every event, name, time and stat comes out
as ``ProfileData`` gives it.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import importlib.util
import os
import re
from collections import defaultdict
from typing import (Callable, Dict, Iterable, Iterator, List, Optional,
                    Tuple)

Event = Tuple[str, float, float, dict]   # name, start, end, stats


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]       # device -> op events
    modules: Dict[str, List[Event]]   # device -> module events
    host: List[Event]                 # host spans


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@functools.lru_cache(maxsize=None)
def xplane_pb2():
    """The ``XSpace`` protobuf module that ships with TensorFlow's profiler,
    loaded by its path so that TensorFlow itself is not imported."""
    spec = importlib.util.find_spec("tensorflow")
    path = (os.path.join(spec.submodule_search_locations[0], "tsl",
                         "profiler", "protobuf", "xplane_pb2.py")
            if spec and spec.submodule_search_locations else "")
    if not os.path.isfile(path):
        raise FileNotFoundError("no tensorflow/tsl/profiler/protobuf/"
                                "xplane_pb2.py among the installed packages")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class _Plane:
    """Names of one ``XPlane``'s event and stat metadata, and the value of
    each stat as ``ProfileData`` gives it."""

    def __init__(self, plane):
        self.plane = plane
        self.stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
        self.event_names = {k: m.name
                            for k, m in plane.event_metadata.items()}

    def stat(self, st) -> Tuple[Optional[str], object]:
        kind = st.WhichOneof("value")
        name = self.stat_names.get(st.metadata_id)
        if kind is None or name is None:
            return None, None
        if kind == "ref_value":
            return name, self.stat_names.get(st.ref_value, "")
        if kind == "bytes_value":
            return name, st.bytes_value.decode()
        return name, getattr(st, kind)

    @functools.cached_property
    def tf_ops(self) -> Dict[int, str]:
        """Event metadata id -> its ``tf_op`` stat.  Only that stat is read:
        others, such as an op's serialized HLO, hold bytes that are no
        text."""
        out = {}
        for k, m in self.plane.event_metadata.items():
            for st in m.stats:
                if self.stat_names.get(st.metadata_id) == "tf_op":
                    out[k] = self.stat(st)[1]
        return out

    def events(self, line, want_stats: bool) -> List[Event]:
        """The line's events; with ``want_stats``, each one's stats and its
        ``tf_op``.  Times are whole ns, as the profiler's own reader gives
        them."""
        out, t0 = [], line.timestamp_ns
        for e in line.events:
            stats = {}
            if want_stats:
                stats = dict(self.stat(st) for st in e.stats)
                if e.metadata_id in self.tf_ops:
                    stats.setdefault("tf_op", self.tf_ops[e.metadata_id])
            start = float(t0 + e.offset_ps // 1000)
            out.append((self.event_names.get(e.metadata_id, ""), start,
                        start + float(e.duration_ps // 1000), stats))
        return out


def tpu_device(plane_name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane_name) is not None


def load(path: str,
         device_plane: Callable[[str], bool] = tpu_device,
         op_line: Callable[[str], bool] = lambda n: n == "XLA Ops",
         module_line: Callable[[str], bool] = lambda n: n == "XLA Modules",
         host_plane: str = "/host:CPU",
         host_line: Callable[[str], bool] = lambda n: True) -> Trace:
    """Read the trace at ``path`` (an ``.xplane.pb``)."""
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for xplane in space.planes:
        if not (device_plane(xplane.name) or xplane.name == host_plane):
            continue
        plane = _Plane(xplane)
        if device_plane(xplane.name):
            for line in xplane.lines:
                if op_line(line.name):
                    ops[xplane.name] += plane.events(line, want_stats=True)
                elif module_line(line.name):
                    modules[xplane.name] += plane.events(line,
                                                         want_stats=False)
        if xplane.name == host_plane:
            for line in xplane.lines:
                if host_line(line.name):
                    host += plane.events(line, want_stats=False)
    return Trace(dict(ops), dict(modules), host)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one of ``events`` runs."""
    return sum(e - s for s, e in clip(union((s, e) for _, s, e, _ in events),
                                      lo, hi))


def gaps(events: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between ``events``."""
    out, t = [], lo
    for s, e in clip(union((s, e) for _, s, e, _ in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: List[Event], lo: float, hi: float
               ) -> Iterator[Tuple[Event, float]]:
    """Each event that overlaps [lo, hi] with its time inside it less the
    time of the events nested in it (a loop op holds its body's ops on the
    same line), so that no time counts twice; in the order they close."""
    stack: List[list] = []           # [end, event, start, child time]

    def close(item):
        end, ev, start, child = item
        return ev, max(0.0, end - start - child)

    for s, e, ev in sorted(((max(ev[1], lo), min(ev[2], hi), ev)
                            for ev in events
                            if min(ev[2], hi) > max(ev[1], lo)),
                           key=lambda c: (c[0], -c[1])):
        while stack and stack[-1][0] <= s:
            yield close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, ev, s, 0.0])
    while stack:
        yield close(stack.pop())


def self_time(events: List[Event], lo: float, hi: float
              ) -> Dict[str, float]:
    """Self time inside [lo, hi] (``self_times``) by op name."""
    out: Dict[str, float] = defaultdict(float)
    for ev, ns in self_times(events, lo, hi):
        out[ev[0]] += ns
    return dict(out)


def scope_path(tf_op: str) -> List[str]:
    """``jit(megastep)/while/body/head/dot_general:`` -> ``['jit(megastep)',
    'while', 'body', 'head', 'dot_general']``."""
    parts = tf_op.split("/")
    parts[-1] = parts[-1].split(":", 1)[0]
    return parts


def scope_time(ops: List[Event], modules: List[Event], lo: float, hi: float
               ) -> Dict[str, Dict[str, float]]:
    """Self time inside [lo, hi] of each program's ops by each component of
    their ``tf_op`` path: an op under ``.../body/head/...`` counts once
    toward ``body`` and once toward ``head``.  An op's program is the module
    event that holds its start; an op outside every module event, or with no
    ``tf_op``, counts nowhere."""
    runs = sorted((s, e, program_name(name)) for name, s, e, _ in modules)
    starts = [r[0] for r in runs]

    def program(ev):
        i = bisect.bisect_right(starts, ev[1]) - 1
        return runs[i][2] if i >= 0 and ev[1] < runs[i][1] else None

    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for ev, ns in self_times(ops, lo, hi):
        prog, tf_op = program(ev), ev[3].get("tf_op")
        if prog is None or tf_op is None:
            continue
        for part in dict.fromkeys(scope_path(tf_op)):
            out[prog][part] += ns
    return {prog: dict(parts) for prog, parts in out.items()}


def op_label(name: str) -> str:
    """``%fusion.7 = f32[8,128]{1,0} fusion(...)`` -> ``%fusion.7 =
    f32[8,128]``: the op and its result's shape."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:120]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} = {shape}"[:120]


def program_name(name: str) -> str:
    """``jit_megastep(123)`` -> ``jit_megastep``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def time_by(events: List[Event], key: Callable[[Event], Optional[str]],
            lo: float, hi: float) -> Dict[str, float]:
    """Summed durations inside [lo, hi] per ``key(event)`` (None skips)."""
    out: Dict[str, float] = defaultdict(float)
    for ev in events:
        k = key(ev)
        if k is None:
            continue
        s, e = max(ev[1], lo), min(ev[2], hi)
        if e > s:
            out[k] += e - s
    return dict(out)


def module_time(trace: Trace, device: str, lo: float, hi: float
                ) -> Dict[str, float]:
    """Device time per program: from module events, or else from each op's
    ``hlo_module`` stat."""
    if trace.modules.get(device):
        return time_by(trace.modules[device],
                       lambda ev: program_name(ev[0]), lo, hi)
    return time_by(trace.ops.get(device, []),
                   lambda ev: ev[3].get("hlo_module"), lo, hi)


def host_at(host: List[Event], t: float) -> str:
    """The innermost host span that covers time ``t``."""
    best, width = "host.other", float("inf")
    for name, s, e, _ in host:
        if s <= t < e and e - s < width:
            best, width = name, e - s
    return best


def idle_by_host(trace: Trace, device: str, lo: float, hi: float,
                 spans: Optional[List[Event]] = None) -> Dict[str, float]:
    """Idle device time in [lo, hi], by the host span at each gap's middle."""
    spans = trace.host if spans is None else spans
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps(trace.ops.get(device, []), lo, hi):
        out[host_at(spans, (s + e) / 2)] += e - s
    return dict(out)


def top(d: Dict[str, float], n: int = 10, scale: float = 1e-9):
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]
