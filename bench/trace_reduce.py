"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
time per program and per operation, and the host spans in idle gaps.

An *op event* is one operation that ran on the device: by default an event
on the ``XLA Ops`` line of a ``/device:TPU:<n>`` plane.  A *module event* is
one run of a compiled program (the ``XLA Modules`` line); where a trace has
no module line, an op's program is its ``hlo_module`` stat.  Host spans are
the events of the host plane's threads (``jax.profiler.TraceAnnotation``
names among them).  All times are nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

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


def _events(line, want_stats: bool) -> List[Event]:
    out = []
    for e in line.events:
        stats = dict(e.stats) if want_stats else {}
        out.append((e.name, float(e.start_ns),
                    float(e.start_ns) + float(e.duration_ns), stats))
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
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in data.planes:
        if device_plane(plane.name):
            for line in plane.lines:
                if op_line(line.name):
                    ops[plane.name] += _events(line, want_stats=True)
                elif module_line(line.name):
                    modules[plane.name] += _events(line, want_stats=False)
        if plane.name == host_plane:
            for line in plane.lines:
                if host_line(line.name):
                    host += _events(line, want_stats=False)
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


def self_time(events: List[Event], lo: float, hi: float
              ) -> Dict[str, float]:
    """Time inside [lo, hi] of each op name less the time of the ops nested
    in it (a loop op holds its body's ops on the same line), so that no
    time counts twice."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []           # [end, name, child time]

    def close(item):
        end, name, start, child = item
        out[name] += max(0.0, end - start - child)

    for name, s, e, _ in sorted(((n, max(s, lo), min(e, hi), st)
                                 for n, s, e, st in events
                                 if min(e, hi) > max(s, lo)),
                                key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


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
