"""From a profiler trace to numbers: device busy time as the union of
the device's op intervals, the idle share of a window, the device time
of the ops or programs whose names match a pattern, the device ops that
took the most time, and the longest idle gaps by what the host was
doing in them.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.
ProfileData``. Device planes are named ``/device:<KIND>:<n>``; their
``XLA Ops`` line holds the ops and their ``XLA Modules`` line the
programs. Host planes (``/host:...``) hold the host's spans, the
benchmark's own ``bench.*`` annotations among them, on the same clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    plane: str
    line: str


@dataclasses.dataclass
class Trace:
    ops: List[Event]          # device ops, every device
    modules: List[Event]      # device programs, every device
    host: List[Event]         # host spans
    devices: List[str]        # device plane names

    def spans(self, name: str) -> List[Interval]:
        """The intervals of the host spans called ``name``."""
        return sorted((e.start_ns, e.end_ns) for e in self.host
                      if e.name == name)


def from_profile(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` -> ``Trace``."""
    ops, modules, host, devices = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            devices.append(plane.name)
            lines = {ln.name: ln for ln in plane.lines}
            for line_name, out in ((OPS_LINE, ops), (MODULES_LINE, modules)):
                if line_name in lines:
                    out.extend(Event(e.name, e.start_ns, e.end_ns,
                                     plane.name, line_name)
                               for e in lines[line_name].events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.end_ns,
                                  plane.name, ln.name)
                            for e in ln.events if e.end_ns > e.start_ns)
    return Trace(ops, modules, host, sorted(set(devices)))


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(intervals: Sequence[Interval], windows: Sequence[Interval]
            ) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = union(intervals), union(windows)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def length(windows: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(windows))


def busy_ns(trace: Trace, windows: Sequence[Interval]) -> float:
    """Device busy time inside ``windows``, averaged over the devices
    that ran ops: the union of each device's op intervals."""
    per: Dict[str, list] = defaultdict(list)
    for e in trace.ops:
        per[e.plane].append((e.start_ns, e.end_ns))
    if not per:
        return 0.0
    return sum(overlap(iv, windows) for iv in per.values()) / len(per)


def idle_percent(trace: Trace, windows: Sequence[Interval]
                 ) -> Optional[float]:
    """100 x (1 - busy / window); ``None`` when there is no window or
    no device op in it."""
    span = length(windows)
    busy = busy_ns(trace, windows)
    if span <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / span)


def matching(events: Iterable[Event], patterns: Sequence[str]
             ) -> List[Event]:
    rx = re.compile("|".join(f"(?:{p})" for p in patterns))
    return [e for e in events if rx.search(e.name)]


def device_seconds(events: Iterable[Event], windows: Sequence[Interval]
                   ) -> float:
    """Summed device time of ``events`` inside ``windows`` (per device
    the union, so nested or overlapping events count once)."""
    per: Dict[str, list] = defaultdict(list)
    for e in events:
        per[e.plane].append((e.start_ns, e.end_ns))
    return sum(overlap(iv, windows) for iv in per.values()) * 1e-9


def short(name: str) -> str:
    """An op or program name without its HLO text or fingerprint:
    ``%fusion.3 = u32[...] fusion(...)`` -> ``%fusion.3``,
    ``jit_pop_many_dyn(3081...)`` -> ``jit_pop_many_dyn``."""
    name = name.split(" = ", 1)[0]
    head, _, tail = name.partition("(")
    return head if tail.rstrip(")").isdigit() else name


def top_ops(trace: Trace, windows: Sequence[Interval], n: int = 10
            ) -> List[list]:
    """The ``n`` op names with the most device seconds in ``windows``,
    summed over devices, each as ``[program/op, seconds]``."""
    by: Dict[str, float] = defaultdict(float)
    win = union(windows)
    mods = defaultdict(list)
    for m in trace.modules:
        mods[m.plane].append(m)
    for lst in mods.values():
        lst.sort(key=lambda m: m.start_ns)
    starts = {p: [m.start_ns for m in lst] for p, lst in mods.items()}
    for e in trace.ops:
        t = overlap([(e.start_ns, e.end_ns)], win)
        if t <= 0:
            continue
        name = short(e.name)
        lst = mods.get(e.plane)
        if lst:
            k = bisect.bisect_right(starts[e.plane], e.start_ns) - 1
            if k >= 0 and lst[k].end_ns >= e.end_ns:
                name = f"{short(lst[k].name)}/{name}"
        by[name] += t * 1e-9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, windows: Sequence[Interval],
              window_names: Sequence[str], n: int = 10) -> List[list]:
    """The device's idle gaps inside ``windows`` (first device), named
    by what the benchmark's own thread was doing at each gap's middle:
    the outermost span on the host line that holds the window spans
    (named ``window_names``), or the window's own name where the thread
    ran Python between calls. Summed by name, the ``n`` largest as
    ``[name, seconds]``."""
    if not trace.devices:
        return []
    dev = trace.devices[0]
    busy = union((e.start_ns, e.end_ns) for e in trace.ops
                 if e.plane == dev)
    gaps: List[Interval] = []
    for ws, we in union(windows):
        t = ws
        for bs, be in busy:
            if be <= ws or bs >= we:
                continue
            if bs > t:
                gaps.append((t, bs))
            t = max(t, be)
        if we > t:
            gaps.append((t, we))
    own = [e for e in trace.host if e.name in window_names]
    lines = {(e.plane, e.line) for e in own}
    # Spans on one thread nest, so the outermost ones are disjoint.
    top: List[Event] = []
    for e in sorted((e for e in trace.host if (e.plane, e.line) in lines
                     and e.name not in window_names),
                    key=lambda e: (e.start_ns, -e.end_ns)):
        if not top or e.start_ns >= top[-1].end_ns:
            top.append(e)
    starts = [e.start_ns for e in top]
    own.sort(key=lambda e: e.start_ns)
    own_starts = [e.start_ns for e in own]
    by: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        k = bisect.bisect_right(starts, mid) - 1
        if k >= 0 and top[k].end_ns >= mid:
            name = top[k].name
        else:
            j = bisect.bisect_right(own_starts, mid) - 1
            name = own[j].name if j >= 0 else "(no host span)"
        by[name] += (e - s) * 1e-9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
