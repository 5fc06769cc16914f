"""The program's own host spans in a trace: the ``repro.*`` spans that
the program under test writes at the boundaries of its layers (its
``repro.spans`` module), on the device trace's clock.

For one direction's passes (``ctx.windows[direction]``): the share of
pass time a kind of span covers, how many of a kind start per pass, and
the share of the device's idle time in the passes that no ``repro.*``
span covers. A span is matched by its name, or by its name followed by
``#``, where the profiler keeps a span's metadata in its name. Every
reader returns ``None`` when the passes hold no ``repro.*`` span at
all: that trace comes from a program without spans, where 0 would be
false.

The names are the program's, written out here: the benchmark reads
them from the trace and imports nothing of the program for it.
"""

from __future__ import annotations

import bisect
from collections import Counter
from typing import List, Optional, Sequence

from benchmarks.chip import trace_reduce
from benchmarks.chip.trace_reduce import Event, Interval

PREFIX = "repro."
FORWARD = "repro.forward"
LOWER = "repro.lower"
HOST_READ = "repro.host_read"


def named(name: str, span: str) -> bool:
    """Whether an event called ``name`` is a ``span``."""
    return name == span or name.startswith(span + "#")


def minus(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the union of ``a`` that the union of ``b`` leaves
    uncovered; sorted and disjoint."""
    out: List[Interval] = []
    b = trace_reduce.union(b)
    j = 0
    for s, e in trace_reduce.union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def _passes(ctx, direction: str) -> List[Interval]:
    return ctx.trace.spans(ctx.windows[direction])


def program_spans(ctx, direction: str) -> Optional[List[Event]]:
    """The ``repro.*`` host spans that overlap the direction's passes,
    or ``None`` where there are none."""
    win = trace_reduce.union(_passes(ctx, direction))
    ends = [e for _, e in win]
    found = []
    for ev in ctx.trace.host:
        if not ev.name.startswith(PREFIX):
            continue
        k = bisect.bisect_right(ends, ev.start_ns)
        if k < len(win) and win[k][0] < ev.end_ns:
            found.append(ev)
    return found or None


def share(ctx, direction: str, span: str) -> Optional[float]:
    """100 x the pass time inside the union of ``span`` spans, over the
    passes' time."""
    found = program_spans(ctx, direction)
    if found is None:
        return None
    win = _passes(ctx, direction)
    kind = [(e.start_ns, e.end_ns) for e in found if named(e.name, span)]
    return 100.0 * trace_reduce.overlap(kind, win) / \
        trace_reduce.length(win)


def per_pass(ctx, direction: str, span: str) -> Optional[float]:
    """``span`` spans that start inside the passes, per pass."""
    found = program_spans(ctx, direction)
    if found is None:
        return None
    win = trace_reduce.union(_passes(ctx, direction))
    starts = [s for s, _ in win]
    n = 0
    for e in found:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if named(e.name, span) and k >= 0 and e.start_ns < win[k][1]:
            n += 1
    return n / len(_passes(ctx, direction))


def idle_unattributed(ctx, direction: str) -> Optional[float]:
    """100 x the device's idle time in the passes (pass time minus the
    union of the ops of the device that ran the most) that no
    ``repro.*`` span covers, over that idle time; ``None`` without
    spans or without device ops. The device is chosen by its ops, not
    by name: a trace may hold device planes that run none."""
    found = program_spans(ctx, direction)
    if found is None or not ctx.trace.ops:
        return None
    counts = Counter(e.plane for e in ctx.trace.ops)
    dev = max(sorted(counts), key=counts.__getitem__)
    busy = [(e.start_ns, e.end_ns) for e in ctx.trace.ops
            if e.plane == dev]
    idle = minus(_passes(ctx, direction), busy)
    idle_ns = trace_reduce.length(idle)
    if idle_ns <= 0:
        return 0.0
    left = minus(idle, [(e.start_ns, e.end_ns) for e in found])
    return 100.0 * trace_reduce.length(left) / idle_ns
