"""What can stall a run, counted so that a stall is attributed instead
of averaged in: compiles and persistent-cache hits, the coder's
grow-and-retry attempts, garbage-collector pauses, the backend each
hot coder op resolved to, and for each measured pass the process's
CPU time, context switches and page faults, with samples of where the
main thread was while the pass ran.

``CompileMonitor`` and ``record_backends`` are copies of the ones in
``chip_smoke.py``; the benchmark keeps its own.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import os
import resource
import statistics
import sys
import threading
import time
from typing import Dict, List, Set


class CompileMonitor:
    """Backend compiles (their seconds and count) and persistent-cache
    hits, from ``jax.monitoring``; ``snapshot()`` reads the totals."""

    _TRACE = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.seconds = 0.0          # tracing, lowering and compiling
        self.backend_seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self._BACKEND:
            self.backend_seconds += duration
            self.programs += 1
        if event in self._TRACE or event == self._BACKEND:
            self.seconds += duration

    def _event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds,
                "backend_compile_s": self.backend_seconds,
                "programs": self.programs, "cache_hits": self.cache_hits}


def record_backends() -> Dict[str, Set[str]]:
    """Record every ``dispatch.resolve`` answer of the run: op -> the
    set of backends it resolved to."""
    from repro.kernels import dispatch

    seen: Dict[str, Set[str]] = {}
    resolve = dispatch.resolve

    def recording(op, *args, **kwargs):
        decision = resolve(op, *args, **kwargs)
        seen.setdefault(op, set()).add(decision.backend)
        return decision

    dispatch.resolve = recording
    return seen


def count_grows() -> List[int]:
    """Count the stream coder's grow-and-retry attempts (a block redone
    with more capacity after an overflow or underflow)."""
    from repro.stream import coder

    grows = [0]
    grow = coder.StreamEncoder._grow

    def counting(self, *args, **kwargs):
        grows[0] += 1
        return grow(self, *args, **kwargs)

    coder.StreamEncoder._grow = counting
    return grows


class GCPauses:
    """Garbage-collector pauses, through ``gc.callbacks``."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def mark(self) -> int:
        return len(self.pauses)

    def since(self, mark: int) -> List[float]:
        return self.pauses[mark:]


def _where(frame) -> str:
    """The innermost frame, and the innermost one in the program
    (``repro/``) where that is another: ``file:line function``."""
    def name(f):
        co = f.f_code
        parts = co.co_filename.split(os.sep)
        return f"{'/'.join(parts[-2:])}:{f.f_lineno} {co.co_name}"

    inner = name(frame)
    f = frame
    while f is not None and f"{os.sep}repro{os.sep}" not in \
            f.f_code.co_filename:
        f = f.f_back
    if f is None or f is frame:
        return inner
    return f"{inner} <- {name(f)}"


class PassWatch:
    """Watch each measured pass: its wall and CPU seconds, context
    switches and page faults (``getrusage``), and, sampled every
    ``interval`` seconds by a daemon thread while the pass runs, where
    the main thread was. ``report()`` names the passes far slower than
    their direction's median and what was different in them."""

    def __init__(self, interval: float = 0.1):
        self.interval = interval
        self.passes: List[dict] = []
        self._main = threading.get_ident()
        self._current = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True,
                                        name="bench-pass-sampler")
        self._thread.start()

    def _sample(self) -> None:
        while not self._stop.wait(self.interval):
            rec = self._current
            frame = sys._current_frames().get(self._main)
            if rec is not None and frame is not None:
                rec["where"][_where(frame)] += 1
            del frame

    @contextlib.contextmanager
    def watch(self, phase: str):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        rec = {"phase": phase, "where": collections.Counter()}
        c0, t0 = time.process_time(), time.perf_counter()
        self._current = rec
        try:
            yield rec
        finally:
            self._current = None
            rec["wall"] = time.perf_counter() - t0
            rec["cpu"] = time.process_time() - c0
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            for k in ("ru_nvcsw", "ru_nivcsw", "ru_majflt", "ru_minflt"):
                rec[k] = getattr(ru1, k) - getattr(ru, k)
            self.passes.append(rec)

    def close(self) -> None:
        self._stop.set()
        self._thread.join()

    def report(self, factor: float = 1.25, min_excess_s: float = 0.5
               ) -> List[str]:
        """One line for each pass that took ``factor`` times its
        direction's median and ``min_excess_s`` more, beside that
        median pass: CPU seconds, switches, faults, and the places the
        sampler found the main thread in more often than in a median
        pass (samples x interval = seconds)."""
        lines = []
        for phase in sorted({p["phase"] for p in self.passes}):
            ps = [p for p in self.passes if p["phase"] == phase]
            med = {k: statistics.median(p[k] for p in ps)
                   for k in ("wall", "cpu", "ru_nvcsw", "ru_nivcsw",
                             "ru_majflt", "ru_minflt")}
            for i, p in enumerate(ps):
                if p["wall"] < max(factor * med["wall"],
                                   med["wall"] + min_excess_s):
                    continue
                base = {w: statistics.median(q["where"].get(w, 0)
                                             for q in ps)
                        for w in p["where"]}
                extra = sorted(((n - base[w], w) for w, n in
                                p["where"].items() if n > base[w]),
                               reverse=True)[:3]
                lines.append(
                    f"stalls: slow {phase} pass {i}: {p['wall']:.3f} s "
                    f"(median {med['wall']:.3f}); CPU {p['cpu']:.3f} s "
                    f"(median {med['cpu']:.3f}); voluntary/involuntary "
                    f"switches {p['ru_nvcsw']}/{p['ru_nivcsw']} (median "
                    f"{med['ru_nvcsw']:g}/{med['ru_nivcsw']:g}); major/"
                    f"minor faults {p['ru_majflt']}/{p['ru_minflt']} "
                    f"(median {med['ru_majflt']:g}/{med['ru_minflt']:g}); "
                    "main thread beyond the median pass: "
                    + ("; ".join(f"{n * self.interval:.1f} s in {w}"
                                 for n, w in extra) or "nowhere"))
        return lines
