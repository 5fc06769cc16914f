"""Measured lane-tiling/unroll autotuner with a persisted decision cache.

The dispatcher (``kernels.dispatch``) resolves backends by pure lookup;
this module is the part that actually *times* candidates. For one
(op, platform, lane-bucket, table-bucket) signature it builds a
representative workload, runs every legal candidate Decision through
the public op (so the measurement includes padding, compaction and
bookkeeping, not just the kernel), keeps the fastest, and persists it
to a versioned JSON cache:

  * location: ``$REPRO_TUNING_CACHE`` if set, else
    ``~/.cache/repro/tuning_cache.json``;
  * format: ``{"version": 1, "entries": {"<platform>/<op>/lanes<B>/``
    ``table<B>": {"backend": ..., "lane_tile": ..., "unroll": ...,``
    ``"ms": ...}}}`` with lane/table counts bucketed to the next power
    of two so one measurement covers a size class;
  * a corrupt, unreadable, or version-mismatched cache file is treated
    as empty (and overwritten on the next ``record``) - tuning state
    can never break coding.

Nothing here runs implicitly: ``codecs.compile`` only *measures* at
lowering when ``REPRO_AUTOTUNE`` is set (see ``ensure``); otherwise a
cache miss falls back to the dispatch heuristic. Candidates never
include ``interpret`` - it exists as an oracle, not a contender - and
the cache neither records nor serves it.

CLI: ``python -m repro.kernels.tuning --lanes 64 --steps 256`` warms
the cache for every hot op and prints the winning decisions.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import (DEFAULT_LANE_TILE, Decision,
                                    available_backends, platform)

CACHE_VERSION = 1
_ENV_CACHE = "REPRO_TUNING_CACHE"
_ENV_AUTOTUNE = "REPRO_AUTOTUNE"

# Hot ops the CLI sweep covers, with the workload knobs they use.
OPS = ("push_many", "push_many_table", "pop_many", "pop_many_dyn",
       "pop_many_grid", "bucketize")

_MEM: Optional[Dict[str, dict]] = None
_MEM_PATH: Optional[str] = None


def cache_path() -> str:
    """The tuning-cache file location (env override or XDG default)."""
    env = os.environ.get(_ENV_CACHE)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "tuning_cache.json")


# Public alias under the package namespace (repro.kernels exports it).
def tuning_cache_path() -> str:
    """Alias of :func:`cache_path` for the ``repro.kernels`` surface."""
    return cache_path()


def refresh() -> None:
    """Drop the in-process cache view (tests use this after swapping
    ``$REPRO_TUNING_CACHE``)."""
    global _MEM, _MEM_PATH
    _MEM = None
    _MEM_PATH = None


def _load() -> Dict[str, dict]:
    """The cache's entries dict; corrupt/stale files read as empty."""
    global _MEM, _MEM_PATH
    path = cache_path()
    if _MEM is not None and _MEM_PATH == path:
        return _MEM
    entries: Dict[str, dict] = {}
    try:
        with open(path, "r", encoding="utf-8") as fh:
            raw = json.load(fh)
        if isinstance(raw, dict) and raw.get("version") == CACHE_VERSION \
                and isinstance(raw.get("entries"), dict):
            entries = raw["entries"]
    except (OSError, ValueError):
        pass   # missing or corrupt: start empty, never fail coding
    _MEM, _MEM_PATH = entries, path
    return entries


def _save(entries: Dict[str, dict]) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump({"version": CACHE_VERSION, "entries": entries}, fh,
                  indent=1, sort_keys=True)
    os.replace(tmp, path)


def _bucket(n: Optional[int]) -> int:
    """Next power of two >= n (0 for unknown): one measurement per size
    class instead of per exact shape."""
    if not n or n <= 0:
        return 0
    b = 1
    while b < n:
        b <<= 1
    return b


def _key(plat: str, op: str, lanes: Optional[int],
         table_size: Optional[int]) -> str:
    return f"{plat}/{op}/lanes{_bucket(lanes)}/table{_bucket(table_size)}"


def lookup(plat: str, op: str, lanes: Optional[int] = None,
           table_size: Optional[int] = None) -> Optional[Decision]:
    """The cached Decision for this signature, or None on miss. Entries
    naming ``interpret`` or a backend unavailable on ``plat`` (or
    malformed entries) are ignored rather than raised."""
    entry = _load().get(_key(plat, op, lanes, table_size))
    if not isinstance(entry, dict):
        return None
    try:
        decision = Decision(
            backend=str(entry["backend"]),
            lane_tile=int(entry.get("lane_tile", DEFAULT_LANE_TILE)),
            unroll=int(entry.get("unroll", 1)))
    except (KeyError, TypeError, ValueError):
        return None
    if decision.backend == "interpret" \
            or decision.backend not in available_backends(plat):
        return None
    return decision


def record(plat: str, op: str, decision: Decision, ms: float,
           lanes: Optional[int] = None,
           table_size: Optional[int] = None) -> None:
    """Persist a measured winner (atomic write, updates the in-process
    view). ``interpret`` is refused: it is never a measured winner."""
    if decision.backend == "interpret":
        raise ValueError("kernels.tuning: the interpret backend is an "
                         "oracle and is never recorded as a winner")
    entries = _load()
    entries[_key(plat, op, lanes, table_size)] = {
        "backend": decision.backend,
        "lane_tile": decision.lane_tile,
        "unroll": decision.unroll,
        "ms": round(ms, 4),
    }
    _save(entries)


def candidates(plat: Optional[str] = None) -> List[Decision]:
    """The Decisions worth timing on ``plat``: compiled-pallas tilings
    on accelerators, unroll factors for the XLA twins everywhere.
    ``interpret`` is excluded - it is the oracle, never a contender."""
    p = plat if plat is not None else platform()
    out: List[Decision] = []
    if "pallas" in available_backends(p):
        for tile in (DEFAULT_LANE_TILE, 2 * DEFAULT_LANE_TILE):
            out.append(Decision("pallas", lane_tile=tile))
    for unroll in (1, 2, 4):
        out.append(Decision("xla", unroll=unroll))
    return out


def _time_ms(fn, reps: int = 3) -> float:
    """Best-of-``reps`` wall time of ``fn`` in ms, compile excluded."""
    jax.block_until_ready(fn())   # warmup: compile outside the clock
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1000.0


def _workload(op: str, lanes: int, steps: int, table_size: int,
              lat_bits: int, precision: int):
    """A representative closure for ``op``: (callable taking a Decision)
    built once, so every candidate times identical inputs."""
    from repro.core import ans
    from repro.kernels.ans import ops as ans_ops
    from repro.kernels.bucketize import ops as bucketize_ops

    key = jax.random.PRNGKey(0)
    half = jnp.uint32(1 << (precision - 1))

    if op == "push_many":
        stack = ans.make_stack(lanes, capacity=4 * steps)
        starts = jnp.zeros((steps, lanes), jnp.uint32)
        freqs = jnp.full((steps, lanes), half, jnp.uint32)
        return lambda d: ans_ops.push_many(stack, starts, freqs,
                                           precision, backend=d)

    if op == "push_many_table":
        stack = ans.make_stack(lanes, capacity=4 * steps)
        table = _uniform_table(lanes, table_size, precision)
        syms = jax.random.randint(key, (steps, lanes), 0, table_size)
        return lambda d: ans_ops.push_many_table(stack, table, syms,
                                                 precision, backend=d)

    if op in ("pop_many", "pop_many_dyn"):
        stack = ans.seed_stack(ans.make_stack(lanes, capacity=4 * steps),
                               key, n_chunks=2 * steps)
        table = _uniform_table(lanes, table_size, precision)
        if op == "pop_many":
            return lambda d: ans_ops.pop_many(stack, table, steps,
                                              precision, backend=d)
        tables = jnp.broadcast_to(table, (steps,) + table.shape)
        return lambda d: ans_ops.pop_many_dyn(stack, tables, precision,
                                              backend=d)

    if op == "pop_many_grid":
        stack = ans.seed_stack(ans.make_stack(lanes, capacity=4 * steps),
                               key, n_chunks=2 * steps)
        mu = jnp.zeros((steps, lanes), jnp.float32)
        sigma = jnp.ones((steps, lanes), jnp.float32)
        return lambda d: ans_ops.pop_many_grid(
            stack, "gaussian", mu, sigma, steps, lat_bits, precision,
            backend=d)

    if op == "bucketize":
        slot = jax.random.randint(
            key, (lanes,), 0, 1 << precision).astype(jnp.uint32)
        mu = jnp.zeros((lanes,), jnp.float32)
        sigma = jnp.ones((lanes,), jnp.float32)
        return lambda d: bucketize_ops.bucketize(
            slot, mu, sigma, lat_bits, precision, backend=d)

    raise ValueError(f"kernels.tuning: unknown op {op!r} "
                     f"(expected one of {OPS})")


def _uniform_table(lanes: int, table_size: int, precision: int):
    with jax.ensure_compile_time_eval():
        edges = jnp.linspace(0, 1 << precision, table_size + 1)
        table = jnp.round(edges).astype(jnp.uint32)
    return jnp.broadcast_to(table, (lanes, table_size + 1))


def autotune_op(op: str, lanes: int, steps: int = 256,
                table_size: int = 16, lat_bits: int = 6,
                precision: int = 14, reps: int = 3) -> Decision:
    """Time every candidate for ``op`` on a representative workload,
    persist the winner, and return it. A candidate that fails to
    compile or run raises: every candidate is a supported path."""
    plat = platform()
    tsize = table_size if op in ("push_many_table", "pop_many",
                                 "pop_many_dyn") else None
    fn = _workload(op, lanes, steps, table_size, lat_bits, precision)
    timed = [(_time_ms(lambda d=d: fn(d), reps=reps), i, d)
             for i, d in enumerate(candidates(plat))]
    best_ms, _, best = min(timed)
    record(plat, op, best, best_ms, lanes=lanes, table_size=tsize)
    return best


def ensure(op: str, lanes: Optional[int] = None,
           table_size: Optional[int] = None, steps: int = 256,
           lat_bits: int = 6, precision: int = 14) -> Optional[Decision]:
    """The lowering-time hook ``codecs.compile`` calls: a cached
    Decision if one exists; measure-and-cache if ``$REPRO_AUTOTUNE`` is
    set; otherwise None (heuristic applies)."""
    cached = lookup(platform(), op, lanes=lanes, table_size=table_size)
    if cached is not None:
        return cached
    if not os.environ.get(_ENV_AUTOTUNE):
        return None
    return autotune_op(op, lanes=lanes or 16, steps=steps,
                       table_size=table_size or 16, lat_bits=lat_bits,
                       precision=precision)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="Warm the kernel tuning cache: time every candidate "
                    "backend per hot op and persist the winners.")
    parser.add_argument("--ops", nargs="*", default=list(OPS),
                        help="ops to tune (default: all hot ops)")
    parser.add_argument("--lanes", type=int, nargs="+", default=[64],
                        help="lane counts to tune (one cache entry per "
                             "power-of-two lane bucket)")
    parser.add_argument("--steps", type=int, default=256)
    parser.add_argument("--table-size", type=int, default=16)
    parser.add_argument("--lat-bits", type=int, default=6)
    parser.add_argument("--precision", type=int, default=14)
    parser.add_argument("--reps", type=int, default=3)
    args = parser.parse_args(argv)

    print(f"platform={platform()}  cache={cache_path()}")
    for lanes in args.lanes:
        if len(args.lanes) > 1:
            print(f"lanes={lanes}:")
        for op in args.ops:
            decision = autotune_op(
                op, lanes=lanes, steps=args.steps,
                table_size=args.table_size, lat_bits=args.lat_bits,
                precision=args.precision, reps=args.reps)
            print(f"  {op:16s} -> {decision.backend}"
                  f"(lane_tile={decision.lane_tile}, "
                  f"unroll={decision.unroll})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
