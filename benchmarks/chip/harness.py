"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; the traffic's runner
runs set-up, the measured window and the comparison with the plain
reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window and reports its per-layer metrics,
each read by ``metrics/<name>.py``. The last line of standard output
is the result; the numbers compared, each beside its limit, are also
the last lines of standard error. Without a TPU, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types
from typing import Dict, List, Optional

from benchmarks.chip import registry, stalls, trace_reduce

CACHE_DIR = os.path.join(registry.ROOT, ".jax_cache")
#: Weights trained on the first run, and the trace of a traced run.
CACHE_ROOT = os.path.join(registry.ROOT, ".bench_cache")
#: JAX's persistent-cache threshold: programs that took less than this
#: to compile are not written. JAX's default of 1 s leaves the float
#: path's ~230 small programs out, and a warm run then compiles them
#: again; at 0 s a warm run's set-up was 8-9 s shorter on a TPU v5e.
CACHE_MIN_COMPILE_S = 0.0


def log(msg: str) -> None:
    print(msg, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def place_compile_cache() -> None:
    """The persistent compile cache at a fixed path in the checkout,
    whatever the environment says, before anything compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_S)


def find_chips(need: int, platform: str = "tpu"):
    """The devices, or an error message when there is no TPU or too
    few chips for the cell."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return None, f"no accelerator found ({e})"
    if devices[0].platform != platform:
        return None, (f"no TPU found: JAX runs on {devices[0].platform!r}; "
                      "the benchmark measures the chip only")
    if len(devices) < need:
        return None, (f"the cell needs {need} chips, JAX sees "
                      f"{len(devices)}")
    return devices, None


def layer_metrics(bench: dict, workload: str, lctx) -> Dict[str, dict]:
    out = {}
    for m in registry.metrics_for(bench, workload, "per_layer"):
        value = registry.metric_reader(m["name"])(lctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(argv=None, *, t0: Optional[float] = None, platform: str = "tpu",
        control: Optional[str] = None, overrides: Optional[dict] = None,
        cache_root: str = CACHE_ROOT, compile_cache: bool = True) -> int:
    """One run of one cell.

    The keywords serve the benchmark's tests and its control run:
    ``platform="cpu"`` drives a run on the CPU; ``control`` puts the
    reference, with matmul inputs rounded to that dtype, in the place
    of the program's forward; ``overrides`` replaces keys of the
    ``config`` or ``traffic`` (smaller sizes for a test);
    ``cache_root`` holds the weights and the trace; ``compile_cache``
    off leaves JAX's persistent cache as it is.
    """
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    for part, d in (overrides or {}).items():
        {"config": config, "traffic": traffic}[part].update(d)

    if compile_cache:
        place_compile_cache()
    devices, err = find_chips(cell["chips"], platform)
    if err:
        print(f"benchmark: {err}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    try:
        peaks = registry.peaks(kind) if platform == "tpu" else {}
    except registry.UnknownDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    log(f"device: {kind} x{len(devices)} ({devices[0].platform}); "
        f"workload {args.workload}, seed {args.seed}, window "
        f"{args.seconds:g} s, trace {args.trace}"
        + (f"; compile cache {CACHE_DIR} (threshold "
           f"{CACHE_MIN_COMPILE_S:g} s)" if compile_cache else ""))

    backends = stalls.record_backends()
    ctx = types.SimpleNamespace(
        t0=t0, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        trace_dir=os.path.join(cache_root, "trace"), traffic=traffic,
        log=log, control=control,
        weights_cache=os.path.join(cache_root, "params"),
        system=registry.part("systems", config["system"]).System(config),
        reference=registry.part("references", config["reference"]),
        compiles=stalls.CompileMonitor(), grows=stalls.count_grows(),
        gc=stalls.GCPauses())
    runner = registry.part("runners", traffic["runner"])
    try:
        res = runner.run(ctx)
    except Exception as e:            # a fault in the program under test
        import traceback
        traceback.print_exc()
        error = f"{type(e).__name__}: {e}"[:300]
        print(f"check run_error: {error} (limit: none)", file=sys.stderr,
              flush=True)
        print(json.dumps({"correct": False, "attempted": 0, "failed": 1,
                          "metrics": {}, "device": device,
                          "checks": {"run_error": {"value": error,
                                                   "limit": "none"}}}),
              flush=True)
        return 0
    log("backends: " + ", ".join(f"{op} -> {'/'.join(sorted(b))}"
                                 for op, b in sorted(backends.items())))

    limits = config["limits"]
    checks = {name: {"value": value, "limit": limits[name]}
              for name, value in res["checks"]}
    correct = (not res["errors"]
               and all(c["value"] <= c["limit"] for c in checks.values()))
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": {}, "device": device}
    if args.trace:
        trace = trace_reduce.read(_xplane(res["trace_dir"]))
        lctx = types.SimpleNamespace(trace=trace, config=config,
                                     peaks=peaks, traffic=traffic,
                                     **res["layer"])
        windows = [iv for name in lctx.windows.values()
                   for iv in trace.spans(name)]
        busy = trace_reduce.busy_ns(trace, windows) * 1e-9
        window_s = trace_reduce.length(windows) * 1e-9
        device["busy_s"] = busy
        device["window_s"] = window_s
        result["metrics"] = layer_metrics(bench, args.workload, lctx)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace, windows),
            "idle_gaps": trace_reduce.idle_gaps(
                trace, windows, list(lctx.windows.values()))}
        log(f"trace: {len(trace.ops)} device ops on "
            f"{len(trace.devices)} device(s), {len(trace.host)} host spans; "
            f"busy {busy:.4f} s of {window_s:.4f} s in the passes")
        runner.cleanup(ctx)
    else:
        for m in registry.metrics_for(bench, args.workload, "end_to_end"):
            if m["name"] in res["e2e"]:
                result["metrics"][m["name"]] = {
                    "value": res["e2e"][m["name"]], "unit": m["unit"]}
    result["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _xplane(trace_dir: str) -> str:
    found: List[str] = []
    for dirpath, _, files in os.walk(trace_dir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)
