"""Benchmark harness entry point - one function per paper table/figure
plus the framework's own perf benches. Prints ``name,...`` CSV lines
and, next to them, writes a machine-readable ``BENCH_<name>.json`` per
bench (rows + wall time) so the perf trajectory can be tracked across
commits; CI uploads the JSON files as artifacts.

Full runs: PYTHONPATH=src python -m benchmarks.run
Quick run: PYTHONPATH=src python -m benchmarks.run --quick
One bench: PYTHONPATH=src python -m benchmarks.run --only stream_throughput
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def _write_json(out_dir: str, name: str, payload: dict) -> str:
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced image counts / training steps")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<name>.json files")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable_compile_cache()

    from benchmarks import (ablation_cleanbits, ans_throughput,
                            codec_compile, dataset_rate, fig3_chain,
                            hvae_rate, latent_lm_gain, lm_compression,
                            loadgen, stream_throughput, table2_rates,
                            table3_predict)

    q = args.quick
    benches = {
        "table2": lambda: table2_rates.run(
            n_images=128 if q else 512, train_steps=400 if q else 2500),
        "fig3": lambda: fig3_chain.run(
            n_images=128 if q else 480, train_steps=300 if q else 1200)[0],
        "table3": lambda: table3_predict.run(
            train_steps=300 if q else 1500, n_images=64 if q else 256),
        "ablation": lambda: ablation_cleanbits.run(
            train_steps=300 if q else 1000, n_images=64 if q else 128),
        "ans_throughput": lambda: ans_throughput.run(
            lanes=128 if q else 256, steps=64 if q else 256),
        "codec_compile": lambda: codec_compile.run(
            lanes=4 if q else 8, n_chain=2 if q else 4,
            hw=8 if q else 12),
        "lm_compression": lambda: lm_compression.run(
            train_steps=120 if q else 250),
        "latent_lm_gain": lambda: latent_lm_gain.run(
            train_steps=120 if q else 300),
        "hvae_rate": lambda: hvae_rate.run(
            train_steps=400 if q else 1500, n_images=32 if q else 128),
        "stream": lambda: stream_throughput.run(
            lanes=64 if q else 128, n_symbols=1024 if q else 4096,
            block=128 if q else 512, n_images=64 if q else 256,
            vae_lanes=16 if q else 32,
            train_steps=300 if q else 1500),
        "dataset_rate": lambda: dataset_rate.run(
            train_steps=300 if q else 1500,
            n_images=256 if q else 2048),
        "loadgen": lambda: loadgen.run(
            clients=4 if q else 8, block_symbols=8 if q else 16,
            max_blocks=3 if q else 5)
        + loadgen.run_cluster(
            clients=4 if q else 6, block_symbols=8 if q else 16,
            max_blocks=3 if q else 5),
    }
    # historical/module aliases for --only (e.g. CI's stream_throughput)
    aliases = {"stream_throughput": "stream", "table2_rates": "table2",
               "table3_predict": "table3"}
    only = aliases.get(args.only, args.only)
    if only and only not in benches:
        print(f"unknown bench {args.only!r}; choose from "
              f"{sorted(benches)}", file=sys.stderr)
        sys.exit(2)

    failures = 0
    for name, fn in benches.items():
        if only and name != only:
            continue
        t0 = time.time()
        try:
            rows = fn()
            dt = time.time() - t0
            us = dt * 1e6 / max(len(rows), 1)
            for row in rows:
                if isinstance(row, dict):
                    payload = ",".join(
                        f"{k}={v:.4f}" if isinstance(v, float) else
                        f"{k}={v}" for k, v in row.items())
                else:
                    payload = ",".join(
                        f"{v:.4f}" if isinstance(v, float) else str(v)
                        for v in row)
                print(f"{name},{us:.0f},{payload}", flush=True)
            path = _write_json(args.json_dir, name, {
                "bench": name, "quick": q, "elapsed_s": dt,
                "rows": [row if isinstance(row, dict)
                         else {"values": list(row)} for row in rows],
            })
            print(f"{name},json,{path}", flush=True)
        except Exception:
            failures += 1
            dt = time.time() - t0
            print(f"{name},FAILED", flush=True)
            traceback.print_exc()
            _write_json(args.json_dir, name, {
                "bench": name, "quick": q, "elapsed_s": dt,
                "failed": True, "error": traceback.format_exc(),
            })
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
