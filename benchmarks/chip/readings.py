#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds in one
process: the program's, or a control's. Two controls: ``--control
float8_e4m3fn`` puts the reference forward with lower-precision matmul
inputs in the program's place; ``--set obs_precision=12`` (any integer
key of the configuration) runs the program's own coder below the
precision the configuration states. The limits in
``configs/<name>.json`` are set from these readings. The benchmark's
own runs never run a control.

    python3 benchmarks/chip/readings.py --workload archive-vae-bernoulli \
        --seeds 11,12,13 [--control float8_e4m3fn] [--set KEY=N] \
        [--seconds 1]

Each run prints its result line as ``run.py`` does; the last line is a
summary: each number's largest reading over the seeds.
"""

import argparse
import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main() -> int:
    from benchmarks.chip import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=N")
    ap.add_argument("--seconds", default="1")
    args = ap.parse_args()
    changed = {k: int(v) for k, v in (kv.split("=") for kv in args.set)}
    label = args.control or ",".join(args.set) or None
    worst: dict = {}
    for seed in args.seeds.split(","):
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = harness.run(["--workload", args.workload, "--seed", seed,
                              "--seconds", args.seconds, "--trace", "0"],
                             t0=time.perf_counter(), control=args.control,
                             overrides={"config": changed})
        lines = buf.getvalue().strip().splitlines()
        if rc != 0 or not lines:
            print(f"seed {seed}: rc {rc}", flush=True)
            return rc or 1
        res = json.loads(lines[-1])
        print(json.dumps({"seed": int(seed), "control": label,
                          "correct": res["correct"],
                          "checks": {k: v["value"] for k, v in
                                     res["checks"].items()},
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
        for k, v in res["checks"].items():
            if isinstance(v["value"], (int, float)):
                worst[k] = max(worst.get(k, v["value"]), v["value"])
    print(json.dumps({"workload": args.workload, "control": label,
                      "largest": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
