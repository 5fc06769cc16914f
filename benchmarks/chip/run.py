#!/usr/bin/env python3
"""Entry point of the on-chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload archive-vae-bernoulli \
        --seed 7 --seconds 10 --trace 0

See ``harness.py``. The checkout's own ``src/`` (the program) and its
root (this package) come first on the import path.
"""

import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

if __name__ == "__main__":
    # The TPU runtime's logs go inside the checkout, not to /tmp.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(ROOT, ".bench_cache", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("benchmark: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        sys.exit(2)
    from benchmarks.chip import harness
    sys.exit(harness.run(sys.argv[1:], t0=T0))
