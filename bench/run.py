"""Run one cell of the benchmark once (see bench/harness.py).

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], t0=T0, root=ROOT))
