"""The control of the comparison that decides ``correct``.

The configurations state exact counts over int32 timestamps in seconds.
The control is the plain reference put in the program's place and run one
precision below, on float32 timestamps: ties and the ``delta`` edge then
round, which a later change might be tempted into.  Its answers go through
the same comparison as the program's and must come out not correct.

    python3 bench/control.py --workload <name> --seed <n> [--seed <n> ...]

prints, per seed, the numbers compared and whether the control passed.
"""

import argparse
import json
import os
import sys

import numpy as np

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0:1] = [_root, os.path.join(_root, "src")]


def control_counts(reference, graph, *, delta: int, l_max: int) -> dict:
    return reference.count_codes(graph.u, graph.v,
                                 graph.t.astype(np.float32),
                                 delta=delta, l_max=l_max)


def readings(cell, seed: int) -> dict:
    from bench import check, harness
    from bench.drivers.common import variant

    graph = variant(harness.make_graph(cell), 0, seed)
    params = harness.paper_params(cell.config)
    want = cell.reference.count_codes(graph.u, graph.v, graph.t, **params)
    got = control_counts(cell.reference, graph, **params)
    numbers, _ = check.compare([got], want)
    return {"seed": seed, "check": numbers, "passed": check.passed(numbers)}


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    for seed in args.seed:
        print(json.dumps({"workload": args.workload,
                          **readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
