"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9] [--smoke]
        [--out-json BENCH_mining.json]

Prints ``name,us_per_call,derived`` CSV rows.  ``--smoke`` runs suites that
support it (a ``run(smoke=...)`` signature) at tiny sizes — the CI mode that
catches suite-registry breakage without paying full benchmark cost.

``--out-json FILE`` additionally collects structured payloads from suites
exposing ``run_json`` (mining: edges/sec + peak-memory estimates; roofline:
ragged-sweep bandwidth; serving: multi-tenant latency + config-lattice
co-mine comparison).  Payloads merge into an existing file by suite name,
so ``BENCH_*.json`` accumulates across invocations instead of clobbering;
each invocation also appends a timestamped entry to a bounded ``history``
list (suite names + argv + the per-suite payloads), so a perf regression
can be traced to the run that introduced it instead of being silently
overwritten by the latest numbers.
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from . import (
    bench_accuracy,
    bench_case_study,
    bench_perf_mining,
    bench_roofline,
    bench_runtime,
    bench_scalability,
    bench_sensitivity,
    bench_serving,
    bench_streaming,
    bench_tzp,
)

SUITES = {
    "fig7_accuracy": bench_accuracy,
    "table2_runtime": bench_runtime,
    "fig8_scaling": bench_scalability,
    "fig9_fig10_sensitivity": bench_sensitivity,
    "table4_tzp": bench_tzp,
    "table6_case_study": bench_case_study,
    "perf_mining": bench_perf_mining,
    "roofline": bench_roofline,
    "streaming": bench_streaming,
    "serving": bench_serving,
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on suite name")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes where the suite supports run(smoke=...)")
    ap.add_argument("--out-json", default=None,
                    help="write structured results from suites exposing "
                         "run_json (edges/sec, peak-memory estimates)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = 0
    payloads: dict[str, object] = {}
    for name, mod in SUITES.items():
        if args.only and args.only not in name:
            continue
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        try:
            if args.out_json and hasattr(mod, "run_json"):
                rows, payloads[name] = mod.run_json(**kwargs)
            else:
                rows = mod.run(**kwargs)
            for row in rows:
                print(row, flush=True)
        except Exception as exc:  # keep the harness going
            failures += 1
            print(f"{name},0.0,ERROR={type(exc).__name__}:{exc}",
                  flush=True)
            traceback.print_exc(file=sys.stderr)
    if args.out_json:
        # merge into an existing BENCH file so suites written by separate
        # invocations (e.g. perf_mining then serving) accumulate instead
        # of clobbering each other; "suites" always holds the LATEST
        # payload per suite (what CI asserts against) while "history"
        # appends one timestamped entry per invocation so older numbers
        # survive a re-run
        try:
            with open(args.out_json) as f:
                existing = json.load(f)
            suites = dict(existing.get("suites", {}))
            history = list(existing.get("history", []))
        except (FileNotFoundError, json.JSONDecodeError):
            suites, history = {}, []
        suites.update(payloads)
        history.append({
            "at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "argv": sys.argv[1:],
            "suites": payloads,
        })
        history = history[-50:]  # bound file growth
        with open(args.out_json, "w") as f:
            json.dump({"argv": sys.argv[1:], "history": history,
                       "suites": suites},
                      f, indent=1, sort_keys=True)
        print(f"json written to {args.out_json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
