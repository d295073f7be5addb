import os
# model-zoo tooling on 512 virtual CPU devices: pinned to the CPU backend
# (here and in the --orchestrate children, which inherit the environment)
# so it never takes a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we ``jit(step).lower(*ShapeDtypeStructs).compile()`` on the
production mesh (single-pod 16x16 and multi-pod 2x16x16) and record:
  * memory_analysis()  — proves the cell fits per-device HBM;
  * cost_analysis()    — per-chip HLO flops / bytes for the roofline;
  * the collective schedule (parsed from post-SPMD HLO) — per-chip traffic.

Results are cached as one JSON per cell under --out; reruns skip finished
cells.  ``--orchestrate`` runs every remaining cell in a fresh subprocess
(compile state does not accumulate; one failing cell cannot kill the sweep).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --orchestrate          # full sweep
  python -m repro.launch.dryrun --report               # print the table
"""

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import all_cells, get_arch        # noqa: E402
from repro.launch import analysis                    # noqa: E402
from repro.launch.mesh import make_production_mesh   # noqa: E402
from repro.obs.timing import Stopwatch               # noqa: E402

DEFAULT_OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "results", "dryrun",
)


def cell_path(out_dir, arch, shape, mesh_kind, tag=""):
    safe = lambda s: s.replace("/", "_")
    suffix = f"__{tag}" if tag else ""
    return os.path.join(
        out_dir, f"{safe(arch)}__{safe(shape)}__{mesh_kind}{suffix}.json"
    )


def _apply_overrides(arch, overrides: str):
    if not overrides:
        return arch
    import dataclasses as _dc

    kv = {}
    for part in overrides.split(","):
        key, val = part.split("=", 1)
        field_type = type(getattr(arch.config, key))
        kv[key] = field_type(val) if field_type is not bool else (
            val.lower() in ("1", "true", "yes"))
    return _dc.replace(arch, config=_dc.replace(arch.config, **kv))


def _compile_workload(wl):
    if wl.in_shardings is None:
        jitted = jax.jit(wl.fn)
    else:
        jitted = jax.jit(wl.fn, in_shardings=wl.in_shardings,
                         out_shardings=wl.out_shardings)
    return jitted.lower(*wl.in_sds).compile()


def _measure(compiled) -> dict:
    cost = compiled.cost_analysis()
    coll = analysis.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
    }


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             out_dir: str, overrides: str = "", tag: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    arch = _apply_overrides(get_arch(arch_name), overrides)
    wl = arch.workload(shape_name, mesh)

    with Stopwatch() as sw:
        compiled = _compile_workload(wl)
    t_compile = sw.seconds

    mem = compiled.memory_analysis()
    full = _measure(compiled)

    # --- scan-depth calibration -------------------------------------------
    # XLA cost_analysis counts a while/scan body ONCE; layer-stacked models
    # would under-report flops by ~n_layers.  Lower depth-1 and depth-2
    # variants: body = f(2) - f(1); corrected = (f(1) - body) + L * body.
    calib = None
    n_layers = getattr(arch.config, "n_layers", 0)
    if n_layers > 2 and arch.family != "mining":
        wl1 = arch.workload_with_depth(shape_name, mesh, 1)
        wl2 = arch.workload_with_depth(shape_name, mesh, 2)
        m1 = _measure(_compile_workload(wl1))
        m2 = _measure(_compile_workload(wl2))

        def corrected(key):
            body = max(m2[key] - m1[key], 0.0)
            outside = max(m1[key] - body, 0.0)
            return outside + n_layers * body

        calib = {
            "flops": corrected("flops"),
            "bytes": corrected("bytes"),
            "coll_bytes": (
                max(m1["coll"]["total_bytes"]
                    - (m2["coll"]["total_bytes"] - m1["coll"]["total_bytes"]),
                    0.0)
                + n_layers * max(
                    m2["coll"]["total_bytes"] - m1["coll"]["total_bytes"],
                    0.0)
            ),
        }

    flops_per_chip = calib["flops"] if calib else full["flops"]
    hlo_bytes_per_chip = calib["bytes"] if calib else full["bytes"]
    coll_bytes_per_chip = (
        calib["coll_bytes"] if calib else full["coll"]["total_bytes"]
    )

    # roofline memory term: unique bytes touched (args + temps + outputs),
    # the TPU-fusion-realistic traffic floor.  The raw op-level HLO bytes
    # (every operand of every op) are kept as an upper bound.
    mem_traffic = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )

    peak_flops = analysis.PEAK_FLOPS
    if arch.family == "mining":
        # integer VPU workload: HLO float-flops are meaningless; use the
        # analytic op count (see configs/ptmt.py) against the VPU peak.
        from repro.configs.ptmt import analytic_mining_terms

        shape_obj = arch._shape(shape_name)
        terms = analytic_mining_terms(arch.config, shape_obj, int(n_chips))
        flops_per_chip = terms["ops_per_chip"]
        mem_traffic = max(mem_traffic, terms["hbm_bytes_per_chip"])
        peak_flops = analysis.VPU_PEAK

    record = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_chips": int(n_chips),
        "kind": wl.kind,
        "model_flops": wl.model_flops,
        "peak_flops": peak_flops,
        "flops_per_chip": flops_per_chip,
        "bytes_per_chip": mem_traffic,
        "hlo_bytes_per_chip_upper": hlo_bytes_per_chip,
        "flops_per_chip_raw": full["flops"],
        "collective_bytes_per_chip": coll_bytes_per_chip,
        "collectives": full["coll"]["per_kind_counts"],
        "collective_bytes_by_kind": full["coll"]["per_kind_bytes"],
        "scan_calibrated": calib is not None,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "compile_s": t_compile,
        "overrides": overrides,
        "tag": tag,
        "status": "ok",
    }
    record.update(analysis.roofline(record))
    os.makedirs(out_dir, exist_ok=True)
    with open(cell_path(out_dir, arch_name, shape_name, mesh_kind, tag),
              "w") as f:
        json.dump(record, f, indent=1)
    return record


def orchestrate(out_dir: str, meshes=("single", "multi"), force=False,
                only_arch=None, timeout=3600):
    cells = [
        (a, s, m) for (a, s) in all_cells() for m in meshes
        if only_arch is None or a == only_arch
    ]
    todo = []
    for a, s, m in cells:
        path = cell_path(out_dir, a, s, m)
        if not force and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("status") == "ok":
                    continue
        todo.append((a, s, m))
    print(f"dry-run sweep: {len(todo)} cells to run "
          f"({len(cells) - len(todo)} cached)")
    failures = []
    for i, (a, s, m) in enumerate(todo):
        with Stopwatch() as sw:
            proc = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", a, "--shape", s, "--mesh", m, "--out", out_dir],
                capture_output=True, text=True, timeout=timeout,
                env=dict(os.environ),
            )
        dt = sw.seconds
        if proc.returncode != 0:
            failures.append((a, s, m))
            err = (proc.stderr or "")[-1500:]
            os.makedirs(out_dir, exist_ok=True)
            with open(cell_path(out_dir, a, s, m), "w") as f:
                json.dump({"arch": a, "shape": s, "mesh": m,
                           "status": "error", "stderr": err}, f, indent=1)
            print(f"[{i+1}/{len(todo)}] FAIL {a}/{s}/{m} ({dt:.0f}s)")
            print(err.splitlines()[-3:] if err else "")
        else:
            print(f"[{i+1}/{len(todo)}] ok   {a}/{s}/{m} ({dt:.0f}s)")
    print(f"done; {len(failures)} failures: {failures}")
    return failures


def report(out_dir: str):
    rows = []
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(out_dir, fn)) as f:
            rows.append(json.load(f))
    hdr = (f"{'arch':22s} {'shape':15s} {'mesh':6s} {'status':6s} "
           f"{'comp_ms':>8s} {'mem_ms':>8s} {'coll_ms':>8s} {'dom':>9s} "
           f"{'useful':>7s} {'roofline':>8s} {'temp_GB':>8s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if r.get("status") != "ok":
            print(f"{r['arch']:22s} {r['shape']:15s} {r['mesh']:6s} ERROR")
            continue
        print(
            f"{r['arch']:22s} {r['shape']:15s} {r['mesh']:6s} "
            f"{r['status']:6s} "
            f"{r['compute_s']*1e3:8.2f} {r['memory_s']*1e3:8.2f} "
            f"{r['collective_s']*1e3:8.2f} {r['dominant']:>9s} "
            f"{r['useful_flops_ratio']:7.3f} {r['roofline_fraction']:8.3f} "
            f"{r['memory']['temp_bytes']/1e9:8.2f}"
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--orchestrate", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--only-arch")
    ap.add_argument("--override", default="",
                    help="config overrides, e.g. gather_dtype=bf16")
    ap.add_argument("--tag", default="",
                    help="result-file suffix for optimized variants")
    args = ap.parse_args()

    if args.report:
        report(args.out)
        return
    if args.orchestrate:
        failures = orchestrate(args.out, force=args.force,
                               only_arch=args.only_arch)
        sys.exit(1 if failures else 0)
    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --orchestrate/--report)")
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, args.out,
                       overrides=args.override, tag=args.tag)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    print(json.dumps(
        {k: rec[k] for k in
         ("arch", "shape", "mesh", "compute_s", "memory_s", "collective_s",
          "dominant", "useful_flops_ratio", "roofline_fraction",
          "compile_s")},
        indent=1,
    ))
    print("memory:", rec["memory"])
    print("collectives:", rec["collectives"])


if __name__ == "__main__":
    main()
