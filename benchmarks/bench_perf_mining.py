"""§Perf evidence for the mining kernel's structural optimizations.

Measures, on real zone batches (not ShapeDtypeStructs):

1. **live-window block skipping** (kernels/zone_scan): the fraction of
   (candidate-block x edge-block) grid cells whose index/time tests skip
   them — the work reduction the 2-D kernel grid buys over the dense
   O(E^2) sweep of the paper-faithful formulation;
2. **adaptive zoning** (core/tzp e_cap): padded-batch size with and without
   the density-adaptive zone shrinking on a bursty stream — zone padding is
   wasted vector work, so the ratio is a direct work saving;
3. measured **unique-code populations** per device-shard, validating the
   hierarchical-merge out_cap used in the dry-run variants;
4. **hierarchical chunked aggregation** (core/executor agg modes): measured
   throughput of legacy whole-batch vs hierarchical fold on one batch,
   plus the planner's peak-memory model showing the zone-count ceiling
   move — at a fixed budget the legacy O(Z*C) flatten caps Z, while the
   hierarchical fold's peak is Z-independent, and the benchmark *runs*
   the fold at a zone count beyond the legacy cap;
5. **engine compiled-plan reuse** (core/engine): cold vs warm
   ``PTMTEngine.discover`` on the same-shaped workload.  The warm call must
   register a compile-cache hit and be measurably faster — this is the
   acceptance gate for the session-engine API and is re-asserted by CI on
   the smoke JSON;
6. **ragged zone layout** (core/tzp ``ZoneBatchLayout``): dense vs
   size-bucketed padding ratio, per-bucket occupancy, and measured
   edges/sec on a bursty corpus whose zone sizes span several power-of-two
   buckets, plus proof that the engine's per-bucket compile cache still
   registers hits under the bucketed layout.  CI asserts
   ``padding_ratio_bucketed < padding_ratio_dense`` on the smoke JSON;
7. **fused single-launch scan** (kernels/zone_scan ``fused_zone_scan_flat``
   + executor ``run_fused``): per-bucket dispatch loop vs ONE bucket-native
   ``pallas_call`` over the concatenated slot stream with the Phase-2
   signed fold fused on-device — only the bounded ``CodeCounts`` table and
   a spill flag return to host.  CI asserts the fused path reports exactly
   one launch per mine and edges/sec no worse than per-bucket;
8. **observability overhead** (repro.obs): the no-op span micro-bench ×
   spans-per-mine projection must stay under 2% of a disabled-mode fused
   mine (asserted), and a live registry snapshot of the instrumented run
   is recorded under ``observability.metrics_sample``.

``run_json`` additionally returns a structured payload for
``benchmarks/run.py --out-json`` (edges/sec + peak-memory estimates + the
warm/cold engine timings — the ``BENCH_mining.json`` perf trajectory).
"""

from __future__ import annotations

import time

import numpy as np

import repro.obs as obs_mod
from repro.core import (
    MiningConfig,
    MiningExecutor,
    PTMTEngine,
    planner,
    transitions,
    tzp,
)
from repro.data import synthetic_graphs as sg

from .common import csv_row, timed

DELTA, L_MAX = 90, 5


def _skip_fraction(batch, delta, l_max, c_blk=256, e_blk=256):
    """Fraction of kernel grid cells skipped by the live-window tests."""
    e = batch.e_cap
    n_c = -(-e // c_blk)
    n_e = -(-e // e_blk)
    zi = np.flatnonzero(batch.valid.any(axis=1))
    t = batch.t[zi]                                     # [Z, E]
    c_hi = np.minimum((np.arange(n_c) + 1) * c_blk, e) - 1
    e_lo = np.minimum(np.arange(n_e) * e_blk, e - 1)
    index_live = (e_lo[None, :] + e_blk - 1) >= (
        np.arange(n_c)[:, None] * c_blk)                # [C, E]
    time_live = (
        t[:, e_lo][:, None, :] <= t[:, c_hi][:, :, None] + l_max * delta
    )                                                    # [Z, C, E]
    live = (index_live[None] & time_live).sum()
    total = len(zi) * n_c * n_e
    return 1.0 - live / max(total, 1)


def _legacy_z_ceiling(budget_bytes, e_cap, l_max, zone_chunk) -> int:
    """Largest zone count whose legacy whole-batch peak fits the budget."""
    lo, hi = 0, 1 << 30
    while lo < hi:
        mid = (lo + hi + 1) // 2
        peak = planner.legacy_peak_bytes(mid, e_cap, l_max,
                                         zone_chunk=zone_chunk)
        if peak <= budget_bytes:
            lo = mid
        else:
            hi = mid - 1
    return lo


def _hierarchical_section(smoke: bool):
    """Throughput of the two agg folds + the memory-ceiling move."""
    n_edges = 4_000 if smoke else 24_000
    g = sg.poisson_stream(n_edges, 300, rate=0.5, seed=7)
    # small-omega, e_cap-split zones: many modest zones, the regime where
    # the O(Z*C) whole-batch flatten is the binding constraint.  The cap
    # stays above the adaptive floor's edge population (~2*L_b*rate) so no
    # edges are dropped and counts remain exact.
    cap = 512 if smoke else 1024
    plan = tzp.plan_zones(g, delta=DELTA, l_max=L_MAX, omega=2, e_cap=cap)
    zc = 4 if smoke else 8
    batch = tzp.build_zone_batch(g, plan, e_cap=cap, pad_zones_to=zc)

    modes = {}
    counts_seen = {}
    for agg in ("legacy", "hierarchical"):
        ex = MiningExecutor(delta=DELTA, l_max=L_MAX, zone_chunk=zc, agg=agg)
        run = lambda: transitions.device_counts_to_dict(ex.run(batch))
        counts, secs = timed(run, warmup=1, repeats=1 if smoke else 2)
        counts_seen[agg] = counts
        modes[agg] = {
            "seconds": secs,
            "edges_per_s": g.n_edges / secs if secs else 0.0,
        }
    assert counts_seen["hierarchical"] == counts_seen["legacy"], \
        "agg modes disagree — differential bug"

    merge_cap = planner.default_merge_cap(zc, batch.e_cap)
    hier_peak = planner.hierarchical_peak_bytes(
        zc, batch.e_cap, L_MAX, merge_cap=merge_cap)
    # the budget IS the fold's own peak: at the memory hierarchical
    # aggregation needs, how many zones could the legacy flatten hold?
    budget = hier_peak
    z_legacy_max = _legacy_z_ceiling(budget, batch.e_cap, L_MAX, zc)
    legacy_peak_at_run = planner.legacy_peak_bytes(
        batch.n_zones, batch.e_cap, L_MAX, zone_chunk=zc)
    ceiling = {
        "budget_mb": budget / 2**20,
        "e_cap": batch.e_cap,
        "zone_chunk": zc,
        "merge_cap": merge_cap,
        "hier_peak_mb": hier_peak / 2**20,
        "legacy_peak_mb_at_run": legacy_peak_at_run / 2**20,
        "z_max_legacy_at_budget": z_legacy_max,
        "z_run": batch.n_zones,
        "ceiling_moved": batch.n_zones > z_legacy_max
        and hier_peak <= budget,
        "motif_types": len(counts_seen["hierarchical"]),
    }
    throughput = {
        "edges": g.n_edges,
        "n_zones": batch.n_zones,
        "e_cap": batch.e_cap,
        "zone_chunk": zc,
        "modes": modes,
    }

    rows = [
        csv_row(
            f"perf_mining/agg_{agg}", m["seconds"],
            f"edges_per_s={m['edges_per_s']:.0f};zones={batch.n_zones};"
            f"zone_chunk={zc}",
        )
        for agg, m in modes.items()
    ]
    rows.append(csv_row(
        "perf_mining/memory_ceiling", 0.0,
        f"budget={ceiling['budget_mb']:.1f}MB;"
        f"legacy_z_max={z_legacy_max};hier_z_run={batch.n_zones};"
        f"hier_peak={ceiling['hier_peak_mb']:.1f}MB;"
        f"legacy_peak_at_run={ceiling['legacy_peak_mb_at_run']:.1f}MB;"
        f"ceiling_moved={ceiling['ceiling_moved']}",
    ))
    return rows, {"throughput": throughput, "memory_ceiling": ceiling}


def _zone_layout_section(smoke: bool):
    """Dense vs size-bucketed layout on a bursty (skewed-zone) corpus."""
    from repro.core import MiningExecutor as _Ex

    n_edges = 2_500 if smoke else 20_000
    g = sg.bursty_stream(n_edges, 250, burst_size=120, burst_span=200,
                         gap_span=30_000, seed=13)
    plan = tzp.plan_zones(g, delta=DELTA, l_max=L_MAX, omega=2)
    layouts = {
        kind: tzp.build_zone_layout(g, plan, layout=kind)
        for kind in ("dense", "bucketed")
    }
    assert layouts["bucketed"].n_buckets >= 3, \
        "bursty corpus must span >= 3 buckets"

    modes = {}
    counts_seen = {}
    for kind, lay in layouts.items():
        ex = _Ex(delta=DELTA, l_max=L_MAX)
        run = lambda lay=lay, ex=ex: transitions.device_counts_to_dict(
            ex.run_layout(lay).counts)
        counts, secs = timed(run, warmup=1, repeats=1 if smoke else 2)
        counts_seen[kind] = counts
        modes[kind] = {
            "seconds": secs,
            "edges_per_s": g.n_edges / secs if secs else 0.0,
            "padding_ratio": lay.padding_ratio,
            "padded_slots": lay.padded_slots,
            "sweep_slots": lay.sweep_slots,
        }
    assert counts_seen["bucketed"] == counts_seen["dense"], \
        "layouts disagree — differential bug"

    # the per-bucket compile cache must keep registering hits: a second
    # same-graph discover dispatches every bucket to a cached executable
    # (and skips host-side planning via the zone-plan cache)
    engine = PTMTEngine(MiningConfig(delta=DELTA, l_max=L_MAX, omega=2,
                                     zone_layout="bucketed"))
    engine.discover(g)
    engine.discover(g)
    payload = {
        "edges": g.n_edges,
        "n_zones": plan.n_zones,
        "modes": modes,
        "padding_ratio_dense": modes["dense"]["padding_ratio"],
        "padding_ratio_bucketed": modes["bucketed"]["padding_ratio"],
        "buckets": layouts["bucketed"].summary()["buckets"],
        "compile_cache_hits_bucketed": engine.stats.compile_cache_hits,
        "plan_cache_hits": engine.stats.plan_cache_hits,
        "speedup_bucketed_vs_dense": (
            modes["dense"]["seconds"] / modes["bucketed"]["seconds"]
            if modes["bucketed"]["seconds"] else 0.0),
    }
    rows = [
        csv_row(
            f"perf_mining/zone_layout_{kind}", m["seconds"],
            f"edges_per_s={m['edges_per_s']:.0f};"
            f"padding_ratio={m['padding_ratio']:.3f};"
            f"sweep_slots={m['sweep_slots']}",
        )
        for kind, m in modes.items()
    ]
    rows.append(csv_row(
        "perf_mining/zone_layout", 0.0,
        f"buckets={len(payload['buckets'])};"
        f"pad_dense={payload['padding_ratio_dense']:.3f};"
        f"pad_bucketed={payload['padding_ratio_bucketed']:.3f};"
        f"speedup={payload['speedup_bucketed_vs_dense']:.2f}x;"
        f"bucketed_cache_hits={payload['compile_cache_hits_bucketed']}",
    ))
    return rows, payload


def _fused_section(smoke: bool):
    """Fused single-launch scan vs the per-bucket dispatch loop.

    Same bursty corpus and bucketed layout; the fused path concatenates
    every bucket into one flat slot stream and runs ONE launch with the
    Phase-2 fold on-device, so candidate codes never round-trip to host.
    Three modes: ``per_bucket`` (one launch per bucket), ``fused`` (the
    ``"auto"``-dispatched lowering — the compiled xla formulation on CPU
    hosts, the Pallas kernel where it compiles) and ``fused_interpret``
    (the Pallas lowering pinned via ``fused_backend="pallas"`` — the old
    interpret-mode baseline on CPU).  Counts must be identical across all
    three.  Launch accounting comes from the executor's metrics registry
    (``repro_mining_launches_total{path=...}`` counter deltas per mine
    plus the ``repro_mining_fused_*`` gauges) — the same surface a scrape
    sees — and one ``RunOutcome.stats`` dict is read to assert the two
    surfaces agree.  CI asserts the fused path reports exactly one launch
    per mine, resolves to the compiled ``fused_xla`` path on CPU, and is
    no slower than the interpret baseline.
    """
    n_edges = 2_500 if smoke else 20_000
    g = sg.bursty_stream(n_edges, 250, burst_size=120, burst_span=200,
                         gap_span=30_000, seed=13)
    plan = tzp.plan_zones(g, delta=DELTA, l_max=L_MAX, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    obs = obs_mod.enabled()
    ex_auto = MiningExecutor(delta=DELTA, l_max=L_MAX, backend="pallas",
                             obs=obs)
    ex_interp = MiningExecutor(delta=DELTA, l_max=L_MAX, backend="pallas",
                               fused_backend="pallas", obs=obs)

    repeats = 2 if smoke else 3
    modes = {}
    counts_seen = {}
    for name, ex, fused in (("per_bucket", ex_auto, False),
                            ("fused", ex_auto, True),
                            ("fused_interpret", ex_interp, True)):
        # probe run: compiles, and tells us which launch-counter label
        # this executor's dispatch actually lands on
        probe = ex.run_layout(lay, fused=fused).stats
        path = probe["path"]
        launch_counter = obs.metrics.counter("repro_mining_launches_total",
                                             path=path)
        c0 = launch_counter.value
        # the interpreter is orders of magnitude slower — one timed rep
        # keeps the full suite's wall time bounded
        reps = repeats if name != "fused_interpret" else (2 if smoke else 1)
        run = lambda ex=ex, fused=fused: transitions.device_counts_to_dict(
            ex.run_layout(lay, fused=fused).counts)
        counts, secs = timed(run, warmup=1, repeats=reps)
        counts_seen[name] = counts
        modes[name] = {
            "seconds": secs,
            "edges_per_s": g.n_edges / secs if secs else 0.0,
            "launches": (launch_counter.value - c0) // (1 + reps),
            "path": path,
            "backend": probe.get("backend", "pallas"),
        }
    assert counts_seen["fused"] == counts_seen["per_bucket"], \
        "fused != per-bucket — differential bug"
    assert counts_seen["fused"] == counts_seen["fused_interpret"], \
        "compiled fused != pallas fused — differential bug"
    assert modes["fused"]["launches"] == 1
    assert modes["fused_interpret"]["launches"] == 1

    gauge = lambda n: int(obs.metrics.gauge(n).value)
    spills = obs.metrics.find("repro_mining_spill_retries_total",
                              path=modes["fused"]["path"])
    # the registry mirrors the RunOutcome stats, never redefines them —
    # assert the two surfaces agree on the fused geometry
    lrs = ex_auto.run_layout(lay, fused=True).stats
    assert (lrs["path"], lrs["launches"]) == (modes["fused"]["path"], 1)
    assert lrs["merge_cap"] == gauge("repro_mining_fused_merge_cap")
    assert lrs["n_slots"] == gauge("repro_mining_fused_slots")

    payload = {
        "edges": g.n_edges,
        "n_buckets": lay.n_buckets,
        "modes": modes,
        "fused_path": modes["fused"]["path"],
        "fused_backend": modes["fused"]["backend"],
        "fused_bounds": lrs["bounds"],
        "launches_fused": modes["fused"]["launches"],
        "launches_per_bucket": modes["per_bucket"]["launches"],
        "edges_per_s_fused": modes["fused"]["edges_per_s"],
        "edges_per_s_fused_interpret":
            modes["fused_interpret"]["edges_per_s"],
        "edges_per_s_per_bucket": modes["per_bucket"]["edges_per_s"],
        "fold_chunk": gauge("repro_mining_fused_fold_chunk"),
        "merge_cap": gauge("repro_mining_fused_merge_cap"),
        "n_slots": gauge("repro_mining_fused_slots"),
        "sweep_slots": gauge("repro_mining_fused_sweep_slots"),
        # cumulative over the section's runs (counters only go up)
        "spill_retries": int(spills.value) if spills else 0,
        "speedup_fused_vs_per_bucket": (
            modes["per_bucket"]["seconds"] / modes["fused"]["seconds"]
            if modes["fused"]["seconds"] else 0.0),
        "speedup_fused_vs_interpret": (
            modes["fused_interpret"]["seconds"] / modes["fused"]["seconds"]
            if modes["fused"]["seconds"] else 0.0),
    }
    rows = [
        csv_row(
            f"perf_mining/scan_{name}", m["seconds"],
            f"edges_per_s={m['edges_per_s']:.0f};launches={m['launches']};"
            f"path={m['path']}",
        )
        for name, m in modes.items()
    ]
    rows.append(csv_row(
        "perf_mining/fused_launch", 0.0,
        f"launches=1_vs_{payload['launches_per_bucket']};"
        f"path={payload['fused_path']};"
        f"speedup_vs_per_bucket="
        f"{payload['speedup_fused_vs_per_bucket']:.2f}x;"
        f"speedup_vs_interpret="
        f"{payload['speedup_fused_vs_interpret']:.2f}x;"
        f"n_slots={payload['n_slots']};fold_chunk={payload['fold_chunk']}",
    ))
    return rows, payload


def _observability_section(smoke: bool):
    """Observability cost proof + a metrics-snapshot sample for the BENCH
    trajectory.

    Two claims land in ``BENCH_mining.json``:

    * **disabled-mode overhead on the fused path is < 2%** — asserted, not
      eyeballed.  The no-op span (what every instrumented call site pays
      when observability is off) is micro-benchmarked in a tight loop, its
      cost scaled by the number of spans one enabled fused mine actually
      emits, and that projection compared against the measured
      disabled-mode run time.  The projection is the right comparison: the
      raw enabled-vs-disabled wall delta is dominated by registry/tracer
      bookkeeping the disabled path never executes, while the projection
      isolates exactly the residue the NULL_OBS design leaves behind.
    * **metrics_sample** — the registry snapshot of the enabled mine, so
      the BENCH file carries the exact export schema downstream tooling
      parses.
    """
    n_edges = 2_500 if smoke else 20_000
    g = sg.bursty_stream(n_edges, 250, burst_size=120, burst_span=200,
                         gap_span=30_000, seed=13)
    plan = tzp.plan_zones(g, delta=DELTA, l_max=L_MAX, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")

    # disabled-mode fused run: the default NULL_OBS executor
    ex_off = MiningExecutor(delta=DELTA, l_max=L_MAX, backend="pallas")
    run_off = lambda: transitions.device_counts_to_dict(
        ex_off.run_layout(lay, fused=True).counts)
    counts_off, secs_off = timed(run_off, warmup=1, repeats=2)

    # enabled run on the same workload: span census + snapshot sample
    obs = obs_mod.enabled()
    ex_on = MiningExecutor(delta=DELTA, l_max=L_MAX, backend="pallas",
                           obs=obs)
    run_on = lambda: transitions.device_counts_to_dict(
        ex_on.run_layout(lay, fused=True).counts)
    counts_on, secs_on = timed(run_on, warmup=1, repeats=2)
    assert counts_on == counts_off, "observability changed mining results"
    n_runs_on = 3  # warmup + repeats
    spans_per_run = -(-len(obs.tracer.events()) // n_runs_on)

    # no-op span micro-bench (per-span cost with observability off)
    iters = 20_000 if smoke else 50_000
    null_tracer = obs_mod.NULL_OBS.tracer
    t0 = time.perf_counter()
    for _ in range(iters):
        with null_tracer.span("noop"):
            pass
    noop_span_s = (time.perf_counter() - t0) / iters

    projected_s = spans_per_run * noop_span_s
    frac = projected_s / secs_off if secs_off else 0.0
    assert frac < 0.02, (
        f"disabled-mode span overhead projects to {frac:.2%} of a fused "
        f"mine ({spans_per_run} spans x {noop_span_s * 1e6:.2f}us vs "
        f"{secs_off:.3f}s) — observability must stay near-free when off")

    payload = {
        "edges": g.n_edges,
        "disabled_seconds": secs_off,
        "enabled_seconds": secs_on,
        "enabled_over_disabled": secs_on / secs_off if secs_off else 0.0,
        "spans_per_run": spans_per_run,
        "noop_span_us": noop_span_s * 1e6,
        "projected_disabled_overhead_fraction": frac,
        "overhead_bound": 0.02,
        "metrics_sample": obs.metrics.snapshot(),
    }
    row = csv_row(
        "perf_mining/observability", secs_on,
        f"disabled_s={secs_off:.3f};enabled_s={secs_on:.3f};"
        f"spans={spans_per_run};noop_span_us={payload['noop_span_us']:.2f};"
        f"projected_off_overhead={frac:.4%}",
    )
    return [row], payload


def _engine_reuse_section(smoke: bool):
    """Cold vs warm ``PTMTEngine.discover`` on one workload shape.

    Parameters are chosen to not collide with any other section's jit-cache
    key (distinct delta/l_max), so the cold call genuinely pays trace +
    compile even when the whole suite runs in one process.
    """
    g = sg.poisson_stream(1_500 if smoke else 8_000, 200, rate=0.5, seed=9)
    engine = PTMTEngine(MiningConfig(delta=75, l_max=4, omega=6,
                                     zone_chunk=4))

    t0 = time.perf_counter()
    cold_res = engine.discover(g)
    cold_s = time.perf_counter() - t0
    # min-of-N warm timing: the reuse property itself is proven
    # deterministically by the compile-cache counter below; the timing
    # only has to survive scheduler noise on a loaded CI runner
    warm_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        warm_res = engine.discover(g)
        warm_s = min(warm_s, time.perf_counter() - t0)

    assert warm_res.counts == cold_res.counts, "warm call changed counts"
    assert engine.stats.compile_cache_hits >= 3, \
        "same-shape discover calls did not register compile-cache hits"
    assert warm_s < cold_s, (
        f"warm engine call ({warm_s:.3f}s) not faster than cold "
        f"({cold_s:.3f}s) — compiled-plan reuse is broken")

    payload = {
        "edges": g.n_edges,
        "cold_seconds": cold_s,
        "warm_seconds": warm_s,
        "warm_runs": 3,
        "speedup": cold_s / warm_s if warm_s else 0.0,
        "compile_cache_hits": engine.stats.compile_cache_hits,
        "compile_cache_misses": engine.stats.compile_cache_misses,
    }
    row = csv_row(
        "perf_mining/engine_reuse", warm_s,
        f"cold_s={cold_s:.3f};warm_s={warm_s:.4f};"
        f"speedup={payload['speedup']:.2f}x;"
        f"hits={payload['compile_cache_hits']}",
    )
    return [row], payload


def run_json(smoke: bool = False):
    """Returns (csv rows, structured payload for BENCH_mining.json)."""
    rows = []
    payload = {"suite": "perf_mining", "smoke": smoke,
               "delta": DELTA, "l_max": L_MAX}
    delta, l_max = DELTA, L_MAX
    scale = 0.1 if smoke else 1.0

    # 1) live-window skipping on two regimes (bursts big enough that a
    #    zone spans many kernel blocks)
    for name, gen in (("bursty", sg.bursty_stream(
                          int(30_000 * scale), 300,
                          burst_size=int(2_000 * scale) or 100,
                          burst_span=900,
                          gap_span=20_000, seed=2)),
                      ("poisson", sg.poisson_stream(int(20_000 * scale), 500,
                                                    rate=0.5, seed=2))):
        plan = tzp.plan_zones(gen, delta=delta, l_max=l_max, omega=20)
        batch = tzp.build_zone_batch(gen, plan)
        frac = _skip_fraction(batch, delta, l_max)
        rows.append(csv_row(
            f"perf_mining/skip_fraction/{name}", 0.0,
            f"omega=20;skipped={frac:.1%};work_reduction="
            f"{1/(1-frac) if frac < 1 else 0:.1f}x",
        ))

    # 2) adaptive zoning on a heavy-burst stream
    # bursts longer than 2*L_b so the adaptive planner can split them
    g = sg.bursty_stream(int(30_000 * scale), 200,
                         burst_size=int(3_000 * scale) or 300,
                         burst_span=5_000, gap_span=36_000, seed=4)
    plan_fixed = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=20)
    b_fixed = tzp.build_zone_batch(g, plan_fixed)
    e_adapt = 768 if not smoke else 96
    plan_adapt = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=20,
                                e_cap=e_adapt)
    b_adapt = tzp.build_zone_batch(g, plan_adapt, e_cap=e_adapt)
    work_fixed = b_fixed.n_zones * b_fixed.e_cap ** 2
    work_adapt = b_adapt.n_zones * b_adapt.e_cap ** 2
    rows.append(csv_row(
        "perf_mining/adaptive_zoning", 0.0,
        f"fixed=({b_fixed.n_zones}z x cap{b_fixed.e_cap});"
        f"adaptive=({b_adapt.n_zones}z x cap{b_adapt.e_cap});"
        f"padded_sweep_work_reduction={work_fixed/work_adapt:.1f}x;"
        f"overflow={b_adapt.overflow}",
    ))

    # 3) unique codes per shard (out_cap validation)
    from repro.core import from_edges

    n3 = int(8000 * scale) or 1000
    g_small = from_edges(g.u[:n3], g.v[:n3], g.t[:n3])
    res = PTMTEngine(MiningConfig(
        delta=delta, l_max=l_max, omega=8, e_cap=1024, allow_overflow=True,
    )).discover(g_small)
    rows.append(csv_row(
        "perf_mining/unique_codes", 0.0,
        f"global_unique={len(res.counts)};"
        f"out_cap_16384_headroom={16384 / max(len(res.counts), 1):.0f}x",
    ))

    # 4) hierarchical aggregation: throughput + the memory-ceiling move
    hier_rows, hier_payload = _hierarchical_section(smoke)
    rows.extend(hier_rows)
    payload.update(hier_payload)

    # 5) engine compiled-plan reuse: warm call must beat cold
    reuse_rows, reuse_payload = _engine_reuse_section(smoke)
    rows.extend(reuse_rows)
    payload["engine_reuse"] = reuse_payload

    # 6) ragged zone layout: bucketed must waste fewer padded slots
    layout_rows, layout_payload = _zone_layout_section(smoke)
    rows.extend(layout_rows)
    payload["zone_layout"] = layout_payload

    # 7) fused single-launch scan: one dispatch, fold on-device
    fused_rows, fused_payload = _fused_section(smoke)
    rows.extend(fused_rows)
    payload["fused"] = fused_payload

    # 8) observability: disabled-mode overhead < 2% + snapshot sample
    obs_rows, obs_payload = _observability_section(smoke)
    rows.extend(obs_rows)
    payload["observability"] = obs_payload
    return rows, payload


def run(smoke: bool = False) -> list[str]:
    rows, _ = run_json(smoke)
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
