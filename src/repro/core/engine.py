"""PTMT session engine — one object that owns config + compilation state.

The paper's pipeline is one fixed lifecycle — plan zones (TZP), expand in
parallel, aggregate, encode — but the entry points had diverged into
per-call parameter bundles that re-resolved backends, capacity plans, and
jit state on every invocation.  :class:`PTMTEngine` is the single factory:

* ``engine.discover(graph)``    — batch PTMT discovery;
* ``engine.sequential(graph)``  — the TMC-analog baseline (one zone, built
  through :func:`repro.core.tzp.single_zone_plan` — no hand-rolled pad);
* ``engine.stream()``           — a :class:`repro.core.streaming.
  StreamingMiner` sharing this engine's executor;
* ``engine.sharded(graph, mesh, axes)`` — the mesh path, with the jitted
  SPMD mining step cached per ``(mesh, axes, out_cap, merge_mode)`` so
  repeated sharded calls skip re-building (and re-jitting) the step;
* serving sessions take the engine whole: ``MotifSession(name,
  engine=engine)``.

The engine resolves the backend **once** (at construction, via the
executor), owns the capacity planner (budget-derived plans are memoized per
batch geometry), and tracks the compiled-executable reuse that the
module-level jit caches provide: every run's
:meth:`~repro.core.executor.MiningExecutor.execution_key` is recorded, and
a key seen before is a **compile-cache hit** — the call dispatches straight
to an existing executable with no re-trace.  ``engine.stats`` exposes the
counters; ``benchmarks/bench_perf_mining.py`` asserts the warm-call
speedup and CI re-checks it on every push.

The legacy ``discover(...)``/``discover_sequential(...)`` kwargs functions
in :mod:`repro.core.api` finished their deprecation cycle and now raise
with a pointer back here.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.obs import get_obs

from . import planner, tzp
from .api import DiscoveryResult, counts_to_result
from .config import MiningConfig
from .executor import MiningExecutor
from .streaming import StreamingMiner
from .temporal_graph import TemporalGraph

__all__ = ["EngineStats", "PTMTEngine"]


@dataclasses.dataclass
class EngineStats:
    """Observable engine counters (mutated in place, cheap to read).

    This dataclass is the stable, zero-dependency *view* of the engine's
    execution history — its fields and meanings are unchanged by the
    observability layer.  When the engine is built with a live
    :class:`repro.obs.Observability` bundle, every increment here is
    mirrored into the bundle's metrics registry
    (``repro_mining_compile_cache_hits_total`` etc.; ``launches`` as
    ``repro_mining_launches_total`` by dispatch path, ``stream_launches``
    under ``path="stream"``, ``sweep_slots`` as
    ``repro_mining_sweep_slots_total`` and ``merge_rows`` as
    ``repro_mining_merge_rows_total`` by dispatch path), so Prometheus
    exports and ``EngineStats`` always agree."""

    discover_calls: int = 0
    discover_many_calls: int = 0    # co-mined multi-config discover calls
    comined_configs: int = 0        # member configs served by shared sweeps
    sequential_calls: int = 0
    sharded_calls: int = 0
    stream_sessions: int = 0
    compile_cache_hits: int = 0     # bucket runs whose execution key was seen
    compile_cache_misses: int = 0   # bucket runs that had to trace + compile
    plan_cache_hits: int = 0        # discover calls that skipped plan_zones
    plan_cache_misses: int = 0      # discover calls that ran Algorithm 1
    zones_mined: int = 0
    launches: int = 0               # scan dispatches (fused layout run = 1)
    stream_launches: int = 0        # scan dispatches of engine.stream() miners
    sweep_slots: int = 0            # candidate-steps of the launched scans
    merge_rows: int = 0             # rows the cross-bucket merges sorted
    fused_runs: int = 0             # discover calls served by the fused path
    padding_ratio: float = 0.0      # last layout's padded-slot waste
    bucket_occupancy: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PTMTEngine:
    """Session object for PTMT discovery: validated config + warm jit state.

    Construct from a :class:`~repro.core.config.MiningConfig` (or field
    overrides — ``PTMTEngine(delta=600, l_max=6)`` builds one), then call
    any mode repeatedly.  Same-shaped workloads reuse compiled executables:
    the backend is resolved once, capacity plans are memoized, and the
    mesh-path SPMD step is cached per mesh geometry.

    Thread-safety matches the underlying executor: concurrent ``discover``
    calls are safe (state is append-only caches and counters); the stats
    are best-effort under races.
    """

    def __init__(self, config: MiningConfig | None = None, *, obs=None,
                 **overrides):
        if config is None:
            config = MiningConfig(**overrides)
        elif overrides:
            config = config.with_updates(**overrides)
        self.config = config
        # obs is deliberately NOT a MiningConfig field: the config is a
        # frozen hashable value object, an Observability bundle is live
        # mutable state.  It rides alongside instead, threaded into the
        # executor (and from there into streaming miners and layouts).
        self.obs = get_obs(obs)
        self.executor = MiningExecutor.from_config(config, obs=self.obs)
        self.stats = EngineStats()
        self._seen_keys: set[tuple] = set()
        self._mesh_steps: dict[tuple, object] = {}
        # host-side zone-plan cache: (graph fingerprint, delta, l_max,
        # omega, e_cap) -> ZonePlan.  Repeated discover on the same graph
        # skips Algorithm 1's O(n) scan entirely (stats.plan_cache_hits).
        # LRU-bounded: plans hold O(n_zones) arrays, and a long-lived
        # engine iterating many distinct graphs must not grow without
        # bound.
        self._zone_plans: dict[tuple, tzp.ZonePlan] = {}
        self._zone_plan_cap = 64
        # lattice-keyed executor cache: dominating MiningConfig -> warm
        # MiningExecutor for that sweep shape.  discover_many over the
        # same tenant mix reuses one executor (and its jit state) per
        # lattice; the engine's own executor serves lattices whose
        # dominating config IS the engine config.  LRU-bounded like the
        # zone-plan cache.
        self._lattice_executors: dict[MiningConfig, MiningExecutor] = {}
        self._lattice_executor_cap = 16

    @property
    def backend(self) -> str:
        return self.executor.backend

    def __repr__(self) -> str:
        return (f"PTMTEngine(backend={self.backend!r}, "
                f"delta={self.config.delta}, l_max={self.config.l_max}, "
                f"compiled_plans={len(self._seen_keys)})")

    # -- compilation-state bookkeeping --------------------------------------

    def _note_execution(self, key: tuple, n_zones: int) -> None:
        """Record a *successful* run's execution key (call after the run —
        a raised overflow/out_cap error compiles nothing and must not
        poison the reuse counters the bench and CI assert on)."""
        if key in self._seen_keys:
            self.stats.compile_cache_hits += 1
            self.obs.metrics.counter(
                "repro_mining_compile_cache_hits_total").inc()
        else:
            self._seen_keys.add(key)
            self.stats.compile_cache_misses += 1
            self.obs.metrics.counter(
                "repro_mining_compile_cache_misses_total").inc()
        self.stats.zones_mined += n_zones

    def capacity_plan(self, n_zones: int, e_cap: int):
        """Budget-derived capacity plan (None without a budget).

        Delegates to the engine-held executor, which memoizes per batch
        geometry — repeated same-shaped runs never re-derive the plan.
        """
        return self.executor.capacity_plan(n_zones, e_cap)

    # -- batch discovery ----------------------------------------------------

    def plan_zones(self, graph: TemporalGraph,
                   config: MiningConfig | None = None) -> tzp.ZonePlan:
        """Zone plan for ``graph``, memoized by graph fingerprint.

        The cache key is ``(graph_fingerprint, delta, l_max, omega,
        e_cap)`` — exactly the inputs Algorithm 1 depends on — so repeated
        ``discover`` on the same stream skips host-side planning entirely.
        ``ZonePlan.to_json``/``from_json`` round-trip exactly, so a plan
        can also be persisted and re-attached out of process.  ``config``
        plans for a non-engine config (the co-mine path plans at a
        lattice's dominating config) through the same cache.
        """
        cfg = config or self.config
        key = (tzp.graph_fingerprint(graph), cfg.delta, cfg.l_max,
               cfg.omega, cfg.e_cap)
        plan = self._zone_plans.get(key)
        if plan is not None:
            self.stats.plan_cache_hits += 1
            self.obs.metrics.counter(
                "repro_mining_plan_cache_hits_total").inc()
            self._zone_plans[key] = self._zone_plans.pop(key)  # LRU bump
            return plan
        with self.obs.tracer.span("engine.plan", n_edges=graph.n_edges):
            plan = tzp.plan_zones(graph, delta=cfg.delta, l_max=cfg.l_max,
                                  omega=cfg.omega, e_cap=cfg.e_cap)
        self._zone_plans[key] = plan
        while len(self._zone_plans) > self._zone_plan_cap:
            self._zone_plans.pop(next(iter(self._zone_plans)))
        self.stats.plan_cache_misses += 1
        self.obs.metrics.counter("repro_mining_plan_cache_misses_total").inc()
        return plan

    def _plan_and_layout(self, graph: TemporalGraph, n_shards: int = 1, *,
                         config: MiningConfig | None = None,
                         executor: MiningExecutor | None = None):
        cfg = config or self.config
        executor = executor or self.executor
        plan = self.plan_zones(graph, config=cfg)
        pad_zones = (executor.zone_chunk or 1) * n_shards
        with self.obs.tracer.span("engine.layout", n_zones=plan.n_zones):
            layout = tzp.build_zone_layout(graph, plan,
                                           layout=cfg.zone_layout,
                                           e_cap=cfg.e_cap,
                                           pad_zones_to=pad_zones,
                                           n_shards=n_shards)
        return plan, layout

    def _note_run(self, keys, layout: tzp.ZoneBatchLayout,
                  run_stats: dict) -> None:
        """Account one layout run: its executions, launches, sweep slots,
        merge rows and layout.

        A fused run is one launch of one executable, so the whole layout
        resolves to a single key ("fused", or "fused_<backend>" when
        dispatch rerouted the kernel, e.g. "fused_xla" on CPU); otherwise
        each bucket has its own.
        """
        if str(run_stats.get("path", "")).startswith("fused"):
            self._note_execution(keys[0], layout.n_zones)
            self.stats.fused_runs += 1
        else:
            for key, bucket in zip(keys, layout.buckets):
                self._note_execution(key, bucket.n_zones)
        self.stats.launches += int(run_stats.get("launches", 0))
        self.stats.sweep_slots += int(run_stats.get("sweep_slots", 0))
        self.stats.merge_rows += int(run_stats.get("merge_rows", 0))
        self._note_layout(layout)

    def _note_layout(self, layout: tzp.ZoneBatchLayout) -> None:
        self.stats.padding_ratio = layout.padding_ratio
        self.stats.bucket_occupancy = {
            b.label or "dense": b.occupancy for b in layout.buckets}

    def discover(self, graph: TemporalGraph) -> DiscoveryResult:
        """PTMT parallel discovery (plan zones → expand → aggregate).

        The zone batch is laid out per ``config.zone_layout`` (size
        buckets by default when zone sizes are skewed); repeated calls on
        recurring bucket shapes dispatch to cached executables
        (``stats.compile_cache_hits``) and repeated calls on the same
        graph skip planning (``stats.plan_cache_hits``).

        Spans: ``engine.mine`` around the whole call; inside it
        ``engine.discover`` (planning, layout and the executor), then
        ``engine.d2h`` (the count table's copy to the host) and
        ``engine.decode`` (the table rendered into the result's dict; its
        ``codes`` is the number of live codes decoded).
        """
        self.stats.discover_calls += 1
        tracer = self.obs.tracer
        with tracer.span("engine.mine", n_edges=graph.n_edges):
            with tracer.span("engine.discover",
                             n_edges=graph.n_edges) as sp:
                plan, layout = self._plan_and_layout(graph)
                keys = self.executor.layout_execution_keys(layout)
                counts, run_stats = self.executor.run_layout(
                    layout, allow_overflow=self.config.allow_overflow)
                sp.set(n_zones=plan.n_zones, path=run_stats.get("path"))
            self._note_run(keys, layout, run_stats)
            with tracer.span("engine.d2h", rows=int(counts.counts.shape[0])):
                counts = jax.device_get(counts)
            with tracer.span("engine.decode") as sp:
                result = counts_to_result(
                    counts, n_zones=plan.n_zones, e_cap=layout.e_cap,
                    overflow=layout.overflow, delta=self.config.delta,
                    l_max=self.config.l_max,
                    layout={**layout.summary(),
                            "execution": dict(run_stats)},
                )
                sp.set(codes=len(result.counts))
            return result

    # -- config-lattice co-mining --------------------------------------------

    def _lattice_executor(self, dominating: MiningConfig) -> MiningExecutor:
        """Warm executor for a lattice's dominating sweep config."""
        if dominating == self.config:
            return self.executor
        ex = self._lattice_executors.get(dominating)
        if ex is not None:
            self._lattice_executors[dominating] = \
                self._lattice_executors.pop(dominating)   # LRU bump
            return ex
        ex = MiningExecutor.from_config(dominating, obs=self.obs)
        self._lattice_executors[dominating] = ex
        while len(self._lattice_executors) > self._lattice_executor_cap:
            self._lattice_executors.pop(next(iter(self._lattice_executors)))
        return ex

    def discover_many(self, graph: TemporalGraph,
                      configs) -> list[DiscoveryResult]:
        """Co-mine N tenant configs from shared dominating Phase-1 sweeps.

        ``configs`` is a sequence of :class:`MiningConfig`s over the SAME
        graph.  Configs differing only in ``delta``/``l_max``/``omega``
        group into one lattice (:func:`repro.core.planner.
        build_config_lattices`) and share ONE Phase-1 expansion planned at
        the dominating ``(max delta, max l_max, max omega)``; each
        member's count table is split out during the Phase-2 fold by
        prefix-truncating candidates on per-edge absorption timestamps.
        Results are byte-identical to per-config :meth:`discover` calls
        (the differential tests assert it), returned in input order.
        """
        configs = list(configs)
        if not configs:
            return []
        self.stats.discover_many_calls += 1
        self.stats.comined_configs += len(configs)
        results: list[DiscoveryResult | None] = [None] * len(configs)
        lattices = planner.build_config_lattices(configs)
        with self.obs.tracer.span("engine.discover_many",
                                  n_edges=graph.n_edges,
                                  n_configs=len(configs),
                                  n_lattices=len(lattices)):
            for lat in lattices:
                self._discover_lattice(graph, lat, results)
        return results

    def _discover_lattice(self, graph: TemporalGraph,
                          lat: planner.ConfigLattice, results: list) -> None:
        """Mine one lattice's shared sweep and scatter member results."""
        dom = lat.dominating
        ex = self._lattice_executor(dom)
        plan, layout = self._plan_and_layout(graph, config=dom, executor=ex)
        params = lat.params
        # compile-cache accounting: a multi-config fold compiles its own
        # executable per (sweep key, member params) — distinct from the
        # single-config executable the same layout would use
        keys = tuple(k + (("multi",) + params,)
                     for k in ex.layout_execution_keys(layout))
        counts_tuple, run_stats = ex.run_layout_multi(
            layout, params, allow_overflow=dom.allow_overflow)
        self._note_run(keys, layout, run_stats)
        layout_summary = {**layout.summary(), "execution": dict(run_stats)}
        for member, idx, counts in zip(lat.members, lat.indices,
                                       counts_tuple):
            results[idx] = counts_to_result(
                counts, n_zones=plan.n_zones, e_cap=layout.e_cap,
                overflow=layout.overflow, delta=member.delta,
                l_max=member.l_max, layout=layout_summary,
            )

    def sequential(self, graph: TemporalGraph) -> DiscoveryResult:
        """TMC-analog baseline: one zone spanning the whole stream (no TZP).

        Always the dense layout (a single zone has nothing to bucket) —
        the one-zone batch goes through the same
        :func:`~repro.core.tzp.build_zone_batch` padding policy as every
        other mode.
        """
        self.stats.sequential_calls += 1
        plan = tzp.single_zone_plan(graph, l_b=self.config.l_b)
        layout = tzp.build_zone_layout(graph, plan, layout="dense")
        batch = layout.buckets[0]
        key = self.executor.execution_key(batch.n_zones, batch.e_cap)
        counts = self.executor.run(batch)
        self._note_execution(key, batch.n_zones)
        return counts_to_result(
            counts, n_zones=1, e_cap=batch.e_cap, overflow=batch.overflow,
            delta=self.config.delta, l_max=self.config.l_max,
            layout=layout.summary(),
        )

    # -- streaming ----------------------------------------------------------

    def stream(self, **overrides) -> StreamingMiner:
        """A fresh :class:`StreamingMiner` bound to this engine's config.

        Without overrides the miner shares this engine's executor (and so
        its warm jit state); with overrides a derived config (and executor)
        is built for the miner alone.  Either way the miner counts its
        scan launches into ``stats.stream_launches``.
        """
        self.stats.stream_sessions += 1
        if overrides:
            return StreamingMiner(config=self.config.with_updates(
                **overrides), obs=self.obs, stats=self.stats)
        return StreamingMiner(config=self.config, executor=self.executor,
                              obs=self.obs, stats=self.stats)

    # -- mesh path ----------------------------------------------------------

    def sharded(
        self,
        graph: TemporalGraph,
        mesh,
        axes: tuple[str, ...] | None = None,
        *,
        out_cap: int = 65536,
        merge_mode: str = "flat",
    ) -> DiscoveryResult:
        """Distributed discovery with zones sharded over ``mesh``.

        The jitted SPMD mining step is cached per ``(mesh, axes, out_cap,
        merge_mode)``; with a bucketed layout each bucket is sharded over
        the mesh independently (its zones were round-robined across the
        shard lanes at build time) and the replicated per-bucket tables
        merge host-side through the same bounded carry as the local path.
        """
        from repro.distributed import mining as dist_mining

        self.stats.sharded_calls += 1
        axes = tuple(axes or mesh.axis_names)
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        plan, layout = self._plan_and_layout(graph, n_shards=n_shards)
        MiningExecutor.check_layout_overflow(
            layout, allow_overflow=self.config.allow_overflow)

        step_key = (mesh, axes, out_cap, merge_mode)
        fn = self._mesh_steps.get(step_key)
        if fn is None:
            fn = dist_mining.make_mine_step(
                mesh, axes, executor=self.executor, out_cap=out_cap,
                merge_mode=merge_mode,
            )
            self._mesh_steps[step_key] = fn
        # sharded executables are per SPMD step, not shared with the local
        # jit cache — key on the step too, or a first sharded call after a
        # same-shaped discover would misreport as a cache hit
        def note(bucket):
            key = (step_key,
                   self.executor.execution_key(bucket.n_zones, bucket.e_cap))
            self._note_execution(key, bucket.n_zones)

        counts = dist_mining.run_mine_layout(
            fn, layout, out_cap=out_cap,
            merge_cap=self.executor.merge_cap, on_bucket=note)
        self._note_layout(layout)
        return counts_to_result(
            counts, n_zones=plan.n_zones, e_cap=layout.e_cap,
            overflow=layout.overflow, delta=self.config.delta,
            l_max=self.config.l_max, layout=layout.summary(),
        )
