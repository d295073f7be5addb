"""Ragged zone batching: size-bucketed layout invariants.

The differential guarantees (bucketed == dense == oracle across backends)
live in ``tests/test_differential.py``; this file covers the layout
machinery itself — bucket capacity math, padding/occupancy accounting,
empty-zone dropping, plan serialization, the engine-level zone-plan cache,
and the bucket-named error paths.
"""

import argparse
import warnings

import numpy as np
import pytest

from repro.core import (
    MiningConfig,
    MiningExecutor,
    PTMTEngine,
    StreamingMiner,
    ZoneChunkError,
    planner,
    transitions,
    tzp,
)
from repro.core.temporal_graph import TemporalGraph, from_edges


@pytest.fixture(autouse=True, scope="module")
def _fresh_jit_caches():
    """The mesh tests below compile large SPMD (shard_map) executables;
    at the tail of a full-suite run the jit caches hold every executable
    the preceding ~300 tests compiled, and that accumulated state has
    been observed to push the XLA:CPU compiler into a segfault on this
    module's first shard_map compile (jax 0.4.37 — the same test passes
    in isolation and after either suite half alone).  Every test here
    compiles its own executables anyway, so start from a clean cache."""
    import jax

    jax.clear_caches()


def _skewed_graph(seed=0, n=300, nodes=10):
    """Bursts of very different sizes + quiet gaps: >= 3 buckets."""
    rng = np.random.default_rng(seed)
    us, vs, ts = [], [], []
    now = 0
    for burst in (3, 50, 7, 28, 2, 60, 12, 40, 5, 33, 9, 51):
        group = rng.integers(0, nodes, size=max(2, burst // 4 + 2))
        for _ in range(burst):
            a, b = rng.choice(group, 2, replace=True)
            us.append(a)
            vs.append(b)
            ts.append(now + int(rng.integers(0, 25)))
        now += 400 + int(rng.integers(0, 200))
    return from_edges(np.asarray(us[:n]), np.asarray(vs[:n]),
                      np.asarray(ts[:n]))


PARAMS = dict(delta=10, l_max=3, omega=2)


# ---------------------------------------------------------------------------
# Bucket capacity math.
# ---------------------------------------------------------------------------


def test_bucket_caps_power_of_two_floor_and_clip():
    counts = np.asarray([0, 1, 7, 8, 9, 100, 4000])
    caps = tzp.bucket_caps(counts, max_cap=512, pad_edges_to=8)
    assert caps.tolist() == [8, 8, 8, 8, 16, 128, 512]
    # non-pow2 pad_edges_to: caps stay aligned to what build_zone_batch
    # will allocate (pow2 floor 16, re-rounded to the 12-multiple 24)
    assert tzp.bucket_caps(np.asarray([1]), max_cap=512,
                           pad_edges_to=12).tolist() == [24]


def test_non_pow2_pad_edges_to_keeps_labels_and_shapes_aligned():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed", pad_edges_to=12)
    for b in lay.buckets:
        assert b.label == f"cap{b.e_cap}"       # label == allocated shape
    caps = [b.e_cap for b in lay.buckets]
    assert len(caps) == len(set(caps))          # one bucket per geometry


def test_empty_plan_bucketed_layout_honors_shard_padding():
    g = TemporalGraph(u=np.zeros(0, np.int32), v=np.zeros(0, np.int32),
                      t=np.zeros(0, np.int32), n_nodes=0)
    plan = tzp.plan_zones(g, delta=5, l_max=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed",
                                pad_zones_to=4, n_shards=4)
    assert lay.buckets[0].n_zones % 4 == 0      # shardable zone axis


def test_layout_padding_strictly_lower_on_skewed_plan():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    dense = tzp.build_zone_layout(g, plan, layout="dense")
    buck = tzp.build_zone_layout(g, plan, layout="bucketed")
    assert buck.n_buckets >= 3
    assert buck.padding_ratio < dense.padding_ratio
    assert buck.sweep_slots < dense.sweep_slots
    # same real edges, identical overflow, top bucket == dense capacity
    assert buck.valid_edges == dense.valid_edges
    assert buck.overflow == dense.overflow == 0
    assert buck.e_cap == dense.e_cap
    # every zone of the plan is either placed once or empty
    placed = np.concatenate([b.perm[b.perm >= 0] for b in buck.buckets])
    expected = np.flatnonzero(np.asarray(plan.count) > 0)
    assert sorted(placed.tolist()) == expected.tolist()


def test_empty_zones_are_dropped_not_padded():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    assert (np.asarray(plan.count) == 0).any(), "need empty zones"
    buck = tzp.build_zone_layout(g, plan, layout="bucketed")
    assert buck.n_zones == int((np.asarray(plan.count) > 0).sum())


def test_all_empty_plan_builds_inert_bucket():
    g = TemporalGraph(u=np.zeros(0, np.int32), v=np.zeros(0, np.int32),
                      t=np.zeros(0, np.int32), n_nodes=0)
    plan = tzp.plan_zones(g, delta=5, l_max=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    ex = MiningExecutor(delta=5, l_max=2)
    assert transitions.device_counts_to_dict(ex.run_layout(lay).counts) == {}


def test_resolve_layout_rules():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    assert tzp.resolve_layout(plan, "auto") == "bucketed"
    assert tzp.resolve_layout(plan, "dense") == "dense"
    single = tzp.single_zone_plan(g, l_b=30)
    assert tzp.resolve_layout(single, "auto") == "dense"
    with pytest.raises(ValueError, match="unknown zone layout"):
        tzp.resolve_layout(plan, "ragged")


# ---------------------------------------------------------------------------
# ZonePlan serialization + graph fingerprint.
# ---------------------------------------------------------------------------


def test_zone_plan_json_round_trip():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    back = tzp.ZonePlan.from_json(plan.to_json())
    assert back == plan
    assert tzp.ZonePlan.from_json(
        {"lo": [], "count": [], "sign": [], "t_start": [], "t_end": [],
         "l_b": 30}).n_zones == 0
    with pytest.raises(ValueError, match="unknown ZonePlan field"):
        tzp.ZonePlan.from_json('{"lo": [], "bogus": 1}')


def test_graph_fingerprint_tracks_content():
    g1 = _skewed_graph(seed=0)
    g2 = _skewed_graph(seed=0)
    g3 = _skewed_graph(seed=1)
    assert tzp.graph_fingerprint(g1) == tzp.graph_fingerprint(g2)
    assert tzp.graph_fingerprint(g1) != tzp.graph_fingerprint(g3)


# ---------------------------------------------------------------------------
# Engine integration: plan cache, per-bucket compile keys, stats.
# ---------------------------------------------------------------------------


def test_engine_plan_cache_skips_replanning():
    g = _skewed_graph()
    eng = PTMTEngine(MiningConfig(zone_layout="bucketed", **PARAMS))
    r1 = eng.discover(g)
    assert eng.stats.plan_cache_misses == 1
    assert eng.stats.plan_cache_hits == 0
    r2 = eng.discover(g)
    assert eng.stats.plan_cache_hits == 1
    assert r1.counts == r2.counts
    # a different stream is a miss, not a poisoned hit
    eng.discover(_skewed_graph(seed=3))
    assert eng.stats.plan_cache_misses == 2


def test_engine_compile_cache_counts_bucket_shapes():
    g = _skewed_graph()
    eng = PTMTEngine(MiningConfig(zone_layout="bucketed", **PARAMS))
    r1 = eng.discover(g)
    n_buckets = len(r1.layout["buckets"])
    assert n_buckets >= 3
    assert eng.stats.compile_cache_misses == n_buckets
    eng.discover(g)
    assert eng.stats.compile_cache_hits == n_buckets


def test_engine_stats_and_result_carry_layout_summary():
    g = _skewed_graph()
    eng = PTMTEngine(MiningConfig(zone_layout="bucketed", **PARAMS))
    res = eng.discover(g)
    assert res.layout["kind"] == "bucketed"
    assert 0.0 <= res.layout["padding_ratio"] < 1.0
    assert eng.stats.padding_ratio == res.layout["padding_ratio"]
    assert set(eng.stats.bucket_occupancy) == {
        b["label"] for b in res.layout["buckets"]}
    dense = PTMTEngine(MiningConfig(zone_layout="dense", **PARAMS))
    dres = dense.discover(g)
    assert dres.layout["kind"] == "dense"
    assert res.layout["padding_ratio"] < dres.layout["padding_ratio"]
    assert res.counts == dres.counts


def test_streaming_inherits_layout_and_stays_exact():
    g = _skewed_graph()
    eng = PTMTEngine(MiningConfig(zone_layout="bucketed", **PARAMS))
    batch = eng.discover(g)
    m = eng.stream()
    for i in range(0, g.n_edges, 53):
        m.ingest(g.u[i:i + 53], g.v[i:i + 53], g.t[i:i + 53])
    assert m.snapshot(final=True).counts == batch.counts
    assert m.last_tail_layout is None or "kind" in m.last_tail_layout


def test_streaming_tail_cache_keyed_by_layout_signature():
    g = _skewed_graph()
    m = StreamingMiner(config=MiningConfig(zone_layout="bucketed", **PARAMS))
    m.ingest(g.u, g.v, g.t)
    m.snapshot()
    m.snapshot()
    assert (m.tail_cache_misses, m.tail_cache_hits) == (1, 1)
    # a layout-affecting change invalidates the cached tail mine
    object.__setattr__(m.config, "zone_layout", "dense")
    m.snapshot()
    assert m.tail_cache_misses == 2


# ---------------------------------------------------------------------------
# Bucket-named error paths.
# ---------------------------------------------------------------------------


def test_zone_chunk_raise_names_bucket():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    odd = max(lay.buckets, key=lambda b: b.n_zones)
    assert odd.n_zones >= 3, "corpus must give a multi-zone bucket"
    zc = odd.n_zones - 1          # never divides n_zones (remainder 1)
    ex = MiningExecutor(delta=PARAMS["delta"], l_max=PARAMS["l_max"],
                        zone_chunk=zc, pad_policy="raise")
    with pytest.raises(ZoneChunkError, match=odd.label):
        ex.run(odd)
    # pad policy pads the same bucket silently and stays exact
    pad_ex = MiningExecutor(delta=PARAMS["delta"], l_max=PARAMS["l_max"],
                            zone_chunk=zc, pad_policy="pad")
    base = MiningExecutor(delta=PARAMS["delta"], l_max=PARAMS["l_max"],
                          zone_chunk=0)
    assert transitions.device_counts_to_dict(pad_ex.run(odd)) == \
        transitions.device_counts_to_dict(base.run(odd))


# ---------------------------------------------------------------------------
# Config + planner surface.
# ---------------------------------------------------------------------------


def test_config_zone_layout_validation_and_cli():
    with pytest.raises(ValueError, match="unknown zone layout"):
        MiningConfig(zone_layout="ragged")
    ap = argparse.ArgumentParser()
    MiningConfig.add_cli_args(ap)
    cfg = MiningConfig.from_cli_args(
        ap.parse_args(["--zone-layout", "bucketed"]))
    assert cfg.zone_layout == "bucketed"
    assert MiningConfig.from_json(cfg.to_json()) == cfg


def test_planner_per_bucket_capacity_beats_global_max():
    g = _skewed_graph()
    plan = tzp.plan_zones(g, **PARAMS)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    plans = planner.plan_layout_capacity(
        lay.bucket_shapes(), l_max=PARAMS["l_max"], memory_budget_mb=0.5)
    assert set(plans) == set(lay.bucket_shapes())
    # at a fixed zone count, a smaller bucket capacity admits at least as
    # large a chunk — the per-bucket derivation the dense global max loses
    tight = dict(l_max=PARAMS["l_max"], memory_budget_mb=0.05)
    assert planner.plan_capacity(n_zones=256, e_cap=16, **tight).zone_chunk \
        > planner.plan_capacity(n_zones=256, e_cap=2048, **tight).zone_chunk
    assert planner.layout_peak_bytes(plans) == max(
        p.est_peak_bytes for p in plans.values())


def test_executor_capacity_plan_memoized_per_bucket_geometry():
    ex = MiningExecutor(delta=10, l_max=3, memory_budget_mb=0.5)
    p_small = ex.capacity_plan(8, 16)
    p_big = ex.capacity_plan(8, 1024)
    assert p_small.zone_chunk >= p_big.zone_chunk
    assert ex.capacity_plan(8, 16) is p_small


def test_merge_partial_counts_requires_input():
    from repro.core.executor import merge_partial_counts

    with pytest.raises(ValueError):
        merge_partial_counts([])


def test_engine_zone_plan_cache_is_bounded():
    eng = PTMTEngine(MiningConfig(**PARAMS))
    eng._zone_plan_cap = 2
    graphs = [_skewed_graph(seed=s, n=60) for s in range(4)]
    for g in graphs:
        eng.discover(g)
    assert len(eng._zone_plans) == 2
    # the most recent graph is still a hit, the oldest was evicted
    eng.discover(graphs[-1])
    assert eng.stats.plan_cache_hits == 1
    eng.discover(graphs[0])
    assert eng.stats.plan_cache_misses == 5


def test_mine_layout_on_mesh_matches_and_enforces_overflow():
    import jax

    from repro.core import ZoneOverflowError
    from repro.distributed import mining as dm

    g = _skewed_graph()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("z",))
    cfg = MiningConfig(**PARAMS)
    plan = tzp.plan_zones(g, **PARAMS)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    counts = dm.mine_layout_on_mesh(lay, mesh, ("z",), config=cfg)
    expect = PTMTEngine(cfg).discover(g).counts
    assert transitions.device_counts_to_dict(counts) == expect

    # overflowed layouts are refused, same policy as the local run_layout
    tight = tzp.plan_zones(g, delta=PARAMS["delta"],
                           l_max=PARAMS["l_max"], omega=2, e_cap=4)
    tight_lay = tzp.build_zone_layout(g, tight, layout="bucketed", e_cap=4)
    assert tight_lay.overflow > 0
    with pytest.raises(ZoneOverflowError, match="bucket"):
        dm.mine_layout_on_mesh(tight_lay, mesh, ("z",), config=cfg)
    with pytest.warns(RuntimeWarning, match="dropped"):
        dm.mine_layout_on_mesh(tight_lay, mesh, ("z",), config=cfg,
                               allow_overflow=True)
