"""PTMTEngine: every mode agrees, compiled plans are reused, and the
serving layer can share one engine across sessions."""

import warnings

import numpy as np
import pytest

from repro.core import (
    MiningConfig,
    PTMTEngine,
    StreamingMiner,
    ZoneOverflowError,
    oracle,
    tzp,
)
from repro.serving.motif import MotifSession

from conftest import random_graph

CFG = MiningConfig(delta=60, l_max=3, omega=4)


def _graph(seed=5, n=300):
    return random_graph(seed, n, 25, 3_000)


# -- mode agreement ---------------------------------------------------------

def test_discover_sequential_stream_agree_and_match_oracle():
    g = _graph()
    engine = PTMTEngine(CFG)
    res = engine.discover(g)
    seq = engine.sequential(g)
    assert res.counts == seq.counts
    assert seq.n_zones == 1

    miner = engine.stream()
    assert miner.executor is engine.executor     # shared warm backend
    for i in range(0, g.n_edges, 64):
        miner.ingest(g.u[i:i + 64], g.v[i:i + 64], g.t[i:i + 64])
    assert miner.snapshot(final=True).counts == res.counts

    expect = dict(oracle.count_codes(g.u, g.v, g.t, CFG.delta, CFG.l_max))
    assert res.counts == expect


def test_sequential_routes_through_zone_batch_padding():
    """The baseline's padding comes from build_zone_batch (pad_edges_to=8),
    not a hand-rolled zero block."""
    g = _graph(seed=2, n=29)
    plan = tzp.single_zone_plan(g, l_b=CFG.l_b)
    assert plan.n_zones == 1 and int(plan.count[0]) == 29
    batch = tzp.build_zone_batch(g, plan)
    assert batch.e_cap == 32 and batch.overflow == 0
    res = PTMTEngine(CFG).sequential(g)
    assert res.e_cap == 32


def test_engine_overrides_and_config_reuse():
    engine = PTMTEngine(CFG, backend="numpy")
    assert engine.config.backend == "numpy"
    assert engine.config.delta == CFG.delta
    assert engine.backend == "numpy"
    # stream(**overrides) derives a new config without touching the engine's
    miner = engine.stream(omega=6)
    assert miner.omega == 6 and engine.config.omega == 4
    assert miner.executor is not engine.executor


# -- compiled-plan reuse ----------------------------------------------------

def test_same_shape_discover_registers_compile_cache_hit():
    g = _graph()
    engine = PTMTEngine(CFG)
    res = engine.discover(g)
    misses = engine.stats.compile_cache_misses
    n_buckets = len(res.layout["buckets"])
    assert engine.stats.compile_cache_hits == 0
    assert misses == n_buckets       # one executable per bucket shape
    engine.discover(g)
    assert engine.stats.compile_cache_hits == n_buckets
    assert engine.stats.compile_cache_misses == misses
    assert engine.stats.discover_calls == 2


def test_different_shape_is_a_miss():
    engine = PTMTEngine(CFG)
    engine.discover(_graph(seed=1, n=300))
    engine.discover(_graph(seed=2, n=2_000))   # different zone geometry
    assert engine.stats.compile_cache_misses >= 2


def test_execution_key_mirrors_padding_and_agg_resolution():
    from repro.core.executor import MiningExecutor

    ex = MiningExecutor(delta=60, l_max=3, zone_chunk=4, agg="auto")
    key_pad = ex.execution_key(10, 64)     # pads 10 -> 12 zones
    assert key_pad == ex.execution_key(12, 64)
    assert key_pad[3] == 12 and key_pad[6] == "hierarchical"
    key_small = ex.execution_key(2, 64)    # zc >= z: unchunked, legacy
    assert key_small[6] == "legacy" and key_small[7] == 0


def test_allow_overflow_flows_from_config():
    g = _graph(seed=7, n=400)
    tight = CFG.with_updates(e_cap=8)
    engine = PTMTEngine(tight)
    with pytest.raises(ZoneOverflowError):
        engine.discover(g)
    # a failed run compiled nothing — it must not poison the reuse stats
    assert engine.stats.compile_cache_misses == 0
    assert engine.stats.zones_mined == 0
    with pytest.warns(RuntimeWarning, match="allow_overflow"):
        res = PTMTEngine(tight.with_updates(allow_overflow=True)).discover(g)
    assert res.overflow > 0


def test_capacity_plan_memoized_per_geometry():
    engine = PTMTEngine(CFG, memory_budget_mb=8.0)
    a = engine.capacity_plan(512, 128)
    assert a is engine.capacity_plan(512, 128)    # same object: memoized
    assert a is not engine.capacity_plan(1024, 128)
    assert PTMTEngine(CFG).capacity_plan(512, 128) is None  # no budget


# -- serving integration ----------------------------------------------------

def test_motif_session_shares_engine_executor():
    engine = PTMTEngine(CFG)
    sess = MotifSession("t0", engine=engine, ingest_batch=64)
    assert sess.miner.executor is engine.executor
    assert sess.config == CFG
    assert engine.stats.stream_sessions == 1

    g = _graph(seed=9, n=256)
    sess.ingest(g.u, g.v, g.t)
    sess.flush()
    total = sess.engine().total_processes()
    # closed-prefix consistency: served totals equal a snapshot's
    assert total == sess.miner.snapshot().total_processes()


def test_motif_session_engine_with_per_tenant_overrides():
    """SessionManager's deployment shape: engine= in session_defaults,
    per-tenant create(**params) overrides win (via engine.stream)."""
    engine = PTMTEngine(CFG)
    sess = MotifSession("t0", engine=engine, omega=6)
    assert sess.config.omega == 6 and engine.config.omega == 4
    assert sess.miner.executor is not engine.executor   # derived config
    with pytest.raises(ValueError, match="not both"):
        MotifSession("t0", engine=engine, config=CFG)


def test_streaming_miner_rejects_config_plus_params():
    with pytest.raises(ValueError, match="not both"):
        StreamingMiner(config=CFG, delta=60)


def test_streaming_miner_requires_delta_l_max_without_config():
    """No silent fallback to the MiningConfig defaults — a forgotten delta
    must fail loudly, not mine with delta=600."""
    with pytest.raises(ValueError, match="delta and l_max are required"):
        StreamingMiner(omega=8)
    with pytest.raises(ValueError, match="delta and l_max are required"):
        MotifSession("t0", l_max=3)


def test_streaming_miner_rejects_disagreeing_executor():
    from repro.core import MiningExecutor

    with pytest.raises(ValueError, match="disagrees with config"):
        StreamingMiner(config=CFG,
                       executor=MiningExecutor(delta=50, l_max=3))


def test_legacy_streaming_kwargs_still_build_a_config():
    miner = StreamingMiner(delta=60, l_max=3, omega=4, backend="ref")
    assert miner.config == CFG


# -- mesh path --------------------------------------------------------------

def test_sharded_caches_mesh_step_and_matches_single_device():
    import jax

    g = _graph(seed=11, n=256)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("z",))
    engine = PTMTEngine(CFG, zone_chunk=2)
    engine.discover(g)
    hits_before = engine.stats.compile_cache_hits
    a = engine.sharded(g, mesh, ("z",))
    # a first sharded call compiles its own SPMD step even after a
    # same-shaped local discover — it must NOT register as a cache hit
    assert engine.stats.compile_cache_hits == hits_before
    b = engine.sharded(g, mesh, ("z",))
    assert engine.stats.compile_cache_hits == \
        hits_before + len(a.layout["buckets"])
    assert a.counts == b.counts
    assert len(engine._mesh_steps) == 1      # step compiled once, reused
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert a.counts == PTMTEngine(CFG).discover(g).counts


# -- spans and counters ------------------------------------------------------

def test_spans_of_one_mine_share_the_root_engine_mine():
    import repro.obs as obs_mod

    obs = obs_mod.enabled()
    engine = PTMTEngine(CFG, obs=obs)
    g = _graph()
    res = engine.discover(g)
    engine.discover(g)
    events = obs.tracer.events()
    mines = [e for e in events if e["name"] == "engine.mine"]
    assert len(mines) == 2
    first = mines[0]["args"]["span_id"]
    mine_events = [e for e in events if e["args"]["root_id"] == first]
    names = {e["name"] for e in mine_events}
    assert {"engine.discover", "engine.d2h", "engine.decode",
            "mine.launch"} <= names
    by_name = {e["name"]: e for e in mine_events}
    discover = by_name["engine.discover"]
    d2h, decode = by_name["engine.d2h"], by_name["engine.decode"]
    # the decode spans follow engine.discover, outside it, in order
    for span in (d2h, decode):
        assert span["args"]["parent_id"] == first
        assert span["ts"] >= discover["ts"] + discover["dur"]
    assert decode["ts"] >= d2h["ts"] + d2h["dur"]
    assert res.counts == engine.discover(g).counts


def test_stream_pairs_are_split_and_their_launches_counted():
    import repro.obs as obs_mod

    obs = obs_mod.enabled()
    engine = PTMTEngine(CFG, obs=obs)
    g = _graph()
    miner = engine.stream()
    for i in range(0, g.n_edges, 64):
        miner.ingest(g.u[i:i + 64], g.v[i:i + 64], g.t[i:i + 64])
    events = obs.tracer.events()
    ids = {e["args"]["span_id"]: e for e in events}
    finalize = [e for e in events if e["name"] == "stream.finalize"]
    assert finalize
    for name in ("stream.pair_layout", "stream.pair_mine",
                 "stream.pair_merge"):
        spans = [e for e in events if e["name"] == name]
        assert len(spans) == len(finalize)
        for e in spans:
            assert ids[e["args"]["parent_id"]]["name"] == "stream.finalize"
            assert ids[e["args"]["root_id"]]["name"] == "stream.ingest"
    pairs = engine.stats.stream_launches
    assert pairs == len(finalize)        # one dense launch per pair
    miner.snapshot(final=True)
    assert engine.stats.stream_launches > pairs         # the tail mine
    assert engine.stats.launches == 0                   # no batch mine
    assert obs.metrics.counter("repro_mining_launches_total",
                               path="stream").value \
        == engine.stats.stream_launches
    # a standalone miner counts into no engine
    solo = StreamingMiner(config=CFG)
    solo.ingest(g.u, g.v, g.t)
    assert solo.stats is None


@pytest.mark.parametrize("fused", ["off", "on"])
def test_engine_counts_sweep_slots_and_mirrors_them(fused):
    import repro.obs as obs_mod

    obs = obs_mod.enabled()
    engine = PTMTEngine(CFG.with_updates(fused=fused, fused_backend="xla"),
                        obs=obs)
    g = _graph()
    res = engine.discover(g)
    run = res.layout["execution"]
    assert run["sweep_slots"] > 0
    assert engine.stats.sweep_slots == run["sweep_slots"]
    engine.discover(g)
    assert engine.stats.sweep_slots == 2 * run["sweep_slots"]
    assert obs.metrics.counter("repro_mining_sweep_slots_total",
                               path=run["path"]).value \
        == engine.stats.sweep_slots


@pytest.mark.parametrize("fused", ["off", "on"])
def test_engine_counts_merge_rows_and_mirrors_them(fused):
    """The per-bucket path's cross-bucket merge rows reach the engine's
    stats and the registry alike; the fused path has no such merge."""
    import repro.obs as obs_mod

    obs = obs_mod.enabled()
    engine = PTMTEngine(CFG.with_updates(fused=fused, fused_backend="xla"),
                        obs=obs)
    g = _graph()
    res = engine.discover(g)
    run = res.layout["execution"]
    engine.discover(g)
    rows = run.get("merge_rows", 0)
    assert engine.stats.merge_rows == 2 * rows
    assert obs.metrics.counter("repro_mining_merge_rows_total",
                               path=run["path"]).value \
        == engine.stats.merge_rows
    if fused == "off":
        assert len(res.layout["buckets"]) > 1 and rows > 0
    else:
        assert "merge_rows" not in run
