"""MiningExecutor + backend registry: dispatch, chunk policy, oracle parity.

Covers the regression for the pre-refactor silent zone drop: ``_mine_batch``
computed ``nchunk = z // zone_chunk`` and discarded the remainder zones when
``zone_chunk`` did not divide the zone count.  The executor must pad (default)
or raise — never drop.
"""

import numpy as np
import pytest

from repro.core import (
    MiningExecutor,
    ZoneChunkError,
    available_backends,
    backends,
    get_backend,
    oracle,
    transitions,
    tzp,
)
from conftest import batch_discover, random_graph


def _counts_dict(counts):
    return transitions.counts_to_dict(
        np.asarray(counts.codes), np.asarray(counts.counts),
        np.asarray(counts.unique_mask),
    )


def _batch_for(g, *, delta, l_max, omega=2, pad_zones_to=1):
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=omega)
    return plan, tzp.build_zone_batch(g, plan, pad_zones_to=pad_zones_to)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------


def test_builtin_backends_registered():
    assert {"ref", "pallas", "numpy"} <= set(available_backends())
    assert get_backend("ref").jittable
    assert not get_backend("numpy").jittable
    assert get_backend("numpy").grade == "oracle"
    assert get_backend("pallas").block_defaults["c_blk"] > 0


def test_unknown_backend_lists_available():
    with pytest.raises(ValueError, match="available"):
        get_backend("no-such-backend")
    with pytest.raises(ValueError, match="available"):
        MiningExecutor(delta=5, l_max=3, backend="no-such-backend")


def test_register_backend_rejects_duplicates_and_accepts_plugins():
    with pytest.raises(ValueError, match="already registered"):
        backends.register_backend("ref", lambda: None)
    spec = backends.register_backend(
        "test-plugin", lambda: get_backend("ref").scan, grade="reference",
    )
    try:
        assert "test-plugin" in available_backends()
        g = random_graph(0, 60, 6, 200)
        got = batch_discover(g, delta=20, l_max=3, omega=2, backend="test-plugin")
        expect = batch_discover(g, delta=20, l_max=3, omega=2, backend="ref")
        assert got.counts == expect.counts
        assert spec.scan is get_backend("ref").scan
    finally:
        backends._REGISTRY.pop("test-plugin", None)


# ---------------------------------------------------------------------------
# Zone-chunk divisibility (the silent-drop regression).
# ---------------------------------------------------------------------------


def test_executor_pads_non_divisible_zone_chunk():
    """z % zone_chunk != 0 must NOT drop the remainder zones."""
    g = random_graph(7, 350, 10, 900)
    delta, l_max = 30, 4
    plan, batch = _batch_for(g, delta=delta, l_max=l_max, omega=2)
    assert batch.n_zones % 2 == 1, "need an odd zone count for the repro"

    expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
    ex = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=2)
    got = _counts_dict(ex.run(batch))
    assert got == expect


def test_executor_raise_policy():
    g = random_graph(7, 350, 10, 900)
    plan, batch = _batch_for(g, delta=30, l_max=4)
    assert batch.n_zones % 2 == 1
    ex = MiningExecutor(delta=30, l_max=4, zone_chunk=2, pad_policy="raise")
    with pytest.raises(ZoneChunkError, match="not divisible"):
        ex.run(batch)


def test_traceable_path_raises_on_non_divisible():
    """Inside a trace there is no host to pad: scan_aggregate must raise."""
    import jax.numpy as jnp

    ex = MiningExecutor(delta=10, l_max=3, zone_chunk=2)
    z, e = 5, 8
    with pytest.raises(ZoneChunkError, match="not divisible"):
        ex.scan_aggregate(
            jnp.zeros((z, e), jnp.int32), jnp.zeros((z, e), jnp.int32),
            jnp.zeros((z, e), jnp.int32), jnp.zeros((z, e), bool),
            jnp.ones(z, jnp.int32),
        )


def test_chunked_scan_matches_unchunked():
    g = random_graph(3, 240, 8, 600)
    delta, l_max = 25, 4
    plan, batch = _batch_for(g, delta=delta, l_max=l_max, pad_zones_to=4)
    assert batch.n_zones % 4 == 0
    base = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=0)
    chunked = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=4)
    assert _counts_dict(base.run(batch)) == _counts_dict(chunked.run(batch))


# ---------------------------------------------------------------------------
# NumPy oracle backend.
# ---------------------------------------------------------------------------


def test_numpy_backend_matches_oracle_end_to_end():
    for seed in range(3):
        g = random_graph(seed, 180, 9, 500)
        delta, l_max = 35, 4
        expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
        got = batch_discover(g, delta=delta, l_max=l_max, omega=3,
                       backend="numpy")
        assert got.counts == expect, f"seed={seed}"


def test_numpy_scan_matches_ref_scan_per_zone():
    from repro.core import expansion, scan_numpy

    g = random_graph(11, 120, 7, 400)
    plan, batch = _batch_for(g, delta=20, l_max=3)
    a = scan_numpy.scan_zones(batch.u, batch.v, batch.t, batch.valid,
                              delta=20, l_max=3)
    b = expansion.scan_zones(batch.u, batch.v, batch.t, batch.valid,
                             delta=20, l_max=3)
    np.testing.assert_array_equal(a.length, np.asarray(b.length))
    np.testing.assert_array_equal(a.code, np.asarray(b.code))


def test_numpy_backend_rejected_in_traced_context():
    ex = MiningExecutor(delta=10, l_max=3, backend="numpy")
    with pytest.raises(ValueError, match="host-only"):
        ex.scan_aggregate(
            np.zeros((2, 8), np.int32), np.zeros((2, 8), np.int32),
            np.zeros((2, 8), np.int32), np.zeros((2, 8), bool),
            np.ones(2, np.int32),
        )


def test_mesh_requires_jittable_backend():
    import jax

    from repro.distributed import mining

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("z",))
    with pytest.raises(ValueError, match="host-only"):
        mining.make_mine_fn(mesh, ("z",), delta=10, l_max=3,
                            backend="numpy")


# ---------------------------------------------------------------------------
# Capacity planner: budgets instead of hardcoded hints.
# ---------------------------------------------------------------------------


def test_plan_capacity_monotone_in_budget():
    from repro.core import planner

    caps = [
        planner.plan_capacity(n_zones=4096, e_cap=1024, l_max=5,
                              memory_budget_mb=mb).zone_chunk
        for mb in (1, 16, 256, 4096)
    ]
    assert all(a <= b for a, b in zip(caps, caps[1:]))
    assert caps[0] >= 1
    assert all(c & (c - 1) == 0 for c in caps), "power-of-two chunks"


def test_plan_capacity_peak_fits_budget():
    from repro.core import planner

    plan = planner.plan_capacity(n_zones=2048, e_cap=512, l_max=4,
                                 memory_budget_mb=64)
    assert plan.fits
    assert plan.est_peak_bytes <= plan.budget_bytes
    # hierarchical peak is Z-independent: same plan at 16x the zones
    plan_big = planner.plan_capacity(n_zones=32768, e_cap=512, l_max=4,
                                     memory_budget_mb=64)
    assert plan_big.zone_chunk == plan.zone_chunk


def test_pallas_mem_model_exceeds_ref():
    """The Pallas kernel pads the edge axis to block multiples, so its
    planner model must never undercount vs the reference model."""
    from repro.core import planner

    for e_cap in (8, 100, 512, 4096):
        assert (planner.pallas_zone_bytes(e_cap, 5)
                >= planner.ref_zone_bytes(e_cap, 5))


def test_suggest_e_cap_power_of_two_and_budget_scaled():
    from repro.core import planner

    small = planner.suggest_e_cap(l_max=5, memory_budget_mb=4)
    big = planner.suggest_e_cap(l_max=5, memory_budget_mb=512)
    assert small & (small - 1) == 0
    assert big > small


def test_budget_derived_zone_chunk_is_exact():
    """An executor given only a memory budget must still be exact, and must
    actually chunk (derived zone_chunk smaller than the zone count)."""
    g = random_graph(13, 400, 10, 1_000)
    delta, l_max = 30, 4
    plan, batch = _batch_for(g, delta=delta, l_max=l_max, omega=2,
                             pad_zones_to=1)
    ex = MiningExecutor(delta=delta, l_max=l_max, memory_budget_mb=0.75)
    zc = ex._zone_chunk_for(batch.n_zones, batch.e_cap)
    assert 0 < zc < batch.n_zones
    expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
    assert _counts_dict(ex.run(batch)) == expect


def test_executor_rejects_unknown_agg_mode():
    with pytest.raises(ValueError, match="agg mode"):
        MiningExecutor(delta=5, l_max=3, agg="no-such-mode")


# ---------------------------------------------------------------------------
# Names in the device trace.
# ---------------------------------------------------------------------------


def _scopes(lowered_text):
    """The named scopes in a lowered program's op locations."""
    import re

    names = re.findall(r'loc\("([^"]*)"', lowered_text)
    return {part for name in names for part in name.split("/")}


def _small_zone_arrays(z=4, e=32):
    rng = np.random.default_rng(0)
    u = rng.integers(0, 6, (z, e)).astype(np.int32)
    v = rng.integers(0, 6, (z, e)).astype(np.int32)
    t = np.sort(rng.integers(0, 500, (z, e)), axis=1).astype(np.int32)
    valid = np.ones((z, e), bool)
    signs = np.array([1, -1] * (z // 2), np.int32)
    return u, v, t, valid, signs


@pytest.mark.parametrize("program", ["hier", "legacy"])
def test_mining_programs_name_the_scan_and_the_fold(program):
    from repro.core import executor as ex_mod

    arrays = _small_zone_arrays()
    scan = get_backend("ref").scan
    if program == "hier":
        lowered = ex_mod._mine_jit_hier.lower(
            *arrays, delta=60, l_max=3, scan=scan, zone_chunk=2,
            params=((60, 3),), merge_caps=(64,))
    else:
        lowered = ex_mod._mine_jit.lower(
            *arrays, delta=60, l_max=3, scan=scan, zone_chunk=2,
            params=((60, 3),))
    scopes = _scopes(lowered.as_text(debug_info=True))
    assert {"zone_scan", "fold"} <= scopes
    assert "merge" not in scopes


def test_cross_bucket_merge_is_named_merge():
    from repro.core import aggregation
    from repro.core import executor as ex_mod

    a = aggregation.empty_counts(16, 2)
    part = aggregation.empty_counts(64, 2)
    text = ex_mod._merge_part_jit.lower(a, part, cap=16, width=8).as_text(
        debug_info=True)
    scopes = _scopes(text)
    assert "merge" in scopes and "fold" not in scopes
    # the sort covers the carry and the part's first ``width`` rows only
    import re

    sorts = re.findall(r"\}\) : \((tensor<\d+xi32>)", text)
    assert set(sorts) == {"tensor<24xi32>"}
    # and merge_partial_counts runs through it, with an exact result
    g, parts = _bucket_parts()
    merged = ex_mod.merge_partial_counts(parts)
    assert _counts_dict(merged.counts) == dict(
        oracle.count_codes(g.u, g.v, g.t, 60, 3))


def _bucket_parts():
    """A bucketed layout's per-bucket count tables (more than one)."""
    g = random_graph(3, 300, 20, 2_000)
    plan = tzp.plan_zones(g, delta=60, l_max=3, omega=2)
    layout = tzp.build_zone_layout(g, plan, layout="bucketed")
    ex = MiningExecutor(delta=60, l_max=3)
    parts = [ex.run_arrays(b.u, b.v, b.t, b.valid, b.sign)
             for b in layout.buckets]
    assert len(parts) > 1
    return g, parts


def _table(codes, counts, rows):
    """A ``count_codes`` table of ``rows`` rows holding these live codes
    (the rows beyond them are all-zero padding)."""
    from repro.core import aggregation

    c = np.zeros((rows, codes.shape[1]), np.int32)
    w = np.zeros(rows, np.int32)
    c[:len(codes)], w[:len(codes)] = codes, counts
    return aggregation.count_codes(c, w)


def _split_live(part, k, pad=20):
    """``part``'s live codes as two tables: the first ``k`` codes and the
    rest, each with ``pad`` padding rows (so each has a padding group)."""
    mask = np.asarray(part.unique_mask)
    codes = np.asarray(part.codes)[mask]
    counts = np.asarray(part.counts)[mask]
    assert len(codes) > k
    return [_table(codes[:k], counts[:k], k + pad),
            _table(codes[k:], counts[k:], len(codes) - k + pad)]


def _every_slot_live(part):
    """``part`` re-tabled at exactly its live codes: no padding group."""
    mask = np.asarray(part.unique_mask)
    n = int(mask.sum())
    table = _table(np.asarray(part.codes)[mask],
                   np.asarray(part.counts)[mask], n)
    assert bool(np.asarray(table.unique_mask).all())
    return table


TRIM_CASES = {
    "buckets": lambda parts: parts,
    "no-live-rows": lambda parts: parts + [_table(
        np.zeros((0, parts[0].codes.shape[1]), np.int32),
        np.zeros(0, np.int32), 64)],
    "no-padding-group": lambda parts: [_every_slot_live(parts[0]),
                                       *parts[1:]],
    "n-plus-1-is-pow2": lambda parts: [*_split_live(parts[0], 15),
                                       *parts[1:]],
    "n-is-pow2": lambda parts: [*_split_live(parts[0], 16), *parts[1:]],
    "spill-retry": lambda parts: parts,
}


@pytest.mark.parametrize("case", sorted(TRIM_CASES))
def test_merge_sorts_each_parts_live_prefix(case, monkeypatch):
    """The cross-bucket merge takes each part at ``min(rows, next_pow2(n +
    1))`` rows, ``n`` its unique codes, counts ``cap + width`` rows a step
    over every attempt, and stays exact: equal to the oracle."""
    from repro.core import executor as ex_mod

    g, parts = _bucket_parts()
    parts = TRIM_CASES[case](parts)
    steps = []
    merge_step = ex_mod._merge_part_jit

    def spy(carry, part, *, cap, width):
        steps.append((cap, width))
        return merge_step(carry, part, cap=cap, width=width)

    monkeypatch.setattr(ex_mod, "_merge_part_jit", spy)
    live = [int(np.asarray(p.unique_mask).sum()) for p in parts]
    rows = [int(p.unique_mask.shape[0]) for p in parts]
    widths = [min(r, tzp.next_pow2(n + 1)) for r, n in zip(rows, live)]
    if case == "spill-retry":
        with pytest.warns(RuntimeWarning, match="merge spilled"):
            out = ex_mod.merge_partial_counts(parts, merge_cap=8)
        assert len(steps) > len(parts)
        assert steps[0][0] == 8
    else:
        out = ex_mod.merge_partial_counts(parts)
        assert {cap for cap, _ in steps} == {sum(live) + 1}
    assert _counts_dict(out.counts) == dict(
        oracle.count_codes(g.u, g.v, g.t, 60, 3))
    assert len(steps) % len(parts) == 0
    assert [w for _, w in steps] == widths * (len(steps) // len(parts))
    assert out.rows == sum(cap + w for cap, w in steps)
    assert widths[0] < rows[0] or case == "no-padding-group"
    if case == "no-live-rows":
        assert widths[-1] == 1 < rows[-1]
    if case == "no-padding-group":
        assert widths[0] == rows[0] == live[0]
    if case == "n-plus-1-is-pow2":
        assert widths[0] == live[0] + 1 == 16
    if case == "n-is-pow2":
        assert widths[0] == 32 and live[0] == 16


def test_single_part_needs_no_merge():
    from repro.core import executor as ex_mod

    _, parts = _bucket_parts()
    out = ex_mod.merge_partial_counts(parts[:1])
    assert out.counts is parts[0] and out.rows == 0


# ---------------------------------------------------------------------------
# What the Phase-1 scan sweeps, and the spans inside a bucket launch.
# ---------------------------------------------------------------------------


def _bucketed_layout(seed=3, omega=2):
    g = random_graph(seed, 400, 20, 3_000)
    plan = tzp.plan_zones(g, delta=60, l_max=3, omega=omega)
    layout = tzp.build_zone_layout(g, plan, layout="bucketed")
    assert layout.n_buckets > 1
    return layout


def _horizon_trips(t, valid, span):
    """``1 + max(h_i - i)`` over valid lanes, row by row: ``h_i`` is the
    last slot whose suffix minimum of valid timestamps is within ``span``
    of lane ``i``'s seed."""
    best = 0
    for row_t, row_valid in zip(np.asarray(t, np.int64),
                                np.asarray(valid, bool)):
        lanes = np.flatnonzero(row_valid)
        if lanes.size:
            suffix = np.minimum.accumulate(np.where(
                row_valid, row_t, np.iinfo(np.int64).max)[::-1])[::-1]
            h = np.searchsorted(suffix, row_t[lanes] + span,
                                side="right") - 1
            best = max(best, int((h - lanes).max()) + 1)
    return best


def _visited_slots(layout, zone_chunk, span):
    """``(padded, real)`` candidate-steps of the per-bucket scans: each
    chunk of ``chunk`` zone rows (a bucket of more zones than the chunk is
    padded to whole chunks) runs its own horizon trips over ``e`` lanes;
    ``real`` leaves the padding rows out."""
    padded = real = 0
    for b in layout.buckets:
        chunk = zone_chunk if zone_chunk and zone_chunk < b.n_zones \
            else b.n_zones
        for lo in range(0, b.n_zones, chunk):
            trips = _horizon_trips(b.t[lo:lo + chunk],
                                   b.valid[lo:lo + chunk], span)
            padded += chunk * b.e_cap * trips
            real += min(chunk, b.n_zones - lo) * b.e_cap * trips
    return padded, real


@pytest.mark.parametrize("zone_chunk", [None, 3])
def test_per_bucket_sweep_slots_are_padded_zones_by_e_squared(zone_chunk):
    """The counter reads the candidate-steps the scans ran: padded zone
    rows by ``e`` by each scan's horizon trips, at most ``e`` (the dense
    ``e**2`` per row)."""
    layout = _bucketed_layout()
    ex = MiningExecutor(delta=60, l_max=3, zone_chunk=zone_chunk,
                        fused="off")
    _, stats = ex.run_layout(layout)
    want, real = _visited_slots(layout, zone_chunk, 60 * 3)
    assert stats["path"] == "per-bucket"
    assert stats["sweep_slots"] == want
    assert want < sum(z * e * e for z, e in layout.bucket_shapes())
    if zone_chunk:
        assert want > real                     # padding rows are swept
    else:
        assert want == layout.sweep_slots


def test_per_bucket_sweep_stops_at_the_horizon_in_wide_zones():
    """Zones eight horizons wide: a bucket's scan runs far fewer trips
    than its ``e`` slots, and the counter says so."""
    layout = _bucketed_layout(omega=8)
    ex = MiningExecutor(delta=60, l_max=3, fused="off")
    _, stats = ex.run_layout(layout)
    trips = [_horizon_trips(b.t, b.valid, 180) for b in layout.buckets]
    assert any(2 * n < b.e_cap for n, b in zip(trips, layout.buckets))
    assert stats["sweep_slots"] == layout.sweep_slots == sum(
        b.n_zones * b.e_cap * n for n, b in zip(trips, layout.buckets))
    assert _counts_dict(ex.run_layout(layout).counts) == \
        _counts_dict(MiningExecutor(delta=60, l_max=3, backend="numpy",
                                    fused="off").run_layout(layout).counts)


def test_fused_sweep_slots_are_the_block_descriptors_sum():
    from repro.core import planner

    layout = _bucketed_layout()
    ex = MiningExecutor(delta=60, l_max=3, backend="xla")
    _, stats = ex.run_layout(layout)
    blk, fold_chunk, _ = ex._fused_geometry(layout)
    fl = tzp.concat_layout(layout, blk=blk, pad_slots_to=fold_chunk,
                           delta=60, l_max=3, bounds=ex.fused_bounds)
    assert stats["path"] == "fused" and stats["launches"] == 1
    assert stats["sweep_slots"] == blk * int((fl.hi - fl.lo).sum()) \
        == planner.fused_sweep_slots(fl.lo, fl.hi, blk)


@pytest.mark.parametrize("agg", ["legacy", "hierarchical"])
def test_bucket_h2d_and_scan_nest_under_the_launch(agg):
    import repro.obs as obs_mod

    obs = obs_mod.enabled()
    layout = _bucketed_layout()
    ex = MiningExecutor(delta=60, l_max=3, agg=agg, zone_chunk=2,
                        fused="off", obs=obs)
    ex.run_layout(layout)
    events = obs.tracer.events()
    launches = {e["args"]["span_id"]: e for e in events
                if e["name"] == "mine.launch"}
    assert len(launches) == layout.n_buckets
    for name in ("mine.bucket_h2d", "mine.bucket_scan"):
        inner = [e for e in events if e["name"] == name]
        assert sorted(e["args"]["parent_id"] for e in inner) == \
            sorted(launches)
        for e in inner:
            launch = launches[e["args"]["parent_id"]]
            assert launch["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= launch["ts"] + launch["dur"]
    h2d = {e["args"]["parent_id"]: e for e in events
           if e["name"] == "mine.bucket_h2d"}
    for e in events:
        if e["name"] == "mine.bucket_scan":
            before = h2d[e["args"]["parent_id"]]
            assert before["ts"] + before["dur"] <= e["ts"]


def test_disabled_tracer_records_nothing_and_adds_no_sync(monkeypatch):
    import jax

    import repro.obs as obs_mod

    layout = _bucketed_layout()
    synced = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(x) or real(x))
    ex = MiningExecutor(delta=60, l_max=3, fused="off")
    want = _counts_dict(ex.run_layout(layout).counts)
    assert synced == [] and ex.obs.tracer.events() == []
    # the same run traced syncs each span it opens
    obs = obs_mod.enabled()
    ex = MiningExecutor(delta=60, l_max=3, fused="off", obs=obs)
    assert _counts_dict(ex.run_layout(layout).counts) == want
    names = [e["name"] for e in obs.tracer.events()]
    assert len(synced) >= names.count("mine.bucket_h2d") + \
        names.count("mine.bucket_scan") > 0


# ---------------------------------------------------------------------------
# One route for a single config and a lattice of them.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("route", ["whole-batch", "bounded", "host-scan"])
def test_single_config_scan_requests_no_timestamps(route):
    """A single config is the one-member lattice and traces its own scan:
    ``run_layout`` never asks for absorption timestamps, while a
    two-member co-mine does, on every fold route."""
    inner = get_backend("numpy" if route == "host-scan" else "ref").scan
    asked = []

    def spy(*args, **kw):
        asked.append(kw.get("with_ts", False))
        return inner(*args, **kw)

    name = f"test-spy-{route}"
    backends.register_backend(name, lambda: spy,
                              jittable=route != "host-scan",
                              supports_comine=True, overwrite=True)
    try:
        layout = _bucketed_layout()
        ex = MiningExecutor(delta=60, l_max=3, backend=name, fused="off",
                            zone_chunk=0 if route == "whole-batch" else 2)
        want = ex.run_layout(layout).counts
        assert asked and not any(asked)
        asked.clear()
        many = ex.run_layout_multi(layout, [(60, 3), (30, 2)]).counts
        assert asked and all(asked)
        assert _counts_dict(many[0]) == _counts_dict(want)
    finally:
        backends._REGISTRY.pop(name, None)


@pytest.mark.parametrize("cap, n_spilled, ceiling, want", [
    (64, 1, 10_000, 128),       # at least doubles
    (64, 100, 10_000, 256),     # cap + n_spilled, up to a power of two
    (64, 1, 100, 100),          # never past the cap that cannot spill
])
def test_grow_cap_after_a_spill(cap, n_spilled, ceiling, want):
    from repro.core.executor import _grow_cap

    assert _grow_cap(cap, n_spilled, ceiling) == want
