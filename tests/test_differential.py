"""Cross-backend differential harness for the full discovery path.

Every graph here flows through the complete ``plan_zones ->
build_zone_batch -> MiningExecutor.run`` pipeline for every backend
(``ref`` jnp reference, ``numpy`` brute-force oracle, ``pallas`` kernel —
interpret mode on CPU; the fused tests additionally sweep the compiled
``xla`` lowering against the interpreted Pallas one) and every
aggregation configuration (chunked vs
unchunked, legacy whole-batch vs hierarchical bounded-carry),
and all results must agree code-for-code — with the standalone oracle as
ground truth whenever the batch is exact (``overflow == 0``).

Two layers:
  * a deterministic corpus of adversarial regimes (bursty, repeated
    timestamps, self-loop-heavy, adaptive ``e_cap``-shrunk zones) that runs
    everywhere, hypothesis installed or not;
  * Hypothesis property tests over generated temporal graphs — the CI
    differential-fuzz step widens the search (profile "fuzz", pinned
    seeds), while tier-1 runs a small derandomized sample (profile
    "tier1", registered in conftest).
"""

import warnings

import numpy as np
import pytest

from repro.core import MiningExecutor, oracle, transitions, tzp
from repro.core.temporal_graph import from_edges
from conftest import batch_discover, random_graph

BACKENDS = ("ref", "numpy", "pallas")


def _dict(counts):
    return transitions.device_counts_to_dict(counts)


# ---------------------------------------------------------------------------
# Deterministic adversarial corpus.
# ---------------------------------------------------------------------------


def _bursty(seed, n=160, nodes=7):
    """Dense bursts separated by dead gaps (zone-boundary stress)."""
    rng = np.random.default_rng(seed)
    t, now = [], 0
    while len(t) < n:
        now += int(rng.integers(40, 120))
        burst = int(rng.integers(3, 18))
        t.extend((now + np.sort(rng.integers(0, 12, burst))).tolist())
    t = np.asarray(t[:n])
    return from_edges(rng.integers(0, nodes, n), rng.integers(0, nodes, n), t)


def _repeated_ts(seed, n=140, nodes=6):
    """Heavy timestamp ties (t > last_t gating, stable-sort order)."""
    rng = np.random.default_rng(seed)
    t = np.sort(rng.integers(0, n // 4, n))      # ~4 edges per timestamp
    return from_edges(rng.integers(0, nodes, n), rng.integers(0, nodes, n), t)


def _self_loops(seed, n=120, nodes=5):
    """~1/3 self-loops (u == v: single-node seeding + same_uv relabeling)."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, nodes, n)
    v = np.where(rng.random(n) < 0.35, u, rng.integers(0, nodes, n))
    return from_edges(u, v, np.sort(rng.integers(0, 400, n)))


CORPUS = [
    ("bursty", _bursty, dict(delta=20, l_max=4, omega=2, e_cap=None)),
    ("repeated-ts", _repeated_ts, dict(delta=6, l_max=3, omega=2,
                                       e_cap=None)),
    ("self-loops", _self_loops, dict(delta=30, l_max=4, omega=3,
                                     e_cap=None)),
    # adaptive zoning: e_cap forces the planner to shrink dense zones
    ("adaptive-ecap", _bursty, dict(delta=15, l_max=3, omega=4, e_cap=24)),
]


@pytest.mark.parametrize("name,gen,params",
                         CORPUS, ids=[c[0] for c in CORPUS])
def test_full_path_backends_agree_on_corpus(name, gen, params):
    g = gen(seed=11)
    e_cap = params["e_cap"]
    results = {}
    for backend in BACKENDS:
        res = batch_discover(g, delta=params["delta"], l_max=params["l_max"],
                       omega=params["omega"], e_cap=e_cap, backend=backend,
                       allow_overflow=True)
        results[backend] = res
    base = results["ref"]
    for backend in BACKENDS[1:]:
        assert results[backend].counts == base.counts, \
            f"{backend} != ref on {name}"
    if base.overflow == 0:
        expect = dict(oracle.count_codes(
            g.u, g.v, g.t, params["delta"], params["l_max"]))
        assert base.counts == expect, f"ref != oracle on {name}"


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("agg", ["legacy", "hierarchical"])
def test_chunked_agg_modes_match_unchunked(backend, agg):
    """Chunked x {legacy, hierarchical} == one-shot whole batch,
    for jittable and host-only backends alike."""
    g = _bursty(seed=3, n=140)
    delta, l_max = 20, 4
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    batch = tzp.build_zone_batch(g, plan, pad_zones_to=4)
    base = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                          zone_chunk=0)
    chunked = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                             zone_chunk=4, agg=agg)
    assert _dict(chunked.run(batch)) == _dict(base.run(batch))


def test_hierarchical_survives_tiny_merge_cap():
    """The spill/retry policy must converge to exact counts from any cap."""
    g = random_graph(9, 260, 9, 700)
    delta, l_max = 25, 4
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    batch = tzp.build_zone_batch(g, plan, pad_zones_to=2)
    base = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=0)
    tiny = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=2,
                          agg="hierarchical", merge_cap=8)
    with pytest.warns(RuntimeWarning, match="merge spilled"):
        got = _dict(tiny.run(batch))
    assert got == _dict(base.run(batch))


def test_merge_cap_retry_terminates_at_full_saturation():
    """Every candidate a distinct live code: the retry ceiling must leave
    room for the all-zero padding row (z*e + 1), or the spill/retry loop
    re-derives the same cap forever (regression: infinite hang)."""
    # temporal path 0-1-2-...: each seed walk absorbs a unique node
    # sequence, so all 8 candidates carry distinct codes
    n = 8
    g = from_edges(np.arange(n), np.arange(n) + 1,
                   2 * np.arange(n))
    delta, l_max = 5, 8
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    batch = tzp.build_zone_batch(g, plan)
    ex = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=1,
                        agg="hierarchical", merge_cap=8)
    base = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=0)
    with pytest.warns(RuntimeWarning, match="merge spilled"):
        got = _dict(ex.run(batch))
    assert got == _dict(base.run(batch))


def test_mesh_hierarchical_matches_single_device():
    """Per-shard hierarchical fold inside shard_map == plain discover."""
    import jax

    from repro.distributed import mining

    g = _bursty(seed=7, n=120)
    delta, l_max = 20, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    batch = tzp.build_zone_batch(g, plan, pad_zones_to=4)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("z",))
    ex = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=2,
                        agg="hierarchical")
    counts = mining.mine_on_mesh(batch, mesh, ("z",), executor=ex)
    expect = batch_discover(g, delta=delta, l_max=l_max, omega=2)
    assert _dict(counts) == expect.counts


# ---------------------------------------------------------------------------
# Ragged zone layouts: bucketed == dense == oracle, every backend.
# ---------------------------------------------------------------------------


def _powerlaw_bursty(seed, n=220, nodes=9):
    """Power-law burst sizes + quiet gaps: zone sizes span several
    power-of-two buckets (the skew regime the bucketed layout targets)."""
    rng = np.random.default_rng(seed)
    us, vs, ts = [], [], []
    now = 0
    while len(ts) < n:
        burst = min(int(rng.pareto(0.9) * 3) + 1, 70)
        group = rng.integers(0, nodes, size=max(2, burst // 4 + 2))
        for _ in range(burst):
            a, b = rng.choice(group, 2, replace=True)
            us.append(a)
            vs.append(b)
            ts.append(now + int(rng.integers(0, 30)))
        now += int(rng.integers(150, 700))
    return from_edges(np.asarray(us[:n]), np.asarray(vs[:n]),
                      np.asarray(ts[:n]))


def _layout_counts(g, *, backend, layout, zone_chunk, delta, l_max, omega,
                   e_cap=None, agg="auto"):
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=omega,
                          e_cap=e_cap)
    lay = tzp.build_zone_layout(g, plan, layout=layout, e_cap=e_cap)
    ex = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                        zone_chunk=zone_chunk, agg=agg)
    return lay, _dict(ex.run_layout(lay, allow_overflow=True).counts)


def test_bursty_corpus_spans_three_buckets():
    """Guard: the layout-differential corpus really exercises >= 3 buckets
    (otherwise the bucketed-vs-dense comparison degenerates)."""
    g = _powerlaw_bursty(seed=5)
    plan = tzp.plan_zones(g, delta=12, l_max=3, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    assert lay.n_buckets >= 3, lay.bucket_shapes()
    assert lay.padding_ratio < tzp.build_zone_layout(
        g, plan, layout="dense").padding_ratio


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("zone_chunk", [0, 4])
def test_bucketed_matches_dense_and_oracle(backend, zone_chunk):
    """bucketed == dense == standalone numpy oracle through the full
    plan -> layout -> run_layout path, chunked and unchunked."""
    g = _powerlaw_bursty(seed=5)
    delta, l_max, omega = 12, 3, 2
    dense_lay, dense = _layout_counts(
        g, backend=backend, layout="dense", zone_chunk=zone_chunk,
        delta=delta, l_max=l_max, omega=omega)
    buck_lay, bucketed = _layout_counts(
        g, backend=backend, layout="bucketed", zone_chunk=zone_chunk,
        delta=delta, l_max=l_max, omega=omega)
    assert buck_lay.n_buckets >= 3
    assert bucketed == dense, f"bucketed != dense on {backend}"
    assert buck_lay.overflow == dense_lay.overflow == 0
    expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
    assert bucketed == expect, f"{backend} bucketed != oracle"


@pytest.mark.parametrize("layout", ["dense", "bucketed"])
def test_layout_survives_tiny_merge_cap_retry(layout):
    """The cross-bucket bounded-carry merge must converge to exact counts
    from any starting cap (spill -> warn -> doubled-cap retry)."""
    g = _powerlaw_bursty(seed=8, n=160)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout=layout)
    base = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=0)
    tiny = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=2,
                          agg="hierarchical", merge_cap=8)
    with pytest.warns(RuntimeWarning, match="merge spilled"):
        got = _dict(tiny.run_layout(lay).counts)
    assert got == _dict(base.run_layout(
        tzp.build_zone_layout(g, plan, layout="dense")).counts)


def test_layout_overflow_names_offending_bucket():
    """Edge-dropping buckets are named in the one layout-wide error."""
    g, delta, l_max = _overflowing_setup()
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2, e_cap=16)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed", e_cap=16)
    assert lay.overflow > 0
    from repro.core import ZoneOverflowError

    ex = MiningExecutor(delta=delta, l_max=l_max)
    with pytest.raises(ZoneOverflowError, match=r"bucket.*cap16"):
        ex.run_layout(lay)
    with pytest.warns(RuntimeWarning, match="dropped"):
        got = ex.run_layout(lay, allow_overflow=True).counts
    # overflow is layout-invariant: the dense batch drops the same edges
    dense = tzp.build_zone_layout(g, plan, layout="dense", e_cap=16)
    assert dense.overflow == lay.overflow
    with pytest.warns(RuntimeWarning, match="dropped"):
        dense_got = ex.run_layout(dense, allow_overflow=True).counts
    assert _dict(got) == _dict(dense_got)


def _front_compact(counts) -> bool:
    """Every live row lies in the first ``unique_mask.sum() + 1`` rows."""
    mask = np.asarray(counts.unique_mask)
    return not mask[int(mask.sum()) + 1:].any()


@pytest.mark.parametrize("backend,zone_chunk,agg", [
    ("ref", 0, "legacy"),            # count_codes via aggregate_zones
    ("ref", 4, "hierarchical"),      # the bounded chunk fold
    ("numpy", 0, "legacy"),          # host scan, whole-batch fold
    ("numpy", 4, "hierarchical"),    # host scan, bounded chunk merges
], ids=["whole-batch", "bucket-fold", "host-scan", "host-scan-fold"])
def test_bucket_tables_keep_live_rows_in_front(backend, zone_chunk, agg):
    """Every producer that feeds the cross-bucket merge leaves its table
    front-compact, so the merge's trim to the live prefix drops nothing."""
    g = _powerlaw_bursty(seed=5)
    plan = tzp.plan_zones(g, delta=12, l_max=3, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    ex = MiningExecutor(delta=12, l_max=3, backend=backend,
                        zone_chunk=zone_chunk, agg=agg, merge_cap=16)
    for b in lay.buckets:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)   # spills
            part = ex.run_arrays(b.u, b.v, b.t, b.valid, b.sign)
        assert _front_compact(part), b.label


def test_trimmed_merge_keeps_per_bucket_fused_and_dense_equal():
    """With the cross-bucket merge sorting each part's live prefix (fewer
    rows than the parts hold), per-bucket == fused == dense == oracle."""
    g = _powerlaw_bursty(seed=5)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    buck = tzp.build_zone_layout(g, plan, layout="bucketed")
    dense = tzp.build_zone_layout(g, plan, layout="dense")
    ex = MiningExecutor(delta=delta, l_max=l_max, fused_backend="xla")
    per_bucket = ex.run_layout(buck, fused=False)
    parts = [ex.run_arrays(b.u, b.v, b.t, b.valid, b.sign)
             for b in buck.buckets]
    cap = sum(int(np.asarray(p.unique_mask).sum()) for p in parts) + 1
    untrimmed = sum(cap + p.unique_mask.shape[0] for p in parts)
    assert 0 < per_bucket.stats["merge_rows"] < untrimmed
    fused = ex.run_layout(buck, fused=True)
    assert fused.stats["launches"] == 1
    got = _dict(per_bucket.counts)
    assert got == _dict(fused.counts)
    assert got == _dict(ex.run_layout(dense, fused=False).counts)
    assert got == dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
    assert len(parts) >= 3


# ---------------------------------------------------------------------------
# Fused single-launch path: one kernel launch == per-bucket == oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused_backend,want_path",
                         [("pallas", "fused"), ("xla", "fused_xla")])
@pytest.mark.parametrize("layout", ["dense", "bucketed"])
def test_fused_matches_per_bucket_and_oracle(layout, fused_backend,
                                             want_path):
    """run_layout(fused=True) — one bucket-native launch with the Phase-2
    fold on-device — must be code-for-code identical to the per-bucket
    path and the standalone numpy oracle, on the >= 3-bucket power-law
    corpus, for BOTH fused lowerings (Pallas interpret on CPU, and the
    compiled xla formulation of the same ``_edge_update`` rule)."""
    g = _powerlaw_bursty(seed=5)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout=layout)
    if layout == "bucketed":
        assert lay.n_buckets >= 3, lay.bucket_shapes()
    ex = MiningExecutor(delta=delta, l_max=l_max, backend="pallas",
                        fused_backend=fused_backend)
    fused_out = ex.run_layout(lay, fused=True)
    fused = _dict(fused_out.counts)
    assert fused_out.stats["path"] == want_path
    assert fused_out.stats["backend"] == fused_backend
    assert fused_out.stats["launches"] == 1
    pb_out = ex.run_layout(lay, fused=False)
    per_bucket = _dict(pb_out.counts)
    assert pb_out.stats["path"] == "per-bucket"
    assert pb_out.stats["launches"] == lay.n_buckets
    assert fused == per_bucket, "fused != per-bucket"
    expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
    assert fused == expect, "fused != oracle"


@pytest.mark.parametrize("bounds", ["full", "live"])
def test_fused_xla_matches_pallas_interpret_byte_identical(bounds):
    """The compiled xla lowering == pallas-interpret == ref == numpy on
    the power-law bursty corpus, under BOTH sweep-bound plans — and the
    live plan dispatches strictly less modeled sweep work."""
    g = _powerlaw_bursty(seed=5)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    results = {}
    for fb in ("pallas", "xla"):
        ex = MiningExecutor(delta=delta, l_max=l_max, backend="pallas",
                            fused_backend=fb, fused_bounds=bounds)
        out = ex.run_layout(lay, fused=True)
        assert out.stats["bounds"] == bounds
        results[fb] = _dict(out.counts)
    assert results["xla"] == results["pallas"]
    for backend in ("ref", "numpy"):
        ex = MiningExecutor(delta=delta, l_max=l_max, backend=backend)
        assert results["xla"] == _dict(
            ex.run_layout(lay, fused=False).counts), backend
    if bounds == "live":
        # never MORE work than the full plan...
        full = tzp.concat_layout(lay, blk=512)
        live = tzp.concat_layout(lay, blk=512, delta=delta, l_max=l_max,
                                 bounds="live")
        assert live.sweep_slots <= full.sweep_slots
        # ...and strictly less on a corpus whose zone time spans exceed
        # the Lemma-4.1 horizon (this one's zones all fit inside it, so
        # the cut cannot bite there)
        from repro.data import synthetic_graphs as sg

        gappy = sg.bursty_stream(2_500, 250, burst_size=120, burst_span=200,
                                 gap_span=30_000, seed=13)
        gplan = tzp.plan_zones(gappy, delta=90, l_max=5, omega=2)
        glay = tzp.build_zone_layout(gappy, gplan, layout="bucketed")
        gfull = tzp.concat_layout(glay, blk=512)
        glive = tzp.concat_layout(glay, blk=512, delta=90, l_max=5,
                                  bounds="live")
        assert glive.sweep_slots < gfull.sweep_slots


def test_fused_compacted_bounds_identical_at_kernel_level():
    """Host-planned [lo, hi) compaction is output-exact at the raw kernel
    level: full == live slot streams, slot for slot, on both lowerings."""
    import jax.numpy as jnp

    from repro.kernels.zone_scan import ops, xla

    g = _powerlaw_bursty(seed=8, n=160)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    outs = {}
    for bounds in ("full", "live"):
        fl = tzp.concat_layout(lay, blk=64, delta=delta, l_max=l_max,
                               bounds=bounds)
        args = tuple(jnp.asarray(x) for x in
                     (fl.u, fl.v, fl.t, fl.valid, fl.zone_id, fl.lo, fl.hi))
        outs[bounds, "xla"] = xla.scan_flat_xla(
            *args, delta=delta, l_max=l_max, blk=64, with_ts=True)
        outs[bounds, "pallas"] = ops.scan_flat(
            *args, delta=delta, l_max=l_max, blk=64, interpret=True,
            with_ts=True)
    base = outs["full", "pallas"]
    for key, got in outs.items():
        for a, b in zip(base, got):
            assert np.array_equal(np.asarray(a), np.asarray(b)), key


@pytest.mark.parametrize("fused_backend", ["pallas", "xla"])
def test_fused_survives_tiny_merge_cap_retry(fused_backend):
    """The on-device bounded fold spills exactly and the host retry with a
    doubled cap must converge to exact counts from any starting cap."""
    g = _powerlaw_bursty(seed=8, n=160)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    base = MiningExecutor(delta=delta, l_max=l_max, backend="pallas")
    tiny = MiningExecutor(delta=delta, l_max=l_max, backend="pallas",
                          fused_backend=fused_backend, merge_cap=8)
    with pytest.warns(RuntimeWarning, match="fused on-device merge spilled"):
        outcome = tiny.run_layout(lay, fused=True)
    got = _dict(outcome.counts)
    assert outcome.stats["spill_retries"] >= 1
    assert outcome.stats["launches"] == 1
    assert got == _dict(base.run_layout(lay, fused=True).counts)


def test_fused_dispatch_policy():
    """"auto" fuses exactly when the resolved fused backend has a flat
    kernel; forcing fused with none available is an error, not a silent
    fallback; fused_backend reroutes (and validates) the lowering."""
    kw = dict(delta=12, l_max=3)
    assert MiningExecutor(backend="pallas", **kw).resolve_fused() is True
    assert MiningExecutor(backend="ref", **kw).resolve_fused() is False
    assert MiningExecutor(backend="numpy", **kw).resolve_fused() is False
    assert MiningExecutor(backend="pallas", fused="off",
                          **kw).resolve_fused() is False
    with pytest.raises(ValueError, match="no fused single-launch scan"):
        MiningExecutor(backend="ref", **kw).resolve_fused(True)
    with pytest.raises(ValueError, match="no fused single-launch scan"):
        MiningExecutor(backend="ref", fused="on", **kw).resolve_fused()
    with pytest.raises(ValueError, match="unknown fused mode"):
        MiningExecutor(backend="ref", fused="always", **kw)
    # an explicit fused_backend opens the fused path from ANY backend...
    rx = MiningExecutor(backend="ref", fused_backend="xla", **kw)
    assert rx.resolve_fused() is True
    assert rx._fused_spec().name == "xla"
    # ...but must itself publish a flat kernel
    with pytest.raises(ValueError, match="no fused single-launch scan"):
        MiningExecutor(backend="pallas", fused_backend="ref", **kw)
    with pytest.raises(ValueError, match="unknown fused bounds"):
        MiningExecutor(backend="pallas", fused_bounds="tight", **kw)
    # on CPU (every CI host) the pallas kernel would interpret, so auto
    # dispatch must reroute fused runs to the compiled xla lowering
    import jax

    if jax.default_backend() == "cpu":
        auto = MiningExecutor(backend="pallas", **kw)
        assert auto._fused_spec().name == "xla"
        pinned = MiningExecutor(backend="pallas", fused_backend="pallas",
                                **kw)
        assert pinned._fused_spec().name == "pallas"


def test_fused_engine_single_launch_and_cache():
    """Through the engine: a pallas discover is served by ONE launch, the
    result records it, and a repeated discover is a compile-cache hit on
    the fused execution key."""
    from repro.core.engine import PTMTEngine

    g = _powerlaw_bursty(seed=5)
    eng = PTMTEngine(delta=12, l_max=3, omega=2, backend="pallas")
    res = eng.discover(g)
    assert res.layout["execution"]["path"] in ("fused", "fused_xla")
    assert res.layout["execution"]["launches"] == 1
    assert eng.stats.fused_runs == 1
    assert eng.stats.launches == 1
    ref = PTMTEngine(delta=12, l_max=3, omega=2, backend="ref").discover(g)
    assert res.counts == ref.counts
    eng.discover(g)
    assert eng.stats.compile_cache_hits == 1
    assert eng.stats.launches == 2


# ---------------------------------------------------------------------------
# pad_policy="pad" x bucketed layout (regression: shared pad_zone_arrays).
# ---------------------------------------------------------------------------


def test_pad_zone_arrays_appends_inert_rows():
    """The shared helper pads with all-invalid zero-sign rows and is a
    no-op at the current row count."""
    g = _bursty(seed=3, n=80)
    plan = tzp.plan_zones(g, delta=20, l_max=4, omega=2)
    batch = tzp.build_zone_batch(g, plan)
    z = batch.n_zones
    u, v, t, valid, signs = tzp.pad_zone_arrays(
        batch.u, batch.v, batch.t, batch.valid, batch.sign, n_rows=z + 3)
    assert u.shape[0] == z + 3
    assert not valid[z:].any() and not signs[z:].any()
    same = tzp.pad_zone_arrays(batch.u, batch.v, batch.t, batch.valid,
                               batch.sign, n_rows=z)
    assert all(a is b for a, b in
               zip(same, (batch.u, batch.v, batch.t, batch.valid,
                          batch.sign)))
    with pytest.raises(ValueError, match="cannot pad"):
        tzp.pad_zone_arrays(batch.u, batch.v, batch.t, batch.valid,
                            batch.sign, n_rows=z - 1)


@pytest.mark.parametrize("backend", BACKENDS)
def test_pad_policy_with_bucketed_layout_non_divisor_chunk(backend):
    """A zone_chunk that divides no bucket's zone count exercises the pad
    path on every bucket of a bucketed layout; counts must match the
    unchunked run exactly, and pad_policy='raise' must refuse."""
    g = _powerlaw_bursty(seed=5)
    delta, l_max = 12, 3
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
    lay = tzp.build_zone_layout(g, plan, layout="bucketed")
    assert lay.n_buckets >= 3
    # pick a chunk size that divides none of the buckets' zone counts
    chunk = 4
    assert all(b.n_zones % chunk for b in lay.buckets), lay.bucket_shapes()
    base = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                          zone_chunk=0)
    padded = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                            zone_chunk=chunk, pad_policy="pad")
    got = _dict(padded.run_layout(lay, fused=False).counts)
    assert got == _dict(base.run_layout(lay, fused=False).counts)
    from repro.core.executor import ZoneChunkError

    strict = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                            zone_chunk=chunk, pad_policy="raise")
    with pytest.raises(ZoneChunkError, match="not divisible"):
        strict.run_layout(lay, fused=False)


# ---------------------------------------------------------------------------
# Overflow must never masquerade as exact counts (regression).
# ---------------------------------------------------------------------------


def _overflowing_setup():
    """A burst denser than e_cap inside the 2*L_b shrink floor: the adaptive
    planner cannot split it further, so edges are genuinely dropped."""
    delta, l_max = 10, 3
    rng = np.random.default_rng(0)
    n = 120
    g = from_edges(rng.integers(0, 6, n), rng.integers(0, 6, n),
                   np.sort(rng.integers(0, 2 * delta * l_max, n)))
    return g, delta, l_max


def test_executor_refuses_overflowed_batch():
    g, delta, l_max = _overflowing_setup()
    plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2, e_cap=16)
    batch = tzp.build_zone_batch(g, plan, e_cap=16)
    assert batch.overflow > 0, "setup must actually drop edges"
    ex = MiningExecutor(delta=delta, l_max=l_max)
    from repro.core import ZoneOverflowError

    with pytest.raises(ZoneOverflowError, match="dropped"):
        ex.run(batch)
    with pytest.warns(RuntimeWarning, match="dropped"):
        got = ex.run(batch, allow_overflow=True)
    # and the opted-in run really does undercount vs the oracle
    expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
    assert sum(_dict(got).values()) < sum(expect.values())


def test_discover_refuses_overflow_and_allows_optin():
    g, delta, l_max = _overflowing_setup()
    from repro.core import ZoneOverflowError

    with pytest.raises(ZoneOverflowError, match="dropped"):
        batch_discover(g, delta=delta, l_max=l_max, omega=2, e_cap=16)
    with pytest.warns(RuntimeWarning, match="dropped"):
        res = batch_discover(g, delta=delta, l_max=l_max, omega=2, e_cap=16,
                       allow_overflow=True)
    assert res.overflow > 0


# ---------------------------------------------------------------------------
# Hypothesis fuzz layer (optional: the corpus above runs without it).
# ---------------------------------------------------------------------------

try:
    import hypothesis as hyp
    from hypothesis import strategies as st
except ImportError:
    hyp = None

if hyp is not None:

    @st.composite
    def temporal_graphs(draw):
        """Small adversarial temporal graphs: bursty gaps, timestamp ties
        and self-loops all arise naturally from the ranges chosen here."""
        n = draw(st.integers(1, 36))
        nodes = draw(st.integers(1, 6))
        u = draw(st.lists(st.integers(0, nodes - 1), min_size=n, max_size=n))
        v = draw(st.lists(st.integers(0, nodes - 1), min_size=n, max_size=n))
        gaps = draw(st.lists(st.integers(0, 9), min_size=n, max_size=n))
        t = np.cumsum(np.asarray(gaps, np.int64))
        return from_edges(np.asarray(u, np.int64), np.asarray(v, np.int64),
                          t)

    @hyp.given(
        g=temporal_graphs(),
        delta=st.integers(2, 8),
        l_max=st.integers(2, 4),
        omega=st.sampled_from([2, 3]),
        e_cap=st.sampled_from([None, 8, 16]),
    )
    def test_fuzz_full_path_ref_vs_numpy_oracle(g, delta, l_max, omega,
                                                e_cap):
        """ref == numpy through the full path on generated graphs; both
        equal the standalone oracle whenever no edges were dropped."""
        kw = dict(delta=delta, l_max=l_max, omega=omega, e_cap=e_cap,
                  allow_overflow=True)
        a = batch_discover(g, backend="ref", **kw)
        b = batch_discover(g, backend="numpy", **kw)
        assert a.counts == b.counts
        assert a.overflow == b.overflow
        if a.overflow == 0:
            expect = dict(oracle.count_codes(g.u, g.v, g.t, delta, l_max))
            assert a.counts == expect

    @hyp.given(
        g=temporal_graphs(),
        delta=st.integers(2, 8),
        l_max=st.integers(2, 4),
        zone_chunk=st.sampled_from([2, 3, 4]),
        agg=st.just("hierarchical"),
    )
    def test_fuzz_hierarchical_agg_matches_legacy(g, delta, l_max,
                                                  zone_chunk, agg):
        """Bounded-carry folds == legacy whole-batch flatten on any batch,
        including zone counts the chunk size does not divide (pad
        policy)."""
        plan = tzp.plan_zones(g, delta=delta, l_max=l_max, omega=2)
        batch = tzp.build_zone_batch(g, plan)
        legacy = MiningExecutor(delta=delta, l_max=l_max, zone_chunk=0)
        folded = MiningExecutor(delta=delta, l_max=l_max,
                                zone_chunk=zone_chunk, agg=agg)
        assert _dict(folded.run(batch)) == _dict(legacy.run(batch))

    @hyp.given(
        g=temporal_graphs(),
        delta=st.integers(2, 6),
        l_max=st.integers(2, 3),
    )
    @hyp.settings(max_examples=10)
    def test_fuzz_pallas_interpret_matches_ref(g, delta, l_max):
        """Pallas (interpret mode on CPU) == ref through the full path.

        Kept to few examples: interpret mode executes the kernel grid in
        Python.  The corpus test covers the adversarial regimes for pallas
        deterministically.
        """
        a = batch_discover(g, delta=delta, l_max=l_max, omega=2, backend="pallas")
        b = batch_discover(g, delta=delta, l_max=l_max, omega=2, backend="ref")
        assert a.counts == b.counts
