"""The benchmark's generators, its reference, its bytes model, and the
invariances the batch and stream mixes lean on."""

import json
import os

import numpy as np
import pytest

from bench import harness, traffic_bytes
from bench.drivers.common import Graph, variant
from bench.reference import ptmt

SPAN_DAYS = {"email-eu": 803, "sms-a": 338}


def cell_for(config):
    return next(harness.resolve(w["name"])
                for w in harness.load_spec()["workloads"]
                if w["config"] == config)


@pytest.mark.parametrize("config", sorted(SPAN_DAYS))
def test_generator_matches_the_published_size(config):
    cell = cell_for(config)
    pub = cell.config["published"]
    g = harness.make_graph(cell)
    assert g.n_edges == pub["edges"]
    assert g.n_nodes == pub["nodes"]
    assert abs(g.t[-1] / 86400 - pub["span_days"]) < 0.01 * pub["span_days"]
    assert np.all(np.diff(g.t) >= 0) and g.t[0] == 0
    # one fixed graph per configuration; a run's seed relabels it
    h = harness.make_graph(cell)
    assert np.array_equal(g.t, h.t) and np.array_equal(g.u, h.u)
    a, b = variant(g, 0, seed=2**31 + 7), variant(g, 0, seed=1)
    assert np.array_equal(a.t, g.t) and not np.array_equal(a.u, b.u)


def small_graph(seed, n=600, n_nodes=9, span=20_000):
    rng = np.random.default_rng(seed)
    return Graph(u=rng.integers(0, n_nodes, n).astype(np.int32),
                 v=rng.integers(0, n_nodes, n).astype(np.int32),
                 t=np.sort(rng.integers(0, span, n)).astype(np.int32),
                 n_nodes=n_nodes)


def loop_reference(u, v, t, delta, l_max):
    """Per-process walk of Definitions 2-4, one edge at a time."""
    out = {}
    n = len(u)
    for s in range(n):
        edges, nodes, last, j = [(u[s], v[s])], {u[s], v[s]}, t[s], s + 1
        while len(edges) < l_max:
            while j < n and t[j] <= last + delta:
                if t[j] > last and (u[j] in nodes or v[j] in nodes):
                    break
                j += 1
            if j >= n or t[j] > last + delta:
                break
            edges.append((u[j], v[j]))
            nodes |= {u[j], v[j]}
            last, j = t[j], j + 1
        labels = {}
        for a, b in edges:
            labels.setdefault(a, len(labels))
            labels.setdefault(b, len(labels))
        code = "".join(f"{labels[a]:x}{labels[b]:x}" for a, b in edges)
        out[code] = out.get(code, 0) + 1
    return out


@pytest.mark.parametrize("seed", range(4))
def test_reference_equals_the_loop_walk(seed):
    g = small_graph(seed, n=300, n_nodes=3 + seed * 3, span=4000 + seed)
    for delta, l_max in ((60, 3), (300, 6), (1, 2)):
        got = ptmt.count_codes(g.u, g.v, g.t, delta=delta, l_max=l_max)
        assert got == loop_reference(g.u.tolist(), g.v.tolist(),
                                     g.t.tolist(), delta, l_max)
        assert sum(got.values()) == g.n_edges


def test_reference_on_an_empty_graph():
    assert ptmt.count_codes([], [], [], delta=5, l_max=3) == {}


@pytest.mark.parametrize("seed", range(2))
def test_counts_are_invariant_under_shift_and_relabel(seed):
    from repro.core.engine import PTMTEngine
    from repro.core.temporal_graph import TemporalGraph

    g = small_graph(seed)
    engine = PTMTEngine(delta=600, l_max=4, omega=2)
    results = []
    for index in (0, 12_345):
        h = variant(g, index, seed=seed) if index else g
        results.append(engine.discover(TemporalGraph(
            u=h.u, v=h.v, t=h.t, n_nodes=h.n_nodes)))
        assert results[-1].counts == ptmt.count_codes(
            h.u, h.v, h.t, delta=600, l_max=4)
    assert results[0].counts == results[1].counts
    shapes = [[(b["n_zones"], b["e_cap"]) for b in r.layout["buckets"]]
              for r in results]
    assert shapes[0] == shapes[1] and len(shapes[0]) > 1
    assert engine.stats.plan_cache_hits == 0


def test_bytes_model_matches_the_planner():
    from repro.core import planner, tzp
    from repro.core.temporal_graph import TemporalGraph

    g = small_graph(0, n=2000, n_nodes=30, span=200_000)
    tg = TemporalGraph(u=g.u, v=g.v, t=g.t, n_nodes=g.n_nodes)
    plan = tzp.plan_zones(tg, delta=600, l_max=6, omega=2)
    layout = tzp.build_zone_layout(tg, plan, layout="bucketed")
    for bounds in ("full", "live"):
        fl = tzp.concat_layout(layout, blk=128, bounds=bounds,
                               delta=600, l_max=6)
        assert traffic_bytes.sweep_slots(fl.lo, fl.hi, fl.blk) == \
            planner.fused_sweep_slots(fl.lo, fl.hi, fl.blk)
        for l_max in (3, 6, 7):
            assert traffic_bytes.traffic_bytes(
                n_slots=fl.n_slots, sweep_slots=fl.sweep_slots,
                blk=fl.blk, l_max=l_max) == \
                planner.fused_traffic_bytes(fl, l_max)


def test_peaks_know_the_v5e_and_refuse_other_devices():
    assert traffic_bytes.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        traffic_bytes.peaks("cpu")
    with open(traffic_bytes.PEAKS) as f:
        assert "TPU v5e" in json.load(f)["source"]
    assert os.path.dirname(traffic_bytes.PEAKS) == harness.BENCH_DIR
