"""The ``wiki-talk`` configuration: its vectorised triadic generator at the
published size, the loop analog's rules at a small one, and the program
equal to the plain reference on a prefix of its own graph."""

import numpy as np
import pytest

from bench import harness
from bench.drivers.common import Graph, variant
from bench.generators import triadic
from bench.generators.rng import stream
from bench.reference import ptmt


@pytest.fixture(scope="module")
def cell():
    return harness.resolve("wiki-talk.batch")


@pytest.fixture(scope="module")
def graph(cell):
    return harness.make_graph(cell)


def small_params(cell, **changes):
    return {**cell.config["generator"]["params"], "n_edges": 20_000,
            **changes}


def test_generator_matches_the_published_size(cell, graph):
    pub = cell.config["published"]
    assert cell.config["reduced"] == []
    assert graph.n_edges == pub["edges"]
    assert graph.n_nodes == pub["nodes"]
    assert abs(graph.t[-1] / 86400 - pub["span_days"]) < \
        0.01 * pub["span_days"]
    assert graph.t[0] == 0 and np.all(np.diff(graph.t) > 0)
    touched = np.unique(np.concatenate([graph.u, graph.v])).size
    assert 0.7 * pub["nodes"] < touched < pub["nodes"]
    # heavy-tailed users: the busiest id takes a share no uniform draw gives
    assert np.bincount(graph.u).max() > 100 * graph.n_edges / pub["nodes"]


def test_one_fixed_graph_per_seed(cell):
    params = small_params(cell)
    a, b = (triadic.generate(params, 0) for _ in range(2))
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    c = triadic.generate(params, 1)
    assert not np.array_equal(a[0], c[0])
    g = Graph(u=a[0], v=a[1], t=a[2], n_nodes=params["n_nodes"])
    h, k = variant(g, 0, seed=2**31 + 7), variant(g, 0, seed=1)
    assert np.array_equal(h.t, g.t) and not np.array_equal(h.u, k.u)


@pytest.mark.parametrize("alpha", [0.0, 0.5, 1.0, 1.5])
def test_fresh_ids_are_the_inverse_cdf(alpha):
    """The guide-table sampler gives the rank a binary search over the
    CDF gives, draw for draw."""
    n_nodes = 5_003
    perm = np.arange(n_nodes)
    got = triadic.fresh_ids(stream(4, 3), 200_000, n_nodes=n_nodes,
                            alpha=alpha, perm=perm)
    cdf = np.cumsum(np.arange(1, n_nodes + 1, dtype=np.float64) ** -alpha)
    x = stream(4, 3).random(200_000) * cdf[-1]
    want = np.minimum(np.searchsorted(cdf, x, side="right"), n_nodes - 1)
    assert np.array_equal(got, want)


def test_closing_edges_copy_their_reference(cell):
    params = small_params(cell)
    d = triadic.draw(params, 3)
    u, v, _ = triadic.generate(params, 3)
    i = np.flatnonzero(d.ref >= 0)
    j, kind = d.ref[i], d.kind[i]
    assert i.min() >= 2
    assert np.all((i - j >= 1) & (i - j <= params["recent_edges"]))
    bc, ab, ca = (kind == k for k in (triadic.BC, triadic.AB, triadic.CA))
    assert np.array_equal(u[i[bc]], v[j[bc]])                  # (b, c)
    assert np.array_equal(u[i[ab]], u[j[ab]])                  # (a, b)
    assert np.array_equal(v[i[ab]], v[j[ab]])
    assert np.array_equal(v[i[ca]], u[j[ca]])                  # (c, a)
    # the fresh node c is new to the referenced edge, bar chance draws
    assert np.mean(v[i[bc]] == u[j[bc]]) < 0.05
    assert np.mean(u[i[ca]] == v[j[ca]]) < 0.05


def closures(u, v, recent):
    """What an edge list shows of its closures: per edge, the distance to
    the nearest of the last ``recent`` edges it closes on (0: none) and
    the kind that edge shows, (a, b), (b, c) or (c, a)."""
    n = len(u)
    dist = np.zeros(n, np.int64)
    kind = np.full(n, -1)
    for d in range(recent, 0, -1):          # nearer edges win
        a, b = u[:-d], v[:-d]
        x, y = u[d:], v[d:]
        for k, hit in ((triadic.CA, y == a), (triadic.BC, x == b),
                       (triadic.AB, (x == a) & (y == b))):
            dist[d:][hit] = d
            kind[d:][hit] = k
    return dist, kind


def test_closure_statistics_match_the_loop_analog(cell):
    """At alpha 0 and an id space so large that chance repeats are rare,
    the closures seen in the vectorised generator's edges match those of
    the loop ``triadic_stream``: their share, their distance and their
    kinds."""
    from repro.data.synthetic_graphs import triadic_stream

    n, n_nodes = 20_000, 10**6
    params = small_params(cell, n_nodes=n_nodes, alpha=0.0)
    u, v, _ = triadic.generate(params, 11)
    loop = triadic_stream(n, n_nodes, p_close=params["p_close"], seed=11)
    stats = []
    for a, b in ((u, v), (loop.u, loop.v)):
        dist, kind = closures(np.asarray(a), np.asarray(b),
                              params["recent_edges"])
        closed = dist > 0
        stats.append({"share": closed.mean(),
                      "mean_dist": dist[closed].mean(),
                      **{f"kind{k}": np.mean(kind[closed] == k)
                         for k in (triadic.BC, triadic.AB, triadic.CA)}})
    vec, ref = stats
    assert abs(vec["share"] - params["p_close"]) < 0.02
    assert abs(vec["share"] - ref["share"]) < 0.02
    assert abs(vec["mean_dist"] - ref["mean_dist"]) < 1.5
    # a nearer copy of the referenced edge can show another kind, so the
    # kinds seen are near, not at, 1/2, 1/4, 1/4
    for k in (triadic.BC, triadic.AB, triadic.CA):
        assert abs(vec[f"kind{k}"] - ref[f"kind{k}"]) < 0.03


def test_discover_equals_the_reference_on_a_prefix(cell, graph):
    """About four growth zones of the configuration's own graph, mined
    through the default path on the CPU: one bucket of ~2,900 slots, as
    the cell's largest."""
    from repro.core.config import MiningConfig
    from repro.core.engine import PTMTEngine
    from repro.core.temporal_graph import TemporalGraph

    k = 12_000
    u, v, t = graph.u[:k], graph.v[:k], graph.t[:k]
    engine = PTMTEngine(MiningConfig(**cell.config["mining"]))
    got = engine.discover(TemporalGraph(u=u, v=v, t=t,
                                        n_nodes=graph.n_nodes))
    assert max(b["e_cap"] for b in got.layout["buckets"]) > 2048
    assert got.counts == ptmt.count_codes(
        u, v, t, **harness.paper_params(cell.config))


def test_control_is_not_correct_on_a_slice(cell, graph):
    """The control (the reference on float32 timestamps) rounds ties and
    the delta edge once times pass 2**24 s (194 days): on 200,000 edges
    from day 440 on, a resolution of 4 s, the comparison that decides
    ``correct`` already refuses it."""
    from bench import check, control

    lo, hi = 1_500_000, 1_700_000
    g = variant(Graph(u=graph.u[lo:hi], v=graph.v[lo:hi],
                      t=graph.t[lo:hi], n_nodes=graph.n_nodes), 0,
                seed=2**31 + 11)
    assert g.t[0] > 2**25
    params = harness.paper_params(cell.config)
    want = ptmt.count_codes(g.u, g.v, g.t, **params)
    numbers, _ = check.compare(
        [control.control_counts(ptmt, g, **params)], want)
    assert not check.passed(numbers)
    assert numbers["count_l1_max"][0] > 0
