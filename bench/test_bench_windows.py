"""The window arithmetic of the batch and stream mixes, on a fake engine
and a fake clock."""

from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench.drivers import batch, stream
from bench.drivers.common import Graph, p95, variant


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def graph(n=10, n_nodes=4):
    rng = np.random.default_rng(0)
    return Graph(u=rng.integers(0, n_nodes, n).astype(np.int32),
                 v=rng.integers(0, n_nodes, n).astype(np.int32),
                 t=np.arange(n, dtype=np.int32), n_nodes=n_nodes)


class FakeEngine:
    """discover takes 0.4 s; ingest takes 10 ms per 2 edges, the final
    snapshot 50 ms."""

    def __init__(self, clock):
        self.clock = clock
        self.seen = []

    def discover(self, g):
        self.clock.now += 0.4
        self.seen.append((int(g.t[0]), g.u.copy()))
        return NS(counts={"01": g.n_edges})

    def stream(self):
        clock, engine = self.clock, self

        class Miner:
            n = 0

            def ingest(self, u, v, t):
                clock.now += 0.005 * len(u)
                self.n += len(u)

            def snapshot(self, final):
                assert final
                clock.now += 0.05
                engine.seen.append(self.n)
                return NS(counts={"01": self.n})

        return Miner()


def test_batch_window_is_extended_to_the_mine_in_flight():
    clock = Clock()
    engine = FakeEngine(clock)
    g = graph()
    state = batch.State(engine=engine, graph=g, seed=5, first_index=2,
                        warm_s=[])
    w = batch.run_window(state, 1.0, clock=clock)
    # mines end at 0.4, 0.8 and 1.2 s; the third crosses 1.0 s and counts
    assert w.seconds == pytest.approx(1.2)
    assert w.edges == 3 * g.n_edges
    assert w.inputs == [2, 3, 4]
    assert w.call_s == pytest.approx([0.4] * 3)
    assert batch.end_to_end(w)["mine_edges_per_s"] == pytest.approx(
        30 / 1.2)
    # each mine saw its own variant: shifted by its index, relabelled
    assert [t0 for t0, _ in engine.seen] == [2, 3, 4]
    assert not np.array_equal(engine.seen[0][1], engine.seen[1][1])


def test_stream_rate_counts_snapshots_and_p95_takes_every_call():
    clock = Clock()
    engine = FakeEngine(clock)
    g = graph(n=10)
    state = stream.State(engine=engine, graph=g, seed=1, chunk=4,
                         first_index=1, warm_s=[])
    w = stream.run_window(state, 0.1, clock=clock)
    # a pass: chunks of 4, 4, 2 edges (20, 20, 10 ms), snapshot 50 ms;
    # the first pass ends at 0.1 s, which closes the window
    assert w.seconds == pytest.approx(0.1)
    assert len(w.answers) == 1 and engine.seen == [10]
    assert w.call_s == pytest.approx([0.02, 0.02, 0.01])
    e2e = stream.end_to_end(w)
    assert e2e["ingest_edges_per_s"] == pytest.approx(10 / 0.1)
    assert e2e["ingest_p95_ms"] == pytest.approx(20.0)

    w = stream.run_window(state, 0.15, clock=clock)
    assert len(w.answers) == 2 and w.inputs == [1, 2]
    assert w.seconds == pytest.approx(0.2)
    assert len(w.call_s) == 6


def test_p95_is_the_nearest_rank():
    assert p95(range(1, 101)) == 95
    assert p95([3.0]) == 3.0
    assert p95([5, 1, 4, 2, 3]) == 5          # ceil(4.75) = 5th of 5
    assert p95(list(range(1, 21))) == 19
    with pytest.raises(ValueError):
        p95([])


def test_variant_shifts_and_relabels_by_seed_and_index():
    g = graph(n=50, n_nodes=7)
    a, b = variant(g, 3, seed=9), variant(g, 3, seed=9)
    assert np.array_equal(a.u, b.u) and np.array_equal(a.t, g.t + 3)
    c = variant(g, 4, seed=9)
    assert not np.array_equal(a.u, c.u)
    # a relabelling: the same edges up to a bijection of node ids
    pairs = {(int(x), int(y)) for x, y in zip(g.u, a.u)}
    assert len({x for x, _ in pairs}) == len(pairs)
    assert a.u.dtype == g.u.dtype and a.t.dtype == g.t.dtype
