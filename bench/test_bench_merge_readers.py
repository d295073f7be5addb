"""The cross-bucket merge's reader: ``merge_rows_per_mine.batch`` from
``EngineStats.merge_rows``, on made-up counters, None where a program
without the counter gives nothing to read, and on a traced small run."""

import pytest

from bench import harness

ROWS = harness.load_reader("merge_rows_per_mine.batch")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


def ctx(stats=None, n_answers=2):
    return harness.LayerContext(
        spans=[], stats=stats or {}, n_answers=n_answers,
        call_ms=[10.0] * n_answers, device=None)


def test_merge_rows_are_read_per_mine():
    stats = {"discover_calls": 4, "merge_rows": 4 * 9_874_184,
             "sweep_slots": 4 * 1_656_556_928}
    assert ROWS(ctx(stats=stats)) == 9_874_184


@pytest.mark.parametrize("context", [
    ctx(stats={"discover_calls": 3, "sweep_slots": 15}),
    ctx(stats={"discover_calls": 0, "merge_rows": 0}),
    ctx(stats={}),
], ids=["no-counter", "no-discover", "empty"])
def test_nothing_to_read_gives_none(context):
    assert ROWS(context) is None


def test_a_traced_small_batch_run_reads_the_merge_rows():
    """Each mine's merge rows are those of one per-bucket run of the
    cell's graph."""
    import dataclasses
    import time

    from bench.test_bench_control import small_cell
    from repro.core import MiningExecutor, tzp
    from repro.core.temporal_graph import TemporalGraph

    cell = small_cell("email-eu.batch")
    cell = dataclasses.replace(cell, mix={**cell.mix, "trace_calls": [0, 1]})
    out = harness.run_cell(cell, seed=2**31 + 7, seconds=0.2, trace=True,
                           t0=time.perf_counter(), look_for_chips=False,
                           log=lambda msg: None)
    value = out["metrics"]["merge_rows_per_mine.batch"]["value"]
    g = harness.make_graph(cell)
    graph = TemporalGraph(u=g.u, v=g.v, t=g.t, n_nodes=g.n_nodes)
    mining = cell.config["mining"]
    layout = tzp.build_zone_layout(graph, tzp.plan_zones(graph, **mining))
    assert layout.n_buckets > 1
    _, stats = MiningExecutor(
        delta=mining["delta"], l_max=mining["l_max"]).run_layout(layout)
    assert value == stats["merge_rows"] > 0
