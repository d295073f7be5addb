"""The executor's scan readers: ``bucket_scan_ms.batch`` from the
``mine.bucket_scan`` spans and ``sweep_slots_per_mine.batch`` from
``EngineStats.sweep_slots``, on made-up spans and counters, and None where
a program without them gives nothing to read."""

import pytest

from bench import harness

SCAN = harness.load_reader("bucket_scan_ms.batch")
SLOTS = harness.load_reader("sweep_slots_per_mine.batch")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")


def ctx(spans=(), stats=None, n_answers=2):
    return harness.LayerContext(
        spans=[harness.SpanRecord(name, start, dur, tid=1)
               for name, start, dur in spans],
        stats=stats or {}, n_answers=n_answers,
        call_ms=[10.0] * n_answers, device=None)


def test_scan_time_is_summed_per_mine():
    spans = [("mine.launch", 0, 5_000), ("mine.bucket_h2d", 0, 1_000),
             ("mine.bucket_scan", 1_000, 3_000),
             ("mine.launch", 6_000, 2_000), ("mine.bucket_scan", 6_500, 1_000),
             ("mine.bucket_scan", 9_000, 2_000)]
    assert SCAN(ctx(spans)) == pytest.approx(3.0)      # 6 ms over 2 mines


def test_sweep_slots_are_read_per_mine():
    stats = {"discover_calls": 4, "sweep_slots": 4 * 25_225_668_608,
             "launches": 16}
    assert SLOTS(ctx(stats=stats)) == 25_225_668_608


@pytest.mark.parametrize("context", [
    ctx(spans=[("mine.launch", 0, 5_000), ("engine.discover", 0, 9_000)]),
    ctx(spans=[("mine.bucket_scan", 0, 5_000)], n_answers=0),
    ctx(stats={"discover_calls": 3, "launches": 15}),
    ctx(stats={"discover_calls": 0, "sweep_slots": 0}),
    ctx(stats={}),
], ids=["no-scan-span", "no-mine", "no-counter", "no-discover", "empty"])
def test_nothing_to_read_gives_none(context):
    assert SCAN(context) is None and SLOTS(context) is None


def test_a_traced_small_batch_run_reads_both():
    """The scan lies inside the executor's time, and the slots are the
    padded zones by e squared of each mine's layout."""
    import dataclasses
    import time

    from bench.test_bench_control import small_cell
    from repro.core import tzp
    from repro.core.temporal_graph import TemporalGraph

    cell = small_cell("email-eu.batch")
    cell = dataclasses.replace(cell, mix={**cell.mix, "trace_calls": [0, 1]})
    out = harness.run_cell(cell, seed=2**31 + 5, seconds=0.2, trace=True,
                           t0=time.perf_counter(), look_for_chips=False,
                           log=lambda msg: None)
    value = {k: m["value"] for k, m in out["metrics"].items()}
    assert 0 < value["bucket_scan_ms.batch"] < value["executor_ms.batch"]
    g = harness.make_graph(cell)
    mining = cell.config["mining"]
    plan = tzp.plan_zones(TemporalGraph(u=g.u, v=g.v, t=g.t,
                                        n_nodes=g.n_nodes), **mining)
    layout = tzp.build_zone_layout(
        TemporalGraph(u=g.u, v=g.v, t=g.t, n_nodes=g.n_nodes), plan)
    assert value["sweep_slots_per_mine.batch"] == layout.sweep_slots
