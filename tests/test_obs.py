"""Observability layer — registry exactness, span semantics, exports.

The guarantees the rest of the stack leans on:

* histograms are **exact** below the sample bound (nearest-rank, matching
  numpy's ``inverted_cdf``) and degrade to bucket interpolation above it;
* spans nest, time-contain their children, record their parent and root
  span (per thread), record the compilations that happen under them,
  appear in a ``jax.profiler`` trace under a prefixed name, and survive
  exceptions;
* the Chrome-trace and Prometheus exports are schema-valid and the JSON
  snapshot round-trips through ``json``;
* the disabled mode (``NULL_OBS``) is shared no-op singletons — no state,
  no files unless asked, identical call surface.
"""

import argparse
import json
import threading

import numpy as np
import pytest

import repro.obs as obs_mod
from repro.obs import NULL_OBS, Observability, get_obs
from repro.obs.metrics import (
    DEFAULT_MS_BUCKETS,
    MetricsRegistry,
    merged_percentile,
)
from repro.obs.timing import Stopwatch, latency_summary, percentile_ms
from repro.obs.tracing import Tracer


# -- metrics ----------------------------------------------------------------


def test_histogram_exact_below_sample_bound():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", sample_bound=64)
    rng = np.random.default_rng(3)
    values = rng.uniform(0.01, 900.0, 50)
    for v in values:
        h.observe(v)
    assert h.exact
    for q in (50, 95, 99):
        want = float(np.percentile(values, q, method="inverted_cdf"))
        assert h.percentile(q) == pytest.approx(want)
    snap = h.snapshot()
    assert snap["count"] == 50 and snap["exact"]
    assert snap["p50"] == pytest.approx(h.percentile(50))


def test_histogram_interpolates_above_sample_bound():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", sample_bound=8)
    values = [0.3, 0.4, 0.6, 1.5, 3.0, 4.0, 7.0, 8.0, 30.0, 700.0]
    for v in values:
        h.observe(v)
    assert not h.exact
    # interpolated percentiles stay inside the containing bucket
    p50 = h.percentile(50)
    assert 2.5 < p50 <= 5.0
    assert h.percentile(99) <= h.snapshot()["max"] == 700.0
    assert h.count == len(values)
    assert h.sum == pytest.approx(sum(values))


def test_histogram_rejects_bad_input():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(3.0, 1.0))
    h = reg.histogram("lat_ms")
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        reg.counter("c_total").inc(-1)


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", path="a")
    c2 = reg.counter("x_total", path="a")
    assert c1 is c2
    assert reg.counter("x_total", path="b") is not c1
    with pytest.raises(TypeError):
        reg.gauge("x_total", path="a")
    assert reg.find("x_total", path="a") is c1
    assert reg.find("nope") is None


def test_merged_percentile_exact_and_bucketed():
    reg = MetricsRegistry()
    a = reg.histogram("h", tenant="a")
    b = reg.histogram("h", tenant="b")
    va, vb = [1.0, 5.0, 9.0], [2.0, 4.0]
    for v in va:
        a.observe(v)
    for v in vb:
        b.observe(v)
    pooled = np.array(va + vb)
    assert merged_percentile([a, b], 50) == pytest.approx(
        float(np.percentile(pooled, 50, method="inverted_cdf")))
    assert merged_percentile([], 50) == 0.0
    # non-exact path: same edges required
    reg2 = MetricsRegistry()
    big = reg2.histogram("h2", sample_bound=2)
    for v in (0.2, 0.7, 3.0, 40.0):
        big.observe(v)
    assert not big.exact
    p = merged_percentile([big], 50)
    assert 0.5 < p <= 40.0
    odd = reg2.histogram("h3", buckets=(1.0, 2.0))
    odd.observe(1.5)
    with pytest.raises(ValueError):
        merged_percentile([big, odd], 50)


def test_prometheus_exposition_schema():
    reg = MetricsRegistry()
    reg.counter("repro_mining_launches_total", path="fused").inc(3)
    reg.gauge("repro_mining_fused_slots").set(128)
    h = reg.histogram("repro_serving_query_latency_ms", tenant="t0")
    h.observe(1.2)
    h.observe(700.0)
    text = reg.to_prometheus()
    assert "# TYPE repro_mining_launches_total counter" in text
    assert 'repro_mining_launches_total{path="fused"} 3' in text
    assert "# TYPE repro_mining_fused_slots gauge" in text
    assert ("# TYPE repro_serving_query_latency_ms histogram" in text)
    assert ('repro_serving_query_latency_ms_bucket'
            '{le="+Inf",tenant="t0"} 2') in text
    assert "repro_serving_query_latency_ms_count" in text
    assert "repro_serving_query_latency_ms_sum" in text
    # every non-comment line is "name{labels} value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        float(value)
        assert name_part.startswith("repro_")


def test_snapshot_is_json_roundtrippable():
    reg = MetricsRegistry()
    reg.counter("a_total").inc()
    reg.histogram("b_ms").observe(2.0)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert json.loads(json.dumps(snap)) == snap


# -- tracing ----------------------------------------------------------------


def test_span_nesting_and_containment():
    tr = Tracer()
    with tr.span("outer", layer="engine"):
        with tr.span("inner"):
            pass
    events = tr.events()
    assert [e["name"] for e in events] == ["inner", "outer"]
    inner, outer = events
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"]["layer"] == "engine"
    assert all(e["ph"] == "X" for e in events)
    assert tr.span_names() == {"inner", "outer"}


def _by_name(tr):
    return {e["name"]: e for e in tr.events()}


def test_span_parent_and_root_ids():
    tr = Tracer()
    with tr.span("root"):
        with tr.span("mid"):
            with tr.span("leaf"):
                pass
        with tr.span("sibling"):
            pass
    with tr.span("next_root"):
        pass
    ev = {n: e["args"] for n, e in _by_name(tr).items()}
    root = ev["root"]["span_id"]
    assert ev["root"]["parent_id"] is None and ev["root"]["root_id"] == root
    assert ev["mid"]["parent_id"] == root
    assert ev["leaf"]["parent_id"] == ev["mid"]["span_id"]
    assert ev["sibling"]["parent_id"] == root
    assert {ev[n]["root_id"] for n in ("mid", "leaf", "sibling")} == {root}
    nxt = ev["next_root"]
    assert nxt["parent_id"] is None and nxt["root_id"] == nxt["span_id"]
    ids = [a["span_id"] for a in ev.values()]
    assert len(set(ids)) == len(ids)


def test_span_ids_stay_on_their_thread():
    """A span opened on another thread while one is open here is a root
    of its own, and its children point at it, not at this thread's span."""
    tr = Tracer()
    gate = threading.Barrier(3)

    def worker(i):
        gate.wait()
        with tr.span(f"w{i}"):
            with tr.span(f"w{i}.child"):
                gate.wait()

    with tr.span("main"):
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        gate.wait()              # both workers are inside their spans now
        gate.wait()
        for t in threads:
            t.join()
    ev = {n: e["args"] for n, e in _by_name(tr).items()}
    for i in range(2):
        top, child = ev[f"w{i}"], ev[f"w{i}.child"]
        assert top["parent_id"] is None
        assert top["root_id"] == top["span_id"]
        assert child["parent_id"] == top["span_id"]
        assert child["root_id"] == top["span_id"]
    assert ev["main"]["parent_id"] is None


def test_compile_recorded_under_the_span_that_compiled():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    tr = Tracer(metrics=reg)

    def fresh_program(x):
        return jnp.cumsum(x * 3) - 1

    f = jax.jit(fresh_program)
    with tr.span("outer"):
        with tr.span("step") as sp:
            sp.sync(f(jnp.arange(13.0)))
    with tr.span("again"):
        f(jnp.arange(13.0)).block_until_ready()      # cached: no compile
    compiles = [e for e in tr.events() if e["name"] == "jax.compile"]
    mine = [e for e in compiles if "fresh_program" in e["args"]["fun_name"]]
    assert len(mine) == 1
    ev = _by_name(tr)
    args = mine[0]["args"]
    assert args["span"] == "step"
    assert args["parent_id"] == ev["step"]["args"]["span_id"]
    assert args["root_id"] == ev["outer"]["args"]["span_id"]
    assert mine[0]["dur"] > 0
    step = ev["step"]
    assert step["ts"] <= mine[0]["ts"]
    assert mine[0]["ts"] + mine[0]["dur"] <= step["ts"] + step["dur"]
    assert all(e["args"]["span"] != "again" for e in compiles)
    counter = reg.counter("repro_jax_compiles_total", span="step")
    assert counter.value == sum(e["args"]["span"] == "step"
                                for e in compiles) >= 1
    # outside every span nothing is recorded
    n = len(tr.events())
    jax.jit(lambda x: x - 7)(jnp.ones(5)).block_until_ready()
    assert len(tr.events()) == n


def test_compiles_on_threads_land_under_their_own_spans():
    import sys

    import jax
    import jax.numpy as jnp

    tr = Tracer()
    n = 6
    gate = threading.Barrier(n)
    errors = []

    def worker(i):
        try:
            f = jax.jit(lambda x: jnp.sin(x) * (i + 2))
            gate.wait(timeout=30)
            with tr.span(f"t{i}"):
                f(jnp.arange(float(i + 3))).block_until_ready()
        except Exception as e:                    # reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    spans = {e["args"]["span_id"]: e for e in tr.events()
             if e["name"] != "jax.compile"}
    compiles = [e for e in tr.events() if e["name"] == "jax.compile"]
    assert {spans[e["args"]["parent_id"]]["name"] for e in compiles} \
        == {f"t{i}" for i in range(n)}
    for e in compiles:
        parent = spans[e["args"]["parent_id"]]
        assert e["tid"] == parent["tid"]
        assert e["args"]["span"] == parent["name"]


def test_one_compile_listener_for_every_tracer():
    from jax._src import monitoring

    from repro.obs import tracing

    tracers = [Tracer() for _ in range(3)]
    listeners = [cb for cb in monitoring._event_duration_secs_listeners
                 if cb is tracing._on_duration_event]
    assert len(listeners) == 1
    assert all(t in tracing._live_tracers for t in tracers)


def test_spans_appear_in_a_profiler_trace_once(tmp_path):
    import jax

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("bench.window"):
            with tr.span("engine.decode"):
                pass
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    host = [ev.name for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    assert host.count("span:engine.decode") == 1
    assert host.count("span:bench.window") == 1
    # the bare names stay free for the caller's own annotations
    assert "engine.decode" not in host and "bench.window" not in host


def test_span_error_and_set_and_sync():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert tr.events()[0]["args"]["error"] == "RuntimeError"
    with tr.span("ok") as sp:
        sp.set(zones=7).sync(np.zeros(4))  # block_until_ready accepts numpy
    assert tr.events()[-1]["args"]["zones"] == 7


def test_tracer_bounded_buffer():
    tr = Tracer(max_events=2)
    for i in range(4):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 2
    assert tr.dropped == 2
    assert tr.to_chrome_trace()["otherData"]["dropped_events"] == 2


def test_chrome_trace_schema(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    path = tmp_path / "trace.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"  # process_name metadata first
    for e in events[1:]:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
        assert e["pid"] and e["tid"]
        assert {"span_id", "parent_id", "root_id"} <= set(e["args"])
    assert doc["otherData"] == {"dropped_events": 0}


def test_tracer_threads_keep_local_nesting():
    tr = Tracer()
    # barrier keeps all workers alive at once so thread ids are distinct
    gate = threading.Barrier(4)

    def worker(i):
        gate.wait()
        with tr.span(f"w{i}"):
            with tr.span(f"w{i}.child"):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.events()) == 8
    tids = {e["tid"] for e in tr.events()}
    assert len(tids) == 4


# -- bundle / disabled mode -------------------------------------------------


def test_null_obs_is_shared_noop():
    assert get_obs(None) is NULL_OBS
    assert not NULL_OBS.enabled
    # one shared span object, one shared instrument each — no allocation
    assert NULL_OBS.tracer.span("a") is NULL_OBS.tracer.span("b")
    assert (NULL_OBS.metrics.counter("x")
            is NULL_OBS.metrics.counter("y", l="v"))
    NULL_OBS.metrics.counter("x").inc()
    NULL_OBS.metrics.histogram("h").observe(1.0)
    NULL_OBS.metrics.gauge("g").set(2)
    assert NULL_OBS.metrics.snapshot() == {
        "counters": [], "gauges": [], "histograms": []}
    assert NULL_OBS.metrics.to_prometheus() == ""
    assert NULL_OBS.tracer.events() == []
    with NULL_OBS.tracer.span("nested") as sp:
        assert sp.set(a=1) is sp and sp.sync(None) is sp


def test_enabled_bundle_and_global_install():
    obs = obs_mod.enabled()
    assert obs.enabled
    assert isinstance(obs, Observability)
    try:
        obs_mod.install_global(obs)
        assert obs_mod.global_obs() is obs
    finally:
        obs_mod.install_global(None)
    assert obs_mod.global_obs() is NULL_OBS


def test_cli_helpers(tmp_path):
    ap = argparse.ArgumentParser()
    obs_mod.add_cli_args(ap)
    m_path = tmp_path / "metrics.json"
    t_path = tmp_path / "trace.json"
    args = ap.parse_args(
        ["--metrics-out", str(m_path), "--trace-out", str(t_path)])
    try:
        obs = obs_mod.from_cli_args(args)
        assert obs.enabled
        assert obs_mod.global_obs() is obs
        obs.metrics.counter("repro_mining_launches_total", path="fused").inc()
        with obs.tracer.span("mine.fused"):
            pass
        obs_mod.write_cli_outputs(obs, args)
    finally:
        obs_mod.install_global(None)
    metrics_doc = json.loads(m_path.read_text())
    assert set(metrics_doc) == {"metrics", "prometheus"}
    assert "# TYPE repro_mining_launches_total counter" \
        in metrics_doc["prometheus"]
    trace_doc = json.loads(t_path.read_text())
    assert any(e.get("name") == "mine.fused"
               for e in trace_doc["traceEvents"])
    # no flags → the null bundle, nothing installed, nothing written
    off = ap.parse_args([])
    assert obs_mod.from_cli_args(off) is NULL_OBS
    obs_mod.write_cli_outputs(NULL_OBS, off)


# -- timing helpers ---------------------------------------------------------


def test_stopwatch_and_latency_summary():
    with Stopwatch() as sw:
        live = sw.seconds
    assert 0.0 <= live <= sw.seconds
    frozen = sw.seconds
    assert sw.seconds == frozen  # frozen after exit
    assert sw.ms == pytest.approx(frozen * 1e3)

    lats = [0.001, 0.002, 0.004, 0.010]
    assert percentile_ms([], 50) == 0.0
    assert percentile_ms(lats, 50) == pytest.approx(
        float(np.percentile(np.array(lats) * 1e3, 50)))
    digest = latency_summary(lats)
    assert set(digest) == {"count", "mean_ms", "p50_ms", "p95_ms",
                           "p99_ms", "max_ms"}
    assert digest["count"] == 4
    assert digest["max_ms"] == pytest.approx(10.0)
