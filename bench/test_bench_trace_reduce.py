"""The interval and idle arithmetic of bench/trace_reduce.py, on synthetic
events."""

from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(device_ops, window, host=()):
    host_line = NS(name="python", events=[ev("bench.window", *window),
                                          *host])
    planes = [NS(name="/host:CPU", lines=[host_line])]
    for dev, ops in device_ops.items():
        planes.append(NS(name=dev, lines=[
            NS(name="XLA Modules", events=[ev("jit_step", 0, 10_000)]),
            NS(name=tr.OPS_LINE, events=[ev(*o) for o in ops])]))
    return NS(planes=planes)


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]


def test_busy_length_clips_to_the_window():
    ivs = [(0, 10), (5, 15), (20, 30), (40, 50)]
    assert tr.busy_length(ivs, 8, 45) == pytest.approx(7 + 10 + 5)
    assert tr.busy_length([], 0, 10) == 0


def test_idle_gaps_cover_what_busy_leaves():
    ivs = [(2, 4), (3, 6), (8, 9)]
    gaps = tr.idle_gaps(ivs, 0, 12)
    assert gaps == [(0, 2), (6, 8), (9, 12)]
    assert sum(e - s for s, e in gaps) + tr.busy_length(ivs, 0, 12) == 12


def test_top_ops_sums_per_name_inside_the_window():
    ops = [("fusion", 0, 10), ("sort", 10, 40), ("fusion", 50, 60),
           ("copy", 200, 300)]
    top = tr.top_ops(ops, 5, 100)
    assert top[0] == ("sort", pytest.approx(30e-9))
    assert top[1] == ("fusion", pytest.approx(15e-9))
    assert [n for n, _ in top] == ["sort", "fusion"]


def test_name_gaps_takes_the_innermost_open_span():
    spans = [("engine.discover", 0, 100), ("engine.plan", 10, 30)]
    named = tr.name_gaps([(12, 20), (40, 90), (150, 151)], spans)
    assert named == [("engine.discover", pytest.approx(50e-9)),
                     ("engine.plan", pytest.approx(8e-9)),
                     ("none", pytest.approx(1e-9))]


def test_reduce_on_a_synthetic_profile():
    # window [100, 1100): device 0 busy 100..300 and 500..600 (300 ns),
    # device 1 busy 100..600 (500 ns); an op before the window is ignored
    p = profile({"/device:TPU:0": [("a", 0, 300), ("b", 250, 50),
                                   ("c", 500, 100)],
                 "/device:TPU:1": [("a", 100, 500)]},
                window=(100, 1000),
                host=[ev("stream.finalize", 300, 200)])
    spans = [("stream.finalize", 300, 500)]
    out = tr.reduce(p, spans)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx((300 + 500) / 2 * 1e-9)
    assert out["idle_pct"] == pytest.approx(60.0)
    assert out["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    assert out["idle_gaps"][0] == ("none", pytest.approx(500e-9))
    assert out["idle_gaps"][1] == ("stream.finalize", pytest.approx(200e-9))


def test_reduce_without_device_planes_returns_none():
    assert tr.reduce(profile({}, window=(0, 10))) is None


def test_reduce_needs_exactly_one_window():
    p = profile({"/device:TPU:0": [("a", 0, 5)]}, window=(0, 10),
                host=[ev("bench.window", 20, 5)])
    with pytest.raises(ValueError, match="one bench.window"):
        tr.reduce(p)


def test_trace_stretch_records_only_its_calls(tmp_path):
    import jax
    import jax.numpy as jnp

    from bench import harness
    from repro.obs.tracing import Tracer

    tracer = Tracer()
    stretch = harness.TraceStretch(1, 2, tracer, str(tmp_path))
    f = jax.jit(lambda x: x * 2)
    for k in range(4):
        stretch(k, True)
        with tracer.span("work"):
            f(jnp.ones(8)).block_until_ready()
        stretch(k, False)
        assert (stretch._open is not None) == (k == 1)
    stretch.close()                       # already closed: no-op
    events = tracer.events()
    window = [e for e in events if e["name"] == "bench.window"]
    assert len(window) == 1
    inside = [e for e in events if e["name"] == "work"
              and window[0]["ts"] <= e["ts"] <= window[0]["ts"]
              + window[0]["dur"]]
    assert len(inside) == 2
    profile = jax.profiler.ProfileData.from_file(
        tr.find_xplane(str(tmp_path)))
    assert len(tr.host_events(profile, "bench.window")) == 1
    # the CPU has no device plane: nothing to reduce, and no error
    assert stretch.reduce(events) is None


def test_a_stretch_past_the_window_is_closed_by_close(tmp_path):
    from bench import harness
    from repro.obs.tracing import Tracer

    stretch = harness.TraceStretch(0, 10, Tracer(), str(tmp_path))
    stretch(0, True)
    stretch(0, False)
    assert stretch._open is not None
    stretch.close()
    assert stretch._open is None
    assert harness.TraceStretch(5, 1, Tracer(), str(tmp_path)).reduce(
        []) is None
