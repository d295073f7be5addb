"""The benchmark harness: one run of one cell, driven by ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name:

* ``bench/configs/<config>.json`` (the file named in ``BENCHMARK.json``):
  the deployment, its generator (``bench/generators/<name>.py``) and its
  plain reference (``bench/reference/<name>.py``);
* ``bench/mixes/<traffic>.json``: the mix's parameters and its driver
  (``bench/drivers/<driver>.py``), which runs set-up and the window;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A run: look for the chips the cell asks for (none: exit 3, no result);
generate the configuration's graph; build one ``PTMTEngine``; warm every
shape the window uses (all of this is ``setup_s``); measure for
``--seconds``; read the device's peak memory; compare every answer of the
window with the reference (``bench/check.py``); print the numbers compared,
then the result as the last line of standard output.  ``--trace 1`` runs
the same window with the program's spans on and the profiler recording,
and reports the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import check
from bench.drivers.common import Graph, variant
from bench.generators.rng import stream

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: JAX monitoring events that mean a program was traced or compiled (or
#: loaded from the persistent cache), and the persistent cache's answers
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
#: compilations slower than this are named in the set-up log
SLOW_COMPILE_S = 2.0
#: random-stream tag of the answer the reference is run on
SAMPLE_TAG = 13


class UnknownName(LookupError):
    """A workload, configuration, mix, driver or metric that is not there."""


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# -- finding things by name ---------------------------------------------------


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise UnknownName(f"no {what} named {name!r}")


def _read_json(path: str, what: str, name: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise UnknownName(f"no {what} named {name!r} ({path})") from None


def _module(package: str, name: str):
    if not name.isidentifier():
        raise UnknownName(f"{package} name {name!r} is not an identifier")
    try:
        return importlib.import_module(f"bench.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"bench.{package}.{name}":
            raise UnknownName(f"no {package} module named {name!r}") from None
        raise


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise UnknownName(f"no metric reader named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics._" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, resolved."""

    name: str
    chips: int
    config: dict
    mix: dict
    driver: object
    generator: object
    reference: object
    end_to_end: list
    per_layer: dict          # metric name -> reader
    units: dict              # metric name -> unit


def resolve(workload: str, root: str = ROOT) -> Cell:
    spec = load_spec(root)
    cell = by_name(spec["workloads"], workload, "workload")
    entry = by_name(spec["configs"], cell["config"], "configuration")
    config = _read_json(os.path.join(root, entry["file"]), "configuration",
                        entry["name"])
    mix = _read_json(os.path.join(root, "bench", "mixes",
                                  f"{cell['traffic']}.json"),
                     "traffic mix", cell["traffic"])
    return Cell(
        name=workload,
        chips=int(cell["chips"]),
        config=config,
        mix=mix,
        driver=_module("drivers", mix["driver"]),
        generator=_module("generators", config["generator"]["name"]),
        reference=_module("reference", config["reference"]),
        end_to_end=[m["name"] for m in spec["end_to_end"]
                    if _applies(m, workload)],
        per_layer={m["name"]: load_reader(m["name"],
                                          os.path.join(root, "bench"))
                   for m in spec["per_layer"] if _applies(m, workload)},
        units={m["name"]: m["unit"]
               for m in spec["end_to_end"] + spec["per_layer"]},
    )


# -- set-up pieces ------------------------------------------------------------


def require_chips(n: int):
    """The devices of a run; a CPU-only JAX or too few chips is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``<checkout>/.jax_cache``, as ``repro.launch.compile_cache`` uses), or
    where ``JAX_COMPILATION_CACHE_DIR`` says.  Every program is cached, so
    a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs traced and compiled (or loaded from the persistent
    cache), and the persistent cache's hits and misses, while ``active``;
    ``slow`` lists the compilations that took over ``SLOW_COMPILE_S``."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self._monitoring = jax.monitoring
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def reset(self) -> None:
        self.traces = self.compiles = self.hits = self.misses = 0
        self.slow = []

    def _on_time(self, event: str, duration: float, **kwargs) -> None:
        if not self.active:
            return
        if event == TRACE_EVENT:
            self.traces += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
            if duration > SLOW_COMPILE_S:
                self.slow.append((kwargs.get("fun_name", "?"), duration))

    def _on_event(self, event: str, **kwargs) -> None:
        if self.active and event == CACHE_HIT_EVENT:
            self.hits += 1
        elif self.active and event == CACHE_MISS_EVENT:
            self.misses += 1

    def summary(self) -> str:
        slow = ", ".join(f"{n} {d:.1f} s" for n, d in self.slow)
        return (f"{self.traces} traces, {self.compiles} compiles, "
                f"persistent cache {self.hits} hits / {self.misses} misses"
                + (f"; slow: {slow}" if slow else ""))

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on_time)
        self._monitoring.unregister_event_listener(self._on_event)


def make_graph(cell: Cell) -> Graph:
    """The configuration's graph, from its own generator seed."""
    gen = cell.config["generator"]
    u, v, t = cell.generator.generate(gen["params"], int(gen["seed"]))
    t = np.asarray(t, np.int64)
    t = t - t[0]
    if t[-1] >= 2**31 - 2**24:
        raise ValueError("the graph's span does not fit int32 seconds")
    return Graph(u=np.asarray(u, np.int32), v=np.asarray(v, np.int32),
                 t=t.astype(np.int32),
                 n_nodes=int(max(u.max(), v.max())) + 1)


# -- the per-layer readers' view of a traced window ---------------------------


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    name: str
    start_us: float
    dur_us: float
    tid: int

    @property
    def dur_ms(self) -> float:
        return self.dur_us / 1e3

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read.

    ``spans``: the program's spans that ended inside the window;
    ``stats``: the change of each numeric ``EngineStats`` field over the
    window; ``n_answers``: mines or passes; ``call_ms``: the host-clock
    duration of each timed call (mines or ingest calls); ``device``: the
    trace reduction (:func:`bench.trace_reduce.reduce`) of the traced
    stretch, or None without one.
    """

    spans: list
    stats: dict
    n_answers: int
    call_ms: list
    device: dict | None

    @property
    def n_calls(self) -> int:
        return len(self.call_ms)

    def self_ms(self, span: SpanRecord, children) -> float:
        """``span``'s duration less that of its ``children`` spans (by
        name, on its thread, inside its interval)."""
        inner = sum(s.dur_us for s in self.spans
                    if s.name in children and s.tid == span.tid
                    and s.start_us >= span.start_us
                    and s.end_us <= span.end_us)
        return (span.dur_us - inner) / 1e3


def _stats(engine) -> dict:
    return {k: v for k, v in engine.stats.as_dict().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


# -- one run ------------------------------------------------------------------


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t0: float, root: str = ROOT, keep_trace: str | None = None,
             look_for_chips: bool = True, log=None) -> dict:
    """One run of one cell; returns the result object (see module doc).
    ``look_for_chips=False`` runs on whatever devices JAX has."""
    import jax

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    devices = require_chips(cell.chips) if look_for_chips else jax.devices()
    cache = enable_compile_cache(root)
    counter = CompileCounter()
    try:
        return _run(cell, seed=seed, seconds=seconds, trace=trace, t0=t0,
                    devices=devices, counter=counter, keep_trace=keep_trace,
                    log=log, cache=cache)
    finally:
        counter.close()


def _run(cell: Cell, *, seed, seconds, trace, t0, devices, counter,
         keep_trace, log, cache) -> dict:
    import jax

    from repro.core.config import MiningConfig
    from repro.core.engine import PTMTEngine
    from repro.obs import enabled as obs_enabled

    log(f"cell {cell.name}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"{len(devices)} x {devices[0].device_kind}, cache {cache}")
    t = time.perf_counter()
    graph = make_graph(cell)
    log(f"set-up: graph {graph.n_edges} edges, {graph.n_nodes} node ids, "
        f"span {graph.t[-1] / 86400:.1f} days, "
        f"{time.perf_counter() - t:.3f} s")
    obs = obs_enabled() if trace else None
    engine = PTMTEngine(MiningConfig(**cell.config["mining"]), obs=obs)
    t = time.perf_counter()
    counter.active = True
    state = cell.driver.prepare(engine, graph, cell.mix, seed)
    log(f"set-up: warm-up {time.perf_counter() - t:.3f} s "
        f"({', '.join(f'{x:.3f}' for x in state.warm_s)} s per call); "
        f"{counter.summary()}")
    counter.reset()

    stretch = None
    if trace:
        first, count = cell.mix["trace_calls"]
        stretch = TraceStretch(first, count, obs.tracer,
                               keep_trace or tempfile.mkdtemp(
                                   prefix="bench-trace-"))
    n_events = len(obs.tracer.events()) if trace else 0
    stats0 = _stats(engine)
    setup_s = time.perf_counter() - t0
    window = cell.driver.run_window(state, seconds, on_call=stretch)
    if stretch is not None:
        stretch.close()
    counter.active = False
    log(f"window: {window.seconds:.3f} s, {len(window.answers)} answers, "
        f"{len(window.call_s)} calls")
    log(f"compiles in window: {counter.compiles} ({counter.summary()})")
    stats = {k: v - stats0.get(k, 0) for k, v in _stats(engine).items()}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        events = obs.tracer.events()[n_events:]
        reduction = stretch.reduce(events)
        if keep_trace is None:
            shutil.rmtree(stretch.log_dir, ignore_errors=True)
        if reduction is not None:
            device.update(busy_s=reduction["busy_s"],
                          window_s=reduction["window_s"])
            breakdown = {
                "device_ops": [list(x) for x in reduction["device_ops"]],
                "idle_gaps": [list(x) for x in reduction["idle_gaps"]]}
        elif devices[0].platform != "cpu":
            raise RuntimeError("no device operation in the traced stretch")
        ctx = LayerContext(
            spans=[SpanRecord(e["name"], e["ts"], e["dur"], e["tid"])
                   for e in events if e["name"] != "bench.window"],
            stats=stats, n_answers=len(window.answers),
            call_ms=[1e3 * x for x in window.call_s], device=reduction)
        metrics = {}
        for name, read in cell.per_layer.items():
            value = read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": cell.units[name]}
    else:
        values = cell.driver.end_to_end(window)
        values["setup_s"] = setup_s
        metrics = {name: {"value": values[name], "unit": cell.units[name]}
                   for name in cell.end_to_end}

    # the program's state is let go before the reference runs
    answers, inputs = window.answers, window.inputs
    del state, engine, window
    pick = inputs[int(stream(seed, SAMPLE_TAG).integers(len(inputs)))]
    g = variant(graph, pick, seed)
    t = time.perf_counter()
    want = cell.reference.count_codes(g.u, g.v, g.t,
                                      **paper_params(cell.config))
    log(f"reference on input {pick}: {len(want)} codes, "
        f"{time.perf_counter() - t:.3f} s")
    numbers, failed = check.compare(answers, want)
    result = {"correct": check.passed(numbers), "attempted": len(answers),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def paper_params(config: dict) -> dict:
    m = config["mining"]
    return {"delta": int(m["delta"]), "l_max": int(m["l_max"])}


class TraceStretch:
    """The profiler over calls ``[first, first + count)`` of the window.

    A chip trace records every device operation, millions a second on the
    stream cell, so it covers a fixed stretch of calls (the mix's
    ``trace_calls``) and not the whole window.  Called by the driver around
    each call; the stretch is marked by a ``bench.window`` annotation in the
    profile and a ``bench.window`` span in the program's tracer, opened
    together, which puts the program's spans on the profile's clock.
    """

    def __init__(self, first: int, count: int, tracer, log_dir: str):
        self.first, self.last = int(first), int(first) + int(count) - 1
        self.tracer = tracer
        self.log_dir = log_dir
        self._open = None

    def __call__(self, k: int, before: bool) -> None:
        import jax

        if before and k == self.first:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            anno = jax.profiler.TraceAnnotation("bench.window")
            span = self.tracer.span("bench.window")
            anno.__enter__()
            span.__enter__()
            self._open = (anno, span)
        elif not before and k == self.last:
            self.close()

    def close(self) -> None:
        import jax

        if self._open is None:
            return
        anno, span = self._open
        span.__exit__(None, None, None)
        anno.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._open = None

    def reduce(self, events) -> dict | None:
        """The trace reduction of the stretch (None without a trace)."""
        import jax

        from bench import trace_reduce

        anchor = [e for e in events if e["name"] == "bench.window"]
        if not anchor:
            return None
        profile = jax.profiler.ProfileData.from_file(
            trace_reduce.find_xplane(self.log_dir))
        (lo, _), = trace_reduce.host_events(profile, "bench.window")
        ts = anchor[0]["ts"]
        spans = [(e["name"], lo + (e["ts"] - ts) * 1e3,
                  lo + (e["ts"] - ts + e["dur"]) * 1e3) for e in events]
        return trace_reduce.reduce(profile, spans)


# -- command line -------------------------------------------------------------


def main(argv=None, *, t0: float | None = None, root: str = ROOT) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    try:
        result = run_cell(resolve(args.workload, root), seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t0=t0, root=root, keep_trace=args.keep_trace)
    except (UnknownName, NoChip) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2 if isinstance(e, UnknownName) else 3
    for name, (value, rel, limit) in result["check"].items():
        print(f"check {name} = {value} (limit {rel} {limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
