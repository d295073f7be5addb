"""Chip smoke test: exact motif-transition mining end to end on a TPU.

    python3 chip_smoke.py [--seed N]              # one chip, every phase
    python3 chip_smoke.py --four-chips [--seed N] # engine.sharded only

Drives the main path through the entry points users call — ``PTMTEngine``
(``discover``, ``sequential``, ``stream``, ``sharded``) and
``MotifService`` — at published graph sizes, with data made from
``--seed``, under the paper defaults ``delta=600, l_max=6, omega=20``.
Every result must be byte-identical across lowerings and devices:

mine      An Email-Eu-core-temporal-sized power-law stream (332,334 edges
          over 986 nodes, the SNAP size; the ``email-eu-like`` generator):
          ``discover`` with the default ``MiningConfig()`` and with
          ``backend="pallas"`` (fused, one launch); the same mine on the
          host's CPU device (NumPy zone scan); and the NumPy oracle, which
          shares no code with the engine.
mine-xla  The same check for the ``fused_backend="xla"`` lowering against
          fused Pallas, the host mine and the oracle, on the graph's first
          16,384 edges: that lowering sweeps every lane from its zone row's
          start, 3.6M loop steps at full size, which did not finish within
          990 s on a TPU v5e.
exact     A CollegeMsg-sized Poisson stream (59,835 edges over 1,899
          nodes): the fused Pallas ``discover`` equals
          ``engine.sequential`` (one zone, no partitioning) under the
          default config — the ``--check-sequential`` signal.
stream    ``engine.stream()`` replays the mine graph in 4,096-edge chunks;
          its final snapshot equals the oracle's counts.
serve     A ``MotifService`` with 2 tenants on the CollegeMsg-sized graph
          answers every query kind in each round; each answer equals the
          same query over batch discovery of the tenant's closed prefix,
          and the ``serve_motifs.py --verify`` check passes at the end.

The phases run concurrently, one thread each, so their compilations
overlap; the chip runs one program at a time.  ``--four-chips`` runs only
``engine.sharded`` over a 4-device mesh, with ``backend="pallas"`` and with
the default config, each compared byte for byte with the one-chip fused
``discover``; it compiles every bucket's sharded program concurrently
before the runs.

The script refuses to run without a TPU, or with ``REPRO_PALLAS_INTERPRET``
set, and asserts that every mine ran the lowering it asked for (no
interpreter, no reroute).  Seconds it prints are set-up plus run,
compilation included, while other phases share the host — not a
performance metric.  On success the last line of standard output is
``{"ok": true, "device": {...}}``; any failure exits non-zero without that
line.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import hashlib
import json
import os
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: published sizes (SNAP): email-Eu-core-temporal and CollegeMsg
EMAIL_EU_EDGES = 332_334
COLLEGEMSG_EDGES = 59_835
#: prefix of the email graph on which the ``xla`` lowering is checked
XLA_CHECK_EDGES = 16_384
STREAM_CHUNK_EDGES = 4096
SERVE_TENANTS = 2
SERVE_ROUNDS = 2
#: collective unique-code budget of the sharded merge; above any device's
#: candidate count here, so it never truncates
SHARDED_OUT_CAP = 1 << 19


class SmokeFailure(AssertionError):
    """A phase produced a wrong or unexpected result."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def digest(counts: dict) -> str:
    blob = json.dumps(sorted(counts.items()), separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def describe(counts: dict) -> str:
    return (f"{len(counts)} motif types, {sum(counts.values())} processes, "
            f"digest {digest(counts)}")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


_T0 = time.perf_counter()
_PRINT_LOCK = threading.Lock()


def say(phase: str, msg: str) -> None:
    """Print one report line; phases run in threads, so lines are locked."""
    with _PRINT_LOCK:
        print(f"[{phase} +{time.perf_counter() - _T0:.0f}s] {msg}",
              flush=True)


def device_line(device) -> str:
    return f"{device.platform} {device.device_kind} (id {device.id})"


def run_tasks(tasks: dict) -> dict:
    """Run zero-argument callables in threads; results keyed as given.

    Re-raises the first failure once every task has ended.
    """
    with concurrent.futures.ThreadPoolExecutor(len(tasks)) as pool:
        futures = {label: pool.submit(fn) for label, fn in tasks.items()}
    return {label: f.result() for label, f in futures.items()}


def prefix(graph, n_edges: int):
    from repro.core.temporal_graph import TemporalGraph

    return TemporalGraph(u=graph.u[:n_edges], v=graph.v[:n_edges],
                         t=graph.t[:n_edges], n_nodes=graph.n_nodes)


def requested_lowering(config) -> str | None:
    """The fused lowering a config asks for; None for the per-bucket path."""
    from repro.core import backends

    name = (config.fused_backend if config.fused_backend != "auto"
            else config.backend)
    if config.fused == "off" or not backends.get_backend(name).supports_fused:
        return None
    return name


def check_lowering(config, stats: dict) -> str:
    """Assert a discover ran the lowering its config asked for."""
    want = requested_lowering(config)
    if want is None:
        require(stats.get("path") == "per-bucket",
                f"expected the per-bucket path, ran {stats}")
        return "per-bucket"
    require(stats.get("backend") == want and stats.get("launches") == 1,
            f"asked for the fused {want!r} lowering in one launch, "
            f"ran {stats}")
    return f"{stats['path']} ({want}, 1 launch, "\
           f"{stats.get('spill_retries', 0)} spill retries)"


def run_discover(phase: str, label: str, graph, engine) -> dict:
    """``engine.discover`` with a lowering check and one report line."""
    res, dt = timed(lambda: engine.discover(graph))
    lowering = check_lowering(engine.config, res.layout["execution"])
    say(phase, f"{label}: {lowering} | {dt:.1f} s set-up+run | "
               f"{describe(res.counts)}")
    return res.counts


def host_discover(phase: str, graph, config, host_device) -> dict:
    """The same discover on the host's CPU device, NumPy zone scan."""
    import jax

    from repro.core import PTMTEngine

    with jax.default_device(host_device):
        res, dt = timed(lambda: PTMTEngine(
            config.with_updates(backend="numpy", fused_backend="auto")
        ).discover(graph))
    say(phase, f"host {device_line(host_device)}: numpy scan, "
               f"{res.layout['execution']['path']} | {dt:.1f} s "
               f"set-up+run | {describe(res.counts)}")
    return res.counts


def oracle_counts(phase: str, graph, config) -> dict:
    """The NumPy oracle's counts, which share no code with the engine."""
    from repro.core import oracle

    got, dt = timed(lambda: dict(oracle.count_codes(
        graph.u, graph.v, graph.t, config.delta, config.l_max)))
    say(phase, f"numpy oracle: {dt:.1f} s | {describe(got)}")
    return got


def verdict(phase: str, counts_by_label: dict) -> dict:
    """Require byte-identical counts across every labelled run."""
    labels = list(counts_by_label)
    first = counts_by_label[labels[0]]
    bad = [lab for lab in labels[1:] if counts_by_label[lab] != first]
    require(not bad, f"{phase}: counts of {bad} differ from {labels[0]!r}")
    say(phase, f"verdict: byte-identical counts across {', '.join(labels)}")
    return first


# -- phases --------------------------------------------------------------


def phase_mine(graph, engines: dict, *, host_device, oracle=None,
               phase: str = "mine") -> dict:
    """Every chip engine, the host-CPU mine and the oracle agree.

    ``oracle`` returns the oracle's counts (e.g. a future shared with
    another phase); by default they are computed here.  The runs are
    concurrent.
    """
    config = next(iter(engines.values())).config
    tasks = {label: functools.partial(run_discover, phase, label, graph,
                                      engine)
             for label, engine in engines.items()}
    tasks["host-cpu"] = functools.partial(host_discover, phase, graph,
                                          config, host_device)
    tasks["oracle"] = oracle or functools.partial(oracle_counts, phase,
                                                  graph, config)
    return verdict(phase, run_tasks(tasks))


def phase_exact(graph, fused_engine, sequential_engine) -> dict:
    """Fused discover == the one-zone sequential baseline."""
    def sequential():
        res, dt = timed(lambda: sequential_engine.sequential(graph))
        say("exact", f"sequential ({sequential_engine.backend} scan, 1 "
                     f"zone) | {dt:.1f} s set-up+run | "
                     f"{describe(res.counts)}")
        return res.counts

    return verdict("exact", run_tasks({
        "fused": functools.partial(run_discover, "exact", "fused discover",
                                   graph, fused_engine),
        "sequential": sequential}))


def phase_stream(graph, engine, expect, *,
                 chunk_edges: int = STREAM_CHUNK_EDGES) -> dict:
    """A chunked replay's final snapshot equals ``expect()``, the expected
    counts (read only once the replay is done)."""
    from repro.core.streaming import replay_stream

    miner = engine.stream()
    (lat, _), dt = timed(lambda: replay_stream(miner, graph, chunk_edges))
    res, dt_final = timed(lambda: miner.snapshot(final=True))
    say("stream", f"{len(lat)} chunks of {chunk_edges} edges, "
                  f"{miner.n_zones_finalized} zones finalized | "
                  f"{dt + dt_final:.1f} s set-up+run | "
                  f"{describe(res.counts)}")
    return verdict("stream", {"stream": res.counts, "expected": expect()})


def _expected_answer(engine, req):
    if req.op == "top_k":
        return engine.top_k_motifs(level=req.level, k=req.k)
    if req.op == "transition_probs":
        return engine.transition_probs(req.code)
    if req.op == "prefix_count":
        return engine.prefix_count(req.code)
    return engine.level_histogram()


def phase_serve(graph, engine, ref, *, tenants: int = SERVE_TENANTS,
                rounds: int = SERVE_ROUNDS) -> dict:
    """Every served answer equals the answer over batch discovery by
    ``ref``, the engine of another lowering."""
    import numpy as np

    from repro.core.api import DiscoveryResult
    from repro.launch.serve_motifs import tenant_streams, verify_against_batch
    from repro.serving.motif import MotifService, QueryRequest
    from repro.serving.motif.query import QueryEngine

    streams = tenant_streams(graph, tenants)
    names = [f"tenant{i}" for i in range(tenants)]
    per_round = -(-max(g.n_edges for g in streams) // rounds)
    service = MotifService(engine=engine, ingest_batch=per_round)
    for name in names:
        service.create_session(name)
    ref_config = ref.config
    answers: dict[str, int] = {}
    t0 = time.perf_counter()
    for r in range(rounds):
        for name, g in zip(names, streams):
            lo, hi = r * per_round, min((r + 1) * per_round, g.n_edges)
            if lo < hi:
                service.ingest(name, g.u[lo:hi], g.v[lo:hi], g.t[lo:hi])
                service.flush(name)
            closed = service.manager.get(name).closed_time
            cut = 0 if closed is None else int(
                np.searchsorted(g.t, closed, side="left"))
            if cut:
                expect = ref.discover(prefix(g, cut))
            else:
                expect = DiscoveryResult(
                    counts={}, n_zones=0, e_cap=0, overflow=0,
                    delta=ref_config.delta, l_max=ref_config.l_max)
            require(expect.overflow == 0,
                    f"batch reference for {name} overflowed")
            oracle_engine = QueryEngine(expect)
            top = oracle_engine.top_k_motifs(k=1)
            code = top[0][0] if top else ""
            for req in (
                    QueryRequest(session=name, op="top_k", k=8),
                    QueryRequest(session=name, op="top_k", level=2, k=8),
                    QueryRequest(session=name, op="transition_probs",
                                 code=code[:2]),
                    QueryRequest(session=name, op="prefix_count",
                                 code=code[:4]),
                    QueryRequest(session=name, op="level_histogram")):
                got = service.query(req).payload
                want = _expected_answer(oracle_engine, req)
                require(got == want,
                        f"round {r} {name} {req.op}({req.code!r}): served "
                        f"{got!r}, batch discovery of the {cut}-edge closed "
                        f"prefix gives {want!r}")
                answers[req.op] = answers.get(req.op, 0) + 1
    dt = time.perf_counter() - t0
    rows = verify_against_batch(
        service, names, streams, delta=ref_config.delta,
        l_max=ref_config.l_max, omega=ref_config.omega,
        e_cap=ref_config.e_cap, backend=ref_config.backend)
    require(all(row["match"] is True for row in rows),
            f"serve_motifs --verify check failed: {rows}")
    say("serve", f"{tenants} tenants x {rounds} rounds, answers "
                 f"{answers} | {dt:.1f} s set-up+run")
    say("serve", "verdict: every answer equals batch discovery of the "
                 "closed prefix; --verify: "
                 + ", ".join(f"{r['tenant']} exact on {r['prefix_edges']} "
                             f"edges" for r in rows))
    return answers


def precompile_sharded(engine, graph, mesh, axes, *, out_cap: int) -> int:
    """Compile every bucket's sharded step for ``graph``, concurrently.

    Builds the same step ``engine.sharded`` builds and lowers it with the
    same device arrays, so each executable lands in the persistent
    compilation cache under the key the later call looks up (a miss only
    costs the compile again).  Returns the number of programs compiled.
    """
    import jax.numpy as jnp

    from repro.distributed import mining as dist_mining

    n_shards = mesh.devices.size
    _, layout = engine._plan_and_layout(graph, n_shards=n_shards)
    step = dist_mining.make_mine_step(mesh, axes, executor=engine.executor,
                                      out_cap=out_cap)

    def compile_bucket(b):
        step.lower(*(jnp.asarray(x) for x in (
            b.u, b.v, b.t, b.valid, b.sign))).compile()

    run_tasks({i: functools.partial(compile_bucket, b)
               for i, b in enumerate(layout.buckets)})
    return len(layout.buckets)


def phase_sharded(graph, engines: dict, one_chip_engine, devices) -> dict:
    """engine.sharded over a mesh == the one-chip discover."""
    import jax

    say("sharded", "devices: " + ", ".join(device_line(d) for d in devices))
    axes = ("zones",)
    mesh = jax.make_mesh((len(devices),), axes, devices=devices)

    def warm(label, engine):
        n, dt = timed(lambda: precompile_sharded(
            engine, graph, mesh, axes, out_cap=SHARDED_OUT_CAP))
        say("sharded", f"{label}: {n} bucket programs compiled in {dt:.1f} s")

    tasks = {label: functools.partial(warm, label, engine)
             for label, engine in engines.items()}
    tasks["one-chip"] = functools.partial(
        run_discover, "sharded", "one-chip discover", graph, one_chip_engine)
    counts = {"one-chip": run_tasks(tasks)["one-chip"]}
    for label, engine in engines.items():
        res, dt = timed(lambda: engine.sharded(
            graph, mesh, axes, out_cap=SHARDED_OUT_CAP))
        say("sharded", f"{label} on {len(devices)} devices "
                       f"({engine.backend} scan per shard) | {dt:.1f} s "
                       f"set-up+run | {describe(res.counts)}")
        counts[f"sharded {label}"] = res.counts
    return verdict("sharded", counts)


# -- driver ----------------------------------------------------------------


def run_phases(phases: dict, device) -> list[str]:
    """Run the phases concurrently; return the names of those that failed."""
    def guarded(name, fn):
        say(name, f"device: {device_line(device)}")
        try:
            fn()
        except Exception:
            traceback.print_exc()
            say(name, "FAILED")
            return False
        return True

    ok = run_tasks({name: functools.partial(guarded, name, fn)
                    for name, fn in phases.items()})
    return [name for name, passed in ok.items() if not passed]


def run_one_chip(email, college, *, configs: dict, host_device, device,
                 xla_edges: int = XLA_CHECK_EDGES) -> list[str]:
    """Every one-chip phase, concurrently; returns the failed phases.

    ``configs`` holds the ``default``, ``pallas`` and ``xla`` mining
    configs.  Each phase builds its own engines, since phases run in
    their own threads; the email graph's oracle is computed once, for
    both the mine and the stream phase.
    """
    from repro.core import PTMTEngine

    engine = lambda name: PTMTEngine(configs[name])
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        email_oracle = pool.submit(oracle_counts, "mine", email,
                                   configs["default"])
        return run_phases({
            "mine": lambda: phase_mine(
                email, {"default": engine("default"),
                        "pallas": engine("pallas")},
                host_device=host_device, oracle=email_oracle.result),
            "mine-xla": lambda: phase_mine(
                prefix(email, xla_edges),
                {"pallas": engine("pallas"), "xla": engine("xla")},
                host_device=host_device, phase="mine-xla"),
            "exact": lambda: phase_exact(
                college, engine("pallas"), engine("default")),
            "stream": lambda: phase_stream(
                email, engine("pallas"), email_oracle.result),
            "serve": lambda: phase_serve(
                college, engine("pallas"), engine("default")),
        }, device)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only engine.sharded on a 4-device mesh")
    args = ap.parse_args(argv)

    if "REPRO_PALLAS_INTERPRET" in os.environ:
        print("chip_smoke: REPRO_PALLAS_INTERPRET is set; unset it — the "
              "smoke run executes compiled kernels only", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    from repro.core import MiningConfig, PTMTEngine
    from repro.data import synthetic_graphs
    from repro.kernels.common import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); this "
              f"smoke run measures nothing on another backend",
              file=sys.stderr)
        return 1
    if resolve_interpret(None, quiet=True):
        print("chip_smoke: Pallas would interpret on this host",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {devices[0].platform} {devices[0].device_kind} x"
          f"{len(devices)}, jax {jax.__version__}", flush=True)

    (email, college), dt = timed(lambda: (
        synthetic_graphs.make("email-eu-like", args.seed,
                              n_edges=EMAIL_EU_EDGES),
        synthetic_graphs.make("collegemsg-like", args.seed,
                              n_edges=COLLEGEMSG_EDGES)))
    print(f"data (seed {args.seed}, {dt:.1f} s): email-eu-like "
          f"{email.n_edges} edges / {email.n_nodes} nodes, collegemsg-like "
          f"{college.n_edges} edges / {college.n_nodes} nodes", flush=True)

    configs = {"default": MiningConfig(),
               "pallas": MiningConfig(backend="pallas"),
               "xla": MiningConfig(fused_backend="xla")}
    if args.four_chips:
        engine = lambda name: PTMTEngine(configs[name])
        failed = run_phases({"sharded": lambda: phase_sharded(
            email, {"pallas": engine("pallas"), "default": engine("default")},
            engine("pallas"), devices[:4])}, devices[0])
    else:
        failed = run_one_chip(email, college, configs=configs,
                              host_device=jax.devices("cpu")[0],
                              device=devices[0])
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
