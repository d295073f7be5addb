"""Stream mix: closed-loop replay through ``engine.stream()``.

Each pass feeds the whole graph in time order, ``chunk_edges`` at a time,
to a fresh miner, the next chunk as soon as ``ingest`` returns, and ends
with ``snapshot(final=True)``.  The window runs until ``seconds`` have
passed and then finishes the pass in flight.
"""

from __future__ import annotations

import dataclasses
import time

from .common import Graph, Window, p95, variant


@dataclasses.dataclass
class State:
    engine: object
    graph: Graph
    seed: int
    chunk: int
    first_index: int
    warm_s: list


def _pass(engine, g: Graph, chunk: int, clock, call_s: list,
          on_call=None) -> dict:
    miner = engine.stream()
    for lo in range(0, g.n_edges, chunk):
        k = len(call_s)
        if on_call:
            on_call(k, True)
        t0 = clock()
        miner.ingest(g.u[lo:lo + chunk], g.v[lo:lo + chunk],
                     g.t[lo:lo + chunk])
        call_s.append(clock() - t0)
        if on_call:
            on_call(k, False)
    return miner.snapshot(final=True).counts


def prepare(engine, graph: Graph, mix: dict, seed: int) -> State:
    chunk = int(mix["chunk_edges"])
    warm_s = []
    for i in range(int(mix["warmup_passes"])):
        t0 = time.perf_counter()
        _pass(engine, variant(graph, i, seed), chunk, time.perf_counter, [])
        warm_s.append(time.perf_counter() - t0)
    return State(engine=engine, graph=graph, seed=seed, chunk=chunk,
                 first_index=len(warm_s), warm_s=warm_s)


def run_window(state: State, seconds: float, clock=time.perf_counter,
               on_call=None) -> Window:
    """``on_call(k, before)`` is called around the window's k-th ingest
    call."""
    answers, inputs, call_s = [], [], []
    index = state.first_index
    start = clock()
    while True:
        g = variant(state.graph, index, state.seed)
        answers.append(_pass(state.engine, g, state.chunk, clock, call_s,
                             on_call))
        inputs.append(index)
        index += 1
        end = clock()
        if end - start >= seconds:
            break
    return Window(seconds=end - start,
                  edges=len(answers) * state.graph.n_edges, call_s=call_s,
                  answers=answers, inputs=inputs)


def end_to_end(window: Window) -> dict:
    return {"ingest_edges_per_s": window.edges / window.seconds,
            "ingest_p95_ms": 1e3 * p95(window.call_s)}
