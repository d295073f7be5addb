"""Batch mix: whole exact mines back to back through ``engine.discover``.

The window runs until ``seconds`` have passed and then finishes the mine in
flight, so it always ends at the end of a mine.
"""

from __future__ import annotations

import dataclasses
import time

from repro.core.temporal_graph import TemporalGraph

from .common import Graph, Window, variant


@dataclasses.dataclass
class State:
    engine: object
    graph: Graph
    seed: int
    first_index: int
    warm_s: list


def _mine(engine, g: Graph):
    return engine.discover(TemporalGraph(u=g.u, v=g.v, t=g.t,
                                         n_nodes=g.n_nodes)).counts


def prepare(engine, graph: Graph, mix: dict, seed: int) -> State:
    warm_s = []
    for i in range(int(mix["warmup_mines"])):
        t0 = time.perf_counter()
        _mine(engine, variant(graph, i, seed))
        warm_s.append(time.perf_counter() - t0)
    return State(engine=engine, graph=graph, seed=seed,
                 first_index=len(warm_s), warm_s=warm_s)


def run_window(state: State, seconds: float, clock=time.perf_counter,
               on_call=None) -> Window:
    """``on_call(k, before)`` is called around the window's k-th mine."""
    answers, inputs, call_s = [], [], []
    index = state.first_index
    start = clock()
    while True:
        k = len(answers)
        if on_call:
            on_call(k, True)
        t0 = clock()
        g = variant(state.graph, index, state.seed)
        answers.append(_mine(state.engine, g))
        end = clock()
        call_s.append(end - t0)
        if on_call:
            on_call(k, False)
        inputs.append(index)
        index += 1
        if end - start >= seconds:
            break
    return Window(seconds=end - start, edges=len(answers) *
                  state.graph.n_edges, call_s=call_s, answers=answers,
                  inputs=inputs)


def end_to_end(window: Window) -> dict:
    return {"mine_edges_per_s": window.edges / window.seconds}
