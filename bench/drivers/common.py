"""What the drivers share: graph variants and the window's record."""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench.generators.rng import stream

#: tag of the relabelling permutations' random stream
RELABEL_TAG = 11


@dataclasses.dataclass(frozen=True)
class Graph:
    u: np.ndarray
    v: np.ndarray
    t: np.ndarray
    n_nodes: int

    @property
    def n_edges(self) -> int:
        return int(self.u.shape[0])


def variant(graph: Graph, index: int, seed: int) -> Graph:
    """The graph with timestamps shifted by ``index`` seconds and node ids
    relabelled by a permutation drawn from ``(seed, index)``.  Motif counts
    are invariant under both; the zone plan's shapes too."""
    perm = stream(seed, RELABEL_TAG, index).permutation(graph.n_nodes)
    perm = perm.astype(graph.u.dtype)
    return Graph(u=perm[graph.u], v=perm[graph.v], t=graph.t + index,
                 n_nodes=graph.n_nodes)


@dataclasses.dataclass
class Window:
    """One measured window.

    ``answers`` are the count tables due in the window, one per mine or
    pass, and ``inputs`` the variant index each was computed from;
    ``call_s`` holds the duration of each timed call (mines or ingest
    calls), ``edges`` the input edges the window processed and ``seconds``
    its length on the host clock.
    """

    seconds: float
    edges: int
    call_s: list
    answers: list
    inputs: list


def p95(values) -> float:
    """Nearest-rank 95th percentile: the smallest value that at least 95%
    of the values do not exceed."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)]
