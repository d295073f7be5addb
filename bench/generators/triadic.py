"""Triadic closure among heavy-tailed users (vectorised).

A copy of ``repro.data.synthetic_graphs.triadic_stream`` (the
``wikitalk-like`` analog) kept with the benchmark, without its Python loop,
with one addition: fresh endpoints follow a power law.

Edge ``i`` comes ``U[1, gap_max_s]`` seconds after edge ``i - 1``.  From
the third edge on, with probability ``p_close`` it closes on an edge
``(a, b)`` drawn uniformly from the last ``recent_edges`` edges, with a
fresh node ``c``: it is ``(b, c)`` with probability 1/2, ``(a, b)`` with
1/4 and ``(c, a)`` with 1/4.  Otherwise both endpoints are fresh.  A fresh
node has popularity rank ``r`` with probability proportional to
``r ** -alpha`` (ranks 1 to ``n_nodes``, drawn by inverse CDF), and ranks
map to ids through a fixed permutation that gives the top rank the last
id, so the id space is the whole ``n_nodes``.

An endpoint copied from an earlier edge may itself be a copy.  Copies are
resolved by pointer jumping over the ``2 * n_edges`` endpoint slots: each
round, every slot still pointing at a copy takes that copy's pointer, so a
chain of ``k`` copies resolves in about ``log2(k)`` rounds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .rng import stream

#: closure kinds: the closing edge is (b, c), (a, b) or (c, a)
BC, AB, CA = 0, 1, 2
#: guide-table intervals per rank of the fresh-endpoint sampler (at least)
GUIDE = 2


@dataclasses.dataclass(frozen=True)
class Draw:
    """The times and closures drawn, before any endpoint.

    Edge ``i`` closes on edge ``ref[i]`` with ``kind[i]``, or is fresh
    where ``ref[i]`` is -1.
    """

    t: np.ndarray
    ref: np.ndarray
    kind: np.ndarray


def draw(params: dict, seed: int) -> Draw:
    n = int(params["n_edges"])
    gaps = stream(seed, 0).integers(1, int(params["gap_max_s"]) + 1, n)
    rng = stream(seed, 1)
    closing = rng.random(n) < float(params["p_close"])
    closing[:2] = False
    edge = np.arange(n, dtype=np.int32)
    window = np.minimum(edge, int(params["recent_edges"]))
    dist = 1 + (rng.random(n) * window).astype(np.int32)
    ref = np.where(closing, edge - dist, -1)
    kind = np.array([BC, BC, AB, CA], np.int8)[rng.integers(0, 4, n)]
    return Draw(t=np.cumsum(gaps), ref=ref, kind=kind)


def sources(d: Draw) -> np.ndarray:
    """Per endpoint slot (``2i`` is edge i's u, ``2i + 1`` its v), the
    slot it copies, or itself where the endpoint is a fresh node."""
    ptr = np.arange(2 * d.ref.shape[0], dtype=np.int32)
    i = np.flatnonzero(d.ref >= 0).astype(np.int32)
    j, kind = d.ref[i], d.kind[i]
    ptr[2 * i] = np.where(kind == BC, 2 * j + 1,      # (b, c)
                          np.where(kind == AB, 2 * j, 2 * i))
    ptr[2 * i + 1] = np.where(kind == AB, 2 * j + 1,  # (a, b)
                              np.where(kind == CA, 2 * j, 2 * i + 1))
    return ptr


def fresh_ids(rng: np.random.Generator, size: int, *, n_nodes: int,
              alpha: float, perm: np.ndarray) -> np.ndarray:
    """Node ids of popularity rank ``r ~ r ** -alpha``.

    Inverse CDF: the rank of ``x = U * total`` is the number of CDF
    entries at or below ``x``.  A guide table over a power of two ``k``
    (at least ``GUIDE`` per rank) of equal intervals holds that number at
    each interval's start; ``U * k`` is exact, so the start is a lower
    bound, at most a few ranks low, and vectorised steps over the draws
    not yet in place finish it (a binary search per draw is slower at this
    size).
    """
    cdf = np.cumsum(np.arange(1, n_nodes + 1, dtype=np.float64) ** -alpha)
    k = 1 << (GUIDE * n_nodes - 1).bit_length()
    guide = np.searchsorted(cdf, np.arange(k) / k * cdf[-1], side="right")
    guide = np.minimum(guide, n_nodes - 1).astype(np.int32)
    draws = rng.random(size)
    x = draws * cdf[-1]
    rank = guide[(draws * k).astype(np.int32)]
    up = np.flatnonzero(cdf[rank] <= x)
    while up.size:
        rank[up] += 1
        up = up[(rank[up] < n_nodes - 1) & (cdf[rank[up]] <= x[up])]
    return perm[rank]


def generate(params: dict, seed: int):
    d = draw(params, seed)
    ptr = sources(d)
    fresh = ptr == np.arange(ptr.shape[0], dtype=np.int32)
    n_nodes = int(params["n_nodes"])
    perm = stream(seed, 2).permutation(n_nodes).astype(np.int32)
    top = int(np.flatnonzero(perm == n_nodes - 1)[0])
    perm[[0, top]] = perm[[top, 0]]
    ids = np.zeros(ptr.shape[0], np.int32)
    ids[fresh] = fresh_ids(stream(seed, 3), int(fresh.sum()),
                           n_nodes=n_nodes, alpha=float(params["alpha"]),
                           perm=perm)
    moving = np.flatnonzero(~fresh)
    while moving.size:
        target = ptr[moving]
        ptr[moving] = ptr[target]
        moving = moving[ptr[moving] != target]
    ends = ids[ptr].reshape(-1, 2)
    return ends[:, 0], ends[:, 1], d.t
