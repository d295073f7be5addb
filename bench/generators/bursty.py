"""Bursts of messages among small groups, separated by quiet gaps
(vectorised).

A copy of ``repro.data.synthetic_graphs.bursty_stream`` (the
``sms-a-like`` analog) kept with the benchmark, without its Python loop.
Each burst holds ``k ~ U[1, burst_size]`` edges (the last one is cut to the
remaining count) at ``U[0, burst_span)`` seconds after the burst's start,
among a group of ``max(2, k // 3 + 2)`` nodes drawn uniformly; a gap of
``U[gap_span, 2 * gap_span)`` seconds follows each burst.
"""

from __future__ import annotations

import numpy as np

from .rng import stream


def generate(params: dict, seed: int):
    n_edges = int(params["n_edges"])
    n_nodes = int(params["n_nodes"])
    burst_size = int(params["burst_size"])
    burst_span = int(params["burst_span_s"])
    gap_span = int(params["gap_span_s"])

    arr = stream(seed, 0)
    k = arr.integers(1, burst_size + 1, size=n_edges)   # >= n_edges bursts
    ends = np.cumsum(k)
    n_bursts = int(np.searchsorted(ends, n_edges)) + 1
    k = k[:n_bursts].copy()
    k[-1] -= int(ends[n_bursts - 1]) - n_edges
    gaps = gap_span + arr.integers(0, gap_span, size=n_bursts)
    start = np.concatenate([[0], np.cumsum(gaps[:-1])])
    burst = np.repeat(np.arange(n_bursts), k)
    t = start[burst] + arr.integers(0, burst_span, size=n_edges)

    rng = stream(seed, 1)
    group = np.maximum(2, k // 3 + 2)
    group_lo = np.concatenate([[0], np.cumsum(group[:-1])])
    members = rng.integers(0, n_nodes, size=int(group.sum()))
    pick = rng.random((2, n_edges)) * group[burst]
    u = members[group_lo[burst] + pick[0].astype(np.int64)]
    v = members[group_lo[burst] + pick[1].astype(np.int64)]

    order = np.argsort(t, kind="stable")
    return u[order], v[order], t[order]
