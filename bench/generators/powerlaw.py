"""Power-law endpoints with Poisson arrivals (vectorised).

A copy of ``repro.data.synthetic_graphs.powerlaw_stream`` (the
``email-eu-like`` analog) kept with the benchmark, with the arrival rate
as a parameter, set to the published mean rate of the dataset.
"""

from __future__ import annotations

import numpy as np

from .rng import stream


def generate(params: dict, seed: int):
    n_edges = int(params["n_edges"])
    n_nodes = int(params["n_nodes"])
    alpha = float(params["alpha"])
    rate = float(params["rate_per_s"])
    gaps = stream(seed, 0).exponential(1.0 / rate, n_edges)
    t = np.cumsum(gaps).astype(np.int64)
    weights = np.arange(1, n_nodes + 1, dtype=np.float64) ** (-alpha)
    p = weights / weights.sum()
    rng = stream(seed, 1)
    u = rng.choice(n_nodes, n_edges, p=p)
    v = rng.choice(n_nodes, n_edges, p=p)
    return u, v, t
