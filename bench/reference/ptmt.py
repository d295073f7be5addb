"""Plain reference for motif-transition-process counts (Definitions 2-4).

Shares no code with the program.  Every edge seeds one process.  A process
whose last edge is at ``t_l`` absorbs the first later edge ``(u, v, t)``
with ``t_l < t <= t_l + delta`` that touches one of its nodes, until it has
``l_max`` edges or no such edge exists.  Its final code is the sequence of
first-occurrence node labels of its edges, written as hex digits (the edge
``(A, B), (B, C)`` gives ``"0112"``).

All processes advance in lockstep, one candidate edge per step, so the
loop runs as many times as the longest scan and each step is a few NumPy
operations over the processes still open.
"""

from __future__ import annotations

import numpy as np


def process_codes(u, v, t, *, delta: int, l_max: int):
    """Per seed edge, its process's label digits ``[n, 2 * l_max]`` (0 pads,
    else label + 1) and its length in edges."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    t = np.asarray(t)
    n = u.shape[0]
    max_nodes = l_max + 1
    nodes = np.full((n, max_nodes), -1, np.int64)
    nodes[:, 0] = u
    n_nodes = np.ones(n, np.int64)
    loop = u == v
    nodes[~loop, 1] = v[~loop]
    n_nodes[~loop] = 2
    digits = np.zeros((n, 2 * l_max), np.int64)
    digits[:, 0] = 1
    digits[:, 1] = np.where(loop, 1, 2)
    length = np.ones(n, np.int64)
    last_t = t.copy()
    cursor = np.arange(1, n + 1)

    open_ = np.flatnonzero((cursor < n) & (length < l_max))
    while open_.size:
        j = cursor[open_]
        tj = t[j]
        in_window = tj <= last_t[open_] + delta
        open_, j, tj = open_[in_window], j[in_window], tj[in_window]
        uj, vj = u[j], v[j]
        nd = nodes[open_]
        hit_u = nd == uj[:, None]
        hit_v = nd == vj[:, None]
        take = (tj > last_t[open_]) & (hit_u.any(1) | hit_v.any(1))

        p, uj, vj = open_[take], uj[take], vj[take]
        hit_u, hit_v = hit_u[take], hit_v[take]
        k = n_nodes[p]
        lab_u = np.where(hit_u.any(1), hit_u.argmax(1), k)
        k = k + ~hit_u.any(1)
        lab_v = np.where(hit_v.any(1), hit_v.argmax(1),
                         np.where(vj == uj, lab_u, k))
        new_v = ~hit_v.any(1) & (vj != uj)
        nodes[p, lab_u] = uj
        nodes[p, lab_v] = vj
        n_nodes[p] = k + new_v
        pos = 2 * length[p]
        digits[p, pos] = lab_u + 1
        digits[p, pos + 1] = lab_v + 1
        length[p] += 1
        last_t[p] = tj[take]

        cursor[open_] = j + 1
        keep = (cursor[open_] < n) & (length[open_] < l_max)
        open_ = open_[keep]
    return digits, length


def count_codes(u, v, t, *, delta: int, l_max: int) -> dict[str, int]:
    """Final code string -> number of processes that end with it."""
    digits, _ = process_codes(u, v, t, delta=delta, l_max=l_max)
    if digits.shape[0] == 0:
        return {}
    key = np.zeros(digits.shape[0], np.int64)
    for col in range(digits.shape[1]):
        key = key * 16 + digits[:, col]
    uniq, counts = np.unique(key, return_counts=True)
    width = digits.shape[1]
    out = {}
    for k, c in zip(uniq.tolist(), counts.tolist()):
        s = format(k, f"0{width}x")
        out["".join(format(int(d, 16) - 1, "x") for d in s if d != "0")] = c
    return out
