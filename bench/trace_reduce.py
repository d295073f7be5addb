"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-operation time.

The trace is the ``*.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/``.  Planes named ``/device:<KIND>:<n>``
are the chips; on a TPU each has a line ``XLA Ops`` with one event per
executed HLO operation, which is what busy time is made of (the ``XLA
Modules`` and ``Steps`` lines span whole programs and would count the
gaps between operations as busy).  Host threads are on ``/host:CPU``; the
harness writes a ``bench.window`` annotation there that marks the measured
stretch.  All times are nanoseconds on the profile's clock.
"""

from __future__ import annotations

import glob
import os

#: the device line whose events are single operations
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_length(intervals, lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` covered by at least one interval."""
    return sum(e - s for s, e in clip(union(intervals), lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The uncovered stretches of ``[lo, hi)``, in time order."""
    gaps, cur = [], lo
    for s, e in clip(union(intervals), lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def device_ops(profile) -> dict[str, list[tuple[str, float, float]]]:
    """``{device plane: [(op name, start_ns, end_ns), ...]}`` from each
    device plane's ``XLA Ops`` line."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
        if ops:
            out[plane.name] = ops
    return out


def host_events(profile, name: str) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of every host event called ``name``."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def top_ops(ops, lo: float, hi: float, k: int = 10):
    """The ``k`` operation names with the most device time in ``[lo, hi)``
    (seconds, summed over their events)."""
    total: dict[str, float] = {}
    for name, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            total[name] = total.get(name, 0.0) + d * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])[:k]


def name_gaps(gaps, spans, k: int = 10):
    """The ``k`` longest gaps, each named by the innermost host span open at
    its midpoint (``spans``: ``(name, start_ns, end_ns)``), in seconds."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        inner, width = "none", float("inf")
        for name, ss, se in spans:
            if ss <= mid < se and se - ss < width:
                inner, width = name, se - ss
        out.append((inner, (e - s) * 1e-9))
    return out


def reduce(profile, spans=()) -> dict | None:
    """Busy and idle accounting of the ``bench.window`` stretch, or None
    where the profile has no device plane with operations (a CPU run).

    Returns ``window_s``, ``busy_s`` (the union of every device's operation
    intervals, averaged over the devices that ran any), ``idle_pct`` and
    the ``device_ops`` and ``idle_gaps`` of the breakdown (taken on the
    first device).  ``spans`` are host spans on the profile's clock, used
    to name the gaps.
    """
    windows = host_events(profile, "bench.window")
    if len(windows) != 1:
        raise ValueError(f"expected one bench.window annotation, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    per_device = device_ops(profile)
    if not per_device:
        return None
    busy = [busy_length([(s, e) for _, s, e in ops], lo, hi)
            for ops in per_device.values()]
    first = per_device[sorted(per_device)[0]]
    gaps = idle_gaps([(s, e) for _, s, e in first], lo, hi)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "devices": sorted(per_device),
        "device_ops": top_ops(first, lo, hi),
        "idle_gaps": name_gaps(gaps, spans),
    }
