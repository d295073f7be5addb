"""Unified mining executor — ONE chunked scan+aggregate engine.

Every discovery entry point (batch ``discover``, the sequential baseline,
``distributed.mining.mine_on_mesh`` and the streaming miner) routes through
:class:`MiningExecutor` instead of carrying its own copy of the zone sweep:

* backend dispatch goes through :mod:`repro.core.backends` (capability-aware,
  pluggable);
* zone chunking (chunks of ``zone_chunk`` zones to bound peak memory) is
  implemented once, with an explicit policy for zone counts that do not
  divide ``zone_chunk`` — **pad** (default: append inert zero-sign rows) or
  **raise** — never a silent remainder drop;
* every run mines a config lattice (see :class:`repro.core.planner.
  ConfigLattice`): ``params``, a tuple of member ``(delta_i, l_max_i)``
  pairs, each dominated by the executor's ``(delta, l_max)``.  One config
  is the one-member lattice; its scan asks for no absorption timestamps,
  so it traces the same program a config-only executor would;
* Phase-2 aggregation has two folds (``agg``):

  - ``"legacy"``      — materialize every chunk's candidate codes, then one
                        whole-batch flatten-and-sort (peak O(Z*C));
  - ``"hierarchical"``— fold each chunk through ``count_codes`` immediately
                        and tree-merge the partial tables inside the
                        ``lax.scan`` carry via
                        :func:`repro.core.aggregation.merge_bounded` — a
                        bounded-width merge whose capacity is ``merge_cap``.
                        Peak memory is O(zone_chunk*C + merge_cap),
                        independent of the zone count.  Spills (more live
                        unique codes than ``merge_cap``) are detected
                        exactly and retried host-side with a larger cap
                        (:func:`_grow_cap`), so results are always exact;
  - ``"auto"`` (default) resolves to ``"hierarchical"`` when chunking is
    active and ``"legacy"`` otherwise (identical numerics either way —
    enforced by ``tests/test_differential.py``).  A lattice of several
    members always takes the bounded fold: N whole-batch tables would each
    be as wide as the batch;

* ``zone_chunk`` itself no longer has to be a hardcoded hint: pass
  ``memory_budget_mb`` and the executor derives the chunk (and
  ``merge_cap``) from the backend's memory model via
  :mod:`repro.core.planner`;
* the jitted programs are module-level, so their compile caches are
  shared by every executor instance;
* host-only backends (``jittable=False``, e.g. the NumPy oracle) run their
  scan outside the jit boundary and only the signed aggregation is jitted.

``scan_aggregate``/``scan_aggregate_partial`` are the traceable cores
(usable inside ``shard_map``); ``run`` is the host-level entry that applies
batching policy first and refuses to mis-report overflowed (edge-dropping)
zone batches as exact counts.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import get_obs

from . import aggregation, backends, encoding, expansion, planner
from .aggregation import CodeCounts
from .tzp import (FUSED_BOUNDS, ZoneBatch, ZoneBatchLayout, concat_layout,
                  next_pow2, pad_zone_arrays)

AGG_MODES = ("auto", "legacy", "hierarchical")


class RunOutcome(NamedTuple):
    """A layout run's result plus the stats of the dispatch that made it.

    ``stats`` travels with the counts instead of being read back off the
    executor, so concurrent runs through one shared executor can no longer
    misattribute each other's ``path``/``launches``/``spill_retries``.
    """

    counts: CodeCounts
    stats: dict


class MultiRunOutcome(NamedTuple):
    """A co-mined layout run: one count table per lattice member config."""

    counts: tuple          # tuple[CodeCounts, ...], aligned with params
    stats: dict


class _BucketRun(NamedTuple):
    """One bucket launch: its counts and what its scans visited.

    ``counts`` holds one table per lattice member.  ``trips`` (a device
    scalar, or an int from a host scan) sums the launch's per-chunk scan
    trips; every chunk has ``rows`` zone rows of ``e`` slots, so the
    candidate-steps are ``rows * e * trips``.  ``retries`` counts its
    merge-cap spill retries.
    """

    counts: tuple
    trips: object
    rows: int
    e: int
    retries: int


def _sweep_slots(runs) -> int:
    """Candidate-steps a layout's bucket launches visited, read in one
    copy after their counts reached the host."""
    trips = jax.device_get([r.trips for r in runs])
    return sum(r.rows * r.e * int(n) for r, n in zip(runs, trips))


def _chunk_rows(z: int, zc: int) -> int:
    """Zone rows one scan of a (padded) ``z``-row batch sees."""
    return zc if zc and zc < z else z

#: Fused single-launch dispatch policy for ``run_layout``: "auto" fuses
#: whenever the backend publishes a bucket-native flat kernel, "on"
#: requires one (erroring otherwise), "off" keeps the per-bucket path.
FUSED_MODES = ("auto", "on", "off")


def _grow_cap(cap: int, n_spilled: int, ceiling: int) -> int:
    """The merge cap to retry with after ``n_spilled`` codes spilled.

    ``cap + n_spilled`` approximates the live-code population (a code cut
    in several steps is counted per step, so it can only overshoot the
    next guess); the cap at least doubles, is rounded up to a power of
    two, and is clamped to ``ceiling``, a cap that provably cannot spill,
    so every retry loop terminates.
    """
    need = max(2 * cap, cap + n_spilled, 8)
    return min(1 << (need - 1).bit_length(), ceiling)


def _grow_spilled(caps, spills, ceiling: int, *, what: str, path: str,
                  obs) -> tuple:
    """Grow the caps of the members that spilled; warn and count the retry
    under the counter label ``path`` (``-multi`` for a co-mine, whose
    warning lists every member's spill).  ``what`` names the merge."""
    new = tuple(_grow_cap(c, n, ceiling) if n else c
                for c, n in zip(caps, spills))
    if path.endswith("-multi"):
        msg = (f"{what} co-mine spilled {list(spills)} unique code(s) across "
               f"{len(caps)} member config(s); retrying with "
               f"merge_caps={list(new)}")
    else:
        msg = (f"{what} merge spilled {spills[0]} unique code(s) at "
               f"merge_cap={caps[0]}; retrying with merge_cap={new[0]}")
    warnings.warn(msg, RuntimeWarning, stacklevel=4)
    obs.metrics.counter("repro_mining_spill_retries_total", path=path).inc()
    return new


@functools.partial(jax.jit, static_argnames=("cap", "width"))
def _merge_part_jit(carry, part, *, cap, width):
    """One cross-bucket ``merge_bounded`` step over ``part``'s first
    ``width`` rows, named ``merge`` in the program (a scope opened around
    an eager call would not reach it).  The slice fuses into the merge's
    concat, so the sort covers ``cap + width`` rows, not the whole part."""
    with jax.named_scope("merge"):
        part = CodeCounts(*(x[:width] for x in part))
        return aggregation.merge_bounded(carry, part, cap=cap)


class MergeOutcome(NamedTuple):
    """A cross-bucket merge's table and the rows its sorts covered:
    ``cap + width`` summed over every step of every spill attempt (0
    when a single part needed no merge)."""

    counts: CodeCounts
    rows: int


def merge_partial_counts(
    parts,
    *,
    merge_cap: int | None = None,
    warn_label: str = "partial",
    obs=None,
) -> MergeOutcome:
    """Fold per-bucket (or per-shard) count tables through ``merge_bounded``.

    The cross-bucket analog of the hierarchical chunk fold: partial tables
    stream through one bounded-width carry instead of a single unbounded
    concat-and-sort, so the resident merge state is O(cap) regardless of
    how many buckets a layout produced.  ``merge_cap`` seeds the carry
    width; a spill (more live unique codes than rows) is detected exactly
    and retried with a larger cap (:func:`_grow_cap`), capped at the
    provably-sufficient ceiling (total live rows + 1 slot for the all-zero
    padding group), so the result is always exact.

    Each part enters its step trimmed to its live prefix: ``n`` unique
    codes lie in its first ``n + 1`` rows (the :class:`CodeCounts`
    invariant), so ``min(rows, next_pow2(n + 1))`` rows keep them all, and
    the power of two bounds the step's compiled shapes.
    """
    obs = get_obs(obs)
    parts = list(parts)
    if not parts:
        raise ValueError("merge_partial_counts needs at least one table")
    if len(parts) == 1:
        return MergeOutcome(parts[0], 0)
    limbs = int(parts[0].codes.shape[1])
    live = [int(n) for n in
            jax.device_get([p.unique_mask.sum() for p in parts])]
    widths = [min(int(p.unique_mask.shape[0]), next_pow2(n + 1))
              for p, n in zip(parts, live)]
    ceiling = sum(live) + 1
    cap = min(int(merge_cap), ceiling) if merge_cap else ceiling
    cap = max(cap, 8)
    rows = 0
    with obs.tracer.span("mine.fold", parts=len(parts)) as sp:
        while True:
            carry = aggregation.empty_counts(cap, limbs)
            spilled = jnp.zeros((), jnp.int32)
            for part, width in zip(parts, widths):
                carry, spill = _merge_part_jit(carry, part, cap=cap,
                                               width=width)
                spilled = spilled + spill
                rows += cap + width
            n_spilled = int(spilled)
            if n_spilled == 0:
                sp.set(merge_cap=cap, merge_rows=rows).sync(carry)
                return MergeOutcome(carry, rows)
            cap, = _grow_spilled((cap,), (n_spilled,), ceiling,
                                 what=warn_label, path="fold", obs=obs)


class ZoneChunkError(ValueError):
    """Zone count does not divide ``zone_chunk`` under pad_policy='raise'."""


class ZoneOverflowError(RuntimeError):
    """The zone batch dropped edges (``ZoneBatch.overflow > 0``).

    Counts mined from such a batch undercount silently; the executor
    refuses to run unless the caller opts in with ``allow_overflow=True``
    (which still warns).  Raise-by-default is the regression guard for the
    bug where ``build_zone_batch`` tallied dropped edges but every consumer
    ignored the tally.
    """


def _trips(res, e: int):
    """Trips a zone scan ran: the count it reports, else a full sweep of
    ``e`` slots (backends that do not bound their loop)."""
    return e if res.trips is None else res.trips


def _sweep(scan, *args, params, delta, l_max, **kw):
    """Run the dominating ``(delta, l_max)`` scan for the members in
    ``params``.  It is asked for per-step absorption timestamps only when
    some member is smaller, so a one-member lattice traces the plain scan
    and runs on backends without co-mine support too."""
    if any(p != (delta, l_max) for p in params):
        kw["with_ts"] = True
    return scan(*args, delta=delta, l_max=l_max, **kw)


def _member_views(code, length, ts, *, params, delta, l_max):
    """Every member's ``(code, length)`` view of dominating sweep output,
    aligned with ``params``.

    The dominating member is the sweep itself; every smaller ``(delta,
    l_max)`` is the timestamp-gap prefix truncation
    (:func:`repro.core.expansion.derive_lengths` +
    :func:`repro.core.encoding.truncate_codes`) — lossless because zone
    streams are time-sorted, so prefix processes of the dominating sweep
    are exactly what the smaller config would have mined.
    """
    views = []
    for d_i, l_i in params:
        if (d_i, l_i) == (delta, l_max):
            views.append((code, length))
        else:
            len_i = expansion.derive_lengths(length, ts, delta=d_i, l_max=l_i)
            views.append((encoding.truncate_codes(code, len_i), len_i))
    return views


def _empty_members(merge_caps, limbs: int) -> tuple:
    """Fresh ``(counts, spilled)`` carries, one per member cap."""
    return tuple((aggregation.empty_counts(cap, limbs), jnp.int32(0))
                 for cap in merge_caps)


def _merge_member(member, part, cap: int):
    """Fold one partial table into a member's ``(counts, spilled)``."""
    counts, spilled = member
    merged, spill = aggregation.merge_bounded(counts, part, cap=cap)
    return merged, spilled + spill


def _padded_zones(z: int, zone_chunk: int) -> int:
    """Zone rows after padding ``z`` up to a multiple of ``zone_chunk``
    (pad_policy 'pad'); ``z`` itself when unchunked."""
    if zone_chunk and zone_chunk < z and z % zone_chunk:
        return z + zone_chunk - z % zone_chunk
    return z


def _n_chunks(z: int, zone_chunk: int) -> int:
    if z % zone_chunk != 0:
        raise ZoneChunkError(
            f"zone count {z} is not divisible by zone_chunk "
            f"{zone_chunk}; pad the batch (pad_policy='pad') or pick a "
            f"divisor — remainder zones would otherwise be dropped"
        )
    return z // zone_chunk


@functools.partial(
    jax.jit, static_argnames=("delta", "l_max", "scan", "zone_chunk",
                              "params")
)
def _mine_jit(u, v, t, valid, signs, *, delta, l_max, scan, zone_chunk,
              params):
    """Jitted legacy path: full zone sweep, then one whole-batch aggregation
    per lattice member.

    jax.jit keys its cache on the static args plus input shapes, so every
    executor instance with the same (scan fn, delta, l_max, params,
    zone_chunk, batch shape) reuses one executable.  The cache is keyed on
    the resolved scan *callable*, not the backend name, so re-registering
    a backend (``overwrite=True``) cannot serve a stale executable.
    Returns ``(*counts, trips)``: one table per member of ``params``, then
    the trips, summed over the chunks.  Shapes are static here, so chunk
    divisibility is checked at trace time (the host path pads beforehand
    under pad_policy='pad').
    """

    def chunk_fn(args):
        cu, cv, ct, cvalid = args
        with jax.named_scope("zone_scan"):
            res = _sweep(scan, cu, cv, ct, cvalid, params=params,
                         delta=delta, l_max=l_max)
        return (res.code, res.length, res.ts), \
            jnp.int32(_trips(res, u.shape[1]))

    z = u.shape[0]
    if zone_chunk and zone_chunk < z:
        nchunk = _n_chunks(z, zone_chunk)
        reshape = lambda x: x.reshape(nchunk, zone_chunk, *x.shape[1:])
        out, trips = jax.lax.map(
            chunk_fn, (reshape(u), reshape(v), reshape(t), reshape(valid)))
        out = jax.tree.map(lambda x: x.reshape(z, *x.shape[2:]), out)
        trips = trips.sum()
    else:
        out, trips = chunk_fn((u, v, t, valid))
    codes, lengths, ts = out
    with jax.named_scope("fold"):
        views = _member_views(codes, lengths, ts, params=params, delta=delta,
                              l_max=l_max)
        return (*(aggregation.aggregate_zones(c, n, signs)
                  for c, n in views), trips)


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "zone_chunk", "params",
                     "merge_caps"),
)
def _mine_jit_hier(u, v, t, valid, signs, *, delta, l_max, scan, zone_chunk,
                   params, merge_caps):
    """Jitted hierarchical streaming aggregation (shared compile cache, as
    ``_mine_jit``).

    Each zone-chunk is scanned once at the dominating ``(delta, l_max)``
    and, for every member of ``params``, immediately signed-counted
    (``aggregate_zones``) from its derived view; the partial tables fold
    through bounded-width carries (``merge_bounded``, one per member cap
    in ``merge_caps``) inside ``lax.scan``, so at no point do all Z*C
    candidate codes coexist.  Returns ``(members, trips)``: one
    ``(CodeCounts[cap], spilled)`` per member — ``spilled > 0`` means its
    cap was too small and its table inexact (the host retries with a
    larger cap) — and the chunks' scan trips, summed.
    """
    z = u.shape[0]
    zc = _chunk_rows(z, zone_chunk)
    nchunk = _n_chunks(z, zc)
    limbs = encoding.n_limbs(l_max)
    reshape = lambda x: x.reshape(nchunk, zc, *x.shape[1:])
    xs = (reshape(u), reshape(v), reshape(t), reshape(valid),
          signs.reshape(nchunk, zc))

    def body(carry, chunk):
        members, trips = carry
        cu, cv, ct, cvalid, csigns = chunk
        with jax.named_scope("zone_scan"):
            res = _sweep(scan, cu, cv, ct, cvalid, params=params,
                         delta=delta, l_max=l_max)
        with jax.named_scope("fold"):
            views = _member_views(res.code, res.length, res.ts,
                                  params=params, delta=delta, l_max=l_max)
            members = tuple(
                _merge_member(m, aggregation.aggregate_zones(c, n, csigns),
                              cap)
                for m, (c, n), cap in zip(members, views, merge_caps))
        return (members, trips + _trips(res, u.shape[1])), None

    init = (_empty_members(merge_caps, limbs), jnp.int32(0))
    (members, trips), _ = jax.lax.scan(body, init, xs)
    return members, trips


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "blk", "fold_chunk",
                     "params", "merge_caps"),
)
def _mine_fused_jit(u, v, t, valid, zone_id, sign, lo, hi, *, delta, l_max,
                    scan, blk, fold_chunk, params, merge_caps):
    """Jitted fused path: single-launch flat scan + on-device Phase-2 fold.

    One executable does the whole mine: the bucket-native kernel sweeps
    every zone of the concatenated layout in a single launch, and each
    member's candidate codes fold straight through ``count_codes`` +
    ``merge_bounded`` in ``fold_chunk``-row slices inside the same jit —
    only the bounded ``CodeCounts`` tables and their spill counters (one
    pair per member) leave the device.  The [S, L] code block never
    round-trips to host.  ``scan`` is a static arg, so the Pallas and XLA
    lowerings compile separately.
    """
    with jax.named_scope("zone_scan"):
        out = _sweep(scan, u, v, t, valid, zone_id, lo, hi, params=params,
                     delta=delta, l_max=l_max, blk=blk)
    code, length = out[:2]
    s, limbs = code.shape
    nchunk = s // fold_chunk
    chunks = lambda x: x.reshape(nchunk, fold_chunk, *x.shape[1:])
    xs = (chunks(code), chunks(length),
          chunks(out[2]) if len(out) > 2 else None, chunks(sign))

    def body(members, chunk):
        c_code, c_len, c_ts, c_sign = chunk
        views = _member_views(c_code, c_len, c_ts, params=params,
                              delta=delta, l_max=l_max)
        new = []
        for m, (code_i, len_i), cap in zip(members, views, merge_caps):
            w = (len_i > 0).astype(jnp.int32) * c_sign
            part = aggregation.count_codes(
                jnp.where(w[:, None] != 0, code_i, 0), w)
            new.append(_merge_member(m, part, cap))
        return tuple(new), None

    with jax.named_scope("fold"):
        members, _ = jax.lax.scan(body, _empty_members(merge_caps, limbs), xs)
    return members


@functools.partial(
    jax.jit, static_argnames=("delta", "l_max", "params", "merge_caps"),
    donate_argnums=(0,)
)
def _merge_chunk_jit(members, codes, lengths, ts, signs, *, delta, l_max,
                     params, merge_caps):
    """Bounded merge of one host-scanned chunk into every member's carry
    (host-only backends); the carries are donated."""
    with jax.named_scope("fold"):
        views = _member_views(codes, lengths, ts, params=params, delta=delta,
                              l_max=l_max)
        return tuple(
            _merge_member(m, aggregation.aggregate_zones(c, n, signs), cap)
            for m, (c, n), cap in zip(members, views, merge_caps))


class MiningExecutor:
    """Chunked scan+aggregate engine over padded zone batches.

    Args:
      delta, l_max: paper parameters (Definitions 2-5).
      backend: registry name ("ref", "pallas", "numpy", or plugin).
      zone_chunk: process zones in chunks of this many to bound peak memory
        (None/0 = whole batch at once); defaults to the backend's hint.
      pad_policy: "pad" appends inert zero-sign zone rows when the zone
        count does not divide ``zone_chunk``; "raise" errors instead.
      agg: Phase-2 aggregation mode — "auto", "legacy" or "hierarchical"
        (see module docstring).
      merge_cap: bounded-merge carry width for the hierarchical fold
        (None = backend hint, else one chunk's candidate rows).  Spills
        are detected exactly and retried with a doubled cap.
      memory_budget_mb: derive ``zone_chunk``/``merge_cap`` from this
        device-memory budget via :mod:`repro.core.planner` whenever
        ``zone_chunk`` was not given explicitly.
      fused: single-launch dispatch policy for :meth:`run_layout` —
        "auto" (default) fuses whenever the resolved fused backend
        publishes a bucket-native flat kernel, "on" requires one, "off"
        keeps the per-bucket path.  A per-call ``run_layout(fused=...)``
        override beats the policy.
      fused_backend: which backend's flat kernel serves fused runs —
        "auto" (default) keeps this executor's backend except on hosts
        where the Pallas kernel would run in *interpret* mode (CPU), where
        the compiled ``xla`` lowering takes over; an explicit registry
        name pins the lowering (e.g. ``"pallas"`` for the differential
        oracle, ``"xla"`` to force the compiled path from any backend).
      fused_bounds: sweep-bound planning for the fused flat stream —
        "live" (default) tightens each candidate block's ``[lo, hi)``
        window to the Lemma-4.1 horizon cut (see
        :func:`repro.core.tzp.concat_layout`), "full" sweeps to each
        block's zone end.  Output-identical; "live" is strictly less
        dispatched work.

    :meth:`run_layout`/:meth:`run_fused` return a :class:`RunOutcome`
    whose ``stats`` describes the dispatch that produced the counts:
    ``path`` ("fused" — suffixed ``fused_<name>`` when the fused kernel
    came from a different backend than the executor's, e.g. "fused_xla" —
    "per-bucket", and their ``-multi`` co-mine variants), ``launches``
    (scan dispatches in the final successful attempt — 1 for fused, one
    per bucket otherwise) and ``spill_retries`` (merge-cap doublings,
    each re-running the launch).  The old ``last_run_stats`` attribute —
    shared mutable state that misattributed under concurrent runs — is
    removed; stats travel only on the returned outcome.
    """

    def __init__(
        self,
        *,
        delta: int,
        l_max: int,
        backend: str = "ref",
        zone_chunk: int | None = None,
        pad_policy: str = "pad",
        agg: str = "auto",
        merge_cap: int | None = None,
        memory_budget_mb: float | None = None,
        fused: str = "auto",
        fused_backend: str = "auto",
        fused_bounds: str = "live",
        obs=None,
    ):
        if pad_policy not in ("pad", "raise"):
            raise ValueError(f"unknown pad_policy {pad_policy!r}")
        if agg not in AGG_MODES:
            raise ValueError(f"unknown agg mode {agg!r}; one of {AGG_MODES}")
        if fused not in FUSED_MODES:
            raise ValueError(
                f"unknown fused mode {fused!r}; one of {FUSED_MODES}")
        if fused_bounds not in FUSED_BOUNDS:
            raise ValueError(
                f"unknown fused bounds {fused_bounds!r}; one of "
                f"{FUSED_BOUNDS}")
        if fused_backend != "auto" and \
                not backends.get_backend(fused_backend).supports_fused:
            raise ValueError(
                f"fused_backend {fused_backend!r} has no fused "
                f"single-launch scan; pick one that publishes a flat "
                f"kernel (or leave it 'auto')")
        self.delta = int(delta)
        self.l_max = int(l_max)
        self.spec = backends.get_backend(backend)
        # an explicit zone_chunk=0 means "unchunked, full batch" (the
        # sequential baseline's contract) and must beat a budget-derived
        # chunk, exactly like any other explicit value; only None falls
        # through to the backend hint / capacity planner
        self._zone_chunk_explicit = zone_chunk is not None
        if zone_chunk is None:
            zone_chunk = self.spec.default_zone_chunk
        self.zone_chunk = int(zone_chunk or 0)
        self.pad_policy = pad_policy
        self.agg = agg
        self.merge_cap = int(merge_cap) if merge_cap else None
        self.memory_budget_mb = memory_budget_mb
        self.fused = fused
        self.fused_backend = fused_backend
        self.fused_bounds = fused_bounds
        self.fused_blk = backends.FUSED_BLK_DEFAULT
        self._plan_cache: dict[tuple, object] = {}
        # spill-adapted fused merge caps, keyed by fold_chunk: once a
        # fused run spills and retries at a larger cap, later runs with
        # the same fold geometry start from that cap directly instead of
        # re-paying the spilled launch (and its recompile) every call.
        # Only consulted when no explicit merge_cap pins the table size;
        # like _plan_cache, a racy lost update under concurrent use is
        # benign (one extra adaptive retry, never a wrong count).
        self._fused_cap_adapt: dict[int, int] = {}
        # observability bundle: NULL_OBS by default (shared no-op
        # singletons), so the hot paths below emit unconditionally
        self.obs = get_obs(obs)

    @classmethod
    def from_config(cls, config, *, obs=None) -> "MiningExecutor":
        """Build an executor from a :class:`repro.core.config.MiningConfig`.

        Duck-typed (any object with the execution fields works) so this
        module never imports ``config`` — the config layer imports the
        executor for ``AGG_MODES``, not the other way around.
        """
        return cls(
            delta=config.delta, l_max=config.l_max, backend=config.backend,
            zone_chunk=config.zone_chunk, agg=config.agg,
            merge_cap=config.merge_cap,
            memory_budget_mb=config.memory_budget_mb,
            fused=getattr(config, "fused", "auto"),
            fused_backend=getattr(config, "fused_backend", "auto"),
            obs=obs,
        )

    @property
    def backend(self) -> str:
        return self.spec.name

    @property
    def _params(self) -> tuple:
        """This executor's own config as a one-member lattice."""
        return ((self.delta, self.l_max),)

    @property
    def last_run_stats(self) -> dict:
        """REMOVED — stats travel on each run's returned outcome."""
        raise RuntimeError(
            "MiningExecutor.last_run_stats was removed after its "
            "deprecation cycle: it was shared mutable state that "
            "misattributed stats under concurrent runs.  Use the stats "
            "field of the RunOutcome/MultiRunOutcome returned by "
            "run_layout()/run_fused() (or PTMTEngine, whose "
            "DiscoveryResult.layout carries the execution summary).")

    def execution_key(self, z: int, e: int) -> tuple:
        """The compile-cache key a ``[z, e]`` zone batch resolves to.

        Mirrors ``run_arrays``'s resolution order exactly: chunk size from
        the raw shape, zone padding, then agg mode and merge cap from the
        padded shape.  Two batches with equal keys reuse one jitted
        executable (the jit caches are keyed on the same statics plus these
        shapes), so :class:`repro.core.engine.PTMTEngine` counts warm calls
        by tracking keys it has seen.  A merge-cap spill retry recompiles at
        a doubled cap without changing the key — rare, and the retry warns.
        """
        zc = self._zone_chunk_for(z, e)
        z = _padded_zones(z, zc)
        mode = self._agg_mode_for(zc, z)
        merge_cap = (self._merge_cap_for(zc, z, e)
                     if mode != "legacy" else 0)
        return (self.backend, self.delta, self.l_max, z, e, zc, mode,
                merge_cap)

    # -- capacity resolution ------------------------------------------------

    def capacity_plan(self, n_zones: int, e_cap: int):
        """Budget-derived :class:`~repro.core.planner.CapacityPlan`, or
        None when no ``memory_budget_mb`` was configured.

        Memoized per ``(n_zones, e_cap)`` — the chunk resolution consults
        it on every run (and the engine's ``execution_key`` again), so
        repeated same-shaped runs must not re-derive the plan.
        """
        if self.memory_budget_mb is None:
            return None
        key = (n_zones, e_cap)
        plan = self._plan_cache.get(key)
        if plan is None:
            plan = planner.plan_capacity(
                n_zones=n_zones, e_cap=e_cap, l_max=self.l_max,
                memory_budget_mb=self.memory_budget_mb,
                mem_model=self.spec.mem_model, merge_cap=self.merge_cap,
            )
            self._plan_cache[key] = plan
        return plan

    def _zone_chunk_for(self, z: int, e: int) -> int:
        if self.zone_chunk:
            return self.zone_chunk
        if self._zone_chunk_explicit:
            return 0           # explicitly unchunked: never consult a budget
        plan = self.capacity_plan(z, e)
        if plan is None:
            return 0
        return plan.zone_chunk if plan.zone_chunk < z else 0

    def _merge_cap_for(self, zc: int, z: int, e: int) -> int:
        if self.merge_cap:
            return self.merge_cap
        if self.spec.default_merge_cap:
            return self.spec.default_merge_cap
        return planner.default_merge_cap(zc or z, e)

    def _agg_mode_for(self, zc: int, z: int) -> str:
        if self.agg != "auto":
            return self.agg
        return "hierarchical" if zc and zc < z else "legacy"

    # -- traceable cores (used inside shard_map by distributed mining) ------

    def _require_jittable(self):
        if not self.spec.jittable:
            raise ValueError(
                f"backend {self.backend!r} is host-only (jittable=False) "
                f"and cannot run inside a traced/sharded computation"
            )

    def scan_aggregate(self, u, v, t, valid, signs) -> CodeCounts:
        """Scan + whole-batch signed-aggregate a [Z, E] batch; traceable.

        Always the legacy (lossless-by-construction) aggregation: inside a
        trace there is no host to run the merge-cap spill/retry policy, so
        callers that want the hierarchical fold must use
        :meth:`scan_aggregate_partial` and surface the spill count
        themselves.  Raises :class:`ZoneChunkError` at trace time when the
        (static) zone count does not divide ``zone_chunk``.
        """
        self._require_jittable()
        counts, _ = _mine_jit(
            u, v, t, valid, signs, delta=self.delta, l_max=self.l_max,
            scan=self.spec.scan, zone_chunk=self.zone_chunk,
            params=self._params)
        return counts

    def scan_aggregate_partial(self, u, v, t, valid, signs):
        """Traceable scan+aggregate honoring the executor's ``agg`` mode.

        Returns ``(CodeCounts, spilled)``.  ``spilled`` is a traced int32:
        0 whenever the result is exact; positive means the hierarchical
        carry overflowed ``merge_cap`` and the caller (e.g. the mesh mining
        step) must surface it — typically via a ``psum`` — so the host can
        re-run with a larger cap instead of silently undercounting.
        """
        self._require_jittable()
        z, e = u.shape
        zc = self._zone_chunk_for(z, e)
        if self._agg_mode_for(zc, z) == "legacy":
            return self.scan_aggregate(u, v, t, valid, signs), jnp.int32(0)
        (member,), _ = _mine_jit_hier(
            u, v, t, valid, signs, scan=self.spec.scan,
            delta=self.delta, l_max=self.l_max, zone_chunk=zc,
            params=self._params,
            merge_caps=(self._merge_cap_for(zc, z, e),),
        )
        return member

    # -- host-level entry points -------------------------------------------

    @staticmethod
    def check_batch_overflow(batch: ZoneBatch, *,
                             allow_overflow: bool = False) -> None:
        """Enforce the overflow policy on a host-built batch.

        Raises :class:`ZoneOverflowError` when the batch dropped edges
        (``batch.overflow > 0``) — such counts undercount and must not
        masquerade as exact.  ``allow_overflow=True`` downgrades the error
        to a warning for callers that knowingly mine a truncated batch.
        The single copy of the policy: ``run`` and the mesh path
        (``api.discover`` before ``mine_on_mesh``) both call it.
        """
        if not batch.overflow:
            return
        where = f" (bucket {batch.label!r})" if batch.label else ""
        msg = (f"zone batch{where} dropped {batch.overflow} edge(s) that "
               f"exceeded e_cap={batch.e_cap}; counts would silently "
               f"undercount (raise e_cap, or shrink zones by planning "
               f"with e_cap / a memory budget)")
        if not allow_overflow:
            raise ZoneOverflowError(msg)
        warnings.warn(msg + " — continuing because allow_overflow=True",
                      RuntimeWarning, stacklevel=3)

    @staticmethod
    def check_layout_overflow(layout: ZoneBatchLayout, *,
                              allow_overflow: bool = False) -> None:
        """One overflow policy across every bucket of a layout.

        Aggregates the per-bucket tallies into a single
        :class:`ZoneOverflowError` (or warning) that names each offending
        bucket, so a truncated burst is attributable to its capacity class
        instead of an anonymous global count.
        """
        bad = [b for b in layout.buckets if b.overflow]
        if not bad:
            return
        detail = ", ".join(
            f"{b.label or 'dense'}: {b.overflow} edge(s) beyond "
            f"e_cap={b.e_cap}" for b in bad)
        msg = (f"zone layout dropped {layout.overflow} edge(s) across "
               f"{len(bad)} bucket(s) [{detail}]; counts would silently "
               f"undercount (raise e_cap, or shrink zones by planning "
               f"with e_cap / a memory budget)")
        if not allow_overflow:
            raise ZoneOverflowError(msg)
        warnings.warn(msg + " — continuing because allow_overflow=True",
                      RuntimeWarning, stacklevel=3)

    def run(self, batch: ZoneBatch, *, allow_overflow: bool = False
            ) -> CodeCounts:
        """Mine a host-built :class:`ZoneBatch` to signed code counts.

        Applies :meth:`check_batch_overflow` first — overflowed batches
        raise unless ``allow_overflow=True``.
        """
        self.check_batch_overflow(batch, allow_overflow=allow_overflow)
        return self.run_arrays(batch.u, batch.v, batch.t, batch.valid,
                               batch.sign, label=batch.label)

    def _fused_spec(self) -> backends.BackendSpec:
        """The backend whose flat kernel serves this executor's fused runs.

        An explicit ``fused_backend`` pins it (validated at construction).
        ``"auto"`` keeps this executor's own backend, except when that
        backend is an accelerator kernel (Pallas) that would execute in
        *interpret* mode on this host (CPU) — there the compiled ``xla``
        lowering is strictly faster at identical output, so it takes over.
        Pallas stays the lowering on real accelerators and the
        differential oracle everywhere (pin ``fused_backend="pallas"``).
        """
        if self.fused_backend != "auto":
            return backends.get_backend(self.fused_backend)
        spec = self.spec
        if spec.supports_fused and spec.grade == "accelerator":
            from repro.kernels.common import resolve_interpret

            if resolve_interpret(None, quiet=True):
                try:
                    xla = backends.get_backend("xla")
                except ValueError:
                    return spec
                if xla.supports_fused:
                    return xla
        return spec

    def _fused_path(self, suffix: str = "") -> str:
        """Stats ``path`` label: "fused" when the executor's own backend
        ran the kernel, "fused_<name>" when dispatch rerouted it."""
        fspec = self._fused_spec()
        base = "fused" if fspec.name == self.backend else \
            f"fused_{fspec.name}"
        return base + suffix

    def resolve_fused(self, fused: bool | None = None) -> bool:
        """Resolve the fused-dispatch decision for a layout run.

        A per-call boolean beats the constructor policy; ``True`` (or
        policy "on") when no fused kernel resolves raises rather than
        silently falling back — the caller asked for one launch and would
        otherwise benchmark the wrong path.  The decision consults the
        *resolved* fused backend (:meth:`_fused_spec`), so e.g.
        ``backend="ref", fused_backend="xla"`` takes the fused path even
        though the reference backend has no flat kernel of its own.
        """
        if fused is None:
            if self.fused == "off":
                return False
            if self.fused == "auto":
                return self._fused_spec().supports_fused
            fused = True
        if fused and not self._fused_spec().supports_fused:
            raise ValueError(
                f"backend {self.backend!r} has no fused single-launch "
                f"scan; use fused=False (or fused='off') for the "
                f"per-bucket path, or pick a fused_backend that has one")
        return bool(fused)

    def run_layout(self, layout: ZoneBatchLayout, *,
                   allow_overflow: bool = False,
                   fused: bool | None = None) -> RunOutcome:
        """Mine a :class:`ZoneBatchLayout` (dense or bucketed) exactly.

        Dispatch is decided by :meth:`resolve_fused`: the fused path
        (:meth:`run_fused`) mines the whole layout in a single
        bucket-native kernel launch with the Phase-2 fold on-device; the
        per-bucket path runs each bucket through :meth:`run_arrays` with
        its own shape — and hence its own budget-derived
        ``zone_chunk``/``merge_cap`` from :meth:`capacity_plan`, keyed on
        the bucket's geometry rather than the global max — then folds the
        per-bucket partial count tables through the signed bounded-carry
        merge (:func:`merge_partial_counts`).  Lemma 4.2's signed sum is
        associative over zones, so either split is exact; the differential
        tests assert fused == per-bucket == dense code-for-code.

        Returns a :class:`RunOutcome` — the counts plus this run's own
        dispatch stats (never read stats back off the executor; that is
        the shared-state race the outcome type exists to close).  The
        executor's own config runs as the one-member lattice of
        :meth:`run_layout_multi`.
        """
        (counts,), stats = self._mine_layout(
            layout, self._params, multi=False,
            allow_overflow=allow_overflow, fused=fused)
        return RunOutcome(counts=counts, stats=stats)

    def _check_comine_params(self, params) -> tuple:
        params = tuple((int(d), int(l)) for d, l in params)
        if not params:
            raise ValueError("co-mine needs at least one (delta, l_max)")
        if not self.spec.supports_comine:
            raise ValueError(
                f"backend {self.backend!r} does not support co-mining "
                f"(its scan has no with_ts timestamp output)")
        for d, l in params:
            if not (1 <= d <= self.delta and 1 <= l <= self.l_max):
                raise ValueError(
                    f"co-mined config (delta={d}, l_max={l}) is not "
                    f"dominated by the sweep config (delta={self.delta}, "
                    f"l_max={self.l_max})")
        return params

    def run_layout_multi(self, layout: ZoneBatchLayout, params, *,
                         allow_overflow: bool = False,
                         fused: bool | None = None) -> MultiRunOutcome:
        """Co-mine N member configs from ONE dominating Phase-1 sweep.

        ``params`` is a sequence of ``(delta_i, l_max_i)`` pairs, each
        dominated by this executor's ``(delta, l_max)`` (the planner's
        :func:`~repro.core.planner.build_config_lattices` guarantees that
        for lattice members).  The layout is swept exactly once per launch
        at the dominating config with per-step absorption timestamps; each
        member's count table is split out during the Phase-2 fold by
        prefix-truncating candidates on those timestamps — byte-identical
        to mining that member independently, at one sweep's cost.

        Returns a :class:`MultiRunOutcome` with one exact
        :class:`CodeCounts` per param (spills retry per member with a
        larger cap, exactly like the single-config paths).
        """
        params = self._check_comine_params(params)
        return MultiRunOutcome(*self._mine_layout(
            layout, params, multi=True, allow_overflow=allow_overflow,
            fused=fused))

    def _mine_layout(self, layout: ZoneBatchLayout, params, *, multi: bool,
                     allow_overflow: bool, fused: bool | None):
        """The one layout route: ``(counts tuple, stats)`` for ``params``.

        ``multi`` marks a co-mine call, whose ``path`` labels carry a
        ``-multi`` suffix and whose stats carry ``n_configs``.
        """
        if self.resolve_fused(fused):
            return self._run_fused(layout, params, multi=multi,
                                   allow_overflow=allow_overflow)
        self.check_layout_overflow(layout, allow_overflow=allow_overflow)
        suffix = "-multi" if multi else ""
        path = "per-bucket" + suffix
        configs = {"n_configs": len(params)} if multi else {}
        with self.obs.tracer.span("mine.layout", path=path,
                                  buckets=layout.n_buckets, **configs):
            runs = [
                self._run_bucket(b.u, b.v, b.t, b.valid, b.sign, params,
                                 suffix=suffix, label=b.label)
                for b in layout.buckets
            ]
            merges = [
                merge_partial_counts(list(p), merge_cap=self.merge_cap,
                                     warn_label="zone-layout bucket",
                                     obs=self.obs)
                for p in zip(*(r.counts for r in runs))]
            stats = {
                "path": path,
                "launches": len(layout.buckets),
                "spill_retries": sum(r.retries for r in runs),
                "sweep_slots": _sweep_slots(runs),
                "merge_rows": sum(m.rows for m in merges),
                **configs,
            }
            metrics = self.obs.metrics
            metrics.counter("repro_mining_launches_total",
                            path=path).inc(len(layout.buckets))
            metrics.counter("repro_mining_sweep_slots_total",
                            path=path).inc(stats["sweep_slots"])
            metrics.counter("repro_mining_merge_rows_total",
                            path=path).inc(stats["merge_rows"])
            return tuple(m.counts for m in merges), stats

    # -- fused single-launch path -------------------------------------------

    def _fused_geometry(self, layout: ZoneBatchLayout) -> tuple[int, int, int]:
        """``(blk, fold_chunk, n_slots_padded)`` for a layout's fused run.

        Derivable from bucket shapes alone (no arrays built), so
        :meth:`fused_execution_key` can report the compile-cache geometry
        without paying the concatenation.  Must agree with
        :func:`repro.core.tzp.concat_layout`'s padding rule.
        """
        blk = self.fused_blk
        real_slots = sum(b.n_real_zones * b.e_cap for b in layout.buckets)
        if self.memory_budget_mb is not None:
            key = ("fused", real_slots)
            plan = self._plan_cache.get(key)
            if plan is None:
                plan = planner.plan_fused_capacity(
                    n_slots=real_slots, l_max=self.l_max,
                    memory_budget_mb=self.memory_budget_mb, blk=blk,
                    merge_cap=self.merge_cap,
                )
                self._plan_cache[key] = plan
            fold_chunk = plan.fold_chunk
        else:
            fold_chunk = planner.default_fold_chunk(real_slots, blk=blk)
        mult = fold_chunk
        s_pad = max(-(-max(real_slots, 1) // mult) * mult, mult)
        return blk, fold_chunk, s_pad

    def _fused_merge_cap(self, fold_chunk: int) -> int:
        if self.merge_cap:
            return self.merge_cap
        base = self.spec.default_merge_cap or max(1024, fold_chunk)
        return max(base, self._fused_cap_adapt.get(fold_chunk, 0))

    def _note_fused_cap(self, fold_chunk: int, cap: int,
                        retries: int) -> None:
        """Remember a spill-adapted cap so the NEXT run starts there."""
        if retries and not self.merge_cap:
            prev = self._fused_cap_adapt.get(fold_chunk, 0)
            self._fused_cap_adapt[fold_chunk] = max(prev, cap)

    def fused_execution_key(self, layout: ZoneBatchLayout) -> tuple:
        """The compile-cache key a fused layout run resolves to.

        The fused analog of :meth:`execution_key`: the jitted executable
        is keyed on the flat stream geometry (padded slot count + block
        size), the fold shape, the resolved fused backend (Pallas and XLA
        lowerings compile separately — ``scan`` is a jit static), and the
        sweep-bounds mode (full and live plans ship different descriptor
        contents under the same shapes).
        """
        blk, fold_chunk, s_pad = self._fused_geometry(layout)
        merge_cap = min(self._fused_merge_cap(fold_chunk), s_pad + 1)
        return ("fused", self.backend, self._fused_spec().name,
                self.fused_bounds, self.delta, self.l_max, s_pad, blk,
                fold_chunk, merge_cap)

    def run_fused(self, layout: ZoneBatchLayout, *,
                  allow_overflow: bool = False) -> RunOutcome:
        """Mine a layout in ONE bucket-native kernel launch, fold on-device.

        The layout is flattened to a :class:`~repro.core.tzp.
        FusedZoneLayout` slot stream (real zone rows only, padded to the
        fold chunk) and handed to the backend's flat kernel inside
        ``_mine_fused_jit`` — a single ``pallas_call`` whose grid spans
        every bucket, with the ``count_codes``/``merge_bounded`` fold in
        the same executable.  Only the bounded count table and the spill
        counter come back; a spill retries host-side with a larger cap
        (ceiling ``n_slots + 1``, which provably cannot spill).
        """
        (counts,), stats = self._run_fused(
            layout, self._params, multi=False, allow_overflow=allow_overflow)
        return RunOutcome(counts=counts, stats=stats)

    def run_fused_multi(self, layout: ZoneBatchLayout, params, *,
                        allow_overflow: bool = False) -> MultiRunOutcome:
        """Co-mine a layout in ONE kernel launch with N on-device folds."""
        params = self._check_comine_params(params)
        return MultiRunOutcome(*self._run_fused(
            layout, params, multi=True, allow_overflow=allow_overflow))

    def _run_fused(self, layout: ZoneBatchLayout, params, *, multi: bool,
                   allow_overflow: bool):
        """The one fused route: ``(counts tuple, stats)`` for ``params``."""
        self.check_layout_overflow(layout, allow_overflow=allow_overflow)
        obs = self.obs
        fspec = self._fused_spec()
        suffix = "-multi" if multi else ""
        path = self._fused_path(suffix)
        blk, fold_chunk, _ = self._fused_geometry(layout)
        fl = concat_layout(layout, blk=blk, pad_slots_to=fold_chunk,
                           delta=self.delta, l_max=self.l_max,
                           bounds=self.fused_bounds)
        cap_ceiling = fl.n_slots + 1
        caps = (min(self._fused_merge_cap(fold_chunk), cap_ceiling),) \
            * len(params)
        with obs.tracer.span("mine.h2d", n_slots=fl.n_slots) as sp:
            arrays = tuple(jnp.asarray(x) for x in (
                fl.u, fl.v, fl.t, fl.valid, fl.zone_id, fl.sign, fl.lo,
                fl.hi))
            sp.sync(arrays)
        retries = 0
        while True:
            # one span per launch attempt: a spill retry at a larger
            # merge_cap recompiles, and its jax.compile event lands here
            with obs.tracer.span("mine.fused", n_slots=fl.n_slots,
                                 merge_cap=max(caps), retry=retries) as sp:
                out = _mine_fused_jit(
                    *arrays, delta=self.delta, l_max=self.l_max,
                    scan=fspec.fused_scan, blk=blk, fold_chunk=fold_chunk,
                    params=params, merge_caps=caps,
                )
                sp.sync(out)
            with obs.tracer.span("mine.d2h"):
                spills = [int(s) for _, s in out]
            if not any(spills):
                break
            caps = _grow_spilled(caps, spills, cap_ceiling, obs=obs,
                                 what="fused on-device", path="fused" + suffix)
            retries += 1
        self._note_fused_cap(fold_chunk, max(caps), retries)
        stats = {
            "path": path,
            "backend": fspec.name,
            "bounds": fl.bounds,
            "launches": 1,
            "spill_retries": retries,
            **({"merge_caps": caps, "n_configs": len(params)} if multi
               else {"merge_cap": caps[0]}),
            "fold_chunk": fold_chunk,
            "n_slots": fl.n_slots,
            "sweep_slots": fl.sweep_slots,
        }
        obs.metrics.counter("repro_mining_launches_total", path=path).inc()
        obs.metrics.counter("repro_mining_sweep_slots_total",
                            path=path).inc(fl.sweep_slots)
        m = obs.metrics
        m.gauge("repro_mining_fused_merge_cap").set(max(caps))
        m.gauge("repro_mining_fused_fold_chunk").set(fold_chunk)
        m.gauge("repro_mining_fused_slots").set(fl.n_slots)
        m.gauge("repro_mining_fused_sweep_slots").set(fl.sweep_slots)
        return tuple(c for c, _ in out), stats

    def layout_execution_keys(self, layout: ZoneBatchLayout,
                              fused: bool | None = None) -> tuple:
        """Execution keys a layout run will resolve to.

        Per-bucket :meth:`execution_key` tuples on the per-bucket path —
        bucket shapes, not whole-layout shapes, key the jit caches, so a
        recurring bucket geometry reuses its compiled executable even when
        the surrounding layout differs.  On the fused path the whole
        layout resolves to one :meth:`fused_execution_key`.
        """
        if self.resolve_fused(fused):
            return (self.fused_execution_key(layout),)
        return tuple(self.execution_key(b.n_zones, b.e_cap)
                     for b in layout.buckets)

    def run_arrays(self, u, v, t, valid, signs, *,
                   label: str = "") -> CodeCounts:
        """Mine raw [Z, E] zone arrays (+ [Z] signs) to signed code counts."""
        return self._run_bucket(u, v, t, valid, signs, self._params,
                                label=label).counts[0]

    def _run_bucket(self, u, v, t, valid, signs, params, *,
                    suffix: str = "", label: str = "") -> _BucketRun:
        """One bucket launch for every member of ``params``.

        Pads the zone count to the chunk, then picks the fold: the
        whole-batch fold for an unchunked one-member lattice under "auto",
        else the bounded fold with its spill retry.

        Spans: ``mine.launch`` around the whole bucket; inside it, on the
        jitted paths, ``mine.bucket_h2d`` (the arrays put on the device)
        and ``mine.bucket_scan`` (the jitted scan and in-bucket fold up to
        its counts, one span per spill attempt).  Each is synced at its
        end only when the tracer is on.
        """
        u, v, t, valid, signs = (np.asarray(x)
                                 for x in (u, v, t, valid, signs))
        z, e = u.shape
        tracer = self.obs.tracer
        with tracer.span("mine.launch", z=z, e=e, label=label) as sp:
            zc = self._zone_chunk_for(z, e)
            if zc and zc < z and z % zc != 0:
                if self.pad_policy == "raise":
                    where = f" in bucket {label!r}" if label else ""
                    raise ZoneChunkError(
                        f"zone count {z}{where} is not divisible by "
                        f"zone_chunk {zc} (pad_policy='raise'); the "
                        f"trailing {z % zc} zone(s) would need inert "
                        f"padding rows — pad the batch (pad_policy='pad') "
                        f"or pick a divisor"
                    )
                u, v, t, valid, signs = pad_zone_arrays(
                    u, v, t, valid, signs, n_rows=_padded_zones(z, zc))
                z = u.shape[0]

            # a co-mine has no whole-batch fold: N tables each as wide as
            # the bucket
            mode = (self._agg_mode_for(zc, z) if len(params) == 1
                    else "hierarchical")
            sp.set(agg=mode, zone_chunk=zc)
            if self.spec.jittable:
                with tracer.span("mine.bucket_h2d") as h2d:
                    u, v, t, valid, signs = arrays = tuple(
                        jnp.asarray(x) for x in (u, v, t, valid, signs))
                    h2d.sync(arrays)
            if mode == "legacy":
                counts, trips = self._run_legacy(u, v, t, valid, signs, zc,
                                                 params)
                retries = 0
            else:
                counts, trips, retries = self._run_bounded(
                    u, v, t, valid, signs, zc, params, suffix)
            sp.sync(counts)
            return _BucketRun(counts, trips, _chunk_rows(z, zc), e, retries)

    def _run_legacy(self, u, v, t, valid, signs, zc, params):
        """Whole-batch fold; returns ``(counts tuple, trips)``."""
        if not self.spec.jittable:
            code, length, ts, trips = self._host_scan(u, v, t, valid, params)
            views = _member_views(code, length, ts, params=params,
                                  delta=self.delta, l_max=self.l_max)
            return tuple(
                aggregation.aggregate_zones(c, n, jnp.asarray(signs))
                for c, n in views), trips
        with self.obs.tracer.span("mine.bucket_scan") as sp:
            *counts, trips = _mine_jit(
                u, v, t, valid, signs, delta=self.delta, l_max=self.l_max,
                scan=self.spec.scan, zone_chunk=zc, params=params,
            )
            sp.sync(counts)
        return tuple(counts), trips

    def _run_bounded(self, u, v, t, valid, signs, zc, params, suffix):
        """Hierarchical fold with the per-member merge-cap spill policy;
        returns ``(counts tuple, trips, retries)``.

        Spills are exact signals, so retrying with a larger cap is
        lossless; only the members that spilled grow their caps.
        ``merge_cap >= z*e + 1`` can never spill (at most z*e distinct
        live codes, plus one row for the all-zero padding group that sorts
        ahead of them), so the loop terminates.
        """
        z, e = u.shape
        cap_ceiling = z * e + 1
        caps = (min(self._merge_cap_for(zc, z, e), cap_ceiling),) \
            * len(params)
        retries = 0
        while True:
            if not self.spec.jittable:
                out, trips = self._fold_host_scan(u, v, t, valid, signs, zc,
                                                  params, caps)
            else:
                # one span per attempt: a spill retry runs the scan again
                with self.obs.tracer.span("mine.bucket_scan",
                                          merge_cap=max(caps)) as sp:
                    out, trips = _mine_jit_hier(
                        u, v, t, valid, signs, delta=self.delta,
                        l_max=self.l_max, scan=self.spec.scan,
                        zone_chunk=zc, params=params, merge_caps=caps,
                    )
                    sp.sync(out)
            spills = [int(s) for _, s in out]
            if not any(spills):
                return tuple(c for c, _ in out), trips, retries
            caps = _grow_spilled(caps, spills, cap_ceiling, obs=self.obs,
                                 what="hierarchical", path="bucket" + suffix)
            retries += 1

    def _host_scan(self, u, v, t, valid, params):
        """A host-only backend's scan of one chunk, its outputs put on the
        device; returns ``(code, length, ts, trips)``."""
        res = _sweep(self.spec.scan, u, v, t, valid, params=params,
                     delta=self.delta, l_max=self.l_max)
        ts = None if res.ts is None else jnp.asarray(res.ts)
        return (jnp.asarray(res.code), jnp.asarray(res.length), ts,
                int(_trips(res, u.shape[1])))

    def _fold_host_scan(self, u, v, t, valid, signs, zc, params, caps):
        """Chunked fold for host-only backends (scan outside jit); returns
        ``(members, trips)``.

        Even the NumPy oracle gets the hierarchical memory bound: only one
        chunk's [zc, E, L] code block exists at a time, merged through the
        same bounded carries as the device paths.
        """
        z, e = u.shape
        zc = _chunk_rows(z, zc)
        members = _empty_members(caps, encoding.n_limbs(self.l_max))
        trips = 0
        for i in range(_n_chunks(z, zc)):
            sl = slice(i * zc, (i + 1) * zc)
            code, length, ts, n = self._host_scan(u[sl], v[sl], t[sl],
                                                  valid[sl], params)
            trips += n
            members = _merge_chunk_jit(
                members, code, length, ts, jnp.asarray(signs[sl]),
                delta=self.delta, l_max=self.l_max, params=params,
                merge_caps=caps,
            )
        return members, trips
