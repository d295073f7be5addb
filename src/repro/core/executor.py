"""Unified mining executor — ONE chunked scan+aggregate engine.

Every discovery entry point (batch ``discover``, the sequential baseline,
``distributed.mining.mine_on_mesh`` and the streaming miner) routes through
:class:`MiningExecutor` instead of carrying its own copy of the zone sweep:

* backend dispatch goes through :mod:`repro.core.backends` (capability-aware,
  pluggable);
* zone chunking (chunks of ``zone_chunk`` zones to bound peak memory) is
  implemented once, with an explicit policy for zone counts that do not
  divide ``zone_chunk`` — **pad** (default: append inert zero-sign rows) or
  **raise** — never the silent remainder drop the pre-refactor
  ``_mine_batch`` had;
* Phase-2 aggregation has three modes (``agg``):

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
                        exactly and retried host-side with a doubled cap,
                        so results are always exact;
  - ``"pipelined"``   — same fold, driven by a host loop that double-buffers
                        chunk dispatch: the next zone-chunk's host->device
                        transfer is issued while the current chunk computes,
                        and the carry buffers are donated to the jitted step
                        so XLA reuses them in place.
  - ``"auto"`` (default) resolves to ``"hierarchical"`` when chunking is
    active and ``"legacy"`` otherwise (identical numerics either way —
    enforced by ``tests/test_differential.py``);

* ``zone_chunk`` itself no longer has to be a hardcoded hint: pass
  ``memory_budget_mb`` and the executor derives the chunk (and
  ``merge_cap``) from the backend's memory model via
  :mod:`repro.core.planner`;
* jit compilation is cached per ``(backend, delta, l_max, zone_chunk,
  merge_cap, batch shape)`` via module-level jitted functions shared by
  every executor instance;
* host-only backends (``jittable=False``, e.g. the NumPy oracle) run their
  scan outside the jit boundary and only the signed aggregation is jitted —
  including a chunked host loop so even the oracle honors the hierarchical
  memory bound.

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
                  pad_zone_arrays)

AGG_MODES = ("auto", "legacy", "hierarchical", "pipelined")


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

    ``trips`` (a device scalar, or an int from a host scan) sums the
    launch's per-chunk scan trips; every chunk has ``rows`` zone rows of
    ``e`` slots, so the candidate-steps are ``rows * e * trips``.
    """

    counts: object         # CodeCounts, or a tuple of them when co-mined
    trips: object
    rows: int
    e: int


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


@functools.partial(jax.jit, static_argnames=("cap",))
def _merge_part_jit(carry, part, *, cap):
    """One cross-bucket ``merge_bounded`` step, named ``merge`` in the
    program (a scope opened around an eager call would not reach it)."""
    with jax.named_scope("merge"):
        return aggregation.merge_bounded(carry, part, cap=cap)


def merge_partial_counts(
    parts,
    *,
    merge_cap: int | None = None,
    warn_label: str = "partial",
    obs=None,
) -> CodeCounts:
    """Fold per-bucket (or per-shard) count tables through ``merge_bounded``.

    The cross-bucket analog of the hierarchical chunk fold: partial tables
    stream through one bounded-width carry instead of a single unbounded
    concat-and-sort, so the resident merge state is O(cap) regardless of
    how many buckets a layout produced.  ``merge_cap`` seeds the carry
    width; a spill (more live unique codes than rows) is detected exactly
    and retried with a doubled cap, capped at the provably-sufficient
    ceiling (total live rows + 1 slot for the all-zero padding group), so
    the result is always exact.
    """
    obs = get_obs(obs)
    parts = list(parts)
    if not parts:
        raise ValueError("merge_partial_counts needs at least one table")
    if len(parts) == 1:
        return parts[0]
    limbs = int(parts[0].codes.shape[1])
    ceiling = sum(int(p.unique_mask.sum()) for p in parts) + 1
    cap = min(int(merge_cap), ceiling) if merge_cap else ceiling
    cap = max(cap, 8)
    with obs.tracer.span("mine.fold", parts=len(parts)) as sp:
        while True:
            carry = aggregation.empty_counts(cap, limbs)
            spilled = jnp.zeros((), jnp.int32)
            for part in parts:
                carry, spill = _merge_part_jit(carry, part, cap=cap)
                spilled = spilled + spill
            n_spilled = int(spilled)
            if n_spilled == 0:
                sp.set(merge_cap=cap).sync(carry)
                return carry
            need = max(2 * cap, cap + n_spilled, 8)
            new_cap = min(1 << (need - 1).bit_length(), ceiling)
            warnings.warn(
                f"{warn_label} merge spilled {n_spilled} unique code(s) at "
                f"merge_cap={cap}; retrying with merge_cap={new_cap}",
                RuntimeWarning, stacklevel=3,
            )
            obs.metrics.counter("repro_mining_spill_retries_total",
                                path="fold").inc()
            cap = new_cap


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


def _chunked_scan(scan, u, v, t, valid, *, delta, l_max, zone_chunk):
    """Sweep a [Z, E] zone batch, optionally in chunks of ``zone_chunk``.

    Traceable; shapes are static here, so divisibility is checked at trace
    time (the executor's host path pads beforehand under pad_policy='pad').
    Returns ``(codes, lengths, trips)``, ``trips`` summed over the chunks.
    """

    def chunk_fn(args):
        cu, cv, ct, cvalid = args
        with jax.named_scope("zone_scan"):
            res = scan(cu, cv, ct, cvalid, delta=delta, l_max=l_max)
        return res.code, res.length, jnp.int32(_trips(res, u.shape[1]))

    z = u.shape[0]
    if zone_chunk and zone_chunk < z:
        nchunk = _n_chunks(z, zone_chunk)
        reshape = lambda x: x.reshape(nchunk, zone_chunk, *x.shape[1:])
        codes, lengths, trips = jax.lax.map(
            chunk_fn, (reshape(u), reshape(v), reshape(t), reshape(valid))
        )
        codes = codes.reshape(z, *codes.shape[2:])
        lengths = lengths.reshape(z, *lengths.shape[2:])
        trips = trips.sum()
    else:
        codes, lengths, trips = chunk_fn((u, v, t, valid))
    return codes, lengths, trips


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


def _hier_fold(scan, u, v, t, valid, signs, *, delta, l_max, zone_chunk,
               merge_cap):
    """Hierarchical streaming aggregation (traceable).

    Each zone-chunk is scanned and immediately signed-counted
    (``aggregate_zones``); the partial tables fold through a bounded-width
    carry (``merge_bounded``) inside ``lax.scan``, so at no point do all
    Z*C candidate codes coexist.  Returns ``(CodeCounts[merge_cap],
    spilled, trips)`` — ``spilled > 0`` means ``merge_cap`` was too small
    and the result is inexact (the host retries with a doubled cap);
    ``trips`` is the chunks' scan trips, summed.
    """
    z = u.shape[0]
    zc = _chunk_rows(z, zone_chunk)
    nchunk = _n_chunks(z, zc)
    limbs = encoding.n_limbs(l_max)
    reshape = lambda x: x.reshape(nchunk, zc, *x.shape[1:])
    xs = (reshape(u), reshape(v), reshape(t), reshape(valid),
          signs.reshape(nchunk, zc))

    def body(carry, chunk):
        counts, spilled, trips = carry
        cu, cv, ct, cvalid, csigns = chunk
        with jax.named_scope("zone_scan"):
            res = scan(cu, cv, ct, cvalid, delta=delta, l_max=l_max)
        with jax.named_scope("fold"):
            part = aggregation.aggregate_zones(res.code, res.length, csigns)
            merged, spill = aggregation.merge_bounded(counts, part,
                                                      cap=merge_cap)
        return (merged, spilled + spill,
                trips + _trips(res, u.shape[1])), None

    init = (aggregation.empty_counts(merge_cap, limbs), jnp.int32(0),
            jnp.int32(0))
    (counts, spilled, trips), _ = jax.lax.scan(body, init, xs)
    return counts, spilled, trips


@functools.partial(
    jax.jit, static_argnames=("delta", "l_max", "scan", "zone_chunk")
)
def _mine_jit(u, v, t, valid, signs, *, delta, l_max, scan, zone_chunk):
    """Jitted legacy path: full zone sweep, then one whole-batch aggregation.

    jax.jit keys its cache on the static args plus input shapes, so every
    executor instance with the same (scan fn, delta, l_max, zone_chunk,
    batch shape) reuses one executable.  The cache is keyed on the resolved
    scan *callable*, not the backend name, so re-registering a backend
    (``overwrite=True``) cannot serve a stale executable.  Returns
    ``(counts, trips)``.
    """
    codes, lengths, trips = _chunked_scan(
        scan, u, v, t, valid, delta=delta, l_max=l_max, zone_chunk=zone_chunk
    )
    with jax.named_scope("fold"):
        return aggregation.aggregate_zones(codes, lengths, signs), trips


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "zone_chunk", "merge_cap"),
)
def _mine_jit_hier(u, v, t, valid, signs, *, delta, l_max, scan, zone_chunk,
                   merge_cap):
    """Jitted hierarchical fold (shared compile cache, as ``_mine_jit``)."""
    return _hier_fold(scan, u, v, t, valid, signs, delta=delta, l_max=l_max,
                      zone_chunk=zone_chunk, merge_cap=merge_cap)


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "merge_cap"),
    donate_argnums=(0, 1, 2),
)
def _pipeline_step(carry, spilled, trips, u, v, t, valid, signs, *, delta,
                   l_max, scan, merge_cap):
    """One pipelined chunk: scan + partial count + bounded merge.

    The carry (and the spill and trip counters) are donated — XLA reuses
    their buffers in place, so the resident aggregation state stays a
    single ``merge_cap`` table no matter how many chunks stream through.
    """
    with jax.named_scope("zone_scan"):
        res = scan(u, v, t, valid, delta=delta, l_max=l_max)
    with jax.named_scope("fold"):
        part = aggregation.aggregate_zones(res.code, res.length, signs)
        merged, spill = aggregation.merge_bounded(carry, part,
                                                  cap=merge_cap)
    return merged, spilled + spill, trips + _trips(res, u.shape[1])


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "blk", "fold_chunk",
                     "merge_cap"),
)
def _mine_fused_jit(u, v, t, valid, zone_id, sign, lo, hi, *, delta, l_max,
                    scan, blk, fold_chunk, merge_cap):
    """Jitted fused path: single-launch flat scan + on-device Phase-2 fold.

    One executable does the whole mine: the bucket-native kernel sweeps
    every zone of the concatenated layout in a single launch, and the
    candidate codes fold straight through ``count_codes`` +
    ``merge_bounded`` in ``fold_chunk``-row slices inside the same jit —
    only the bounded ``CodeCounts`` table and the spill counter leave the
    device.  The [S, L] code block never round-trips to host.  ``scan``
    is a static arg, so the Pallas and XLA lowerings compile separately.
    """
    with jax.named_scope("zone_scan"):
        code, length = scan(u, v, t, valid, zone_id, lo, hi,
                            delta=delta, l_max=l_max, blk=blk)
    s, limbs = code.shape
    nchunk = s // fold_chunk

    def body(carry, chunk):
        counts, spilled = carry
        chunk_codes, chunk_w = chunk
        part = aggregation.count_codes(chunk_codes, chunk_w)
        merged, spill = aggregation.merge_bounded(counts, part,
                                                  cap=merge_cap)
        return (merged, spilled + spill), None

    init = (aggregation.empty_counts(merge_cap, limbs), jnp.int32(0))
    with jax.named_scope("fold"):
        w = (length > 0).astype(jnp.int32) * sign
        codes = jnp.where(w[:, None] != 0, code, 0)
        xs = (codes.reshape(nchunk, fold_chunk, limbs),
              w.reshape(nchunk, fold_chunk))
        (counts, spilled), _ = jax.lax.scan(body, init, xs)
    return counts, spilled


@functools.partial(
    jax.jit, static_argnames=("merge_cap",), donate_argnums=(0, 1)
)
def _merge_chunk_jit(carry, spilled, codes, lengths, signs, *, merge_cap):
    """Bounded merge of one host-scanned chunk (host-only backends)."""
    with jax.named_scope("fold"):
        part = aggregation.aggregate_zones(codes, lengths, signs)
        merged, spill = aggregation.merge_bounded(carry, part,
                                                  cap=merge_cap)
    return merged, spilled + spill


# ---------------------------------------------------------------------------
# Config-lattice co-mining: derive every member config's Phase-2 tables from
# ONE dominating Phase-1 sweep (see planner.ConfigLattice).
# ---------------------------------------------------------------------------


def _derive_member(code, length, ts, *, d_i, l_i, delta, l_max):
    """A member config's (code, length) view of dominating sweep output.

    The dominating member is the sweep itself; every smaller ``(delta,
    l_max)`` is the timestamp-gap prefix truncation
    (:func:`repro.core.expansion.derive_lengths` +
    :func:`repro.core.encoding.truncate_codes`) — lossless because zone
    streams are time-sorted, so prefix processes of the dominating sweep
    are exactly what the smaller config would have mined.
    """
    if (d_i, l_i) == (delta, l_max):
        return code, length
    len_i = expansion.derive_lengths(length, ts, delta=d_i, l_max=l_i)
    return encoding.truncate_codes(code, len_i), len_i


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "zone_chunk", "params",
                     "merge_caps"),
)
def _mine_multi_jit(u, v, t, valid, signs, *, delta, l_max, scan, zone_chunk,
                    params, merge_caps):
    """Jitted multi-config hierarchical fold over a [Z, E] zone batch.

    ONE ``with_ts`` dominating scan per chunk; each member of ``params``
    (a tuple of ``(delta_i, l_max_i)``) folds its derived candidate view
    through its own bounded merge carry.  Returns a tuple of
    ``(CodeCounts, spilled)`` pairs aligned with ``params``, and the
    chunks' scan trips, summed.
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
            res = scan(cu, cv, ct, cvalid, delta=delta, l_max=l_max,
                       with_ts=True)
        new_carry = []
        with jax.named_scope("fold"):
            for (d_i, l_i), (counts, spilled), cap in zip(params, members,
                                                          merge_caps):
                code_i, len_i = _derive_member(
                    res.code, res.length, res.ts,
                    d_i=d_i, l_i=l_i, delta=delta, l_max=l_max)
                part = aggregation.aggregate_zones(code_i, len_i, csigns)
                merged, spill = aggregation.merge_bounded(counts, part,
                                                          cap=cap)
                new_carry.append((merged, spilled + spill))
        return (tuple(new_carry), trips + _trips(res, u.shape[1])), None

    init = tuple(
        (aggregation.empty_counts(cap, limbs), jnp.int32(0))
        for cap in merge_caps)
    out, _ = jax.lax.scan(body, (init, jnp.int32(0)), xs)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("delta", "l_max", "scan", "blk", "fold_chunk",
                     "params", "merge_caps"),
)
def _mine_fused_multi_jit(u, v, t, valid, zone_id, sign, lo, hi, *, delta,
                          l_max, scan, blk, fold_chunk, params, merge_caps):
    """Jitted fused co-mine: ONE flat kernel launch, N on-device folds.

    The single-launch analog of :func:`_mine_multi_jit`: the dominating
    sweep runs once over the concatenated layout (with per-step absorption
    timestamps), then every member config's derived candidate view streams
    through its own ``count_codes`` + ``merge_bounded`` fold inside the
    same executable.
    """
    with jax.named_scope("zone_scan"):
        code, length, ts = scan(u, v, t, valid, zone_id, lo, hi,
                                delta=delta, l_max=l_max, blk=blk,
                                with_ts=True)
    s, limbs = code.shape
    nchunk = s // fold_chunk
    xs = (code.reshape(nchunk, fold_chunk, limbs),
          length.reshape(nchunk, fold_chunk),
          ts.reshape(nchunk, fold_chunk, ts.shape[-1]),
          sign.reshape(nchunk, fold_chunk))

    def body(carry, chunk):
        c_code, c_len, c_ts, c_sign = chunk
        new_carry = []
        for (d_i, l_i), (counts, spilled), cap in zip(params, carry,
                                                      merge_caps):
            code_i, len_i = _derive_member(
                c_code, c_len, c_ts,
                d_i=d_i, l_i=l_i, delta=delta, l_max=l_max)
            w = (len_i > 0).astype(jnp.int32) * c_sign
            codes_m = jnp.where(w[:, None] != 0, code_i, 0)
            part = aggregation.count_codes(codes_m, w)
            merged, spill = aggregation.merge_bounded(counts, part, cap=cap)
            new_carry.append((merged, spilled + spill))
        return tuple(new_carry), None

    init = tuple(
        (aggregation.empty_counts(cap, limbs), jnp.int32(0))
        for cap in merge_caps)
    with jax.named_scope("fold"):
        out, _ = jax.lax.scan(body, init, xs)
    return out


@functools.partial(
    jax.jit, static_argnames=("d_i", "l_i", "delta", "l_max", "merge_cap")
)
def _derive_merge_chunk_jit(carry, spilled, codes, lengths, ts, signs, *,
                            d_i, l_i, delta, l_max, merge_cap):
    """One member config's bounded merge of a host-scanned chunk."""
    with jax.named_scope("fold"):
        code_i, len_i = _derive_member(codes, lengths, ts, d_i=d_i,
                                       l_i=l_i, delta=delta, l_max=l_max)
        part = aggregation.aggregate_zones(code_i, len_i, signs)
        merged, spill = aggregation.merge_bounded(carry, part,
                                                  cap=merge_cap)
    return merged, spilled + spill


class MiningExecutor:
    """Chunked scan+aggregate engine over padded zone batches.

    Args:
      delta, l_max: paper parameters (Definitions 2-5).
      backend: registry name ("ref", "pallas", "numpy", or plugin).
      zone_chunk: process zones in chunks of this many to bound peak memory
        (None/0 = whole batch at once); defaults to the backend's hint.
      pad_policy: "pad" appends inert zero-sign zone rows when the zone
        count does not divide ``zone_chunk``; "raise" errors instead.
      agg: Phase-2 aggregation mode — "auto", "legacy", "hierarchical" or
        "pipelined" (see module docstring).
      merge_cap: bounded-merge carry width for the hierarchical modes
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
        codes, lengths, _ = _chunked_scan(
            self.spec.scan, u, v, t, valid,
            delta=self.delta, l_max=self.l_max, zone_chunk=self.zone_chunk,
        )
        with jax.named_scope("fold"):
            return aggregation.aggregate_zones(codes, lengths, signs)

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
        counts, spilled, _ = _hier_fold(
            self.spec.scan, u, v, t, valid, signs,
            delta=self.delta, l_max=self.l_max, zone_chunk=zc,
            merge_cap=self._merge_cap_for(zc, z, e),
        )
        return counts, spilled

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
        the shared-state race the outcome type exists to close).
        """
        if self.resolve_fused(fused):
            return self.run_fused(layout, allow_overflow=allow_overflow)
        self.check_layout_overflow(layout, allow_overflow=allow_overflow)
        with self.obs.tracer.span("mine.layout", path="per-bucket",
                                  buckets=layout.n_buckets):
            runs = [
                self._run_bucket(b.u, b.v, b.t, b.valid, b.sign,
                                 label=b.label)
                for b in layout.buckets
            ]
            counts = merge_partial_counts([r.counts for r in runs],
                                          merge_cap=self.merge_cap,
                                          warn_label="zone-layout bucket",
                                          obs=self.obs)
            stats = {
                "path": "per-bucket",
                "launches": len(layout.buckets),
                "spill_retries": 0,
                "sweep_slots": _sweep_slots(runs),
            }
            self.obs.metrics.counter(
                "repro_mining_launches_total",
                path="per-bucket").inc(len(layout.buckets))
            self.obs.metrics.counter(
                "repro_mining_sweep_slots_total",
                path="per-bucket").inc(stats["sweep_slots"])
            return RunOutcome(counts=counts, stats=stats)

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
        counter come back; a spill retries host-side with a doubled cap
        (ceiling ``n_slots + 1``, which provably cannot spill).
        """
        self.check_layout_overflow(layout, allow_overflow=allow_overflow)
        obs = self.obs
        fspec = self._fused_spec()
        path = self._fused_path()
        blk, fold_chunk, _ = self._fused_geometry(layout)
        fl = concat_layout(layout, blk=blk, pad_slots_to=fold_chunk,
                           delta=self.delta, l_max=self.l_max,
                           bounds=self.fused_bounds)
        cap_ceiling = fl.n_slots + 1
        merge_cap = min(self._fused_merge_cap(fold_chunk), cap_ceiling)
        with obs.tracer.span("mine.h2d", n_slots=fl.n_slots) as sp:
            arrays = tuple(jnp.asarray(x) for x in (
                fl.u, fl.v, fl.t, fl.valid, fl.zone_id, fl.sign, fl.lo,
                fl.hi))
            sp.sync(arrays)
        retries = 0
        while True:
            # one span per launch attempt: a spill retry at a doubled
            # merge_cap recompiles, and its jax.compile event lands here
            with obs.tracer.span("mine.fused", n_slots=fl.n_slots,
                                 merge_cap=merge_cap, retry=retries) as sp:
                counts, spilled = _mine_fused_jit(
                    *arrays, delta=self.delta, l_max=self.l_max,
                    scan=fspec.fused_scan, blk=blk,
                    fold_chunk=fold_chunk, merge_cap=merge_cap,
                )
                sp.sync((counts, spilled))
            with obs.tracer.span("mine.d2h"):
                n_spilled = int(spilled)
            if n_spilled == 0:
                self._note_fused_cap(fold_chunk, merge_cap, retries)
                stats = {
                    "path": path,
                    "backend": fspec.name,
                    "bounds": fl.bounds,
                    "launches": 1,
                    "spill_retries": retries,
                    "merge_cap": merge_cap,
                    "fold_chunk": fold_chunk,
                    "n_slots": fl.n_slots,
                    "sweep_slots": fl.sweep_slots,
                }
                obs.metrics.counter("repro_mining_launches_total",
                                    path=path).inc()
                obs.metrics.counter("repro_mining_sweep_slots_total",
                                    path=path).inc(fl.sweep_slots)
                m = obs.metrics
                m.gauge("repro_mining_fused_merge_cap").set(merge_cap)
                m.gauge("repro_mining_fused_fold_chunk").set(fold_chunk)
                m.gauge("repro_mining_fused_slots").set(fl.n_slots)
                m.gauge("repro_mining_fused_sweep_slots").set(fl.sweep_slots)
                return RunOutcome(counts=counts, stats=stats)
            need = max(2 * merge_cap, merge_cap + n_spilled, 8)
            new_cap = min(1 << (need - 1).bit_length(), cap_ceiling)
            warnings.warn(
                f"fused on-device merge spilled {n_spilled} unique code(s) "
                f"at merge_cap={merge_cap}; retrying with "
                f"merge_cap={new_cap}",
                RuntimeWarning, stacklevel=3,
            )
            obs.metrics.counter("repro_mining_spill_retries_total",
                                path="fused").inc()
            merge_cap = new_cap
            retries += 1

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

    # -- config-lattice co-mining --------------------------------------------

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
        doubled cap, exactly like the single-config paths).
        """
        params = self._check_comine_params(params)
        if self.resolve_fused(fused):
            return self.run_fused_multi(layout, params,
                                        allow_overflow=allow_overflow)
        self.check_layout_overflow(layout, allow_overflow=allow_overflow)
        with self.obs.tracer.span("mine.layout", path="per-bucket-multi",
                                  buckets=layout.n_buckets,
                                  n_configs=len(params)):
            runs = []
            retries_total = 0
            for b in layout.buckets:
                run, retries = self._run_arrays_multi(
                    b.u, b.v, b.t, b.valid, b.sign, params, label=b.label)
                runs.append(run)
                retries_total += retries
            counts = tuple(
                merge_partial_counts(list(p), merge_cap=self.merge_cap,
                                     warn_label="zone-layout bucket",
                                     obs=self.obs)
                for p in zip(*(r.counts for r in runs)))
            sweep_slots = _sweep_slots(runs)
            self.obs.metrics.counter(
                "repro_mining_launches_total",
                path="per-bucket-multi").inc(len(layout.buckets))
            self.obs.metrics.counter(
                "repro_mining_sweep_slots_total",
                path="per-bucket-multi").inc(sweep_slots)
            stats = {
                "path": "per-bucket-multi",
                "launches": len(layout.buckets),
                "spill_retries": retries_total,
                "sweep_slots": sweep_slots,
                "n_configs": len(params),
            }
            return MultiRunOutcome(counts=counts, stats=stats)

    def run_fused_multi(self, layout: ZoneBatchLayout, params, *,
                        allow_overflow: bool = False) -> MultiRunOutcome:
        """Co-mine a layout in ONE kernel launch with N on-device folds."""
        params = self._check_comine_params(params)
        self.check_layout_overflow(layout, allow_overflow=allow_overflow)
        obs = self.obs
        fspec = self._fused_spec()
        path = self._fused_path("-multi")
        blk, fold_chunk, _ = self._fused_geometry(layout)
        fl = concat_layout(layout, blk=blk, pad_slots_to=fold_chunk,
                           delta=self.delta, l_max=self.l_max,
                           bounds=self.fused_bounds)
        cap_ceiling = fl.n_slots + 1
        caps = [min(self._fused_merge_cap(fold_chunk), cap_ceiling)
                for _ in params]
        with obs.tracer.span("mine.h2d", n_slots=fl.n_slots) as sp:
            arrays = tuple(jnp.asarray(x) for x in (
                fl.u, fl.v, fl.t, fl.valid, fl.zone_id, fl.sign, fl.lo,
                fl.hi))
            sp.sync(arrays)
        retries = 0
        while True:
            with obs.tracer.span("mine.fused", n_slots=fl.n_slots,
                                 n_configs=len(params), retry=retries) as sp:
                out = _mine_fused_multi_jit(
                    *arrays, delta=self.delta, l_max=self.l_max,
                    scan=fspec.fused_scan, blk=blk,
                    fold_chunk=fold_chunk, params=params,
                    merge_caps=tuple(caps),
                )
                sp.sync(out)
            with obs.tracer.span("mine.d2h"):
                spills = [int(sp_i) for _, sp_i in out]
            if not any(spills):
                self._note_fused_cap(fold_chunk, max(caps), retries)
                stats = {
                    "path": path,
                    "backend": fspec.name,
                    "bounds": fl.bounds,
                    "launches": 1,
                    "spill_retries": retries,
                    "merge_caps": tuple(caps),
                    "fold_chunk": fold_chunk,
                    "n_slots": fl.n_slots,
                    "sweep_slots": fl.sweep_slots,
                    "n_configs": len(params),
                }
                obs.metrics.counter("repro_mining_launches_total",
                                    path=path).inc()
                obs.metrics.counter("repro_mining_sweep_slots_total",
                                    path=path).inc(fl.sweep_slots)
                return MultiRunOutcome(
                    counts=tuple(c for c, _ in out), stats=stats)
            for i, n_spilled in enumerate(spills):
                if n_spilled:
                    need = max(2 * caps[i], caps[i] + n_spilled, 8)
                    caps[i] = min(1 << (need - 1).bit_length(), cap_ceiling)
            warnings.warn(
                f"fused co-mine spilled {spills} unique code(s) across "
                f"{len(params)} member config(s); retrying with "
                f"merge_caps={caps}",
                RuntimeWarning, stacklevel=3,
            )
            obs.metrics.counter("repro_mining_spill_retries_total",
                                path="fused-multi").inc()
            retries += 1

    def _run_arrays_multi(self, u, v, t, valid, signs, params, *,
                          label: str = ""):
        """Co-mine raw [Z, E] zone arrays; returns ``(_BucketRun,
        retries)``, the run's counts a tuple aligned with ``params``.

        Mirrors :meth:`run_arrays`'s pad/chunk resolution, but always takes
        the bounded hierarchical fold — the multi path has no legacy
        whole-batch mode (an unchunked batch is simply one chunk).
        """
        u, v, t, valid, signs = (np.asarray(x)
                                 for x in (u, v, t, valid, signs))
        z, e = u.shape
        with self.obs.tracer.span("mine.launch", z=z, e=e, label=label,
                                  multi=len(params)) as sp:
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
            sp.set(zone_chunk=zc)
            counts, trips, retries = self._run_bounded_multi(
                u, v, t, valid, signs, zc, params)
            return _BucketRun(counts, trips, _chunk_rows(z, zc), e), retries

    def _run_bounded_multi(self, u, v, t, valid, signs, zc, params):
        """Multi-config bounded fold with per-member spill/retry;
        returns ``(counts tuple, trips, retries)``."""
        z, e = u.shape
        cap_ceiling = z * e + 1
        base_cap = min(self._merge_cap_for(zc, z, e), cap_ceiling)
        caps = [base_cap for _ in params]
        retries = 0
        while True:
            if not self.spec.jittable:
                out, trips = self._fold_host_scan_multi(
                    u, v, t, valid, signs, zc, params, caps)
            else:
                out, trips = _mine_multi_jit(
                    jnp.asarray(u), jnp.asarray(v), jnp.asarray(t),
                    jnp.asarray(valid), jnp.asarray(signs),
                    delta=self.delta, l_max=self.l_max, scan=self.spec.scan,
                    zone_chunk=zc, params=params, merge_caps=tuple(caps),
                )
            spills = [int(sp) for _, sp in out]
            if not any(spills):
                return tuple(c for c, _ in out), trips, retries
            for i, n_spilled in enumerate(spills):
                if n_spilled:
                    need = max(2 * caps[i], caps[i] + n_spilled, 8)
                    caps[i] = min(1 << (need - 1).bit_length(), cap_ceiling)
            warnings.warn(
                f"co-mine hierarchical merge spilled {spills} unique "
                f"code(s) across {len(params)} member config(s); retrying "
                f"with merge_caps={caps}",
                RuntimeWarning, stacklevel=3,
            )
            self.obs.metrics.counter("repro_mining_spill_retries_total",
                                     path="bucket-multi").inc()
            retries += 1

    def _fold_host_scan_multi(self, u, v, t, valid, signs, zc, params, caps):
        """Chunked multi-config fold for host-only backends; returns
        ``(carries, trips)``."""
        z, e = u.shape
        zc = _chunk_rows(z, zc)
        nchunk = _n_chunks(z, zc)
        limbs = encoding.n_limbs(self.l_max)
        carries = [
            (aggregation.empty_counts(cap, limbs), jnp.int32(0))
            for cap in caps]
        trips = 0
        for i in range(nchunk):
            sl = slice(i * zc, (i + 1) * zc)
            res = self.spec.scan(u[sl], v[sl], t[sl], valid[sl],
                                 delta=self.delta, l_max=self.l_max,
                                 with_ts=True)
            trips += int(_trips(res, e))
            codes = jnp.asarray(res.code)
            lengths = jnp.asarray(res.length)
            ts = jnp.asarray(res.ts)
            sg = jnp.asarray(signs[sl])
            for ci, ((d_i, l_i), cap) in enumerate(zip(params, caps)):
                carry, spilled = carries[ci]
                carries[ci] = _derive_merge_chunk_jit(
                    carry, spilled, codes, lengths, ts, sg,
                    d_i=d_i, l_i=l_i, delta=self.delta, l_max=self.l_max,
                    merge_cap=cap,
                )
        return carries, trips

    def run_arrays(self, u, v, t, valid, signs, *,
                   label: str = "") -> CodeCounts:
        """Mine raw [Z, E] zone arrays (+ [Z] signs) to signed code counts."""
        return self._run_bucket(u, v, t, valid, signs, label=label).counts

    def _run_bucket(self, u, v, t, valid, signs, *,
                    label: str = "") -> _BucketRun:
        """One bucket launch of :meth:`run_arrays`, with its scan trips.

        Spans: ``mine.launch`` around the whole bucket; inside it, on the
        jitted legacy and hierarchical paths, ``mine.bucket_h2d`` (the
        arrays put on the device) and ``mine.bucket_scan`` (the jitted scan
        and in-bucket fold up to its counts, one span per spill attempt).
        Each is synced at its end only when the tracer is on.
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

            mode = self._agg_mode_for(zc, z)
            sp.set(agg=mode, zone_chunk=zc)
            if self.spec.jittable and mode != "pipelined":
                # the pipelined fold puts each chunk itself, behind the
                # running one
                with tracer.span("mine.bucket_h2d") as h2d:
                    u, v, t, valid, signs = arrays = tuple(
                        jnp.asarray(x) for x in (u, v, t, valid, signs))
                    h2d.sync(arrays)
            if mode == "legacy":
                counts, trips = self._run_legacy(u, v, t, valid, signs, zc)
            else:
                counts, trips = self._run_bounded(u, v, t, valid, signs, zc,
                                                  mode)
            sp.sync(counts)
            return _BucketRun(counts, trips, _chunk_rows(z, zc), e)

    def _run_legacy(self, u, v, t, valid, signs, zc):
        """Whole-batch fold; returns ``(counts, trips)``."""
        if not self.spec.jittable:
            res = self.spec.scan(u, v, t, valid,
                                 delta=self.delta, l_max=self.l_max)
            return aggregation.aggregate_zones(
                jnp.asarray(res.code), jnp.asarray(res.length),
                jnp.asarray(signs),
            ), _trips(res, u.shape[1])
        with self.obs.tracer.span("mine.bucket_scan") as sp:
            counts, trips = _mine_jit(
                u, v, t, valid, signs, delta=self.delta, l_max=self.l_max,
                scan=self.spec.scan, zone_chunk=zc,
            )
            sp.sync(counts)
        return counts, trips

    def _run_bounded(self, u, v, t, valid, signs, zc, mode):
        """Hierarchical/pipelined fold with the merge-cap spill policy;
        returns ``(counts, trips)``.

        Spills are exact signals, so retrying with a doubled cap is
        lossless; ``merge_cap >= z*e + 1`` can never spill (at most z*e
        distinct live codes, plus one row for the all-zero padding group
        that sorts ahead of them), so the loop terminates.
        """
        z, e = u.shape
        cap_ceiling = z * e + 1
        merge_cap = min(self._merge_cap_for(zc, z, e), cap_ceiling)
        while True:
            if not self.spec.jittable:
                counts, spilled, trips = self._fold_host_scan(
                    u, v, t, valid, signs, zc, merge_cap)
            elif mode == "pipelined":
                counts, spilled, trips = self._fold_pipelined(
                    u, v, t, valid, signs, zc, merge_cap)
            else:
                # one span per attempt: a spill retry runs the scan again
                with self.obs.tracer.span("mine.bucket_scan",
                                          merge_cap=merge_cap) as sp:
                    counts, spilled, trips = _mine_jit_hier(
                        u, v, t, valid, signs, delta=self.delta,
                        l_max=self.l_max, scan=self.spec.scan,
                        zone_chunk=zc, merge_cap=merge_cap,
                    )
                    sp.sync((counts, spilled))
            n_spilled = int(spilled)
            if n_spilled == 0:
                return counts, trips
            # cap+spilled approximates the live-code population (a code cut
            # in several steps is counted per step, so it can only
            # overshoot the next guess); exactness is re-checked each
            # round, and the z*e+1 ceiling provably cannot spill
            need = max(2 * merge_cap, merge_cap + n_spilled, 8)
            new_cap = min(1 << (need - 1).bit_length(), cap_ceiling)
            warnings.warn(
                f"hierarchical merge spilled {n_spilled} unique code(s) at "
                f"merge_cap={merge_cap}; retrying with merge_cap={new_cap}",
                RuntimeWarning, stacklevel=3,
            )
            self.obs.metrics.counter("repro_mining_spill_retries_total",
                                     path="bucket").inc()
            merge_cap = new_cap

    def _fold_pipelined(self, u, v, t, valid, signs, zc, merge_cap):
        """Host-driven double-buffered chunk pipeline.

        Each jitted step is dispatched asynchronously; the *next* chunk's
        host->device transfer (``jax.device_put``) is issued immediately
        after, overlapping with the in-flight compute.  Carry buffers are
        donated, so aggregation state never exceeds one ``merge_cap``
        table.
        """
        z, e = u.shape
        zc = _chunk_rows(z, zc)
        nchunk = _n_chunks(z, zc)
        limbs = encoding.n_limbs(self.l_max)

        def put(i):
            sl = slice(i * zc, (i + 1) * zc)
            return tuple(jax.device_put(x[sl])
                         for x in (u, v, t, valid, signs))

        carry = aggregation.empty_counts(merge_cap, limbs)
        spilled = jnp.zeros((), jnp.int32)
        trips = jnp.zeros((), jnp.int32)
        nxt = put(0)
        for i in range(nchunk):
            cur = nxt
            carry, spilled, trips = _pipeline_step(
                carry, spilled, trips, *cur, delta=self.delta,
                l_max=self.l_max, scan=self.spec.scan, merge_cap=merge_cap,
            )
            if i + 1 < nchunk:
                nxt = put(i + 1)    # async H2D behind the running chunk
        return carry, spilled, trips

    def _fold_host_scan(self, u, v, t, valid, signs, zc, merge_cap):
        """Chunked fold for host-only backends (scan outside jit).

        Even the NumPy oracle gets the hierarchical memory bound: only one
        chunk's [zc, E, L] code block exists at a time, merged through the
        same bounded carry as the device paths.
        """
        z, e = u.shape
        zc = _chunk_rows(z, zc)
        nchunk = _n_chunks(z, zc)
        limbs = encoding.n_limbs(self.l_max)
        carry = aggregation.empty_counts(merge_cap, limbs)
        spilled = jnp.zeros((), jnp.int32)
        trips = 0
        for i in range(nchunk):
            sl = slice(i * zc, (i + 1) * zc)
            res = self.spec.scan(u[sl], v[sl], t[sl], valid[sl],
                                 delta=self.delta, l_max=self.l_max)
            trips += int(_trips(res, e))
            carry, spilled = _merge_chunk_jit(
                carry, spilled, jnp.asarray(res.code),
                jnp.asarray(res.length), jnp.asarray(signs[sl]),
                merge_cap=merge_cap,
            )
        return carry, spilled, trips
