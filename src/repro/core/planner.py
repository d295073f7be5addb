"""Capacity planner — derive mining capacities from a device-memory budget.

Hardcoded ``zone_chunk`` hints do not transfer between graphs: the right
chunk size depends on the zone batch's edge capacity, on ``l_max`` (node
table + code limbs scale with it), and on how much device memory the
deployment actually has.  This module owns the arithmetic:

* a per-zone **memory model** of the scan (inputs + expansion state +
  outputs).  Backends can override it via ``BackendSpec.mem_model`` — the
  Pallas kernel, for example, pads the edge axis up to block multiples;
* peak-memory estimates for the **legacy** whole-batch aggregation
  (O(Z*C*L): every zone's candidate codes are materialized, flattened and
  sorted at once) and for the **hierarchical** chunked fold
  (O(zone_chunk*C*L + merge_cap*L): one chunk of scan state plus one
  bounded-width merge table, independent of Z);
* :func:`plan_capacity`, which picks the largest power-of-two
  ``zone_chunk`` (and matching ``merge_cap``) whose hierarchical peak fits
  the budget, and :func:`suggest_e_cap` for sizing the zone capacity
  itself.

Estimates are analytic, not measured — they exist to pick sane shapes and
to make the O(Z*C) -> O(zone_chunk*C) ceiling move auditable (see
EXPERIMENTS.md and ``benchmarks/bench_perf_mining.py``), not to account
for every XLA temporary.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

from . import encoding

# host->device inputs: u, v, t int32 + valid bool, per edge slot
_INPUT_BYTES_PER_EDGE = 13
# sort-based counting touches ~2 copies of the (code, count) row stream
# (operand + sorted output) before the segment-sum
_SORT_COPIES = 2


def ref_zone_bytes(e_cap: int, l_max: int) -> int:
    """Per-zone scan footprint of the vectorized reference backend.

    inputs (u, v, t, valid) + ZoneState (length, last_t, n_nodes int32;
    done bool; nodes int32[E, l_max+1]; code int32[E, L]) + ZoneResult
    (code int32[E, L], length int32[E]).
    """
    limbs = encoding.n_limbs(l_max)
    k = l_max + 1
    state = 13 + 4 * k + 4 * limbs
    out = 4 * limbs + 4
    return e_cap * (_INPUT_BYTES_PER_EDGE + state + out)


def pallas_zone_bytes(e_cap: int, l_max: int, *, c_blk: int = 512,
                      e_blk: int = 256) -> int:
    """Pallas kernel model: the edge axis pads up to the larger block."""
    blk = max(c_blk, e_blk)
    e_pad = -(-e_cap // blk) * blk
    return ref_zone_bytes(e_pad, l_max)


def count_table_bytes(rows: int, l_max: int) -> int:
    """Footprint of one sorted count table of ``rows`` (code, count) rows."""
    limbs = encoding.n_limbs(l_max)
    return _SORT_COPIES * rows * 4 * (limbs + 1)


def legacy_peak_bytes(n_zones: int, e_cap: int, l_max: int, *,
                      zone_chunk: int = 0,
                      mem_model: Callable[[int, int], int] | None = None,
                      ) -> int:
    """Peak estimate of whole-batch aggregation: O(Z*C) regardless of chunking.

    Chunking the scan (``lax.map``) bounds the *scan state* to one chunk,
    but the legacy path still materializes every zone's candidate codes
    before the single flatten-and-sort — that [Z*C, L] stream is the term
    the hierarchical fold removes.
    """
    model = mem_model or ref_zone_bytes
    limbs = encoding.n_limbs(l_max)
    chunk = min(zone_chunk, n_zones) if zone_chunk else n_zones
    scan_state = chunk * model(e_cap, l_max)
    all_codes = n_zones * e_cap * (4 * limbs + 4)
    return scan_state + all_codes + count_table_bytes(n_zones * e_cap, l_max)


def hierarchical_peak_bytes(zone_chunk: int, e_cap: int, l_max: int, *,
                            merge_cap: int,
                            mem_model: Callable[[int, int], int] | None = None,
                            ) -> int:
    """Peak estimate of the chunked fold: independent of the zone count."""
    model = mem_model or ref_zone_bytes
    scan_state = zone_chunk * model(e_cap, l_max)
    merge_rows = merge_cap + zone_chunk * e_cap
    limbs = encoding.n_limbs(l_max)
    carry = merge_cap * 4 * (limbs + 1)
    return scan_state + carry + count_table_bytes(merge_rows, l_max)


def fused_peak_bytes(n_slots: int, l_max: int, *, fold_chunk: int,
                     merge_cap: int) -> int:
    """Peak estimate of the fused single-launch path.

    The concatenated stream's resident state: six flat int32 input arrays
    (u, v, t, valid, zone_id, sign), the kernel's [S, L] code + [S] length
    outputs (HBM-resident between the scan and the fold — they never
    round-trip to host), the bounded merge carry, and one fold step's sort
    scratch (``fold_chunk + merge_cap`` rows).  Unlike the per-bucket
    hierarchical model there is no per-zone scan-state term: candidate
    state lives in registers/VMEM per grid step, not in an allocated
    [zone_chunk, E] batch.
    """
    limbs = encoding.n_limbs(l_max)
    inputs = 6 * 4 * n_slots
    outputs = n_slots * (4 * limbs + 4)
    carry = merge_cap * 4 * (limbs + 1)
    return (inputs + outputs + carry
            + count_table_bytes(fold_chunk + merge_cap, l_max))


def default_fold_chunk(n_slots: int, *, blk: int) -> int:
    """Fold-chunk default: ~4096 candidate rows per on-device fold step,
    scaled up (to at most 16384) once the stream is large enough that the
    sequential merge chain would dominate — every fold step pays an
    O(merge_cap) bounded merge regardless of chunk size, so a big stream
    folded in 4096-row steps spends more time merging than scanning.
    Rounded to a ``blk`` multiple and clamped to the (blk-aligned) stream
    so tiny layouts do not pad up to a chunk they cannot fill."""
    scaled = min(16384, n_slots // 8) // blk * blk
    target = max(blk, 4096 // blk * blk, scaled)
    slots = max(-(-max(n_slots, 1) // blk) * blk, blk)
    return min(target, slots)


@dataclasses.dataclass(frozen=True)
class FusedCapacityPlan:
    """Budget-derived capacities for the fused single-launch path."""

    fold_chunk: int
    merge_cap: int
    budget_bytes: int
    est_peak_bytes: int

    @property
    def fits(self) -> bool:
        return self.est_peak_bytes <= self.budget_bytes


def plan_fused_capacity(
    *,
    n_slots: int,
    l_max: int,
    memory_budget_mb: float,
    blk: int,
    merge_cap: int | None = None,
) -> FusedCapacityPlan:
    """Largest ``blk``-multiple ``fold_chunk`` whose fused peak fits.

    Mirrors :func:`plan_capacity` for the flat stream: the fold chunk is
    the only free memory knob (the stream itself is workload-determined),
    doubling from ``blk`` while the estimate stays under budget.
    ``merge_cap`` defaults to one fold chunk's rows, exactly like the
    per-bucket default of one zone chunk's rows.
    """
    if memory_budget_mb <= 0:
        raise ValueError("memory_budget_mb must be > 0")
    budget = int(memory_budget_mb * 2**20)
    ceiling = default_fold_chunk(n_slots, blk=blk)

    def peak(fc: int) -> int:
        cap = merge_cap if merge_cap is not None else max(1024, fc)
        return fused_peak_bytes(n_slots, l_max, fold_chunk=fc, merge_cap=cap)

    fc = blk
    while fc * 2 <= ceiling and peak(fc * 2) <= budget:
        fc *= 2
    cap = merge_cap if merge_cap is not None else max(1024, fc)
    return FusedCapacityPlan(
        fold_chunk=fc, merge_cap=cap, budget_bytes=budget,
        est_peak_bytes=peak(fc),
    )


def default_merge_cap(zone_chunk: int, e_cap: int) -> int:
    """One chunk's candidate rows: the first chunk can never spill, and the
    carry is no bigger than the partial table it merges with.  The 1024-row
    floor (~a few tens of KB) absorbs small chunks whose live-unique
    population exceeds one chunk's rows, avoiding spill-retry recompiles."""
    return max(1024, zone_chunk * e_cap)


@dataclasses.dataclass(frozen=True)
class CapacityPlan:
    """Budget-derived mining capacities (all sizes in bytes)."""

    zone_chunk: int
    merge_cap: int
    budget_bytes: int
    per_zone_bytes: int
    est_peak_bytes: int

    @property
    def fits(self) -> bool:
        return self.est_peak_bytes <= self.budget_bytes


def plan_capacity(
    *,
    n_zones: int,
    e_cap: int,
    l_max: int,
    memory_budget_mb: float,
    mem_model: Callable[[int, int], int] | None = None,
    merge_cap: int | None = None,
) -> CapacityPlan:
    """Largest power-of-two ``zone_chunk`` whose hierarchical peak fits.

    ``merge_cap`` defaults to one chunk's candidate rows and scales with
    the chosen chunk.  The floor is ``zone_chunk=1``; a plan whose
    ``fits`` is False means even one zone exceeds the budget (the caller
    should shrink ``e_cap`` — see :func:`suggest_e_cap`).
    """
    if memory_budget_mb <= 0:
        raise ValueError("memory_budget_mb must be > 0")
    n_zones = max(int(n_zones), 1)
    budget = int(memory_budget_mb * 2**20)

    def peak(zc: int) -> int:
        cap = merge_cap if merge_cap is not None else default_merge_cap(zc,
                                                                        e_cap)
        return hierarchical_peak_bytes(zc, e_cap, l_max, merge_cap=cap,
                                       mem_model=mem_model)

    zc = 1
    while zc * 2 <= n_zones and peak(zc * 2) <= budget:
        zc *= 2
    cap = merge_cap if merge_cap is not None else default_merge_cap(zc, e_cap)
    model = mem_model or ref_zone_bytes
    return CapacityPlan(
        zone_chunk=zc,
        merge_cap=cap,
        budget_bytes=budget,
        per_zone_bytes=model(e_cap, l_max),
        est_peak_bytes=peak(zc),
    )


def plan_layout_capacity(
    bucket_shapes,
    *,
    l_max: int,
    memory_budget_mb: float,
    mem_model: Callable[[int, int], int] | None = None,
    merge_cap: int | None = None,
) -> dict[tuple[int, int], CapacityPlan]:
    """Per-bucket capacity plans for a size-bucketed zone layout.

    ``bucket_shapes`` is a sequence of ``(n_zones, e_cap)`` pairs (see
    ``ZoneBatchLayout.bucket_shapes``).  Each bucket's ``zone_chunk`` and
    ``merge_cap`` are derived from its **own** edge capacity — the whole
    point of the ragged layout: a quiet bucket with e_cap=64 fits far more
    zones per chunk than the dense plan sized by the global max would
    allow, so the device stays occupied instead of sweeping padding.
    Duplicate shapes collapse to one plan.

    Introspection/benchmark helper: at runtime the same per-bucket
    derivation happens inside ``MiningExecutor.run_arrays`` via
    ``capacity_plan`` (which memoizes :func:`plan_capacity` per bucket
    geometry); this function mirrors it for offline what-if analysis
    without building batches.
    """
    return {
        shape: plan_capacity(
            n_zones=shape[0], e_cap=shape[1], l_max=l_max,
            memory_budget_mb=memory_budget_mb, mem_model=mem_model,
            merge_cap=merge_cap,
        )
        for shape in dict.fromkeys(tuple(s) for s in bucket_shapes)
    }


def layout_peak_bytes(plans: dict[tuple[int, int], CapacityPlan]) -> int:
    """Peak estimate of a bucketed run: buckets execute sequentially, so
    the layout's peak is the worst single bucket, not the sum."""
    return max((p.est_peak_bytes for p in plans.values()), default=0)


def fused_sweep_slots(lo, hi, blk: int) -> int:
    """Dispatched sweep work of a fused flat stream: each candidate block
    of ``blk`` lanes streams its ``[lo, hi)`` window once, so the slot-cell
    cost is ``blk * sum(hi - lo)``.

    The fused analog of :attr:`repro.core.tzp.ZoneBatchLayout.sweep_slots`,
    and the quantity host-planned compaction attacks: tightening ``hi`` to
    the Lemma-4.1 horizon cut (``tzp.concat_layout(bounds="live")``)
    shrinks this model directly, and with it the compiled kernel's chunk
    traffic below.
    """
    return int(blk) * int(sum(int(h) - int(l) for l, h in zip(lo, hi)))


def fused_traffic_bytes(fl, l_max: int) -> int:
    """Traffic model of one fused launch (int32 everywhere).

    * chunk loads — each candidate block streams its ``hi - lo`` window
      once (shared across the block's lanes): 5 arrays (u/v/t/valid/zid)
      x 4 B x ``sweep_slots / blk`` slot-loads;
    * lane loads — every slot is read once as a candidate lane
      (t/valid/zid): 3 x 4 B x ``n_slots``;
    * outputs — per-lane code limbs + length: ``(limbs + 1) x 4 B x
      n_slots`` written by the kernel, read back by the on-device fold.

    ``fl`` is a :class:`repro.core.tzp.FusedZoneLayout`; the roofline
    benchmark divides this by measured wall time for achieved bytes/s.
    """
    limbs = encoding.n_limbs(l_max)
    chunk = (fl.sweep_slots // fl.blk) * 5 * 4
    lanes = fl.n_slots * 3 * 4
    out = fl.n_slots * (limbs + 1) * 4 * 2
    return chunk + lanes + out


# ---------------------------------------------------------------------------
# Config lattice — grouping N tenant configs into shared dominating sweeps.
# ---------------------------------------------------------------------------

# Fields a lattice member may vary while still sharing one Phase-1 sweep.
# ``delta``/``l_max`` shrink losslessly from the dominating sweep by prefix-
# truncating candidates on absorption timestamps; ``omega`` only shapes zone
# geometry (never counts), so planning at the max omega is exact.
_LATTICE_FREE_FIELDS = ("delta", "l_max", "omega")


@dataclasses.dataclass(frozen=True)
class ConfigLattice:
    """One co-minable group of configs plus its dominating sweep config.

    ``members`` preserve the caller's order; ``indices`` are their
    positions in the original request, so ``discover_many`` can return
    results aligned with its input.  ``dominating`` is the member-wise
    maximum over the free fields — every member's process table is a
    prefix-truncation of the dominating sweep's (see
    :func:`repro.core.expansion.derive_lengths`).
    """

    dominating: object                  # MiningConfig (duck-typed)
    members: tuple                      # tuple[MiningConfig, ...]
    indices: tuple[int, ...]

    @property
    def n_configs(self) -> int:
        return len(self.members)

    @property
    def params(self) -> tuple[tuple[int, int], ...]:
        """Per-member ``(delta, l_max)`` — the executor fold's static key."""
        return tuple((m.delta, m.l_max) for m in self.members)


def lattice_key(config) -> tuple:
    """Compatibility key: everything about a config *except* the free
    fields.  Configs with equal keys can share one dominating sweep."""
    d = config.to_dict()
    for f in _LATTICE_FREE_FIELDS:
        d.pop(f, None)
    return tuple(sorted(d.items()))


def dominating_config(configs):
    """The member-wise max config a lattice plans its shared sweep at."""
    if not configs:
        raise ValueError("dominating_config needs at least one config")
    return configs[0].with_updates(
        delta=max(c.delta for c in configs),
        l_max=max(c.l_max for c in configs),
        omega=max(c.omega for c in configs),
    )


def build_config_lattices(configs) -> list[ConfigLattice]:
    """Group configs into co-minable lattices (input order preserved).

    Configs differing only in ``delta``/``l_max``/``omega`` land in one
    lattice; anything else (backend, e_cap, zone layout, merge caps, ...)
    splits them, because those change the sweep itself rather than how its
    candidate table is folded.
    """
    groups: dict[tuple, list[int]] = {}
    for i, cfg in enumerate(configs):
        groups.setdefault(lattice_key(cfg), []).append(i)
    return [
        ConfigLattice(
            dominating=dominating_config([configs[i] for i in idxs]),
            members=tuple(configs[i] for i in idxs),
            indices=tuple(idxs),
        )
        for idxs in groups.values()
    ]


def comine_peak_bytes(zone_chunk: int, e_cap: int, l_max_dom: int, *,
                      merge_caps, mem_model=None) -> int:
    """Peak estimate of the multi-config hierarchical fold.

    One dominating-config scan chunk (plus its ``ts`` int32[E, l_max]
    timestamp table) is resident at a time, but every member keeps its own
    bounded merge carry and the fold sorts one member's table at a time —
    so the count-table term scales with the *largest* member cap while the
    carry term sums over members.
    """
    model = mem_model or ref_zone_bytes
    scan_state = zone_chunk * (model(e_cap, l_max_dom) + 4 * l_max_dom * e_cap)
    limbs = encoding.n_limbs(l_max_dom)
    carry = sum(cap * 4 * (limbs + 1) for cap in merge_caps)
    worst = max(merge_caps, default=0)
    return scan_state + carry + count_table_bytes(
        worst + zone_chunk * e_cap, l_max_dom)


def suggest_e_cap(
    *,
    l_max: int,
    memory_budget_mb: float,
    zone_chunk: int = 1,
    mem_model: Callable[[int, int], int] | None = None,
    pad_edges_to: int = 8,
) -> int:
    """Largest power-of-two zone edge capacity that fits the budget with
    ``zone_chunk`` zones in flight (the planner's answer to "how dense a
    zone can this device even hold?")."""
    if memory_budget_mb <= 0:
        raise ValueError("memory_budget_mb must be > 0")
    budget = int(memory_budget_mb * 2**20)
    e = pad_edges_to
    while hierarchical_peak_bytes(
            zone_chunk, e * 2, l_max,
            merge_cap=default_merge_cap(zone_chunk, e * 2),
            mem_model=mem_model) <= budget:
        e *= 2
        if e >= 1 << 24:        # 16M edges per zone: beyond any real batch
            break
    return e
