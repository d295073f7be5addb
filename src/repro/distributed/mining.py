"""Distributed PTMT: zones sharded over the mesh (the paper's thread pool).

Per-device scan + signed aggregation is delegated to
:class:`repro.core.executor.MiningExecutor` (``scan_aggregate_partial`` is
traceable and runs inside the ``shard_map`` body); this module owns only the
collective merge.  Phase-2 aggregation becomes a **multi-level merge**:

  1. every device folds its own zones into a partial count table — when the
     executor is chunked this is the hierarchical bounded-carry fold
     (O(zone_chunk*C) peak instead of O(zones_local*C), see
     ``core/executor.py``), never one whole-shard flatten;
  2. only the first ``out_cap`` rows (a configurable unique-code budget) are
     ``all_gather``-ed and merged, shrinking the collective payload from
     O(zones_local * e_cap) to O(out_cap) per device.

Overflow of either budget — the collective ``out_cap`` or the hierarchical
``merge_cap`` carry — is detected and surfaced (psum of a flag) rather than
silently truncated.  This replaces the paper's atomic global hash merge with
a deterministic, collective-friendly reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import aggregation
from repro.core.aggregation import CodeCounts
from repro.core.executor import MiningExecutor, merge_partial_counts

from .collectives import shard_map_compat


def _as_executor(
    executor: MiningExecutor | None,
    *,
    delta: int | None,
    l_max: int | None,
    backend: str,
    zone_chunk: int | None,
    agg: str = "auto",
    merge_cap: int | None = None,
    config=None,
) -> MiningExecutor:
    if executor is None and config is not None:
        executor = MiningExecutor.from_config(config)
    if executor is None:
        if delta is None or l_max is None:
            raise ValueError(
                "pass an executor, a MiningConfig, or delta+l_max")
        executor = MiningExecutor(delta=delta, l_max=l_max, backend=backend,
                                  zone_chunk=zone_chunk, agg=agg,
                                  merge_cap=merge_cap)
    if not executor.spec.jittable:
        raise ValueError(
            f"backend {executor.backend!r} is host-only and cannot be "
            f"sharded over a mesh; use a jittable backend"
        )
    return executor


def make_mine_fn(
    mesh: jax.sharding.Mesh,
    axes: tuple[str, ...],
    *,
    executor: MiningExecutor | None = None,
    config=None,
    delta: int | None = None,
    l_max: int | None = None,
    backend: str = "ref",
    zone_chunk: int = 0,
    agg: str = "auto",
    merge_cap: int | None = None,
    out_cap: int = 65536,
    merge_mode: str = "flat",
):
    """Build the (unjitted) SPMD mining step for a zone batch.

    Returns ``fn(u, v, t, valid, signs) -> (CodeCounts, overflow)`` where the
    zone axis (leading) is sharded over ``axes`` and the result is replicated.
    Pass a configured :class:`MiningExecutor`, a
    :class:`repro.core.config.MiningConfig`, or the legacy
    delta/l_max/backend/zone_chunk (+ agg/merge_cap) kwargs (an executor is
    built internally).  With a chunked executor the per-shard aggregation is
    the hierarchical bounded-carry fold; its merge-cap spills are folded
    into the returned overflow flag.

    merge_mode:
      "flat"         — one all_gather over every axis, then a single merge
                       (paper-faithful analog of the atomic global merge);
      "hierarchical" — gather+merge one mesh axis at a time (innermost
                       first).  Duplicate codes collapse at each stage, so
                       per-device traffic drops from O(n_devices * out_cap)
                       to O(sum(axis sizes) * out_cap) — the beyond-paper
                       collective optimization measured in EXPERIMENTS.md
                       §Perf.
    """
    executor = _as_executor(executor, delta=delta, l_max=l_max,
                            backend=backend, zone_chunk=zone_chunk,
                            agg=agg, merge_cap=merge_cap, config=config)
    zone_spec = P(axes)
    scalar_spec = P(axes)

    def _compact(counts_: aggregation.CodeCounts, cap: int):
        send_codes = jnp.where(
            counts_.unique_mask[:cap, None], counts_.codes[:cap], 0)
        send_counts = jnp.where(
            counts_.unique_mask[:cap], counts_.counts[:cap], 0)
        overflow = (counts_.unique_mask.sum() > cap).astype(jnp.int32)
        return send_codes, send_counts, overflow

    def step(u, v, t, valid, signs):
        local, merge_spill = executor.scan_aggregate_partial(
            u, v, t, valid, signs)
        cap = min(out_cap, local.counts.shape[0])
        overflow = merge_spill
        if merge_mode == "hierarchical":
            merged = local
            for axis in reversed(axes):      # innermost (fastest) first
                send_codes, send_counts, ovf = _compact(merged, cap)
                overflow = overflow + ovf
                all_codes = jax.lax.all_gather(send_codes, axis, tiled=True)
                all_counts = jax.lax.all_gather(send_counts, axis,
                                                tiled=True)
                merged = aggregation.count_codes(all_codes, all_counts)
        else:
            send_codes, send_counts, ovf = _compact(local, cap)
            overflow = overflow + ovf
            all_codes = jax.lax.all_gather(send_codes, axes, tiled=True)
            all_counts = jax.lax.all_gather(send_counts, axes, tiled=True)
            merged = aggregation.count_codes(all_codes, all_counts)
        overflow = jax.lax.psum(overflow, axes)
        return merged, overflow

    return shard_map_compat(
        step,
        mesh=mesh,
        in_specs=(zone_spec, zone_spec, zone_spec, zone_spec, scalar_spec),
        out_specs=(CodeCounts(P(), P(), P()), P()),
    )


def make_mine_step(mesh, axes, **kw):
    """Jitted variant of :func:`make_mine_fn`."""
    return jax.jit(make_mine_fn(mesh, axes, **kw))


def run_mine_fn(fn, batch, *, out_cap: int = 65536) -> CodeCounts:
    """Drive a built mining step over a host :class:`ZoneBatch`.

    The single copy of the device-transfer + overflow-surfacing policy:
    :func:`mine_on_mesh` (one-shot) and
    :meth:`repro.core.engine.PTMTEngine.sharded` (cached step) both call
    it.  A positive psum'd overflow flag — collective ``out_cap`` exceeded
    or a hierarchical ``merge_cap`` carry spill — raises instead of
    silently truncating.
    """
    counts, overflow = fn(
        jnp.asarray(batch.u), jnp.asarray(batch.v), jnp.asarray(batch.t),
        jnp.asarray(batch.valid), jnp.asarray(batch.sign),
    )
    if int(overflow) > 0:
        raise RuntimeError(
            f"unique-code budget overflow on the mesh (psum flag "
            f"{int(overflow)}): either a device exceeded out_cap="
            f"{out_cap} at the collective merge or its hierarchical "
            f"merge_cap carry spilled; re-run with a larger out_cap / "
            f"merge_cap"
        )
    return counts


def run_mine_layout(fn, layout, *, out_cap: int = 65536,
                    merge_cap: int | None = None,
                    on_bucket=None) -> CodeCounts:
    """Drive a built SPMD step over every bucket of a layout and merge.

    The single copy of the per-bucket shard policy: each bucket runs
    through :func:`run_mine_fn` (``jax.jit`` re-specializes per bucket
    shape and caches), then the replicated partial tables fold through the
    bounded signed carry.  ``on_bucket(bucket)`` is invoked after each
    bucket's run — :meth:`repro.core.engine.PTMTEngine.sharded` uses it to
    record per-bucket execution keys.  Callers enforce the overflow policy
    (``MiningExecutor.check_layout_overflow``) before building device
    batches.
    """
    parts = []
    for bucket in layout.buckets:
        parts.append(run_mine_fn(fn, bucket, out_cap=out_cap))
        if on_bucket is not None:
            on_bucket(bucket)
    return merge_partial_counts(parts, merge_cap=merge_cap,
                                warn_label="sharded bucket").counts


def mine_layout_on_mesh(
    layout,
    mesh: jax.sharding.Mesh,
    axes: tuple[str, ...],
    *,
    executor: MiningExecutor | None = None,
    config=None,
    delta: int | None = None,
    l_max: int | None = None,
    backend: str = "ref",
    zone_chunk: int | None = None,
    agg: str = "auto",
    merge_cap: int | None = None,
    out_cap: int = 65536,
    merge_mode: str = "flat",
    allow_overflow: bool = False,
) -> CodeCounts:
    """Distributed discovery over a host-built ``ZoneBatchLayout``.

    Sharding is **per bucket**: each size bucket's zone axis is sharded
    over the mesh independently (its zones were round-robined across the
    shard lanes at build time, so the static load balance holds within
    every capacity class), one SPMD step serves every bucket (``jax.jit``
    re-specializes per bucket shape and caches — recurring bucket
    geometries reuse executables), and the replicated per-bucket count
    tables fold through the bounded signed carry
    (:func:`repro.core.executor.merge_partial_counts`) host-side.  Build
    the layout with ``n_shards = prod(mesh axis sizes)`` so every bucket's
    zone count divides the shard count.  Layouts that dropped edges raise
    :class:`~repro.core.executor.ZoneOverflowError` (same policy as the
    local ``run_layout``) unless ``allow_overflow=True``.
    """
    ex = _as_executor(executor, delta=delta, l_max=l_max, backend=backend,
                      zone_chunk=zone_chunk, agg=agg, merge_cap=merge_cap,
                      config=config)
    MiningExecutor.check_layout_overflow(layout,
                                         allow_overflow=allow_overflow)
    fn = make_mine_step(mesh, axes, executor=ex, out_cap=out_cap,
                        merge_mode=merge_mode)
    return run_mine_layout(fn, layout, out_cap=out_cap,
                           merge_cap=ex.merge_cap)


def mine_on_mesh(
    batch,
    mesh: jax.sharding.Mesh,
    axes: tuple[str, ...],
    *,
    executor: MiningExecutor | None = None,
    config=None,
    delta: int | None = None,
    l_max: int | None = None,
    backend: str = "ref",
    zone_chunk: int | None = None,
    agg: str = "auto",
    merge_cap: int | None = None,
    out_cap: int = 65536,
) -> CodeCounts:
    """Run distributed discovery over a host-built :class:`ZoneBatch`.

    One-shot: builds (and jits) the step per call.  For repeated sharded
    runs use :meth:`repro.core.engine.PTMTEngine.sharded`, which caches the
    compiled step per mesh geometry.
    """
    fn = make_mine_step(
        mesh, axes, executor=executor, config=config, delta=delta,
        l_max=l_max, backend=backend, zone_chunk=zone_chunk or 0, agg=agg,
        merge_cap=merge_cap, out_cap=out_cap,
    )
    return run_mine_fn(fn, batch, out_cap=out_cap)


def input_specs(n_zones: int, e_cap: int):
    """ShapeDtypeStructs for the mining step (dry-run stand-ins)."""
    zs = jax.ShapeDtypeStruct((n_zones, e_cap), jnp.int32)
    return dict(
        u=zs, v=zs, t=zs,
        valid=jax.ShapeDtypeStruct((n_zones, e_cap), jnp.bool_),
        signs=jax.ShapeDtypeStruct((n_zones,), jnp.int32),
    )
