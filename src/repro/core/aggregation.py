"""Phase 2 — overlap-aware signed aggregation (sort + segment-sum).

The paper deduplicates boundary-zone candidates with hash sets and an atomic
global merge.  On TPU we instead exploit Lemma 4.2 directly: count every zone
independently and give growth zones weight +1, boundary zones weight -1.  The
signed sum over identical codes *is* the inclusion-exclusion reconciliation
``|G| = sum|G_i| - sum|B_i|`` — no hashing, no atomics, fully vectorized:

  1. flatten (zone, candidate) -> one stream of (code limbs, weight);
  2. lexicographic sort by limbs (``lax.sort`` with num_keys = n_limbs);
  3. group boundaries by adjacent-difference; segment-sum the weights.

Everything is static-shape; invalid slots carry the all-zero code (sorts
first) with weight 0 and are dropped by the caller via the validity mask.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class CodeCounts(NamedTuple):
    """Sorted unique codes with (possibly signed-cancelled) counts.

    ``codes`` int32[N, L] — row i is meaningful where ``unique_mask[i]``;
    ``counts`` int32[N]   — aligned with codes;
    ``unique_mask`` bool[N].
    The all-zero padding code, if present, is masked out.

    Invariant: every row where ``unique_mask`` is true lies in the first
    ``unique_mask.sum() + 1`` rows.  :func:`count_codes` writes its groups
    to the front (the +1 is the padding group), :func:`merge_bounded`
    keeps a prefix of them, and :func:`empty_counts` has none; the
    cross-bucket merge trims each part to that prefix before sorting.
    """

    codes: jax.Array
    counts: jax.Array
    unique_mask: jax.Array


def empty_counts(capacity: int, limbs: int) -> CodeCounts:
    """An all-padding count table (the identity element of merging)."""
    return CodeCounts(
        codes=jnp.zeros((capacity, limbs), jnp.int32),
        counts=jnp.zeros((capacity,), jnp.int32),
        unique_mask=jnp.zeros((capacity,), bool),
    )


@jax.jit
def count_codes(codes, weights) -> CodeCounts:
    """Signed counting of code rows.

    Args:
      codes:   int32[N, L] limb codes (all-zero rows = padding).
      weights: int32[N] signed weights (0 for padding).
    """
    n, limbs = codes.shape
    if n == 0:
        return empty_counts(0, limbs)
    operands = tuple(codes[:, i] for i in range(limbs)) + (weights,)
    sorted_ops = jax.lax.sort(operands, num_keys=limbs)
    sorted_codes = jnp.stack(sorted_ops[:limbs], axis=1)
    sorted_w = sorted_ops[limbs]

    prev = jnp.roll(sorted_codes, 1, axis=0)
    boundary = jnp.any(sorted_codes != prev, axis=1)
    boundary = boundary.at[0].set(True)
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1

    counts = jax.ops.segment_sum(sorted_w, gid, num_segments=n)
    unique_codes = jnp.zeros_like(sorted_codes).at[gid].set(sorted_codes)
    n_unique = gid[-1] + 1
    idx = jnp.arange(n)
    unique_mask = (idx < n_unique) & jnp.any(unique_codes != 0, axis=1)
    return CodeCounts(codes=unique_codes, counts=counts,
                      unique_mask=unique_mask)


@functools.partial(jax.jit, donate_argnums=())
def aggregate_zones(zone_codes, zone_lengths, zone_signs) -> CodeCounts:
    """Flatten a [Z, C, L] zone-result batch and signed-count it.

    Args:
      zone_codes:   int32[Z, C, L] final candidate codes.
      zone_lengths: int32[Z, C] process lengths (0 = padding slot).
      zone_signs:   int32[Z] +1 growth / -1 boundary / 0 padded zone row.
    """
    z, c, limbs = zone_codes.shape
    flat_codes = zone_codes.reshape(z * c, limbs)
    w = (zone_lengths > 0).astype(jnp.int32) * zone_signs[:, None]
    flat_w = w.reshape(z * c)
    flat_codes = jnp.where(flat_w[:, None] != 0, flat_codes, 0)
    return count_codes(flat_codes, flat_w)


@jax.jit
def merge_counts(a: CodeCounts, b: CodeCounts) -> CodeCounts:
    """Merge two (e.g. per-device) count maps into one."""
    codes = jnp.concatenate([
        jnp.where(a.unique_mask[:, None], a.codes, 0),
        jnp.where(b.unique_mask[:, None], b.codes, 0),
    ])
    counts = jnp.concatenate([
        jnp.where(a.unique_mask, a.counts, 0),
        jnp.where(b.unique_mask, b.counts, 0),
    ])
    return count_codes(codes, counts)


def live_rows(c: CodeCounts):
    """(codes, counts) with dead rows zeroed.

    A row is live when it is a unique code whose signed count has not fully
    cancelled.  Cancelled rows (count 0) are semantically absent but still
    occupy table slots after :func:`count_codes`; zeroing their codes lets
    the next merge reclaim the capacity — they collapse into the all-zero
    padding group instead of holding a bounded-width carry slot forever.
    """
    live = c.unique_mask & (c.counts != 0)
    return jnp.where(live[:, None], c.codes, 0), jnp.where(live, c.counts, 0)


@functools.partial(jax.jit, static_argnames=("cap",))
def merge_bounded(a: CodeCounts, b: CodeCounts, *, cap: int):
    """Merge ``b`` into ``a``, bounding the result to ``cap`` rows.

    The carry primitive of hierarchical aggregation: fold partial per-chunk
    count tables through a fixed-capacity table so the merge tree has
    bounded width (peak memory O(cap + len(b)) instead of O(total
    candidates)).  Unique codes compact to the front sorted, so truncating
    to ``cap`` rows is exact whenever the live-unique population fits.

    Returns ``(merged, spilled)`` where ``spilled`` is the number of live
    unique codes that did NOT fit in ``cap`` rows.  ``spilled > 0`` means
    the result is inexact and the caller must re-run with a larger cap
    (the executor's spill policy doubles ``merge_cap`` and retries — exact
    overflow detection makes the retry loop lossless).
    """
    a_codes, a_counts = live_rows(a)
    b_codes, b_counts = live_rows(b)
    merged = count_codes(jnp.concatenate([a_codes, b_codes]),
                         jnp.concatenate([a_counts, b_counts]))
    live = merged.unique_mask & (merged.counts != 0)
    spilled = live[cap:].sum(dtype=jnp.int32)
    total = merged.counts.shape[0]
    if total >= cap:
        out = CodeCounts(codes=merged.codes[:cap], counts=merged.counts[:cap],
                         unique_mask=merged.unique_mask[:cap])
    else:
        pad = cap - total
        out = CodeCounts(
            codes=jnp.pad(merged.codes, ((0, pad), (0, 0))),
            counts=jnp.pad(merged.counts, (0, pad)),
            unique_mask=jnp.pad(merged.unique_mask, (0, pad)),
        )
    return out, spilled
