"""Phase-2 aggregation edge cases + the merge algebra tree-merging rests on.

``count_codes``/``merge_counts``/``merge_bounded`` are the primitives every
aggregation path (whole-batch, hierarchical carry, mesh collective, stream
finalization) composes, so their edge cases — empty inputs, all-padding
batches, fully-cancelled signed counts, near-int32 saturation — and the
associativity of merging (merge order must not change results, the algebraic
precondition for *any* merge tree) are pinned here.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import transitions
from repro.core.aggregation import (
    CodeCounts,
    count_codes,
    empty_counts,
    merge_bounded,
    merge_counts,
)

LIMBS = 2


def _counts_of(pairs, capacity=None):
    """CodeCounts from [(code_row, count), ...] via count_codes."""
    n = capacity or max(len(pairs), 1)
    codes = np.zeros((n, LIMBS), np.int32)
    w = np.zeros(n, np.int32)
    for i, (row, cnt) in enumerate(pairs):
        codes[i] = row
        w[i] = cnt
    return count_codes(jnp.asarray(codes), jnp.asarray(w))


def _as_dict(c: CodeCounts) -> dict:
    codes = np.asarray(c.codes)
    counts = np.asarray(c.counts)
    mask = np.asarray(c.unique_mask) & (counts != 0)
    return {tuple(int(x) for x in codes[i]): int(counts[i])
            for i in np.flatnonzero(mask)}


# ---------------------------------------------------------------------------
# Empty / all-padding inputs.
# ---------------------------------------------------------------------------


def test_count_codes_empty_input():
    out = count_codes(jnp.zeros((0, LIMBS), jnp.int32),
                      jnp.zeros((0,), jnp.int32))
    assert out.codes.shape == (0, LIMBS)
    assert out.counts.shape == (0,)
    assert not np.asarray(out.unique_mask).any()
    assert _as_dict(out) == {}


def test_merge_counts_of_empties_is_empty():
    a = empty_counts(0, LIMBS)
    b = empty_counts(4, LIMBS)
    assert _as_dict(merge_counts(a, b)) == {}
    assert _as_dict(merge_counts(b, b)) == {}


def test_all_padding_batch_counts_nothing():
    """All-zero codes with zero weights — the fully-padded zone chunk."""
    out = count_codes(jnp.zeros((16, LIMBS), jnp.int32),
                      jnp.zeros((16,), jnp.int32))
    assert not np.asarray(out.unique_mask).any()
    assert _as_dict(out) == {}
    assert transitions.device_counts_to_dict(out) == {}


def test_padding_code_with_nonzero_weight_stays_masked():
    """The all-zero code is padding by contract even if a weight leaks in."""
    out = count_codes(jnp.zeros((4, LIMBS), jnp.int32),
                      jnp.asarray([3, 0, 0, 0], jnp.int32))
    assert _as_dict(out) == {}


# ---------------------------------------------------------------------------
# Signed cancellation.
# ---------------------------------------------------------------------------


def test_fully_cancelled_counts_disappear():
    c = _counts_of([((7, 0), 5), ((7, 0), -5), ((9, 1), 2)], capacity=8)
    assert _as_dict(c) == {(9, 1): 2}
    assert transitions.device_counts_to_dict(c) == \
        transitions.counts_to_dict(np.asarray(c.codes), np.asarray(c.counts),
                                   np.asarray(c.unique_mask))


def test_merge_cancels_across_tables():
    a = _counts_of([((7, 0), 5), ((3, 2), 1)])
    b = _counts_of([((7, 0), -5), ((4, 0), 1)])
    assert _as_dict(merge_counts(a, b)) == {(3, 2): 1, (4, 0): 1}


def test_merge_bounded_reclaims_cancelled_slots():
    """A cancelled code must not hold a bounded-carry slot forever."""
    a = _counts_of([((7, 0), 5), ((7, 0), -5)], capacity=4)   # cancelled
    b = _counts_of([((3, 1), 1), ((4, 1), 1), ((5, 1), 1)], capacity=4)
    merged, spilled = merge_bounded(a, b, cap=4)
    # 3 live codes + the padding group fit in 4 rows only because the
    # cancelled (7, 0) row was reclaimed
    assert int(spilled) == 0
    assert _as_dict(merged) == {(3, 1): 1, (4, 1): 1, (5, 1): 1}


# ---------------------------------------------------------------------------
# Bounded merge: spill detection and exactness.
# ---------------------------------------------------------------------------


def test_merge_bounded_exact_when_it_fits():
    a = _counts_of([((2, 0), 1), ((3, 0), 2)], capacity=8)
    b = _counts_of([((3, 0), 40), ((9, 9), -1)], capacity=8)
    merged, spilled = merge_bounded(a, b, cap=8)
    assert int(spilled) == 0
    assert _as_dict(merged) == {(2, 0): 1, (3, 0): 42, (9, 9): -1}


def test_merge_bounded_detects_spill_exactly():
    pairs_a = [((i + 1, 0), 1) for i in range(6)]
    pairs_b = [((i + 1, 1), 1) for i in range(6)]
    a = _counts_of(pairs_a, capacity=8)
    b = _counts_of(pairs_b, capacity=8)
    merged, spilled = merge_bounded(a, b, cap=4)
    # 12 live codes, one leading padding-group row possible; at most 4 rows
    # kept -> at least 8 must be reported lost, never silently dropped
    assert int(spilled) >= 8
    assert len(_as_dict(merged)) <= 4


def test_merge_bounded_pads_small_inputs_to_cap():
    a = _counts_of([((5, 0), 1)], capacity=2)
    b = _counts_of([((6, 0), 1)], capacity=2)
    merged, spilled = merge_bounded(a, b, cap=16)
    assert merged.counts.shape == (16,)
    assert int(spilled) == 0
    assert _as_dict(merged) == {(5, 0): 1, (6, 0): 1}


# ---------------------------------------------------------------------------
# int32 saturation boundary.
# ---------------------------------------------------------------------------


def test_counts_near_int32_max_survive_exactly():
    big = 2**30
    rest = 2**31 - 1 - big          # big + rest == int32 max
    a = _counts_of([((11, 0), big)], capacity=4)
    b = _counts_of([((11, 0), rest), ((12, 0), -(2**31 - 1))], capacity=4)
    merged = merge_counts(a, b)
    d = _as_dict(merged)
    assert d[(11, 0)] == 2**31 - 1
    assert d[(12, 0)] == -(2**31 - 1)


def test_duplicate_rows_accumulate_near_saturation():
    quarter = 2**29
    c = _counts_of([((2, 3), quarter)] * 3, capacity=4)
    assert _as_dict(c) == {(2, 3): 3 * quarter}


# ---------------------------------------------------------------------------
# Associativity / commutativity (the tree-merge precondition).
# ---------------------------------------------------------------------------


def _random_counts(rng, n_codes=12, capacity=16):
    pairs = []
    for _ in range(rng.integers(0, n_codes)):
        code = (int(rng.integers(0, 5)), int(rng.integers(0, 5)))
        if code == (0, 0):
            continue
        pairs.append((code, int(rng.integers(-6, 7))))
    return _counts_of(pairs, capacity=capacity)


@pytest.mark.parametrize("seed", range(6))
def test_merge_counts_associative_and_commutative(seed):
    rng = np.random.default_rng(seed)
    a, b, c = (_random_counts(rng) for _ in range(3))
    left = merge_counts(merge_counts(a, b), c)
    right = merge_counts(a, merge_counts(b, c))
    flipped = merge_counts(c, merge_counts(b, a))
    assert _as_dict(left) == _as_dict(right) == _as_dict(flipped)


def test_merge_bounded_order_invariant_when_no_spill():
    """Folding parts in any order gives the same table (cap generous)."""
    rng = np.random.default_rng(42)
    parts = [_random_counts(rng) for _ in range(5)]

    def fold(order):
        acc = empty_counts(64, LIMBS)
        for i in order:
            acc, spilled = merge_bounded(acc, parts[i], cap=64)
            assert int(spilled) == 0
        return _as_dict(acc)

    expect = fold(range(5))
    assert fold([4, 2, 0, 3, 1]) == expect
    assert fold([1, 0, 3, 2, 4]) == expect


def test_hypothesis_merge_associativity():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    code = st.tuples(st.integers(0, 6), st.integers(0, 6)).filter(
        lambda c: c != (0, 0))
    table = st.lists(st.tuples(code, st.integers(-50, 50)), max_size=10).map(
        lambda pairs: _counts_of(pairs, capacity=16))

    @hyp.given(a=table, b=table, c=table)
    @hyp.settings(deadline=None)
    def check(a, b, c):
        left = _as_dict(merge_counts(merge_counts(a, b), c))
        right = _as_dict(merge_counts(a, merge_counts(b, c)))
        assert left == right

    check()


# ---------------------------------------------------------------------------
# Front compaction: every live row lies in the first unique_mask.sum() + 1
# rows, so the cross-bucket merge may trim a part to that prefix.
# ---------------------------------------------------------------------------


def _front_compact(c: CodeCounts) -> bool:
    mask = np.asarray(c.unique_mask)
    return not mask[int(mask.sum()) + 1:].any()


def test_empty_counts_are_front_compact():
    assert _front_compact(empty_counts(0, LIMBS))
    assert _front_compact(empty_counts(16, LIMBS))


def test_hypothesis_producers_keep_live_rows_in_front():
    """``count_codes``, ``aggregate_zones`` and ``merge_bounded`` (at caps
    below, at and above the live population) on tables with padding
    rows, zero weights, cancelling pairs and no padding at all."""
    from repro.core.aggregation import aggregate_zones

    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    code = st.tuples(st.integers(0, 3), st.integers(0, 3))
    row = st.tuples(code, st.integers(-3, 3))
    table = st.lists(row, min_size=12, max_size=12).map(
        lambda pairs: _counts_of(pairs, capacity=12))
    zones = st.tuples(
        st.lists(code, min_size=12, max_size=12),
        st.lists(st.integers(0, 2), min_size=12, max_size=12),
        st.lists(st.sampled_from([-1, 0, 1]), min_size=3, max_size=3))

    @hyp.given(a=table, b=table, z=zones, cap=st.sampled_from([4, 8, 30]))
    @hyp.settings(deadline=None)
    def check(a, b, z, cap):
        codes, lengths, signs = z
        zoned = aggregate_zones(
            jnp.asarray(np.asarray(codes, np.int32).reshape(3, 4, LIMBS)),
            jnp.asarray(np.asarray(lengths, np.int32).reshape(3, 4)),
            jnp.asarray(np.asarray(signs, np.int32)))
        merged, _ = merge_bounded(a, b, cap=cap)
        for c in (a, b, zoned, merged):
            assert _front_compact(c)

    check()
