"""The horizon-bounded Phase-1 scan (``core/expansion.scan_zones``).

The scan offers lane ``i`` the edge ``i + d`` of its row at trip ``d`` and
stops after ``1 + max(h_i - i)`` trips, where ``h_i`` is the last slot that
can still lie within ``l_max * delta`` of lane ``i``'s seed.  Every output
must equal the NumPy oracle's walk (``core/scan_numpy``), which sees every
slot, on rows that stress the bound: pad slots, empty rows, rows whose
whole span lies inside one horizon, ties, self-loops and unsorted rows.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding, expansion, scan_numpy, tzp

DELTA, L_MAX = 10, 4


def _trips_brute(t, valid, span):
    """``1 + max(h_i - i)`` over valid lanes, straight from the definition:
    ``h_i`` is the last slot ``j`` with some valid edge at or after ``j``
    no later than ``t_i + span``."""
    best = 0
    for row_t, row_valid in zip(np.asarray(t, np.int64),
                                np.asarray(valid, bool)):
        for i in np.flatnonzero(row_valid):
            h = max(j for j in range(len(row_t))
                    if row_valid[j:].any()
                    and row_t[j:][row_valid[j:]].min() <= row_t[i] + span)
            best = max(best, h - i + 1)
    return best


def _rows(seed, z=3, e=24, nodes=5, span=200):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, nodes, (z, e)).astype(np.int32)
    v = rng.integers(0, nodes, (z, e)).astype(np.int32)
    t = np.sort(rng.integers(0, span, (z, e)), axis=1).astype(np.int32)
    return u, v, t, np.ones((z, e), bool)


def _sorted_with_pads():
    u, v, t, valid = _rows(0)
    valid[0, 17:] = False           # pad slots close each row
    valid[2, 9:] = False
    return u, v, t, valid


def _all_invalid_row():
    u, v, t, valid = _rows(1)
    valid[1] = False
    return u, v, t, valid


def _bursty():
    # the whole row lies inside one horizon: the scan sweeps every slot
    u, v, t, valid = _rows(2, nodes=3, span=DELTA * L_MAX)
    return u, v, t, valid


def _equal_timestamps():
    u, v, t, valid = _rows(3, nodes=3)
    t[:, 4:12] = t[:, 4:5]          # a run of ties in each row
    t = np.maximum.accumulate(t, axis=1)
    return u, v, t, valid


def _self_loops():
    u, v, t, valid = _rows(4, nodes=4)
    v[:, ::3] = u[:, ::3]
    return u, v, t, valid


def _unsorted():
    u, v, t, valid = _rows(5)
    rng = np.random.default_rng(5)
    t[0] = rng.permutation(t[0])
    t[1, 20] = t[1, 0]              # one early edge near the row's end
    return u, v, t, valid


CASES = {
    "sorted-with-pads": _sorted_with_pads,
    "all-invalid-row": _all_invalid_row,
    "bursty-full-span": _bursty,
    "equal-timestamps": _equal_timestamps,
    "self-loops": _self_loops,
    "unsorted-row": _unsorted,
    "with-ts-derive": _sorted_with_pads,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_horizon_scan_matches_the_numpy_oracle(case):
    u, v, t, valid = CASES[case]()
    with_ts = case == "with-ts-derive"
    got = expansion.scan_zones(u, v, t, valid, delta=DELTA, l_max=L_MAX,
                               with_ts=with_ts)
    want = scan_numpy.scan_zones(u, v, t, valid, delta=DELTA, l_max=L_MAX,
                                 with_ts=with_ts)
    np.testing.assert_array_equal(np.asarray(got.length), want.length)
    np.testing.assert_array_equal(np.asarray(got.code), want.code)
    e = u.shape[1]
    trips = int(got.trips)
    assert trips == _trips_brute(t, valid, DELTA * L_MAX)
    assert want.length.max() > 1    # some candidate really extended
    if case == "bursty-full-span":
        assert trips == e
    elif case != "unsorted-row":
        assert trips < e
    if case == "all-invalid-row":
        # the empty row alone runs no trip and seeds nothing
        alone = expansion.scan_zones(*(x[1:2] for x in (u, v, t, valid)),
                                     delta=DELTA, l_max=L_MAX)
        assert int(alone.trips) == 0
        assert not np.asarray(alone.length).any()
    if with_ts:
        np.testing.assert_array_equal(np.asarray(got.ts), want.ts)
        # a smaller config's counts come from the dominating sweep
        for d, l in [(DELTA // 2, L_MAX), (DELTA, L_MAX - 1), (3, 2)]:
            small = scan_numpy.scan_zones(u, v, t, valid, delta=d, l_max=l)
            lengths = expansion.derive_lengths(got.length, got.ts,
                                               delta=d, l_max=l)
            codes = encoding.truncate_codes(got.code, lengths)
            np.testing.assert_array_equal(np.asarray(lengths), small.length)
            np.testing.assert_array_equal(
                np.asarray(codes)[..., :small.code.shape[-1]], small.code)


@pytest.mark.parametrize("seed", range(6))
def test_jitted_trip_count_is_one_plus_the_widest_horizon(seed):
    rng = np.random.default_rng(seed)
    z, e = int(rng.integers(1, 5)), int(rng.integers(1, 30))
    t = np.sort(rng.integers(-50, 300, (z, e)), axis=1).astype(np.int32)
    if seed % 2:
        t = rng.integers(0, 300, (z, e)).astype(np.int32)   # unsorted
    valid = rng.random((z, e)) < 0.8
    span = int(rng.integers(0, 100))
    want = _trips_brute(t, valid, span)
    assert int(expansion.horizon_trips(jnp.asarray(t), jnp.asarray(valid),
                                       span=span)) == want
    assert tzp.horizon_trips_np(t, valid, span=span) == want


def test_trip_count_saturates_instead_of_wrapping():
    big = np.iinfo(np.int32).max
    t = np.array([[big - 5, big - 3, big, 0]], np.int32)
    valid = np.array([[True, True, True, False]])
    for span in (100, big):
        assert int(expansion.horizon_trips(
            jnp.asarray(t), jnp.asarray(valid), span=span)) == 3
    assert tzp.horizon_trips_np(t, valid, span=100) == 3
