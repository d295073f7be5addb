"""Phase-3 deterministic relabeling encoding: host + device agreement."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import encoding


def test_limb_count():
    assert encoding.n_limbs(6) == 2
    assert encoding.n_limbs(3) == 1
    assert encoding.n_limbs(7) == 2
    assert encoding.n_limbs(12) == 4
    with pytest.raises(ValueError):
        encoding.n_limbs(15)


def test_roundtrip_simple():
    code = encoding.encode_label_string_np("010212", l_max=6)
    assert encoding.decode_code_np(code) == "010212"
    assert encoding.code_length_np(code) == 3


def test_paper_example_triangle():
    # Fig 2: (A,B),(B,C),(A,C) -> 010212? labels: A=0,B=1 then B=1,C=2 then
    # A=0,C=2 -> digits 01|12|02 -> "011202"... the motif string per paper's
    # scheme: first-occurrence relabeling concatenated in temporal order.
    code = encoding.encode_process_np([(7, 9), (9, 4), (7, 4)], l_max=3)
    assert encoding.decode_code_np(code) == "011202"


def test_prefix_property_sorts_together():
    parent = encoding.encode_label_string_np("0101", l_max=6)
    child = encoding.encode_label_string_np("010121", l_max=6)
    other = encoding.encode_label_string_np("0102", l_max=6)
    # parent < child < other in limb-lexicographic order
    assert tuple(parent) < tuple(child) < tuple(other)


def test_prefix_truncation():
    code = encoding.encode_label_string_np("010212", l_max=6)
    p2 = encoding.prefix_code_np(code, 2)
    assert encoding.decode_code_np(p2) == "0102"
    p1 = encoding.prefix_code_np(code, 1)
    assert encoding.decode_code_np(p1) == "01"
    p0 = encoding.prefix_code_np(code, 0)
    assert encoding.decode_code_np(p0) == ""


def test_append_digit_matches_host():
    l_max = 6
    code = encoding.empty_code((1,), l_max)
    digits = [1, 2, 2, 3, 1, 3]
    for pos, d in enumerate(digits):
        code = encoding.append_digit(
            code, jnp.full((1,), pos, jnp.int32), jnp.full((1,), d, jnp.int32)
        )
    host = encoding.encode_digits_np(digits, l_max)
    np.testing.assert_array_equal(np.asarray(code)[0], host)


@settings(deadline=None, max_examples=50)
@given(
    st.lists(st.tuples(st.integers(0, 9), st.integers(0, 9)),
             min_size=1, max_size=6)
)
def test_encode_process_injective_on_label_sequence(edges):
    """Processes with different label sequences get different codes."""
    l_max = 6
    code = encoding.encode_process_np(edges, l_max)
    s = encoding.decode_code_np(code)
    assert len(s) == 2 * len(edges)
    # decoding is the exact label sequence
    labels: dict[int, int] = {}
    expect = []
    for u, v in edges:
        for node in (u, v):
            labels.setdefault(node, len(labels))
        expect.append(format(labels[u], "x"))
        expect.append(format(labels[v], "x"))
    assert s == "".join(expect)


@settings(deadline=None, max_examples=30)
@given(st.integers(1, 14), st.data())
def test_roundtrip_random(l_max, data):
    n_digits = data.draw(st.integers(1, 2 * l_max))
    digits = data.draw(
        st.lists(st.integers(1, min(15, l_max + 1)),
                 min_size=n_digits, max_size=n_digits)
    )
    code = encoding.encode_digits_np(digits, l_max)
    assert [int(c, 16) + 1 for c in encoding.decode_code_np(code)] == digits


# -- vector decode -----------------------------------------------------------

_INT32 = st.integers(-2**31, 2**31 - 1)


def _pack(digits):
    """7 digits (each 0..15) -> one int32 limb, as the device packs them."""
    limb = 0
    for d in digits:
        limb = (limb << encoding.DIGIT_BITS) | d
    return limb


def _limb_strategy():
    """Limbs whose digits are mostly label digits, with zeros inside and
    0xF digits; plus raw int32 limbs (negative ones included)."""
    digit = st.one_of(st.integers(1, 15), st.just(0), st.just(15))
    packed = st.lists(digit, min_size=encoding.DIGITS_PER_LIMB,
                      max_size=encoding.DIGITS_PER_LIMB).map(_pack)
    return st.one_of(packed, st.just(0), _INT32)


@pytest.mark.parametrize("l_max", [1, 3, 6, 7, 12, 14])
@given(data=st.data())
def test_decode_codes_np_equals_the_row_decode(l_max, data):
    limbs = encoding.n_limbs(l_max)
    rows = data.draw(st.lists(
        st.one_of(st.just([0] * limbs),
                  st.lists(_limb_strategy(), min_size=limbs,
                           max_size=limbs)),
        max_size=12))
    codes = np.asarray(rows, np.int32).reshape(len(rows), limbs)
    got = encoding.decode_codes_np(codes)
    assert got == [encoding.decode_code_np(r) for r in codes]
    assert all(type(s) is str for s in got)


@pytest.mark.parametrize("l_max", [1, 3, 6, 7, 12, 14])
def test_decode_codes_np_named_cases(l_max):
    limbs = encoding.n_limbs(l_max)
    valid = encoding.encode_label_string_np("0" + "e" * (2 * l_max - 1),
                                            l_max)
    interior_zero = np.zeros(limbs, np.int32)
    interior_zero[0] = _pack([1, 0, 2, 0, 0, 15, 3])
    negative = np.full(limbs, -1, np.int32)      # every digit 0xF
    negative[-1] = -2**31 + 0xF                  # six zeros, then 0xF
    codes = np.stack([np.zeros(limbs, np.int32), valid, interior_zero,
                      negative])
    expect = [encoding.decode_code_np(r) for r in codes]
    assert expect[0] == "" and expect[2] == "01e2"
    assert "e" in expect[1] and "e" in expect[3]
    assert encoding.decode_codes_np(codes) == expect
    assert encoding.decode_codes_np(np.zeros((0, limbs), np.int32)) == []
