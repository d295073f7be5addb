"""Transition-tree invariants (core/transitions.py).

The tree is a prefix aggregation of final-code counts; its defining
invariants are

  * ``through`` at a node == processes whose code extends-or-equals it;
  * ``evolved == through - stopped`` everywhere;
  * children's ``through`` sum to the parent's ``evolved`` (every evolving
    process takes exactly one next step), so ``transition_rows`` shares sum
    to 1 at every branching node.
"""

from collections import defaultdict

import numpy as np
import pytest

from repro.core import aggregation, encoding, transitions
from conftest import batch_discover, random_graph

KNOWN = {"01": 5, "0101": 3, "0102": 2, "010201": 1}


def _walk(tree):
    stack = [tree.root]
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.children.values())


def test_build_tree_from_final_counts():
    tree = transitions.build_tree(KNOWN)
    total = sum(KNOWN.values())
    assert tree.root.through == total
    n01 = tree.node("01")
    assert n01.through == total          # every code extends "01"
    assert n01.stopped == 5
    assert n01.evolved == 6
    n0101 = tree.node("0101")
    assert (n0101.through, n0101.stopped, n0101.evolved) == (3, 3, 0)
    n0102 = tree.node("0102")
    assert (n0102.through, n0102.stopped, n0102.evolved) == (3, 2, 1)
    n010201 = tree.node("010201")
    assert (n010201.through, n010201.stopped) == (1, 1)
    with pytest.raises(KeyError):
        tree.node("0103")


@pytest.fixture(scope="module")
def mined_tree():
    g = random_graph(7, 900, 10, 3_000)
    res = batch_discover(g, delta=25, l_max=4, omega=3)
    assert res.overflow == 0
    return transitions.build_tree(res.counts), res


def test_evolved_invariant_everywhere(mined_tree):
    tree, _ = mined_tree
    for node in _walk(tree):
        assert node.evolved == node.through - node.stopped
        assert node.evolved >= 0
        assert node.stopped >= 0


def test_children_partition_evolved(mined_tree):
    tree, _ = mined_tree
    for node in _walk(tree):
        child_through = sum(ch.through for ch in node.children.values())
        assert child_through == node.evolved, node.code


def test_transition_rows_shares_sum_to_one(mined_tree):
    tree, _ = mined_tree
    branching = 0
    for node in _walk(tree):
        rows = node.transition_rows()
        assert len(rows) == len(node.children)
        if rows:
            branching += 1
            assert sum(share for _, _, share in rows) == pytest.approx(1.0)
            for code, count, share in rows:
                assert code.startswith(node.code)
                assert len(code) == len(node.code) + 2
                assert count == node.children[code].through
                assert share == pytest.approx(count / node.evolved)
    assert branching > 0                 # the graph actually branched


def test_level_histogram_matches_tree(mined_tree):
    tree, res = mined_tree
    hist = transitions.level_histogram(res.counts)
    assert sum(hist.values()) == tree.root.through == res.total_processes()
    for level, total in hist.items():
        assert total == sum(
            cnt for code, cnt in res.counts.items()
            if len(code) // 2 == level
        )


# -- counts_to_dict against the per-row loop ---------------------------------

def _loop_counts_to_dict(codes, counts, mask=None):
    """The per-row decode loop ``counts_to_dict`` replaced (reference)."""
    out = defaultdict(int)
    if mask is None:
        mask = np.ones(counts.shape, bool)
    for row, cnt in zip(codes[mask], counts[mask]):
        if cnt != 0:
            out[encoding.decode_code_np(row)] += int(cnt)
    return {k: v for k, v in out.items() if v != 0}


def _table(case, seed=0, l_max=6, n=200):
    """(codes, counts, mask) of one named table shape."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, l_max + 1, n)
    strings = ["".join(format(int(x), "x")
                       for x in rng.integers(0, 3, 2 * k)) for k in lengths]
    codes = np.stack([encoding.encode_label_string_np(s, l_max)
                      for s in strings])
    codes = np.unique(codes, axis=0)                 # sorted, unique
    counts = rng.integers(-50, 50, len(codes)).astype(np.int32)
    counts[counts == 0] = 1
    mask = None
    if case == "unsorted":
        order = rng.permutation(len(codes))
        codes, counts = codes[order], counts[order]
    elif case == "cancelling_duplicates":
        codes = np.concatenate([codes, codes[::3], codes[1::3]])
        counts = np.concatenate([counts, -counts[::3], 2 * counts[1::3]])
    elif case == "partial_mask":
        mask = rng.random(len(codes)) < 0.5
    elif case == "zero_counts":
        counts[::4] = 0
        mask = np.ones(len(codes), bool)
    elif case == "empty":
        codes, counts = codes[:0], counts[:0]
        mask = np.zeros(0, bool)
    return codes, counts, mask


@pytest.mark.parametrize("case", ["sorted_unique", "unsorted",
                                  "cancelling_duplicates", "partial_mask",
                                  "zero_counts", "empty"])
def test_counts_to_dict_equals_the_row_loop(case):
    codes, counts, mask = _table(case)
    got = transitions.counts_to_dict(codes, counts, mask)
    expect = _loop_counts_to_dict(codes, counts, mask)
    assert got == expect
    assert all(type(k) is str for k in got)
    assert all(type(v) is int for v in got.values())
    assert 0 not in got.values()
    if case == "cancelling_duplicates":
        assert len(got) < len(np.unique(codes, axis=0))


def test_device_counts_to_dict_of_a_mined_table(mined_tree):
    _, res = mined_tree
    codes = np.stack([encoding.encode_label_string_np(s, 4)
                      for s in sorted(res.counts)])
    counts = np.asarray([res.counts[s] for s in sorted(res.counts)],
                        np.int32)
    table = aggregation.count_codes(codes, counts)
    got = transitions.device_counts_to_dict(table)
    assert got == res.counts
    assert got == _loop_counts_to_dict(np.asarray(table.codes),
                                       np.asarray(table.counts),
                                       np.asarray(table.unique_mask))
