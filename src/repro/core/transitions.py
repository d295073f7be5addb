"""Motif-transition statistics and the transition tree (reporting layer).

Final-code counts are sufficient statistics for the whole discovery problem:
a process that stopped at code ``c`` passed through every even-length prefix
of ``c``, so per-level transition counts (Fig. 6 / Table 6 of the paper) are
prefix aggregations.  This module is host-side numpy — it renders results,
the device pipeline never depends on it.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

import jax
import numpy as np

from . import encoding


@dataclasses.dataclass
class TransitionNode:
    """One motif type in the transition tree."""

    code: str                     # paper-style label string, e.g. "0101"
    stopped: int = 0              # processes that ended here
    through: int = 0              # processes that reached here (>= stopped)
    children: dict = dataclasses.field(default_factory=dict)

    @property
    def evolved(self) -> int:
        return self.through - self.stopped

    def transition_rows(self):
        """Rows like Table 6: (child code, count, share of evolved)."""
        total = sum(ch.through for ch in self.children.values())
        rows = []
        for code in sorted(self.children):
            ch = self.children[code]
            share = ch.through / total if total else 0.0
            rows.append((code, ch.through, share))
        return rows


class TransitionTree:
    """Trie over motif codes with stopped/through counts."""

    def __init__(self):
        self.root = TransitionNode(code="")

    def add(self, code: str, count: int):
        node = self.root
        node.through += count
        for level in range(2, len(code) + 1, 2):
            prefix = code[:level]
            if prefix not in node.children:
                node.children[prefix] = TransitionNode(code=prefix)
            node = node.children[prefix]
            node.through += count
        node.stopped += count

    def node(self, code: str) -> TransitionNode:
        node = self.root
        for level in range(2, len(code) + 1, 2):
            node = node.children[code[:level]]
        return node

    def render(self, code: str = "", max_depth: int = 2) -> str:
        """ASCII rendering of the transition tree (Fig. 6 analog)."""
        start = self.node(code) if code else self.root
        lines = []

        def walk(node, depth):
            if depth > max_depth:
                return
            for child_code, count, share in node.transition_rows():
                lines.append(
                    f"{'  ' * depth}{child_code}: {count} ({share:.1%})"
                )
                walk(node.children[child_code], depth + 1)

        walk(start, 0)
        return "\n".join(lines)


def counts_to_dict(codes: np.ndarray, counts: np.ndarray,
                   mask: np.ndarray | None = None) -> dict[str, int]:
    """Device count arrays -> {code string: count}, dropping zeros.

    Rows where ``mask`` holds and the count is non-zero are decoded in one
    vector pass; counts of equal strings are summed and zero totals
    dropped.  A device table is sorted and unique, so its labels are
    strictly increasing and go straight into the dict; any other table is
    folded first.
    """
    counts = np.asarray(counts)
    live = counts != 0
    if mask is not None:
        live &= np.asarray(mask, bool)
    labels = encoding.label_bytes_np(np.asarray(codes)[live])
    counts = counts[live].astype(np.int64)
    if labels.size > 1 and not np.all(labels[1:] > labels[:-1]):
        labels, inverse = np.unique(labels, return_inverse=True)
        totals = np.zeros(labels.size, np.int64)
        np.add.at(totals, inverse, counts)
        labels, counts = labels[totals != 0], totals[totals != 0]
    return dict(zip(encoding.label_strings_np(labels), counts.tolist()))


def device_counts_to_dict(counts) -> dict[str, int]:
    """:class:`~repro.core.aggregation.CodeCounts` -> {code string: count}.

    One ``jax.device_get`` copies the three arrays to the host together.
    """
    host = jax.device_get(counts)
    return counts_to_dict(host.codes, host.counts, host.unique_mask)


def build_tree(final_counts: dict[str, int]) -> TransitionTree:
    tree = TransitionTree()
    for code, count in final_counts.items():
        tree.add(code, count)
    return tree


def level_histogram(final_counts: dict[str, int]) -> dict[int, int]:
    """Processes per final length (1..l_max)."""
    hist: dict[int, int] = defaultdict(int)
    for code, count in final_counts.items():
        hist[len(code) // 2] += count
    return dict(hist)
