"""The comparison that decides ``correct``.

Every answer due in the window (a count table: final code -> processes) is
compared with the plain reference's table of the same input, code by code.
The numbers compared, each against its limit:

* ``count_l1_max``: the largest, over the answers, of the summed absolute
  count differences; exact counts give 0, and the limit is 0;
* ``answers_checked``: how many answers were compared; at least 1.
"""

from __future__ import annotations

#: name -> (relation, limit)
LIMITS = {"count_l1_max": ("<=", 0), "answers_checked": (">=", 1)}


def l1(got: dict, want: dict) -> int:
    if got == want:
        return 0
    return sum(abs(got.get(k, 0) - want.get(k, 0))
               for k in set(got) | set(want))


def compare(answers, want: dict) -> tuple[dict, int]:
    """``{name: [value, relation, limit]}`` for each number compared, and
    how many answers differ from ``want``."""
    gaps = [l1(a, want) for a in answers]
    values = {"count_l1_max": max(gaps, default=0),
              "answers_checked": len(answers)}
    numbers = {k: [values[k], rel, lim] for k, (rel, lim) in LIMITS.items()}
    return numbers, sum(1 for g in gaps if g)


def passed(numbers: dict) -> bool:
    ok = {"<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b}
    return all(ok[rel](v, lim) for v, rel, lim in numbers.values())
