"""Executor (core/executor.py): rows the cross-bucket merge sorted per mine
(``EngineStats.merge_rows`` over ``discover_calls``): each merge step's
carry cap plus the width of the part it took, over every spill attempt.
None where the program has no such counter."""


def read(ctx):
    mines = ctx.stats.get("discover_calls", 0)
    rows = ctx.stats.get("merge_rows")
    if not mines or rows is None:
        return None
    return rows / mines
