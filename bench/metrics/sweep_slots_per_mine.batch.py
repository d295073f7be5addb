"""Executor (core/executor.py): candidate-steps the launched Phase-1 scans
visit per mine (``EngineStats.sweep_slots`` over ``discover_calls``),
counted on the host from shapes.  None where the program has no such
counter."""


def read(ctx):
    mines = ctx.stats.get("discover_calls", 0)
    slots = ctx.stats.get("sweep_slots")
    if not mines or slots is None:
        return None
    return slots / mines
