"""Executor dispatch: scan launches per mine (``EngineStats.launches``)."""


def read(ctx):
    mines = ctx.stats.get("discover_calls", 0)
    if not mines:
        return None
    return ctx.stats["launches"] / mines
