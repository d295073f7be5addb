"""Device: share of the traced window in which no operation ran on the
chip (bench/trace_reduce.py)."""


def read(ctx):
    return None if ctx.device is None else ctx.device["idle_pct"]
