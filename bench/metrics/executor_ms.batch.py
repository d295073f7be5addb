"""Executor (core/executor.py: h2d, scan launches, Phase-2 fold, d2h,
spill retries, decode): self time of ``engine.discover`` per mine, its
duration less its ``engine.plan`` and ``engine.layout`` children."""


def read(ctx):
    discovers = [s for s in ctx.spans if s.name == "engine.discover"]
    if not discovers:
        return None
    ms = sum(ctx.self_ms(s, ("engine.plan", "engine.layout"))
             for s in discovers)
    return ms / len(discovers)
