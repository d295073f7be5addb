"""Zone planning and layout (core/tzp.py via core/engine.py): milliseconds
of ``engine.plan`` and ``engine.layout`` spans per mine in the window."""


def read(ctx):
    if not ctx.n_answers:
        return None
    ms = sum(s.dur_ms for s in ctx.spans
             if s.name in ("engine.plan", "engine.layout"))
    return ms / ctx.n_answers
