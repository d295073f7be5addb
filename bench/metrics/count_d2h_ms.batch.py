"""Result decode (core/engine.py): milliseconds per mine of the
``engine.d2h`` span, the copy of the count table's codes, counts and mask
from the device to the host after ``engine.discover`` has closed."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "engine.d2h"]
    if not spans or not ctx.n_answers:
        return None
    return sum(s.dur_ms for s in spans) / ctx.n_answers
