"""Result decode (core/api.py ``counts_to_result``, core/transitions.py):
milliseconds per mine of the ``engine.decode`` span, which renders the
host copy of the count table into the result's dict of code strings."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "engine.decode"]
    if not spans or not ctx.n_answers:
        return None
    return sum(s.dur_ms for s in spans) / ctx.n_answers
