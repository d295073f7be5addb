"""Result decode (core/api.py ``counts_to_result``, core/transitions.py):
milliseconds per mine between the end of the ``engine.discover`` span and
the return of ``discover``, which render the device count table into the
result's dict of code strings."""


def read(ctx):
    discovers = [s for s in ctx.spans if s.name == "engine.discover"]
    if not discovers or len(discovers) != ctx.n_calls:
        return None
    return (sum(ctx.call_ms) - sum(s.dur_ms for s in discovers)) / ctx.n_calls
