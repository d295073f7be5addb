"""Streaming finalization (core/streaming.py): milliseconds of
``stream.finalize`` spans per ingested chunk."""


def read(ctx):
    if not ctx.n_calls:
        return None
    return sum(s.dur_ms for s in ctx.spans
               if s.name == "stream.finalize") / ctx.n_calls
