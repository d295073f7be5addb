"""Streaming finalization: scan launches of the stream's miners (pair
finalizations and tail mines, ``EngineStats.stream_launches``) per ingest
call."""


def read(ctx):
    launches = ctx.stats.get("stream_launches")
    if launches is None or not ctx.n_calls:
        return None
    return launches / ctx.n_calls
