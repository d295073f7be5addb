"""Streaming finalization (core/streaming.py ``_finalize_pair``):
milliseconds of ``stream.pair_mine`` spans, the executor's run of each
finalized pair (h2d, the scan launch and its fold, synced), per ingest
call."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "stream.pair_mine"]
    if not spans or not ctx.n_calls:
        return None
    return sum(s.dur_ms for s in spans) / ctx.n_calls
