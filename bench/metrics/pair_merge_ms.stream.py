"""Streaming finalization (core/streaming.py ``_finalize_pair``):
milliseconds of ``stream.pair_merge`` spans, which copy each finalized
pair's counts to the host, decode them and merge them into the running
totals, per ingest call."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "stream.pair_merge"]
    if not spans or not ctx.n_calls:
        return None
    return sum(s.dur_ms for s in spans) / ctx.n_calls
