"""Streaming finalization (core/streaming.py ``_finalize_pair``):
milliseconds of ``stream.pair_layout`` spans, which build each finalized
pair's zone layout, per ingest call."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "stream.pair_layout"]
    if not spans or not ctx.n_calls:
        return None
    return sum(s.dur_ms for s in spans) / ctx.n_calls
