"""Executor (core/executor.py ``run_arrays``): milliseconds per mine of the
``mine.bucket_scan`` spans, each a bucket's jitted Phase-1 scan and
in-bucket fold from dispatch to its counts, synced.  None where the
program has no such span."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "mine.bucket_scan"]
    if not spans or not ctx.n_answers:
        return None
    return sum(s.dur_ms for s in spans) / ctx.n_answers
