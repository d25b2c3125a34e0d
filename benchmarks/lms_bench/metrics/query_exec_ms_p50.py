"""Median server-side time of ``QueryEngine.query`` over the window's
dashboard queries (the ``query:`` host spans, any thread)."""

import statistics


def read(ctx):
    spans = ctx.get("spans")
    if spans is None:
        return None
    d = spans.durations.get("query:engine.query")
    if not d:
        return None
    return statistics.median(d) * 1e3
