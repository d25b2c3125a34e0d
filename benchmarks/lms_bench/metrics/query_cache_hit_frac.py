"""Share of the query engine's queries (the program's ``lms.query.exec``
spans that started in the traced window) answered from its result
cache (``cache=hit``)."""

from benchmarks.lms_bench import progtrace


def read(ctx):
    ev = progtrace.for_ctx(ctx)
    if not ev:
        return None
    spans = progtrace.spans_in_window(ev, "lms.query.exec")
    if not spans:
        return None
    return sum(sp[4].get("cache") == "hit" for sp in spans) / len(spans)
