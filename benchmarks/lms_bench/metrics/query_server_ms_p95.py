"""95th percentile (nearest rank) of the server's whole ``POST /query/v2``
handling (the program's ``lms.http.post`` spans: body read, parse,
dispatch, JSON encode and send) that started in the traced window."""

from benchmarks.lms_bench import bench, progtrace


def read(ctx):
    ev = progtrace.for_ctx(ctx)
    if not ev:
        return None
    ms = [(e - s) * 1e-6 for _, s, e, _, meta in
          progtrace.spans_in_window(ev, "lms.http.post")
          if meta.get("path") == "/query/v2"]
    return bench.percentile(ms, 95) if ms else None
