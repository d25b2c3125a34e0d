"""Host time per step spent in ``MetricsRouter.write`` (router, TSDB,
rollups) on the training thread: the ``ingest:`` host spans."""


def read(ctx):
    spans = ctx.get("spans")
    if spans is None or not ctx.get("steps"):
        return None
    s = spans.thread_total_s(ctx["train_thread"], "ingest:")
    return s / ctx["steps"] * 1e3
