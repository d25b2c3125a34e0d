"""95th percentile of the dashboard viewers' query latency over every
query due in the window, each timed from when it was due (so a viewer
held up by a slow answer counts the wait)."""

from benchmarks.lms_bench import bench


def read(ctx):
    ms = ctx.get("query_ms")
    if not ms:
        return None
    return bench.percentile(ms, 95)
