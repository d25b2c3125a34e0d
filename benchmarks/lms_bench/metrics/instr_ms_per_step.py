"""Host time per step in the job's instrumentation (HostAgent, UserMetric,
marker flushes) on the training thread, excluding the router writes they
make: the self time of the ``instr:`` host spans."""


def read(ctx):
    spans = ctx.get("spans")
    if spans is None or not ctx.get("steps"):
        return None
    s = spans.thread_self_s(ctx["train_thread"], "instr:")
    return s / ctx["steps"] * 1e3
