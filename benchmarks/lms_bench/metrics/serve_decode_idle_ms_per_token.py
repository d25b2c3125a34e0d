"""Device-idle milliseconds between consecutive runs of the decode
program within a batch, per traced decode step: the host's per-token loop
(the argmax's trip to the host, the row loop, the next dispatch), from the
profiler trace."""

from benchmarks.lms_bench import servetrace


def read(ctx):
    st = ctx.get("serve_trace")
    r = st and servetrace.idle_between_decodes(st)
    if not r:
        return None
    idle_ns, steps = r
    return idle_ns * 1e-6 / steps
