"""Device-idle milliseconds per traced step under the training loop's
``train.loop.monitor`` phase (the HostAgent, UserMetric and marker work
between steps), from the program's own spans on the profiler's clock."""

from benchmarks.lms_bench import progtrace


def read(ctx):
    return progtrace.idle_ms_per_step(ctx, ("train.loop.monitor",))
