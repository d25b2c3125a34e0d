"""Device-idle milliseconds per traced step under the training loop's
``train.loop.data``, ``train.loop.h2d`` and ``train.loop.dispatch``
phases (the next batch, its copy to the device and the step's dispatch),
from the program's own spans on the profiler's clock."""

from benchmarks.lms_bench import progtrace


def read(ctx):
    return progtrace.idle_ms_per_step(
        ctx, ("train.loop.data", "train.loop.h2d", "train.loop.dispatch"))
