"""Share of the traced window's device-idle time that no ``train.loop.*``
phase of the training loop covers."""

from benchmarks.lms_bench import progtrace


def read(ctx):
    ev = progtrace.for_ctx(ctx)
    r = ev and progtrace.idle_by_phase(ev)
    if not r:
        return None
    by_phase = r[0]
    total = sum(by_phase.values())
    return by_phase[None] / total if total > 0 else 0.0
