"""Share of the traced window in which no operation ran on the device
(union of the device's op intervals, from the profiler trace)."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or "steps" not in ctx:
        return None
    return 1.0 - trace["busy_share"]
