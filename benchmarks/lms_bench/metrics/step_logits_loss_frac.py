"""Share of the traced window's device busy time in ops of the step
program's ``logits_loss`` named scope (each busy instant given to the
innermost op covering it)."""

from benchmarks.lms_bench import progtrace


def read(ctx):
    return progtrace.scope_frac(ctx, "logits_loss")
