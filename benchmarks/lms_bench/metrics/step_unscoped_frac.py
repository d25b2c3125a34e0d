"""Share of the traced window's device busy time in ops under none of the
step program's named scopes (embed, attention, mlp, norm, logits_loss,
optimizer), each busy instant given to the innermost op covering it."""

from benchmarks.lms_bench import progtrace


def read(ctx):
    return progtrace.scope_frac(ctx, None)
