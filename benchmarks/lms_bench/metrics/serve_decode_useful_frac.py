"""Tokens delivered by decode steps over the row-steps decode ran (batch
rows times the batch's decode steps, which run until its longest answer
is done), over the window's batches."""


def read(ctx):
    batches = ctx.get("serve_batches")
    if not batches:
        return None
    delivered = sum(len(r.output) - 1 for b in batches for r in b.requests)
    steps = sum(ctx["max_batch"] * (max(r.max_new_tokens
                                        for r in b.requests) - 1)
                for b in batches)
    return delivered / steps
