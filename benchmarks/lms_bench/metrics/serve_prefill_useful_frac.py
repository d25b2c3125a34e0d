"""Real prompt tokens over the tokens prefill computed (batch rows times
the batch's padded prompt length), over the window's batches."""


def read(ctx):
    batches = ctx.get("serve_batches")
    if not batches:
        return None
    real = sum(len(r.prompt) for b in batches for r in b.requests)
    computed = sum(ctx["max_batch"] * b.plen for b in batches)
    return real / computed
