"""95th percentile (nearest rank) of the host-clock gaps between
consecutive calls of the engine's decode step within a batch, over every
batch of the window: the inter-token latency the batch's clients see.
Stamped in every run; reported from the traced run's line."""

from benchmarks.lms_bench import bench


def read(ctx):
    gaps = ctx.get("decode_gaps_s")
    if not gaps:
        return None
    return bench.percentile(gaps, 95) * 1e3
