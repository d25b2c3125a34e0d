"""Model FLOP utilization of the serving window: the useful operations of
the window's requests (each prompt once and one step per delivered token
after the first, at the request's own positions; ``flops/<family>.py``)
over the window and the chips' bf16 peak.  Padding, the decode steps of
finished rows and expert capacity the dispatch fills with nothing do not
count."""

from benchmarks.lms_bench import bench


def read(ctx):
    batches = ctx.get("serve_batches")
    if not batches:
        return None
    conf = ctx["config"]
    fl = bench.flops_module(conf["flops"])
    useful = sum(fl.request_flops(conf, len(r.prompt), len(r.output))
                 for b in batches for r in b.requests)
    peak = bench.peaks(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return useful / ctx["window_s"] / peak
