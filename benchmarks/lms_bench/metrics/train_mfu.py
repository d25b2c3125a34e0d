"""Model FLOP utilization of the training window: the step's model
operations (``flops/<family>.py``, from the configuration's widths) times
the steps completed, over the window and the chips' bf16 peak."""

from benchmarks.lms_bench import bench


def read(ctx):
    if "steps" not in ctx or "batch" not in ctx:
        return None
    conf = ctx["config"]
    per_step = bench.flops_module(conf["flops"]).train_step_flops(
        conf, ctx["batch"], ctx["seq_len"])
    peak = bench.peaks(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return per_step * ctx["steps"] / ctx["window_s"] / peak
