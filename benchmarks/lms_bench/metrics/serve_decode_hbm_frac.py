"""Share of the chip's HBM bandwidth that the decode program reaches: the
bytes a traced decode step must read (``flops/<family>.py``'s
``decode_step_bytes`` at each traced step's batch and position, averaged)
over the decode program's device busy time per run (ops inside its runs on
the trace's "XLA Modules" line), over ``peaks.json``'s bandwidth."""

from benchmarks.lms_bench import bench, servetrace


def read(ctx):
    st = ctx.get("serve_trace")
    r = st and servetrace.decode_busy(st)
    if not r:
        return None
    busy_ns, runs = r
    conf = ctx["config"]
    fl = bench.flops_module(conf["flops"])
    steps = [fl.decode_step_bytes(conf, ctx["max_batch"], b.plen + i)
             for b in ctx["serve_batches"][ctx["trace_from"]:]
             for i in range(max(q.max_new_tokens for q in b.requests) - 1)]
    bw = bench.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return sum(steps) / len(steps) / (busy_ns * 1e-9 / runs) / bw
