"""Training driver: ``python -m repro.launch.train --arch lms-demo ...``.

Runs a *monitored* training job on whatever devices this process has (the
CPU demo path trains lms-demo for a few hundred steps; on a TPU pod slice
the same driver runs per-host under the production mesh).  Features wired
here: elastic mesh construction, LMS stack (+optional HTTP endpoint for
out-of-process collectors), checkpoint auto-resume, failure injection, and
the XLA latency-hiding-scheduler flags for compute/comm overlap on TPU.
"""

from __future__ import annotations

import argparse
import os
import sys


# Compute/comm overlap: these XLA flags enable the latency-hiding scheduler
# on TPU (no-ops on the CPU demo).  Set before jax initializes.
TPU_PERF_FLAGS = (
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_overlap_compute_collective_tc=true"
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro-train")
    ap.add_argument("--arch", default="lms-demo")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--remat", default="none",
                    choices=["none", "minimal", "full"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8", "bf16"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--tp", type=int, default=0,
                    help="model-parallel axis size (0 = auto)")
    ap.add_argument("--lms-out", default="lms_out")
    ap.add_argument("--lms-http", action="store_true",
                    help="serve the router's HTTP endpoint")
    ap.add_argument("--no-monitor", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a failure (restart-path testing)")
    ap.add_argument("--user", default=os.environ.get("USER", "user"))
    ap.add_argument("--overlap-flags", action="store_true",
                    help="append TPU latency-hiding XLA flags")
    args = ap.parse_args(argv)

    if args.overlap_flags:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
            + TPU_PERF_FLAGS

    import jax

    from repro.configs import ShapeConfig, TrainConfig, get_config
    from repro.core import MonitoringStack
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_for
    from repro.launch.steps import build_train_bundle, make_pc
    from repro.parallel.sharding import rules_for
    from repro.train.loop import train

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeConfig("cli", seq_len=args.seq_len,
                        global_batch=args.global_batch, kind="train")
    tcfg = TrainConfig(
        learning_rate=args.lr, total_steps=args.steps,
        warmup_steps=max(1, args.steps // 20),
        optimizer=args.optimizer, num_microbatches=args.microbatches,
        remat_policy=args.remat, grad_compression=args.grad_compression,
        ckpt_dir=args.ckpt_dir, ckpt_interval=args.ckpt_interval,
        monitor=not args.no_monitor)

    ndev = len(jax.devices())
    mesh = pc = in_shardings = None
    if ndev > 1:
        mesh = make_mesh_for(ndev, model=args.tp)
        rules = rules_for("train")
        if args.grad_compression != "none" and "pod" in mesh.axis_names:
            rules = rules.with_overrides(batch=("data",))
        pc = make_pc(rules, mesh)
        in_shardings = build_train_bundle(cfg, shape, tcfg, mesh,
                                          rules).in_shardings
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    stack = MonitoringStack.inprocess(out_dir=args.lms_out,
                                      serve_http=args.lms_http)
    if args.lms_http:
        print(f"LMS HTTP endpoint: {stack.http.url}")

    losses = []

    def cb(step, metrics):
        losses.append(float(metrics["loss"]))
        if step % 10 == 0 or step == 1:
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"grad {float(metrics['grad_norm']):.3f}", flush=True)

    result = train(cfg, tcfg, shape, stack=stack, pc=pc, mesh=mesh,
                   in_shardings=in_shardings,
                   fail_at_step=args.fail_at_step, step_callback=cb,
                   user=args.user)
    print(f"done: steps={result.steps_run} final_loss={result.last_loss:.4f}"
          f" resumed_from={result.resumed_from}")
    for f in result.findings:
        print(f"finding: {f.rule} on {f.host} ({f.duration_s:.0f}s)")

    # end-of-job dashboard (paper Fig. 2/3 artifacts)
    jobs = stack.router.jobs.all_jobs()
    if jobs:
        p = stack.dashboards.write_dashboard(jobs[-1])
        stack.dashboards.write_admin_view(jobs)
        print(f"dashboard: {p}")
    stack.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
