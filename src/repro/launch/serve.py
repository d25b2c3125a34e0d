"""Serving driver: ``python -m repro.launch.serve --arch lms-demo --smoke``.

Loads (or random-inits) weights, starts a monitored ServingEngine, runs a
synthetic request workload, and writes the job dashboard.  On a pod slice
this driver is launched per-host with the serve rule table (TP-sharded
bf16 weights); the CPU demo path serves the reduced config.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro-serve")
    ap.add_argument("--arch", default="lms-demo")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="",
                    help="restore weights from a training checkpoint")
    ap.add_argument("--lms-out", default="lms_out")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.core import MonitoringStack
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.transformer import init_model_params
    from repro.serve.engine import ServingEngine

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_model_params(cfg, seed=0)
    if args.ckpt_dir:
        from repro.ckpt import load_checkpoint
        step, out = load_checkpoint(args.ckpt_dir, {"params": params})
        params = out["params"]
        print(f"restored weights from step {step}")

    stack = MonitoringStack.inprocess(out_dir=args.lms_out)
    rng = np.random.default_rng(0)
    with stack.job(f"serve-{cfg.name}", user="server",
                   hosts=["host0"], tags={"arch": cfg.name}) as job:
        um = stack.usermetric(host="host0")
        eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                            max_len=args.max_len, usermetric=um)
        for _ in range(args.requests):
            plen = int(rng.integers(4, 17))
            eng.submit(rng.integers(1, cfg.vocab_size, plen),
                       max_new_tokens=args.max_new_tokens)
        done = eng.run_until_empty()
        um.flush()

    lat = [r.finished_at - r.submitted_at for r in done]
    ttft = [r.first_token_at - r.submitted_at for r in done]
    print(f"served {len(done)} requests | "
          f"ttft p50 {np.percentile(ttft, 50) * 1e3:.1f}ms | "
          f"latency p50 {np.percentile(lat, 50) * 1e3:.1f}ms "
          f"p99 {np.percentile(lat, 99) * 1e3:.1f}ms")
    p = stack.dashboards.write_dashboard(job)
    print(f"dashboard: {p}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
