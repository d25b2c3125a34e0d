#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/lms_bench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process: it sets the cell up (weights, compilation, warm-up), measures
for ``--seconds``, checks what the timed path produced against a plain
reference, and prints one JSON result line as the last line of standard
output (the numbers compared, with their limits, are also the last lines
of standard error).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a run with host spans and a
profiler trace.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse         # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import sys              # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu writes no log files of its own (they would go to a fixed /tmp path)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.lms_bench import bench
    cell = bench.load_cell(args.workload)
    try:
        devices = bench.check_devices(cell.chips)
    except bench.NoDevice as e:
        print(f"lms_bench: {e}", file=sys.stderr)
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of a run, however small, comes from the cache next time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    generator = bench.generator_module(cell.traffic["generator"])
    outcome = generator.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_PROCESS, devices)
    line = bench.result_line(cell, outcome, devices, bool(args.trace))
    bench.print_checks(outcome.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
