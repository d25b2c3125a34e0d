#!/usr/bin/env python3
"""Readings that a cell's limits are set from.

    python3 benchmarks/lms_bench/calibrate.py --workload <cell> \\
        --seeds 11,12,13 [--out readings.jsonl]

For each seed, in one process, the cell's generator (``calibrate_seed``)
reads the numbers a run compares, for the program and for the control
(the reference put in the program's place in a lower precision:
per-tensor scaled float8 operands for every matmul), and for the faults
it plants.  The benchmark's own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control and the faults on the first N "
                         "seeds only (default: all)")
    args = ap.parse_args(argv)

    from benchmarks.lms_bench import bench
    cell = bench.load_cell(args.workload)
    try:
        devices = bench.check_devices(cell.chips)
    except bench.NoDevice as e:
        print(f"lms_bench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.core import MonitoringStack
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    generator = bench.generator_module(cell.traffic["generator"])
    sink = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        control = args.control_seeds is None or i < args.control_seeds
        stack = MonitoringStack.inprocess(
            out_dir=str(bench.OUT_DIR / "calibrate" / "lms"))
        try:
            r = generator.calibrate_seed(cell, seed, stack, control,
                                         devices=devices)
        finally:
            stack.close()
        r["elapsed_s"] = time.monotonic() - T_PROCESS
        print(json.dumps(r), flush=True)
        if sink:
            sink.write(json.dumps(r) + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
