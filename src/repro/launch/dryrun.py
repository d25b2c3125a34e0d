import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init); everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. assembles abstract params / optimizer state / caches / inputs with
     their NamedShardings from the logical-axis rule table,
  3. ``jax.jit(step).lower(...).compile()`` — any sharding mismatch, OOM-at-
     compile or unsupported collective fails the cell (a bug in our system),
  4. records ``memory_analysis()``, ``cost_analysis()``, and the HLO-walker
     costs (trip-count-corrected FLOPs, bytes, collective bytes) plus the
     three-term roofline into ``results/dryrun/<mesh>/<arch>__<shape>.json``.

Usage::

    python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --skip-existing
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import (ASSIGNED_ARCHS, SHAPES, TrainConfig, get_config,
                           supports_shape)
from repro.core.analysis import RooflineAnalyzer
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_bundle, lower_bundle


def default_train_cfg(cfg, shape=None, dp: int = 16) -> TrainConfig:
    """Production defaults by model size (DESIGN.md §6): microbatch count +
    remat policy chosen so saved activations fit v5e HBM alongside the
    (FSDP-sharded) optimizer state; giants drop to factored Adafactor
    without first moment.  ``nm`` is capped so every microbatch still spans
    the full DP axis (global_batch / nm >= dp) — smaller microbatches make
    GSPMD silently replicate compute across the surplus DP shards."""
    n = cfg.param_count()
    if n > 100e9:
        tc = TrainConfig(optimizer="adafactor", beta1=0.0,
                         num_microbatches=32, remat_policy="minimal")
    elif n > 5e9:
        tc = TrainConfig(optimizer="adamw", num_microbatches=16,
                         remat_policy="minimal")
    else:
        tc = TrainConfig(optimizer="adamw", num_microbatches=1,
                         remat_policy="minimal")
    if shape is not None:
        max_nm = max(1, shape.global_batch // max(dp, 1))
        while tc.num_microbatches > max_nm or \
                shape.global_batch % tc.num_microbatches:
            tc.num_microbatches //= 2
        tc.num_microbatches = max(1, tc.num_microbatches)
    return tc


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # one new token per row


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             skip_existing: bool = False, save_hlo: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    path = os.path.join(out_dir, mesh_name, f"{arch}__{shape_name}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "kind": shape.kind, "status": "ok", "time_s": None}

    if not supports_shape(cfg, shape):
        record["status"] = "skipped"
        record["reason"] = ("full-attention arch at 524288-token decode is "
                            "not deployable (O(S^2)); see DESIGN.md §5")
        _write(path, record)
        return record

    t0 = time.monotonic()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        dp = chips // mesh.devices.shape[-1]          # pod x data
        bundle = build_bundle(cfg, shape, mesh,
                              train_cfg=default_train_cfg(cfg, shape, dp))
        lowered = lower_bundle(bundle, mesh)
        compiled = lowered.compile()

        mem = compiled.memory_analysis()
        record["memory_per_device"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        }
        ca = compiled.cost_analysis()
        record["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "note": "while bodies counted once (uncorrected)",
        }
        hlo_text = compiled.as_text()
        if save_hlo:
            import gzip
            with gzip.open(path.replace(".json", ".hlo.txt.gz"), "wt") as f:
                f.write(hlo_text)
        hlo = analyze_hlo(hlo_text)
        record["hlo_analysis"] = hlo

        # memory term uses the TPU-fusion bytes model (bytes_fused); the raw
        # unfused count stays in hlo_analysis for reference
        model_flops = model_flops_for(cfg, shape)
        roof = RooflineAnalyzer().analyze(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            hlo_flops=hlo["global"]["flops"],
            hbm_bytes=hlo["global"]["bytes_fused"],
            collective_bytes=hlo["global"]["collective_wire_bytes"],
            model_flops=model_flops)
        record["roofline"] = {
            "chips": chips,
            "compute_s": roof.compute_s,
            "memory_s": roof.memory_s,
            "collective_s": roof.collective_s,
            "dominant": roof.dominant,
            "bound_step_s": roof.bound_s,
            "model_flops": model_flops,
            "hlo_flops": roof.hlo_flops,
            "useful_flop_ratio": roof.useful_flop_ratio,
            "collective_operand_bytes_global":
                hlo["global"]["collective_operand_bytes"],
            "classification": roof.classify(),
        }
    except Exception as e:                                # noqa: BLE001
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["time_s"] = round(time.monotonic() - t0, 1)
    _write(path, record)
    return record


def _write(path: str, record: dict):
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dryrun")
    ap.add_argument("--arch", action="append", default=None,
                    help="architecture id(s); default: all assigned")
    ap.add_argument("--shape", action="append", default=None,
                    help="shape name(s); default: all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args(argv)

    archs = args.arch or ASSIGNED_ARCHS
    shapes = args.shape or list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                r = run_cell(arch, shape, multi, args.out,
                             args.skip_existing)
                dom = r.get("roofline", {}).get("dominant", "-")
                print(f"[{r['status']:7s}] {r['mesh']:10s} {arch:24s} "
                      f"{shape:12s} dominant={dom:10s} "
                      f"t={r.get('time_s')}s", flush=True)
                if r["status"] == "error":
                    failures += 1
                    print(r["error"][:500], flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
