"""Shared plumbing of the benchmark: the spec, a cell's files, the device,
the per-layer readers and the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``configs/<config>.json`` and
``traffic/<traffic>.json`` hold them, ``generators/<generator>.py`` runs the
traffic, ``flops/<family>.py`` counts the model's operations and
``metrics/<metric>.py`` reads one per-layer metric.  Adding a cell, a
configuration or a metric adds files and never edits one.
"""

from __future__ import annotations

import hashlib
import importlib
import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPEC_PATH = ROOT / "BENCHMARK.json"
# run outputs (traces, the stack's dashboards); listed in .gitignore
OUT_DIR = HERE / "out"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec(path: Path = SPEC_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of the spec with its configuration and traffic mix."""

    name: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: list            # metric entries this cell reports
    per_layer: list
    limits: dict                # compared number -> its limit

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec: Optional[dict] = None,
              base: Path = HERE) -> Cell:
    spec = spec if spec is not None else load_spec()
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[entry["config"]]
    config = _read_json(ROOT / cfg_entry["file"])
    traffic = _read_json(base / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    layer = [m for m in spec["per_layer"] if _applies(m, name)]
    return Cell(name, entry, config, traffic, e2e, layer,
                limits(name, base))


def seed31(seed: int) -> int:
    """A seed of any size -> a stable non-negative 31-bit seed (JAX keeps
    only 32 bits of a key's seed, so distinct large seeds would collide)."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def check_devices(chips: int):
    """The devices the cell runs on; raises NoDevice without a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def memory_peak(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of the cell's devices."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def peaks(device_kind: str) -> dict:
    table = _read_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def limits(cell: str, base: Path = HERE) -> dict:
    """``limits/<cell>.json``: compared number -> its limit."""
    return {k: float(v["limit"]) for k, v in
            _read_json(base / "limits" / f"{cell}.json").items()}


def flops_module(family: str):
    return importlib.import_module(f"benchmarks.lms_bench.flops.{family}")


def generator_module(name: str):
    return importlib.import_module(f"benchmarks.lms_bench.generators.{name}")


def metric_reader(name: str, base: Path = HERE) -> Callable:
    """``metrics/<name>.py``'s ``read(ctx)``; names may hold dots."""
    path = base / "metrics" / f"{name}.py"
    mod_name = "lms_bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# statistics the generators share
# --------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of all values."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    k = max(0, math.ceil(q / 100.0 * len(vals)) - 1)
    return float(vals[k])


# --------------------------------------------------------------------------
# checks and the result line
# --------------------------------------------------------------------------


@dataclass
class Check:
    """One number compared with its limit: passes when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a generator hands back to the harness."""

    e2e: dict                       # metric name -> value
    attempted: int
    failed: int
    checks: list                    # [Check]
    memory_peak_bytes: int
    ctx: dict = field(default_factory=dict)     # for per-layer readers
    trace: Optional[dict] = None    # trace_reduce.reduce(...) output

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def result_line(cell: Cell, outcome: Outcome, devices, trace: bool) -> dict:
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        if trace:
            value = metric_reader(m["name"])(outcome.ctx)
        else:
            value = outcome.e2e.get(m["name"])
            if value is None:
                raise RuntimeError(f"{cell.name} does not report "
                                   f"{m['name']}")
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(outcome.memory_peak_bytes)}
    line = {"correct": outcome.correct, "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": device}
    if trace and outcome.trace is not None:
        device["busy_s"] = outcome.trace["busy_s"]
        device["window_s"] = outcome.trace["window_s"]
        line["breakdown"] = outcome.trace["breakdown"]
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def log(msg: str):
    """A progress line on standard error."""
    print(f"lms_bench: {msg}", file=sys.stderr, flush=True)


def print_checks(checks, stream=None):
    stream = stream or sys.stderr
    for c in checks:
        verdict = "ok" if c.ok else "FAIL"
        print(f"check {c.name} {c.value!r} limit {c.limit!r} {verdict}",
              file=stream, flush=True)
