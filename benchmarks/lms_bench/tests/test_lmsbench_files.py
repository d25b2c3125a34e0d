"""Cells, configurations, traffic mixes and metrics are found by name from
files alone, and the command refuses to run without a chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT)]

from benchmarks.lms_bench import bench  # noqa: E402

HERE = ROOT / "benchmarks" / "lms_bench"
SPEC = bench.load_spec()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_from_its_files(cell):
    c = bench.load_cell(cell)
    assert c.config["name"] == c.entry["config"]
    assert (HERE / "generators" / f"{c.traffic['generator']}.py").exists()
    assert bench.flops_module(c.config["flops"])
    assert c.limits and all(v >= 0 for v in c.limits.values())
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        # a reader that finds nothing to read returns nothing
        assert bench.metric_reader(m["name"])({}) is None


def test_configs_state_their_source_and_cuts():
    for entry in SPEC["configs"]:
        conf = json.loads((ROOT / entry["file"]).read_text())
        assert conf["name"] == entry["name"]
        assert conf["source"] == entry["source"]
        assert conf["reduced"] == entry["reduced"]
        assert set(conf["reduced"]) <= set(conf["published"])


def test_a_new_cell_is_new_files_only(tmp_path):
    """Cells, their traffic mixes, their limits and a per-layer metric,
    added as files beside a copy of the spec, load without editing any
    file: a one-chip cell with a larger batch, and the same mix on four
    chips, which the train generator runs over a mesh of them."""
    for sub in ("traffic", "limits", "metrics"):
        (tmp_path / sub).mkdir()
    spec = json.loads(json.dumps(SPEC))
    new = {"granite-3-8b.train.b4": 1, "granite-3-8b.train.mesh2x2": 4}
    traffic = json.loads((HERE / "traffic" / "train_unmonitored.json")
                         .read_text())
    traffic["batch"] = 4
    (tmp_path / "traffic" / "train_b4.json").write_text(json.dumps(traffic))
    for name, chips in new.items():
        spec["workloads"].append({"name": name, "config": "granite-3-8b-l2",
                                  "traffic": "train_b4", "chips": chips,
                                  "why": "a larger batch"})
        (tmp_path / "limits" / f"{name}.json").write_text(
            json.dumps({"loss_gap": {"limit": 1e-3}}))
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "train_tokens_per_s",
                              "workloads": list(new)})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].extend(new)
    (tmp_path / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return ctx.get('steps')\n")
    for name, chips in new.items():
        cell = bench.load_cell(name, spec, base=tmp_path)
        assert cell.traffic["batch"] == 4 and cell.chips == chips
        assert cell.limits == {"loss_gap": 1e-3}
        assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                        "setup_s"]
        assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    reader = bench.metric_reader("steps_seen", base=tmp_path)
    assert reader({"steps": 7}) == 7 and reader({}) is None


def test_seed_of_any_size_maps_to_31_bits():
    seeds = [0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 33 + 5, 5]
    mapped = [bench.seed31(s) for s in seeds]
    assert all(0 <= m < 2 ** 31 for m in mapped)
    assert len(set(mapped)) == len(seeds)
    assert bench.seed31(2 ** 33 + 5) == bench.seed31(2 ** 33 + 5)


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "granite-3-8b.train.dash8", "--seed", "4294967297", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_serve_deck_realizes_the_stated_mix():
    """Every batch of the deck is full (one request per client) and the
    deck holds the mix's prompt and answer lengths in their shares."""
    from collections import Counter
    t = json.loads((HERE / "traffic" / "serve_closed8.json").read_text())
    assert t["clients"] == t["max_batch"]
    prompts, new = Counter(), Counter()
    for b in t["batches"]:
        assert len(b["prompts"]) == len(b["new"]) == t["clients"]
        prompts.update(b["prompts"])
        new.update(b["new"])
    n = t["clients"] * len(t["batches"])
    assert {str(k): v / n for k, v in prompts.items()} == t["prompt_lengths"]
    assert {str(k): v / n for k, v in new.items()} == t["new_tokens"]
    assert max(p for b in t["batches"] for p in b["prompts"]) + \
        max(new) <= t["max_len"]
