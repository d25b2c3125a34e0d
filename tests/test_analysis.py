"""Pathological-job rules and the pattern decision tree."""

import pytest

from repro.core.analysis import (StreamAnalyzer, ThresholdRule,
                                 classify_job, default_rules, evaluate_rule,
                                 evaluate_rules_on_db)
from repro.core.line_protocol import Point
from repro.core.perf_groups import PEAK_FLOPS, derive_all
from repro.core.tsdb import Database

S = 1_000_000_000   # ns


def test_threshold_timeout_fig4():
    """Paper Fig. 4: metric below threshold for > timeout => finding."""
    rule = ThresholdRule("break", "hpm", "mfu", "<", 0.05, 600.0)
    times = [i * 60 * S for i in range(40)]             # one point a minute
    values = [0.5] * 10 + [0.01] * 15 + [0.5] * 15      # 15 min dip
    fs = evaluate_rule(rule, times, values, "h0")
    assert len(fs) == 1
    assert fs[0].duration_s >= 600
    # a dip shorter than the timeout is NOT a finding
    values = [0.5] * 10 + [0.01] * 5 + [0.5] * 25
    assert evaluate_rule(rule, times, values) == []


def test_nan_counts_as_below():
    rule = ThresholdRule("break", "hpm", "loss", "<", 1e9, 1.0)
    assert rule.check(float("nan"))


def test_open_ended_finding():
    rule = ThresholdRule("break", "hpm", "mfu", "<", 0.05, 600.0)
    times = [i * 60 * S for i in range(20)]
    values = [0.01] * 20                                 # never recovers
    fs = evaluate_rule(rule, times, values)
    assert len(fs) == 1


def test_stream_analyzer_fires_once():
    an = StreamAnalyzer([ThresholdRule("idle", "hpm", "mfu", "<", 0.05,
                                       60.0)])
    for i in range(30):
        an.observe(Point("hpm", {"hostname": "h0"}, {"mfu": 0.01},
                         i * 10 * S))
    assert len(an.findings) == 1
    assert an.findings[0].host == "h0"
    # recovery resets the state -> a second episode fires again
    an.observe(Point("hpm", {"hostname": "h0"}, {"mfu": 0.9}, 301 * S))
    for i in range(30):
        an.observe(Point("hpm", {"hostname": "h0"}, {"mfu": 0.01},
                         (310 + i * 10) * S))
    assert len(an.findings) == 2


def test_rules_on_db_group_by_host():
    db = Database("t")
    for host, mfu in (("h0", 0.5), ("h1", 0.001)):
        db.write([Point("hpm", {"hostname": host, "jobid": "j"},
                        {"mfu": mfu}, i * 120 * S) for i in range(10)])
    fs = evaluate_rules_on_db(db, default_rules(), jobid="j")
    assert {f.host for f in fs if f.rule == "compute_break"} == {"h1"}


def test_decision_tree_branches():
    cases = [
        ({"data_stall_frac": 0.5}, "ingest-bound"),
        ({"straggler_skew": 0.3}, "load-imbalance"),
        ({"collective_frac": 0.6}, "collective-bound"),
        ({"memory_frac": 0.8, "useful_flop_ratio": 0.3},
         "recompute-heavy memory-bound"),
        ({"memory_frac": 0.8, "useful_flop_ratio": 0.9}, "memory-bound"),
        ({"memory_frac": 0.2, "collective_frac": 0.1, "mfu": 0.1},
         "latency/overhead-bound"),
        ({"memory_frac": 0.2, "collective_frac": 0.1, "mfu": 0.6},
         "compute-bound"),
    ]
    for metrics, want in cases:
        out = classify_job(metrics)
        assert out["pattern"] == want, (metrics, out)
        assert out["remedy"]
        assert out["path"]


def test_perf_groups_derive():
    raw = {"hlo_flops": 1e15, "model_flops": 8e14, "step_time_s": 2.0,
           "hlo_bytes": 1e12, "collective_bytes": 1e11,
           "tokens_per_step": 1e6, "data_wait_s": 0.2,
           "hbm_bytes_in_use": 8e9}
    d = derive_all(raw)
    assert d["gflops_per_s"] == pytest.approx(5e5)
    assert d["mfu"] == pytest.approx(8e14 / 2.0 / PEAK_FLOPS)
    assert d["useful_flop_ratio"] == pytest.approx(0.8)
    assert d["tokens_per_s"] == pytest.approx(5e5)
    assert d["data_stall_frac"] == pytest.approx(0.1)
    assert d["hbm_used_gb"] == pytest.approx(8.0)
