"""A whole run of each training cell at a tiny size on the CPU (the look for
a chip skipped): correct as it stands, and not correct with the timed path
broken underneath, once for each fault the cell can have."""

import pytest

from lmsbench_tiny import cell, run

import repro.core.query as query_mod
from repro.core import MonitoringStack
import repro.train.loop as loop_mod
from repro.train.step import make_train_step

from benchmarks.lms_bench.generators import train as train_drv

CELLS = ["granite-3-8b.train.dash8", "granite-3-8b.train.unmonitored"]


@pytest.mark.parametrize("name", CELLS)
def test_tiny_run_is_correct(name):
    out = run(cell(name))
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0
    assert out.e2e["train_tokens_per_s"] > 0
    names = {c.name for c in out.checks}
    assert {"loss3_gap", "grad_norm_gap", "change_norm_gap",
            "change_diff"} <= names
    if name.endswith("dash8"):
        assert {"dashboard_gap", "points_lost"} <= names
        assert out.ctx["queries"] > 0 and min(out.ctx["query_ms"]) > 0


def _broken_step(fault):
    def factory(model_cfg, train_cfg, **kw):
        step, opt = make_train_step(model_cfg, train_cfg, **kw)

        def broken(params, opt_state, batch, s):
            if fault == "unchanged":
                _, _, metrics = step(params, opt_state, batch, s)
                return params, opt_state, metrics
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half, s)
        return broken, opt
    return factory


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(loop_mod, "make_train_step", _broken_step(fault))
    out = run(cell("granite-3-8b.train.unmonitored"), seconds=0.5)
    assert not out.correct
    failed = {c.name for c in out.checks if not c.ok}
    assert "change_diff" in failed


def test_altered_dashboard_answer_is_not_correct(monkeypatch):
    real = query_mod.QueryEngine.query

    def altered(self, spec):
        res = real(self, spec)
        groups = {g: {m: ({"times": col["times"],
                           "values": [v * 1.001 for v in col["values"]]}
                          if isinstance(col, dict) else col)
                      for m, col in e.items()} for g, e in res.groups.items()}
        return query_mod.QueryResult(res.fingerprint, res.window_ns, groups,
                                     res.meta)
    monkeypatch.setattr(query_mod.QueryEngine, "query", altered)
    out = run(cell("granite-3-8b.train.dash8"))
    assert not out.correct
    assert [c.name for c in out.checks if not c.ok] == ["dashboard_gap"]


def test_control_and_half_batch_fail_the_cells_limits(tmp_path):
    """The float8 control and the reference with half of the batch left
    out, put in the program's place, fail the cell's own limits; the
    program passes them."""
    c = cell("granite-3-8b.train.dash8")
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        r = train_drv.calibrate_seed(c, 4, stack)
    finally:
        stack.close()

    def failed(readings):
        return {k for k, lim in c.limits.items()
                if k in readings and readings[k] > lim}
    assert not failed(r["program"])
    assert "change_diff" in failed(r["control_fp8"])
    assert "change_diff" in failed(r["fault_half_batch"])
