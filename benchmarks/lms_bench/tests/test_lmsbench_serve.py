"""A whole run of the serving cell at a tiny size on the CPU (the look for a
chip skipped): correct as it stands, not correct with a served token
altered where the engine produces it, and the float8 control reads far
above the program."""

from lmsbench_tiny import cell, run

import repro.serve.engine as engine_mod
from repro.core import MonitoringStack

from benchmarks.lms_bench import bench
from benchmarks.lms_bench.generators import serve

CELL = "mixtral-8x7b.serve.closed8"


def test_tiny_run_is_correct():
    out = run(cell(CELL))
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted % 8 == 0
    assert out.e2e["serve_tokens_per_s"] > 0
    ctx = dict(out.ctx, device_kind="TPU v5 lite")
    for name in ("serve_prefill_useful_frac", "serve_decode_useful_frac",
                 "serve_mfu"):
        v = bench.metric_reader(name)(ctx)
        assert 0 < v <= 1, (name, v)


def test_altered_token_is_not_correct(monkeypatch):
    real = engine_mod.ServingEngine.run_batch

    def altered(self):
        done = real(self)
        for r in done:
            r.output[-1] = (r.output[-1] + 1) % self.cfg.vocab_size
        return done
    monkeypatch.setattr(engine_mod.ServingEngine, "run_batch", altered)
    out = run(cell(CELL))
    failed = [c.name for c in out.checks if not c.ok]
    assert failed == ["served_logit_gap_mean"]


# at this size every served token is the reference's best (mean gap 0 on
# seeds 1-4) and the float8 control reads 0.0117-0.117 (CPU rehearsal):
# the tiny model's own limit lies between
TINY_MEAN_GAP_LIMIT = 5e-3


def test_control_reads_above_the_limit(tmp_path):
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        r = serve.calibrate_seed(cell(CELL), 1, stack)
    finally:
        stack.close()
    assert r["program"]["served_logit_gap_mean"] <= TINY_MEAN_GAP_LIMIT
    assert r["control_fp8"]["served_logit_gap_mean"] > TINY_MEAN_GAP_LIMIT
