"""A whole run of the serving cell at a tiny size on the CPU (the look for a
chip skipped): correct as it stands; not correct with a served token
altered where the engine produces it, nor with the program broken under
the timed path (the decode step's cache read one position off, one
expert's output dropped, a decode step that returns its cache unchanged);
and the float8 control reads far above the program.  The serve readers
are checked on hand-built contexts."""

import json
from pathlib import Path

import pytest
from lmsbench_tiny import cell, run

import repro.serve.engine as engine_mod
from repro.core import MonitoringStack

from benchmarks.lms_bench import bench
from benchmarks.lms_bench.flops import moe
from benchmarks.lms_bench.generators import serve

CELL = "mixtral-8x7b.serve.closed8"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_tiny_run_is_correct():
    out = run(cell(CELL))
    assert out.correct, out.checks
    assert [c.name for c in out.checks] == [
        "replay_tokens_mismatch", "served_logit_rel_err", "requests_wrong"]
    assert out.failed == 0 and out.attempted % 8 == 0
    assert out.e2e["serve_tokens_per_s"] > 0
    ctx = dict(out.ctx, device_kind="TPU v5 lite")
    for name in ("serve_prefill_useful_frac", "serve_decode_useful_frac",
                 "serve_mfu"):
        v = bench.metric_reader(name)(ctx)
        assert 0 < v <= 1, (name, v)
    # every decode call of the window is stamped, in traced runs or not
    steps = sum(max(r.max_new_tokens for r in b.requests) - 1
                for b in out.ctx["serve_batches"])
    assert len(out.ctx["decode_gaps_s"]) == steps - len(
        out.ctx["serve_batches"])
    assert bench.metric_reader("serve_token_gap_p95_ms")(ctx) > 0


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
    assert failed == ["replay_tokens_mismatch"]


def _pos_off(prefill, decode):
    return prefill, lambda params, cache, tokens, pos, extras=None: decode(
        params, cache, tokens, pos - 1, extras)


def _expert_dropped(prefill, decode):
    def drop(params):
        m = dict(params["moe_layers"]["moe"])
        m["w_down"] = m["w_down"].at[:, 0].set(0)
        return dict(params, moe_layers=dict(params["moe_layers"], moe=m))
    return (lambda params, *a: prefill(drop(params), *a),
            lambda params, *a: decode(drop(params), *a))


def _cache_unchanged(prefill, decode):
    def stuck(params, cache, *a):
        logits, _ = decode(params, cache, *a)
        return logits, cache
    return prefill, stuck


@pytest.mark.parametrize("fault", [_pos_off, _expert_dropped,
                                   _cache_unchanged],
                         ids=["decode_pos_off", "expert_dropped",
                              "cache_unchanged"])
def test_broken_program_is_not_correct(monkeypatch, fault):
    """The program's own step functions broken: the replay runs the same
    broken programs and agrees with what was served, and the logits'
    distance from the reference fails."""
    real = engine_mod.make_serve_fns
    monkeypatch.setattr(engine_mod, "make_serve_fns",
                        lambda cfg, **kw: fault(*real(cfg, **kw)))
    out = run(cell(CELL))
    failed = [c.name for c in out.checks if not c.ok]
    assert failed == ["served_logit_rel_err"]


# at this size the program reads 0.0087-0.0122 on seeds 1-3 and the float8
# control 0.129-0.214; on seed 1 the planted faults read 0.298-0.334 (CPU
# rehearsal): the tiny model's own limit lies between
TINY_REL_ERR_LIMIT = 0.06


def test_control_reads_above_the_limit(tmp_path):
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        r = serve.calibrate_seed(cell(CELL), 1, stack)
    finally:
        stack.close()
    assert r["program"]["served_logit_rel_err"] <= TINY_REL_ERR_LIMIT
    assert r["program"]["replay_tokens_mismatch"] == 0
    assert r["control_fp8"]["served_logit_rel_err"] > TINY_REL_ERR_LIMIT
    for fault in serve.FAULTS:
        assert r[fault]["served_logit_rel_err"] > TINY_REL_ERR_LIMIT, fault


def test_sample_is_whole_batches_with_the_longest_first():
    def batch(plen, news):
        reqs = [engine_mod.Request(i, [1] * plen, n, output=[0] * n)
                for i, n in enumerate(news)]
        return serve.Batch(plen, 0.0, 1.0, reqs)
    batches = [batch(8, [2, 4]), batch(16, [2, 4]), batch(16, [8, 2]),
               batch(4, [2, 2])]
    picked = serve.sample_batches(batches, 3, seed=5)
    assert picked[0] == 2 and len(picked) == 2
    assert serve.sample_batches(batches, 3, seed=5) == picked
    # the seed draws the others
    orders = {tuple(serve.sample_batches(batches, 100, seed=s))
              for s in range(10)}
    assert len(orders) > 1 and all(o[0] == 2 and sorted(o) == [0, 1, 2, 3]
                                   for o in orders)
    # batches that tie on the longest request: the seed picks among them
    ties = [batch(16, [8]), batch(16, [8]), batch(8, [8])]
    assert {serve.sample_batches(ties, 1, seed=s)[0] for s in range(10)} \
        == {0, 1}


# --------------------------------------------------------------------------
# the serve readers on hand-built contexts (nanoseconds on one clock)
# --------------------------------------------------------------------------


TRACE = {"window": [0.0, 100e6],
         "busy": [[0.0, 17e6], [19e6, 24e6], [26e6, 34e6], [40e6, 54e6]],
         # four decode runs in the window, 16 ms busy; one after it
         "decode_programs": [[12e6, 16e6], [20e6, 24e6], [30e6, 34e6],
                             [50e6, 54e6], [200e6, 204e6]],
         # the next batch's prefill
         "prefill_programs": [[40e6, 48e6]]}


def test_decode_idle_reads_idle_between_a_batchs_decode_runs():
    read = bench.metric_reader("serve_decode_idle_ms_per_token")
    # 2 ms idle after the first run, 2 ms after the second; the gap that
    # holds the prefill lies between batches
    assert read({"serve_trace": TRACE}) == pytest.approx(1.0)
    assert read({"serve_trace": dict(TRACE, busy=[])}) is None
    assert read({"serve_trace": dict(TRACE, decode_programs=[])}) is None


def test_decode_hbm_frac_is_bytes_over_busy_time_and_bandwidth():
    conf = json.loads((CONFIGS / "mixtral-8x7b-l2.json").read_text())

    def batch(plen, news):
        reqs = [engine_mod.Request(i, [1] * plen, n) for i, n in
                enumerate(news)]
        return serve.Batch(plen, 0.0, 1.0, reqs)
    ctx = {"serve_trace": TRACE, "config": conf, "max_batch": 8,
           "device_kind": "TPU v5 lite", "trace_from": 1,
           # the first batch lies before the traced window
           "serve_batches": [batch(64, [9, 9]), batch(512, [2, 3])]}
    read = bench.metric_reader("serve_decode_hbm_frac")
    want_bytes = (moe.decode_step_bytes(conf, 8, 512)
                  + moe.decode_step_bytes(conf, 8, 513)) / 2
    assert read(ctx) == pytest.approx(want_bytes / 4e-3 / 819e9)
    assert read(dict(ctx, serve_trace=dict(TRACE, decode_programs=[]))) \
        is None


def test_token_gap_p95_is_the_nearest_rank():
    read = bench.metric_reader("serve_token_gap_p95_ms")
    gaps = [0.010] * 18 + [0.020, 0.050]
    assert read({"decode_gaps_s": gaps}) == pytest.approx(20.0)
    assert read({"decode_gaps_s": []}) is None
