"""End-to-end: monitored training loop (+failure/restart) and serving."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, TrainConfig, get_config
from repro.core import MonitoringStack
from repro.models.transformer import init_model_params
from repro.serve.engine import ServingEngine
from repro.train.loop import InjectedFailure, TrainResult, train

TINY = ShapeConfig("tiny", seq_len=32, global_batch=4, kind="train")


def test_train_loss_decreases(tmp_path):
    cfg = get_config("lms-demo", smoke=True)
    tcfg = TrainConfig(total_steps=8, warmup_steps=1, learning_rate=5e-3)
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    losses = []
    r = train(cfg, tcfg, TINY, stack=stack,
              step_callback=lambda s, m: losses.append(float(m["loss"])))
    assert r.steps_run == 8
    assert losses[-1] < losses[0]
    db = stack.backend.db("global")
    assert "hpm" in db.measurements() and "train" in db.measurements()
    # HPM points carry derived perf-group metrics with job tags
    s = db.select("hpm", ["mfu"])[0]
    assert "jobid" in s.tags


def test_failure_injection_and_resume(tmp_path):
    cfg = get_config("lms-demo", smoke=True)
    ck = str(tmp_path / "ck")
    tcfg = TrainConfig(total_steps=6, warmup_steps=1, ckpt_dir=ck,
                       ckpt_interval=2)
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path / "l1"))
    with pytest.raises(InjectedFailure):
        train(cfg, tcfg, TINY, stack=stack, fail_at_step=4, job_id="j")
    # restart resumes from the last atomic checkpoint and finishes
    stack2 = MonitoringStack.inprocess(out_dir=str(tmp_path / "l2"))
    r = train(cfg, tcfg, TINY, stack=stack2, job_id="j2")
    assert r.resumed_from == 4
    assert r.final_step == 6
    assert not math.isnan(r.last_loss)
    # restart event recorded for the dashboards
    ev = stack2.backend.db("global").select("run_state")
    texts = [v for s in ev for v in s.values["event"]]
    assert any("starting" in t and "step 4" in t for t in texts)


def test_deterministic_replay_after_resume(tmp_path):
    """Data source is step-keyed: a resumed run sees the same batches."""
    from repro.data import SyntheticTokenSource
    src = SyntheticTokenSource(100, seed=0)
    a = src.batch(5, 4, 8)
    b = src.batch(5, 4, 8)
    np.testing.assert_array_equal(a, b)


def test_serving_engine(tmp_path):
    cfg = get_config("lms-demo", smoke=True)
    params = init_model_params(cfg, seed=0)
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    with stack.job("serve1", user="u", hosts=["h0"]):
        um = stack.usermetric(host="h0")
        eng = ServingEngine(cfg, params, max_batch=4, max_len=64,
                            usermetric=um, jit=False)
        rids = [eng.submit(np.arange(1, 5 + i), max_new_tokens=6)
                for i in range(5)]
        done = eng.run_until_empty()
        um.flush()
    assert len(done) == 5
    assert all(len(r.output) == 6 for r in done)
    assert all(r.first_token_at is not None for r in done)
    db = stack.backend.db("global")
    assert "serve_request" in db.measurements()
    assert "serve_decode" in db.measurements()
    # per-request latency metrics tagged with the job
    s = db.select("serve_request")[0]
    assert s.tags["jobid"] == "serve1"


def test_serving_greedy_consistency():
    """Engine output == manual prefill+argmax loop (same params)."""
    cfg = get_config("lms-demo", smoke=True)
    params = init_model_params(cfg, seed=0)
    from repro.models.transformer import forward, init_cache
    prompt = np.arange(1, 9, dtype=np.int32)

    eng = ServingEngine(cfg, params, max_batch=1, max_len=32, jit=False)
    eng.submit(prompt, max_new_tokens=4)
    out = eng.run_until_empty()[0].output

    cache = init_cache(cfg, 1, 32)
    logits, cache, _ = forward(params, cfg, tokens=jnp.asarray(prompt)[None],
                               mode="prefill", cache=cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(3):
        logits, cache, _ = forward(params, cfg,
                                   tokens=jnp.asarray([[toks[-1]]]),
                                   mode="decode", cache=cache,
                                   pos=jnp.int32(pos))
        toks.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    assert out == toks


def test_serving_masks_vocab_padding():
    """Ids in the padded vocab tail are not tokens: the serve steps score
    them -inf, so greedy decoding never emits one."""
    import dataclasses
    from repro.models.transformer import init_cache
    from repro.serve.engine import make_serve_fns

    cfg = dataclasses.replace(get_config("lms-demo", smoke=True),
                              vocab_size=500)
    assert cfg.vocab_padded == 512
    params = init_model_params(cfg, seed=0)
    prefill, _ = make_serve_fns(cfg)
    logits, _ = prefill(params, jnp.arange(1, 9, dtype=jnp.int32)[None],
                        init_cache(cfg, 1, 32))
    assert bool(jnp.all(jnp.isneginf(logits[:, cfg.vocab_size:])))
    assert bool(jnp.all(jnp.isfinite(logits[:, :cfg.vocab_size])))

    eng = ServingEngine(cfg, params, max_batch=2, max_len=32, jit=False)
    for n in (5, 8):
        eng.submit(np.arange(1, n), max_new_tokens=4)
    done = eng.run_until_empty()
    assert all(0 <= t < cfg.vocab_size for r in done for t in r.output)


# rows that differ in prompt length and answer length, served as a batch of
# three and a batch of two (max_batch=3)
MIXED = [(3, 2), (9, 6), (5, 4), (7, 5), (4, 1)]
SERVE_CFGS = ["lms-demo", "mixtral-8x7b"]


def _mixed_engine(arch, jit):
    cfg = get_config(arch, smoke=True)
    params = init_model_params(cfg, seed=0)
    eng = ServingEngine(cfg, params, max_batch=3, max_len=32, jit=jit)
    rng = np.random.default_rng(0)
    prompts = [(rng.integers(1, cfg.vocab_size, n, dtype=np.int32), m)
               for n, m in MIXED]
    for p, m in prompts:
        eng.submit(p, max_new_tokens=m)
    return cfg, params, eng, prompts


def _synchronous_greedy(cfg, params, batch, jit):
    """The batch decoded one step at a time, each step's argmax read to
    the host before the next step is dispatched; rows laid out as the
    engine lays them out."""
    from repro.models.transformer import init_cache
    from repro.serve.engine import make_serve_fns
    prefill, decode = make_serve_fns(cfg)
    if jit:
        prefill, decode = jax.jit(prefill), jax.jit(decode)
    plen = max(len(p) for p, _ in batch)
    toks = np.zeros((len(batch), plen), np.int32)
    for i, (p, _) in enumerate(batch):
        toks[i, plen - len(p):] = p
    logits, cache = prefill(params, jnp.asarray(toks),
                            init_cache(cfg, len(batch), 32))
    steps = [np.asarray(jnp.argmax(logits, axis=-1))]
    for s in range(max(m for _, m in batch) - 1):
        logits, cache = decode(params, cache,
                               jnp.asarray(steps[-1])[:, None],
                               jnp.int32(plen + s))
        steps.append(np.asarray(jnp.argmax(logits, axis=-1)))
    return [[int(t[i]) for t in steps[:m]] for i, (_, m) in enumerate(batch)]


@pytest.mark.parametrize("jit", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("arch", SERVE_CFGS)
def test_lagged_readback_gives_the_synchronous_tokens(arch, jit):
    cfg, params, eng, prompts = _mixed_engine(arch, jit)
    done = eng.run_until_empty()
    want = (_synchronous_greedy(cfg, params, prompts[:3], jit)
            + _synchronous_greedy(cfg, params, prompts[3:], jit))
    assert [r.output for r in done] == want
    assert [len(r.output) for r in done] == [m for _, m in MIXED]


class _Tokens:
    """One step's tokens as the engine holds them; logs the host's read."""

    def __init__(self, arr, step, log):
        self.arr, self.step, self.log = arr, step, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.step))
        return np.asarray(self.arr, dtype)


@pytest.mark.parametrize("arch", SERVE_CFGS)
def test_decode_calls_and_the_one_step_lag(arch):
    """``decode`` is called max_new - 1 times a batch with (B, 1) int32
    tokens and an int32 scalar position, and the host reads step s's
    tokens only after it has called decode for step s + 1."""
    _, _, eng, _ = _mixed_engine(arch, jit=True)
    log, calls = [], []
    decode, greedy_next = eng.decode, eng.greedy_next

    def logged_next(logits, pos):
        tok, pos = greedy_next(logits, pos)
        step = sum(1 for e in log if e[0] == "decode")
        return _Tokens(tok, step, log), pos

    def logged_decode(params, cache, tokens, pos):
        calls.append((tokens.arr.shape, tokens.arr.dtype, pos.shape,
                      pos.dtype))
        log.append(("decode", tokens.step + 1))
        return decode(params, cache, tokens.arr, pos)

    eng.decode, eng.greedy_next = logged_decode, logged_next
    for b, m in ((3, 6), (2, 5)):
        log.clear()
        calls.clear()
        eng.run_batch()
        assert calls == [((b, 1), jnp.int32, (), jnp.int32)] * (m - 1)
        # every step's tokens are read once, in order
        assert [s for e, s in log if e == "read"] == list(range(m))
        at = {e: i for i, e in enumerate(log)}
        assert at[("read", 0)] < at[("decode", 1)]      # prefill's sync
        for s in range(1, m - 1):
            assert at[("decode", s + 1)] < at[("read", s)]
        assert at[("decode", m - 1)] < at[("read", m - 1)]


def test_serve_decode_point_carries_the_readback_wait(tmp_path):
    cfg = get_config("lms-demo", smoke=True)
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        with stack.job("serve2", user="u", hosts=["h0"]):
            um = stack.usermetric(host="h0")
            eng = ServingEngine(cfg, init_model_params(cfg, seed=0),
                                max_batch=3, max_len=32, usermetric=um)
            for n, m in MIXED:
                eng.submit(np.arange(1, n + 1), max_new_tokens=m)
            eng.run_until_empty()
            um.flush()
        (s,) = stack.backend.db("global").select(
            "serve_decode", ["readback_wait_s", "decode_time_s"])
    finally:
        stack.close()
    waits, times = s.values["readback_wait_s"], s.values["decode_time_s"]
    assert len(waits) == len(times) == 2
    for w, t in zip(waits, times):
        assert 0 <= w <= t


def test_straggler_finding_triggers_elastic_halt(tmp_path):
    """Monitoring is load-bearing: a sustained straggler finding (emitted by
    a simulated peer host) halts the loop so the launcher can restart
    elastically without the slow host."""
    from repro.core import Point, now_ns

    cfg = get_config("lms-demo", smoke=True)
    tcfg = TrainConfig(total_steps=50, warmup_steps=1,
                       halt_on_straggler=True)
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))

    t0 = now_ns()

    def inject_straggler(step, metrics):
        # a peer host reports sustained step-time skew (simulated timeline
        # so the 30 s timeout of the rule elapses immediately)
        stack.router.write(Point(
            "hpm", {"hostname": "peer-h9"},
            {"straggler_skew": 0.5}, t0 + step * 40 * 10 ** 9))

    r = train(cfg, tcfg, TINY, stack=stack, step_callback=inject_straggler,
              job_id="strag")
    assert r.steps_run < 50, "loop should halt early"
    assert any(f.rule == "step_time_straggler" for f in r.findings)
    ev = stack.backend.db("global").select("run_state")
    texts = [v for s in ev for v in s.values["event"]]
    assert any("halt: straggler:peer-h9" in t for t in texts)


def test_train_markers_roofline_end_to_end(tmp_path):
    """ROADMAP item 3 acceptance: train with markers on, then one
    roofline QuerySpec answers per-region fractions from the TSDB."""
    from repro.core.marker import MARKER_MEASUREMENT, roofline_spec

    cfg = get_config("lms-demo", smoke=True)
    tcfg = TrainConfig(total_steps=6, warmup_steps=1)
    stack = MonitoringStack.inprocess(out_dir=str(tmp_path))
    try:
        r = train(cfg, tcfg, TINY, stack=stack, job_id="mk-e2e")
        assert r.steps_run == 6
        db = stack.backend.db("global")
        regions = set(db.tag_values(MARKER_MEASUREMENT, "region"))
        assert {"train_step", "data_wait"} <= regions
        # marker points get job enrichment like every other measurement
        s = db.select(MARKER_MEASUREMENT, ["time_s"],
                      tags={"region": "train_step"})[0]
        assert s.tags.get("jobid") == "mk-e2e"
        # the one canonical spec, served by the query engine
        res = stack.backend.query_engine("global").query(
            roofline_spec("mk-e2e"))
        g = res.groups["train_step"]
        fracs = [v for v in g["roofline_frac"]["values"] if v is not None]
        assert fracs and all(f > 0.0 for f in fracs)
        # data_wait carries no flops/bytes: timing only, no roofline
        assert "roofline_frac" not in res.groups["data_wait"]
    finally:
        stack.close()
