"""The program's own spans on the profiler's clock (``jax.profiler``
annotations): a monitored training run on the CPU traced with
``jax.profiler`` and read back with ``ProfileData``, and the step
program's named scopes."""

import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ShapeConfig, TrainConfig, get_config
from repro.core import HttpQueryClient, MonitoringStack, QuerySpec
from repro.train.loop import train

TINY = ShapeConfig("tiny", seq_len=32, global_batch=4, kind="train")
STEPS = 3
# names the benchmark harness gives its own spans; the program uses none
HARNESS_PREFIXES = ("lms_bench:", "instr:", "ingest:", "query:", "serve:")


def _host_events(trace_dir):
    """[(name, start_ns, end_ns, thread, stats)] of every host event, in
    start order."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                s = float(e.start_ns)
                out.append((e.name, s, s + float(e.duration_ns),
                            f"{line.name}#{i}", dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A 3-step monitored run and a repeated dashboard query over HTTP,
    all under one profiler trace."""
    tmp = tmp_path_factory.mktemp("spans")
    cfg = get_config("lms-demo", smoke=True)
    tcfg = TrainConfig(total_steps=STEPS, warmup_steps=1)
    stack = MonitoringStack.inprocess(out_dir=str(tmp / "lms"),
                                      serve_http=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            r = train(cfg, tcfg, TINY, stack=stack, job_id="spans")
            client = HttpQueryClient(stack.http.url)
            spec = QuerySpec(measurement="train", metrics=("loss",),
                             tags={"jobid": "spans"}, window_ns=10 ** 9)
            client.query(spec)
            client.query(spec)
        finally:
            jax.profiler.stop_trace()
    finally:
        stack.close()
    assert r.steps_run == STEPS
    return _host_events(tmp / "trace")


def _loop_thread(events):
    threads = {t for n, _, _, t, _ in events if n.startswith("train.loop.")}
    assert len(threads) == 1
    return threads.pop()


def _covered(intervals, lo, hi):
    t, total = lo, 0.0
    for s, e in sorted(intervals):
        s, e = max(s, t), min(e, hi)
        if e > s:
            total += e - s
            t = e
    return total


def test_each_step_is_tiled_by_its_loop_phases(traced_run):
    thread = _loop_thread(traced_run)
    steps = [(s, e, st) for n, s, e, t, st in traced_run
             if n == "train" and t == thread]
    assert [st["step_num"] for _, _, st in steps] == list(range(STEPS))
    phases = [(s, e) for n, s, e, t, _ in traced_run
              if n.startswith("train.loop.") and t == thread]
    for lo, hi, _ in steps:
        inside = [(s, e) for s, e in phases if lo <= s and e <= hi]
        assert _covered(inside, lo, hi) >= 0.95 * (hi - lo)
    names = {n for n, *_ in traced_run if n.startswith("train.loop.")}
    assert {"train.loop.data", "train.loop.h2d", "train.loop.compile",
            "train.loop.dispatch", "train.loop.sync",
            "train.loop.monitor"} <= names


def test_stack_spans_nest_under_the_monitor_phase(traced_run):
    thread = _loop_thread(traced_run)
    steps = [(s, e) for n, s, e, t, _ in traced_run
             if n == "train" and t == thread]
    monitor = [(s, e) for n, s, e, t, _ in traced_run
               if n == "train.loop.monitor" and t == thread]
    in_steps = [(n, s, e) for n, s, e, t, _ in traced_run
                if n.startswith("lms.") and t == thread
                and any(lo <= s < hi for lo, hi in steps)]
    assert {"lms.agent.collect_step", "lms.usermetric.metric",
            "lms.router.write", "lms.router.publish"} <= \
        {n for n, _, _ in in_steps}
    for n, s, e in in_steps:
        assert any(lo <= s and e <= hi for lo, hi in monitor), n
    writes = [st for n, *_, st in traced_run if n == "lms.router.write"]
    assert writes and all(st["points"] >= 1 for st in writes)


def test_marker_regions_are_spans(traced_run):
    names = [n for n, *_ in traced_run]
    assert names.count("marker.train_step") == STEPS
    # externally timed regions (record) open no span
    assert "marker.data_wait" not in names
    assert "lms.marker.flush" in names


def test_a_repeated_query_hits_the_engine_cache(traced_run):
    execs = [(s, e, t, st) for n, s, e, t, st in traced_run
             if n == "lms.query.exec"]
    assert [st["cache"] for *_, st in execs] == ["miss", "hit"]
    posts = [(s, e, t) for n, s, e, t, st in traced_run
             if n == "lms.http.post" and st["path"] == "/query/v2"]
    assert len(posts) == 2
    # the engine runs inside the server's request handling
    for s, e, t, _ in execs:
        assert any(ps <= s and e <= pe and pt == t for ps, pe, pt in posts)


def test_no_program_span_carries_a_harness_prefix(traced_run):
    assert not [n for n, *_ in traced_run if n.startswith(HARNESS_PREFIXES)]


def test_annotations_carry_metadata(tmp_path):
    """What the program's spans rely on: an annotation's keyword arguments
    and ``set_metadata`` arrive as its event's stats, and a decorated
    function keeps its name."""
    @functools.partial(jax.profiler.annotate_function, name="lms.test.fn")
    def fn(x):
        return x + 1

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("lms.test.span", points=3) as sp:
            sp.set_metadata(cache="hit")
            assert fn(1) == 2
    finally:
        jax.profiler.stop_trace()
    ev = {n: st for n, _, _, _, st in _host_events(tmp_path)}
    assert ev["lms.test.span"]["points"] == 3
    assert ev["lms.test.span"]["cache"] == "hit"
    assert "lms.test.fn" in ev
    assert fn.__name__ == "fn"


def test_step_program_ops_carry_the_named_scopes():
    """Forward, backward and the update of the compiled step carry the six
    scopes in their op names."""
    from repro.models.transformer import init_model_params
    from repro.train.step import make_train_step
    cfg = get_config("granite-3-8b", smoke=True)
    tcfg = TrainConfig(warmup_steps=1, remat_policy="minimal",
                       total_steps=10)
    step, opt = make_train_step(cfg, tcfg)
    params = jax.eval_shape(lambda: init_model_params(cfg, 0))
    state = jax.eval_shape(opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(step).lower(params, state, batch, 0).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    scopes = {p for n in names for p in re.split(r"[/()]", n)}
    assert {"embed", "attention", "mlp", "norm", "logits_loss",
            "optimizer"} <= scopes
    backward = {p for n in names if "transpose(" in n
                for p in re.split(r"[/()]", n)}
    assert {"attention", "mlp", "norm", "logits_loss"} <= backward
