"""The program-span reductions (``progtrace.py``) on hand-built events, on
a trace recorded here on the CPU and on an excerpt of one recorded on a
TPU v5e, and the per-layer readers built on them."""

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT)]

from benchmarks.lms_bench import bench, progtrace  # noqa: E402
from benchmarks.lms_bench.trace_reduce import WINDOW  # noqa: E402

MAIN = "python3#0"
EXCERPT = Path(__file__).parent / "data" / "progtrace_excerpt_v5e_dash8.json"


def _ev(ops=(), spans=(), window=(0.0, 1000.0)):
    return {"window": list(window), "ops": [list(o) for o in ops],
            "spans": [list(s) for s in spans]}


def _loop_events():
    """One step in a 1000 ns window: device busy 100-400 and 600-900,
    the loop's phases around it (monitor holds dispatch and sync)."""
    ops = [("fusion.1", 100.0, 400.0, "attention"),
           ("fusion.2", 600.0, 900.0, "mlp")]
    spans = [("train", 0.0, 990.0, MAIN, {"step_num": 7}),
             ("train.loop.data", 0.0, 50.0, MAIN, {}),
             ("train.loop.h2d", 50.0, 100.0, MAIN, {}),
             ("train.loop.monitor", 400.0, 700.0, MAIN, {}),
             ("train.loop.dispatch", 450.0, 500.0, MAIN, {}),
             ("train.loop.sync", 500.0, 650.0, MAIN, {}),
             ("lms.router.write", 420.0, 440.0, MAIN, {"points": 2}),
             ("train.loop.callback", 900.0, 950.0, MAIN, {})]
    return _ev(ops, spans)


def test_busy_time_goes_to_the_innermost_op_under_a_while():
    ops = [("while.1", 0.0, 1000.0, None),
           ("fusion.1", 100.0, 300.0, "attention"),
           ("fusion.2", 300.0, 600.0, "mlp"),
           ("fusion.3", 700.0, 800.0, "optimizer"),
           ("fusion.4", 1000.0, 1200.0, "logits_loss")]
    r = progtrace.busy_by_scope(_ev(ops, window=(0.0, 1300.0)))
    # the loop's own time is what its body ops leave of it
    assert r == {"attention": 200.0, "mlp": 300.0, "optimizer": 100.0,
                 "logits_loss": 200.0, None: 400.0}
    # the shares add up to the busy time: no op is counted twice
    assert sum(r.values()) == 1200.0


def test_busy_time_is_clipped_to_the_window():
    ops = [("while.1", 0.0, 1000.0, None),
           ("fusion.1", 100.0, 300.0, "attention")]
    r = progtrace.busy_by_scope(_ev(ops, window=(200.0, 500.0)))
    assert r == {"attention": 100.0, None: 200.0}


def test_a_program_without_scopes_gives_no_shares():
    ops = [("while.1", 0.0, 1000.0, None), ("fusion.1", 0.0, 10.0, None)]
    assert progtrace.busy_by_scope(_ev(ops)) is None


def test_a_program_with_loop_phases_but_no_scoped_op_is_refused():
    """The program that writes the loop's phases scopes its step too: no
    device op, or ops without scopes, there mean the reader looks in the
    wrong place, and the metrics must not drop out of the line unseen."""
    ev = _loop_events()
    progtrace.check(ev)
    ev["ops"] = [[n, s, e, None] for n, s, e, _ in ev["ops"]]
    with pytest.raises(ValueError, match="named scope"):
        progtrace.check(ev)
    ev["ops"] = []
    with pytest.raises(ValueError, match="no device op"):
        progtrace.check(ev)
    # a program without the loop's phases (the parent's) is not refused
    ev["spans"] = [s for s in ev["spans"] if not s[0].startswith("train")]
    progtrace.check(ev)


def test_idle_time_goes_to_the_innermost_loop_phase():
    by_phase, steps = progtrace.idle_by_phase(_loop_events())
    assert steps == 1
    assert by_phase == {"train.loop.data": 50.0, "train.loop.h2d": 50.0,
                        "train.loop.monitor": 50.0,
                        "train.loop.dispatch": 50.0,
                        "train.loop.sync": 100.0,
                        "train.loop.callback": 50.0, None: 50.0}


def test_a_trace_without_loop_phases_gives_nothing():
    ev = _loop_events()
    ev["spans"] = [s for s in ev["spans"] if not s[0].startswith("train")]
    assert progtrace.idle_by_phase(ev) is None


@pytest.mark.parametrize("intervals,lo,hi,want", [
    ([(0, 10, "a")], 0, 10, [(0, 10, "a")]),
    ([(0, 10, "a"), (2, 4, "b")], 0, 10,
     [(0, 2, "a"), (2, 4, "b"), (4, 10, "a")]),
    ([(0, 10, "a"), (2, 4, "b"), (3, 4, "c")], 1, 5,
     [(1, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "a")]),
    ([(0, 4, "a"), (6, 8, "b")], 0, 10, [(0, 4, "a"), (6, 8, "b")]),
])
def test_innermost_pieces(intervals, lo, hi, want):
    assert progtrace.innermost(intervals, lo, hi) == want


def test_the_window_must_match_the_runs_own_trace():
    ev = _loop_events()
    assert progtrace.window_matches(ev, 1000e-9)
    assert not progtrace.window_matches(ev, 1100e-9)
    assert not progtrace.window_matches({"window": None}, 1000e-9)


def _readers(monkeypatch, ev):
    monkeypatch.setattr(progtrace, "for_ctx", lambda ctx: ev)
    ctx = {"trace": {"window_s": 1e-6}}
    names = [m["name"] for m in bench.load_spec()["per_layer"]
             if (bench.HERE / "metrics" / f"{m['name']}.py").read_text()
             .count("progtrace")]
    return {n: bench.metric_reader(n)(ctx) for n in names}


def test_readers_on_hand_built_events(monkeypatch):
    ev = _loop_events()
    ev["spans"] += [
        ("lms.http.post", 100.0, 300.0, "python3#2", {"path": "/query/v2"}),
        ("lms.http.post", 300.0, 310.0, "python3#2", {"path": "/write"}),
        ("lms.query.exec", 120.0, 200.0, "python3#2", {"cache": "hit"}),
        ("lms.query.exec", 500.0, 600.0, "python3#3", {"cache": "miss"}),
        ("lms.query.exec", 2000.0, 2100.0, "python3#3", {"cache": "hit"})]
    got = _readers(monkeypatch, ev)
    assert got["loop_idle_monitor_ms_per_step"] == pytest.approx(50e-6)
    assert got["loop_idle_input_ms_per_step"] == pytest.approx(150e-6)
    assert got["loop_idle_sync_ms_per_step"] == pytest.approx(100e-6)
    assert got["idle_unattributed_frac.train"] == pytest.approx(50 / 400)
    assert got["step_attention_frac"] == pytest.approx(0.5)
    assert got["step_logits_loss_frac"] == 0.0
    assert got["step_optimizer_frac"] == 0.0
    assert got["step_unscoped_frac"] == 0.0
    assert got["query_server_ms_p95"] == pytest.approx(200e-6)
    # the query outside the window is not counted
    assert got["query_cache_hit_frac"] == pytest.approx(0.5)


def test_readers_on_a_program_without_spans_or_scopes(monkeypatch):
    """The parent's program has neither: every reader gives nothing."""
    ev = _ev([("while.1", 0.0, 900.0, None)])
    got = _readers(monkeypatch, ev)
    assert len(got) == 10 and set(got.values()) == {None}
    assert set(_readers(monkeypatch, None).values()) == {None}


def test_load_events_reads_a_cpu_trace(tmp_path):
    """Spans with their metadata and threads, and the window, from a trace
    written here; ``for_ctx`` takes it only at the window's own length,
    and refuses a trace with loop phases but no device op."""
    import jax
    from jax.profiler import TraceAnnotation as span
    trace = tmp_path / "cell" / "trace"
    jax.profiler.start_trace(str(trace))
    with jax.profiler.TraceAnnotation(WINDOW):
        with jax.profiler.StepTraceAnnotation("train", step_num=3):
            with span("train.loop.sync"):
                with span("lms.router.write", points=4) as sp:
                    sp.set_metadata(cache="miss")
        with span("instr:not.a.program.span"):
            pass
    jax.profiler.stop_trace()
    path = progtrace.newest_xplane(tmp_path)
    ev = progtrace.load_events(path)
    names = [s[0] for s in ev["spans"]]
    assert names == ["train", "train.loop.sync", "lms.router.write"]
    step, sync, write = ev["spans"]
    assert step[4]["step_num"] == 3
    assert write[4] == {"points": 4, "cache": "miss"}
    assert step[3] == sync[3] == write[3]
    assert step[1] <= sync[1] <= write[1] and write[2] <= sync[2] <= step[2]
    lo, hi = ev["window"]
    assert lo <= step[1] and step[2] <= hi
    window_s = (hi - lo) * 1e-9
    # taken at the window's own length, and then refused: the CPU runs
    # the loop's phases with no device op on an "XLA Ops" line
    with pytest.raises(ValueError, match="no device op"):
        progtrace.for_ctx({"trace": {"window_s": window_s}},
                          out_dir=tmp_path)
    assert progtrace.for_ctx({"trace": {"window_s": window_s * 2}},
                             out_dir=tmp_path) is None
    assert progtrace.for_ctx({}, out_dir=tmp_path) is None


@pytest.mark.parametrize("framework_name,scope", [
    # as a TPU v5e trace gives them
    ("jit(train_step)/transpose(jvp(logits_loss))/dot_general:",
     "logits_loss"),
    ("jit(train_step)/transpose(jvp(while))/body/closed_call/attention/"
     "dot_general", "attention"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/jvp(while)/body/dynamic_update_slice", None),
    (None, None),
])
def test_an_ops_scope_comes_from_its_framework_name(framework_name, scope):
    assert progtrace.scope_of(framework_name) == scope


def test_framework_names_come_from_the_trace_viewer_json(tmp_path):
    """The op's ``tf_op`` keyed by its ``long_name``, from the
    ``<host>.trace.json.gz`` beside ``<host>.xplane.pb``."""
    xplane = tmp_path / "host.xplane.pb"
    assert progtrace.framework_names(str(xplane)) == {}
    events = [
        {"ph": "X", "name": "fusion.3", "args": {
            "long_name": "%fusion.3 = bf16[8]{0} fusion(%p)",
            "tf_op": "jit(train_step)/attention/dot_general"}},
        {"ph": "X", "name": "copy.1", "args": {
            "long_name": "%copy.1 = f32[8]{0} copy(%p)"}},
        {"ph": "X", "name": "train.loop.sync", "args": {}}]
    with gzip.open(tmp_path / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    assert progtrace.framework_names(str(xplane)) == {
        "%fusion.3 = bf16[8]{0} fusion(%p)":
        "jit(train_step)/attention/dot_general"}


def test_readers_on_a_recorded_v5e_trace_excerpt(monkeypatch):
    """Two whole steps of a traced ``granite-3-8b.train.dash8`` run on one
    TPU v5e, as ``load_events`` read them (times from the first step's
    start): the phases tile each step, the stack's spans sit in the
    monitoring phase, and every reader finds what it reads."""
    ev = json.loads(EXCERPT.read_text())
    progtrace.check(ev)
    steps = progtrace.spans_in_window(ev, "train")
    assert len(steps) == 2
    for _, s, e, thread, meta in steps:
        assert "step_num" in meta
        phases = progtrace.innermost(
            [(ps, pe, n) for n, ps, pe, t, _ in ev["spans"]
             if n.startswith(progtrace.PHASE) and t == thread], s, e)
        assert sum(pe - ps for ps, pe, _ in phases) >= 0.95 * (e - s)
    loop = steps[0][3]
    monitor = [(s, e) for n, s, e, t, _ in ev["spans"]
               if n == "train.loop.monitor"]
    stack = [sp for sp in ev["spans"] if sp[0].startswith("lms.")
             and sp[3] == loop]
    assert {sp[0] for sp in stack} >= {"lms.agent.collect_step",
                                       "lms.router.write",
                                       "lms.usermetric.metric"}
    for _, s, e, *_ in stack:
        assert any(ms <= s and e <= me for ms, me in monitor)
    assert any(sp[0] == "marker.train_step" for sp in ev["spans"])

    got = _readers(monkeypatch, ev)
    assert None not in got.values()
    assert got["idle_unattributed_frac.train"] <= 0.1
    assert got["step_unscoped_frac"] <= 0.1
    shares = progtrace.busy_by_scope(ev)
    assert set(shares) == set(progtrace.SCOPES) | {None}
    assert sum(shares.values()) == pytest.approx(
        sum(e - s for s, e in progtrace._union(
            [(s, e) for _, s, e, _ in ev["ops"]])), rel=1e-9)
    assert 0.0 <= got["query_cache_hit_frac"] <= 1.0
